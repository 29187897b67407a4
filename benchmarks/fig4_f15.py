"""Paper Fig. 4 — runtime of 10,000 CEC2010-F15 evaluations (D=1000, m=50).

Published reference points (3.7 GHz Xeon E5, 2015 runtimes):
    Matlab 935 ms | Java 991 ms | JS/Node 1234 ms | JS/Chrome-worker 1238 ms
(the paper's headline: JS ~32% slower than Java).

We measure the same workload in five implementations:
    numpy        — plain vectorized numpy (the 'interpreted language' tier)
    numpy_loop   — per-individual loop (what the JS/Java reference code
                   actually did: one evaluation at a time)
    jax_jit      — jitted batched jnp (the production eval path)
    pallas       — the streaming F15 eval kernel (rotation stack streamed
                   per group; interpret mode on CPU, MXU-blocked on TPU)
    pallas_generation — the *whole EA hot loop* for the same 10k
                   evaluations: one fused generation+evaluation step of the
                   grid-tiled megakernel (pop=n_evals, D=1000 — a
                   (10000, 1000) f32 population, far beyond one VMEM tile,
                   so the ``pallas`` engine auto-routes to the tiled
                   streaming kernel). The paper's figure times evaluation
                   alone; this row shows what the paper *should* have
                   timed — selection, crossover, mutation and F15 fused in
                   one kernel, at the same evaluation count.

``--smoke`` shrinks the workload (D=128, 256 evals) for CI while forcing
the generation row through an explicit >=2x2x2 tile grid, so the tiled
code path is exercised end-to-end on every gate run.
"""
from __future__ import annotations

import argparse
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core.problems import f15_ref, make_f15_consts
from repro.kernels.rastrigin import ops as f15_ops

PAPER_MS = {"matlab": 935.0, "java": 991.0, "js_node": 1234.0,
            "js_chrome_worker": 1238.0}


def _np_consts(consts):
    return {k: np.asarray(v) for k, v in consts.items()}


def f15_numpy(consts, pop: np.ndarray) -> np.ndarray:
    o, perm, M = consts["o"], consts["perm"], consts["M"]
    G, m, _ = M.shape
    z = (pop - o)[:, perm].reshape(pop.shape[0], G, m)
    rot = np.einsum("ngm,gmk->ngk", z, M)
    r = rot * rot - 10.0 * np.cos(2 * np.pi * rot) + 10.0
    return r.sum(axis=(-1, -2))


def f15_numpy_loop(consts, pop: np.ndarray) -> np.ndarray:
    """One evaluation at a time — faithful to how the paper's JS/Java code
    consumed the benchmark (per-candidate objective calls)."""
    o, perm, M = consts["o"], consts["perm"], consts["M"]
    G, m, _ = M.shape
    out = np.empty(pop.shape[0])
    for i in range(pop.shape[0]):
        z = (pop[i] - o)[perm].reshape(G, m)
        total = 0.0
        for g in range(G):
            rot = z[g] @ M[g]
            total += float(np.sum(rot * rot - 10 * np.cos(2 * np.pi * rot)
                                  + 10.0))
        out[i] = total
    return out


def _generation_impl(n_evals: int, dim: int, group: int, smoke: bool):
    """The fused tiled generation+F15 row: one generation of a pop=n_evals
    island == n_evals fused fitness evaluations."""
    from repro.core import EAConfig
    from repro.core.problems import make_f15
    from repro.kernels import ga as gk

    problem = make_f15(dim=dim, group=group)
    cfg = EAConfig(max_pop=n_evals, min_pop=min(8, n_evals),
                   crossover="blend", mutation_sigma=0.3)
    pop = problem.init_population(jax.random.key(0), n_evals)
    fit = problem.evaluate(problem.consts, pop)
    rng = jax.random.key(1)
    if smoke:
        # force a >=2x2x2 grid regardless of the small smoke shape
        kern = gk.get_kernel("generation_eval", "float", "pallas_tiled")
        kwargs = {"tile_pop": max(8, n_evals // 2),
                  "tile_len": max(8, dim // 2)}
    else:
        # the real engine route: (n_evals, dim) f32 exceeds the VMEM
        # budget, so impl='pallas' dispatches to the tiled streaming kernel
        kern = gk.get_kernel("generation_eval", "float", "pallas")
        kwargs = {}
    step = jax.jit(lambda k: kern(k, pop, fit, jnp.int32(n_evals), cfg,
                                  problem.genome, problem.fused,
                                  consts=problem.consts, **kwargs))
    return lambda: step(rng)[1].block_until_ready()


def bench(n_evals: int = 10_000, dim: int = 1000, group: int = 50,
          repeats: int = 3, include_loop: bool = True,
          include_pallas: bool = True, include_generation: bool = True,
          smoke: bool = False) -> List[Dict]:
    if smoke:
        n_evals, dim, group, repeats = 256, 128, 16, 1
        include_loop = False
    consts = make_f15_consts(jax.random.key(2010), dim, group)
    np_consts = _np_consts(consts)
    pop = np.random.default_rng(0).uniform(
        -5, 5, (n_evals, dim)).astype(np.float32)
    jpop = jnp.asarray(pop)

    impls = {}
    impls["numpy"] = lambda: f15_numpy(np_consts, pop)
    if include_loop:
        impls["numpy_loop"] = lambda: f15_numpy_loop(np_consts, pop)
    jit_ref = jax.jit(f15_ref)
    impls["jax_jit"] = lambda: jit_ref(consts, jpop).block_until_ready()
    if include_pallas:
        impls["pallas"] = lambda: f15_ops.f15(
            consts, jpop).block_until_ready()
    if include_generation:
        impls["pallas_generation"] = _generation_impl(n_evals, dim, group,
                                                      smoke)

    rows = []
    for name, fn in impls.items():
        fn()  # warmup / compile
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            fn()
            times.append((time.perf_counter() - t0) * 1e3)
        rows.append({"impl": name, "ms": float(np.median(times)),
                     "n_evals": n_evals, "dim": dim})
    return rows


def summarize(rows: List[Dict]) -> List[str]:
    out = ["impl,ms_per_10k_evals,vs_paper_java"]
    for r in rows:
        out.append(f"{r['impl']},{r['ms']:.1f},"
                   f"{r['ms']/PAPER_MS['java']:.2f}x")
    for k, v in PAPER_MS.items():
        out.append(f"paper_{k},{v:.1f},{v/PAPER_MS['java']:.2f}x")
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-evals", type=int, default=10_000)
    ap.add_argument("--no-loop", action="store_true")
    ap.add_argument("--no-pallas", action="store_true")
    ap.add_argument("--no-generation", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="CI trim: D=128, 256 evals, tiled generation "
                         "forced through a >=2x2x2 grid")
    args = ap.parse_args(argv)
    use_compile_cache()
    rows = bench(args.n_evals, include_loop=not args.no_loop,
                 include_pallas=not args.no_pallas,
                 include_generation=not args.no_generation,
                 smoke=args.smoke)
    print("\n".join(summarize(rows)))


if __name__ == "__main__":
    main()

#!/usr/bin/env python3
"""Smoke test of the island engine on a TPU, through its normal entry points.

    python3 chip_smoke.py               # one chip: phases a-d
    python3 chip_smoke.py --four-chips  # the sharded async path on 4 chips

Phases on one chip:

(a) refuse to run unless JAX's first device is a TPU; print its kind, the
    device count and the compile-cache directory.
(b) Fig-3 deployment through ``run_fused``: trap-40x4 (L=160 binary),
    max_pop=1024, 8 islands, 3 epochs of 200 generations, ``impl="pallas"``.
    Islands and pool must be bit-identical to the same seeded run with
    ``impl="pallas_ref"``, and the program must hold a Mosaic kernel
    (``tpu_custom_call``).
(c) Fig-4 deployment: one ``generation_eval`` of CEC2010-F15 at D=1000,
    m=50, pop 10,000 through ``impl="pallas"`` (which routes to the tiled
    kernel). The fused fitness must agree with a float64 numpy F15 of the
    returned population. Then one generation with crossover and mutation
    off: every child row must equal some parent row bit for bit, which
    shows the f32 one-hot gather is exact on the chip.
(d) ``python -m repro.server --port 0`` as a child process, and
    ``run_experiment_async`` with ``AsyncHostBridge(server=URL)`` and
    ``impl="pallas"`` for a few ticks. The bridge must lose nothing, the
    server's ledger must balance with the bridge's, and the child must
    never have loaded the TPU runtime.

``--four-chips`` runs only the sharded path users take with ``--sharded``:
``run_fused_sharded_async`` in its degenerate configuration must be
bit-identical to ``run_fused_sharded`` on a 4-device mesh with
``impl="pallas"``, with the islands spread over 4 distinct devices.

Every line but the last is a log line. The last line is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``,
printed only when every phase passed; otherwise the script exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# The deployments' sizes (the paper's Fig-3 and Fig-4 cells).
FIG3 = {"max_pop": 1024, "generations": 200, "islands": 8, "epochs": 3}
FIG4 = {"n": 10_000, "dim": 1000, "group": 50}
SERVED = {"max_pop": 256, "islands": 8, "ticks": 6}
# how a compiled Mosaic kernel shows in a lowered program
KERNEL_MARK = "tpu_custom_call"


class SmokeFailure(Exception):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)
    log(f"  ok: {what}")


def trees_equal(a, b) -> bool:
    import jax
    import numpy as np

    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    if len(la) != len(lb):
        return False
    for x, y in zip(la, lb):
        if hasattr(x, "dtype") and jax.dtypes.issubdtype(
                x.dtype, jax.dtypes.prng_key):
            x, y = jax.random.key_data(x), jax.random.key_data(y)
        x, y = np.asarray(x), np.asarray(y)
        if x.dtype != y.dtype or not np.array_equal(x, y):
            return False
    return True


def maps_libtpu(pid) -> bool:
    """Whether process ``pid`` has the TPU runtime mapped: a process that
    started a TPU backend has loaded libtpu."""
    with open(f"/proc/{pid}/maps") as f:
        return "libtpu" in f.read()


# ---------------------------------------------------------------------------
# (a) the device
# ---------------------------------------------------------------------------
def phase_device(want_count: int):
    import jax

    from repro.compile_cache import use_compile_cache
    from repro.kernels import interpret_mode

    cache_dir = use_compile_cache()
    devs = jax.devices()
    d0 = devs[0]
    log(f"(a) platform={d0.platform} kind={d0.device_kind} "
        f"count={len(devs)} compile_cache={cache_dir}")
    if d0.platform != "tpu":
        raise SmokeFailure(f"no TPU: JAX's first device is {d0.platform!r}")
    check(len(devs) >= want_count, f"at least {want_count} TPU device(s)")
    check(interpret_mode() is False, "Pallas kernels compile (no interpret "
                                     "mode)")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def _fig3(impl: str):
    from repro.core import EAConfig, MigrationConfig, make_trap

    problem = make_trap(n_traps=40, l=4)
    cfg = EAConfig(max_pop=FIG3["max_pop"], min_pop=FIG3["max_pop"],
                   generations_per_epoch=FIG3["generations"], impl=impl)
    return problem, cfg, MigrationConfig(topology="pool")


# ---------------------------------------------------------------------------
# (b) Fig-3 through run_fused
# ---------------------------------------------------------------------------
def phase_fig3():
    import jax
    import numpy as np

    from repro.core import evolution, island as island_lib, pool as pool_lib
    from repro.core import run_fused

    n_islands, epochs = FIG3["islands"], FIG3["epochs"]
    log(f"(b) Fig-3: trap-40x4 L=160, max_pop={FIG3['max_pop']}, "
        f"{n_islands} islands, {epochs} epochs x {FIG3['generations']} "
        f"generations")
    out = {}
    for impl in ("pallas", "pallas_ref"):
        problem, cfg, mig = _fig3(impl)
        t0 = time.perf_counter()
        isl, pool, ep = run_fused(problem, cfg, mig, n_islands=n_islands,
                                  max_epochs=epochs, rng=jax.random.key(0))
        jax.block_until_ready((isl, pool))
        log(f"  {impl}: epochs={int(ep)} "
            f"best={float(np.asarray(isl.best_fitness).max())} "
            f"evals={int(np.asarray(isl.evaluations).sum())} "
            f"wall_incl_compile_s={time.perf_counter() - t0:.1f}")
        out[impl] = (isl, pool)
        if impl == "pallas":
            # the program run_fused compiled, lowered again from shapes
            k_init, k_loop = jax.random.split(jax.random.key(0))
            shapes = jax.eval_shape(
                lambda k: (island_lib.init_islands(k, n_islands, problem,
                                                   cfg),
                           pool_lib.pool_init(mig.pool_capacity,
                                              problem.genome)), k_init)
            from functools import partial
            text = jax.jit(partial(
                evolution.fused_scan, problem=problem, cfg=cfg, mig=mig,
                w2=False, max_epochs=epochs, with_stats=False)).lower(
                    *shapes, k_loop, 0, False, ()).as_text()
            check(KERNEL_MARK in text,
                  "run_fused program holds the Mosaic generation kernel")
    check(trees_equal(out["pallas"], out["pallas_ref"]),
          "islands and pool bit-identical: pallas == pallas_ref")
    best = float(np.asarray(out["pallas"][0].best_fitness).max())
    check(np.isfinite(best) and best > 0, f"best fitness finite ({best})")


# ---------------------------------------------------------------------------
# (c) Fig-4: F15 at D=1000, pop 10,000, and the float gather
# ---------------------------------------------------------------------------
def phase_fig4():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.fig4_f15 import f15_numpy
    from repro.core import EAConfig
    from repro.core.problems import make_f15
    from repro.kernels import ga as gk
    from repro.kernels.ga import ops as ga_ops

    n, dim, group = FIG4["n"], FIG4["dim"], FIG4["group"]
    log(f"(c) Fig-4: F15 D={dim} m={group}, pop {n}, impl=pallas")
    problem = make_f15(dim=dim, group=group)
    cfg = EAConfig(max_pop=n, min_pop=n, crossover="blend",
                   mutation_sigma=0.3)
    spec = ga_ops.make_spec(cfg, problem.genome, fused=problem.fused)
    check(ga_ops.untiled_vmem_bytes(n, dim, spec) > ga_ops.VMEM_BUDGET_BYTES,
          f"pop {n} x {dim} routes to the tiled kernel")
    pop = problem.init_population(jax.random.key(0), n)
    fit = problem.evaluate(problem.consts, pop)
    kern = gk.get_kernel("generation_eval", "float", "pallas")

    def step(k, pop, fit, cfg):
        return kern(k, pop, fit, jnp.int32(n), cfg, problem.genome,
                    problem.fused, consts=problem.consts)

    t0 = time.perf_counter()
    gen = jax.jit(lambda k, p, f: step(k, p, f, cfg))
    new_pop, new_fit = jax.block_until_ready(gen(jax.random.key(1), pop,
                                                 fit))
    log(f"  generation_eval (incl. autotune + compile) "
        f"{time.perf_counter() - t0:.1f}s")
    check(KERNEL_MARK in gen.lower(jax.random.key(1), pop,
                                         fit).as_text(),
          "generation_eval holds Mosaic kernels")
    new_pop = np.asarray(new_pop)
    new_fit = np.asarray(new_fit)
    check(new_pop.shape == (n, dim) and new_fit.shape == (n,)
          and np.isfinite(new_fit).all(), f"finite ({n}, {dim}) output")
    consts64 = {k: np.asarray(v, np.float64 if k != "perm" else np.int64)
                for k, v in problem.consts.items()}
    want = f15_numpy(consts64, new_pop.astype(np.float64))
    rel = np.abs(-new_fit - want) / np.maximum(np.abs(want), 1.0)
    log(f"  fused F15 vs float64 numpy: max rel err {rel.max():.3e}")
    check(rel.max() < 1e-4, "fused F15 fitness agrees with numpy (rel 1e-4)")

    # crossover and mutation off: children are parents, gathered by the
    # f32 one-hot matmul; exact only if the chip keeps every f32 bit
    cfg0 = EAConfig(max_pop=n, min_pop=n, crossover="blend",
                    crossover_rate=0.0, mutation_rate=0.0)
    gen0 = jax.jit(lambda k, p, f: step(k, p, f, cfg0))
    kids = np.asarray(gen0(jax.random.key(2), pop, fit)[0])
    parents = np.asarray(pop)
    rows = {r.tobytes() for r in parents}
    missing = sum(r.tobytes() not in rows for r in kids)
    log(f"  one-hot f32 gather: {n - missing}/{n} child rows equal a parent "
        f"row bit for bit")
    check(missing == 0, "f32 one-hot gather is exact on the chip")


# ---------------------------------------------------------------------------
# (d) served path: pool server child + AsyncHostBridge
# ---------------------------------------------------------------------------
def _start_server():
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src") + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.server", "--port", "0"],
        cwd=str(ROOT), env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        line = proc.stdout.readline()
        if not line:
            break
        m = re.search(r"listening on (http://\S+)", line)
        if m:
            return proc, m.group(1)
    _stop(proc)
    raise SmokeFailure("pool server child did not start")


def _stop(proc) -> None:
    if proc.poll() is None:
        proc.terminate()
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=30)


def phase_served():
    import jax
    import numpy as np

    from repro.core import (AsyncConfig, AsyncHostBridge, EAConfig,
                            MigrationConfig, make_trap, run_experiment_async)
    from repro.server import RemotePoolServer

    ticks, n_islands = SERVED["ticks"], SERVED["islands"]
    log(f"(d) served path: python -m repro.server + AsyncHostBridge, "
        f"{n_islands} islands, {ticks} ticks, impl=pallas")
    proc, url = _start_server()
    bridge = feeder = None
    try:
        log(f"  server child pid={proc.pid} url={url}")
        feeder = RemotePoolServer(url)
        feeder.put(np.ones(160, np.int8), 1.0, uuid=1)  # one volunteer
        bridge = AsyncHostBridge(url, pull=4)
        problem = make_trap(n_traps=40, l=4)
        cfg = EAConfig(max_pop=SERVED["max_pop"],
                       min_pop=SERVED["max_pop"] // 2,
                       generations_per_epoch=20, impl="pallas")
        res = run_experiment_async(
            problem, cfg, MigrationConfig(topology="pool"),
            AsyncConfig(min_rate=0.5, max_rate=1.0, seed=1),
            n_islands=n_islands, max_ticks=ticks, rng=jax.random.key(0),
            host_bridge=bridge)
        pool = bridge.flush(res.pool)
        st = bridge.stats()
        server = feeder.stats()
        log(f"  bridge={st} server_puts={server['puts']} "
            f"server_size={server['size']} ticks={res.epochs} "
            f"fires={res.total_fires}")
        check(st["lost"] == 0 and st["dropped"] == 0,
              "bridge lost and dropped nothing")
        check(0 < st["pushed"] <= res.epochs,
              "at most one acknowledged put per tick")
        check(server["puts"] == st["pushed"] + 1 and server["rejected"] == 0,
              "server counted every put: bridge pushes + the volunteer's")
        check(st["pulled"] == 1,
              "bridge pulled the volunteer's entry exactly once")
        entries, _, _ = feeder.get_since(-1, limit=4096)
        check(len(entries) == server["size"] == server["puts"],
              "every acknowledged put reads back from the server")
        best = float(np.asarray(pool.fitness).max())
        check(np.isfinite(best), f"device pool best finite ({best})")
        check(maps_libtpu("self"), "this process holds the TPU runtime")
        check(proc.poll() is None and not maps_libtpu(proc.pid),
              "server child never loaded the TPU runtime")
    finally:
        if bridge is not None:
            bridge.close()
        if feeder is not None:
            feeder.close()
        _stop(proc)


# ---------------------------------------------------------------------------
# four chips: the sharded async driver vs the sharded sync driver
# ---------------------------------------------------------------------------
def phase_four_chips():
    import jax
    import numpy as np

    from repro.core import AsyncConfig
    from repro.core.sharded import run_fused_sharded, run_fused_sharded_async
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(4)
    problem, cfg, mig = _fig3("pallas")
    per, epochs = 2, 2
    log(f"(4) sharded Fig-3 on mesh {dict(mesh.shape)}: {per} islands per "
        f"chip, {epochs} epochs x {FIG3['generations']} generations, "
        f"impl=pallas")
    t0 = time.perf_counter()
    sync = run_fused_sharded(mesh, problem, cfg, mig, islands_per_shard=per,
                             max_epochs=epochs, rng=jax.random.key(0))
    jax.block_until_ready(sync)
    log(f"  run_fused_sharded: {time.perf_counter() - t0:.1f}s incl. compile")
    t0 = time.perf_counter()
    asyn = run_fused_sharded_async(mesh, problem, cfg, mig, AsyncConfig(),
                                   islands_per_shard=per, max_ticks=epochs,
                                   rng=jax.random.key(0))
    jax.block_until_ready(asyn)
    log(f"  run_fused_sharded_async: {time.perf_counter() - t0:.1f}s incl. "
        f"compile")
    devices = {s.device for s in sync[0].pop.addressable_shards}
    check(len(devices) == 4, f"island leaves live on 4 distinct devices "
                             f"({sorted(d.id for d in devices)})")
    check(trees_equal(sync[:2], asyn[:2]),
          "degenerate async == sync, bit for bit (islands and pool)")
    best = float(np.asarray(sync[0].best_fitness).max())
    check(np.isfinite(best), f"best fitness finite ({best})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded async path, on 4 chips")
    args = ap.parse_args(argv)
    src = ROOT / "src"
    if not (src / "repro").is_dir() or not (ROOT / "benchmarks").is_dir():
        print(f"chip_smoke: no repro checkout beside {__file__}",
              file=sys.stderr)
        return 2
    sys.path[:0] = [str(src), str(ROOT)]
    try:
        device = phase_device(4 if args.four_chips else 1)
        if args.four_chips:
            phase_four_chips()
        else:
            phase_fig3()
            phase_fig4()
            phase_served()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

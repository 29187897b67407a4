"""Paper Fig. 3 — baseline trap-40 experiment: time/evaluations to solution
vs population size (512 vs 1024), 50 runs, 5M-eval budget.

Paper reference numbers (NodEO/JS on an i7-4770): pop 512 -> 66% success,
~69 s mean; pop 1024 -> 100% success, 3.46 s mean. We reproduce the
*design* exactly (single island, same trap constants, same budget) and
report our times alongside; success-rate ordering and the pop-size effect
direction are the reproduction targets (absolute seconds are hardware-
and-runtime specific).

Default run count is trimmed for CI (--runs 50 reproduces the paper).
"""
from __future__ import annotations

import argparse
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core import EAConfig, make_trap
from repro.core import island as island_lib


def run_single_island(pop_size: int, seed: int, impl: str = "jnp",
                      max_evals: int = 5_000_000) -> Dict:
    """One paper-style run: a single island, no pool, run to solution or
    budget. Returns evals/time/success."""
    problem = make_trap(n_traps=40, l=4, a=1.0, b=2.0, z=3.0, impl=impl)
    cfg = EAConfig(max_pop=pop_size, min_pop=pop_size,
                   generations_per_epoch=200, max_evaluations=max_evals,
                   mutation_rate=1.0 / 160, crossover="two_point",
                   elite=2)
    state = island_lib.init_island(jax.random.key(seed), problem, cfg,
                                   pop_size=pop_size)
    epoch = jax.jit(lambda s: island_lib.island_epoch(s, problem, cfg))
    t0 = time.perf_counter()
    while True:
        state = epoch(state)
        done = bool(state.done)
        if done:
            break
    state.best_fitness.block_until_ready()
    dt = time.perf_counter() - t0
    success = float(state.best_fitness) >= problem.optimum - 1e-9
    return {"pop": pop_size, "seed": seed, "success": success,
            "evaluations": int(state.evaluations), "seconds": dt,
            "best": float(state.best_fitness)}


def run(runs: int = 10, pops=(512, 1024), impl: str = "jnp",
        max_evals: int = 5_000_000, verbose: bool = False) -> List[Dict]:
    rows = []
    for pop in pops:
        for seed in range(runs):
            r = run_single_island(pop, seed, impl, max_evals)
            rows.append(r)
            if verbose:
                print(f"  pop {pop} seed {seed}: success={r['success']} "
                      f"evals={r['evaluations']} t={r['seconds']:.2f}s")
    return rows


def summarize(rows: List[Dict]) -> List[str]:
    out = ["pop,runs,success_rate,mean_seconds_success,mean_evals_success"]
    for pop in sorted({r["pop"] for r in rows}):
        sub = [r for r in rows if r["pop"] == pop]
        succ = [r for r in sub if r["success"]]
        rate = len(succ) / len(sub)
        ms = np.mean([r["seconds"] for r in succ]) if succ else float("nan")
        me = np.mean([r["evaluations"] for r in succ]) if succ else float("nan")
        out.append(f"{pop},{len(sub)},{rate:.2f},{ms:.3f},{me:.0f}")
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--runs", type=int, default=10)
    ap.add_argument("--impl", choices=["jnp", "pallas"], default="jnp")
    ap.add_argument("--max-evals", type=int, default=5_000_000)
    args = ap.parse_args(argv)
    use_compile_cache()
    rows = run(args.runs, impl=args.impl, max_evals=args.max_evals,
               verbose=True)
    print("\n".join(summarize(rows)))
    print("paper reference: pop 512 -> 66% success ~69s; "
          "pop 1024 -> 100% success ~3.46s (JS/NodEO, i7-4770)")


if __name__ == "__main__":
    main()

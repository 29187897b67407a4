"""Paper-methodology speed baselines: evaluations/sec + time-to-solution.

The NodIO paper's central contribution is a *series of speed measurements*
("there is no fast lunch", arXiv:1511.01088, ran the same EA across
languages; arXiv:1802.03707 native vs browser). This harness is the
jax/pallas analogue of those tables: for every (problem x genome length x
generation-engine impl) scenario it runs repeated seeded experiments
through the fused ``lax.scan`` driver and records

* ``evals_per_sec`` — fitness evaluations per wall-clock second, the
  paper's universal cross-language throughput metric (mean/std over runs,
  steady-state: one untimed warm-up run absorbs compilation);
* ``time_to_solution_s`` / ``evals_to_solution`` — wall seconds and
  evaluation count of the runs that hit the optimum (the paper's Fig-3
  metric), with the success rate alongside;

and writes them to ``BENCH_speed.json`` together with the host/backend
block (:mod:`benchmarks.hostmeta`) — the repo's machine-readable speed
trajectory. ``impl`` rows compare the classic jnp generation path against
the fused Pallas megakernel and the grid-tiled streaming engine
(interpret-mode off-TPU, so on CPU the pallas rows measure the emulation,
not the hardware — the JSON's ``host.env.pallas_interpret`` field says
which reading applies). The payload also carries the generation-engine
roofline section (:func:`benchmarks.roofline.generation_roofline`): one
generation step per impl placed against the HBM-bandwidth ceiling, which
is how the tiled kernel's throughput is judged against the memory wall
rather than against another interpreter.

CLI:  PYTHONPATH=src python -m benchmarks.speed_baseline [--full]
(or through ``python -m benchmarks.run``, which owns the JSON when run as
the suite).
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core import EAConfig, MigrationConfig, make_problem, run_fused

# One scenario = one paper-table row family: a problem at a genome length,
# with EA settings sized so the run fits the harness budget.
SMOKE_SCENARIOS = (
    {"problem": "trap", "kwargs": {"n_traps": 8, "l": 4},
     "cfg": {"max_pop": 64, "min_pop": 64, "generations_per_epoch": 5}},
    {"problem": "rastrigin", "kwargs": {"dim": 16},
     "cfg": {"max_pop": 64, "min_pop": 64, "generations_per_epoch": 5,
             "crossover": "blend", "mutation_sigma": 0.5}},
)

FULL_SCENARIOS = (
    {"problem": "trap", "kwargs": {"n_traps": 40, "l": 4},   # the paper's
     "cfg": {"max_pop": 256, "min_pop": 256,
             "generations_per_epoch": 100}},
    {"problem": "trap", "kwargs": {"n_traps": 80, "l": 4},   # 2x genome
     "cfg": {"max_pop": 256, "min_pop": 256,
             "generations_per_epoch": 100}},
    {"problem": "royal_road", "kwargs": {"n_blocks": 16, "r": 8},
     "cfg": {"max_pop": 256, "min_pop": 256,
             "generations_per_epoch": 100}},
    {"problem": "rastrigin", "kwargs": {"dim": 20},
     "cfg": {"max_pop": 256, "min_pop": 256, "generations_per_epoch": 100,
             "crossover": "blend", "mutation_sigma": 0.5}},
    {"problem": "rastrigin", "kwargs": {"dim": 100},
     "cfg": {"max_pop": 256, "min_pop": 256, "generations_per_epoch": 100,
             "crossover": "blend", "mutation_sigma": 0.5}},
)


def bench_scenario(scenario: Dict[str, Any], impl: str, *, runs: int,
                   islands: int, epochs: int,
                   verbose: bool = False) -> Dict[str, Any]:
    """Repeated seeded runs of one (scenario, impl) cell -> one JSON row."""
    problem = make_problem(scenario["problem"], **scenario.get("kwargs", {}))
    cfg = EAConfig(impl=impl, **scenario.get("cfg", {}))
    mig = MigrationConfig(topology="ring")  # collective-cheap, pool-free

    def one(seed: int) -> Dict[str, float]:
        t0 = time.perf_counter()
        isl, _, ep = run_fused(problem, cfg, mig, n_islands=islands,
                               max_epochs=epochs, rng=jax.random.key(seed))
        isl.best_fitness.block_until_ready()
        dt = time.perf_counter() - t0
        evals = int(np.asarray(isl.evaluations).sum())
        best = float(np.asarray(isl.best_fitness).max())
        success = (problem.optimum is not None
                   and best >= problem.optimum - cfg.success_eps)
        return {"seconds": dt, "evals": evals, "best": best,
                "success": success, "epochs": int(ep)}

    one(10_000)  # warm-up: compile + first-touch, excluded from timing
    rows = [one(seed) for seed in range(runs)]
    eps = [r["evals"] / r["seconds"] for r in rows]
    solved = [r for r in rows if r["success"]]
    mean_eps = float(np.mean(eps))
    out = {
        "problem": problem.name,
        "genome_kind": problem.genome.kind,
        "genome_length": problem.genome.length,
        "impl": impl,
        "runs": runs,
        "islands": islands,
        "max_epochs": epochs,
        "max_pop": cfg.max_pop,
        "generations_per_epoch": cfg.generations_per_epoch,
        "evals_per_sec": mean_eps,
        "evals_per_sec_std": float(np.std(eps)),
        # the regression gate compares medians: on a noisy 1-core CI box
        # one stolen timeslice skews a mean but not a 3-repeat median
        "evals_per_sec_median": float(np.median(eps)),
        "evals_per_sec_cv": (float(np.std(eps) / mean_eps)
                             if mean_eps else 0.0),
        "wall_s_mean": float(np.mean([r["seconds"] for r in rows])),
        "evaluations_mean": float(np.mean([r["evals"] for r in rows])),
        "success_rate": len(solved) / len(rows),
        "time_to_solution_s": (float(np.mean([r["seconds"] for r in solved]))
                               if solved else None),
        "evals_to_solution": (float(np.mean([r["evals"] for r in solved]))
                              if solved else None),
        "best_fitness_mean": float(np.mean([r["best"] for r in rows])),
    }
    if verbose:
        print(f"  {out['problem']:>14s} L={out['genome_length']:<5d} "
              f"{impl:>10s}: {out['evals_per_sec']:.0f} evals/s "
              f"success={out['success_rate']:.2f}")
    return out


DEFAULT_IMPLS = ("jnp", "pallas", "pallas_tiled")


def run(full: bool = False, impls: Sequence[str] = DEFAULT_IMPLS,
        runs: Optional[int] = None, islands: Optional[int] = None,
        epochs: Optional[int] = None,
        verbose: bool = False) -> List[Dict[str, Any]]:
    """The whole sweep: scenarios x impls. ``full`` selects the
    paper-scale table; the default is the CI smoke (2 scenarios)."""
    scenarios = FULL_SCENARIOS if full else SMOKE_SCENARIOS
    # 3 smoke repeats (was 1): the CI gate medians over them so host
    # noise on the shared runner stops flapping the 30% threshold
    runs = runs if runs is not None else (5 if full else 3)
    islands = islands if islands is not None else (8 if full else 4)
    epochs = epochs if epochs is not None else (20 if full else 3)
    return [bench_scenario(s, impl, runs=runs, islands=islands,
                           epochs=epochs, verbose=verbose)
            for s in scenarios for impl in impls]


def summarize(rows: List[Dict[str, Any]]) -> List[str]:
    out = ["problem,genome_length,impl,evals_per_sec,success_rate,"
           "time_to_solution_s"]
    for r in rows:
        tts = ("" if r["time_to_solution_s"] is None
               else f"{r['time_to_solution_s']:.3f}")
        out.append(f"{r['problem']},{r['genome_length']},{r['impl']},"
                   f"{r['evals_per_sec']:.0f},{r['success_rate']:.2f},{tts}")
    return out


def payload(rows: List[Dict[str, Any]],
            roofline: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """The BENCH_speed.json body (host block added by hostmeta.stamp).

    ``roofline`` is the generation-engine roofline section; when omitted
    it is computed here (small smoke shape) so every BENCH_speed.json
    carries roofline-placed generation rows."""
    if roofline is None:
        from benchmarks.roofline import generation_roofline
        roofline = generation_roofline(repeats=2)
    return {
        "benchmark": "speed_baseline",
        "driver": "run_fused[lax.scan]",
        "metric": "fitness evaluations per wall-clock second (steady "
                  "state; one untimed warm-up run absorbs compilation) + "
                  "time/evals to solution over seeded repeats",
        "impl_axis": "EAConfig.impl generation engine: 'jnp' = classic "
                     "four-op jax.random path, 'pallas' = fused "
                     "selection->crossover->mutation->fitness VMEM "
                     "megakernel (auto-routes to the tiled engine beyond "
                     "a VMEM estimate), 'pallas_tiled' = grid-tiled "
                     "streaming megakernel forced (interpret-mode "
                     "emulation off-TPU — see host.env.pallas_interpret)",
        "rows": rows,
        "generation_roofline": roofline,
    }


def main(argv=None):
    from benchmarks import hostmeta

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--full", action="store_true",
                    help="paper-scale scenario table (5 problems x impls "
                         "x 5 seeded runs)")
    ap.add_argument("--impls", nargs="+", default=list(DEFAULT_IMPLS),
                    help="generation-engine impls to compare")
    ap.add_argument("--runs", type=int, default=None)
    ap.add_argument("--islands", type=int, default=None)
    ap.add_argument("--epochs", type=int, default=None)
    ap.add_argument("--json", default="BENCH_speed.json")
    args = ap.parse_args(argv)
    use_compile_cache()
    rows = run(full=args.full, impls=args.impls, runs=args.runs,
               islands=args.islands, epochs=args.epochs, verbose=True)
    print("\n".join(summarize(rows)))
    with open(args.json, "w") as fh:
        json.dump(hostmeta.stamp(payload(rows)), fh, indent=2)
    print(f"wrote {args.json}")


if __name__ == "__main__":
    main()

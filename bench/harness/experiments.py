"""The ``experiments`` traffic entry: whole experiments back to back
through one of the program's drivers, each from a key folded from the
seed.

The mix names the driver, ``drivers/<driver>.py`` beside this package
(:func:`.spec.driver`), which is handed the problem, the EA and migration
configurations, the epochs and the cell's chips. An experiment is one
call of it from fresh islands to the first solution or to the evaluation
budget, timed from the call to its result on the host. The window starts
experiments until ``seconds`` have passed and ends when the last one
completes, so no experiment is cut and the rate covers all the time of
the window.

After the window a sample of the experiments, drawn from the seed, and
the slowest one are handed to :mod:`.check` with their final state.
"""
from __future__ import annotations

import math
import time
from contextlib import nullcontext
from pathlib import Path
from typing import Any, Dict, List

import numpy as np

from . import spec

# the bench directory this file sits in: its drivers and problems
BENCH_DIR = Path(__file__).resolve().parents[1]

# keys of the warm-up experiments: outside any window's range of indices
WARMUP_INDEX = 2**31 - 1


def max_epochs(cfg: Dict[str, Any]) -> int:
    """Epochs until every island has spent the budget: the scan length."""
    per_epoch = cfg["population"] * cfg["ea"]["generations_per_epoch"]
    return max(1, math.ceil((cfg["budget_evals"] - cfg["population"])
                            / per_epoch))


def build_ea(cfg: Dict[str, Any], impl: str):
    from repro.core import EAConfig, MigrationConfig

    ea = dict(cfg["ea"])
    kw = {k: ea[k] for k in ("selection", "tournament_k", "crossover",
                             "crossover_rate", "mutation_rate", "elite",
                             "generations_per_epoch", "mutation_sigma")
          if k in ea}
    n = cfg["population"]
    return (EAConfig(max_pop=n, min_pop=n, max_evaluations=cfg["budget_evals"],
                     impl=impl, **kw),
            MigrationConfig(topology=cfg["migration"]["topology"],
                            pool_capacity=cfg["migration"]["pool_capacity"]))


def kernel_shape(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """What the work counts need: rows, genes and islands per kernel call
    (the generation kernel is vmapped over a chip's islands)."""
    g = cfg["genome"]
    return {"rows": cfg["population"], "length": g["length"],
            "gene_bytes": 1 if g["kind"] == "binary" else 4,
            "islands_per_call": cfg["islands"],
            "group": cfg["problem"].get("group", 0)}


def make(cfg: Dict[str, Any], mix: Dict[str, Any], seed: int, devices):
    return Experiments(cfg, mix, seed, devices)


class Experiments:
    """Set-up, window and answers of one ``experiments`` cell run."""

    def __init__(self, cfg: Dict[str, Any], mix: Dict[str, Any], seed: int,
                 devices):
        import jax

        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.problem, self.consts = spec.problem(
            cfg["problem"]["kind"], BENCH_DIR).build(cfg["problem"])
        self.ea, self.mig = build_ea(cfg, mix["impl"])
        self.epochs = max_epochs(cfg)
        self.base = jax.random.key(seed)
        self._drive = spec.driver(mix["driver"], BENCH_DIR).make(
            self.problem, self.ea, self.mig, cfg, self.epochs, devices)
        self.records: List[Dict[str, Any]] = []
        self._kept: Dict[int, Any] = {}
        self._checked = 0

    def _one(self, i: int, annotate):
        import jax

        key = jax.random.fold_in(self.base, i)
        with annotate("experiment"):
            t0 = time.perf_counter()
            isl, pool, ep = self._drive(key)
            best, evals, epochs = jax.device_get(
                (isl.best_fitness, isl.evaluations, ep))
            t1 = time.perf_counter()
        optimum = self.problem.optimum
        solved = (optimum is not None
                  and float(np.max(best)) >= optimum - self.ea.success_eps)
        rec = {"i": i, "seconds": t1 - t0, "solved": bool(solved),
               "evals": int(np.sum(evals, dtype=np.int64)),
               "epochs": int(epochs)}
        return rec, (isl, pool)

    def warm_up(self) -> None:
        """Compile and run every shape the window uses: one experiment."""
        self._one(WARMUP_INDEX, lambda name: nullcontext())

    def window(self, seconds: float, annotate, traced: bool = False) -> float:
        pick = np.random.default_rng([self.seed, 3])
        share = self.mix.get("check_share", 0.1)
        cap = self.mix.get("check_max", 10**9)
        slowest = None
        t_w0 = time.perf_counter()
        with annotate("window"):
            i = 0
            while True:
                rec, state = self._one(i, annotate)
                self.records.append(rec)
                if pick.random() < share and len(self._kept) < cap:
                    self._kept[i] = state
                if slowest is None or rec["seconds"] > slowest[0]:
                    slowest = (rec["seconds"], i, state)
                i += 1
                if time.perf_counter() - t_w0 >= seconds:
                    break
        t_w1 = time.perf_counter()
        self._kept.setdefault(slowest[1], slowest[2])
        self._checked = len(self._kept)
        return t_w1 - t_w0

    def metrics(self, window_s: float) -> Dict[str, float]:
        secs = [r["seconds"] for r in self.records]
        return {"evals_per_s": sum(r["evals"] for r in self.records)
                / window_s,
                "solve_s_p95": float(np.percentile(secs, 95))}

    def counts(self) -> Dict[str, int]:
        # an experiment run to its first solution fails when it spends the
        # budget unsolved; one run to the budget has no other outcome
        failed = 0
        if self.mix["until"] == "solution":
            failed = sum(not r["solved"] for r in self.records)
        return {"attempted": len(self.records), "failed": failed}

    def answers(self) -> List[Dict[str, np.ndarray]]:
        """The kept experiments' final state on the host; frees it on the
        device."""
        out = [state_to_host(*st) for _, st in sorted(self._kept.items())]
        self._kept.clear()
        return out

    def verify(self, control: bool = False) -> Dict[str, float]:
        return self.numbers(self.answers(), control)

    def numbers(self, answers: List[Dict[str, np.ndarray]],
                control: bool = False) -> Dict[str, float]:
        """The numbers compared, from the kept answers; with ``control``
        the reference in bfloat16 stands in for the program."""
        from . import check
        from . import control as control_lib

        if control:
            answers = control_lib.answers(self.cfg, self.consts, answers)
        return check.numbers(self.cfg, self.consts, answers, self.seed)

    def shape(self) -> Dict[str, Any]:
        return kernel_shape(self.cfg)

    def close(self) -> None:
        """Nothing outlives a run of this traffic."""

    def notes(self) -> Dict[str, Any]:
        """What the result line carries besides the metrics: the epochs the
        experiments took and the tile sizes the autotuner picked."""
        from repro.kernels.ga import autotune

        epochs: Dict[str, int] = {}
        for r in self.records:
            epochs[str(r["epochs"])] = epochs.get(str(r["epochs"]), 0) + 1
        tiles = {k: [v["tile_pop"], v["tile_len"]]
                 for k, v in autotune.load_cache().items()}
        return {"epochs": epochs, "checked": self._checked, "tiles": tiles}


def state_to_host(isl, pool) -> Dict[str, np.ndarray]:
    import jax

    return {k: np.asarray(v) for k, v in jax.device_get(
        {"pop": isl.pop, "fitness": isl.fitness, "pop_size": isl.pop_size,
         "best_genome": isl.best_genome, "best_fitness": isl.best_fitness,
         "pool_genomes": pool.genomes, "pool_fitness": pool.fitness}).items()}

"""The comparison that decides ``correct``: the program's final state of
each kept experiment against the plain reference of the configuration's
problem (``problems/<kind>.py``).

Numbers compared, each against the limit in the configuration's file:

* ``fit_gap``: the widest gap between a fitness the program reports and
  the reference's float64 fitness of the same genome, over every valid
  row of every island, each island's best and every pool entry, relative
  to max(|reference|, 1). It covers the fused or streamed eval and the
  pool. A gene altered after its fitness was taken shows here.
* ``stalled_blocks``: each island's valid rows are cut into 8 blocks; a
  block is stalled when its mean reference fitness is not above a random
  population's mean by 10 standard errors (the reference draws that
  population itself, from the seed). Exact, limit 0. A generation step
  that returns its state unchanged, or leaves islands or rows out, shows
  here.
* ``bf16_exact_genes`` (float genomes): the share of the valid rows'
  genes, strictly inside the problem's bounds, whose float32 value has
  its low 16 bits zero, i.e. is exactly a bfloat16. Float32 arithmetic
  leaves about 2^-16 of them so; a gather that rounds parents to
  bfloat16 hands every copied, unmutated gene over rounded. Genes clipped
  to a bound are exact in any precision and are left out.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import numpy as np

from . import spec

BLOCKS = 8
SIGMAS = 10.0
RANDOM_ROWS = 4096


def _rel_gap(prog: np.ndarray, ref: np.ndarray) -> float:
    prog = np.asarray(prog, np.float64)
    if prog.size == 0:
        return 0.0
    return float(np.max(np.abs(prog - ref) / np.maximum(np.abs(ref), 1.0)))


def bf16_exact(genes: np.ndarray, low: float, high: float) -> Tuple[int, int]:
    """(genes exactly a bfloat16, genes counted) of float32 ``genes``
    strictly inside (low, high)."""
    g = np.ascontiguousarray(genes, np.float32)
    inside = (g > low) & (g < high)
    exact = (g.view(np.uint32) & np.uint32(0xFFFF)) == 0
    return int(np.sum(exact & inside)), int(np.sum(inside))


def numbers(cfg: Dict[str, Any], consts, answers: List[Dict[str, Any]],
            seed: int) -> Dict[str, float]:
    p = cfg["problem"]
    prob = spec.problem(p["kind"])
    rand = prob.reference(p, consts, prob.random_population(p, RANDOM_ROWS,
                                                            seed))
    mu, sd = float(rand.mean()), float(rand.std())
    floats = cfg["genome"]["kind"] == "float"
    gap, stalled, exact, counted = 0.0, 0, 0, 0
    for a in answers:
        for isl in range(a["pop"].shape[0]):
            n = int(a["pop_size"][isl])
            pop = a["pop"][isl, :n]
            ref = prob.reference(p, consts, pop)
            gap = max(gap, _rel_gap(a["fitness"][isl, :n], ref))
            for block in np.array_split(ref, BLOCKS):
                if block.mean() <= mu + SIGMAS * sd / np.sqrt(block.size):
                    stalled += 1
            if floats:
                e, c = bf16_exact(pop, p["low"], p["high"])
                exact, counted = exact + e, counted + c
        gap = max(gap, _rel_gap(a["best_fitness"],
                                prob.reference(p, consts, a["best_genome"])))
        live = np.isfinite(a["pool_fitness"])
        gap = max(gap, _rel_gap(a["pool_fitness"][live],
                                prob.reference(p, consts,
                                               a["pool_genomes"][live])))
    out = {"fit_gap": gap, "stalled_blocks": float(stalled)}
    if floats:
        out["bf16_exact_genes"] = exact / max(counted, 1)
    return out


def verdict(nums: Dict[str, float],
            limits: Dict[str, float]) -> Tuple[bool, Dict[str, Dict]]:
    """``correct`` and each number beside its limit. A number with no
    limit, or one that is not finite, fails."""
    table, ok = {}, True
    for k, v in nums.items():
        lim = limits.get(k)
        good = lim is not None and np.isfinite(v) and v <= lim
        ok &= bool(good)
        table[k] = {"value": v, "limit": lim}
    return ok, table

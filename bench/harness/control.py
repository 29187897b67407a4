"""The controls of the comparison, each of which must come out not
correct, which shows the comparison can fail:

* :func:`answers`: the reference put in the program's place and computed
  one precision lower than the configuration states (bfloat16 for float32
  fitness). Every fitness of the kept answers (rows, bests, pool) is
  recomputed by the problem's ``reference_bf16``, and the comparison then
  runs on them.
* :func:`plant_bf16_gather`: the program with its parent gather in
  bfloat16 (the step below the float32 genes the configuration states).
  The program's own kernel is handed the population rounded to bfloat16,
  so every parent row, elite and copy it gathers arrives rounded, and the
  fused fitness agrees with the rounded genes: only the genes can show it.
"""
from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from . import spec

PLANT = "bench_bf16_gather"


def answers(cfg: Dict[str, Any], consts,
            answers: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """The kept answers with every fitness the program reported replaced
    by the bfloat16 reference's."""
    p = cfg["problem"]
    f = spec.problem(p["kind"]).reference_bf16
    out = []
    for a in answers:
        c = dict(a)
        c["fitness"] = np.stack([f(p, consts, pop) for pop in a["pop"]])
        c["best_fitness"] = f(p, consts, a["best_genome"])
        live = np.isfinite(a["pool_fitness"])
        pf = a["pool_fitness"].astype(np.float64).copy()
        if live.any():
            pf[live] = f(p, consts, a["pool_genomes"][live])
        c["pool_fitness"] = pf
        out.append(c)
    return out


def plant_bf16_gather(base_impl: str) -> str:
    """Register the program's ``base_impl`` kernels fed a bfloat16-rounded
    population under the impl name :data:`PLANT`, and return that name."""
    import jax.numpy as jnp

    from repro.kernels.ga import get_kernel, register_kernel

    def planted(op):
        def kernel(rng, pop, *args, **kw):
            kind = args[3].kind
            if kind == "float":
                pop = pop.astype(jnp.bfloat16).astype(pop.dtype)
            return get_kernel(op, kind, base_impl)(rng, pop, *args, **kw)
        return kernel

    for op in ("generation", "generation_eval"):
        for kind in ("binary", "float"):
            register_kernel(op, kind, PLANT)(planted(op))
    return PLANT

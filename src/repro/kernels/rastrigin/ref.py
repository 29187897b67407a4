"""Pure-jnp oracle for F15 (= repro.core.problems.f15_ref)."""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp


def f15(consts: Dict[str, jax.Array], pop: jax.Array) -> jax.Array:
    o, perm, M = consts["o"], consts["perm"], consts["M"]
    n_groups, m, _ = M.shape
    z = (pop - o)[:, perm]
    zg = z.reshape(pop.shape[0], n_groups, m)
    rot = jnp.einsum("ngm,gmk->ngk", zg, M,
                     precision=jax.lax.Precision.HIGHEST)
    r = rot * rot - 10.0 * jnp.cos(2.0 * jnp.pi * rot) + 10.0
    return r.sum(axis=(-1, -2))

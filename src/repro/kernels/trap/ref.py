"""Pure-jnp oracle for the trap kernel (= repro.core.problems.trap_fitness_ref)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import ordered_sum


def trap_fitness(pop: jax.Array, *, n_traps: int, l: int, a: float, b: float,
                 z: float) -> jax.Array:
    n = pop.shape[0]
    blocks = pop.reshape(n, n_traps, l).astype(jnp.float32)
    u = blocks.sum(-1)
    f = jnp.where(u <= z, a * (z - u) / z, b * (u - z) / (l - z))
    return ordered_sum(f)

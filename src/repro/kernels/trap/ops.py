"""Public wrapper: pad to the block, dispatch the Pallas kernel."""
from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp

from .. import interpret_mode
from . import trap as _k
from . import ref as _ref


def trap_fitness(consts: Dict[str, float], pop: jax.Array, *, n_traps: int,
                 pop_block: int = _k.POP_BLOCK,
                 force_ref: bool = False) -> jax.Array:
    """Drop-in for problems.trap_fitness_ref backed by the Pallas kernel.

    consts: {'a','b','z','l'} must be *python* scalars (they are baked into
    the kernel as static constants — the Problem carries them in a closure,
    never through a jit boundary); pop: (N, n_traps*l) int8.
    """
    a, b, z, l = (float(consts["a"]), float(consts["b"]),
                  float(consts["z"]), int(consts["l"]))
    if force_ref:
        return _ref.trap_fitness(pop, n_traps=n_traps, l=l, a=a, b=b, z=z)
    n = pop.shape[0]
    pb = min(pop_block, max(8, n))
    pad = (-n) % pb
    if pad:
        pop = jnp.pad(pop, ((0, pad), (0, 0)))
    out = _k.trap_fitness_kernel(pop, n_traps=n_traps, l=l, a=a, b=b, z=z,
                                 interpret=interpret_mode(), pop_block=pb)
    return out[:n]

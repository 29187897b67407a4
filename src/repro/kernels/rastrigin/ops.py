"""Public wrapper: shift/permute/pad on the host side of the graph, kernel
for the rotate+reduce hot loop."""
from __future__ import annotations

from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from .. import interpret_mode
from . import rastrigin as _k
from . import ref as _ref

MXU_LANE = 128


def _pad_up(x: int, to: int) -> int:
    return -(-x // to) * to


@partial(jax.jit, static_argnames=("pop_block", "force_ref", "lane",
                                   "interpret"))
def f15(consts: Dict[str, jax.Array], pop: jax.Array, *,
        pop_block: int = _k.POP_BLOCK, force_ref: bool = False,
        lane: int = MXU_LANE, interpret: Optional[bool] = None) -> jax.Array:
    """CEC2010-F15 fitness (minimization value). pop: (N, D) f32 -> (N,).
    ``interpret`` defaults to :func:`repro.kernels.interpret_mode`."""
    if force_ref:
        return _ref.f15(consts, pop)
    o, perm, M = consts["o"], consts["perm"], consts["M"]
    G, m, _ = M.shape
    mp = _pad_up(m, lane)
    n = pop.shape[0]
    pb = min(pop_block, max(8, n))
    pad_n = (-n) % pb

    z = (pop - o)[:, perm].reshape(n, G, m)
    z = jnp.pad(z.astype(jnp.float32), ((0, pad_n), (0, 0), (0, mp - m)))
    Mp = jnp.pad(M.astype(jnp.float32), ((0, 0), (0, mp - m), (0, mp - m)))
    out = _k.f15_kernel(z.reshape(n + pad_n, G * mp), Mp,
                        interpret=(interpret_mode() if interpret is None
                                   else interpret), pop_block=pb)
    return out[:n]

"""Pallas kernel: fused CEC2010-F15 fitness (shift -> group-rotate ->
Rastrigin -> reduce).

Hardware adaptation (the paper's Fig-4 workload, re-blocked for the MXU):
the JS/Java implementations loop per individual and per group; here a grid
step processes a (POP_BLOCK, m̂) tile of the *pre-permuted, shifted*
population against one group's m̂×m̂ rotation matrix (m̂ = m padded to the
128-lane MXU width). The rotation is a single MXU matmul; the Rastrigin
reduction (square/cos/sum) runs on the VPU over the same VMEM tile. Group
results accumulate into the output block across the (sequential, innermost)
group grid dimension.

Padding is exact: padded coordinates are zero, and rastrigin(0) = 0, so
padded lanes contribute nothing.

Grid: (N/POP_BLOCK, G) — the (POP_BLOCK, 1) output column is revisited
across g (accumulation).
VMEM per step: POP_BLOCK*m̂ (z tile) + m̂*m̂ (M_g) + POP_BLOCK*m̂ (rotated)
≈ 256*128*4B * 2 + 64KB ≈ 320 KiB — well within a v5e core's 128 MiB VMEM
budget with double buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

POP_BLOCK = 256
TWO_PI = 6.283185307179586


def _f15_kernel(z_ref, m_ref, out_ref):
    g = pl.program_id(1)
    z = z_ref[...]                       # (PB, m̂) f32, pre-shifted+permuted
    M = m_ref[0]                         # (m̂, m̂) f32, zero-padded
    rot = jnp.dot(z, M, preferred_element_type=jnp.float32,
                  precision=jax.lax.Precision.HIGHEST)
    r = rot * rot - 10.0 * jnp.cos(TWO_PI * rot) + 10.0
    part = r.sum(axis=-1, keepdims=True)  # (PB, 1)

    @pl.when(g == 0)
    def _init():
        out_ref[...] = part

    @pl.when(g != 0)
    def _acc():
        out_ref[...] += part


def f15_kernel(zp: jax.Array, M: jax.Array, *, interpret: bool = False,
               pop_block: int = POP_BLOCK) -> jax.Array:
    """zp: (N, G*m̂) pre-shifted/permuted/padded; M: (G, m̂, m̂) -> (N,) f32."""
    n, Dp = zp.shape
    G, mp, _ = M.shape
    assert Dp == G * mp, (Dp, G, mp)
    assert n % pop_block == 0
    grid = (n // pop_block, G)
    return pl.pallas_call(
        _f15_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((pop_block, mp), lambda i, g: (i, g)),
            pl.BlockSpec((1, mp, mp), lambda i, g: (g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((pop_block, 1), lambda i, g: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1), jnp.float32),
        interpret=interpret,
    )(zp, M.reshape(G, mp, mp))[:, 0]

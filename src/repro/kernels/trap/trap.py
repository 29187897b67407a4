"""Pallas kernel: batched Ackley trap fitness over bitstring populations.

The EA hot loop evaluates the whole (padded) population every generation.
One grid step scores a (POP_BLOCK, n_traps*l) tile held in VMEM: bits are
summed per l-wide trap block (a matmul with a 0/1 block-membership matrix,
exact on bits) and the piecewise-linear trap value is reduced over traps
into a (POP_BLOCK, 1) column. Population tiles are
independent -> embarrassingly parallel grid.

Layout: chromosomes are int8 in HBM; a tile is (POP_BLOCK, L) int8 = e.g.
256x160 = 40 KiB -> comfortably VMEM-resident together with the f32
intermediates. All trap parameters are static (baked into the kernel).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import ordered_sum
from ..ga.common import block_sums

POP_BLOCK = 256


def _trap_kernel(pop_ref, out_ref, *, n_traps: int, l: int, a: float,
                 b: float, z: float):
    bits = pop_ref[...].astype(jnp.float32)            # (PB, n_traps*l)
    u = block_sums(bits, l)                            # (PB, n_traps)
    f = jnp.where(u <= z, a * (z - u) / z, b * (u - z) / (l - z))
    out_ref[...] = ordered_sum(f)[:, None]             # (PB, 1)


def trap_fitness_kernel(pop: jax.Array, *, n_traps: int, l: int, a: float,
                        b: float, z: float, interpret: bool = False,
                        pop_block: int = POP_BLOCK) -> jax.Array:
    """pop: (N, n_traps*l) int8 with N % pop_block == 0 -> (N,) f32."""
    n, L = pop.shape
    assert L == n_traps * l, (L, n_traps, l)
    assert n % pop_block == 0, (n, pop_block)
    grid = (n // pop_block,)
    kernel = functools.partial(_trap_kernel, n_traps=n_traps, l=l, a=a, b=b,
                               z=z)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((pop_block, L), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((pop_block, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1), jnp.float32),
        interpret=interpret,
    )(pop)[:, 0]

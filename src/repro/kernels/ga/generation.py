"""Pallas megakernel: one fused GA generation per island.

One ``pallas_call`` invocation runs the *entire* inner loop body of the
evolutionary algorithm — tournament/roulette selection, crossover,
mutation, and (optionally) the trap/royal-road/rastrigin fitness of the
new population — on a single VMEM-resident (max_pop, L) genome tile. The
host-visible alternative is four jnp ops with four PRNG splits and an HBM
round-trip between each (``ga.next_generation``); here nothing leaves
VMEM between selection and the evaluated child.

Shapes are small by design (an island's padded population: 256x160 int8 =
40 KiB binary, 256x1000 f32 = 1 MiB float — far under a core's VMEM), so
the kernel uses no grid: the whole tile is one program, and batching over
islands comes from ``jax.vmap`` on the ``pallas_call`` (one grid dimension
per vmapped axis). Randomness is generated on chip from a counter-based
Threefry stream (:mod:`.prng`) seeded by two uint32 key words — no noise
tensors are materialized in HBM.

The algorithm body is :func:`repro.kernels.ga.common.generation_math`,
shared with the jnp oracle (:mod:`.ref`) — interpret-mode parity is
bit-exact for binary genomes by construction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import GenerationSpec, generation_math, spec_needs_consts


def _generation_kernel(seed_ref, size_ref, pop_ref, fit_ref, *refs,
                       spec: GenerationSpec, with_consts: bool):
    if with_consts:
        o_ref, perm_ref, m_ref, *outs = refs
        consts = {"o": o_ref[...], "perm": perm_ref[...], "M": m_ref[...]}
    else:
        outs, consts = refs, None
    n = pop_ref.shape[0]
    out = generation_math(seed_ref[0, 0], seed_ref[0, 1], pop_ref[...],
                          fit_ref[...].reshape(n), size_ref[0, 0], spec,
                          consts=consts)
    if spec.fused_eval is None:
        outs[0][...] = out
    else:
        outs[0][...] = out[0]
        outs[1][...] = out[1].reshape(n, 1)


def generation_kernel(seed: jax.Array, size: jax.Array, pop: jax.Array,
                      fitness: jax.Array, spec: GenerationSpec,
                      interpret: bool = False, consts=None):
    """seed: (2,) uint32; size: (1,) int32; pop: (max_pop, L);
    fitness: (max_pop,) f32 -> new pop (max_pop, L) [+ (max_pop,) f32 raw
    fitness when ``spec.fused_eval`` is set]. Fused evals with array
    constants (f15) take them via ``consts`` — the arrays ride into VMEM as
    extra kernel operands.

    Every operand is at least 2-D, so that under ``jax.vmap`` (one grid
    step per island) each block still spans its array's last two dims, as
    Mosaic requires: the seed and size ride in SMEM as (1, 2) and (1, 1),
    fitness goes in as a (1, max_pop) row and comes out as a column."""
    n, L = pop.shape
    with_consts = spec_needs_consts(spec)
    kernel = functools.partial(_generation_kernel, spec=spec,
                               with_consts=with_consts)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    operands = [seed.reshape(1, 2), size.reshape(1, 1), pop,
                fitness.reshape(1, n)]
    if with_consts:
        if consts is None:
            raise ValueError(f"fused eval {spec.eval_spec['eval']!r} "
                             "needs problem consts")
        operands += [jnp.asarray(consts["o"], jnp.float32),
                     jnp.asarray(consts["perm"], jnp.int32),
                     jnp.asarray(consts["M"], jnp.float32)]
    out_shape = jax.ShapeDtypeStruct((n, L), pop.dtype)
    if spec.fused_eval is None:
        return pl.pallas_call(
            kernel, out_shape=out_shape,
            in_specs=[smem, smem] + [vmem] * (len(operands) - 2),
            out_specs=vmem, interpret=interpret)(*operands)
    new_pop, fit = pl.pallas_call(
        kernel, out_shape=(out_shape,
                           jax.ShapeDtypeStruct((n, 1), jnp.float32)),
        in_specs=[smem, smem] + [vmem] * (len(operands) - 2),
        out_specs=(vmem, vmem), interpret=interpret)(*operands)
    return new_pop, fit.reshape(n)

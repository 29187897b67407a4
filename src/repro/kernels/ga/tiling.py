"""Grid-tiled streaming GA generation: the beyond-VMEM megakernel.

The single-tile megakernel (:mod:`.generation`) holds the whole
(max_pop, L) genome matrix in VMEM — perfect for island-sized populations,
impossible for the paper's Fig-4 regime (pop 64k x L 1000 f32 = 256 MB).
This module re-blocks the same generation math over a Pallas grid

    ``grid = (ni, nj, nk)``  —  ni x nj output tiles, nk source blocks,

with ``BlockSpec`` index maps streaming HBM tiles through VMEM (Pallas
pipelines each BlockSpec'd operand through double-buffered VMEM copies
automatically, so tile (k+1) DMAs in while tile k is in compute):

* output tile (i, j): rows [i*TP, (i+1)*TP) x genes [j*TL, (j+1)*TL)
* pop block (k, j): source rows [k*TP, (k+1)*TP) of the same gene slice
* plan vectors (idx_a/idx_b/cut1/cut2/gate from
  :func:`~.common.selection_plan`, computed once outside the grid): row
  slice i.

The innermost (fastest) grid axis is k: parent gather is a blocked one-hot
matmul contraction — ``onehot(idx, source block) @ pop_block`` accumulated
into persistent VMEM scratch (``pltpu.VMEM``) across k. A one-hot gather
row is 1*source_row + 0*rest, so the blocked accumulation is *exactly* the
gathered parent row, bitwise, while staying MXU-native. At k == nk-1 the
accumulated parent tiles run :func:`~.common.child_tile_math` with the
tile origin as the global RNG offset (see :mod:`.prng`, "tiling-invariant
counters") and the child tile is written out — which is why any (TP, TL)
tiling is bit-identical to the untiled kernel and the jnp oracle.

Fused evaluation under tiling:

* separable evals (trap / royal_road / onemax / rastrigin / sphere) are
  column reductions — each output tile adds its partial fitness
  (:func:`~.common.separable_fused_tile`) into a per-row-block fitness
  output revisited across j.
* f15 is *not* column-separable (permutation + per-group rotation), so the
  tiled path is two streaming kernels: tiled generation, then the
  :mod:`repro.kernels.rastrigin` eval kernel, whose own grid streams the
  per-group rotation stack ``M[g]`` through VMEM one (m̂ x m̂) matrix at a
  time against (POP_BLOCK, m̂) population tiles.

Tile sizes come from :mod:`.autotune` (cached per device_kind); the
registry's ``pallas`` impl auto-routes here once the untiled VMEM estimate
exceeds the budget (see ``ops.py``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import (GenerationSpec, child_tile_math, gather_split,
                     onehot_matmul, selection_plan, separable_fused_tile,
                     spec_needs_consts)

DEFAULT_TILE_POP = 256
DEFAULT_TILE_LEN = 512


def _pad_up(x: int, to: int) -> int:
    return -(-x // to) * to


def _onehot_block(idx: jax.Array, k, tk: int) -> jax.Array:
    """(TP, TK) f32 one-hot of per-row source indices (a (TP, 1) column)
    vs source block k."""
    lanes = (jnp.asarray(k, jnp.int32) * tk
             + jax.lax.broadcasted_iota(jnp.int32, (1, tk), 1))
    return (idx == lanes).astype(jnp.float32)


def _tiled_kernel(seed_ref, idxa_ref, idxb_ref, c1_ref, c2_ref, gate_ref,
                  pop_ref, *refs, spec: GenerationSpec, tp: int, tl: int,
                  fused: bool):
    if fused:
        out_ref, fit_ref, pa_acc, pb_acc = refs
    else:
        out_ref, pa_acc, pb_acc = refs
        fit_ref = None
    i = pl.program_id(0)
    j = pl.program_id(1)
    k = pl.program_id(2)
    nk = pl.num_programs(2)

    popb = pop_ref[...].astype(jnp.float32)          # (TP, TL) source block
    split = gather_split(spec)
    part_a = onehot_matmul(_onehot_block(idxa_ref[...], k, tp), popb, split)
    part_b = onehot_matmul(_onehot_block(idxb_ref[...], k, tp), popb, split)

    @pl.when(k == 0)
    def _init():
        pa_acc[...] = part_a
        pb_acc[...] = part_b

    @pl.when(k != 0)
    def _acc():
        pa_acc[...] += part_a
        pb_acc[...] += part_b

    @pl.when(k == nk - 1)
    def _emit():
        kids = child_tile_math(seed_ref[0, 0], seed_ref[0, 1], pa_acc[...],
                               pb_acc[...], c1_ref[...], c2_ref[...],
                               gate_ref[...], spec,
                               row0=i * tp, col0=j * tl)
        out_ref[...] = kids.astype(out_ref.dtype)
        if fit_ref is not None:
            part = separable_fused_tile(kids, spec.eval_spec, j * tl,
                                        spec.length)[:, None]

            @pl.when(j == 0)
            def _fit_init():
                fit_ref[...] = part

            @pl.when(j != 0)
            def _fit_acc():
                fit_ref[...] += part


def _eval_group_size(spec: GenerationSpec) -> int:
    """Column-block granularity a separable fused eval reduces over (trap
    l / royal-road r); tile widths must be multiples of it."""
    ev = spec.eval_spec
    if ev is None:
        return 1
    return int({"trap": ev.get("l", 1),
                "royal_road": ev.get("r", 1)}.get(ev["eval"], 1))


def generation_tiled(seed: jax.Array, size: jax.Array, pop: jax.Array,
                     fitness: jax.Array, spec: GenerationSpec, *,
                     tile_pop: int = DEFAULT_TILE_POP,
                     tile_len: int = DEFAULT_TILE_LEN,
                     interpret: bool = False, consts=None):
    """Tiled drop-in for :func:`.generation.generation_kernel` — same
    contract, any population size. Ragged shapes are zero-padded up to the
    tile grid; padded rows/genes are computed but sliced off (their RNG
    draws live on disjoint or discarded counters, so valid output is
    bit-identical to the untiled kernel for every tiling)."""
    n, L = pop.shape
    fused_spec = spec.eval_spec
    f15 = spec_needs_consts(spec)

    if f15:
        # two-kernel streaming path: tiled generation, then the rastrigin
        # engine's grid kernel streaming the rotation stack per group.
        gen_spec = GenerationSpec(**{**dataclass_asdict(spec),
                                     "fused_eval": None})
        new_pop = generation_tiled(seed, size, pop, fitness, gen_spec,
                                   tile_pop=tile_pop, tile_len=tile_len,
                                   interpret=interpret)
        if consts is None:
            raise ValueError("fused f15 evaluation needs problem consts")
        from ..rastrigin import ops as f15_ops
        fit = -f15_ops.f15(consts, new_pop.astype(jnp.float32),
                           interpret=interpret)
        return new_pop, fit

    fused = fused_spec is not None
    gsz = _eval_group_size(spec)
    tp = max(8, min(tile_pop, _pad_up(n, 8)))
    tl = _pad_up(max(gsz, min(tile_len, _pad_up(L, gsz))), gsz)
    np_, lp = _pad_up(n, tp), _pad_up(L, tl)

    k0, k1 = seed[0], seed[1]
    plan = selection_plan(k0, k1, fitness, size[0], spec, n)
    pad_r, pad_c = np_ - n, lp - L
    popp = jnp.pad(pop, ((0, pad_r), (0, pad_c)))
    # plan rows and fused fitness travel as (np_, 1) columns: Mosaic
    # refuses 1-D (tp,) row blocks whose tiling differs from XLA's
    pcol = lambda v: jnp.pad(v, (0, pad_r))[:, None]  # noqa: E731

    ni, nj, nk = np_ // tp, lp // tl, np_ // tp
    grid = (ni, nj, nk)
    row_spec = pl.BlockSpec((tp, 1), lambda i, j, k: (i, 0))
    out_shape = [jax.ShapeDtypeStruct((np_, lp), pop.dtype)]
    out_specs = [pl.BlockSpec((tp, tl), lambda i, j, k: (i, j))]
    if fused:
        out_shape.append(jax.ShapeDtypeStruct((np_, 1), jnp.float32))
        out_specs.append(row_spec)

    kernel = functools.partial(_tiled_kernel, spec=spec, tp=tp, tl=tl,
                               fused=fused)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),         # (1, 2) seed
            row_spec, row_spec, row_spec, row_spec, row_spec,
            pl.BlockSpec((tp, tl), lambda i, j, k: (k, j)),  # pop source
        ],
        out_specs=out_specs if fused else out_specs[0],
        out_shape=tuple(out_shape) if fused else out_shape[0],
        scratch_shapes=[pltpu.VMEM((tp, tl), jnp.float32),
                        pltpu.VMEM((tp, tl), jnp.float32)],
        interpret=interpret,
        name="gen_tiled",
    )(seed.reshape(1, 2), pcol(plan.idx_a), pcol(plan.idx_b),
      pcol(plan.cut1), pcol(plan.cut2), pcol(plan.gate), popp)

    if fused:
        new_pop, fit = out
        return new_pop[:n, :L], fit[:n, 0]
    return out[:n, :L]


def dataclass_asdict(spec: GenerationSpec) -> dict:
    """Shallow field dict of a GenerationSpec (dataclasses.asdict recurses
    into the fused_eval tuple; we want the fields verbatim)."""
    import dataclasses
    return {f.name: getattr(spec, f.name)
            for f in dataclasses.fields(spec)}

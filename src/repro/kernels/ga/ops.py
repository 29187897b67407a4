"""Public wrappers + built-in registrations for the GA kernel engine.

Adapts the driver-side contract (``rng`` typed key, ``EAConfig`` +
``GenomeSpec`` statics, scalar ``pop_size``) to the kernel contract
(two uint32 seed words, :class:`~repro.kernels.ga.common.GenerationSpec`),
and registers the built-in impls:

* ``jnp``          — the classic :func:`repro.core.ga.next_generation_jnp`
                     path (four ops, jax.random streams).
* ``pallas``       — the fused VMEM megakernel (interpret mode on the CPU).
                     Auto-routes to the tiled engine once the untiled VMEM
                     estimate exceeds :data:`VMEM_BUDGET_BYTES`, so callers
                     never fall off a VMEM cliff by growing the population.
* ``pallas_tiled`` — the grid-tiled streaming kernel (:mod:`.tiling`)
                     explicitly, tile sizes from :mod:`.autotune` unless
                     given. Bit-identical to ``pallas`` for any tiling.
* ``pallas_ref``   — the megakernel's pure-jnp oracle (same counter RNG).

``generation_eval`` fuses the problem's fitness into the same kernel and
is registered for the kernel family only — the ``jnp`` impl keeps
evaluation in ``Problem.evaluate`` (that split *is* the baseline the speed
harness measures against). Fused evals with array constants (f15) receive
them via the optional ``consts`` kwarg that every kernel-family entry
accepts.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from .. import interpret_mode
from . import autotune as _autotune
from . import generation as _k
from . import ref as _ref
from . import tiling as _tiling
from .common import GenerationSpec, spec_needs_consts
from .registry import register_kernel

# Routing threshold for impl='pallas': estimated VMEM working set of the
# single-tile megakernel (6 f32 copies of the genome tile for pop/parents/
# children + the (n, n) one-hot gather and selection blocks) above which
# the call silently becomes a tiled-engine call. Far under real VMEM
# (16 MiB vs ~128 MiB/core) so the untiled path keeps headroom for the
# pipeline's own buffers.
VMEM_BUDGET_BYTES = 16 * 2**20


def untiled_vmem_bytes(n: int, L: int,
                       spec: Optional[GenerationSpec] = None) -> int:
    est = n * L * 4 * 6 + n * n * 4 * 2
    if spec is not None and spec_needs_consts(spec):
        est += L * L * 4 + 2 * n * L * 4  # perm one-hot + rotated copies
    return est


def make_spec(cfg, genome,
              fused: Optional[Dict[str, Any]] = None) -> GenerationSpec:
    """Freeze the (EAConfig, GenomeSpec[, Problem.fused]) statics into the
    kernel-side :class:`GenerationSpec` (hashable, jit-constant)."""
    return GenerationSpec(
        kind=genome.kind,
        length=genome.length,
        elite=cfg.elite,
        selection=cfg.selection,
        tournament_k=cfg.tournament_k,
        crossover=cfg.crossover,
        crossover_rate=cfg.crossover_rate,
        mutation_rate=cfg.mut_rate(genome),
        mutation_sigma=cfg.mutation_sigma,
        low=genome.low,
        high=genome.high,
        fused_eval=(tuple(sorted(fused.items()))
                    if fused is not None else None),
    )


def _seed_words(rng: jax.Array) -> jax.Array:
    """Typed PRNG key -> the (2,) uint32 words seeding the counter RNG.

    Key data is 2 words under the default threefry impl; other impls (rbg:
    4 words, 1-word impls) are folded/padded to exactly two so the engine
    works under any ``jax_default_prng_impl``.
    """
    data = jax.random.key_data(rng).astype(jnp.uint32).ravel()
    if data.shape[0] == 1:
        return jnp.stack([data[0], jnp.uint32(0)])
    k0, k1 = data[0], data[1]
    for w in range(2, data.shape[0]):  # static: fold extra words into k1
        k1 = k1 ^ data[w]
    return jnp.stack([k0, k1])


def _size_vec(pop_size) -> jax.Array:
    return jnp.asarray(pop_size, jnp.int32).reshape(1)


# ---------------------------------------------------------------------------
# generation: (rng, pop, fitness, pop_size, cfg, genome) -> new_pop
# ---------------------------------------------------------------------------
def _tiles(pop, genome, tile_pop, tile_len):
    if tile_pop is not None and tile_len is not None:
        return tile_pop, tile_len
    tp, tl = _autotune.best_tiles(pop.shape[0], genome.length, genome.kind)
    return tile_pop or tp, tile_len or tl


@register_kernel("generation", "binary", "pallas")
@register_kernel("generation", "float", "pallas")
def generation(rng, pop, fitness, pop_size, cfg, genome, *,
               interpret: Optional[bool] = None, consts=None):
    spec = make_spec(cfg, genome)
    interpret = interpret_mode() if interpret is None else interpret
    if untiled_vmem_bytes(*pop.shape, spec) > VMEM_BUDGET_BYTES:
        tp, tl = _tiles(pop, genome, None, None)
        return _tiling.generation_tiled(_seed_words(rng), _size_vec(pop_size),
                                        pop, fitness, spec, tile_pop=tp,
                                        tile_len=tl, interpret=interpret)
    return _k.generation_kernel(_seed_words(rng), _size_vec(pop_size), pop,
                                fitness, spec, interpret=interpret)


@register_kernel("generation", "binary", "pallas_tiled")
@register_kernel("generation", "float", "pallas_tiled")
def generation_tiled(rng, pop, fitness, pop_size, cfg, genome, *,
                     interpret: Optional[bool] = None,
                     tile_pop: Optional[int] = None,
                     tile_len: Optional[int] = None, consts=None):
    spec = make_spec(cfg, genome)
    interpret = interpret_mode() if interpret is None else interpret
    tp, tl = _tiles(pop, genome, tile_pop, tile_len)
    return _tiling.generation_tiled(_seed_words(rng), _size_vec(pop_size),
                                    pop, fitness, spec, tile_pop=tp,
                                    tile_len=tl, interpret=interpret)


@register_kernel("generation", "binary", "pallas_ref")
@register_kernel("generation", "float", "pallas_ref")
def generation_ref(rng, pop, fitness, pop_size, cfg, genome, *, consts=None):
    spec = make_spec(cfg, genome)
    return _ref.generation(_seed_words(rng), _size_vec(pop_size), pop,
                           fitness, spec)


# ---------------------------------------------------------------------------
# generation_eval: ... + fused spec -> (new_pop, raw_fitness)
# ---------------------------------------------------------------------------
@register_kernel("generation_eval", "binary", "pallas")
@register_kernel("generation_eval", "float", "pallas")
def generation_eval(rng, pop, fitness, pop_size, cfg, genome, fused, *,
                    interpret: Optional[bool] = None, consts=None):
    spec = make_spec(cfg, genome, fused=fused)
    interpret = interpret_mode() if interpret is None else interpret
    if untiled_vmem_bytes(*pop.shape, spec) > VMEM_BUDGET_BYTES:
        tp, tl = _tiles(pop, genome, None, None)
        return _tiling.generation_tiled(_seed_words(rng), _size_vec(pop_size),
                                        pop, fitness, spec, tile_pop=tp,
                                        tile_len=tl, interpret=interpret,
                                        consts=consts)
    return _k.generation_kernel(_seed_words(rng), _size_vec(pop_size), pop,
                                fitness, spec, interpret=interpret,
                                consts=consts)


@register_kernel("generation_eval", "binary", "pallas_tiled")
@register_kernel("generation_eval", "float", "pallas_tiled")
def generation_eval_tiled(rng, pop, fitness, pop_size, cfg, genome, fused, *,
                          interpret: Optional[bool] = None,
                          tile_pop: Optional[int] = None,
                          tile_len: Optional[int] = None, consts=None):
    spec = make_spec(cfg, genome, fused=fused)
    interpret = interpret_mode() if interpret is None else interpret
    tp, tl = _tiles(pop, genome, tile_pop, tile_len)
    return _tiling.generation_tiled(_seed_words(rng), _size_vec(pop_size),
                                    pop, fitness, spec, tile_pop=tp,
                                    tile_len=tl, interpret=interpret,
                                    consts=consts)


@register_kernel("generation_eval", "binary", "pallas_ref")
@register_kernel("generation_eval", "float", "pallas_ref")
def generation_eval_ref(rng, pop, fitness, pop_size, cfg, genome, fused, *,
                        consts=None):
    spec = make_spec(cfg, genome, fused=fused)
    return _ref.generation(_seed_words(rng), _size_vec(pop_size), pop,
                           fitness, spec, consts=consts)


def _register_jnp():
    # Runs at import time, so importing repro.kernels.ga pulls repro.core.
    # That is safe only while no repro.core module imports kernels.ga at
    # *top level* (core.ga defers its registry import to dispatch time) —
    # keep it that way or move this registration to first lookup.
    from repro.core import ga as core_ga

    @register_kernel("generation", "binary", "jnp")
    @register_kernel("generation", "float", "jnp")
    def generation_jnp(rng, pop, fitness, pop_size, cfg, genome):
        return core_ga.next_generation_jnp(rng, pop, fitness, pop_size, cfg,
                                           genome)
    return generation_jnp


_register_jnp()

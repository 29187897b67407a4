"""Vectorized genetic operators (the NodEO 'Classic' algorithm, JAX-native).

All operators act on a full padded population at once; per-individual
randomness comes from explicitly split PRNG keys. Selection only ever draws
parent *indices* in ``[0, pop_size)`` so padded lanes (>= pop_size) are never
selected — they are still written each generation (fixed SPMD lanes) but are
invisible to the algorithm (fitness forced to -inf).

``next_generation`` is the dispatch point of the generation-operator engine
(``EAConfig.impl``): the classic jnp path below is the ``'jnp'`` impl; any
other impl resolves a kernel from the ``repro.kernels.ga`` registry (the
fused Pallas megakernel and its counter-RNG jnp oracle ship built in).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from .types import Array, EAConfig, GenomeSpec

NEG_INF = float("-inf")  # not a jnp scalar: that would start a backend


def mask_fitness(fitness: Array, pop_size: Array) -> Array:
    """Force padded lanes to -inf so they never win selection/argmax."""
    lanes = jnp.arange(fitness.shape[0])
    return jnp.where(lanes < pop_size, fitness, NEG_INF)


# ---------------------------------------------------------------------------
# Selection
# ---------------------------------------------------------------------------
def tournament_select(rng: Array, fitness: Array, pop_size: Array, n: int,
                      k: int = 2) -> Array:
    """Return (n,) parent indices via size-k tournaments over valid lanes."""
    cand = jax.random.randint(rng, (n, k), 0, jnp.maximum(pop_size, 1))
    cf = fitness[cand]                                  # (n, k)
    return cand[jnp.arange(n), jnp.argmax(cf, axis=1)]


def roulette_logits(fitness: Array, pop_size: Array) -> Array:
    """Log-weights for fitness-proportional selection (shifted to
    positives). Invalid lanes get *exactly* ``-inf`` — the old
    ``log(w + 1e-30)`` formulation gave padded lanes a tiny but nonzero
    logit, i.e. a nonzero selection probability."""
    masked = mask_fitness(fitness, pop_size)
    valid = jnp.isfinite(masked)
    finite = jnp.where(valid, masked, 0.0)
    lo = jnp.min(jnp.where(valid, masked, jnp.inf))
    w = jnp.where(valid, finite - lo + 1e-6, 1.0)  # valid lanes: w >= 1e-6
    return jnp.where(valid, jnp.log(w), NEG_INF)


def roulette_select(rng: Array, fitness: Array, pop_size: Array, n: int) -> Array:
    """Fitness-proportional selection (masked: padded lanes unselectable)."""
    return jax.random.categorical(rng, roulette_logits(fitness, pop_size),
                                  shape=(n,))


def select(rng: Array, fitness: Array, pop_size: Array, n: int,
           cfg: EAConfig) -> Array:
    if cfg.selection == "tournament":
        return tournament_select(rng, fitness, pop_size, n, cfg.tournament_k)
    if cfg.selection == "roulette":
        return roulette_select(rng, fitness, pop_size, n)
    raise ValueError(f"unknown selection {cfg.selection!r}")


# ---------------------------------------------------------------------------
# Crossover
# ---------------------------------------------------------------------------
def two_point_crossover(rng: Array, pa: Array, pb: Array) -> Array:
    """Classic 2-point crossover; works for binary and float genomes.

    pa/pb: (n, L) parent pairs -> (n, L) children.
    """
    n, L = pa.shape
    k1, k2 = jax.random.split(rng)
    cut = jnp.sort(jax.random.randint(k1, (n, 2), 0, L + 1), axis=1)
    pos = jnp.arange(L)[None, :]
    inside = (pos >= cut[:, :1]) & (pos < cut[:, 1:])
    return jnp.where(inside, pb, pa)


def uniform_crossover(rng: Array, pa: Array, pb: Array) -> Array:
    mask = jax.random.bernoulli(rng, 0.5, pa.shape)
    return jnp.where(mask, pb, pa)


def blend_crossover(rng: Array, pa: Array, pb: Array, alpha: float = 0.5) -> Array:
    """BLX-alpha for float genomes."""
    u = jax.random.uniform(rng, pa.shape, jnp.float32,
                           -alpha, 1.0 + alpha)
    return (pa + u * (pb - pa)).astype(pa.dtype)


def crossover(rng: Array, pa: Array, pb: Array, cfg: EAConfig,
              genome: GenomeSpec) -> Array:
    k_cx, k_rate = jax.random.split(rng)
    if cfg.crossover == "two_point":
        kids = two_point_crossover(k_cx, pa, pb)
    elif cfg.crossover == "uniform":
        kids = uniform_crossover(k_cx, pa, pb)
    elif cfg.crossover == "blend":
        if genome.kind != "float":
            raise ValueError("blend crossover requires float genome")
        kids = blend_crossover(k_cx, pa, pb)
    else:
        raise ValueError(f"unknown crossover {cfg.crossover!r}")
    do = jax.random.bernoulli(k_rate, cfg.crossover_rate, (pa.shape[0], 1))
    return jnp.where(do, kids, pa)


# ---------------------------------------------------------------------------
# Mutation
# ---------------------------------------------------------------------------
def mutate(rng: Array, pop: Array, cfg: EAConfig, genome: GenomeSpec) -> Array:
    rate = cfg.mut_rate(genome)
    if genome.kind == "binary":
        flips = jax.random.bernoulli(rng, rate, pop.shape)
        return jnp.where(flips, 1 - pop, pop).astype(pop.dtype)
    k_m, k_g = jax.random.split(rng)
    hits = jax.random.bernoulli(k_m, rate, pop.shape)
    noise = jax.random.normal(k_g, pop.shape, jnp.float32) * cfg.mutation_sigma
    out = jnp.where(hits, pop + noise, pop)
    return jnp.clip(out, genome.low, genome.high).astype(pop.dtype)


# ---------------------------------------------------------------------------
# One full generation
# ---------------------------------------------------------------------------
def next_generation(rng: Array, pop: Array, fitness: Array, pop_size: Array,
                    cfg: EAConfig, genome: GenomeSpec) -> Array:
    """Produce the next padded population.

    Layout: slots [0, elite) hold the elite (best of the *valid* lanes),
    slots [elite, max_pop) hold fresh children. Lanes >= pop_size are
    computed but algorithmically inert.

    Dispatches on ``cfg.impl``: 'jnp' runs the classic path below;
    anything else resolves a registered generation kernel from the
    operator registry (repro.kernels.ga — e.g. the fused Pallas
    megakernel for 'pallas', its jnp oracle for 'pallas_ref').
    """
    if cfg.impl != "jnp":
        from repro.kernels.ga import get_kernel  # deferred: core<->kernels

        kern = get_kernel("generation", genome.kind, cfg.impl)
        return kern(rng, pop, fitness, pop_size, cfg, genome)
    return next_generation_jnp(rng, pop, fitness, pop_size, cfg, genome)


def next_generation_jnp(rng: Array, pop: Array, fitness: Array,
                        pop_size: Array, cfg: EAConfig,
                        genome: GenomeSpec) -> Array:
    """The classic jnp generation (the ``impl='jnp'`` registry entry)."""
    n = pop.shape[0]
    masked = mask_fitness(fitness, pop_size)
    k_sa, k_sb, k_cx, k_mut = jax.random.split(rng, 4)

    n_children = n - cfg.elite
    ia = select(k_sa, masked, pop_size, n_children, cfg)
    ib = select(k_sb, masked, pop_size, n_children, cfg)
    kids = crossover(k_cx, pop[ia], pop[ib], cfg, genome)
    kids = mutate(k_mut, kids, cfg, genome)

    _, elite_idx = jax.lax.top_k(masked, cfg.elite)
    return jnp.concatenate([pop[elite_idx], kids], axis=0)

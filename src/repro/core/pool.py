"""Device-resident chromosome pool + collective migration.

This is the TPU-native analogue of the paper's Node.js REST pool server:

* ``PUT`` — after each autonomous epoch every island contributes its best
  individual; under SPMD the contributions are ``all_gather``-ed so every
  shard applies the *same deterministic update* to its replica of the pool
  (the pool is replicated state, like the single server, but without the
  single point of failure).
* ``GET`` — each island draws a uniformly random pool member with its own
  PRNG key (the paper's "random individual from the server").

An alternative ``ring`` mode trades the all_gather for a
``collective_permute`` (classic ring-island migration) — cheaper on the
interconnect; measured against all_gather in §Perf.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import acceptance as acceptance_lib
from .types import (AcceptanceConfig, Array, GenomeSpec, MigrationConfig,
                    PoolState)

NEG_INF = float("-inf")  # not a jnp scalar: that would start a backend

_ALWAYS = AcceptanceConfig()


def pool_init(capacity: int, genome: GenomeSpec) -> PoolState:
    return PoolState(
        genomes=jnp.zeros((capacity, genome.length), genome.dtype),
        fitness=jnp.full((capacity,), NEG_INF, jnp.float32),
        ptr=jnp.int32(0),
        count=jnp.int32(0),
    )


def pool_reset(pool: PoolState) -> PoolState:
    return PoolState(
        genomes=jnp.zeros_like(pool.genomes),
        fitness=jnp.full_like(pool.fitness, NEG_INF),
        ptr=jnp.int32(0),
        count=jnp.int32(0),
    )


def pool_put_batch(pool: PoolState, genomes: Array, fitness: Array,
                   valid: Optional[Array] = None,
                   acc: Optional[AcceptanceConfig] = None,
                   rng: Optional[Array] = None) -> PoolState:
    """Insert k entries through the acceptance engine (core.acceptance).
    ``valid`` masks out entries (e.g. islands whose PUT was lost); invalid
    entries never take a slot. ``acc`` selects the registered acceptance
    policy (default: 'always', the legacy ring insert — slots advance from
    the ring pointer exactly as before the engine existed); ``rng`` feeds
    stochastic custom policies (built-ins ignore it).

    Deterministic given inputs — safe to replay identically on every shard.
    """
    return acceptance_lib.apply_policy(pool, genomes, fitness, valid, rng,
                                       acc if acc is not None else _ALWAYS)


def pool_get_random(pool: PoolState, rng: Array) -> Tuple[Array, Array]:
    """Uniform random pool member; (-inf fitness, zeros) when pool is empty
    (= server down / cold start — the island will treat it as a no-op)."""
    idx = jax.random.randint(rng, (), 0, jnp.maximum(pool.count, 1))
    empty = pool.count == 0
    fit = jnp.where(empty, NEG_INF, pool.fitness[idx])
    return pool.genomes[idx], fit


def pool_best(pool: PoolState) -> Tuple[Array, Array]:
    i = jnp.argmax(pool.fitness)
    return pool.genomes[i], pool.fitness[i]


def pool_insert_host(pool: PoolState, genomes: Sequence[np.ndarray],
                     fits: Sequence[float],
                     acc: Optional[AcceptanceConfig] = None,
                     rng: Optional[Array] = None) -> PoolState:
    """Insert host-side entries (e.g. a PoolServer's volunteer
    contributions, pulled by a sync or async HostBridge) into the device
    pool through the acceptance engine. Accepts a ``device_get``'d (numpy)
    pool — re-wraps it so the ``.at[]`` update works either way."""
    pool = jax.tree.map(jnp.asarray, pool)
    return pool_put_batch(
        pool,
        jnp.asarray(np.stack(list(genomes)), pool.genomes.dtype),
        jnp.asarray(list(fits), jnp.float32),
        acc=acc, rng=rng)


# ---------------------------------------------------------------------------
# Migration — thin wrappers over the unified engine (core.migration).
# Kept for API stability; new code should call migration.migrate directly.
# ---------------------------------------------------------------------------
def migrate_batch(pool: PoolState, bests_genome: Array, bests_fitness: Array,
                  rng: Array, available: Array | bool = True,
                  mig: Optional[MigrationConfig] = None, epoch: Array | int = 0,
                  ) -> Tuple[PoolState, Array, Array]:
    """PUT all island bests, then GET one random immigrant per island
    (or whatever exchange ``mig.topology`` selects — default: pool).

    available=False emulates a dead server: pool unchanged, immigrants are
    marked -inf so islands continue standalone (the paper's fault-tolerance
    property).
    """
    from . import migration  # local import: migration imports pool primitives
    return migration.migrate(pool, bests_genome, bests_fitness, rng,
                             mig if mig is not None else MigrationConfig(),
                             axis=None, epoch=epoch, available=available)


def migrate_sharded(pool: PoolState, bests_genome: Array, bests_fitness: Array,
                    rng: Array, axis: str, cfg: MigrationConfig,
                    available: Array | bool = True, epoch: Array | int = 0,
                    ) -> Tuple[PoolState, Array, Array]:
    """Collective migration across the ``axis`` mesh dimension, dispatched
    through the topology registry (core.migration). ``cfg.topology`` picks
    the strategy; the legacy ``cfg.collective='ring'`` still selects the
    ring. Local arrays carry this shard's islands: bests_* is (n_local, L).
    """
    from . import migration  # local import: migration imports pool primitives
    return migration.migrate(pool, bests_genome, bests_fitness, rng, cfg,
                             axis=axis, epoch=epoch, available=available)

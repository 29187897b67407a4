"""Shared pytree/state types for the NodIO evolutionary runtime.

Conventions
-----------
* All *state* containers are ``NamedTuple``s (automatically pytrees, jit/vmap
  friendly). All *configuration* containers are frozen dataclasses (hashable,
  usable as jit static arguments).
* Fitness is always MAXIMIZED. Minimization problems negate internally.
* Populations are padded to a static ``max_pop``; the *effective* population
  size of an island is carried in ``IslandState.pop_size`` (NodIO-W²
  heterogeneity: sizes are drawn per island from [128, 256] and differ between
  islands while the SPMD lanes stay static).
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

Array = jax.Array
PyTree = Any


# ---------------------------------------------------------------------------
# Genomes
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class GenomeSpec:
    """Static description of a chromosome.

    kind: 'binary' (int8 0/1 vector) or 'float' (float32 vector in bounds).
    length: number of genes.
    low/high: bounds for float genomes (ignored for binary).
    """

    kind: str
    length: int
    low: float = -5.0
    high: float = 5.0

    def __post_init__(self):
        if self.kind not in ("binary", "float"):
            raise ValueError(f"unknown genome kind {self.kind!r}")

    @property
    def dtype(self):
        return jnp.int8 if self.kind == "binary" else jnp.float32


# ---------------------------------------------------------------------------
# EA configuration (static — hashable, goes into jit as a constant)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class EAConfig:
    """Configuration of the per-island 'Classic' NodEO-style GA.

    ``impl`` selects the generation-operator implementation — the fifth
    engine axis (repro.kernels.ga registry): 'jnp' (the classic four-op
    jax.random path, the default and the legacy-exact anchor), 'pallas'
    (the fused selection->crossover->mutation[->fitness] VMEM megakernel
    with on-chip counter RNG; interpret mode on the CPU), 'pallas_ref' (the
    megakernel's pure-jnp oracle — same counter RNG, bit-exact vs 'pallas'
    in interpret mode for binary genomes), or any custom registration.
    Note 'pallas'/'pallas_ref' draw their randomness from a different
    (counter-based) stream than 'jnp', so trajectories differ between the
    jnp and kernel families while each family is internally reproducible.
    """

    max_pop: int = 256              # static lane count (padded population)
    min_pop: int = 128              # W²: per-island pop ~ U[min_pop, max_pop]
    generations_per_epoch: int = 100  # the paper's migration interval n
    tournament_k: int = 2
    selection: str = "tournament"    # 'tournament' | 'roulette'
    crossover: str = "two_point"     # 'two_point' | 'uniform' | 'blend'
    crossover_rate: float = 0.9
    mutation_rate: Optional[float] = None  # None -> 1/L per gene
    mutation_sigma: float = 0.3      # gaussian sigma for float genomes
    elite: int = 2                   # elitism count
    max_evaluations: int = 5_000_000  # paper's evaluation budget
    success_eps: float = 1e-8
    impl: str = "jnp"                # 'jnp' | 'pallas' | 'pallas_ref' | custom

    def mut_rate(self, genome: GenomeSpec) -> float:
        return self.mutation_rate if self.mutation_rate is not None else 1.0 / genome.length


@dataclasses.dataclass(frozen=True)
class AcceptanceConfig:
    """Immigrant-acceptance policy — *which* candidates enter a pool, and
    which resident each one replaces (core.acceptance registry).

    The paper's server accepts every PUT, which drives the pool toward
    premature convergence as volunteers flood it with near-identical
    elites; the registered policies make replacement a pluggable strategy
    (the fourth orthogonal engine axis: topology x driver x runtime x
    acceptance).

    policy:  registered acceptance policy (core.acceptance): 'always'
             (legacy ring insert — the bit-for-bit correctness anchor) |
             'elitist' (replace-worst-if-better) | 'crowding' (replace the
             nearest resident by genome distance, deterministic tie-break)
             | 'dedup' (reject candidates within ``epsilon`` of a resident,
             then elitist) | any custom registration.
    epsilon: rejection radius for 'dedup' (0.0 = exact duplicates only).
    metric:  genome distance: 'hamming' | 'l2' | 'auto' (hamming for
             integer genomes, L2 for float).
    """

    policy: str = "always"
    epsilon: float = 0.0
    metric: str = "auto"

    def __post_init__(self):
        if self.epsilon < 0.0:
            raise ValueError("epsilon must be >= 0")
        if self.metric not in ("auto", "hamming", "l2"):
            raise ValueError(f"unknown metric {self.metric!r}")


@dataclasses.dataclass(frozen=True)
class MigrationConfig:
    """Pool/migration policy — the paper's PUT(best)/GET(random) cycle."""

    pool_capacity: int = 64          # chromosomes retained server-side
    get_random: bool = True          # GET a uniformly random pool member
    replace: str = "worst"           # immigrant replaces 'worst' | 'random'
    # Legacy alias: 'ring' selects the ring topology — in EVERY driver now
    # (pre-refactor only the sharded driver honoured it). Set ``topology``
    # explicitly instead; any explicit value (including 'pool') wins.
    collective: str = "all_gather"
    # Registered migration topology (core.migration): 'pool' | 'ring' |
    # 'torus' | 'random_graph' | 'broadcast_best' | any custom registration.
    # None = unset: resolves to the legacy ``collective`` mapping ('ring' ->
    # ring), else 'pool'.
    topology: Optional[str] = None
    # Immigrant-acceptance policy (core.acceptance): dispatched by every
    # pool insert (device PUT, host-bridge absorb) and, for policies other
    # than 'always', as a per-island gate on migration deliveries.
    acceptance: AcceptanceConfig = AcceptanceConfig()


# ---------------------------------------------------------------------------
# Dynamic state pytrees
# ---------------------------------------------------------------------------
class IslandState(NamedTuple):
    """State of one island (or a batch of islands when leading axis added).

    pop:          (max_pop, L) genome array
    fitness:      (max_pop,)   float32, -inf on padded lanes
    pop_size:     ()           int32, effective population size
    rng:          ()           PRNG key
    generation:   ()           int32, generations completed (this experiment)
    evaluations:  ()           int32, fitness evaluations charged (this island)
    best_fitness: ()           float32, best ever seen (this experiment)
    best_genome:  (L,)         genome of the best ever
    done:         ()           bool, island found the optimum
    experiments:  ()           int32, W² restart counter (solved experiments)
    uuid:         ()           int32, island identity (for host-pool requests)
    """

    pop: Array
    fitness: Array
    pop_size: Array
    rng: Array
    generation: Array
    evaluations: Array
    best_fitness: Array
    best_genome: Array
    done: Array
    experiments: Array
    uuid: Array


class PoolState(NamedTuple):
    """Device-resident chromosome pool (the REST server's array analogue).

    A fixed-capacity ring buffer. ``count`` saturates at capacity; ``ptr`` is
    the next write slot. Replicated (or per-shard identical) under SPMD.
    """

    genomes: Array   # (capacity, L)
    fitness: Array   # (capacity,) -inf for empty slots
    ptr: Array       # () int32 next write position
    count: Array     # () int32 number of valid entries (<= capacity)


class ExperimentStats(NamedTuple):
    """Per-epoch record emitted by the evolution driver."""

    epoch: Array
    best_fitness: Array       # global best across islands
    mean_best: Array          # mean of island bests
    total_evaluations: Array
    n_done: Array             # islands that found the optimum
    experiments_solved: Array  # cumulative W² solved-experiment count


class ExperimentState(NamedTuple):
    """The *whole* run state of one experiment — the unit of durability.

    Everything a fused driver carries across epochs lives here, so a
    device->host snapshot of this one pytree is sufficient to kill the
    process and resume bit-for-bit (checkpoint.Checkpointer serializes it;
    the segmented drivers in core.evolution / core.async_migration /
    core.sharded produce and consume it).  NodIO's stance made state: the
    experiment, not the process, is the durable object.

    Fields carried through the device scan (the drivers' scan carry — a
    static meta-test pins this correspondence so new carry state cannot
    silently escape checkpointing):

    islands:   IslandState batch (leading axis = islands)
    pool:      PoolState (the replicated device pool)
    astate:    AsyncState for the async runtimes, ``()`` for sync drivers
    key:       () PRNG key — the driver loop's migration-key stream
    epoch:     () int32 — epochs (sync) / ticks (async) completed
    stopped:   () bool — early-success latch (non-W²)
    obs:       ObsCounters (repro.obs.counters) when the run was asked
               for observability (``return_obs=True``), else ``()`` —
               an empty pytree adds no snapshot leaves, so obs-disabled
               checkpoints are unchanged

    Host-managed fields (not in the scan carry, documented as such in the
    coverage meta-test):

    stats:     stacked per-epoch ExperimentStats rows recorded so far
               (numpy, leading axis = epochs), or ``()`` when the run was
               started without stats
    next_uuid: () int32 — monotonic island-uuid watermark; elastic grow
               allocates from here so a shrink->grow sequence never hands
               a joiner a departed island's identity
    """

    islands: IslandState
    pool: PoolState
    astate: Any
    key: Array
    epoch: Array
    stopped: Array
    stats: Any
    next_uuid: Array
    obs: Any = ()

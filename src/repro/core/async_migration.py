"""Asynchronous per-island migration runtime — NodIO without a global clock.

NodIO's defining property is *asynchrony*: volunteer islands evolve at
their own pace, join and leave at will, and exchange individuals through a
pool server with no epoch barrier. The synchronous drivers
(:mod:`repro.core.evolution` / :mod:`repro.core.sharded`) migrate in
lockstep; this module removes the barrier while keeping every island on
the same SPMD program:

* **Logical clocks + a volunteer-speed model.** Every island carries a
  clock and a per-island ``rate`` sampled from
  ``[AsyncConfig.min_rate, max_rate]`` (the paper's heterogeneous browsers
  — a phone accrues clock slower than a desktop). Each global *tick* the
  clock advances by the island's rate; when it crosses
  ``AsyncConfig.period`` the island *fires*: it evolves one autonomous
  epoch, emits its best, and absorbs immigrants. Non-firing islands are
  untouched that tick (masked dense compute — the SPMD-native encoding of
  "everyone runs at their own pace").
* **Staleness-bounded immigrant inbox.** A per-island on-device ring
  buffer (``inbox_capacity`` slots). Deliveries land in the destination's
  inbox stamped with their birth tick; the destination absorbs the best
  entry no older than ``staleness`` ticks at its *own* next fire, so a
  fast neighbour's emission waits for a slow island instead of forcing a
  barrier — and expires instead of going arbitrarily stale.
* **Churn.** ``churn_fraction`` of the islands get a seeded down-window
  (``available=False`` mid-run): a down island freezes — no evolution, no
  clock accrual, no exchange — then rejoins with its state intact (the
  paper's fault-tolerance experiment, Fig. 3).
* **Topology registry dispatch.** Exchange goes through
  :func:`repro.core.migration.migrate` with the per-island fire mask as
  the vector ``available`` — all five registered topologies (and any
  custom one honouring the vector contract) work asynchronously.
* **Generation-engine transparency.** The autonomous phase evolves through
  ``island_epoch`` -> ``ga.next_generation``, i.e. through the operator
  registry (``EAConfig.impl``): non-firing islands stay inert under the
  fused Pallas megakernel exactly as under the jnp path (the fire mask
  selects *states*, not ops — masked islands' kernel outputs are computed
  and discarded, the SPMD-native dense encoding).

**Correctness anchor:** in the degenerate configuration (all rates 1.0,
``staleness`` 0, no churn) every island fires every tick and the runtime
is bit-for-bit the synchronous driver — ``run_fused_async`` equals
``run_fused`` exactly, per topology (tests/test_async_migration.py).

Three driver contexts, mirroring PR 1:

* :func:`run_experiment_async` — host loop (churn injection via the seeded
  schedule, pool-server failure via ``server_up``, non-blocking
  :class:`AsyncHostBridge` sync).
* :func:`run_fused_async` — the whole run as one ``lax.scan`` with the
  per-island fire mask carried through the scan.
* :func:`repro.core.sharded.run_fused_sharded_async` — the same scan body
  inside ``shard_map`` (islands + their async state sharded, pool
  replicated).
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
from functools import partial
from typing import Callable, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import counters as obs_lib
from repro.obs import trace as obs_trace

from . import acceptance as acceptance_lib
from . import evolution as evolution_lib
from . import island as island_lib
from . import migration as migration_lib
from . import pool as pool_lib
from .evolution import (RunResult, bcast_mask, collect_stats, fused_jit,
                        success_mask, unique_buffers)
from .pool import NEG_INF
from .problems import Problem
from .types import (Array, EAConfig, ExperimentState, ExperimentStats,
                    IslandState, MigrationConfig, PoolState)


# ---------------------------------------------------------------------------
# Configuration + state
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class AsyncConfig:
    """Volunteer-speed, staleness and churn policy (static / hashable).

    rate ~ U[min_rate, max_rate] per island, in clock units per tick;
    period is the clock budget of one autonomous epoch. With
    min_rate = max_rate = period = 1 every island fires every tick (the
    synchronous degenerate configuration). staleness is the maximum age in
    ticks an inbox immigrant stays absorbable (0 = same-tick only).
    churn_fraction of islands get one seeded down-window inside
    [churn_window[0], churn_window[1]) x max_ticks.
    """

    period: float = 1.0
    min_rate: float = 1.0
    max_rate: float = 1.0
    staleness: int = 0
    inbox_capacity: int = 4
    churn_fraction: float = 0.0
    churn_window: Tuple[float, float] = (0.25, 0.75)
    seed: int = 0

    def __post_init__(self):
        if not (0.0 < self.min_rate <= self.max_rate <= 1.0):
            raise ValueError("need 0 < min_rate <= max_rate <= 1")
        if self.inbox_capacity < 1:
            raise ValueError("inbox_capacity must be >= 1")
        if self.staleness < 0:
            raise ValueError("staleness must be >= 0")

    @property
    def degenerate(self) -> bool:
        """True when this config is the synchronous anchor."""
        return (self.min_rate == self.max_rate == self.period == 1.0
                and self.churn_fraction == 0.0)


class AsyncState(NamedTuple):
    """Per-island asynchrony state (leading axis = islands; a pytree).

    clock/rate:            () per island — logical clock + volunteer speed
    down_start/down_end:   () per island — churn window in ticks
                           (start > every tick => never churns)
    inbox_genomes:         (C, L) per island — immigrant ring buffer
    inbox_fitness:         (C,)   per island — -inf marks an empty slot
    inbox_born:            (C,)   per island — birth tick (-1 = empty)
    inbox_ptr:             ()     per island — next write slot
    fires:                 ()     per island — cumulative fire count
    """

    clock: Array
    rate: Array
    down_start: Array
    down_end: Array
    inbox_genomes: Array
    inbox_fitness: Array
    inbox_born: Array
    inbox_ptr: Array
    fires: Array


def init_async_state(rng: Array, n_islands: int, acfg: AsyncConfig,
                     max_ticks: int, genome) -> AsyncState:
    """Sample the volunteer-speed model and the seeded churn schedule."""
    k_rate, k_who, k_start, k_dur = jax.random.split(
        jax.random.fold_in(rng, acfg.seed), 4)
    if acfg.min_rate == acfg.max_rate:
        # exact value — the degenerate anchor must accrue 1.0 per tick
        rate = jnp.full((n_islands,), acfg.min_rate, jnp.float32)
    else:
        rate = jax.random.uniform(k_rate, (n_islands,), jnp.float32,
                                  acfg.min_rate, acfg.max_rate)
    lo = max(1, int(acfg.churn_window[0] * max_ticks))
    hi = max(lo + 1, int(acfg.churn_window[1] * max_ticks))
    churned = jax.random.uniform(k_who, (n_islands,)) < acfg.churn_fraction
    start = jax.random.randint(k_start, (n_islands,), lo, hi, jnp.int32)
    dur = jax.random.randint(k_dur, (n_islands,), 1,
                             max(2, (hi - lo)), jnp.int32)
    never = jnp.int32(max_ticks + 1)
    down_start = jnp.where(churned, start, never)
    cap = int(acfg.inbox_capacity)
    length = genome.length
    return AsyncState(
        clock=jnp.zeros((n_islands,), jnp.float32),
        rate=rate,
        down_start=down_start,
        down_end=jnp.where(churned, start + dur, never),
        inbox_genomes=jnp.zeros((n_islands, cap, length), genome.dtype),
        inbox_fitness=jnp.full((n_islands, cap), NEG_INF, jnp.float32),
        inbox_born=jnp.full((n_islands, cap), -1, jnp.int32),
        inbox_ptr=jnp.zeros((n_islands,), jnp.int32),
        fires=jnp.zeros((n_islands,), jnp.int32),
    )


def _select(mask: Array, new, old):
    """Per-island tree select (mask broadcast over trailing dims)."""
    return jax.tree.map(
        lambda a, b: jnp.where(bcast_mask(mask, a.ndim), a, b), new, old)


# ---------------------------------------------------------------------------
# Inbox ring buffer
# ---------------------------------------------------------------------------
def _inbox_push(astate: AsyncState, imm_g: Array, imm_f: Array,
                tick: Array) -> AsyncState:
    """Stamp this tick's valid deliveries into the destination inboxes."""
    push = jnp.isfinite(imm_f)
    n, cap = astate.inbox_fitness.shape
    rows = jnp.arange(n)
    slot = astate.inbox_ptr
    new_g = astate.inbox_genomes.at[rows, slot].set(
        imm_g.astype(astate.inbox_genomes.dtype))
    new_f = astate.inbox_fitness.at[rows, slot].set(imm_f)
    new_b = astate.inbox_born.at[rows, slot].set(
        jnp.asarray(tick, jnp.int32))
    return astate._replace(
        inbox_genomes=jnp.where(push[:, None, None], new_g,
                                astate.inbox_genomes),
        inbox_fitness=jnp.where(push[:, None], new_f, astate.inbox_fitness),
        inbox_born=jnp.where(push[:, None], new_b, astate.inbox_born),
        inbox_ptr=(astate.inbox_ptr + push.astype(jnp.int32)) % cap,
    )


def _inbox_take(astate: AsyncState, tick: Array, staleness: int,
                absorb: Array, with_ledger: bool = False):
    """Best live (age <= staleness) entry per absorbing island; consumed
    entries are cleared so nothing is absorbed twice.

    ``with_ledger=True`` appends ``(consumed, take_age)`` to the return —
    the per-island absorbed mask and the age in ticks of each absorbed
    entry (observability's inbox-staleness histogram)."""
    age = jnp.asarray(tick, jnp.int32) - astate.inbox_born
    live = ((astate.inbox_born >= 0) & (age >= 0) & (age <= staleness)
            & jnp.isfinite(astate.inbox_fitness))
    cand = jnp.where(live, astate.inbox_fitness, NEG_INF)
    n, cap = cand.shape
    rows = jnp.arange(n)
    j = jnp.argmax(cand, axis=1)
    take_f = jnp.where(absorb, cand[rows, j], NEG_INF)
    take_g = astate.inbox_genomes[rows, j]
    consumed = absorb & jnp.isfinite(take_f)
    cleared = (consumed[:, None] & (jnp.arange(cap)[None, :] == j[:, None]))
    astate = astate._replace(
        inbox_fitness=jnp.where(cleared, NEG_INF, astate.inbox_fitness),
        inbox_born=jnp.where(cleared, -1, astate.inbox_born),
    )
    if with_ledger:
        return take_g, take_f, astate, consumed, age[rows, j]
    return take_g, take_f, astate


# ---------------------------------------------------------------------------
# One asynchronous tick
# ---------------------------------------------------------------------------
def async_step(islands: IslandState, pool: PoolState, astate: AsyncState,
               rng: Array, problem: Problem, cfg: EAConfig,
               mig: MigrationConfig, acfg: AsyncConfig, w2: bool,
               server_up: Array | bool = True, tick: Array | int = 0,
               axis: Optional[str] = None, obs=None):
    """One global tick: clocks accrue, firing islands evolve an epoch and
    exchange through the topology registry, everyone else is untouched.

    ``server_up=False`` loses the whole exchange (the paper's dead pool
    server) without stopping local evolution or clock accrual; churned-down
    islands additionally freeze entirely. In the degenerate config this is
    exactly :func:`repro.core.evolution.epoch_step`.

    ``obs`` (an :class:`~repro.obs.counters.ObsCounters`) switches on the
    counter ledger — churn down-ticks, the delivery ledger and the absorb
    age histogram — and appends it to the return tuple.
    """
    tick = jnp.asarray(tick, jnp.int32)
    up = ~((astate.down_start <= tick) & (tick < astate.down_end))
    clock = astate.clock + jnp.where(up, astate.rate, 0.0)
    fire = up & (clock >= acfg.period)
    clock = jnp.where(fire, clock - acfg.period, clock)

    if obs is not None:
        obs = obs_lib.record_churn(obs, ~up)

    # autonomous phase — only firing islands advance (their own rng stream)
    evolved = jax.vmap(
        lambda s: island_lib.island_epoch(s, problem, cfg))(islands)
    islands = _select(fire, evolved, islands)

    # exchange: the fire mask is the topology's vector availability
    exchange = fire & jnp.asarray(server_up)
    if obs is not None:
        pool, imm_g, imm_f, delivered, accepted = migration_lib.migrate(
            pool, islands.best_genome, islands.best_fitness, rng, mig,
            axis=axis, epoch=tick, available=exchange, with_ledger=True)
        obs = obs_lib.record_exchange(obs, exchange, delivered, accepted)
    else:
        pool, imm_g, imm_f = migration_lib.migrate(
            pool, islands.best_genome, islands.best_fitness, rng, mig,
            axis=axis, epoch=tick, available=exchange)

    # deliveries land in the destination inbox; absorption happens at the
    # destination's own fire (staleness-bounded)
    astate = _inbox_push(astate, imm_g, imm_f, tick)
    if obs is not None:
        take_g, take_f, astate, consumed, take_age = _inbox_take(
            astate, tick, acfg.staleness, fire, with_ledger=True)
        obs = obs_lib.record_absorb(obs, consumed, take_age)
    else:
        take_g, take_f, astate = _inbox_take(astate, tick, acfg.staleness,
                                             fire)
    # re-gate at absorb: an entry accepted at delivery time may have gone
    # stale relative to the island's *current* best by its absorb tick.
    # Deterministic policies make this idempotent, so the degenerate
    # config (same-tick absorb) stays bit-for-bit the sync driver.
    acc = getattr(mig, "acceptance", None)
    if acc is not None and acc.policy != "always":
        take_f = acceptance_lib.gate_immigrants(
            islands.best_genome, islands.best_fitness, take_g, take_f,
            jax.random.fold_in(rng, 0xAB50), acc)
    received = jax.vmap(
        partial(island_lib.receive_immigrant, replace=mig.replace)
    )(islands, take_g, take_f)
    islands = _select(fire, received, islands)

    if w2:
        succeeded = fire & success_mask(islands, problem, cfg)
        restarted = jax.vmap(
            lambda s: island_lib.restart_island(s, problem, cfg))(islands)
        islands = _select(succeeded, restarted, islands)

    astate = astate._replace(clock=clock,
                             fires=astate.fires + fire.astype(jnp.int32))
    if obs is not None:
        return islands, pool, astate, obs
    return islands, pool, astate


# ---------------------------------------------------------------------------
# Host-level async driver (faithful NodIO shape: churn + server failure +
# non-blocking host bridge live in the host loop)
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class AsyncRunResult(RunResult):
    astate: Optional[AsyncState] = None
    total_fires: int = 0


def run_experiment_async(problem: Problem,
                         cfg: EAConfig = EAConfig(),
                         mig: MigrationConfig = MigrationConfig(),
                         acfg: AsyncConfig = AsyncConfig(),
                         n_islands: int = 8,
                         max_ticks: int = 100,
                         rng: Optional[Array] = None,
                         w2: bool = False,
                         server_up: Optional[Callable[[int], bool]] = None,
                         host_bridge=None,
                         stop_on_success: bool = True,
                         verbose: bool = False) -> AsyncRunResult:
    """Asynchronous :func:`repro.core.evolution.run_experiment`.

    Same contract, but epochs are *ticks*: each island fires on its own
    clock (``acfg``), so a tick advances only the islands whose clock
    crossed the period. ``host_bridge`` accepts a blocking
    :class:`~repro.core.migration.HostBridge` or the non-blocking
    :class:`AsyncHostBridge` (server I/O off the driver thread).
    """
    rng = jax.random.key(0) if rng is None else rng
    k_init, rng = jax.random.split(rng)
    islands = island_lib.init_islands(k_init, n_islands, problem, cfg)
    dpool = pool_lib.pool_init(mig.pool_capacity, problem.genome)
    astate = init_async_state(jax.random.fold_in(k_init, 7), n_islands,
                              acfg, max_ticks, problem.genome)

    step = jax.jit(partial(async_step, problem=problem, cfg=cfg, mig=mig,
                           acfg=acfg, w2=w2))
    stats: List[ExperimentStats] = []
    t0 = time.perf_counter()
    success = False
    evals_at_solution = None
    tick = 0
    for tick in range(1, max_ticks + 1):
        rng, k_mig = jax.random.split(rng)
        up = True if server_up is None else bool(server_up(tick))
        islands, dpool, astate = step(islands, dpool, astate, k_mig,
                                      server_up=up, tick=tick)
        if host_bridge is not None:
            dpool = host_bridge.sync(dpool, tick)

        st = jax.tree.map(lambda x: np.asarray(x),
                          collect_stats(islands, tick))
        stats.append(st)
        if verbose:
            n_fired = int(np.asarray(astate.fires).sum())
            print(f"tick {tick}: best={st.best_fitness:.4f} "
                  f"evals={int(st.total_evaluations)} "
                  f"fires={n_fired} server={'up' if up else 'DOWN'}")
        succeeded_now = bool(np.asarray(
            success_mask(islands, problem, cfg)).any()) or (
                w2 and int(st.experiments_solved) > 0)
        if succeeded_now and not success:
            success = True
            evals_at_solution = int(st.total_evaluations)
        if success and stop_on_success and not w2:
            break

    return AsyncRunResult(
        islands=islands, pool=dpool, stats=stats, success=success,
        epochs=tick, wall_time_s=time.perf_counter() - t0,
        evaluations=int(np.asarray(islands.evaluations).sum()),
        evaluations_to_solution=evals_at_solution,
        astate=astate, total_fires=int(np.asarray(astate.fires).sum()))


# ---------------------------------------------------------------------------
# Fused async driver: the fire mask carried through one lax.scan
# ---------------------------------------------------------------------------
def fused_scan_async(islands: IslandState, pool: PoolState,
                     astate: AsyncState, key: Array,
                     tick0: Array | int = 0, stopped0: Array | bool = False,
                     obs0=(), *, problem: Problem, cfg: EAConfig,
                     mig: MigrationConfig, acfg: AsyncConfig,
                     w2: bool, max_ticks: int, axis: Optional[str] = None,
                     with_stats: bool = True):
    """``max_ticks`` ticks of the asynchronous experiment as one
    ``lax.scan`` — the async mirror of
    :func:`repro.core.evolution.fused_scan` (same key schedule, same
    early-stop freeze, same stats stacking), with the per-island
    clocks/fire-mask/inbox carried through the scan. Like its sync mirror
    this is a resumable *segment*: the full carry (islands, pool, astate,
    key, tick, stopped) enters as arguments and leaves as results, so
    chained segments are bit-for-bit one long scan
    (:func:`repro.core.evolution.run_segments`).  ``obs0`` — an
    :class:`~repro.obs.counters.ObsCounters` to accumulate through the
    carry (``()`` disables); returned in the slot before ``stats``."""
    with_obs = hasattr(obs0, "_fields")

    def _global_success(islands: IslandState) -> Array:
        s = success_mask(islands, problem, cfg).any()
        if axis is not None:
            s = jax.lax.psum(s.astype(jnp.int32), axis) > 0
        return s

    def body(carry, _):
        islands, pool, astate, key, tick, stopped, obs = carry
        key, k_mig = jax.random.split(key)

        def live(args):
            i, p, a, o = args
            # tick + 1: match the host drivers' 1-based tick numbers
            if with_obs:
                return async_step(i, p, a, k_mig, problem, cfg, mig, acfg,
                                  w2, server_up=True, tick=tick + 1,
                                  axis=axis, obs=o)
            i, p, a = async_step(i, p, a, k_mig, problem, cfg, mig, acfg,
                                 w2, server_up=True, tick=tick + 1,
                                 axis=axis)
            return i, p, a, o

        islands, pool, astate, obs = jax.lax.cond(
            stopped, lambda a: a, live, (islands, pool, astate, obs))
        tick = jnp.where(stopped, tick, tick + 1)
        if not w2:
            stopped = stopped | _global_success(islands)
        if with_obs:
            obs = obs_lib.record_early_stop(obs, stopped, tick)
        stats = collect_stats(islands, tick, axis=axis) if with_stats else ()
        return (islands, pool, astate, key, tick, stopped, obs), stats

    stopped0 = jnp.asarray(stopped0)
    if not w2:
        # idempotent re-latch: fresh runs test the init population, resumed
        # segments OR with the restored latch (same value either way)
        stopped0 = stopped0 | _global_success(islands)
    init = (islands, pool, astate, key, jnp.asarray(tick0, jnp.int32),
            stopped0, obs0)
    (islands, pool, astate, key, ticks, stopped, obs), stats = jax.lax.scan(
        body, init, None, length=max_ticks)
    return islands, pool, astate, key, ticks, stopped, obs, stats


def run_fused_async(problem: Problem,
                    cfg: EAConfig = EAConfig(),
                    mig: MigrationConfig = MigrationConfig(),
                    acfg: AsyncConfig = AsyncConfig(),
                    n_islands: int = 8,
                    max_ticks: int = 100,
                    rng: Optional[Array] = None,
                    w2: bool = False,
                    return_stats: bool = False,
                    return_astate: bool = False,
                    return_obs: bool = False,
                    snapshot_every: Optional[int] = None,
                    snapshot_dir: Optional[str] = None,
                    snapshot_keep: int = 3,
                    checkpointer=None,
                    resume: bool = False):
    """Asynchronous :func:`repro.core.evolution.run_fused`: jitted
    ``lax.scan`` segments with donated island/pool/async buffers. In the
    degenerate ``acfg`` the result is bit-for-bit :func:`run_fused`'s.
    Durability kwargs behave exactly as in :func:`run_fused` — the
    snapshot additionally carries the :class:`AsyncState` (clocks, churn
    windows, inbox), and an elastic resume gives grown islands
    churn-rejoin async rows (fresh clock, never-churn window)."""
    rng = jax.random.key(0) if rng is None else rng
    ckpt = evolution_lib.resolve_checkpointer(snapshot_dir, checkpointer,
                                              snapshot_keep)

    def fresh_state(n: int) -> ExperimentState:
        # the sync driver's compiled set-up: degenerate async == run_fused
        state, k_init = evolution_lib.fresh_experiment_state(
            problem, cfg, mig, n, rng, return_obs)
        astate0 = init_async_state(jax.random.fold_in(k_init, 7), n,
                                   acfg, max_ticks, problem.genome)
        return state._replace(
            astate=astate0,
            stats=evolution_lib.empty_stats() if return_stats else ())

    state = None
    if resume:
        if ckpt is None:
            raise ValueError("resume=True needs snapshot_dir or checkpointer")
        state = evolution_lib.restore_experiment_state(
            ckpt, fresh_state(n_islands))
        if int(state.islands.pop.shape[0]) != n_islands:
            from repro.runtime import elastic as elastic_lib  # deferred: avoid cycle
            state = elastic_lib.resize_experiment(state, n_islands, problem,
                                                  cfg)
    if state is None:
        state = fresh_state(n_islands)

    def segment_fn(state: ExperimentState, seg_len: int):
        run = fused_jit(
            problem,
            ("async", cfg, mig, acfg, w2, seg_len, return_stats,
             return_obs),
            lambda: jax.jit(partial(fused_scan_async, problem=problem,
                                    cfg=cfg, mig=mig, acfg=acfg, w2=w2,
                                    max_ticks=seg_len,
                                    with_stats=return_stats),
                            donate_argnums=(0, 1, 2)))
        islands, pool, astate = unique_buffers(
            (state.islands, state.pool, state.astate))
        islands, pool, astate, key, tick, stopped, obs, seg_stats = run(
            islands, pool, astate, state.key, state.epoch, state.stopped,
            state.obs)
        return state._replace(islands=islands, pool=pool, astate=astate,
                              key=key, epoch=tick, stopped=stopped,
                              obs=obs), seg_stats

    state = evolution_lib.run_segments(
        state, max_ticks, segment_fn, snapshot_every=snapshot_every,
        checkpointer=ckpt, w2=w2, return_stats=return_stats)
    out = (state.islands, state.pool, state.epoch)
    if return_stats:
        out += (state.stats,)
    if return_astate:
        out += (state.astate,)
    if return_obs:
        out += (obs_lib.harvest(state.obs),)
    return out


# ---------------------------------------------------------------------------
# Non-blocking host bridge: server I/O off the driver thread
# ---------------------------------------------------------------------------
class AsyncHostBridge(migration_lib.HostBridge):
    """A :class:`~repro.core.migration.HostBridge` whose server round-trips
    run on a daemon worker thread — the device driver never blocks on the
    pool server (a browser island's async XHR).

    ``sync`` (non-blocking) does two things: (a) applies whatever
    immigrants the worker fetched since the last call to the device pool,
    (b) enqueues this tick's best-out + a fetch job and returns
    immediately. Delivery is *exactly-once*: the worker drains the server
    with :meth:`~repro.core.async_pool.PoolServer.get_since` (a
    monotonically advancing sequence cursor), so each server entry enters
    the device pool at most once, and the bridge's own pushes are never
    echoed back. Server loss is tolerated and counted, like any lost XHR.

    When puts outpace the drain the server's ring eviction can retire
    entries the cursor never reached; ``get_since`` detects and counts
    them, and the bridge accumulates the tally in ``self.dropped``
    (surfaced by :meth:`stats`) — overflow demotes exactly-once to
    *detected* at-most-once instead of silent loss.

    ``cursor_id`` names a server-side cursor
    (:meth:`~repro.core.async_pool.PoolServer.get_since`): with it set, the
    drain position survives the death of *either* end — a restarted bridge
    resumes from the server's stored cursor instead of re-reading the whole
    pool, and a journal-rehydrated server restores the stored cursor on
    replay, so exactly-once holds across both restarts.

    Like the parent, ``server`` may be a URL string — the worker then
    speaks the JSON wire protocol to a networked service through
    :class:`~repro.server.client.RemotePoolServer`. The cursor the worker
    threads through ``get_since`` is opaque (``-1`` cold): in-process it
    is the server's int sequence, over the wire it is the service's
    per-shard cursor vector; the exactly-once contract is identical, and
    the in-process path is bit-for-bit unchanged.

    :meth:`flush` blocks until the worker has drained the job queue —
    tests and orderly shutdown only; the driver never needs it.
    """

    def __init__(self, server, pull: int = 4, uuid: int = -1,
                 acceptance=None, cursor_id: Optional[str] = None,
                 experiment: str = "default"):
        super().__init__(server, every=1, pull=pull, uuid=uuid,
                         acceptance=acceptance, experiment=experiment)
        self._jobs: "queue.Queue" = queue.Queue()
        self._fetched: List[Tuple[np.ndarray, float]] = []
        self._flock = threading.Lock()
        self._last_seq = -1
        self._cursor_id = cursor_id
        self._absorbs = 0
        self.dropped = 0
        self._stop = threading.Event()
        self._worker = threading.Thread(target=self._run, daemon=True)
        self._worker.start()

    # -- worker side ---------------------------------------------------------
    def _run(self):
        while True:
            job = self._jobs.get()
            if job is None:
                self._jobs.task_done()
                return
            genome, fitness = job
            try:
                if genome is not None:
                    with obs_trace.span("bridge.put"):
                        self.server.put(genome, fitness, uuid=self.uuid)
                    with self._flock:
                        self.pushed += 1
                # read the cursor under the lock, do server I/O outside
                # it, publish results under it — the driver thread reads
                # every one of these through stats()/_absorb_fetched
                with self._flock:
                    cursor = self._last_seq
                with obs_trace.span("bridge.drain"):
                    entries, cursor, dropped = self.server.get_since(
                        cursor, limit=self.pull, cursor_id=self._cursor_id)
                fresh = [(e.genome.copy(), e.fitness) for e in entries
                         if e.uuid != self.uuid]
                with self._flock:
                    self._last_seq = cursor
                    self.dropped += dropped
                    if fresh:
                        self._fetched.extend(fresh)
            except Exception:  # noqa: BLE001 — any server-side failure is a
                # lost XHR: count it and keep the worker alive (a dead
                # worker would deadlock flush() on the unjoined queue)
                with self._flock:
                    self.lost += 1
            finally:
                self._jobs.task_done()

    # -- driver side ---------------------------------------------------------
    def _absorb_fetched(self, pool: PoolState) -> PoolState:
        with self._flock:
            got, self._fetched = self._fetched, []
        if got:
            self._absorbs += 1
            pool = pool_lib.pool_insert_host(
                pool, [g for g, _ in got], [f for _, f in got],
                acc=self.acceptance,
                rng=jax.random.fold_in(jax.random.key(17), self._absorbs))
            self.pulled += len(got)
        return pool

    def sync(self, pool: PoolState, epoch: int = 0) -> PoolState:
        """Absorb fetched immigrants, enqueue best-out + fetch; never waits
        on the server."""
        with obs_trace.span("bridge.sync", epoch=int(epoch)):
            pool = self._absorb_fetched(pool)
            if int(np.asarray(pool.count)) > 0:
                g, f = pool_lib.pool_best(pool)
                self._jobs.put((np.asarray(g), float(f)))
            else:
                self._jobs.put((None, 0.0))
        return pool

    def flush(self, pool: PoolState) -> PoolState:
        """Drain the worker, then absorb anything it fetched (blocking)."""
        self._jobs.join()
        return self._absorb_fetched(pool)

    def stats(self):
        with self._flock:
            out = super().stats()
            out["dropped"] = self.dropped
        return out

    def close(self):
        if self._worker.is_alive():
            self._jobs.put(None)
            self._worker.join(timeout=5.0)

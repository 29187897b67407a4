"""The NodIO experiment loop: islands × pool, epochs of autonomous evolution.

Two drivers:

* :func:`run_experiment` — host-level loop around a jitted
  ``(epoch + migrate)`` step. This is the faithful NodIO shape: the host loop
  is where volunteer churn, server failure, host-pool interop and logging
  live (exactly the concerns the paper handles over HTTP).
* :func:`run_fused` — the whole experiment as one ``lax.scan`` over epochs:
  donated island/pool buffers, per-epoch stats stacked on device, one
  compile per (problem, config, topology). Maximum device throughput (the
  "all islands on one pod" configuration); used by the performance
  benchmarks. The same scan body runs inside ``shard_map`` for the SPMD
  variant (see :func:`repro.core.sharded.run_fused_sharded`).

Both operate on a *batch* of islands (leading axis) and support the W²
variant: restart-on-solution + heterogeneous population sizes. Migration
is dispatched through the pluggable topology registry
(:mod:`repro.core.migration` — selected by ``MigrationConfig.topology``).
The per-generation hot path inside every epoch dispatches through the
operator-kernel registry (:mod:`repro.kernels.ga` — selected by
``EAConfig.impl``): since ``cfg`` is a static jit argument, each impl
(classic jnp / fused Pallas megakernel / its oracle) gets its own compiled
driver via ``fused_jit`` with no driver-side branching.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from functools import partial
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import counters as obs_lib
from repro.obs import trace as obs_trace

from . import island as island_lib
from . import migration as migration_lib
from . import pool as pool_lib
from .problems import Problem
from .types import (Array, EAConfig, ExperimentState, ExperimentStats,
                    IslandState, MigrationConfig, PoolState)


# ---------------------------------------------------------------------------
# One epoch: autonomous evolution + topology migration (+ W² restart)
# ---------------------------------------------------------------------------
def epoch_step(islands: IslandState, pool: PoolState, rng: Array,
               problem: Problem, cfg: EAConfig, mig: MigrationConfig,
               w2: bool, available: Array | bool, epoch: Array | int = 0,
               axis: Optional[str] = None, obs=None):
    """One epoch for a batch of islands. ``axis=None`` runs batched on one
    shard; with a mesh axis name the call must execute inside ``shard_map``
    and migration uses collectives over that axis.

    ``obs`` (an :class:`~repro.obs.counters.ObsCounters`) switches on the
    on-device counter ledger: the return grows to ``(islands, pool, obs)``
    and migration runs ``with_ledger`` so delivered/accepted/rejected
    balance exactly.  ``obs=None`` (the default) is the legacy 2-tuple."""
    islands = jax.vmap(lambda s: island_lib.island_epoch(s, problem, cfg))(islands)

    if obs is not None:
        pool, imm_g, imm_f, delivered, accepted = migration_lib.migrate(
            pool, islands.best_genome, islands.best_fitness, rng, mig,
            axis=axis, epoch=epoch, available=available, with_ledger=True)
        n = islands.best_fitness.shape[0]
        fired = jnp.broadcast_to(jnp.asarray(available), (n,))
        obs = obs_lib.record_exchange(obs, fired, delivered, accepted)
        # the sync driver absorbs at delivery: every accepted immigrant
        # enters the island the same epoch — age 0 by definition
        obs = obs_lib.record_absorb(obs, accepted,
                                    jnp.zeros((n,), jnp.int32))
    else:
        pool, imm_g, imm_f = migration_lib.migrate(
            pool, islands.best_genome, islands.best_fitness, rng, mig,
            axis=axis, epoch=epoch, available=available)
    islands = jax.vmap(
        partial(island_lib.receive_immigrant, replace=mig.replace)
    )(islands, imm_g, imm_f)

    if w2:
        succeeded = _success_mask(islands, problem, cfg)
        restarted = jax.vmap(
            lambda s: island_lib.restart_island(s, problem, cfg))(islands)
        islands = jax.tree.map(
            lambda r, o: jnp.where(
                _bcast(succeeded, r.ndim), r, o), restarted, islands)
    if obs is not None:
        return islands, pool, obs
    return islands, pool


def _bcast(mask: Array, ndim: int) -> Array:
    return mask.reshape(mask.shape + (1,) * (ndim - 1))


def _success_mask(islands: IslandState, problem: Problem,
                  cfg: EAConfig) -> Array:
    if problem.optimum is None:
        return jnp.zeros_like(islands.done)
    return islands.best_fitness >= problem.optimum - cfg.success_eps


# Public names for sibling driver modules (core.async_migration rebuilds
# the epoch from these pieces — sharing them is what makes the degenerate
# async configuration bit-for-bit equal to this driver).
bcast_mask = _bcast
success_mask = _success_mask


def collect_stats(islands: IslandState, epoch: Array | int,
                  axis: Optional[str] = None) -> ExperimentStats:
    """Per-epoch record. Under SPMD (``axis`` given, inside shard_map) the
    reductions are finished with psum/pmax so every shard returns the same
    *global* stats (replicated output)."""
    best = islands.best_fitness.max()
    mean = islands.best_fitness.mean()
    evals = islands.evaluations.sum()
    n_done = islands.done.sum()
    solved = islands.experiments.sum()
    if axis is not None:
        n_shards = jax.lax.axis_size(axis)
        best = jax.lax.pmax(best, axis)
        mean = jax.lax.psum(mean, axis) / n_shards  # equal n_local per shard
        evals = jax.lax.psum(evals, axis)
        n_done = jax.lax.psum(n_done, axis)
        solved = jax.lax.psum(solved, axis)
    return ExperimentStats(
        epoch=jnp.asarray(epoch, jnp.int32),
        best_fitness=best,
        mean_best=mean,
        total_evaluations=evals,
        n_done=n_done,
        experiments_solved=solved,
    )


# ---------------------------------------------------------------------------
# Host-level driver (faithful NodIO shape)
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class RunResult:
    islands: IslandState
    pool: PoolState
    stats: List[ExperimentStats]
    success: bool
    epochs: int
    wall_time_s: float
    evaluations: int
    # evaluations summed over islands at the first epoch with a success
    evaluations_to_solution: Optional[int] = None


def run_experiment(problem: Problem,
                   cfg: EAConfig = EAConfig(),
                   mig: MigrationConfig = MigrationConfig(),
                   n_islands: int = 8,
                   max_epochs: int = 100,
                   rng: Optional[Array] = None,
                   w2: bool = False,
                   server_up: Optional[Callable[[int], bool]] = None,
                   host_pool=None,
                   host_bridge: Optional[migration_lib.HostBridge] = None,
                   stop_on_success: bool = True,
                   verbose: bool = False) -> RunResult:
    """Run a NodIO experiment.

    server_up(epoch) -> bool lets tests/benchmarks kill the pool server for
    arbitrary epochs (paper §2, fault tolerance). ``host_pool`` (a
    core.async_pool.PoolServer) — when given, migration additionally goes
    through the host REST-semantics pool, mixing device islands with any
    external volunteer clients attached to the same server.
    ``host_bridge`` (a core.migration.HostBridge) — two-way sync: the device
    pool's best is PUT to the bridged PoolServer and server entries (e.g.
    volunteer contributions) are pulled into the device pool as immigrants.
    """
    rng = jax.random.key(0) if rng is None else rng
    k_init, rng = jax.random.split(rng)
    islands = island_lib.init_islands(k_init, n_islands, problem, cfg)
    dpool = pool_lib.pool_init(mig.pool_capacity, problem.genome)

    step = jax.jit(partial(epoch_step, problem=problem, cfg=cfg, mig=mig,
                           w2=w2))
    stats: List[ExperimentStats] = []
    t0 = time.perf_counter()
    success = False
    evals_at_solution = None
    epoch = 0
    for epoch in range(1, max_epochs + 1):
        rng, k_mig = jax.random.split(rng)
        up = True if server_up is None else bool(server_up(epoch))
        islands, dpool = step(islands, dpool, k_mig, available=up,
                              epoch=epoch)

        if host_pool is not None and up:
            _host_pool_exchange(host_pool, islands)
        if host_bridge is not None:
            dpool = host_bridge.sync(dpool, epoch)

        st = jax.tree.map(lambda x: np.asarray(x), collect_stats(islands, epoch))
        stats.append(st)
        if verbose:
            print(f"epoch {epoch}: best={st.best_fitness:.4f} "
                  f"evals={int(st.total_evaluations)} done={int(st.n_done)} "
                  f"solved={int(st.experiments_solved)} server={'up' if up else 'DOWN'}")
        succeeded_now = bool(np.asarray(
            _success_mask(islands, problem, cfg)).any()) or (
                w2 and int(st.experiments_solved) > 0)
        if succeeded_now and not success:
            success = True
            evals_at_solution = int(st.total_evaluations)
        if success and stop_on_success and not w2:
            break

    return RunResult(
        islands=islands, pool=dpool, stats=stats, success=success,
        epochs=epoch, wall_time_s=time.perf_counter() - t0,
        evaluations=int(np.asarray(islands.evaluations).sum()),
        evaluations_to_solution=evals_at_solution)


def _host_pool_exchange(host_pool, islands: IslandState) -> None:
    """Mirror device-island bests into the host PoolServer (PUT) and account
    external immigrants (GET) — best-effort; failures are swallowed exactly
    like a browser client losing its XHR."""
    try:
        bests = np.asarray(islands.best_genome)
        fits = np.asarray(islands.best_fitness)
        uuids = np.asarray(islands.uuid)
        for g, f, u in zip(bests, fits, uuids):
            host_pool.put(g, float(f), uuid=int(u))
    except Exception:  # noqa: BLE001 — server down is a tolerated condition
        pass


# ---------------------------------------------------------------------------
# Fully fused driver (lax.scan — benchmark configuration)
# ---------------------------------------------------------------------------
def fused_scan(islands: IslandState, pool: PoolState, key: Array,
               epoch0: Array | int = 0, stopped0: Array | bool = False,
               obs0=(), *,
               problem: Problem, cfg: EAConfig, mig: MigrationConfig,
               w2: bool, max_epochs: int, axis: Optional[str] = None,
               with_stats: bool = True):
    """``max_epochs`` epochs of the experiment as one ``lax.scan`` — a
    resumable *segment*: the whole scan carry (islands, pool, key, epoch,
    stopped) enters as arguments and leaves as results, so chaining
    segments is bit-for-bit one long scan (the segmented snapshot drivers
    rely on exactly this identity; see :func:`run_segments`).

    Per-epoch :class:`ExperimentStats` are stacked on device (shape
    ``(max_epochs, ...)``) — no host round-trip per epoch. Early success
    (non-W²) freezes the carry via ``lax.cond`` so the remaining iterations
    are skipped at device speed; ``epoch`` counts the live ones and the
    stats rows after a stop repeat the frozen final state. With ``axis``
    the same body runs inside ``shard_map``: the success test and the stats
    reductions finish with psum/pmax so every shard agrees.
    ``with_stats=False`` skips stats entirely (returning ``()`` in their
    place) — under SPMD that avoids the per-epoch psum/pmax scalar
    collectives when the caller would discard them anyway.

    ``obs0`` — an :class:`~repro.obs.counters.ObsCounters` to accumulate
    through the carry (``()`` disables, the default; the flag is static
    via the pytree structure).  Returned in the slot before ``stats``.
    """
    with_obs = hasattr(obs0, "_fields")

    def _global_success(islands: IslandState) -> Array:
        s = _success_mask(islands, problem, cfg).any()
        if axis is not None:
            s = jax.lax.psum(s.astype(jnp.int32), axis) > 0
        return s

    def body(carry, _):
        islands, pool, key, epoch, stopped, obs = carry
        key, k_mig = jax.random.split(key)

        def live(args):
            i, p, o = args
            # epoch + 1: match the host-loop drivers' 1-based epoch numbers
            # (torus alternates direction on epoch parity)
            if with_obs:
                return epoch_step(i, p, k_mig, problem, cfg, mig, w2, True,
                                  epoch=epoch + 1, axis=axis, obs=o)
            i, p = epoch_step(i, p, k_mig, problem, cfg, mig, w2, True,
                              epoch=epoch + 1, axis=axis)
            return i, p, o

        islands, pool, obs = jax.lax.cond(stopped, lambda a: a, live,
                                          (islands, pool, obs))
        epoch = jnp.where(stopped, epoch, epoch + 1)
        if not w2:
            stopped = stopped | _global_success(islands)
        if with_obs:
            # outside the freeze cond and idempotent: latches the first
            # stopping epoch, no-ops forever after
            obs = obs_lib.record_early_stop(obs, stopped, epoch)
        stats = collect_stats(islands, epoch, axis=axis) if with_stats else ()
        return (islands, pool, key, epoch, stopped, obs), stats

    stopped0 = jnp.asarray(stopped0)
    if not w2:
        # idempotent re-latch: a fresh run tests the init population, a
        # resumed segment ORs with the restored latch (same value either way)
        stopped0 = stopped0 | _global_success(islands)
    init = (islands, pool, key, jnp.asarray(epoch0, jnp.int32), stopped0,
            obs0)
    (islands, pool, key, epochs, stopped, obs), stats = jax.lax.scan(
        body, init, None, length=max_epochs)
    return islands, pool, key, epochs, stopped, obs, stats


def unique_buffers(tree):
    """Copy any leaf that aliases an earlier leaf (jax caches small
    constants, e.g. a fresh pool's ptr/count are one buffer) so the whole
    tree can be donated without `donated twice` errors. Keyed on the
    underlying device buffers, not Python ids — two distinct ``jax.Array``
    wrappers can share one buffer (e.g. two equal ``arange`` constants
    after a ``device_put``)."""
    seen = set()

    def key(x):
        try:
            return tuple(s.data.unsafe_buffer_pointer()
                         for s in x.addressable_shards)
        except Exception:  # noqa: BLE001 — non-Array leaf / exotic backend
            return id(x)

    def f(x):
        k = key(x)
        if k in seen:
            return x.copy()
        seen.add(k)
        return x

    return jax.tree.map(f, tree)


# One compiled driver per (problem identity, config, topology, driver shape).
# Problem's dataclass equality excludes ``consts``, so the cache is keyed on
# object identity (the id is validated against the stored problem — the
# jitted closure keeps it alive, so a live hit can't be a recycled id).
# Bounded LRU over (problem, static_key) pairs: jitted drivers and their
# executables are evicted oldest-first.
_FUSED_CACHE: "collections.OrderedDict[tuple, Tuple[Problem, Callable]]" = \
    collections.OrderedDict()
_FUSED_CACHE_MAX = 32


def fused_jit(problem: Problem, static_key: tuple,
              builder: Callable[[], Callable]) -> Callable:
    """Memoize ``builder()`` per ``problem`` object + ``static_key`` so
    repeated fused runs reuse one compiled executable per topology."""
    key = (id(problem), static_key)
    entry = _FUSED_CACHE.get(key)
    if entry is None or entry[0] is not problem:
        _FUSED_CACHE[key] = entry = (problem, builder())
        while len(_FUSED_CACHE) > _FUSED_CACHE_MAX:
            _FUSED_CACHE.popitem(last=False)
    _FUSED_CACHE.move_to_end(key)
    return entry[1]


def fresh_experiment_state(problem: Problem, cfg: EAConfig,
                           mig: MigrationConfig, n_islands: int, rng: Array,
                           with_obs: bool = False,
                           ) -> Tuple[ExperimentState, Array]:
    """A fresh experiment's device state (``stats`` left ``()`` for the
    host to fill) and ``k_init``, the key its islands were drawn from:
    ``rng`` splits into ``(k_init, k_loop)`` and ``k_loop`` is the scan's
    key. Built by one compiled program per problem and shape, called with
    the experiment's key as its only argument: one launch where op-by-op
    construction makes one per primitive. Drivers compared bit for bit
    with :func:`run_fused` build their islands here too (compiled and
    op-by-op float fitness can differ in the last bit)."""
    def build(rng):
        k_init, k_loop = jax.random.split(rng)
        state = ExperimentState(
            islands=island_lib.init_islands(k_init, n_islands, problem, cfg),
            pool=pool_lib.pool_init(mig.pool_capacity, problem.genome),
            astate=(), key=k_loop, epoch=jnp.int32(0),
            stopped=jnp.asarray(False), stats=(),
            next_uuid=jnp.int32(n_islands),
            obs=obs_lib.init_obs(n_islands) if with_obs else ())
        return state, k_init

    init = fused_jit(
        problem, ("init", cfg, mig.pool_capacity, n_islands, with_obs),
        lambda: jax.jit(build))
    return init(rng)


# ---------------------------------------------------------------------------
# Durable segmented execution: ExperimentState snapshots between sub-scans
# ---------------------------------------------------------------------------
def empty_stats() -> ExperimentStats:
    """Zero-row stacked stats — the ``stats`` field of a fresh
    :class:`~repro.core.types.ExperimentState` (structure template for
    checkpoint restore; dtypes match :func:`collect_stats` exactly)."""
    z32 = np.zeros((0,), np.int32)
    zf = np.zeros((0,), np.float32)
    return ExperimentStats(epoch=z32, best_fitness=zf, mean_best=zf,
                           total_evaluations=z32, n_done=z32,
                           experiments_solved=z32)


def segment_plan(done: int, total: int,
                 snapshot_every: Optional[int]) -> List[int]:
    """Split the remaining ``total - done`` epochs into scan-segment
    lengths: ``snapshot_every``-sized chunks plus a remainder (at most two
    distinct lengths -> at most two compiles). ``None``/0 = one segment."""
    if total <= done:
        return []
    if not snapshot_every or snapshot_every <= 0:
        return [total - done]
    out = []
    at = done
    while at < total:
        n = min(snapshot_every, total - at)
        out.append(n)
        at += n
    return out


def _device_part(state: ExperimentState) -> ExperimentState:
    """jnp-ify the scan-carried fields (a restored checkpoint holds numpy —
    donation needs device arrays) and leave host-managed fields alone."""
    dev = jax.tree.map(jnp.asarray,
                       (state.islands, state.pool, state.astate, state.key,
                        state.epoch, state.stopped, state.obs))
    return state._replace(islands=dev[0], pool=dev[1], astate=dev[2],
                          key=dev[3], epoch=dev[4], stopped=dev[5],
                          obs=dev[6])


def resolve_checkpointer(snapshot_dir, checkpointer, keep: int = 3):
    """One Checkpointer per run: an explicit instance wins, else one is
    built on ``snapshot_dir`` (None -> no snapshotting)."""
    if checkpointer is not None:
        return checkpointer
    if snapshot_dir is None:
        return None
    from repro.checkpoint import Checkpointer  # deferred: keep core import-light
    return Checkpointer(snapshot_dir, keep=keep)


def restore_experiment_state(checkpointer, template: ExperimentState,
                             ) -> ExperimentState:
    """Load the latest snapshot into ``template``'s structure (leaf shapes
    come from the manifest, so an elastic resume at a different island
    count restores fine) and return it jnp-ified for the next segment."""
    state = checkpointer.restore_latest(target=template)
    return _device_part(state)


def run_segments(state: ExperimentState, max_steps: int, segment_fn, *,
                 snapshot_every: Optional[int] = None, checkpointer=None,
                 w2: bool = False, return_stats: bool = False,
                 ) -> ExperimentState:
    """The segmented driver loop shared by every fused driver.

    ``segment_fn(state, seg_len) -> (state', seg_stats)`` runs one jitted
    scan segment of ``seg_len`` epochs on the device part of ``state``.
    Between segments the *whole* :class:`ExperimentState` is snapshotted
    device->host (``Checkpointer.save_async`` — serialization happens off
    the driver thread) so a kill -9 loses at most ``snapshot_every`` epochs
    and a resume is bit-for-bit the uninterrupted run: chaining scan
    segments over the carried (islands, pool, key, epoch, stopped) is
    exactly one long scan.

    Early success breaks out of the remaining segments; the stacked stats
    are padded with the frozen final row so their shape — (max_steps, ...)
    — and values match the single-scan driver exactly (a frozen scan
    iteration emits an identical row).
    """
    stats_host = state.stats if isinstance(state.stats,
                                           ExperimentStats) else None
    epoch = int(np.asarray(state.epoch))
    for seg_len in segment_plan(epoch, max_steps, snapshot_every):
        # span args are host ints: the segment's first epoch is the plan's
        with obs_trace.span("driver.segment", seg_len=seg_len, epoch=epoch):
            state, seg_stats = segment_fn(state, seg_len)
        with obs_trace.span("driver.wait", epoch=epoch):
            stopped = (not w2) and bool(np.asarray(state.stopped))
        epoch += seg_len
        if return_stats:
            seg_np = jax.tree.map(np.asarray, seg_stats)
            stats_host = seg_np if stats_host is None else jax.tree.map(
                lambda a, b: np.concatenate([a, b]), stats_host, seg_np)
            state = state._replace(stats=stats_host)
        if checkpointer is not None:
            checkpointer.save_async(int(np.asarray(state.epoch)), state)
        if stopped:
            break
    if return_stats and stats_host is not None:
        rows = int(stats_host.epoch.shape[0])
        if rows and rows < max_steps:
            pad = max_steps - rows
            stats_host = jax.tree.map(
                lambda a: np.concatenate([a, np.repeat(a[-1:], pad, axis=0)]),
                stats_host)
            state = state._replace(stats=stats_host)
    if checkpointer is not None:
        checkpointer.wait()   # surface write errors before declaring success
    return state


def run_fused(problem: Problem,
              cfg: EAConfig = EAConfig(),
              mig: MigrationConfig = MigrationConfig(),
              n_islands: int = 8,
              max_epochs: int = 100,
              rng: Optional[Array] = None,
              w2: bool = False,
              return_stats: bool = False,
              return_obs: bool = False,
              snapshot_every: Optional[int] = None,
              snapshot_dir: Optional[str] = None,
              snapshot_keep: int = 3,
              checkpointer=None,
              resume: bool = False):
    """Entire experiment as jitted ``lax.scan`` segments with donated
    island/pool buffers. Returns ``(islands, pool, epochs)`` — plus the
    stacked per-epoch :class:`ExperimentStats` when ``return_stats`` is
    true, plus the harvested :class:`~repro.obs.counters.ObsCounters`
    dict when ``return_obs`` is true (appended last). Stops early on
    global success (non-W²).

    Durability: ``snapshot_every=k`` splits the scan into ``k``-epoch
    segments and snapshots the full :class:`ExperimentState` to
    ``snapshot_dir`` after each; ``resume=True`` restores the latest
    snapshot and continues — bit-for-bit identical to the uninterrupted
    seeded run. A resume with a different ``n_islands`` triggers elastic
    resize (``repro.runtime.elastic``): shrink slices islands off, grow
    seeds fresh islands from the pool under new (never recycled) uuids.
    """
    rng = jax.random.key(0) if rng is None else rng
    ckpt = resolve_checkpointer(snapshot_dir, checkpointer, snapshot_keep)
    if resume and ckpt is None:
        raise ValueError("resume=True needs snapshot_dir or checkpointer")

    with obs_trace.span("driver.init", n_islands=n_islands, resume=resume):
        state, _ = fresh_experiment_state(problem, cfg, mig, n_islands, rng,
                                          return_obs)
        state = state._replace(stats=empty_stats() if return_stats else ())
        if resume:
            # the fresh state is the structure template: restore replaces
            # every leaf, including the key
            state = restore_experiment_state(ckpt, state)
            if int(state.islands.pop.shape[0]) != n_islands:
                from repro.runtime import elastic as elastic_lib  # deferred: avoid cycle
                state = elastic_lib.resize_experiment(state, n_islands,
                                                      problem, cfg)

    def segment_fn(state: ExperimentState, seg_len: int):
        run = fused_jit(
            problem,
            ("batched", cfg, mig, w2, seg_len, return_stats, return_obs),
            lambda: jax.jit(partial(fused_scan, problem=problem, cfg=cfg,
                                    mig=mig, w2=w2, max_epochs=seg_len,
                                    with_stats=return_stats),
                            donate_argnums=(0, 1)))
        islands, pool = unique_buffers((state.islands, state.pool))
        islands, pool, key, epoch, stopped, obs, seg_stats = run(
            islands, pool, state.key, state.epoch, state.stopped, state.obs)
        return state._replace(islands=islands, pool=pool, key=key,
                              epoch=epoch, stopped=stopped,
                              obs=obs), seg_stats

    state = run_segments(state, max_epochs, segment_fn,
                         snapshot_every=snapshot_every, checkpointer=ckpt,
                         w2=w2, return_stats=return_stats)
    out = (state.islands, state.pool, state.epoch)
    if return_stats:
        out += (state.stats,)
    if return_obs:
        out += (obs_lib.harvest(state.obs),)
    return out

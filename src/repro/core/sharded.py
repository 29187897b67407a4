"""SPMD NodIO: islands sharded across a mesh axis via shard_map.

Maps the volunteer fleet onto hardware: every device (or device row) hosts a
contiguous slab of islands; migration is the only cross-device communication,
dispatched through the pluggable topology registry (core.migration — pool
all_gather, ring/torus permutes, random graph, elite broadcast), mirroring
the paper's server round-trip every ``generations_per_epoch``.

The generation operator (``EAConfig.impl`` -> repro.kernels.ga registry)
is shard-local compute with no collectives, so the fused Pallas megakernel
runs unchanged inside ``shard_map`` — each shard's island slab evolves in
its own VMEM tiles and only migration crosses devices.

Immigrant acceptance (``MigrationConfig.acceptance`` -> core.acceptance)
is replica-deterministic by construction under SPMD: the pool topology's
PUT policy runs on the all_gather'd candidates + all_gather'd valid/fire
mask with a pre-shard-fold key, so every shard computes the identical slot
assignment for its pool replica; the per-island receive gate is
collective-free and purely local. No driver below needs topology- or
policy-specific code — ``mig`` carries both axes as static config.

Three drivers:

* :func:`run_sharded` — host loop around a jitted shard_map epoch step.
  The host loop is where server failure and the host↔device pool bridge
  (core.migration.HostBridge) live.
* :func:`run_fused_sharded` — the whole experiment as one
  ``shard_map(lax.scan)``: donated buffers, per-epoch stats stacked on
  device, a single compile per topology.
* :func:`run_fused_sharded_async` — the asynchronous per-island-clock
  runtime (core.async_migration) in the same fused shard_map shape.

Both work on any 1-D mesh ("islands" axis). On the production mesh the same
step runs with the island axis mapped to ("pod", "data") and fitness
evaluation sharded over "model" (see launch/evolve.py).
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.obs import counters as obs_lib
from repro.obs.counters import ObsCounters

from . import async_migration as async_lib
from . import evolution as evolution_lib
from . import island as island_lib
from . import migration as migration_lib
from . import pool as pool_lib
from .async_migration import AsyncConfig, AsyncState
from .problems import Problem
from .types import (Array, EAConfig, ExperimentState, ExperimentStats,
                    IslandState, MigrationConfig, PoolState)


def _island_spec(axis: str):
    return IslandState(*[P(axis)] * len(IslandState._fields))


def _pool_spec():
    return PoolState(*[P()] * len(PoolState._fields))


def _obs_spec(axis: str, enabled: bool):
    """Per-island counters are row-sharded; the early-stop latch is a
    replicated scalar (derived from the psum'd stop flag). ``()`` when
    observability is off — the carry slot stays an empty pytree."""
    if not enabled:
        return ()
    return ObsCounters(
        **{f: (P() if f == "early_stop_epoch" else P(axis))
           for f in ObsCounters._fields})


def make_sharded_epoch(mesh: Mesh, axis: str, problem: Problem,
                       cfg: EAConfig, mig: MigrationConfig, w2: bool = False):
    """Build the jitted SPMD epoch step for ``mesh`` with islands sharded
    over ``axis``. Pool state is replicated; island batch is sharded.
    The per-shard body is evolution.epoch_step — the exact same code path
    as the batched drivers, with collectives enabled by ``axis``."""
    def body(islands, pool, rng, available, epoch):
        return evolution_lib.epoch_step(islands, pool, rng, problem, cfg,
                                        mig, w2, available, epoch, axis)

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(_island_spec(axis), _pool_spec(), P(), None, P()),
        out_specs=(_island_spec(axis), _pool_spec()),
        check_vma=False,
    )
    return jax.jit(fn)


def _init_sharded(mesh: Mesh, axis: str, problem: Problem, cfg: EAConfig,
                  mig: MigrationConfig, islands_per_shard: int, rng: Array,
                  ) -> Tuple[IslandState, PoolState, Array, Array]:
    """Returns (islands, pool, rng', k_init) — k_init is the key handed to
    init_islands, so sibling drivers can derive matching per-island state
    (the async driver folds it into the churn/rate schedule)."""
    n_islands = mesh.shape[axis] * islands_per_shard
    k_init, rng = jax.random.split(rng)
    islands = island_lib.init_islands(k_init, n_islands, problem, cfg)
    pool = pool_lib.pool_init(mig.pool_capacity, problem.genome)
    ish = jax.tree.map(
        lambda x: jax.device_put(x, NamedSharding(
            mesh, P(axis, *([None] * (x.ndim - 1))))),
        islands)
    psh = jax.tree.map(
        lambda x: jax.device_put(x, NamedSharding(mesh, P())), pool)
    return ish, psh, rng, k_init


def run_sharded(mesh: Mesh, problem: Problem,
                cfg: EAConfig = EAConfig(),
                mig: MigrationConfig = MigrationConfig(),
                islands_per_shard: int = 4,
                max_epochs: int = 50,
                rng: Optional[Array] = None,
                w2: bool = False,
                axis: str = "islands",
                server_up=None,
                host_bridge: Optional[migration_lib.HostBridge] = None,
                ) -> Tuple[IslandState, PoolState, int]:
    """Run a sharded experiment until success or max_epochs (host loop).

    ``server_up(epoch) -> bool`` injects pool-server failure; while the
    server is down migration is a no-op and islands evolve standalone.
    ``host_bridge`` syncs the replicated device pool with a host PoolServer
    between epochs (volunteer clients join the pod's experiment).
    """
    rng = jax.random.key(0) if rng is None else rng
    ish, psh, rng, _ = _init_sharded(mesh, axis, problem, cfg, mig,
                                     islands_per_shard, rng)
    step = make_sharded_epoch(mesh, axis, problem, cfg, mig, w2)
    epoch = 0
    for epoch in range(1, max_epochs + 1):
        rng, k = jax.random.split(rng)
        up = True if server_up is None else bool(server_up(epoch))
        ish, psh = step(ish, psh, k, up, epoch)
        # due() check first: sync would no-op anyway, but the device_get
        # round-trip of the replicated pool is worth skipping off-cycle
        if host_bridge is not None and host_bridge.due(epoch):
            psh = jax.tree.map(
                lambda x: jax.device_put(x, NamedSharding(mesh, P())),
                host_bridge.sync(jax.device_get(psh), epoch))
        if problem.optimum is not None and not w2:
            best = float(jax.device_get(ish.best_fitness.max()))
            if best >= problem.optimum - cfg.success_eps:
                break
    return ish, psh, epoch


def _place_state(mesh: Mesh, axis: str, state: ExperimentState,
                 ) -> ExperimentState:
    """device_put an :class:`ExperimentState` onto ``mesh``: islands (and
    AsyncState, when present) sharded over ``axis``, pool/key/epoch/stopped
    replicated. Host-managed fields (stats, next_uuid) stay on host. A
    restored checkpoint holds plain numpy, so this is also the elastic
    reshard: leaves land with whatever shardings the *new* mesh asks for."""
    def row_sharded(x):
        return jax.device_put(x, NamedSharding(
            mesh, P(axis, *([None] * (jnp.asarray(x).ndim - 1)))))

    def replicated(x):
        return jax.device_put(jnp.asarray(x), NamedSharding(mesh, P()))

    obs = state.obs
    if hasattr(obs, "_fields"):
        obs = obs._replace(
            **{f: (replicated(v) if f == "early_stop_epoch"
                   else row_sharded(v))
               for f, v in zip(obs._fields, obs)})
    return state._replace(
        islands=jax.tree.map(row_sharded, state.islands),
        pool=jax.tree.map(replicated, state.pool),
        astate=jax.tree.map(row_sharded, state.astate),
        key=replicated(state.key),
        epoch=replicated(state.epoch),
        stopped=replicated(state.stopped),
        obs=obs)


def run_fused_sharded(mesh: Mesh, problem: Problem,
                      cfg: EAConfig = EAConfig(),
                      mig: MigrationConfig = MigrationConfig(),
                      islands_per_shard: int = 4,
                      max_epochs: int = 50,
                      rng: Optional[Array] = None,
                      w2: bool = False,
                      axis: str = "islands",
                      return_stats: bool = False,
                      return_obs: bool = False,
                      snapshot_every: Optional[int] = None,
                      snapshot_dir: Optional[str] = None,
                      snapshot_keep: int = 3,
                      checkpointer=None,
                      resume: bool = False):
    """The whole sharded experiment as ``shard_map(lax.scan)`` segments —
    donated island/pool buffers, per-epoch global stats stacked on device
    (psum/pmax-reduced, replicated). Returns ``(islands, pool, epochs)``
    (+ stacked stats when asked). Durability kwargs as in
    :func:`repro.core.evolution.run_fused`; restore lands leaves on host
    and re-places them with *this* mesh's shardings, so a checkpoint from
    one topology resumes on another (elastic volunteer pool)."""
    rng = jax.random.key(0) if rng is None else rng
    n_islands = mesh.shape[axis] * islands_per_shard
    ckpt = evolution_lib.resolve_checkpointer(snapshot_dir, checkpointer,
                                              snapshot_keep)

    ish, psh, rng, _ = _init_sharded(mesh, axis, problem, cfg, mig,
                                     islands_per_shard, rng)
    _, k_loop = jax.random.split(rng)
    state = ExperimentState(
        islands=ish, pool=psh, astate=(), key=k_loop, epoch=jnp.int32(0),
        stopped=jnp.asarray(False),
        stats=evolution_lib.empty_stats() if return_stats else (),
        next_uuid=jnp.int32(n_islands),
        obs=obs_lib.init_obs(n_islands) if return_obs else ())
    if resume:
        if ckpt is None:
            raise ValueError("resume=True needs snapshot_dir or checkpointer")
        state = ckpt.restore_latest(target=state)
        if int(jnp.asarray(state.islands.pop).shape[0]) != n_islands:
            from repro.runtime import elastic as elastic_lib  # deferred: avoid cycle
            state = elastic_lib.resize_experiment(state, n_islands, problem,
                                                  cfg)
    state = _place_state(mesh, axis, state)

    def segment_fn(state: ExperimentState, seg_len: int):
        def build():
            # with return_stats=False the scan emits () in the stats slot
            # and skips the per-epoch psum/pmax scalar reductions entirely
            stats_spec = (ExperimentStats(
                *[P()] * len(ExperimentStats._fields))
                if return_stats else ())
            obs_spec = _obs_spec(axis, return_obs)
            fn = jax.shard_map(
                partial(evolution_lib.fused_scan, problem=problem, cfg=cfg,
                        mig=mig, w2=w2, max_epochs=seg_len, axis=axis,
                        with_stats=return_stats),
                mesh=mesh,
                in_specs=(_island_spec(axis), _pool_spec(), P(), P(), P(),
                          obs_spec),
                out_specs=(_island_spec(axis), _pool_spec(), P(), P(), P(),
                           obs_spec, stats_spec),
                check_vma=False,
            )
            return jax.jit(fn, donate_argnums=(0, 1))

        run = evolution_lib.fused_jit(
            problem,
            ("sharded", cfg, mig, w2, seg_len, axis, mesh, return_stats,
             return_obs),
            build)
        islands, pool = evolution_lib.unique_buffers(
            (state.islands, state.pool))
        islands, pool, key, epoch, stopped, obs, seg_stats = run(
            islands, pool, state.key, state.epoch, state.stopped, state.obs)
        return state._replace(islands=islands, pool=pool, key=key,
                              epoch=epoch, stopped=stopped,
                              obs=obs), seg_stats

    state = evolution_lib.run_segments(
        state, max_epochs, segment_fn, snapshot_every=snapshot_every,
        checkpointer=ckpt, w2=w2, return_stats=return_stats)
    out = (state.islands, state.pool, state.epoch)
    if return_stats:
        out += (state.stats,)
    if return_obs:
        out += (obs_lib.harvest(state.obs),)
    return out


# ---------------------------------------------------------------------------
# Asynchronous SPMD driver: per-island clocks inside shard_map(lax.scan)
# ---------------------------------------------------------------------------
def _astate_spec(axis: str):
    return AsyncState(*[P(axis)] * len(AsyncState._fields))


def run_fused_sharded_async(mesh: Mesh, problem: Problem,
                            cfg: EAConfig = EAConfig(),
                            mig: MigrationConfig = MigrationConfig(),
                            acfg: AsyncConfig = AsyncConfig(),
                            islands_per_shard: int = 4,
                            max_ticks: int = 50,
                            rng: Optional[Array] = None,
                            w2: bool = False,
                            axis: str = "islands",
                            return_stats: bool = False,
                            return_astate: bool = False,
                            return_obs: bool = False,
                            snapshot_every: Optional[int] = None,
                            snapshot_dir: Optional[str] = None,
                            snapshot_keep: int = 3,
                            checkpointer=None,
                            resume: bool = False):
    """Asynchronous :func:`run_fused_sharded`: the whole churn-tolerant
    per-island-clock experiment as ``shard_map(lax.scan)`` segments.
    Islands and their :class:`~repro.core.async_migration.AsyncState`
    (clock, rate, churn window, immigrant inbox) are sharded over ``axis``;
    the pool is replicated; the per-shard fire mask is the vector
    availability for the topology collectives. In the degenerate ``acfg``
    this is bit-for-bit :func:`run_fused_sharded`. Durability kwargs as in
    :func:`run_fused_sharded` — the snapshot additionally carries the
    sharded AsyncState."""
    rng = jax.random.key(0) if rng is None else rng
    n_islands = mesh.shape[axis] * islands_per_shard
    ckpt = evolution_lib.resolve_checkpointer(snapshot_dir, checkpointer,
                                              snapshot_keep)

    ish, psh, rng, k_init = _init_sharded(mesh, axis, problem, cfg, mig,
                                          islands_per_shard, rng)
    _, k_loop = jax.random.split(rng)
    astate = async_lib.init_async_state(
        jax.random.fold_in(k_init, 7), n_islands, acfg, max_ticks,
        problem.genome)
    state = ExperimentState(
        islands=ish, pool=psh, astate=astate, key=k_loop,
        epoch=jnp.int32(0), stopped=jnp.asarray(False),
        stats=evolution_lib.empty_stats() if return_stats else (),
        next_uuid=jnp.int32(n_islands),
        obs=obs_lib.init_obs(n_islands) if return_obs else ())
    if resume:
        if ckpt is None:
            raise ValueError("resume=True needs snapshot_dir or checkpointer")
        state = ckpt.restore_latest(target=state)
        if int(jnp.asarray(state.islands.pop).shape[0]) != n_islands:
            from repro.runtime import elastic as elastic_lib  # deferred: avoid cycle
            state = elastic_lib.resize_experiment(state, n_islands, problem,
                                                  cfg)
    state = _place_state(mesh, axis, state)

    def segment_fn(state: ExperimentState, seg_len: int):
        def build():
            stats_spec = (ExperimentStats(
                *[P()] * len(ExperimentStats._fields))
                if return_stats else ())
            obs_spec = _obs_spec(axis, return_obs)
            fn = jax.shard_map(
                partial(async_lib.fused_scan_async, problem=problem,
                        cfg=cfg, mig=mig, acfg=acfg, w2=w2,
                        max_ticks=seg_len, axis=axis,
                        with_stats=return_stats),
                mesh=mesh,
                in_specs=(_island_spec(axis), _pool_spec(),
                          _astate_spec(axis), P(), P(), P(), obs_spec),
                out_specs=(_island_spec(axis), _pool_spec(),
                           _astate_spec(axis), P(), P(), P(), obs_spec,
                           stats_spec),
                check_vma=False,
            )
            return jax.jit(fn, donate_argnums=(0, 1, 2))

        run = evolution_lib.fused_jit(
            problem,
            ("sharded_async", cfg, mig, acfg, w2, seg_len, axis, mesh,
             return_stats, return_obs),
            build)
        islands, pool, astate = evolution_lib.unique_buffers(
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

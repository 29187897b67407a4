"""Unified migration engine: pluggable topologies + host↔device pool bridge.

The paper's contribution is pool-mediated migration (PUT best / GET random
against a chromosome server), but *which* islands exchange with which is a
policy — and the follow-up work on asynchronous distributed GAs shows the
topology is the dominant scaling lever. This module makes topology a
first-class, registered strategy so every driver (host loop, fused
``lax.scan``, SPMD ``shard_map``) dispatches through one code path.

A topology is a function with the :class:`Topology` signature. It runs in
two contexts, selected by ``axis``:

* ``axis=None`` — *batched* mode: ``bests_*`` carry every island
  (leading axis = n_islands) on one shard.
* ``axis="islands"`` — *SPMD* mode: the call executes inside ``shard_map``
  and ``bests_*`` carry only this shard's islands; cross-shard exchange uses
  collectives over ``axis``.

Both contexts honour the paper's fault-tolerance property: when
``available`` is False the pool is left untouched and every immigrant
fitness is ``-inf`` (a lost XHR — the island continues standalone).

``available`` may also be a *per-island vector* ``(n_local,)`` — the
asynchronous runtime's fire mask (:mod:`repro.core.async_migration`):
island ``i`` participates in the exchange this step iff ``available[i]``.
Vector semantics per topology:

* ``pool`` — only participating islands PUT (masked ``valid`` slots, so
  the ring pointer advances exactly by the number of firing islands) and
  only participating islands' GETs are honoured (others read ``-inf``).
  Both verbs belong to the island's own fire event, the paper's
  client-at-its-own-pace behaviour.
* permute/broadcast topologies — non-participating *sources* contribute
  ``-inf`` (their stale best is not re-emitted), while deliveries to any
  destination are returned un-masked: the async runtime buffers them in
  the destination's staleness-bounded inbox and the destination absorbs
  at its own next fire.

With an all-True vector both reduce bit-for-bit to the scalar ``True``
path — the async runtime's degenerate-configuration anchor.

Built-in topologies
-------------------
``pool``            all_gather'd PUT/GET pool — the faithful paper
                    semantics (bit-for-bit the legacy ``migrate_sharded``
                    all_gather path).
``ring``            each shard's bests go to the next shard
                    (``collective_permute``); pool bypassed.
``torus``           2-D grid permute: east neighbours on even epochs,
                    south neighbours on odd epochs; pool bypassed.
``random_graph``    seeded per-epoch permutation of sources — every epoch a
                    fresh random 1-regular exchange graph; pool bypassed.
``broadcast_best``  psum-argmax elite broadcast: every island receives the
                    global best of the epoch; pool bypassed.

Register your own with::

    @register_topology("my_topo")
    def my_topo(pool, bests_genome, bests_fitness, rng, *, mig, axis=None,
                epoch=0, available=True):
        ...
        return pool, immigrant_genomes, immigrant_fitness

and select it via ``MigrationConfig(topology="my_topo")``.
"""
from __future__ import annotations

from typing import Dict, Optional, Protocol, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import trace as obs_trace

from . import acceptance as acceptance_lib
from .pool import (NEG_INF, pool_best, pool_get_random, pool_insert_host,
                   pool_put_batch)
from .types import AcceptanceConfig, Array, MigrationConfig, PoolState


# ---------------------------------------------------------------------------
# Protocol + registry
# ---------------------------------------------------------------------------
class Topology(Protocol):
    """One migration step: PUT this epoch's bests, return the immigrants.

    Must be pure/jittable, honour ``available=False`` as a no-op (pool
    unchanged, immigrant fitness ``-inf``), and support both ``axis=None``
    (batched) and ``axis=<mesh axis name>`` (inside ``shard_map``).
    ``available`` may also be a per-island ``(n_local,)`` fire mask — see
    the module docstring for the vector semantics every built-in follows.
    """

    def __call__(self, pool: PoolState, bests_genome: Array,
                 bests_fitness: Array, rng: Array, *, mig: MigrationConfig,
                 axis: Optional[str] = None, epoch: Array | int = 0,
                 available: Array | bool = True,
                 ) -> Tuple[PoolState, Array, Array]: ...


TOPOLOGIES: Dict[str, Topology] = {}


def register_topology(name: str):
    """Decorator: register a :class:`Topology` under ``name``."""
    def deco(fn: Topology) -> Topology:
        TOPOLOGIES[name] = fn
        fn.topology_name = name
        return fn
    return deco


def available_topologies() -> Tuple[str, ...]:
    return tuple(sorted(TOPOLOGIES))


def get_topology(name: str) -> Topology:
    try:
        return TOPOLOGIES[name]
    except KeyError:
        raise KeyError(f"unknown topology {name!r}; "
                       f"registered: {available_topologies()}") from None


def resolve_topology_name(mig: MigrationConfig) -> str:
    """Topology selected by ``mig``. An explicit ``topology`` (including
    'pool') always wins; only when it is unset (None) does the legacy
    ``collective`` field map 'ring' to the ring."""
    name = getattr(mig, "topology", None)
    if name is not None:
        return name
    return "ring" if getattr(mig, "collective", "all_gather") == "ring" \
        else "pool"


def migrate(pool: PoolState, bests_genome: Array, bests_fitness: Array,
            rng: Array, mig: MigrationConfig, *, axis: Optional[str] = None,
            epoch: Array | int = 0, available: Array | bool = True,
            with_ledger: bool = False):
    """Dispatch one migration step through the registered topology, then
    gate the deliveries through the acceptance engine.

    Every topology's immigrants — pool GETs and permute/broadcast
    deliveries alike — pass the per-destination-island receive gate
    (:func:`repro.core.acceptance.gate_immigrants`): each island runs
    ``mig.acceptance`` against its own current best and rejected
    deliveries read ``-inf``. The ``always`` policy skips the gate
    entirely (bit-for-bit legacy behaviour). The pool topology's PUT side
    additionally dispatches the same policy against the shared pool
    residents (see :func:`pool_topology`).

    ``with_ledger=True`` returns ``(pool, imm_g, imm_f, delivered,
    accepted)`` instead of the 3-tuple: per-island boolean masks of the
    finite deliveries before and after the gate (so ``delivered ==
    accepted + rejected`` balances by construction — the observability
    counters' ledger, :mod:`repro.obs.counters`)."""
    topo = get_topology(resolve_topology_name(mig))
    pool, imm_g, imm_f = topo(pool, bests_genome, bests_fitness, rng,
                              mig=mig, axis=axis, epoch=epoch,
                              available=available)
    delivered = jnp.isfinite(imm_f)
    acc = getattr(mig, "acceptance", None)
    if acc is not None and acc.policy != "always":
        imm_f = acceptance_lib.gate_immigrants(
            bests_genome, bests_fitness, imm_g, imm_f,
            jax.random.fold_in(rng, 0x5EED), acc)
    if with_ledger:
        return pool, imm_g, imm_f, delivered, jnp.isfinite(imm_f)
    return pool, imm_g, imm_f


def _mask_unavailable(imm_f: Array, available) -> Array:
    return jnp.where(jnp.asarray(available), imm_f, NEG_INF)


def _avail_parts(available) -> Tuple[Optional[Array], Optional[Array]]:
    """Split ``available`` into ``(scalar, vector)`` — exactly one is set.

    Scalar: the sync drivers' whole-step gate. Vector ``(n_local,)``: the
    async runtime's per-island fire mask (see module docstring)."""
    a = jnp.asarray(available)
    return (a, None) if a.ndim == 0 else (None, a)


def _grid(n: int) -> Tuple[int, int]:
    """Most-square (rows, cols) factorization of ``n`` (rows <= cols)."""
    r = int(np.sqrt(n))
    while n % r:
        r -= 1
    return r, n // r


# ---------------------------------------------------------------------------
# pool — the faithful PUT(best)/GET(random) server semantics
# ---------------------------------------------------------------------------
@register_topology("pool")
def pool_topology(pool: PoolState, bests_genome: Array, bests_fitness: Array,
                  rng: Array, *, mig: MigrationConfig,
                  axis: Optional[str] = None, epoch: Array | int = 0,
                  available: Array | bool = True,
                  ) -> Tuple[PoolState, Array, Array]:
    """PUT all bests into the replicated pool, GET one random immigrant per
    island. SPMD: contributions are all_gather'd so every shard applies the
    same deterministic update to its pool replica (single server semantics
    without the single point of failure). The PUT dispatches
    ``mig.acceptance`` against the pool residents — the policy sees the
    all_gather'd candidates and valid mask with a pre-shard-fold key, so
    every replica makes the identical slot decisions."""
    n_local = bests_genome.shape[0]
    scalar, vec = _avail_parts(available)
    acc = getattr(mig, "acceptance", None)
    k_put = jax.random.fold_in(rng, 0xACC)   # replicated: derived pre-fold
    put_valid = vec
    if axis is not None:
        bests_genome = jax.lax.all_gather(bests_genome, axis, tiled=True)
        bests_fitness = jax.lax.all_gather(bests_fitness, axis, tiled=True)
        if vec is not None:
            # every replica must apply the same masked PUT
            put_valid = jax.lax.all_gather(vec, axis, tiled=True)
    if vec is None:
        new_pool = pool_put_batch(pool, bests_genome, bests_fitness,
                                  acc=acc, rng=k_put)
        pool = jax.tree.map(lambda a, b: jnp.where(scalar, a, b),
                            new_pool, pool)
    else:
        pool = pool_put_batch(pool, bests_genome, bests_fitness,
                              valid=put_valid, acc=acc, rng=k_put)
    if axis is not None:
        # Decorrelate shards: fold the shard index into the key.
        rng = jax.random.fold_in(rng, jax.lax.axis_index(axis))
    keys = jax.random.split(rng, n_local)
    genomes, fits = jax.vmap(lambda k: pool_get_random(pool, k))(keys)
    return pool, genomes, _mask_unavailable(fits, available)


# ---------------------------------------------------------------------------
# ring — classic directional island ring; pool bypassed
# ---------------------------------------------------------------------------
@register_topology("ring")
def ring_topology(pool: PoolState, bests_genome: Array, bests_fitness: Array,
                  rng: Array, *, mig: MigrationConfig,
                  axis: Optional[str] = None, epoch: Array | int = 0,
                  available: Array | bool = True,
                  ) -> Tuple[PoolState, Array, Array]:
    """Island/shard ``i`` sends its bests to ``i+1`` (mod n). Each best is
    delivered exactly once; the pool is bypassed (cheap on the wire)."""
    scalar, vec = _avail_parts(available)
    if vec is not None:  # async fire mask: silent sources contribute -inf
        bests_fitness = jnp.where(vec, bests_fitness, NEG_INF)
    if axis is not None:
        n = jax.lax.axis_size(axis)
        perm = [(i, (i + 1) % n) for i in range(n)]
        imm_g = jax.lax.ppermute(bests_genome, axis, perm)
        imm_f = jax.lax.ppermute(bests_fitness, axis, perm)
    else:
        imm_g = jnp.roll(bests_genome, 1, axis=0)     # i receives from i-1
        imm_f = jnp.roll(bests_fitness, 1, axis=0)
    if vec is not None:   # already source-masked; destinations buffer
        return pool, imm_g, imm_f
    return pool, imm_g, _mask_unavailable(imm_f, scalar)


# ---------------------------------------------------------------------------
# torus — 2-D grid permute, direction alternates per epoch; pool bypassed
# ---------------------------------------------------------------------------
@register_topology("torus")
def torus_topology(pool: PoolState, bests_genome: Array, bests_fitness: Array,
                   rng: Array, *, mig: MigrationConfig,
                   axis: Optional[str] = None, epoch: Array | int = 0,
                   available: Array | bool = True,
                   ) -> Tuple[PoolState, Array, Array]:
    """Islands/shards arranged on the most-square (R, C) torus. Even epochs
    migrate east ((r, c) -> (r, c+1)), odd epochs south ((r, c) -> (r+1, c)),
    so each best is delivered exactly once per epoch while information still
    spreads in both grid dimensions over time. A prime n factors as (1, n):
    the south roll would be a self-delivery no-op, so the grid-degenerate
    case migrates east every epoch (a plain ring)."""
    scalar, vec = _avail_parts(available)
    if vec is not None:
        bests_fitness = jnp.where(vec, bests_fitness, NEG_INF)
    east = jnp.asarray(epoch) % 2 == 0
    if axis is not None:
        n = jax.lax.axis_size(axis)
        R, C = _grid(n)
        perm_e = [(r * C + c, r * C + (c + 1) % C)
                  for r in range(R) for c in range(C)]
        if R == 1:
            imm_g = jax.lax.ppermute(bests_genome, axis, perm_e)
            imm_f = jax.lax.ppermute(bests_fitness, axis, perm_e)
            if vec is not None:
                return pool, imm_g, imm_f
            return pool, imm_g, _mask_unavailable(imm_f, scalar)
        perm_s = [(r * C + c, ((r + 1) % R) * C + c)
                  for r in range(R) for c in range(C)]
        # cond, not where: `east` is replicated so every shard takes the
        # same branch, and only one direction's permute hits the wire
        # (migration is the drivers' only cross-device traffic)
        imm_g, imm_f = jax.lax.cond(
            east,
            lambda gf: (jax.lax.ppermute(gf[0], axis, perm_e),
                        jax.lax.ppermute(gf[1], axis, perm_e)),
            lambda gf: (jax.lax.ppermute(gf[0], axis, perm_s),
                        jax.lax.ppermute(gf[1], axis, perm_s)),
            (bests_genome, bests_fitness))
    else:
        n = bests_genome.shape[0]
        R, C = _grid(n)

        def _shift(x):
            if R == 1:
                return jnp.roll(x, 1, axis=0)
            g = x.reshape((R, C) + x.shape[1:])
            return jnp.where(east, jnp.roll(g, 1, axis=1),
                             jnp.roll(g, 1, axis=0)).reshape(x.shape)

        imm_g, imm_f = _shift(bests_genome), _shift(bests_fitness)
    if vec is not None:
        return pool, imm_g, imm_f
    return pool, imm_g, _mask_unavailable(imm_f, scalar)


# ---------------------------------------------------------------------------
# random_graph — seeded per-epoch permutation; pool bypassed
# ---------------------------------------------------------------------------
@register_topology("random_graph")
def random_graph_topology(pool: PoolState, bests_genome: Array,
                          bests_fitness: Array, rng: Array, *,
                          mig: MigrationConfig, axis: Optional[str] = None,
                          epoch: Array | int = 0,
                          available: Array | bool = True,
                          ) -> Tuple[PoolState, Array, Array]:
    """A fresh uniformly random 1-regular exchange graph every epoch:
    island/shard ``i`` receives from ``perm[i]`` where ``perm`` is a seeded
    permutation derived from the (replicated) epoch key — identical on every
    shard, so delivery stays exactly-once without any host coordination."""
    scalar, vec = _avail_parts(available)
    if vec is not None:
        bests_fitness = jnp.where(vec, bests_fitness, NEG_INF)
    if axis is not None:
        n = jax.lax.axis_size(axis)
        perm = jax.random.permutation(rng, n)
        # (n_shards, n_local, ...) stacks; every shard indexes its source.
        all_g = jax.lax.all_gather(bests_genome, axis)
        all_f = jax.lax.all_gather(bests_fitness, axis)
        src = perm[jax.lax.axis_index(axis)]
        imm_g, imm_f = all_g[src], all_f[src]
    else:
        n = bests_genome.shape[0]
        perm = jax.random.permutation(rng, n)
        imm_g, imm_f = bests_genome[perm], bests_fitness[perm]
    if vec is not None:
        return pool, imm_g, imm_f
    return pool, imm_g, _mask_unavailable(imm_f, scalar)


# ---------------------------------------------------------------------------
# broadcast_best — psum-argmax elite broadcast; pool bypassed
# ---------------------------------------------------------------------------
@register_topology("broadcast_best")
def broadcast_best_topology(pool: PoolState, bests_genome: Array,
                            bests_fitness: Array, rng: Array, *,
                            mig: MigrationConfig, axis: Optional[str] = None,
                            epoch: Array | int = 0,
                            available: Array | bool = True,
                            ) -> Tuple[PoolState, Array, Array]:
    """Every island receives the epoch's global elite. SPMD: only the small
    fitness vector is all_gather'd; the winning genome itself is broadcast
    with a single psum (the owning shard contributes it, everyone else
    contributes zeros) — one activation-sized all-reduce instead of
    gathering n_total genomes."""
    n_local = bests_fitness.shape[0]
    scalar, vec = _avail_parts(available)
    if vec is not None:  # silent islands don't compete for the elite slot
        bests_fitness = jnp.where(vec, bests_fitness, NEG_INF)
    if axis is not None:
        all_f = jax.lax.all_gather(bests_fitness, axis, tiled=True)
        g = jnp.argmax(all_f)
        owner, local_i = g // n_local, g % n_local
        mine = jax.lax.axis_index(axis) == owner
        contrib = jnp.where(mine, bests_genome[local_i], 0).astype(jnp.float32)
        elite_g = jax.lax.psum(contrib, axis).astype(bests_genome.dtype)
        elite_f = all_f[g]
    else:
        i = jnp.argmax(bests_fitness)
        elite_g, elite_f = bests_genome[i], bests_fitness[i]
    imm_g = jnp.broadcast_to(elite_g, (n_local,) + elite_g.shape)
    imm_f = jnp.broadcast_to(elite_f, (n_local,))
    if vec is not None:
        return pool, imm_g, imm_f
    return pool, imm_g, _mask_unavailable(imm_f, scalar)


# ---------------------------------------------------------------------------
# Host ↔ device pool bridge
# ---------------------------------------------------------------------------
class HostBridge:
    """Periodic sync between the device-resident :class:`PoolState` and a
    host :class:`~repro.core.async_pool.PoolServer`.

    Direction *out*: the device pool's current best is PUT to the server
    (so browser/CPU volunteer clients attached to the same server see the
    pod's progress). Direction *in*: up to ``pull`` random server entries
    are inserted into the device pool (so volunteer contributions become
    GET-able immigrants for the device islands). This is the paper's
    client-server scenario at pod scale: SPMD pods and host volunteer
    clients participate in one experiment.

    Server loss is tolerated exactly like a browser client's lost XHR:
    ``sync`` swallows :class:`PoolUnavailable` and counts the loss.

    ``acceptance`` selects the policy the *device* pool applies to pulled
    server entries (core.acceptance); pair it with a PoolServer built with
    the same :class:`~repro.core.types.AcceptanceConfig` so both sides of
    the bridge make the same replacement decisions.

    ``server`` may also be a URL string (``http://host:port`` or
    ``host:port``), in which case the bridge talks the JSON wire protocol
    to a networked ``python -m repro.server`` service via
    :class:`~repro.server.client.RemotePoolServer` — same verbs, same
    lost-XHR tolerance, nothing else changes. The in-process path is
    untouched: a PoolServer instance is used exactly as before.
    """

    def __init__(self, server, every: int = 1, pull: int = 4,
                 uuid: int = -1,
                 acceptance: Optional[AcceptanceConfig] = None,
                 experiment: str = "default"):
        if every < 1:
            raise ValueError("every must be >= 1")
        if isinstance(server, str):
            # deferred import: repro.server is an optional tier on top of
            # core, core must not hard-depend on it
            from repro.server.client import RemotePoolServer
            server = RemotePoolServer(server, experiment=experiment)
        self.server = server
        self.every = every
        self.pull = pull
        self.uuid = uuid
        self.acceptance = acceptance
        self.pushed = 0
        self.pulled = 0
        self.lost = 0

    def due(self, epoch: int) -> bool:
        """True when this epoch is a sync epoch. Drivers that must pay a
        transfer to call :meth:`sync` (e.g. run_sharded's device_get of the
        replicated pool) can check this first; the policy lives here."""
        return epoch % self.every == 0

    def sync(self, pool: PoolState, epoch: int = 0) -> PoolState:
        """Best-out / immigrants-in. Returns the (possibly updated) device
        pool; a no-op on off-cycle epochs or when the server is down."""
        if not self.due(epoch):
            return pool
        from .async_pool import PoolUnavailable  # local: avoid import cycle

        with obs_trace.span("bridge.sync", epoch=int(epoch)):
            # best-out
            try:
                if int(pool.count) > 0:
                    g, f = pool_best(pool)
                    with obs_trace.span("bridge.put"):
                        self.server.put(np.asarray(g), float(f),
                                        uuid=self.uuid)
                    self.pushed += 1
            except PoolUnavailable:
                self.lost += 1
            # immigrants-in
            genomes, fits = [], []
            for _ in range(self.pull):
                try:
                    with obs_trace.span("bridge.get"):
                        g, f = self.server.get_random()
                except PoolUnavailable:
                    # an up-but-empty server is a normal cold start, not an
                    # outage — only count the loss when the server is down
                    if not getattr(self.server, "up", False):
                        self.lost += 1
                    break
                genomes.append(np.asarray(g))
                fits.append(float(f))
            if genomes:
                pool = pool_insert_host(pool, genomes, fits,
                                        acc=self.acceptance,
                                        rng=jax.random.fold_in(
                                            jax.random.key(17), epoch))
                self.pulled += len(genomes)
        return pool

    def stats(self) -> Dict[str, int]:
        return {"pushed": self.pushed, "pulled": self.pulled,
                "lost": self.lost}

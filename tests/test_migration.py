"""Unified migration engine: registry, topology semantics, fused drivers,
host bridge. SPMD properties (pool-replica consistency, exactly-once
delivery across shards, bit-for-bit legacy equivalence) run in a subprocess
with 8 fake devices, isolated from the session's single-device state."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (EAConfig, HostBridge, MigrationConfig, PoolServer,
                        make_onemax, migration, run_experiment, run_fused)
from repro.core import pool as pool_lib
from repro.core.pool import NEG_INF, pool_get_random, pool_put_batch
from repro.core.types import GenomeSpec

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ALL_TOPOLOGIES = ("pool", "ring", "torus", "random_graph", "broadcast_best")

GEN = GenomeSpec("binary", 8)


def _bests(n):
    """n islands with distinct fitness and identifiable genomes."""
    g = (jnp.arange(n, dtype=jnp.int8)[:, None]
         * jnp.ones((n, GEN.length), jnp.int8))
    f = jnp.arange(n, dtype=jnp.float32)
    return g, f


class TestRegistry:
    def test_builtins_registered(self):
        assert set(ALL_TOPOLOGIES) <= set(migration.available_topologies())

    def test_unknown_topology_raises(self):
        with pytest.raises(KeyError, match="unknown topology"):
            migration.get_topology("no_such_topology")

    def test_custom_registration_dispatches(self):
        @migration.register_topology("_test_identity")
        def identity(pool, bg, bf, rng, *, mig, axis=None, epoch=0,
                     available=True):
            return pool, bg, jnp.where(jnp.asarray(available), bf, NEG_INF)

        try:
            pool = pool_lib.pool_init(4, GEN)
            g, f = _bests(4)
            _, ig, if_ = migration.migrate(
                pool, g, f, jax.random.key(0),
                MigrationConfig(topology="_test_identity"))
            np.testing.assert_array_equal(np.asarray(ig), np.asarray(g))
        finally:
            del migration.TOPOLOGIES["_test_identity"]

    def test_legacy_collective_ring_still_selects_ring(self):
        mig = MigrationConfig(collective="ring")
        assert migration.resolve_topology_name(mig) == "ring"
        assert migration.resolve_topology_name(MigrationConfig()) == "pool"
        # an explicit topology always wins over the legacy alias
        both = MigrationConfig(topology="pool", collective="ring")
        assert migration.resolve_topology_name(both) == "pool"


class TestBatchedTopologies:
    """axis=None semantics on a single shard."""

    @pytest.mark.parametrize("topo", ALL_TOPOLOGIES)
    def test_unavailable_is_noop(self, topo):
        pool = pool_lib.pool_init(8, GEN)
        g, f = _bests(6)
        new_pool, ig, if_ = migration.migrate(
            pool, g, f, jax.random.key(0), MigrationConfig(topology=topo),
            epoch=1, available=False)
        assert int(new_pool.count) == 0            # PUT lost
        assert np.isneginf(np.asarray(if_)).all()  # GET lost

    @pytest.mark.parametrize("topo", ["ring", "torus", "random_graph"])
    @pytest.mark.parametrize("epoch", [0, 1])
    def test_exactly_once_delivery(self, topo, epoch):
        g, f = _bests(8)
        _, ig, if_ = migration.migrate(
            pool_lib.pool_init(4, GEN), g, f, jax.random.key(3),
            MigrationConfig(topology=topo), epoch=epoch)
        # each island's best arrives at exactly one island
        assert sorted(np.asarray(if_).tolist()) == sorted(
            np.asarray(f).tolist())
        # genome rides along with its fitness
        np.testing.assert_array_equal(
            np.asarray(ig[:, 0]).astype(np.float32), np.asarray(if_))

    def test_ring_is_a_shift(self):
        g, f = _bests(6)
        _, _, if_ = migration.migrate(
            pool_lib.pool_init(4, GEN), g, f, jax.random.key(0),
            MigrationConfig(topology="ring"))
        np.testing.assert_array_equal(np.asarray(if_),
                                      np.roll(np.asarray(f), 1))

    def test_torus_alternates_direction(self):
        g, f = _bests(8)  # 2 x 4 grid
        mig = MigrationConfig(topology="torus")
        _, _, east = migration.migrate(pool_lib.pool_init(4, GEN), g, f,
                                       jax.random.key(0), mig, epoch=0)
        _, _, south = migration.migrate(pool_lib.pool_init(4, GEN), g, f,
                                        jax.random.key(0), mig, epoch=1)
        fe = np.asarray(f).reshape(2, 4)
        np.testing.assert_array_equal(np.asarray(east).reshape(2, 4),
                                      np.roll(fe, 1, axis=1))
        np.testing.assert_array_equal(np.asarray(south).reshape(2, 4),
                                      np.roll(fe, 1, axis=0))

    @pytest.mark.parametrize("epoch", [0, 1])
    def test_torus_prime_count_never_self_delivers(self, epoch):
        """n=5 factors as (1, 5): the south direction would be a no-op, so
        the degenerate grid must migrate east (ring) every epoch."""
        g, f = _bests(5)
        _, _, if_ = migration.migrate(
            pool_lib.pool_init(4, GEN), g, f, jax.random.key(0),
            MigrationConfig(topology="torus"), epoch=epoch)
        np.testing.assert_array_equal(np.asarray(if_),
                                      np.roll(np.asarray(f), 1))

    def test_random_graph_varies_with_key(self):
        g, f = _bests(16)
        mig = MigrationConfig(topology="random_graph")
        outs = [np.asarray(migration.migrate(
            pool_lib.pool_init(4, GEN), g, f, jax.random.key(s), mig)[2])
            for s in range(4)]
        assert any(not np.array_equal(outs[0], o) for o in outs[1:])

    def test_broadcast_best_sends_elite_everywhere(self):
        g, f = _bests(6)
        _, ig, if_ = migration.migrate(
            pool_lib.pool_init(4, GEN), g, f, jax.random.key(0),
            MigrationConfig(topology="broadcast_best"))
        assert (np.asarray(if_) == 5.0).all()
        np.testing.assert_array_equal(
            np.asarray(ig), np.full((6, GEN.length), 5, np.int8))

    def test_pool_topology_bit_for_bit_with_legacy(self):
        """The refactored 'pool' dispatch reproduces the pre-refactor
        migrate_batch implementation exactly at fixed seed."""
        def legacy_migrate_batch(pool, bg, bf, rng, available=True):
            n = bg.shape[0]
            available = jnp.asarray(available)
            new_pool = pool_put_batch(pool, bg, bf)
            pool = jax.tree.map(lambda a, b: jnp.where(available, a, b),
                                new_pool, pool)
            keys = jax.random.split(rng, n)
            genomes, fits = jax.vmap(
                lambda k: pool_get_random(pool, k))(keys)
            return pool, genomes, jnp.where(available, fits, NEG_INF)

        g, f = _bests(6)
        for seed in range(3):
            rng = jax.random.key(seed)
            p0 = pool_lib.pool_init(4, GEN)
            ref = legacy_migrate_batch(p0, g, f, rng)
            got = migration.migrate(p0, g, f, rng, MigrationConfig())
            for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(got)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestFusedDriver:
    CFG = EAConfig(max_pop=32, min_pop=16, generations_per_epoch=5,
                   mutation_rate=0.05)

    @pytest.mark.parametrize("topo", ALL_TOPOLOGIES)
    def test_all_topologies_run_fused_and_host(self, topo):
        mig = MigrationConfig(topology=topo, pool_capacity=8)
        isl, pool, ep, stats = run_fused(
            make_onemax(16), self.CFG, mig, n_islands=4, max_epochs=4,
            rng=jax.random.key(0), return_stats=True)
        res = run_experiment(make_onemax(16), self.CFG, mig, n_islands=4,
                             max_epochs=4, rng=jax.random.key(0),
                             stop_on_success=False)
        assert stats.best_fitness.shape == (4,)
        assert np.isfinite(float(isl.best_fitness.max()))
        assert np.isfinite(float(res.islands.best_fitness.max()))

    def test_stats_stacked_and_monotone(self):
        _, _, _, stats = run_fused(make_onemax(48), self.CFG, n_islands=4,
                                   max_epochs=6, rng=jax.random.key(1),
                                   return_stats=True)
        bests = np.asarray(stats.best_fitness)
        evals = np.asarray(stats.total_evaluations)
        assert bests.shape == (6,)
        assert (np.diff(bests) >= 0).all()
        assert (np.diff(evals) >= 0).all()

    def test_early_stop_freezes_carry(self):
        isl, _, ep, stats = run_fused(make_onemax(8), self.CFG, n_islands=4,
                                      max_epochs=10, rng=jax.random.key(2),
                                      return_stats=True)
        ep = int(ep)
        assert ep < 10
        epochs_col = np.asarray(stats.epoch)
        assert epochs_col.max() == ep          # frozen after the stop
        evals = np.asarray(stats.total_evaluations)
        assert (evals[ep:] == evals[-1]).all()  # no phantom work

    def test_compile_cache_reused(self):
        problem = make_onemax(24)
        mig = MigrationConfig(topology="ring")
        run_fused(problem, self.CFG, mig, n_islands=4, max_epochs=2,
                  rng=jax.random.key(0))
        key = (id(problem),
               ("batched", self.CFG, mig, False, 2, False, False))
        import repro.core.evolution as evo
        jitted = evo._FUSED_CACHE[key][1]
        run_fused(problem, self.CFG, mig, n_islands=4, max_epochs=2,
                  rng=jax.random.key(1))
        assert evo._FUSED_CACHE[key][1] is jitted


class TestHostBridge:
    def test_best_out_immigrants_in(self):
        server = PoolServer(capacity=16, seed=0)
        server.put(np.full(8, 7, np.int8), 99.0, uuid=42)  # volunteer entry
        bridge = HostBridge(server, pull=16)
        pool = pool_lib.pool_init(24, GEN)
        g, f = _bests(4)
        pool = pool_put_batch(pool, g, f)
        pool = bridge.sync(pool, epoch=1)
        # volunteer's 99.0 entry is now in the device pool (16 uniform
        # draws over a 2-entry server can't all miss it at this seed)
        assert float(pool.fitness.max()) == 99.0
        # the device pool's best reached the server
        assert server.stats()["puts"] == 2
        assert bridge.pushed == 1 and bridge.pulled == 16

    def test_server_down_is_tolerated(self):
        server = PoolServer(capacity=16, seed=0)
        server.kill()
        bridge = HostBridge(server)
        pool = pool_put_batch(pool_lib.pool_init(8, GEN), *_bests(4))
        before = np.asarray(pool.fitness).copy()
        pool = bridge.sync(pool, epoch=1)
        np.testing.assert_array_equal(np.asarray(pool.fitness), before)
        assert bridge.lost >= 1

    def test_sync_accepts_device_get_numpy_pool(self):
        """run_sharded hands sync a device_get'd (numpy) PoolState; the
        pull-insert path must re-wrap it for the .at[] update."""
        server = PoolServer(capacity=16, seed=0)
        server.put(np.full(8, 1, np.int8), 5.0)
        bridge = HostBridge(server, pull=2)
        pool = pool_put_batch(pool_lib.pool_init(8, GEN), *_bests(4))
        np_pool = jax.tree.map(np.asarray, pool)   # what device_get returns
        out = bridge.sync(np_pool, epoch=1)
        # the two pulled entries were inserted into the (numpy) pool
        assert bridge.pulled == 2
        assert int(np.asarray(out.count)) == int(np.asarray(pool.count)) + 2

    def test_off_cycle_epochs_skip(self):
        server = PoolServer(capacity=16, seed=0)
        bridge = HostBridge(server, every=3)
        pool = pool_put_batch(pool_lib.pool_init(8, GEN), *_bests(4))
        bridge.sync(pool, epoch=1)
        bridge.sync(pool, epoch=2)
        assert bridge.pushed == 0
        bridge.sync(pool, epoch=3)
        assert bridge.pushed == 1

    def test_run_experiment_wiring(self):
        server = PoolServer(capacity=32, seed=0)
        server.put(np.ones(16, np.int8), 16.0)  # a solved volunteer genome
        bridge = HostBridge(server, pull=4)
        cfg = EAConfig(max_pop=32, min_pop=16, generations_per_epoch=2)
        res = run_experiment(make_onemax(16), cfg, n_islands=4, max_epochs=4,
                             rng=jax.random.key(0), host_bridge=bridge)
        assert bridge.pushed >= 1
        # the volunteer's perfect genome can seed the device pool
        assert res.success


SPMD_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from functools import partial
    from jax.sharding import PartitionSpec as P
    from repro.core import EAConfig, MigrationConfig, make_onemax, migration
    from repro.core import pool as pool_lib
    from repro.core.pool import NEG_INF, pool_get_random, pool_put_batch
    from repro.core.sharded import run_fused_sharded, run_sharded
    from repro.core.types import GenomeSpec, PoolState
    from repro.launch.mesh import make_host_mesh

    AX = "islands"
    mesh = make_host_mesh()
    N_SHARDS = mesh.shape[AX]
    PER = 2
    N = N_SHARDS * PER
    GEN = GenomeSpec("binary", 8)
    out = {}

    g = (jnp.arange(N, dtype=jnp.int8)[:, None]
         * jnp.ones((N, GEN.length), jnp.int8))
    f = jnp.arange(N, dtype=jnp.float32)
    POOL_SPEC = PoolState(*[P()] * len(PoolState._fields))

    def run_topo(topo, epoch=0, available=True, cap=32):
        mig = MigrationConfig(topology=topo, pool_capacity=cap)

        def body(pool, bg, bf, rng):
            pool, ig, if_ = migration.migrate(
                pool, bg, bf, rng, mig, axis=AX, epoch=epoch,
                available=available)
            # stack each shard's pool replica for host-side comparison
            return jax.tree.map(lambda x: x[None], pool), ig, if_

        fn = jax.shard_map(
            body, mesh=mesh,
            in_specs=(POOL_SPEC, P(AX), P(AX), P()),
            out_specs=(PoolState(*[P(AX)] * len(PoolState._fields)),
                       P(AX), P(AX)),
            check_vma=False)
        pool0 = pool_lib.pool_init(cap, GEN)
        return fn(pool0, g, f, jax.random.key(7))

    # (a) pool-replica consistency across shards
    pools, ig, if_ = run_topo("pool")
    out["pool_replicas_equal"] = all(
        bool((np.asarray(x) == np.asarray(x)[0]).all())
        for x in jax.tree.leaves(pools))
    out["pool_put_all"] = int(np.asarray(pools.count)[0]) == N

    # (b) ring / torus / random_graph deliver each shard's best exactly once
    for topo in ("ring", "torus", "random_graph"):
        for epoch in (0, 1):
            _, ig, if_ = run_topo(topo, epoch=epoch)
            ok = sorted(np.asarray(if_).tolist()) == sorted(
                np.asarray(f).tolist())
            out[f"{topo}_e{epoch}_exactly_once"] = bool(ok)
    # ring: shard s receives shard s-1's block
    _, ig, if_ = run_topo("ring")
    exp = np.roll(np.asarray(f).reshape(N_SHARDS, PER), 1, axis=0).ravel()
    out["ring_shift"] = bool((np.asarray(if_) == exp).all())

    # broadcast_best: everyone gets the global elite
    _, ig, if_ = run_topo("broadcast_best")
    out["broadcast_elite"] = bool(
        (np.asarray(if_) == float(N - 1)).all()
        and (np.asarray(ig) == N - 1).all())

    # (c) available=False is a no-op for every topology
    for topo in migration.available_topologies():
        pools, _, if_ = run_topo(topo, available=False)
        out[f"{topo}_down_noop"] = bool(
            np.isneginf(np.asarray(if_)).all()
            and int(np.asarray(pools.count)[0]) == 0)

    # (d) pool topology bit-for-bit vs the legacy migrate_sharded all_gather
    def legacy_migrate_sharded(pool, bg, bf, rng, axis, available=True):
        all_g = jax.lax.all_gather(bg, axis, tiled=True)
        all_f = jax.lax.all_gather(bf, axis, tiled=True)
        available = jnp.asarray(available)
        new_pool = pool_put_batch(pool, all_g, all_f)
        pool = jax.tree.map(lambda a, b: jnp.where(available, a, b),
                            new_pool, pool)
        n_local = bg.shape[0]
        rng = jax.random.fold_in(rng, jax.lax.axis_index(axis))
        keys = jax.random.split(rng, n_local)
        genomes, fits = jax.vmap(lambda k: pool_get_random(pool, k))(keys)
        return pool, genomes, jnp.where(available, fits, NEG_INF)

    def run_impl(impl):
        def body(pool, bg, bf, rng):
            return impl(pool, bg, bf, rng)
        fn = jax.shard_map(body, mesh=mesh,
                           in_specs=(POOL_SPEC, P(AX), P(AX), P()),
                           out_specs=(POOL_SPEC, P(AX), P(AX)),
                           check_vma=False)
        return fn(pool_lib.pool_init(16, GEN), g, f, jax.random.key(11))

    mig = MigrationConfig(pool_capacity=16)
    ref = run_impl(partial(legacy_migrate_sharded, axis=AX))
    got = run_impl(lambda p, bg, bf, r: migration.migrate(
        p, bg, bf, r, mig, axis=AX, epoch=3))
    out["pool_bit_for_bit"] = all(
        bool((np.asarray(a) == np.asarray(b)).all())
        for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(got)))

    # (e) every topology under both drivers (host loop + fused scan)
    cfg = EAConfig(max_pop=32, min_pop=16, generations_per_epoch=3,
                   mutation_rate=0.05)
    for topo in migration.available_topologies():
        mig = MigrationConfig(topology=topo, pool_capacity=16)
        isl, _, ep = run_sharded(mesh, make_onemax(24), cfg, mig,
                                 islands_per_shard=2, max_epochs=3,
                                 rng=jax.random.key(0))
        isl2, _, ep2, stats = run_fused_sharded(
            mesh, make_onemax(24), cfg, mig, islands_per_shard=2,
            max_epochs=3, rng=jax.random.key(0), return_stats=True)
        out[f"{topo}_drivers"] = bool(
            np.isfinite(float(isl.best_fitness.max()))
            and np.isfinite(float(isl2.best_fitness.max()))
            and np.asarray(stats.best_fitness).shape == (3,))

    print(json.dumps(out))
""")


def test_spmd_migration_properties():
    env = dict(os.environ,
               PYTHONPATH=os.path.join(REPO, "src")
               + os.pathsep + os.environ.get("PYTHONPATH", ""))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", SPMD_SCRIPT], env=env,
                          cwd=REPO, capture_output=True, text=True,
                          timeout=1200)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    bad = {k: v for k, v in out.items() if v is not True and not (
        isinstance(v, bool) and v)}
    assert not bad, f"failed SPMD properties: {bad}"

"""Immigrant-acceptance engine (core.acceptance): registry, the 'always'
bit-for-bit anchor, policy semantics, the per-island receive gate, the host
PoolServer mirror, diversity preservation, degenerate-async equivalence,
and SPMD replica consistency (subprocess-isolated on 8 fake devices)."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (AcceptanceConfig, AsyncConfig, EAConfig,
                        MigrationConfig, PoolServer, acceptance, make_onemax,
                        make_trap, run_fused, run_fused_async)
from repro.core import pool as pool_lib
from repro.core.pool import NEG_INF
from repro.core.types import GenomeSpec, PoolState

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ALL_POLICIES = ("always", "elitist", "crowding", "dedup")
GEN = GenomeSpec("binary", 8)
CFG = EAConfig(max_pop=32, min_pop=16, generations_per_epoch=5,
               mutation_rate=0.05)


def _legacy_pool_put_batch(pool, genomes, fitness, valid=None):
    """The pre-engine pool_put_batch, verbatim — the bit-for-bit anchor."""
    k = genomes.shape[0]
    cap = pool.genomes.shape[0]
    if valid is None:
        valid = jnp.ones((k,), bool)
    if k > cap:
        score = jnp.where(valid, fitness, NEG_INF)
        _, top = jax.lax.top_k(score, cap)
        genomes, fitness, valid = genomes[top], fitness[top], valid[top]
        k = cap
    order = jnp.argsort(~valid, stable=True)
    genomes, fitness = genomes[order], fitness[order]
    n_valid = valid.sum().astype(jnp.int32)
    slots = (pool.ptr + jnp.arange(k, dtype=jnp.int32)) % cap
    write = jnp.arange(k) < n_valid
    safe_slots = jnp.where(write, slots, cap)
    new_genomes = pool.genomes.at[safe_slots].set(
        genomes.astype(pool.genomes.dtype), mode="drop")
    new_fitness = pool.fitness.at[safe_slots].set(fitness, mode="drop")
    return PoolState(
        genomes=new_genomes, fitness=new_fitness,
        ptr=(pool.ptr + n_valid) % cap,
        count=jnp.minimum(pool.count + n_valid, cap))


def _mk_pool(fits, cap=None, gen=GEN):
    """A pool whose first len(fits) slots hold identifiable residents."""
    cap = cap or len(fits)
    pool = pool_lib.pool_init(cap, gen)
    g = (jnp.arange(len(fits), dtype=jnp.int8)[:, None]
         * jnp.ones((len(fits), gen.length), jnp.int8))
    return pool_lib.pool_put_batch(pool, g, jnp.asarray(fits, jnp.float32))


class TestRegistry:
    def test_builtins_registered(self):
        assert set(ALL_POLICIES) <= set(acceptance.available_policies())

    def test_unknown_policy_raises(self):
        with pytest.raises(KeyError, match="unknown acceptance policy"):
            acceptance.get_policy("no_such_policy")

    def test_custom_registration_dispatches(self):
        @acceptance.register_policy("_test_reject_all")
        def reject_all(pool_g, pool_f, cand_g, cand_f, valid, rng, *,
                       ptr, count, acc):
            cap = pool_f.shape[0]
            return (jnp.full((cand_f.shape[0],), cap, jnp.int32), ptr,
                    count)

        try:
            pool = pool_lib.pool_init(4, GEN)
            out = pool_lib.pool_put_batch(
                pool, jnp.ones((2, 8), jnp.int8), jnp.array([1.0, 2.0]),
                acc=AcceptanceConfig(policy="_test_reject_all"))
            assert int(out.count) == 0
            assert np.isneginf(np.asarray(out.fitness)).all()
        finally:
            del acceptance.ACCEPTANCE_POLICIES["_test_reject_all"]

    def test_config_validation(self):
        with pytest.raises(ValueError):
            AcceptanceConfig(epsilon=-1.0)
        with pytest.raises(ValueError):
            AcceptanceConfig(metric="cosine")


class TestAlwaysBitForBit:
    """AcceptanceConfig(policy='always') == the legacy ring insert,
    bit for bit, over random batches, valid masks and overflow."""

    @pytest.mark.parametrize("kind", ["binary", "float"])
    def test_random_streams(self, kind):
        rngs = np.random.default_rng(0 if kind == "binary" else 1)
        gen = GenomeSpec(kind, 6)
        for _ in range(40):
            cap = int(rngs.integers(1, 9))
            k = int(rngs.integers(1, 14))    # includes k > cap overflow
            ref = pool_lib.pool_init(cap, gen)
            got = pool_lib.pool_init(cap, gen)
            for step in range(3):
                if kind == "binary":
                    g = rngs.integers(0, 2, (k, 6)).astype(np.int8)
                else:
                    g = rngs.normal(size=(k, 6)).astype(np.float32)
                f = rngs.normal(size=(k,)).astype(np.float32)
                valid = (None if step == 0
                         else jnp.asarray(rngs.random(k) < 0.7))
                ref = _legacy_pool_put_batch(ref, jnp.asarray(g),
                                             jnp.asarray(f), valid)
                got = pool_lib.pool_put_batch(
                    got, jnp.asarray(g), jnp.asarray(f), valid,
                    acc=AcceptanceConfig(policy="always"),
                    rng=jax.random.key(step))
                for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(got)):
                    np.testing.assert_array_equal(np.asarray(a),
                                                  np.asarray(b))

    def test_default_acc_is_always(self):
        """pool_put_batch with no acc kwarg is the legacy path."""
        g = jnp.ones((3, 8), jnp.int8)
        f = jnp.array([1.0, 2.0, 3.0])
        ref = _legacy_pool_put_batch(pool_lib.pool_init(4, GEN), g, f)
        got = pool_lib.pool_put_batch(pool_lib.pool_init(4, GEN), g, f)
        for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(got)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestElitist:
    ACC = AcceptanceConfig(policy="elitist")

    def test_fills_empty_pool_first(self):
        pool = pool_lib.pool_init(4, GEN)
        pool = pool_lib.pool_put_batch(
            pool, jnp.ones((2, 8), jnp.int8), jnp.array([5.0, 3.0]),
            acc=self.ACC)
        assert int(pool.count) == 2
        kept = sorted(x for x in np.asarray(pool.fitness).tolist()
                      if np.isfinite(x))
        assert kept == [3.0, 5.0]

    def test_replaces_worst_only_if_better(self):
        pool = _mk_pool([5.0, 1.0, 3.0])
        out = pool_lib.pool_put_batch(
            pool, jnp.full((1, 8), 9, jnp.int8), jnp.array([2.0]),
            acc=self.ACC)
        fits = sorted(np.asarray(out.fitness).tolist())
        assert fits == [2.0, 3.0, 5.0]      # the 1.0 resident lost
        assert int(out.count) == 3
        out2 = pool_lib.pool_put_batch(
            out, jnp.full((1, 8), 9, jnp.int8), jnp.array([1.5]),
            acc=self.ACC)
        assert sorted(np.asarray(out2.fitness).tolist()) == fits  # rejected

    def test_batch_challenges_ranked_worst(self):
        """Best candidate vs worst resident, 2nd vs 2nd-worst, ..."""
        pool = _mk_pool([0.0, 5.0])
        out = pool_lib.pool_put_batch(
            pool, jnp.full((2, 8), 7, jnp.int8), jnp.array([1.0, 9.0]),
            acc=self.ACC)
        # 9.0 beats worst (0.0); 1.0 challenges 5.0 and loses
        assert sorted(np.asarray(out.fitness).tolist()) == [5.0, 9.0]


class TestCrowding:
    ACC = AcceptanceConfig(policy="crowding")

    def test_replaces_nearest_if_fitter(self):
        gen = GenomeSpec("binary", 4)
        pool = pool_lib.pool_init(2, gen)
        pool = pool_lib.pool_put_batch(
            pool, jnp.asarray([[0, 0, 0, 0], [1, 1, 1, 1]], jnp.int8),
            jnp.array([1.0, 2.0]))
        cand = jnp.asarray([[1, 1, 1, 0]], jnp.int8)   # nearest: all-ones
        out = pool_lib.pool_put_batch(pool, cand, jnp.array([5.0]),
                                      acc=self.ACC)
        fits = np.asarray(out.fitness).tolist()
        assert fits == [1.0, 5.0]           # slot 1 (nearest) was replaced

    def test_nearest_not_fitter_is_rejected(self):
        gen = GenomeSpec("binary", 4)
        pool = pool_lib.pool_init(2, gen)
        pool = pool_lib.pool_put_batch(
            pool, jnp.asarray([[0, 0, 0, 0], [1, 1, 1, 1]], jnp.int8),
            jnp.array([1.0, 9.0]))
        cand = jnp.asarray([[1, 1, 1, 0]], jnp.int8)   # nearest holds 9.0
        out = pool_lib.pool_put_batch(pool, cand, jnp.array([5.0]),
                                      acc=self.ACC)
        assert np.asarray(out.fitness).tolist() == [1.0, 9.0]

    def test_conflict_resolved_to_fittest_candidate(self):
        gen = GenomeSpec("binary", 4)
        pool = pool_lib.pool_init(1, gen)
        pool = pool_lib.pool_put_batch(
            pool, jnp.asarray([[1, 1, 1, 1]], jnp.int8), jnp.array([1.0]))
        cands = jnp.asarray([[1, 1, 1, 0], [1, 1, 0, 1]], jnp.int8)
        out = pool_lib.pool_put_batch(pool, cands, jnp.array([3.0, 7.0]),
                                      acc=self.ACC)
        assert np.asarray(out.fitness).tolist() == [7.0]
        np.testing.assert_array_equal(np.asarray(out.genomes[0]),
                                      [1, 1, 0, 1])

    def test_diversity_never_collapses_below_always(self):
        """The headline property: on a deceptive trap run the crowding
        pool keeps at least the accept-everything baseline's diversity."""
        from benchmarks.pool_throughput import _mean_pairwise_distance
        problem = make_trap(n_traps=6, l=4)
        div = {}
        for pol in ("always", "crowding"):
            mig = MigrationConfig(
                pool_capacity=16, topology="pool",
                acceptance=AcceptanceConfig(policy=pol))
            _, pool, _ = run_fused(problem, CFG, mig, n_islands=8,
                                   max_epochs=8, rng=jax.random.key(3),
                                   w2=True)
            count = int(np.asarray(pool.count))
            assert count >= 2
            div[pol] = _mean_pairwise_distance(
                np.asarray(pool.genomes)[:count])
        assert div["crowding"] >= div["always"]


class TestDedup:
    def test_rejects_epsilon_duplicates(self):
        gen = GenomeSpec("binary", 4)
        pool = pool_lib.pool_init(4, gen)
        pool = pool_lib.pool_put_batch(
            pool, jnp.asarray([[1, 1, 0, 0]], jnp.int8), jnp.array([5.0]))
        acc = AcceptanceConfig(policy="dedup", epsilon=1.0)
        # hamming distance 1 from the resident -> rejected despite fitter
        out = pool_lib.pool_put_batch(
            pool, jnp.asarray([[1, 1, 1, 0]], jnp.int8), jnp.array([9.0]),
            acc=acc)
        assert int(out.count) == 1
        assert float(out.fitness[0]) == 5.0
        # distance 2 > epsilon -> accepted into a free slot
        out = pool_lib.pool_put_batch(
            pool, jnp.asarray([[0, 0, 1, 1]], jnp.int8), jnp.array([9.0]),
            acc=acc)
        assert int(out.count) == 2

    def test_epsilon_zero_rejects_exact_clones_only(self):
        gen = GenomeSpec("binary", 4)
        pool = pool_lib.pool_init(4, gen)
        pool = pool_lib.pool_put_batch(
            pool, jnp.asarray([[1, 0, 1, 0]], jnp.int8), jnp.array([5.0]))
        acc = AcceptanceConfig(policy="dedup")
        clone = pool_lib.pool_put_batch(
            pool, jnp.asarray([[1, 0, 1, 0]], jnp.int8), jnp.array([9.0]),
            acc=acc)
        assert int(clone.count) == 1                 # exact clone rejected
        near = pool_lib.pool_put_batch(
            pool, jnp.asarray([[1, 0, 1, 1]], jnp.int8), jnp.array([9.0]),
            acc=acc)
        assert int(near.count) == 2                  # distance 1 accepted

    def test_rejects_duplicates_within_one_batch(self):
        """Two epsilon-close candidates in a single PUT batch: only the
        first survives — matching the host mirror's one-at-a-time stream
        (which would make the first a resident before the second arrives)."""
        gen = GenomeSpec("binary", 4)
        pool = pool_lib.pool_init(4, gen)
        cands = jnp.asarray([[1, 0, 1, 0], [1, 0, 1, 0], [0, 1, 0, 1]],
                            jnp.int8)
        out = pool_lib.pool_put_batch(
            pool, cands, jnp.array([5.0, 9.0, 7.0]),
            acc=AcceptanceConfig(policy="dedup"))
        assert int(out.count) == 2               # the clone was rejected
        kept = sorted(x for x in np.asarray(out.fitness).tolist()
                      if np.isfinite(x))
        assert kept == [5.0, 7.0]

    def test_survivors_fall_through_to_elitist(self):
        gen = GenomeSpec("binary", 4)
        pool = pool_lib.pool_init(1, gen)
        pool = pool_lib.pool_put_batch(
            pool, jnp.asarray([[1, 1, 1, 1]], jnp.int8), jnp.array([5.0]))
        acc = AcceptanceConfig(policy="dedup")
        worse = pool_lib.pool_put_batch(
            pool, jnp.asarray([[0, 0, 0, 0]], jnp.int8), jnp.array([2.0]),
            acc=acc)
        assert float(worse.fitness[0]) == 5.0        # not fitter -> reject
        better = pool_lib.pool_put_batch(
            pool, jnp.asarray([[0, 0, 0, 0]], jnp.int8), jnp.array([7.0]),
            acc=acc)
        assert float(better.fitness[0]) == 7.0


class TestReceiveGate:
    def _dest(self, fits):
        n = len(fits)
        g = (jnp.arange(n, dtype=jnp.int8)[:, None]
             * jnp.ones((n, GEN.length), jnp.int8))
        return g, jnp.asarray(fits, jnp.float32)

    def test_elitist_gate_rejects_not_fitter(self):
        dg, df = self._dest([5.0, 1.0])
        imm_g = jnp.ones((2, GEN.length), jnp.int8)
        imm_f = jnp.array([3.0, 3.0])
        out = acceptance.gate_immigrants(
            dg, df, imm_g, imm_f, jax.random.key(0),
            AcceptanceConfig(policy="elitist"))
        assert np.isneginf(float(out[0]))            # 3.0 <= 5.0 rejected
        assert float(out[1]) == 3.0                  # 3.0 > 1.0 accepted

    def test_dedup_gate_rejects_clone_of_own_best(self):
        dg, df = self._dest([5.0, 5.0])
        imm_f = jnp.array([9.0, 9.0])
        imm_g = jnp.stack([dg[0], jnp.full((GEN.length,), 7, jnp.int8)])
        out = acceptance.gate_immigrants(
            dg, df, imm_g, imm_f, jax.random.key(0),
            AcceptanceConfig(policy="dedup"))
        assert np.isneginf(float(out[0]))            # clone of own best
        assert float(out[1]) == 9.0

    def test_neg_inf_immigrants_stay_rejected(self):
        dg, df = self._dest([1.0])
        out = acceptance.gate_immigrants(
            dg, df, jnp.ones((1, GEN.length), jnp.int8),
            jnp.asarray([NEG_INF]), jax.random.key(0),
            AcceptanceConfig(policy="crowding"))
        assert np.isneginf(float(out[0]))

    @pytest.mark.parametrize("topo", ["pool", "ring", "broadcast_best"])
    def test_migrate_dispatches_gate_for_every_topology(self, topo):
        """With an elitist acceptance, deliveries not fitter than the
        destination's own best arrive as -inf through migrate()."""
        from repro.core import migration
        n = 4
        g = (jnp.arange(n, dtype=jnp.int8)[:, None]
             * jnp.ones((n, GEN.length), jnp.int8))
        f = jnp.arange(n, dtype=jnp.float32)
        mig = MigrationConfig(topology=topo, pool_capacity=8,
                              acceptance=AcceptanceConfig(policy="elitist"))
        _, _, imm_f = migration.migrate(
            pool_lib.pool_init(8, GEN), g, f, jax.random.key(0), mig,
            epoch=0)
        imm_f = np.asarray(imm_f)
        # every finite delivery is strictly fitter than the dest's own best
        finite = np.isfinite(imm_f)
        assert (imm_f[finite] > np.asarray(f)[finite]).all()
        # the worst island (island 0 has best -inf-adjacent 0.0) can still
        # receive; the globally best island can never hear a fitter genome
        assert np.isneginf(imm_f[np.argmax(np.asarray(f))])


class TestHostMirror:
    """Device pool and host PoolServer make the same decisions for the
    same single-candidate stream."""

    @pytest.mark.parametrize("policy", ["elitist", "crowding", "dedup"])
    def test_same_resident_multiset(self, policy):
        cap = 4
        acc = AcceptanceConfig(policy=policy, epsilon=0.0)
        server = PoolServer(capacity=cap, acceptance=acc)
        pool = pool_lib.pool_init(cap, GEN)
        rngs = np.random.default_rng(5)
        for i in range(32):
            g = rngs.integers(0, 2, GEN.length).astype(np.int8)
            f = float(np.round(rngs.normal(), 3))
            server.put(g, f)
            pool = pool_lib.pool_put_batch(
                pool, jnp.asarray(g)[None], jnp.asarray([f]), acc=acc)
        dev = sorted(x for x in np.asarray(pool.fitness).tolist()
                     if np.isfinite(x))
        host = sorted(e.fitness for e in server._entries)
        assert dev == pytest.approx(host)

    def test_host_rejections_counted(self):
        acc = AcceptanceConfig(policy="elitist")
        server = PoolServer(capacity=1, acceptance=acc)
        server.put(np.zeros(4, np.int8), 5.0)
        server.put(np.ones(4, np.int8), 1.0)         # worse -> rejected
        st = server.stats()
        assert st["rejected"] == 1 and st["size"] == 1
        assert st["best_fitness"] == 5.0

    def test_unknown_policy_host_mirror_raises(self):
        with pytest.raises(KeyError, match="no host mirror"):
            acceptance.host_accept(
                np.zeros((1, 4)), np.zeros(1), np.zeros(4), 1.0,
                AcceptanceConfig(policy="nope"), capacity=1)


class TestDegenerateAsyncEquivalence:
    """The PR-2 anchor survives the new axis: degenerate async == sync,
    bit for bit, under every acceptance policy."""

    @pytest.mark.parametrize("policy", ALL_POLICIES)
    @pytest.mark.parametrize("topo", ["pool", "ring"])
    def test_fused_bit_for_bit(self, policy, topo):
        problem = make_onemax(24)
        mig = MigrationConfig(topology=topo, pool_capacity=8,
                              acceptance=AcceptanceConfig(policy=policy))
        sync = run_fused(problem, CFG, mig, n_islands=6, max_epochs=4,
                         rng=jax.random.key(0), w2=True)
        asyn = run_fused_async(problem, CFG, mig, AsyncConfig(),
                               n_islands=6, max_ticks=4,
                               rng=jax.random.key(0), w2=True)
        for a, b in zip(jax.tree.leaves(sync[:2]),
                        jax.tree.leaves(asyn[:2])):
            if hasattr(a, "dtype") and jax.dtypes.issubdtype(
                    a.dtype, jax.dtypes.prng_key):
                a, b = jax.random.key_data(a), jax.random.key_data(b)
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


SPMD_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.core import (AcceptanceConfig, EAConfig, MigrationConfig,
                            make_onemax, migration)
    from repro.core import pool as pool_lib
    from repro.core.sharded import run_fused_sharded
    from repro.core.types import GenomeSpec, PoolState
    from repro.launch.mesh import make_host_mesh

    AX = "islands"
    mesh = make_host_mesh()
    N = mesh.shape[AX] * 2
    GEN = GenomeSpec("binary", 8)
    out = {}

    g = (jnp.arange(N, dtype=jnp.int8)[:, None]
         * jnp.ones((N, GEN.length), jnp.int8))
    f = jnp.arange(N, dtype=jnp.float32)
    POOL_SPEC = PoolState(*[P()] * len(PoolState._fields))

    def run_policy(policy, available=True, cap=8):
        mig = MigrationConfig(topology="pool", pool_capacity=cap,
                              acceptance=AcceptanceConfig(policy=policy))

        def body(pool, bg, bf, rng):
            pool, ig, if_ = migration.migrate(
                pool, bg, bf, rng, mig, axis=AX, epoch=0,
                available=available)
            return jax.tree.map(lambda x: x[None], pool), ig, if_

        fn = jax.shard_map(
            body, mesh=mesh,
            in_specs=(POOL_SPEC, P(AX), P(AX), P()),
            out_specs=(PoolState(*[P(AX)] * len(PoolState._fields)),
                       P(AX), P(AX)),
            check_vma=False)
        return fn(pool_lib.pool_init(cap, GEN), g, f, jax.random.key(7))

    # every policy's pool replica is identical on every shard (the policy
    # ran on the all_gather'd candidates with a pre-fold key)
    for policy in ("always", "elitist", "crowding", "dedup"):
        pools, ig, if_ = run_policy(policy)
        out[f"{policy}_replicas_equal"] = all(
            bool((np.asarray(x) == np.asarray(x)[0]).all())
            for x in jax.tree.leaves(pools))

    # elitist on a small pool keeps the globally best contributions,
    # identically on every replica
    pools, _, _ = run_policy("elitist", cap=4)
    fits = np.asarray(pools.fitness)[0]
    out["elitist_keeps_top4"] = sorted(fits.tolist()) == [
        float(N - 4), float(N - 3), float(N - 2), float(N - 1)]

    # the sharded fused driver runs every policy end to end
    cfg = EAConfig(max_pop=32, min_pop=16, generations_per_epoch=3,
                   mutation_rate=0.05)
    for policy in ("elitist", "crowding", "dedup"):
        mig = MigrationConfig(topology="pool", pool_capacity=16,
                              acceptance=AcceptanceConfig(policy=policy))
        isl, pool, ep = run_fused_sharded(
            mesh, make_onemax(24), cfg, mig, islands_per_shard=2,
            max_epochs=3, rng=jax.random.key(0))
        out[f"{policy}_sharded_driver"] = bool(
            np.isfinite(float(isl.best_fitness.max())))
    print(json.dumps(out))
""")


def test_spmd_acceptance_replica_consistency():
    env = dict(os.environ,
               PYTHONPATH=os.path.join(REPO, "src")
               + os.pathsep + os.environ.get("PYTHONPATH", ""))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", SPMD_SCRIPT], env=env,
                          cwd=REPO, capture_output=True, text=True,
                          timeout=1200)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    bad = {k: v for k, v in out.items() if v is not True}
    assert not bad, f"failed SPMD acceptance properties: {bad}"

"""Integration tests: full NodIO experiments (host driver + fused driver)."""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (EAConfig, MigrationConfig, make_onemax,
                        make_rastrigin, make_trap, run_experiment, run_fused)
from repro.core import evolution
from repro.core.evolution import epoch_step, collect_stats
from repro.core import ga
from repro.core import island as island_lib
from repro.core import pool as pool_lib

FAST = EAConfig(max_pop=64, min_pop=32, generations_per_epoch=20,
                max_evaluations=500_000)
BENCH = Path(__file__).resolve().parents[1] / "bench"


def _leaves_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        if jax.dtypes.issubdtype(x.dtype, jax.dtypes.prng_key):
            x, y = jax.random.key_data(x), jax.random.key_data(y)
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(x, y)


class TestRunExperiment:
    def test_onemax_solves(self):
        res = run_experiment(make_onemax(32), FAST, n_islands=4, max_epochs=30,
                             rng=jax.random.key(0))
        assert res.success
        assert res.evaluations_to_solution is not None
        assert res.evaluations_to_solution <= res.evaluations

    def test_trap_paper_problem_small(self):
        """Scaled-down paper problem (8 traps) solves with migration."""
        res = run_experiment(make_trap(n_traps=8, l=4), FAST, n_islands=8,
                             max_epochs=60, rng=jax.random.key(1))
        assert res.success
        assert float(res.islands.best_fitness.max()) == pytest.approx(16.0)

    def test_stats_monotonic_evaluations(self):
        res = run_experiment(make_trap(n_traps=6, l=4), FAST, n_islands=4,
                             max_epochs=10, stop_on_success=False,
                             rng=jax.random.key(2))
        evals = [int(s.total_evaluations) for s in res.stats]
        assert all(b >= a for a, b in zip(evals, evals[1:]))

    def test_best_fitness_never_decreases(self):
        res = run_experiment(make_trap(n_traps=10, l=4), FAST, n_islands=4,
                             max_epochs=15, stop_on_success=False,
                             rng=jax.random.key(3))
        bests = [float(s.best_fitness) for s in res.stats]
        assert all(b >= a - 1e-6 for a, b in zip(bests, bests[1:]))

    def test_server_down_islands_continue(self):
        """Paper fault tolerance: server dead the whole run — islands still
        improve (they just don't migrate)."""
        res = run_experiment(make_onemax(48), FAST, n_islands=4, max_epochs=20,
                             server_up=lambda epoch: False,
                             rng=jax.random.key(4), stop_on_success=False)
        assert int(res.pool.count) == 0  # nothing ever reached the pool
        bests = [float(s.best_fitness) for s in res.stats]
        assert bests[-1] > bests[0]

    def test_intermittent_server(self):
        res = run_experiment(make_onemax(48), FAST, n_islands=4, max_epochs=12,
                             server_up=lambda e: e % 2 == 0,
                             rng=jax.random.key(5), stop_on_success=False)
        assert int(res.pool.count) > 0

    def test_w2_restarts_accumulate_experiments(self):
        cfg = EAConfig(max_pop=64, min_pop=32, generations_per_epoch=30)
        res = run_experiment(make_onemax(16), cfg, n_islands=4, max_epochs=10,
                             w2=True, rng=jax.random.key(6),
                             stop_on_success=False)
        assert int(res.stats[-1].experiments_solved) >= 2


class TestRunFused:
    def test_matches_solvability(self):
        isl, pool, epochs = run_fused(make_onemax(32), FAST, n_islands=4,
                                      max_epochs=30, rng=jax.random.key(0))
        assert float(isl.best_fitness.max()) == 32.0
        assert int(epochs) <= 30

    def test_early_exit_on_success(self):
        isl, _, epochs = run_fused(make_onemax(8), FAST, n_islands=4,
                                   max_epochs=50, rng=jax.random.key(1))
        assert int(epochs) < 50


class TestFreshState:
    """run_fused builds an experiment's fresh state as one compiled program
    per (problem, cfg, pool capacity, island count, obs), keyed by the
    experiment's key alone."""

    MIG = MigrationConfig(pool_capacity=16)

    def _eager(self, problem, key, n):
        k_init, k_loop = jax.random.split(key)
        return (island_lib.init_islands(k_init, n, problem, FAST),
                pool_lib.pool_init(self.MIG.pool_capacity, problem.genome),
                k_loop)

    def test_compiles_once_for_every_key(self):
        p = make_trap(n_traps=6, l=4)
        for i in range(3):
            run_fused(p, FAST, self.MIG, n_islands=4, max_epochs=1,
                      rng=jax.random.fold_in(jax.random.key(0), i))
        init = evolution.fused_jit(
            p, ("init", FAST, self.MIG.pool_capacity, 4, False),
            lambda: pytest.fail("run_fused left no set-up program"))
        assert init._cache_size() == 1

    def test_trap_state_is_the_eager_state_bit_for_bit(self):
        p = make_trap(n_traps=6, l=4)
        key = jax.random.key(11)
        islands, pool, epochs = run_fused(p, FAST, self.MIG, n_islands=5,
                                          max_epochs=0, rng=key)
        e_islands, e_pool, k_loop = self._eager(p, key, 5)
        _leaves_equal((islands, pool), (e_islands, e_pool))
        assert int(epochs) == 0
        state, k_init = evolution.fresh_experiment_state(
            p, FAST, self.MIG, 5, key, with_obs=True)
        _leaves_equal((state.islands, state.pool, state.key, k_init),
                      (e_islands, e_pool, k_loop,
                       jax.random.split(key)[0]))
        assert (int(state.epoch), bool(state.stopped),
                int(state.next_uuid)) == (0, False, 5)
        assert state.stats == () and state.astate == ()
        _leaves_equal(state.obs, evolution.obs_lib.init_obs(5))

    def test_float_state_matches_its_own_genes(self):
        """Compiled float fitness may round differently from op-by-op
        fitness, so a float problem's fitness is held to the genes it
        came with."""
        p = make_rastrigin(dim=16)
        key = jax.random.key(3)
        islands, pool, _ = run_fused(p, FAST, self.MIG, n_islands=4,
                                     max_epochs=0, rng=key)
        e_islands, e_pool, _ = self._eager(p, key, 4)
        for got, want in zip(jax.tree.leaves((islands, pool)),
                             jax.tree.leaves((e_islands, e_pool))):
            if jax.dtypes.issubdtype(got.dtype, jax.dtypes.prng_key):
                continue
            assert got.shape == want.shape and got.dtype == want.dtype
        _leaves_equal((islands.pop, islands.pop_size, islands.rng,
                       islands.evaluations, islands.uuid, pool),
                      (e_islands.pop, e_islands.pop_size, e_islands.rng,
                       e_islands.evaluations, e_islands.uuid, e_pool))
        refit = jax.vmap(lambda pop, n: ga.mask_fitness(
            p.evaluate(p.consts, pop), n))(islands.pop, islands.pop_size)
        np.testing.assert_allclose(
            np.asarray(islands.fitness), np.asarray(refit),
            rtol=8 * np.finfo(np.float32).eps)
        best = np.argmax(np.asarray(islands.fitness), axis=1)
        rows = np.arange(best.size)
        np.testing.assert_array_equal(
            np.asarray(islands.best_fitness),
            np.asarray(islands.fitness)[rows, best])
        np.testing.assert_array_equal(np.asarray(islands.best_genome),
                                      np.asarray(islands.pop)[rows, best])

    def test_driver_init_issues_one_launch(self, tmp_path):
        """Under the profiler, the launches that start inside driver.init
        number one per experiment (the op-by-op set-up made one per
        primitive)."""
        if str(BENCH) not in sys.path:
            sys.path.insert(0, str(BENCH))
        from harness import program
        from harness import trace

        p = make_trap(n_traps=6, l=4)
        keys = [jax.random.key(i) for i in range(3)]

        def run(key):
            return run_fused(p, FAST, self.MIG, n_islands=4, max_epochs=2,
                             rng=key)

        jax.block_until_ready(run(keys[0]))   # compiled outside the session
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("window"):
                for key in keys[1:]:
                    with jax.profiler.TraceAnnotation("experiment"):
                        jax.block_until_ready(run(key))
        finally:
            jax.profiler.stop_trace()
        tr, prog = trace.load(tmp_path), program.load(tmp_path)
        assert len(tr.spans("experiment")) == 2
        assert program.dispatches_by_span(tr, prog)["driver.init"] == 1.0


class TestMigrationEffect:
    def test_pool_accumulates_island_bests(self):
        p = make_trap(n_traps=6, l=4)
        cfg = FAST
        mig = MigrationConfig(pool_capacity=16)
        islands = island_lib.init_islands(jax.random.key(0), 4, p, cfg)
        pool = pool_lib.pool_init(mig.pool_capacity, p.genome)
        islands, pool = jax.jit(
            lambda i, q, k: epoch_step(i, q, k, p, cfg, mig, False, True)
        )(islands, pool, jax.random.key(1))
        assert int(pool.count) == 4
        # pool members are the island bests
        pf = sorted(x for x in np.asarray(pool.fitness).tolist()
                    if np.isfinite(x))
        ib = sorted(np.asarray(islands.best_fitness).tolist())
        # island bests can only have improved by the immigrant step ordering;
        # pool holds the pre-migration bests — every pool fitness must be <= island best max
        assert pf[-1] <= ib[-1] + 1e-6

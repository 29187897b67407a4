"""GA evolution-kernel engine (repro.kernels.ga): registry contracts,
counter-RNG distributions, GA operator invariants under every registered
impl, jnp<->pallas(interpret) parity (bit-exact for binary genomes), the
fused generation+evaluation path, async fire-mask inertness, and SPMD
replica parity (subprocess-isolated on 8 fake devices)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (AsyncConfig, EAConfig, MigrationConfig, make_onemax,
                        make_rastrigin, make_royal_road, make_sphere,
                        make_trap, run_fused, run_fused_async)
from repro.core import ga as core_ga
from repro.core import island as island_lib
from repro.core.async_migration import async_step, init_async_state
from repro.core.types import GenomeSpec
from repro.kernels import ga as gk
from repro.kernels.ga import prng

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BIN = GenomeSpec("binary", 24)
FLT = GenomeSpec("float", 16, -5.0, 5.0)
KERNEL_IMPLS = ("pallas", "pallas_tiled", "pallas_ref")


def _pop(rng, n, spec):
    if spec.kind == "binary":
        return jax.random.bernoulli(rng, 0.5, (n, spec.length)).astype(jnp.int8)
    return jax.random.uniform(rng, (n, spec.length), jnp.float32,
                              spec.low, spec.high)


def _fit(pop):
    return pop.astype(jnp.float32).sum(-1)


def _gen(impl, rng, pop, fit, pop_size, cfg, genome):
    kern = gk.get_kernel("generation", genome.kind, impl)
    return kern(rng, pop, fit, jnp.int32(pop_size), cfg, genome)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------
class TestRegistry:
    def test_builtin_impls_complete(self):
        for kind in ("binary", "float"):
            assert set(gk.available_impls("generation", kind)) >= {
                "jnp", "pallas", "pallas_tiled", "pallas_ref"}
            # the fused op ships for the kernel family only — the jnp impl
            # keeps evaluation in Problem.evaluate (that IS the baseline)
            assert set(gk.available_impls("generation_eval", kind)) == {
                "pallas", "pallas_tiled", "pallas_ref"}

    def test_common_impls_across_kinds(self):
        assert {"jnp", "pallas", "pallas_tiled", "pallas_ref"} <= set(
            gk.available_impls("generation"))

    def test_unknown_impl_raises_with_inventory(self):
        with pytest.raises(KeyError, match="pallas"):
            gk.get_kernel("generation", "binary", "no_such_impl")

    def test_has_kernel(self):
        assert gk.has_kernel("generation", "float", "pallas")
        assert not gk.has_kernel("generation_eval", "float", "jnp")

    def test_custom_registration_dispatches_from_ea_config(self):
        @gk.register_kernel("generation", "binary", "_test_reverse")
        def reverse_gen(rng, pop, fitness, pop_size, cfg, genome):
            return pop[::-1]

        try:
            cfg = EAConfig(max_pop=8, min_pop=8, impl="_test_reverse")
            pop = _pop(jax.random.key(0), 8, BIN)
            out = core_ga.next_generation(jax.random.key(1), pop, _fit(pop),
                                          jnp.int32(8), cfg, BIN)
            np.testing.assert_array_equal(np.asarray(out),
                                          np.asarray(pop)[::-1])
        finally:
            del gk.registry._KERNELS[("generation", "binary",
                                      "_test_reverse")]

    def test_jnp_registry_entry_is_classic_path(self):
        cfg = EAConfig(max_pop=16, min_pop=8)
        pop = _pop(jax.random.key(0), 16, BIN)
        via_registry = _gen("jnp", jax.random.key(5), pop, _fit(pop), 12,
                            cfg, BIN)
        direct = core_ga.next_generation_jnp(jax.random.key(5), pop,
                                             _fit(pop), jnp.int32(12), cfg,
                                             BIN)
        np.testing.assert_array_equal(np.asarray(via_registry),
                                      np.asarray(direct))


# ---------------------------------------------------------------------------
# Counter-based RNG
# ---------------------------------------------------------------------------
class TestPrng:
    K = (jnp.uint32(0xDEAD), jnp.uint32(0xBEEF))

    def test_deterministic(self):
        a = prng.random_bits(*self.K, (8, 16), salt=1)
        b = prng.random_bits(*self.K, (8, 16), salt=1)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_salt_and_key_separate_streams(self):
        a = prng.random_bits(*self.K, (8, 16), salt=1)
        b = prng.random_bits(*self.K, (8, 16), salt=2)
        c = prng.random_bits(jnp.uint32(1), jnp.uint32(2), (8, 16), salt=1)
        assert (np.asarray(a) != np.asarray(b)).any()
        assert (np.asarray(a) != np.asarray(c)).any()

    def test_uniform_range_and_mean(self):
        u = np.asarray(prng.uniform(*self.K, (64, 64), salt=3))
        assert u.min() >= 0.0 and u.max() < 1.0
        assert abs(u.mean() - 0.5) < 0.02

    def test_randint_bounds(self):
        r = np.asarray(prng.randint(*self.K, (32, 32), 7, salt=4))
        assert r.min() >= 0 and r.max() < 7 and r.dtype == np.int32

    def test_bernoulli_rate(self):
        b = np.asarray(prng.bernoulli(*self.K, (64, 64), 0.3, salt=5))
        assert abs(b.mean() - 0.3) < 0.03

    def test_normal_moments(self):
        z = np.asarray(prng.normal(*self.K, (64, 64), salt=6))
        assert np.isfinite(z).all()
        assert abs(z.mean()) < 0.05 and abs(z.std() - 1.0) < 0.05


# ---------------------------------------------------------------------------
# jnp <-> pallas(interpret) parity, every registered kernel configuration
# ---------------------------------------------------------------------------
PARITY_CASES = [
    (BIN, "tournament", "two_point"),
    (BIN, "tournament", "uniform"),
    (BIN, "roulette", "two_point"),
    (FLT, "tournament", "blend"),
    (FLT, "roulette", "uniform"),
]


class TestParity:
    @pytest.mark.parametrize("spec,selection,crossover", PARITY_CASES)
    @pytest.mark.parametrize("pop_size", [32, 19])  # full + padded lanes
    def test_generation_matches_oracle(self, spec, selection, crossover,
                                       pop_size):
        cfg = EAConfig(max_pop=32, min_pop=8, selection=selection,
                       crossover=crossover, mutation_rate=0.1)
        pop = _pop(jax.random.key(7), 32, spec)
        fit = _fit(pop)
        got = _gen("pallas", jax.random.key(11), pop, fit, pop_size, cfg,
                   spec)
        want = _gen("pallas_ref", jax.random.key(11), pop, fit, pop_size,
                    cfg, spec)
        if spec.kind == "binary":
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        else:
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=1e-6)

    @pytest.mark.parametrize("maker,kw", [
        (make_trap, {"n_traps": 6, "l": 4}),
        (make_onemax, {"length": 24}),
        (make_royal_road, {"n_blocks": 6, "r": 4}),
        (make_rastrigin, {"dim": 16}),
        (make_sphere, {"dim": 16}),
    ])
    def test_fused_eval_matches_separate_eval(self, maker, kw):
        """generation_eval == generation + Problem.evaluate, per impl pair."""
        problem = maker(**kw)
        spec = problem.genome
        cfg = EAConfig(max_pop=32, min_pop=8,
                       crossover="blend" if spec.kind == "float"
                       else "two_point")
        pop = problem.init_population(jax.random.key(0), 32)
        fit = problem.evaluate(problem.consts, pop)
        rng = jax.random.key(13)
        outs = {}
        for impl in KERNEL_IMPLS:
            kern = gk.get_kernel("generation_eval", spec.kind, impl)
            new_pop, new_fit = kern(rng, pop, fit, jnp.int32(24), cfg, spec,
                                    problem.fused)
            plain = _gen(impl, rng, pop, fit, 24, cfg, spec)
            want_fit = problem.evaluate(problem.consts, new_pop)
            if spec.kind == "binary":
                np.testing.assert_array_equal(np.asarray(new_pop),
                                              np.asarray(plain))
                np.testing.assert_array_equal(np.asarray(new_fit),
                                              np.asarray(want_fit))
            else:
                np.testing.assert_allclose(np.asarray(new_pop),
                                           np.asarray(plain), atol=1e-6)
                np.testing.assert_allclose(np.asarray(new_fit),
                                           np.asarray(want_fit), rtol=1e-5,
                                           atol=1e-4)
            outs[impl] = np.asarray(new_pop)
        # the grid-tiled kernel is bit-identical to the single-tile kernel
        # for BOTH genome kinds (same math, streamed); the jnp oracle is
        # bit-exact only for binary (float differs by FMA contraction)
        np.testing.assert_array_equal(outs["pallas"], outs["pallas_tiled"])
        if spec.kind == "binary":
            np.testing.assert_array_equal(outs["pallas"],
                                          outs["pallas_ref"])


# ---------------------------------------------------------------------------
# Operator invariants, per kernel impl
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("impl", KERNEL_IMPLS)
class TestInvariants:
    def test_elite_preserves_best_valid(self, impl):
        cfg = EAConfig(max_pop=32, min_pop=8, elite=2, mutation_rate=0.5)
        pop = _pop(jax.random.key(0), 32, BIN)
        fit = _fit(pop)
        masked = core_ga.mask_fitness(fit, jnp.int32(20))
        new = _gen(impl, jax.random.key(1), pop, fit, 20, cfg, BIN)
        best = np.asarray(pop[int(jnp.argmax(masked))])
        np.testing.assert_array_equal(np.asarray(new[0]), best)

    @pytest.mark.parametrize("selection", ["tournament", "roulette"])
    def test_padded_lanes_invisible(self, impl, selection):
        """Valid lanes all-zero, padded lanes all-one: with mutation off,
        no padded gene may leak into any child under either selection."""
        n, ps = 32, 20
        lanes = jnp.arange(n)[:, None]
        pop = jnp.where(lanes < ps, 0, 1).astype(jnp.int8) * jnp.ones(
            (n, BIN.length), jnp.int8)
        fit = _fit(pop)  # valid: 0.0, padded: L (tempting if selectable)
        cfg = EAConfig(max_pop=n, min_pop=8, selection=selection,
                       mutation_rate=0.0)
        new = _gen(impl, jax.random.key(2), pop, fit, ps, cfg, BIN)
        assert int(np.asarray(new).sum()) == 0

    def test_float_clipped_after_mutation(self, impl):
        cfg = EAConfig(max_pop=32, min_pop=8, mutation_rate=1.0,
                       mutation_sigma=100.0)
        pop = _pop(jax.random.key(3), 32, FLT)
        new = np.asarray(_gen(impl, jax.random.key(4), pop, _fit(pop), 32,
                              cfg, FLT))
        assert new.min() >= FLT.low and new.max() <= FLT.high

    def test_binary_stays_binary(self, impl):
        cfg = EAConfig(max_pop=32, min_pop=8, mutation_rate=0.5)
        new = np.asarray(_gen(impl, jax.random.key(5),
                              _pop(jax.random.key(6), 32, BIN),
                              _fit(_pop(jax.random.key(6), 32, BIN)), 32,
                              cfg, BIN))
        assert new.dtype == np.int8 and set(np.unique(new)) <= {0, 1}

    def test_output_shape_static_across_pop_sizes(self, impl):
        cfg = EAConfig(max_pop=32, min_pop=8)
        pop = _pop(jax.random.key(7), 32, BIN)
        for ps in (8, 20, 32):
            new = _gen(impl, jax.random.key(8), pop, _fit(pop), ps, cfg, BIN)
            assert new.shape == pop.shape and new.dtype == pop.dtype


# ---------------------------------------------------------------------------
# Grid-tiled streaming engine: bit-identity across tilings + ragged shapes
# ---------------------------------------------------------------------------
TILED_CASES = [
    # (spec, crossover, tile_pop, tile_len) — tiles chosen so the grid is
    # >=2x2x2 (pop blocks x genome blocks x gather blocks) wherever the
    # shape allows, plus ragged shapes that need padding on either axis
    (GenomeSpec("binary", 24), "two_point", 16, 8),
    (GenomeSpec("binary", 24), "uniform", 8, 24),
    (GenomeSpec("binary", 23), "two_point", 16, 16),  # ragged genome
    (GenomeSpec("float", 16, -5.0, 5.0), "blend", 8, 8),
    (GenomeSpec("float", 19, -5.0, 5.0), "uniform", 16, 8),  # ragged genome
]


class TestTiledParity:
    @pytest.mark.parametrize("spec,crossover,tile_pop,tile_len", TILED_CASES)
    @pytest.mark.parametrize("n,pop_size", [(32, 32), (32, 19), (37, 30)])
    def test_tiled_matches_untiled_and_oracle(self, spec, crossover,
                                              tile_pop, tile_len, n,
                                              pop_size):
        """Any tiling is bit-identical to the single-tile kernel (both
        genome kinds); binary genomes are additionally bit-identical to the
        jnp oracle (float differs from the oracle only by FMA contraction,
        exactly like the untiled kernel does)."""
        cfg = EAConfig(max_pop=n, min_pop=8, crossover=crossover,
                       mutation_rate=0.1)
        pop = _pop(jax.random.key(7), n, spec)
        fit = _fit(pop)
        rng = jax.random.key(11)
        untiled = _gen("pallas", rng, pop, fit, pop_size, cfg, spec)
        ref = _gen("pallas_ref", rng, pop, fit, pop_size, cfg, spec)
        kern = gk.get_kernel("generation", spec.kind, "pallas_tiled")
        tiled = kern(rng, pop, fit, jnp.int32(pop_size), cfg, spec,
                     tile_pop=tile_pop, tile_len=tile_len)
        np.testing.assert_array_equal(np.asarray(tiled),
                                      np.asarray(untiled))
        if spec.kind == "binary":
            np.testing.assert_array_equal(np.asarray(tiled),
                                          np.asarray(ref))
        else:
            np.testing.assert_allclose(np.asarray(tiled), np.asarray(ref),
                                       atol=1e-6)

    def test_tiling_invariant_across_tile_sizes(self):
        """The same call through different tile geometries is ONE stream:
        every tiling yields the same bits (the re-keyed counter RNG is
        addressed by absolute (row, col), not by tile)."""
        spec = GenomeSpec("float", 24, -5.0, 5.0)
        cfg = EAConfig(max_pop=32, min_pop=8, crossover="blend",
                       mutation_rate=0.2)
        pop = _pop(jax.random.key(1), 32, spec)
        fit = _fit(pop)
        kern = gk.get_kernel("generation", "float", "pallas_tiled")
        outs = [np.asarray(kern(jax.random.key(3), pop, fit, jnp.int32(28),
                                cfg, spec, tile_pop=tp, tile_len=tl))
                for tp, tl in ((32, 24), (16, 8), (8, 24), (8, 8), (16, 12))]
        for other in outs[1:]:
            np.testing.assert_array_equal(outs[0], other)

    @pytest.mark.parametrize("selection", ["tournament", "roulette"])
    def test_padded_lanes_invisible_under_tiling(self, selection):
        """Same contract as the untiled kernel, but forced through a
        >=2x2x2 grid: no padded gene may leak across tile boundaries."""
        n, ps = 32, 20
        lanes = jnp.arange(n)[:, None]
        pop = jnp.where(lanes < ps, 0, 1).astype(jnp.int8) * jnp.ones(
            (n, BIN.length), jnp.int8)
        fit = _fit(pop)
        cfg = EAConfig(max_pop=n, min_pop=8, selection=selection,
                       mutation_rate=0.0)
        kern = gk.get_kernel("generation", "binary", "pallas_tiled")
        new = kern(jax.random.key(2), pop, fit, jnp.int32(ps), cfg, BIN,
                   tile_pop=16, tile_len=8)
        assert int(np.asarray(new).sum()) == 0

    def test_fused_trap_fitness_accumulates_across_genome_tiles(self):
        """Fused separable eval streamed across genome tiles == whole-row
        eval, including the padded-tail correction for all-zero trap
        blocks."""
        problem = make_trap(n_traps=6, l=4)
        cfg = EAConfig(max_pop=32, min_pop=8, crossover="two_point")
        pop = problem.init_population(jax.random.key(0), 32)
        fit = problem.evaluate(problem.consts, pop)
        kern = gk.get_kernel("generation_eval", "binary", "pallas_tiled")
        for tp, tl in ((16, 8), (8, 12), (8, 24)):
            new_pop, new_fit = kern(jax.random.key(9), pop, fit,
                                    jnp.int32(24), cfg, problem.genome,
                                    problem.fused, tile_pop=tp, tile_len=tl)
            np.testing.assert_allclose(
                np.asarray(new_fit),
                np.asarray(problem.evaluate(problem.consts, new_pop)),
                rtol=1e-5, atol=1e-4)

    def test_fused_f15_matches_reference_eval(self):
        """The fused F15 path (rotation-stack streaming): tiled == untiled
        population bit-exact; fused fitness == Problem.evaluate (f15_ref)
        within fp32 tolerance for both."""
        from repro.core.problems import make_f15
        problem = make_f15(dim=64, group=8)
        cfg = EAConfig(max_pop=16, min_pop=8, crossover="blend",
                       mutation_sigma=0.3)
        pop = problem.init_population(jax.random.key(0), 16)
        fit = problem.evaluate(problem.consts, pop)
        rng = jax.random.key(21)
        outs = {}
        for impl, kw in (("pallas", {}),
                         ("pallas_tiled", {"tile_pop": 8, "tile_len": 16})):
            kern = gk.get_kernel("generation_eval", "float", impl)
            new_pop, new_fit = kern(rng, pop, fit, jnp.int32(12), cfg,
                                    problem.genome, problem.fused,
                                    consts=problem.consts, **kw)
            np.testing.assert_allclose(
                np.asarray(new_fit),
                np.asarray(problem.evaluate(problem.consts, new_pop)),
                rtol=2e-4, atol=1e-3)
            outs[impl] = np.asarray(new_pop)
        np.testing.assert_array_equal(outs["pallas"], outs["pallas_tiled"])

    def test_pallas_impl_auto_routes_beyond_vmem_budget(self):
        """impl='pallas' must hand off to the tiled engine once the untiled
        working-set estimate exceeds the VMEM budget (the routing itself —
        the actual beyond-VMEM run is benchmark territory)."""
        from repro.kernels.ga import ops
        assert ops.untiled_vmem_bytes(64, 128) <= ops.VMEM_BUDGET_BYTES
        assert ops.untiled_vmem_bytes(65536, 1024) > ops.VMEM_BUDGET_BYTES
        # f15 fused raises the estimate (perm one-hot + rotated copies)
        spec = ops.make_spec(EAConfig(max_pop=8, min_pop=8),
                             GenomeSpec("float", 1000, -5.0, 5.0),
                             fused={"eval": "f15", "m": 50, "n_groups": 20})
        assert (ops.untiled_vmem_bytes(10_000, 1000, spec)
                > ops.VMEM_BUDGET_BYTES)


class TestPrngTiling:
    K = (jnp.uint32(0x1234), jnp.uint32(0x5678))

    def test_counter_offsets_tile_into_full_stream(self):
        """A tile drawn with (offset, row_stride) reads the exact window of
        the full-array stream — the property the grid kernel rides on."""
        full = np.asarray(prng.random_bits(*self.K, (16, 24), salt=9,
                                           row_stride=24))
        for r0, c0, h, w in ((0, 0, 8, 12), (8, 12, 8, 12), (8, 0, 4, 24),
                             (4, 4, 8, 8)):
            tile = np.asarray(prng.random_bits(*self.K, (h, w), salt=9,
                                               offset=(r0, c0),
                                               row_stride=24))
            np.testing.assert_array_equal(full[r0:r0 + h, c0:c0 + w], tile)

    def test_negative_row_offset_wraps_consistently(self):
        """Child draws address rows relative to the elite offset; a
        negative row0 must wrap identically to the full draw starting
        there."""
        a = np.asarray(prng.uniform(*self.K, (8, 8), salt=3,
                                    offset=(-2, 0), row_stride=8))
        b = np.asarray(prng.uniform(*self.K, (4, 8), salt=3,
                                    offset=(-2, 0), row_stride=8))
        np.testing.assert_array_equal(a[:4], b)


class TestAutotune:
    def test_cache_roundtrip_and_reuse(self, tmp_path):
        from repro.kernels.ga import autotune
        path = tmp_path / "autotune_ga.json"
        tp, tl = autotune.best_tiles(4096, 1024, "float", cache_path=path)
        assert (tp, tl) in autotune.CANDIDATES
        cache = autotune.load_cache(path)
        key = autotune.cache_key(4096, 1024, "float")
        assert key.startswith(autotune.device_kind()) and key in cache
        entry = cache[key]
        assert (entry["tile_pop"], entry["tile_len"]) == (tp, tl)
        # second call is served from the cache file
        assert autotune.best_tiles(4096, 1024, "float",
                                   cache_path=path) == (tp, tl)
        # another shape or genome kind is tuned on its own
        autotune.best_tiles(256, 160, "binary", cache_path=path)
        assert set(autotune.load_cache(path)) == {
            key, autotune.cache_key(256, 160, "binary")}
        summary = autotune.cache_summary(path)
        assert key in summary["entries"]

    def test_force_resweeps(self, tmp_path):
        from repro.kernels.ga import autotune
        path = tmp_path / "autotune_ga.json"
        autotune.save_cache({autotune.cache_key(256, 256, "float"): {
            "tile_pop": 1, "tile_len": 1, "timed": False,
            "kind": "float", "shape": [256, 256]}}, path)
        assert autotune.best_tiles(256, 256, "float",
                                   cache_path=path) == (1, 1)
        tp, tl = autotune.best_tiles(256, 256, "float", cache_path=path,
                                     force=True)
        assert (tp, tl) in autotune.CANDIDATES

    @pytest.mark.parametrize("refuse_all", [False, True])
    def test_sweep_reports_refused_candidates(self, tmp_path, monkeypatch,
                                              refuse_all):
        """A compiled sweep names every candidate the compiler refused, and
        raises when none compiles instead of guessing a tiling."""
        from repro.kernels.ga import autotune

        def fake_time(n, L, kind, tp, tl):
            if refuse_all or tp == 512:
                raise ValueError(f"block ({tp}, {tl}) refused")
            return tp * tl * 1e-9

        monkeypatch.setattr(autotune, "interpret_mode", lambda: False)
        monkeypatch.setattr(autotune, "_time_candidate", fake_time)
        path = tmp_path / "autotune_ga.json"
        if refuse_all:
            with pytest.warns(UserWarning, match="refused"):
                with pytest.raises(RuntimeError, match="no tile candidate"):
                    autotune.best_tiles(1024, 512, "float", cache_path=path)
            assert autotune.load_cache(path) == {}
            return
        with pytest.warns(UserWarning, match="512x256 refused"):
            tp, tl = autotune.best_tiles(1024, 512, "float", cache_path=path)
        assert (tp, tl) == (128, 256)
        entry = autotune.load_cache(path)[
            autotune.cache_key(1024, 512, "float")]
        assert entry["timed"] and set(entry["refused"]) == {"512x256",
                                                            "512x512"}


    def test_sweep_runs_while_a_kernel_is_traced(self, tmp_path,
                                                 monkeypatch):
        """On the chip the sweep runs inside the caller's jit trace (the
        tile size is a static of the kernel being traced); its timed
        candidates must still execute. Here the candidates run in
        interpret mode, which the CPU can execute."""
        from repro.kernels.ga import autotune, tiling
        real = tiling.generation_tiled
        monkeypatch.setattr(autotune, "interpret_mode", lambda: False)
        monkeypatch.setattr(autotune, "CANDIDATES", ((8, 16), (16, 16)))
        monkeypatch.setattr(tiling, "generation_tiled", lambda *a, **k: real(
            *a, **{**k, "interpret": True}))
        path = tmp_path / "autotune_ga.json"
        tiles = jax.jit(lambda x: x + sum(autotune.best_tiles(
            32, 16, "float", cache_path=path)))(0)
        entry = autotune.load_cache(path)[autotune.cache_key(32, 16,
                                                             "float")]
        assert entry["timed"] and set(entry["sweep_s"]) == {"8x16", "16x16"}
        assert int(tiles) == entry["tile_pop"] + entry["tile_len"]


# ---------------------------------------------------------------------------
# Driver-level parity: fused scan, async fire masks
# ---------------------------------------------------------------------------
def _assert_trees_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        if hasattr(x, "dtype") and jax.dtypes.issubdtype(
                x.dtype, jax.dtypes.prng_key):
            x, y = jax.random.key_data(x), jax.random.key_data(y)
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


class TestDrivers:
    def test_run_fused_parity(self):
        problem = make_trap(n_traps=4, l=4)
        mig = MigrationConfig(topology="ring", pool_capacity=8)
        outs = {}
        for impl in KERNEL_IMPLS:
            cfg = EAConfig(max_pop=16, min_pop=8, generations_per_epoch=2,
                           impl=impl)
            outs[impl] = run_fused(problem, cfg, mig, n_islands=4,
                                   max_epochs=3, rng=jax.random.key(0),
                                   w2=True)
        _assert_trees_equal(outs["pallas"][:2], outs["pallas_ref"][:2])
        _assert_trees_equal(outs["pallas"][:2], outs["pallas_tiled"][:2])

    def test_run_fused_async_parity_under_fire_masks(self):
        """Heterogeneous clocks + churn: the fire-masked pallas engine is
        bit-for-bit its oracle — masked lanes stayed inert identically."""
        problem = make_trap(n_traps=4, l=4)
        mig = MigrationConfig(topology="pool", pool_capacity=8)
        acfg = AsyncConfig(min_rate=0.3, max_rate=1.0, staleness=2,
                           churn_fraction=0.5, seed=3)
        outs = {}
        for impl in KERNEL_IMPLS:
            cfg = EAConfig(max_pop=16, min_pop=8, generations_per_epoch=2,
                           impl=impl)
            outs[impl] = run_fused_async(problem, cfg, mig, acfg,
                                         n_islands=6, max_ticks=5,
                                         rng=jax.random.key(0), w2=True,
                                         return_astate=True)
        _assert_trees_equal(outs["pallas"], outs["pallas_ref"])
        _assert_trees_equal(outs["pallas"], outs["pallas_tiled"])

    def test_non_firing_islands_inert(self):
        """A tick in which no island's clock crosses the period must leave
        every island untouched under the pallas engine."""
        problem = make_onemax(16)
        cfg = EAConfig(max_pop=16, min_pop=8, generations_per_epoch=2,
                       impl="pallas")
        mig = MigrationConfig(topology="pool", pool_capacity=8)
        acfg = AsyncConfig(min_rate=0.4, max_rate=0.4)  # fires every ~3rd
        islands = island_lib.init_islands(jax.random.key(0), 4, problem, cfg)
        from repro.core import pool as pool_lib
        pool = pool_lib.pool_init(8, problem.genome)
        astate = init_async_state(jax.random.key(1), 4, acfg, 4,
                                  problem.genome)
        new_islands, _, new_astate = jax.jit(
            lambda i, p, a, k: async_step(i, p, a, k, problem, cfg, mig,
                                          acfg, False, tick=1))(
            islands, pool, astate, jax.random.key(2))
        assert int(np.asarray(new_astate.fires).sum()) == 0
        _assert_trees_equal(new_islands, islands)

    def test_royal_road_solves_with_pallas_engine(self):
        problem = make_royal_road(n_blocks=3, r=2)
        cfg = EAConfig(max_pop=32, min_pop=32, generations_per_epoch=10,
                       mutation_rate=0.05, impl="pallas")
        isl, _, ep = run_fused(problem, cfg,
                               MigrationConfig(topology="ring"),
                               n_islands=4, max_epochs=10,
                               rng=jax.random.key(0))
        assert float(np.asarray(isl.best_fitness).max()) == problem.optimum


# ---------------------------------------------------------------------------
# SPMD: the megakernel inside shard_map on the 8-fake-device mesh
# ---------------------------------------------------------------------------
SPMD_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    import numpy as np
    from repro.core import EAConfig, MigrationConfig, make_trap
    from repro.core.sharded import run_fused_sharded
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh()
    problem = make_trap(n_traps=4, l=4)
    mig = MigrationConfig(topology="ring", pool_capacity=8)
    outs = {}
    for impl in ("pallas", "pallas_ref"):
        cfg = EAConfig(max_pop=16, min_pop=8, generations_per_epoch=2,
                       impl=impl)
        outs[impl] = run_fused_sharded(mesh, problem, cfg, mig,
                                       islands_per_shard=2, max_epochs=3,
                                       rng=jax.random.key(0))
    ok = True
    for a, b in zip(jax.tree.leaves(outs["pallas"][:2]),
                    jax.tree.leaves(outs["pallas_ref"][:2])):
        if hasattr(a, "dtype") and jax.dtypes.issubdtype(
                a.dtype, jax.dtypes.prng_key):
            a, b = jax.random.key_data(a), jax.random.key_data(b)
        ok = ok and bool((np.asarray(a) == np.asarray(b)).all())
    best = float(np.asarray(outs["pallas"][0].best_fitness).max())
    print(json.dumps({"parity": ok, "n_devices": jax.device_count(),
                      "finite_best": bool(np.isfinite(best))}))
""")


def test_spmd_megakernel_parity():
    env = dict(os.environ,
               PYTHONPATH=os.path.join(REPO, "src")
               + os.pathsep + os.environ.get("PYTHONPATH", ""))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", SPMD_SCRIPT], env=env,
                          cwd=REPO, capture_output=True, text=True,
                          timeout=1200)
    assert proc.returncode == 0, proc.stderr[-4000:]
    import json
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["n_devices"] == 8
    assert out["parity"] and out["finite_best"]

"""Optimizer, schedules, clipping, gradient compression."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.optim import (adamw_init, adamw_update, clip_by_global_norm,
                         global_norm, make_schedule)
from repro.optim.compression import (_dequant_int8, _quant_int8,
                                     compress_psum, init_error)


def _params():
    return {"w": jnp.ones((4, 4), jnp.float32), "b": jnp.zeros((4,), jnp.float32),
            "nested": ({"x": jnp.full((2,), 2.0)},)}  # structural tuple!


class TestAdamW:
    def test_init_shapes(self):
        p = _params()
        st = adamw_init(p)
        assert jax.tree.structure(st.m) == jax.tree.structure(p)
        assert st.master is None  # fp32 params -> no master copy
        assert int(st.step) == 0

    def test_descends_quadratic(self):
        p = {"w": jnp.array([3.0, -2.0])}
        st = adamw_init(p)
        for _ in range(200):
            g = {"w": 2 * p["w"]}
            p, st, _ = adamw_update(g, st, p, lr=0.05, weight_decay=0.0)
        np.testing.assert_allclose(np.asarray(p["w"]), 0.0, atol=1e-2)

    def test_bf16_params_master_copy(self):
        p = {"w": jnp.ones((8,), jnp.bfloat16)}
        st = adamw_init(p)
        assert st.master is not None
        g = {"w": jnp.full((8,), 1e-3, jnp.bfloat16)}
        p2, st2, _ = adamw_update(g, st, p, lr=1e-4, weight_decay=0.0)
        assert p2["w"].dtype == jnp.bfloat16
        # master accumulates sub-bf16 updates
        assert float(jnp.abs(st2.master["w"] - 1.0).max()) > 0

    def test_weight_decay_shrinks(self):
        p = {"w": jnp.full((4,), 10.0)}
        st = adamw_init(p)
        g = {"w": jnp.zeros((4,))}
        p2, _, _ = adamw_update(g, st, p, lr=0.1, weight_decay=0.1)
        assert float(p2["w"][0]) < 10.0

    def test_structural_tuples_survive(self):
        p = _params()
        st = adamw_init(p)
        g = jax.tree.map(jnp.ones_like, p)
        p2, st2, m = adamw_update(g, st, p, lr=1e-3)
        assert jax.tree.structure(p2) == jax.tree.structure(p)
        assert int(st2.step) == 1


class TestSchedules:
    def test_cosine_warmup_and_decay(self):
        s = make_schedule("cosine", 1.0, 1000, warmup_steps=100)
        assert float(s(0)) == 0.0
        assert float(s(50)) == pytest.approx(0.5)
        assert float(s(100)) == pytest.approx(1.0, rel=1e-2)
        assert float(s(1000)) < 0.2

    def test_wsd_three_phases(self):
        s = make_schedule("wsd", 1.0, 1000, warmup_steps=100)
        assert float(s(50)) == pytest.approx(0.5)
        assert float(s(500)) == pytest.approx(1.0)   # stable phase
        assert float(s(899)) == pytest.approx(1.0)
        assert float(s(999)) < 0.1                   # decay phase

    def test_constant(self):
        s = make_schedule("constant", 0.3, 100)
        assert float(s(77)) == pytest.approx(0.3)


class TestClip:
    def test_noop_under_limit(self):
        t = {"a": jnp.array([0.3, 0.4])}
        out, norm = clip_by_global_norm(t, 1.0)
        np.testing.assert_allclose(np.asarray(out["a"]),
                                   np.asarray(t["a"]), rtol=1e-6)
        assert float(norm) == pytest.approx(0.5)

    def test_clips_over_limit(self):
        t = {"a": jnp.array([3.0, 4.0])}
        out, norm = clip_by_global_norm(t, 1.0)
        assert float(norm) == pytest.approx(5.0)
        assert float(global_norm(out)) == pytest.approx(1.0, rel=1e-5)


class TestCompression:
    def test_int8_roundtrip_error_bounded(self):
        x = jax.random.normal(jax.random.key(0), (128,))
        q, s = _quant_int8(x)
        err = float(jnp.abs(_dequant_int8(q, s) - x).max())
        assert err <= float(s) * 0.5 + 1e-6

    @pytest.mark.parametrize("method", ["none", "bf16", "int8"])
    def test_compress_psum_mean(self, method):
        """Compressed cross-pod mean approximates the true mean; error
        feedback captures the residual."""
        import os
        from jax.sharding import PartitionSpec as P
        from repro.compat import make_mesh
        mesh = make_mesh((1,), ("pod",))
        g = {"w": jax.random.normal(jax.random.key(1), (64,))}
        e = init_error(g)

        def f(g, e):
            return compress_psum(g, e, "pod", method=method)

        out, err = jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
            check_vma=False))(g, e)
        resid = np.asarray(out["w"]) + np.asarray(err["w"]) \
            - np.asarray(g["w"])
        np.testing.assert_allclose(resid, 0.0, atol=2e-2)
        if method == "none":
            np.testing.assert_allclose(np.asarray(out["w"]),
                                       np.asarray(g["w"]), rtol=1e-6)

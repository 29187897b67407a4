"""Sharding rules: divisibility, no duplicate mesh axes, ZeRO-1, batch."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.launch import shardings as sh
from repro.launch.mesh import make_host_mesh
from repro.models import build_model


@pytest.fixture(scope="module")
def mesh16():
    # fake (data=1, model=1) won't exercise divisibility; build an abstract
    # 16x16 mesh from the single CPU device via AbstractMesh
    from jax.sharding import AbstractMesh
    return AbstractMesh((16, 16), ("data", "model"))


def _pspecs(arch, mesh, mode):
    cfg = get_config(arch)
    m = build_model(cfg)
    return cfg, m, sh.tree_pspecs(m.param_axes(), m.abstract_params(), cfg,
                                  mesh, mode)


class TestParamSpecs:
    @pytest.mark.parametrize("arch", ["yi-9b", "qwen3-32b", "granite-34b",
                                      "dbrx-132b", "rwkv6-3b", "hymba-1.5b"])
    def test_no_duplicate_axes(self, arch, mesh16):
        cfg, m, specs = _pspecs(arch, mesh16, "serve")
        for spec in jax.tree.leaves(
                specs, is_leaf=lambda x: isinstance(x, P)):
            flat = []
            for e in spec:
                if isinstance(e, tuple):
                    flat.extend(e)
                elif e is not None:
                    flat.append(e)
            assert len(flat) == len(set(flat)), (arch, spec)

    def test_train_mode_ff_model_sharded(self, mesh16):
        cfg, m, specs = _pspecs("yi-9b", mesh16, "train")
        wg = specs["segments"][0][0]["ffn"]["wg"]
        assert wg == P(None, None, "model")     # (layers, d, ff)

    def test_serve_mode_fully_sharded(self, mesh16):
        cfg, m, specs = _pspecs("yi-9b", mesh16, "serve")
        wg = specs["segments"][0][0]["ffn"]["wg"]
        assert wg[1] == "data" and wg[2] == "model"

    def test_vocab_sharded_after_padding(self, mesh16):
        cfg, m, specs = _pspecs("qwen3-32b", mesh16, "train")
        assert specs["embed"][0] == "model"
        assert cfg.padded_vocab % 256 == 0

    def test_indivisible_replicated(self, mesh16):
        cfg, m, specs = _pspecs("hymba-1.5b", mesh16, "train")
        # 25 q-heads * 64 = 1600 % 16 == 0 -> shardable; kv 5*64=320 % 16 = 0
        att = specs["segments"][1][0]["mixer"]["attn"]
        assert att["wq"][-1] == "model"


class TestZero1:
    def test_moments_pick_up_data_axis(self, mesh16):
        shape = (48, 4096, 11008)
        spec = P(None, None, "model")
        z = sh.zero1_pspec(spec, shape, mesh16)
        assert z == P(None, "data", "model")

    def test_no_candidate_stays(self, mesh16):
        z = sh.zero1_pspec(P("model"), (16,), mesh16)
        assert z == P("model")


class TestBatch:
    def test_batch_over_dp(self, mesh16):
        specs = {"tokens": jax.ShapeDtypeStruct((256, 4096), jnp.int32),
                 "index": jax.ShapeDtypeStruct((), jnp.int32)}
        ps = sh.batch_pspecs(specs, mesh16)
        assert ps["tokens"] == P("data", None)
        assert ps["index"] == P()

    def test_indivisible_batch_replicates(self, mesh16):
        specs = {"tokens": jax.ShapeDtypeStruct((1, 64), jnp.int32)}
        ps = sh.batch_pspecs(specs, mesh16)
        assert ps["tokens"] == P(None, None)

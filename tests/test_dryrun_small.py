"""Dry-run machinery on a small fake-device mesh (subprocess-isolated so
the 8-device XLA_FLAGS never leaks into other tests).

The full 16x16 / 2x16x16 x 40-cell matrix runs via
``python -m repro.launch.dryrun --all`` (results in benchmarks/results/);
here we prove the machinery end-to-end at 2x4 with reduced configs.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, sys
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from repro.compat import make_mesh
    from repro.configs import get_config
    from repro.launch import shardings as sh
    from repro.launch.dryrun import collective_bytes, cost_of
    from repro.launch.steps import (abstract_train_state, make_train_step)
    from repro.models import build_model
    from repro.optim import make_schedule

    arch = sys.argv[1]
    cfg = get_config(arch, smoke=True)
    model = build_model(cfg)
    mesh = make_mesh((2, 4), ("data", "model"))
    p_shapes = model.abstract_params()
    p_pspecs = sh.tree_pspecs(model.param_axes(), p_shapes, cfg, mesh,
                              "train")
    p_shard = jax.tree.map(lambda s: NamedSharding(mesh, s), p_pspecs)
    state = abstract_train_state(model)
    opt_pspecs = sh.opt_state_pspecs(p_pspecs, p_shapes, mesh)
    state_shard = type(state)(
        params=p_shard,
        opt=jax.tree.map(lambda s: NamedSharding(mesh, s), opt_pspecs))
    B, S = 8, 32
    specs = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
             "labels": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    if cfg.n_encoder_layers:
        specs["src_embed"] = jax.ShapeDtypeStruct((B, 16, cfg.d_model),
                                                  cfg.activation_dtype)
    if cfg.family == "vlm":
        specs["vision_embed"] = jax.ShapeDtypeStruct(
            (B, cfg.vision_seq, cfg.d_model), cfg.activation_dtype)
    bshard = {k: NamedSharding(mesh, v)
              for k, v in sh.batch_pspecs(specs, mesh).items()}
    step = make_train_step(model, schedule=make_schedule("cosine", 1e-3,
                                                         100))
    fn = jax.jit(step, in_shardings=(state_shard, bshard),
                 out_shardings=(state_shard, None))
    with jax.set_mesh(mesh):
        compiled = fn.lower(state, specs).compile()
    fl, by = cost_of(compiled)
    co = collective_bytes(compiled.as_text())
    ma = compiled.memory_analysis()
    print(json.dumps({"flops": fl, "bytes": by,
                      "coll_total": co.get("total", 0.0),
                      "temp": ma.temp_size_in_bytes,
                      "devices": len(jax.devices())}))
""")


def _run(arch: str) -> dict:
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", SCRIPT, arch],
                         capture_output=True, text=True, env=env,
                         cwd=REPO, timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("arch", ["yi-9b", "olmoe-1b-7b", "rwkv6-3b",
                                  "seamless-m4t-large-v2"])
def test_smoke_config_compiles_on_8_fake_devices(arch):
    rec = _run(arch)
    assert rec["devices"] == 8
    assert rec["flops"] > 0
    assert rec["coll_total"] > 0          # sharded training must communicate
    assert rec["temp"] < 2 * 2**30        # smoke config stays tiny


def test_collective_parser_units():
    from repro.launch.dryrun import _type_bytes, collective_bytes
    assert _type_bytes("f32[16,128]") == 16 * 128 * 4
    assert _type_bytes("(bf16[8,8], u8[4])") == 8 * 8 * 2 + 4
    hlo = """
      %ag = bf16[2048,16]{1,0} all-gather(%x), replica_groups={{0,1}}
      %ar.1 = f32[1024]{0} all-reduce-start(%y), to_apply=%add
      %dn = f32[1024]{0} all-reduce-done(%ar.1)
      %rs = f32[512]{0} reduce-scatter(%z), dimensions={0}
    """
    co = collective_bytes(hlo)
    assert co["all-gather"] == 2048 * 16 * 2
    assert co["all-reduce"] == 1024 * 4 * 2   # ring factor 2, start only
    assert co["reduce-scatter"] == 512 * 4
    assert co["total"] == co["all-gather"] + co["all-reduce"] + co["reduce-scatter"]

import os
os.environ["XLA_FLAGS"] = os.environ.get(
    "REPRO_DRYRUN_XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: prove every (arch x shape x mesh) cell lowers,
compiles, shards coherently and fits — without hardware.

For each cell this driver:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. constructs ShapeDtypeStruct inputs (launch/input_specs) and
     NamedShardings from the logical-axis rules (launch/shardings),
  3. jits the step (train_step / prefill / decode) with explicit
     in_shardings, ``.lower()``s and ``.compile()``s it,
  4. records compiled.memory_analysis() (the fits-in-HBM proof) and
  5. lowers two *cost variants* (layer-scan unroll=1 and unroll=2,
     accumulation off) whose compiled cost_analysis / collective bytes are
     extrapolated to the true per-step totals — XLA counts a scanned body
     once, so   total = u1 + ratio * (u2 - u1),
     ratio = sum(n_i - 1) / #scanned-segments (exact when scanned bodies
     cost the same — true for every assigned arch; see DESIGN.md).

Usage:
  python -m repro.launch.dryrun --arch yi-9b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --all [--multi-pod] [--skip-cost]
Outputs JSON per cell under benchmarks/results/dryrun/.
"""
import argparse
import json
import re
import sys
import time
import traceback
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P


from repro.configs import ARCHS, get_config
from repro.launch import shardings as sh
from repro.launch.input_specs import SHAPES, cell_supported, input_specs
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import (abstract_train_state, make_decode_step,
                                make_prefill_step, make_train_step)
from repro.models import build_model
from repro.models.common import axes_maker
from repro.optim import make_schedule

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "benchmarks", "results", "dryrun")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

# ring-collective bytes-on-wire factor per output element
_COLL_FACTOR = {"all-reduce": 2.0, "all-gather": 1.0, "reduce-scatter": 1.0,
                "all-to-all": 1.0, "collective-permute": 1.0}


def accum_for(cfg) -> int:
    """Gradient-accumulation microbatching policy for train_4k (memory)."""
    if cfg.d_model >= 8192 or (cfg.is_moe and cfg.d_model >= 6144):
        return 16
    if cfg.d_model >= 4096 or cfg.is_moe or cfg.family == "hybrid":
        return 8
    return 4


# ---------------------------------------------------------------------------
# HLO collective parsing
# ---------------------------------------------------------------------------
_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|f8e4m3fn|f8e5m2|s64|u64|s32|u32"
                       r"|s16|u16|s8|u8|pred)\[([0-9,]*)\]")
_COLL_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:[a-z0-9]+\[[0-9,]*\][^ ]*))\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")


def _type_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Per-device wire bytes by collective type (ring factors applied)."""
    out: Dict[str, float] = {}
    for m in _COLL_RE.finditer(hlo_text):
        type_str, op = m.group(1), m.group(2)
        if op == "all-reduce" and "-done" in hlo_text[m.start():m.start() + 2]:
            continue
        b = _type_bytes(type_str) * _COLL_FACTOR[op]
        out[op] = out.get(op, 0.0) + b
    out["total"] = sum(v for k, v in out.items() if k != "total")
    return out


def cost_of(compiled) -> Tuple[float, float]:
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    return float(ca.get("flops", 0.0)), float(ca.get("bytes accessed", 0.0))


# ---------------------------------------------------------------------------
# Cell runners
# ---------------------------------------------------------------------------
def scan_ratio(model) -> float:
    """sum(n_i - 1) / #scanned-segments over all layer scans in the model."""
    segs = [s for s in (model.plan + model.enc_plan) if s.n >= 2]
    if not segs:
        return 0.0
    return sum(s.n - 1 for s in segs) / len(segs)


def build_cell(arch: str, shape: str, multi_pod: bool, *,
               unroll: int = 1, accum: Optional[int] = None,
               sharding: str = "auto", cost_mode: bool = False):
    """Returns (jitted_fn, abstract_args, mesh, model, cfg).

    sharding='dp' switches training cells to pure-DP + ZeRO-3 (see
    launch.shardings _rules 'train_dp')."""
    cfg = get_config(arch)
    model = build_model(cfg)
    mesh = make_production_mesh(multi_pod=multi_pod)
    kind = SHAPES[shape]["kind"]
    specs, axes = input_specs(cfg, model, shape)
    if kind == "train":
        mode = "train_dp" if sharding == "dp" else "train"
    else:
        mode = "serve"

    # params
    p_shapes = model.abstract_params()
    p_axes = model.param_axes()
    p_pspecs = sh.tree_pspecs(p_axes, p_shapes, cfg, mesh, mode)
    p_shard = jax.tree.map(lambda s: NamedSharding(mesh, s), p_pspecs)

    # batch-like inputs
    def spec_shardings(specs_tree, axes_tree):
        ps = sh.tree_pspecs(axes_tree, specs_tree, cfg, mesh, mode)
        return jax.tree.map(lambda s: NamedSharding(mesh, s), ps)

    if kind == "train":
        state = abstract_train_state(model)
        opt_pspecs = sh.opt_state_pspecs(p_pspecs, p_shapes, mesh, zero1=True)
        state_shard = type(state)(
            params=p_shard,
            opt=jax.tree.map(lambda s: NamedSharding(mesh, s), opt_pspecs))
        batch_shard = {
            k: NamedSharding(mesh, v)
            for k, v in sh.batch_pspecs(specs, mesh, mode).items()}
        # cost variants count with single-level remat: the two-point
        # unroll extrapolation is exact there; nested remat adds at most
        # one extra forward (~+25% FLOPs) — noted in EXPERIMENTS.md.
        step = make_train_step(
            model, schedule=make_schedule(cfg.schedule, 3e-4, 10_000),
            accum_steps=(accum if accum is not None else accum_for(cfg)),
            remat_mode=("layer" if cost_mode or cfg.n_layers < 40
                        else "nested"),
            unroll=unroll)
        fn = jax.jit(step, in_shardings=(state_shard, batch_shard),
                     out_shardings=(state_shard, None),
                     donate_argnums=(0,))
        args = (state, specs)
    elif kind == "prefill":
        batch_shard = {
            k: NamedSharding(mesh, v)
            for k, v in sh.batch_pspecs(specs, mesh).items()}
        step = make_prefill_step(model, unroll=unroll)
        fn = jax.jit(step, in_shardings=(p_shard, batch_shard))
        args = (p_shapes, specs)
    else:  # decode
        flat_shard: Dict[str, Any] = {}
        for k, v in specs.items():
            if k in ("token", "index"):
                bp = sh.batch_pspecs({k: v}, mesh)[k]
                flat_shard[k] = NamedSharding(mesh, bp)
            else:
                flat_shard[k] = spec_shardings(v, axes[k])
        step = make_decode_step(model, unroll=unroll)
        # decode donates its caches (in-place ring update, as in real serving)
        fn = jax.jit(step, in_shardings=(p_shard, flat_shard),
                     donate_argnums=(1,))
        args = (p_shapes, specs)
    return fn, args, mesh, model, cfg


def run_cell(arch: str, shape: str, multi_pod: bool,
             skip_cost: bool = False, accum: Optional[int] = None,
             sharding: str = "auto", tag: str = "") -> Dict[str, Any]:
    mesh_name = "2x16x16" if multi_pod else "16x16"
    cfg = get_config(arch)
    ok, why = cell_supported(cfg, shape)
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape, "mesh": mesh_name,
        "supported": ok, "skip_reason": why,
        "sharding": sharding, "tag": tag,
    }
    if not ok:
        return rec

    t0 = time.perf_counter()
    fn, args, mesh, model, _ = build_cell(arch, shape, multi_pod,
                                          accum=accum, sharding=sharding)
    with jax.set_mesh(mesh):
        lowered = fn.lower(*args)
        t1 = time.perf_counter()
        compiled = lowered.compile()
    t2 = time.perf_counter()
    ma = compiled.memory_analysis()
    rec.update(
        lower_s=round(t1 - t0, 2), compile_s=round(t2 - t1, 2),
        argument_bytes=int(ma.argument_size_in_bytes),
        output_bytes=int(ma.output_size_in_bytes),
        temp_bytes=int(ma.temp_size_in_bytes),
        alias_bytes=int(ma.alias_size_in_bytes),
        peak_bytes_per_device=int(ma.argument_size_in_bytes
                                  + ma.output_size_in_bytes
                                  + ma.temp_size_in_bytes
                                  - ma.alias_size_in_bytes),
        accum=(accum if accum is not None else
               (accum_for(cfg) if SHAPES[shape]["kind"] == "train" else 1)),
    )

    if not skip_cost:
        # cost variants: accumulation off, unroll 1 vs 2 (same math/step).
        # MoE sequence-chunking is disabled here — its inner scan would be
        # counted once by cost_analysis (the memory it saves is irrelevant
        # to a lower-only compile); collective volume per token is the same.
        import repro.models.moe as moe_mod
        ratio = scan_ratio(model)
        costs = {}
        saved_chunk = moe_mod.SEQ_CHUNK
        moe_mod.SEQ_CHUNK = 1 << 30
        try:
            for u in (1, 2):
                fnu, argsu, _, _, _ = build_cell(arch, shape, multi_pod,
                                                 unroll=u, accum=1,
                                                 sharding=sharding,
                                                 cost_mode=True)
                with jax.set_mesh(mesh):
                    cu = fnu.lower(*argsu).compile()
                fl, by = cost_of(cu)
                co = collective_bytes(cu.as_text())
                costs[u] = (fl, by, co)
        finally:
            moe_mod.SEQ_CHUNK = saved_chunk
        f1, b1, c1 = costs[1]
        f2, b2, c2 = costs[2]
        rec.update(
            scan_ratio=ratio,
            hlo_flops_per_device=f1 + ratio * (f2 - f1),
            hlo_bytes_per_device=b1 + ratio * (b2 - b1),
            collective_bytes_per_device={
                k: c1.get(k, 0.0) + ratio * (c2.get(k, 0.0) - c1.get(k, 0.0))
                for k in set(c1) | set(c2)},
            raw_u1={"flops": f1, "bytes": b1, "coll": c1},
            raw_u2={"flops": f2, "bytes": b2, "coll": c2},
        )
    return rec


def save_record(rec: Dict[str, Any]) -> str:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    suffix = f"_{rec['tag']}" if rec.get("tag") else ""
    tag = f"{rec['arch']}_{rec['shape']}_{rec['mesh']}{suffix}.json"
    path = os.path.join(RESULTS_DIR, tag)
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return path


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-cost", action="store_true")
    ap.add_argument("--accum", type=int, default=None)
    ap.add_argument("--sharding", choices=["auto", "dp"], default="auto")
    ap.add_argument("--tag", default="")
    args = ap.parse_args(argv)

    meshes = [False, True] if (args.both_meshes or args.all) else \
        [args.multi_pod]
    cells = []
    if args.all:
        cells = [(a, s) for a in ARCHS for s in SHAPES]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    failures = 0
    for arch, shape in cells:
        for mp in meshes:
            tag = f"{arch} x {shape} x {'2x16x16' if mp else '16x16'}"
            try:
                # roofline cost terms are reported single-pod only; the
                # multi-pod pass proves the 'pod' axis shards (compile-only)
                rec = run_cell(arch, shape, mp,
                               skip_cost=args.skip_cost or mp,
                               accum=args.accum, sharding=args.sharding,
                               tag=args.tag)
                path = save_record(rec)
                if not rec["supported"]:
                    print(f"[skip] {tag}: {rec['skip_reason']}")
                else:
                    print(f"[ok]   {tag}: compile {rec['compile_s']}s "
                          f"peak/dev {rec['peak_bytes_per_device']/2**30:.2f} GiB"
                          f" -> {os.path.relpath(path)}")
                print(compiled_summary(rec))
            except Exception as e:  # noqa: BLE001
                failures += 1
                print(f"[FAIL] {tag}: {type(e).__name__}: {e}")
                traceback.print_exc()
    if failures:
        sys.exit(1)


def compiled_summary(rec: Dict[str, Any]) -> str:
    if not rec.get("supported"):
        return ""
    lines = [f"       memory: arg {rec['argument_bytes']/2**30:.2f} + temp "
             f"{rec['temp_bytes']/2**30:.2f} GiB/device"]
    if "hlo_flops_per_device" in rec:
        co = rec["collective_bytes_per_device"]
        lines.append(
            f"       cost/device: {rec['hlo_flops_per_device']/1e12:.2f} "
            f"TFLOP, {rec['hlo_bytes_per_device']/2**30:.2f} GiB HBM, "
            f"{co.get('total', 0)/2**30:.3f} GiB wire")
    return "\n".join(lines)


if __name__ == "__main__":
    main()

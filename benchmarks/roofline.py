"""Roofline analysis: dry-run HLO artifacts + generation-engine placement.

Two sections share one hardware model:

**Dry-run section** (the original): reads benchmarks/results/dryrun/*.json
(written by repro.launch.dryrun) and derives, per (arch x shape) on the
single-pod mesh:

    compute term    = HLO_FLOPs_per_device / peak_FLOPs      [s]
    memory term     = HLO_bytes_per_device / HBM_bw          [s]
    collective term = wire_bytes_per_device / ICI_bw         [s]

(The dry-run HLO module is the per-device SPMD program, so its cost numbers
are already per-device; scan bodies are extrapolated by the dry-run's
two-point unroll method.) The dominant term is the bottleneck; MODEL_FLOPS
over HLO_FLOPs measures how much compiled compute is useful.

**Generation section** (:func:`generation_roofline`): times one GA
generation step per engine impl (jnp vs pallas vs pallas_tiled) on a
synthetic population and places the measured evals/sec against the
*memory-bandwidth* roofline — a generation is bandwidth-bound (its only
mandatory traffic is read-population + write-population, ~2·L·itemsize
bytes per evaluation; the arithmetic per gene is trivial), so

    ceiling_evals_per_sec = HBM_bw / (2 * L * itemsize)

and ``roofline_fraction = measured / ceiling`` says how far each engine
sits below the memory wall. On the CPU there is no device to place, so the
rows carry their times and ``roofline_fraction: "not measured"``.

Hardware constants are a per-``device_kind`` table (:data:`HW_TABLE`) with
a CLI override (``--peak-flops/--hbm-bw/--ici-bw``). A device kind that is
not in the table is an error, not a default.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import time
from typing import Any, Dict, List, Optional

# Per-device_kind hardware constants (bf16 peak, HBM bandwidth, per-link
# ICI bandwidth). Keys are matched case-insensitively as substrings of
# jax's device_kind string ("TPU v5 lite" is a v5e); first match wins.
HW_TABLE: Dict[str, Dict[str, float]] = {
    "v5 lite": {"peak_flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
    "v5e":     {"peak_flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
    "v5p":     {"peak_flops": 459e12, "hbm_bw": 2765e9, "ici_bw": 90e9},
    "v4":      {"peak_flops": 275e12, "hbm_bw": 1228e9, "ici_bw": 50e9},
    "v3":      {"peak_flops": 123e12, "hbm_bw": 900e9, "ici_bw": 70e9},
}
_DEFAULT_KIND = "v5e"

# Module-level v5e constants kept for backward compatibility with callers
# that import them directly.
PEAK_FLOPS = HW_TABLE[_DEFAULT_KIND]["peak_flops"]
HBM_BW = HW_TABLE[_DEFAULT_KIND]["hbm_bw"]
ICI_BW = HW_TABLE[_DEFAULT_KIND]["ici_bw"]

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "results", "dryrun")


def hw_constants(device_kind: Optional[str] = None,
                 override: Optional[Dict[str, float]] = None
                 ) -> Dict[str, Any]:
    """Resolve hardware constants for ``device_kind`` (defaults to the
    current jax device), applying any non-None ``override`` entries.
    Raises ``KeyError`` for a device kind that is not in :data:`HW_TABLE`."""
    if device_kind is None:
        import jax
        device_kind = jax.devices()[0].device_kind
    matched = next((key for key in HW_TABLE
                    if key.lower() in str(device_kind).lower()), None)
    if matched is None:
        raise KeyError(f"no hardware constants for device kind "
                       f"{device_kind!r}; known: {sorted(HW_TABLE)}")
    out = {"device_kind": str(device_kind), "table_entry": matched,
           **HW_TABLE[matched]}
    for k, v in (override or {}).items():
        if v is not None:
            out[k] = float(v)
            out["table_entry"] = "cli-override"
    return out


def model_flops_per_device(rec: Dict) -> Optional[float]:
    """Analytic useful FLOPs per device for the cell."""
    from repro.configs import get_config
    from repro.launch.input_specs import SHAPES

    cfg = get_config(rec["arch"])
    total, active = cfg.param_count()
    info = SHAPES[rec["shape"]]
    kind = info["kind"]
    n_chips = 512 if rec["mesh"] == "2x16x16" else 256
    if kind == "train":
        tokens = info["seq"] * info["batch"]
        return 6.0 * active * tokens / n_chips
    if kind == "prefill":
        tokens = info["seq"] * info["batch"]
        return 2.0 * active * tokens / n_chips
    # decode: one token per sequence
    return 2.0 * active * info["batch"] / n_chips


def analyze(rec: Dict, hw: Optional[Dict[str, float]] = None
            ) -> Optional[Dict]:
    if not rec.get("supported") or "hlo_flops_per_device" not in rec:
        return None
    hw = hw or {"peak_flops": PEAK_FLOPS, "hbm_bw": HBM_BW,
                "ici_bw": ICI_BW}
    mf = model_flops_per_device(rec)
    note = ""
    flops = rec["hlo_flops_per_device"]
    if flops <= rec.get("raw_u1", {}).get("flops", 0):
        # two-point unroll delta came out non-linear (XLA fused the
        # doubled body differently) — fall back to the analytic count at
        # a typical 0.8 useful-ratio, and say so.
        flops = mf / 0.8
        rec = dict(rec, hlo_flops_per_device=flops)
        note = "flops~analytic (unroll extrapolation non-linear)"
    compute = flops / hw["peak_flops"]
    memory = rec["hlo_bytes_per_device"] / hw["hbm_bw"]
    wire = rec["collective_bytes_per_device"].get("total", 0.0)
    collective = wire / hw["ici_bw"]
    terms = {"compute": compute, "memory": memory, "collective": collective}
    dominant = max(terms, key=terms.get)
    bound = max(terms.values())
    return {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        "compute_s": compute, "memory_s": memory, "collective_s": collective,
        "dominant": dominant,
        "model_flops_per_device": mf,
        "useful_ratio": (mf / rec["hlo_flops_per_device"]
                         if rec["hlo_flops_per_device"] else None),
        # roofline fraction: ideal compute time over the binding term
        "roofline_fraction": (mf / hw["peak_flops"]) / bound if bound
        else None,
        "peak_gib_per_device": rec["peak_bytes_per_device"] / 2**30,
        "accum": rec.get("accum"),
        "note": note,
        "collectives": {k: v for k, v in
                        rec["collective_bytes_per_device"].items()
                        if k != "total"},
    }


def load_records(mesh: str = "16x16") -> List[Dict]:
    out = []
    for path in sorted(glob.glob(os.path.join(RESULTS_DIR, "*.json"))):
        with open(path) as f:
            rec = json.load(f)
        if rec.get("mesh") == mesh and not rec.get("tag"):
            out.append(rec)   # tagged records are hillclimb probes
    return out


def table(mesh: str = "16x16",
          hw: Optional[Dict[str, float]] = None) -> List[str]:
    rows = ["arch,shape,compute_s,memory_s,collective_s,dominant,"
            "roofline_frac,useful_ratio,peak_GiB,note"]
    for rec in load_records(mesh):
        if not rec.get("supported"):
            rows.append(f"{rec['arch']},{rec['shape']},,,,skipped,,,,"
                        f"\"{rec['skip_reason']}\"")
            continue
        a = analyze(rec, hw=hw)
        if a is None:
            rows.append(f"{rec['arch']},{rec['shape']},,,,compiled-only,,,"
                        f"{rec['peak_bytes_per_device']/2**30:.2f},")
            continue
        rows.append(
            f"{a['arch']},{a['shape']},{a['compute_s']:.3f},"
            f"{a['memory_s']:.3f},{a['collective_s']:.3f},{a['dominant']},"
            f"{a['roofline_fraction']:.3f},{a['useful_ratio']:.3f},"
            f"{a['peak_gib_per_device']:.2f},{a['note']}")
    return rows


# ---------------------------------------------------------------------------
# Generation-engine roofline (jnp vs pallas vs pallas_tiled)
# ---------------------------------------------------------------------------
def _bench_generation(impl: str, n: int, L: int, kind: str,
                      repeats: int, tile_pop: Optional[int],
                      tile_len: Optional[int]) -> float:
    """Median seconds for one generation step of impl on an (n, L) pop."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import EAConfig
    from repro.core.types import GenomeSpec
    from repro.kernels import ga as gk

    genome = (GenomeSpec("binary", L) if kind == "binary"
              else GenomeSpec("float", L, -5.0, 5.0))
    cfg = EAConfig(max_pop=n, min_pop=min(8, n),
                   crossover="two_point" if kind == "binary" else "blend",
                   impl=impl)
    k_init, k_step = jax.random.split(jax.random.key(0))
    pop = (jax.random.bernoulli(k_init, 0.5, (n, L)).astype(jnp.int8)
           if kind == "binary"
           else jax.random.uniform(k_init, (n, L), jnp.float32, -5.0, 5.0))
    fit = pop.astype(jnp.float32).sum(-1)
    kern = gk.get_kernel("generation", kind, impl)
    kwargs = {}
    if impl == "pallas_tiled":
        kwargs = {"tile_pop": tile_pop, "tile_len": tile_len}
    step = jax.jit(lambda k: kern(k, pop, fit, jnp.int32(n), cfg, genome,
                                  **kwargs))
    step(k_step).block_until_ready()  # compile + warm-up
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        # repro-lint: disable=RNG01 -- same key every repeat on purpose: each sample must time identical work
        step(k_step).block_until_ready()
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def generation_roofline(impls=("jnp", "pallas", "pallas_tiled"), *,
                        n: int = 2048, L: int = 256, kind: str = "binary",
                        repeats: int = 3,
                        tile_pop: Optional[int] = None,
                        tile_len: Optional[int] = None,
                        hw: Optional[Dict[str, Any]] = None
                        ) -> Dict[str, Any]:
    """Measure generation evals/sec per impl and place each against the
    memory-bandwidth roofline. Returns the BENCH_speed.json section. On
    the CPU backend nothing is placed: the roofline share is "not
    measured"."""
    import jax

    if hw is None and jax.default_backend() != "cpu":
        hw = hw_constants()
    itemsize = 1 if kind == "binary" else 4
    bytes_per_eval = 2 * L * itemsize      # mandatory: read pop + write pop
    ceiling = hw["hbm_bw"] / bytes_per_eval if hw else None
    rows = []
    for impl in impls:
        sec = _bench_generation(impl, n, L, kind, repeats, tile_pop,
                                tile_len)
        eps = n / sec
        rows.append({
            "impl": impl, "pop": n, "genome_length": L,
            "genome_kind": kind,
            "evals_per_sec": eps,
            "seconds_per_generation": sec,
            "roofline_fraction": (eps / ceiling if ceiling
                                  else "not measured"),
        })
    return {
        "metric": "single generation-step throughput vs HBM roofline "
                  "(ceiling = hbm_bw / (2 * L * itemsize); on the CPU the "
                  "pallas rows time interpret-mode emulation and the "
                  "roofline share is not measured)",
        "hw": hw or "not measured",
        "bytes_per_eval_min": bytes_per_eval,
        "ceiling_evals_per_sec": ceiling,
        "rows": rows,
    }


def _hw_override(args) -> Dict[str, float]:
    return {"peak_flops": args.peak_flops, "hbm_bw": args.hbm_bw,
            "ici_bw": args.ici_bw}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="16x16")
    ap.add_argument("--device-kind", default=_DEFAULT_KIND,
                    help="HW_TABLE row for the dry-run table (the dry-run "
                         "compiles for a v5e pod, wherever it runs)")
    ap.add_argument("--peak-flops", type=float, default=None)
    ap.add_argument("--hbm-bw", type=float, default=None)
    ap.add_argument("--ici-bw", type=float, default=None)
    ap.add_argument("--generation", action="store_true",
                    help="also run the generation-engine roofline "
                         "(jnp vs pallas vs pallas_tiled)")
    ap.add_argument("--pop", type=int, default=2048)
    ap.add_argument("--genome-length", type=int, default=256)
    ap.add_argument("--kind", default="binary",
                    choices=["binary", "float"])
    args = ap.parse_args(argv)
    hw = hw_constants(args.device_kind, _hw_override(args))
    print(f"# hw: {hw}")
    print("\n".join(table(args.mesh, hw=hw)))
    if args.generation:
        from repro.compile_cache import use_compile_cache
        use_compile_cache()
        section = generation_roofline(n=args.pop, L=args.genome_length,
                                      kind=args.kind)
        print("impl,pop,L,evals_per_sec,roofline_fraction")
        for r in section["rows"]:
            print(f"{r['impl']},{r['pop']},{r['genome_length']},"
                  f"{r['evals_per_sec']:.0f},{r['roofline_fraction']}")


if __name__ == "__main__":
    main()

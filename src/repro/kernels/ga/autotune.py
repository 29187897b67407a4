"""Tile-size autotune for the grid-tiled generation kernel.

The tiled kernel's throughput is set almost entirely by its (tile_pop,
tile_len) blocking: the tile must be big enough to amortize grid overhead
and keep the MXU fed, small enough that the ~4 resident buffers (two
parent-accumulator scratch tiles + double-buffered in/out copies) fit
VMEM. The right point is device-dependent, so:

* **On TPU** :func:`best_tiles` sweeps :data:`CANDIDATES` with real timed
  runs (synthetic population of the requested shape, ``block_until_ready``
  timing of the steady state after one warm-up) and picks the highest
  evals/sec.
  A candidate the compiler refuses is reported; if none compiles, the
  sweep raises.
* **On the CPU** (interpret mode — tests, CI) timing is meaningless, so a
  VMEM-model heuristic picks the largest candidate under the budget.

Results are cached as JSON keyed by device kind, genome kind and shape
(:func:`cache_key`) at ``benchmarks/results/autotune_ga.json`` (override
with the ``REPRO_GA_AUTOTUNE_CACHE`` env var) so a sweep runs once per
key; ``benchmarks/hostmeta.py`` folds the cache into the BENCH host block,
which is how tuned tile sizes travel with published numbers.
"""
from __future__ import annotations

import json
import os
import time
import warnings
from pathlib import Path
from typing import Dict, Optional, Tuple

import jax
import numpy as np

from .. import interpret_mode

# (tile_pop, tile_len) sweep grid — all MXU/VPU-aligned.
CANDIDATES: Tuple[Tuple[int, int], ...] = (
    (128, 256), (128, 512), (256, 256), (256, 512), (256, 1024),
    (512, 256), (512, 512),
)

# VMEM budget the heuristic models: 2 scratch accumulators + pipelined
# in/out copies of the (tp, tl) tile, f32, double-buffered ≈ 8 tiles,
# plus the (tp, tp) one-hot blocks. Conservative vs a real core's VMEM.
_HEURISTIC_VMEM = 8 * 2**20


def _default_cache_path() -> Path:
    env = os.environ.get("REPRO_GA_AUTOTUNE_CACHE")
    if env:
        return Path(env)
    return (Path(__file__).resolve().parents[4] / "benchmarks" / "results"
            / "autotune_ga.json")


def device_kind() -> str:
    return jax.devices()[0].device_kind


def load_cache(path: Optional[Path] = None) -> Dict[str, dict]:
    path = Path(path or _default_cache_path())
    if not path.exists():
        return {}
    try:
        return json.loads(path.read_text())
    except (json.JSONDecodeError, OSError):
        return {}


def save_cache(cache: Dict[str, dict], path: Optional[Path] = None) -> Path:
    path = Path(path or _default_cache_path())
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(cache, indent=2, sort_keys=True) + "\n")
    return path


def _tile_bytes(tp: int, tl: int) -> int:
    return 8 * tp * tl * 4 + 2 * tp * tp * 4


def _heuristic(n: int, L: int) -> Tuple[int, int]:
    """Largest candidate whose modeled VMEM footprint fits the budget,
    preferring wide genome tiles (fewer j-steps => fewer RNG redraws)."""
    fits = [(tp, tl) for tp, tl in CANDIDATES
            if _tile_bytes(tp, tl) <= _HEURISTIC_VMEM]
    best = max(fits, key=lambda c: (min(c[1], L), min(c[0], n)))
    return best


def _time_candidate(n: int, L: int, kind: str, tp: int, tl: int,
                    runs: int = 3) -> float:
    """Median seconds per tiled generation on synthetic data (TPU only)."""
    from repro.core.types import EAConfig, GenomeSpec
    from . import ops as _ops
    from . import tiling as _tiling

    genome = (GenomeSpec("binary", L) if kind == "binary"
              else GenomeSpec("float", L, -5.0, 5.0))
    cfg = EAConfig(max_pop=n, min_pop=min(8, n))
    spec = _ops.make_spec(cfg, genome)
    # host-made inputs and an AOT-compiled step: best_tiles runs while the
    # caller's kernel is being traced, and nothing here may join that trace
    gen = np.random.default_rng(0)
    pop = (gen.integers(0, 2, (n, L), dtype=np.int8) if kind == "binary"
           else gen.uniform(-5.0, 5.0, (n, L)).astype(np.float32))
    args = (np.array([0x9E3779B9, 1], np.uint32), np.array([n], np.int32),
            pop, pop.astype(np.float32).sum(-1))
    step = jax.jit(lambda seed, size, pop, fit: _tiling.generation_tiled(
        seed, size, pop, fit, spec, tile_pop=tp, tile_len=tl,
        interpret=False)).lower(*args).compile()
    step(*args).block_until_ready()  # warm up
    times = []
    for _ in range(runs):
        t0 = time.perf_counter()
        step(*args).block_until_ready()
        times.append(time.perf_counter() - t0)
    return sorted(times)[len(times) // 2]


def cache_key(n: int, L: int, kind: str) -> str:
    """Cache entries are per device kind, genome kind and shape: the best
    tiling of one shape says nothing about another."""
    return f"{device_kind()}|{kind}|{int(n)}x{int(L)}"


def best_tiles(n: int, L: int, kind: str = "float", *,
               cache_path: Optional[Path] = None,
               force: bool = False) -> Tuple[int, int]:
    """Tuned (tile_pop, tile_len) for a (n, L) population of ``kind``.

    Reads the cache first; on a miss sweeps (TPU) or applies the VMEM
    heuristic (interpret mode) and writes the cache. On the TPU each
    candidate the compiler refuses is reported, and a sweep in which no
    candidate compiles raises rather than falling back to the heuristic.
    """
    cache = load_cache(cache_path)
    key = cache_key(n, L, kind)
    entry = cache.get(key)
    if entry is not None and not force:
        return int(entry["tile_pop"]), int(entry["tile_len"])

    entry = {"shape": [int(n), int(L)], "kind": kind}
    if interpret_mode():
        tp, tl = _heuristic(n, L)
        entry.update(tile_pop=tp, tile_len=tl, timed=False)
    else:
        timings, refused = {}, {}
        for tp, tl in CANDIDATES:
            try:
                timings[(tp, tl)] = _time_candidate(n, L, kind, tp, tl)
            except (ValueError, NotImplementedError,
                    jax.errors.JaxRuntimeError) as e:
                refused[f"{tp}x{tl}"] = str(e).splitlines()[0][:300]
                warnings.warn(f"autotune {key}: tile {tp}x{tl} refused: "
                              f"{refused[f'{tp}x{tl}']}", stacklevel=2)
        if not timings:
            raise RuntimeError(f"autotune {key}: no tile candidate "
                               f"compiled: {refused}")
        tp, tl = min(timings, key=timings.get)
        entry.update(tile_pop=tp, tile_len=tl, timed=True, refused=refused,
                     sweep_s={f"{a}x{b}": t
                              for (a, b), t in sorted(timings.items())})

    cache[key] = entry
    try:
        save_cache(cache, cache_path)
    except OSError:
        pass  # read-only checkout: tuning still applies, just not cached
    return int(entry["tile_pop"]), int(entry["tile_len"])


def cache_summary(path: Optional[Path] = None) -> Dict[str, object]:
    """Compact cache view for the BENCH host block."""
    p = Path(path or _default_cache_path())
    cache = load_cache(p)
    return {"path": str(p),
            "entries": {k: {kk: v[kk] for kk in
                            ("tile_pop", "tile_len", "timed") if kk in v}
                        for k, v in cache.items()}}

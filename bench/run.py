#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<mix>.json``); the mix names the entry it drives
(``bench/harness/<entry>.py``, whose ``make(cfg, mix, seed, devices)`` is
handed the chips the cell asks for) and what the entry reads, such as a
driver (``bench/drivers/<driver>.py``); the configuration names its
problem (``bench/problems/<kind>.py``). ``harness/spec.py`` lists the
interface of each. A run sets up (compile cache, the pinned tile, problem
data, one warm-up of every shape), measures for ``--seconds``, checks
what the timed path produced against the plain reference, and prints one
JSON line last on stdout:

* ``--trace 0``: the cell's end-to-end metrics, taken on the host clock;
* ``--trace 1``: its per-layer metrics, read from a profiler trace of the
  window (``bench/metrics/<metric>.py``), with ``busy_s``, ``window_s``
  and the ``breakdown`` of device time and idle gaps.

Each number compared and its limit is printed last on stderr and under
the result's last key, ``check``. Without a TPU, or with fewer chips than
the cell asks for, the run prints no result and exits 3.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from contextlib import nullcontext  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

from harness import spec, tiles  # noqa: E402

# the traced window: a few seconds of the same traffic, in a run of its own
TRACE_SECONDS = 10.0


class NoChip(Exception):
    pass


def require_chips(n: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {devs[0].platform!r}")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX sees {len(devs)}")
    return devs


def device_info(devs_used):
    import jax

    devs = jax.devices()
    peak = 0
    for d in devs_used:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def make_entry(cfg, mix, seed, devices, bench_dir=BENCH):
    """The entry the mix names (``harness/<entry>.py``), on ``devices``."""
    return spec.entry(mix["entry"], bench_dir).make(cfg, mix, seed, devices)


def _annotate(on: bool):
    if not on:
        return lambda name: nullcontext()
    import jax

    return lambda name: jax.profiler.TraceAnnotation(name)


def run_cell(bench, cell, cfg, mix, *, seed: int, seconds: float,
             trace: bool, t_start: float, devs_used, trace_dir=None,
             control: bool = False, pin_kind=None, bench_dir=BENCH):
    """Set up, measure and check one run; returns the result dict. With
    ``pin_kind`` (the chip's device kind) the configuration's tile is
    pinned first (:mod:`harness.tiles`). Entries and metric readers are
    found under ``bench_dir``."""
    if pin_kind is not None:
        tiles.pin(cfg, pin_kind)
    entry = make_entry(cfg, mix, seed, devs_used, bench_dir)
    try:
        return _measure(bench, cell, cfg, entry, seconds=seconds,
                        trace=trace, t_start=t_start, devs_used=devs_used,
                        trace_dir=trace_dir, control=control,
                        pin_kind=pin_kind, bench_dir=bench_dir)
    finally:
        entry.close()


def _measure(bench, cell, cfg, entry, *, seconds, trace, t_start, devs_used,
             trace_dir, control, pin_kind, bench_dir):
    import jax

    from harness import check, peaks as peaks_lib

    entry.warm_up()
    if pin_kind is not None:
        tiles.check(cfg, pin_kind)
    logdir = None
    if trace:
        seconds = min(seconds, TRACE_SECONDS)
        logdir = Path(trace_dir) if trace_dir else Path(
            tempfile.mkdtemp(prefix="bench_trace_"))
        # host annotations on, Python's function tracer off: it records
        # every call and slows the host that the idle share is about
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(logdir), profiler_options=opts)
    setup_s = time.perf_counter() - t_start
    try:
        window_s = entry.window(seconds, _annotate(trace), traced=trace)
    finally:
        if trace:
            jax.profiler.stop_trace()
    device = device_info(devs_used)
    nums = entry.verify(control=control)
    correct, table = check.verdict(nums, cfg["limits"])

    result = {"correct": correct, **entry.counts()}
    if trace:
        from harness import trace as trace_lib
        tr = trace_lib.load(logdir)
        if trace_dir is None:
            shutil.rmtree(logdir, ignore_errors=True)
        ctx = {"trace": tr, "shape": entry.shape(),
               "peaks": peaks_lib.peaks(device["kind"]),
               "entry": entry}
        metrics = {}
        for m in spec.cell_metrics(bench, cell["name"], "per_layer"):
            v = spec.metric_reader(m["name"], bench_dir).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        lo, hi = tr.window()
        device.update(busy_s=trace_lib.mean_busy_s(tr), window_s=hi - lo)
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = trace_lib.breakdown(tr)
    else:
        got = dict(entry.metrics(window_s), setup_s=setup_s)
        result["metrics"] = {
            m["name"]: {"value": got[m["name"]], "unit": m["unit"]}
            for m in spec.cell_metrics(bench, cell["name"], "end_to_end")}
        result["device"] = device
    result["notes"] = entry.notes()
    result["check"] = table
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the raw profiler trace here")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no program beside {BENCH} (src/repro)",
              file=sys.stderr)
        return 2
    bench = spec.load_benchmark(ROOT)
    cell = spec.workload(bench, args.workload)
    cfg = spec.config(bench, cell["config"])
    mix = spec.traffic(cell["traffic"])

    import jax

    from repro.compile_cache import use_compile_cache

    try:
        devs = require_chips(cell["chips"])
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    use_compile_cache()
    # every program into the persistent cache, however quick to compile
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    result = run_cell(bench, cell, cfg, mix, seed=args.seed,
                      seconds=args.seconds, trace=bool(args.trace),
                      t_start=T_START, devs_used=devs[:cell["chips"]],
                      trace_dir=args.trace_dir,
                      pin_kind=devs[0].device_kind)
    for k, v in result["check"].items():
        print(f"check {k} = {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

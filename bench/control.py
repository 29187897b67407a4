#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, for one cell, on
many seeds in one process.

    python3 bench/control.py --workload <name> --seeds 1,2,3 --seconds <s> \
        [--plant bf16_gather]

For each seed the cell sets up, runs a window at its own load and keeps
the answers it would check. Each number is then computed twice on the
same answers: as the program produced them (a lower reading), and with
the reference computed in bfloat16 in the program's place (an upper
reading). With ``--plant bf16_gather`` the program itself runs with its
parent gather in bfloat16 (``harness/control.py``), and its numbers are
upper readings. One JSON line per seed. The benchmark's own runs never
run a control; it needs the chip the cell asks for.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from harness import spec, tiles  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--plant", choices=("bf16_gather",), default=None)
    args = ap.parse_args(argv)

    import run as bench_run
    from harness import check
    from harness import control as control_lib
    from repro.compile_cache import use_compile_cache

    bench = spec.load_benchmark()
    cell = spec.workload(bench, args.workload)
    cfg = spec.config(bench, cell["config"])
    mix = spec.traffic(cell["traffic"])
    try:
        devs = bench_run.require_chips(cell["chips"])
    except bench_run.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 3
    use_compile_cache()
    tiles.pin(cfg, devs[0].device_kind)
    if args.plant:
        mix = dict(mix, impl=control_lib.plant_bf16_gather(mix["impl"]))
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        entry = bench_run.make_entry(cfg, mix, seed)
        try:
            entry.warm_up()
            entry.window(args.seconds, lambda name: bench_run.nullcontext())
            answers = entry.answers()
        finally:
            entry.close()
        out = {"seed": seed, "plant": args.plant}
        out["program"] = entry.numbers(answers)
        out["correct"] = check.verdict(out["program"], cfg["limits"])[0]
        if not args.plant:
            out["control"] = entry.numbers(answers, control=True)
            out["control_correct"] = check.verdict(out["control"],
                                                   cfg["limits"])[0]
        out["seconds"] = time.perf_counter() - t0
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

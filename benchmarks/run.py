"""Benchmark orchestrator — one section per paper table/figure + roofline.

Prints ``name,value,derived`` CSV blocks. Flags trim runtimes for CI; the
full paper-scale settings are documented per module.
"""
from __future__ import annotations

import argparse
import json
import sys
import time


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale settings (50 trap runs etc.)")
    ap.add_argument("--skip", nargs="*", default=[],
                    choices=["fig3", "fig4", "pool", "migration", "speed",
                             "roofline"])
    ap.add_argument("--migration-json", default="BENCH_migration.json",
                    help="machine-readable per-topology throughput output")
    ap.add_argument("--speed-json", default="BENCH_speed.json",
                    help="machine-readable speed-baseline output "
                         "(evals/sec + time-to-solution per problem x "
                         "genome length x generation-engine impl)")
    args = ap.parse_args(argv)
    from benchmarks import hostmeta
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    t0 = time.perf_counter()

    if "fig3" not in args.skip:
        print("== Fig 3: trap-40 baseline (time/evals to solution) ==")
        from benchmarks import fig3_trap
        rows = fig3_trap.run(runs=50 if args.full else 8,
                             max_evals=5_000_000,   # the paper's budget
                             verbose=False)
        print("\n".join(fig3_trap.summarize(rows)))
        print()

    if "fig4" not in args.skip:
        print("== Fig 4: F15 10k-evaluation runtime ==")
        from benchmarks import fig4_f15
        rows = fig4_f15.bench(n_evals=10_000,       # the paper's workload
                              include_loop=True,
                              include_pallas=True)
        print("\n".join(fig4_f15.summarize(rows)))
        print()

    if "pool" not in args.skip:
        print("== Pool scalability (paper §2) ==")
        from benchmarks import pool_throughput
        for r in pool_throughput.bench_host_pool(
                requests=4000 if args.full else 800):
            print(f"host_pool,{r['clients']}_clients,"
                  f"{r['requests_per_s']:.0f}_req/s")
        for r in pool_throughput.bench_device_pool(
                island_counts=(4, 16, 64) if args.full else (4, 16)):
            print(f"device_pool,{r['islands']}_islands,"
                  f"{r['generations_per_s']:.0f}_gens/s")
        print()

    if "migration" not in args.skip:
        print("== Migration topologies (fused lax.scan driver) ==")
        from benchmarks import pool_throughput
        rows = pool_throughput.bench_migration(
            islands=32 if args.full else 16,
            epochs=20 if args.full else 6)
        for r in rows:
            print(f"migration,{r['topology']},"
                  f"{r['epochs_per_s']:.2f}_epochs/s,"
                  f"{r['generations_per_s']:.0f}_gens/s")
        print("== Sync vs async runtime under churn ==")
        async_rows = pool_throughput.bench_async(
            islands=32 if args.full else 16,
            epochs=20 if args.full else 6)
        for r in async_rows:
            print(f"async,{r['runtime']},{r['topology']},"
                  f"{r['ticks_per_s']:.2f}_ticks/s,"
                  f"{r['island_epochs_per_s']:.0f}_island_epochs/s")
        print("== Acceptance policies (policy x topology, diversity) ==")
        acceptance_rows = pool_throughput.bench_acceptance(
            islands=32 if args.full else 16,
            epochs=20 if args.full else 6)
        for r in acceptance_rows:
            print(f"acceptance,{r['policy']},{r['topology']},"
                  f"{r['epochs_per_s']:.2f}_epochs/s,"
                  f"diversity={r['diversity']:.2f}"
                  f"({r['diversity_source']})")
        with open(args.migration_json, "w") as fh:
            json.dump(hostmeta.stamp(
                      {"benchmark": "migration_topologies",
                       "driver": "run_fused[lax.scan]",
                       "rows": rows,
                       "async_vs_sync_under_churn": {
                           "driver": "run_fused_async[lax.scan"
                                     "+per-island fire mask]",
                           "rows": async_rows},
                       "bench_acceptance": {
                           "driver": "run_fused[lax.scan]"
                                     "+core.acceptance policy",
                           "diversity_metric": "mean pairwise genome "
                                               "distance (final pool; "
                                               "island bests for "
                                               "pool-bypassing topologies)",
                           "rows": acceptance_rows}}), fh, indent=2)
        print(f"wrote {args.migration_json}")
        print()

    if "speed" not in args.skip:
        print("== Speed baseline (evals/sec, jnp vs pallas vs pallas_tiled "
              "generation engine + HBM roofline placement) ==")
        from benchmarks import speed_baseline
        speed_rows = speed_baseline.run(full=args.full, verbose=False)
        print("\n".join(speed_baseline.summarize(speed_rows)))
        with open(args.speed_json, "w") as fh:
            json.dump(hostmeta.stamp(speed_baseline.payload(speed_rows)),
                      fh, indent=2)
        print(f"wrote {args.speed_json}")
        print()

    if "roofline" not in args.skip:
        print("== Roofline (from dry-run artifacts; see EXPERIMENTS.md) ==")
        from benchmarks import roofline
        try:
            rows = roofline.table("16x16")
            print("\n".join(rows) if len(rows) > 1
                  else "no dry-run artifacts yet — run "
                       "`python -m repro.launch.dryrun --all` first")
        except Exception as e:  # noqa: BLE001
            print(f"roofline unavailable: {e}")
        print()

    print(f"total benchmark wall time: {time.perf_counter()-t0:.1f}s")


if __name__ == "__main__":
    main()

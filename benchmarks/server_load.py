"""10k-volunteer load harness for the networked pool service.

The paper's scalability claim is operational: the non-blocking
single-threaded server kept serving as volunteers piled on and "the
limit so far has not been found". This harness probes our
``python -m repro.server`` tier the same way: a fleet of simulated
browser volunteers (multiprocess x asyncio — each worker process runs
thousands of keep-alive connections on one event loop) hammers a real
server subprocess over the JSON wire protocol while a drainer thread
plays the pod bridge, draining the pool exactly-once via a named
``get_since`` cursor.

Each volunteer is ``examples/volunteer_sim.py``'s browser loop over the
wire: GET a random chromosome (fall back to a fresh random genome when
the pool is cold), push a few bits toward the all-ones optimum, evaluate
onemax host-side, PUT the result, think, repeat. Workers import only the
pure wire client (no jax) so 4 processes don't pay 4 jax imports.

Recorded per scenario (``BENCH_server.json``, hostmeta-stamped):
requests/sec, p50/p99 latency (log-spaced histogram merged across
workers), throttled (429) and lost-XHR counts, and the exactly-once
ledger — every drained entry is checked unique by ``(shard, seq)`` and
the cursor/delivered/dropped accounting must balance. The committed
baseline's 10k row must carry ``dropped == 0``.

    PYTHONPATH=src python benchmarks/server_load.py                  # smoke
    PYTHONPATH=src python benchmarks/server_load.py --full           # + 10k
    PYTHONPATH=src python benchmarks/server_load.py --scenario smoke \
        --json /tmp/fresh_server.json      # the CI smoke + regression gate

``scripts/check_server_regress.py`` gates requests/sec against the
committed baseline (same cpu_count only — a 1-core container and a CI
runner are different universes).
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import random
import signal
import subprocess
import sys
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SRC = os.path.join(_REPO, "src")
for _p in (_SRC, _REPO):   # _REPO: `from benchmarks import hostmeta`
    if _p not in sys.path:
        sys.path.insert(0, _p)

# The log-spaced mergeable latency histogram lives in repro.obs.metrics,
# shared with the HTTP frontend and the timeline CLI (stdlib-only — no jax
# in workers).
from repro.obs.metrics import (  # noqa: E402
    HIST_BINS, hist_index, hist_percentile)


# ---------------------------------------------------------------------------
# worker process: N asyncio volunteers on one event loop (no jax import)
# ---------------------------------------------------------------------------
async def _volunteer(cfg: Dict[str, Any], idx: int, deadline: float,
                     hist: List[int], totals: Dict[str, int]) -> None:
    from repro.server.client import AsyncWireClient

    rng = random.Random(cfg["seed"] * 100003 + idx)
    client = AsyncWireClient(
        cfg["url"], experiment=cfg["experiment"],
        client_id=f"w{cfg['worker_id']}-v{idx}", timeout=30.0,
        max_retries=2)
    uuid = 1000 + cfg["worker_id"] * cfg["clients"] + idx
    length = cfg["genome_len"]
    # stagger connects so 10k SYNs don't land in one accept-queue burst
    await asyncio.sleep(rng.uniform(0.0, cfg["ramp"]))
    try:
        while time.monotonic() < deadline:
            got = await client.get_random(n=1)
            if got:
                genome = list(got[0]["chromosome"])
            else:   # cold pool (or lost XHR): start from random bits
                genome = [rng.randint(0, 1) for _ in range(length)]
            for _ in range(4):  # the browser tab's tiny hill-climb
                genome[rng.randrange(length)] = 1
            fitness = float(sum(genome))   # onemax, evaluated host-side
            ok = await client.put_batch([(genome, fitness, uuid)])
            totals["puts_ok" if ok is not None else "puts_failed"] += 1
            totals["gets_ok" if got is not None else "gets_failed"] += 1
            for ms in client.pop_latencies():
                hist[hist_index(ms)] += 1
                totals["responses"] += 1
            if time.monotonic() >= deadline:
                break
            await asyncio.sleep(rng.uniform(cfg["think_min"],
                                            cfg["think_max"]))
    finally:
        totals["lost"] += client.lost
        totals["throttled"] += client.throttled
        await client.aclose()


async def _worker_main(cfg: Dict[str, Any]) -> Dict[str, Any]:
    hist = [0] * HIST_BINS
    totals = {k: 0 for k in ("puts_ok", "puts_failed", "gets_ok",
                             "gets_failed", "responses", "lost",
                             "throttled")}
    t0 = time.monotonic()
    deadline = t0 + cfg["ramp"] + cfg["duration"]
    tasks = [asyncio.create_task(_volunteer(cfg, i, deadline, hist, totals))
             for i in range(cfg["clients"])]
    await asyncio.gather(*tasks, return_exceptions=True)
    elapsed = time.monotonic() - t0
    return {"worker_id": cfg["worker_id"], "clients": cfg["clients"],
            "elapsed_s": elapsed, "hist": hist, **totals}


def worker_entry(raw: str) -> int:
    cfg = json.loads(raw)
    result = asyncio.run(_worker_main(cfg))
    print(json.dumps(result), flush=True)
    return 0


# ---------------------------------------------------------------------------
# parent: server subprocess + exactly-once drainer + worker fleet
# ---------------------------------------------------------------------------
SCENARIOS: Dict[str, Dict[str, Any]] = {
    # the CI smoke: small fleet, short burst, single shard
    "smoke": dict(clients=500, workers=2, duration=5.0, ramp=2.0,
                  shards=1, capacity=4096, genome_len=64,
                  think_min=0.2, think_max=1.0),
    # the headline: 10k concurrent volunteers against 4 shards
    "load_10k": dict(clients=10_000, workers=4, duration=20.0, ramp=8.0,
                     shards=4, capacity=8192, genome_len=64,
                     think_min=4.0, think_max=12.0),
}


class Drainer(threading.Thread):
    """The pod-bridge side of the experiment: drain the pool with a named
    server-side cursor and prove exactly-once — no ``(shard, seq)`` seen
    twice, and the final ledger ``sum(cursor+1) == delivered + dropped``
    must balance."""

    def __init__(self, url: str, experiment: str, shards: int):
        super().__init__(daemon=True)
        from repro.server.client import RemotePoolServer
        self.client = RemotePoolServer(url, experiment=experiment,
                                       client_id="bench-drain",
                                       timeout=30.0)
        self.shards = shards
        self.cursor: Any = -1
        self.seen: set = set()
        self.delivered = 0
        self.dropped = 0
        self.duplicates = 0
        self.errors = 0
        self._halt = threading.Event()

    def _drain_once(self, limit: int = 2048) -> int:
        entries, self.cursor, dropped = self.client.get_since(
            self.cursor, limit=limit, cursor_id="bench-drain")
        self.dropped += dropped
        for e in entries:
            key = (e.shard, e.seq)
            if key in self.seen:
                self.duplicates += 1
            self.seen.add(key)
        self.delivered += len(entries)
        return len(entries)

    def run(self) -> None:
        from repro.core.async_pool import PoolUnavailable
        while not self._halt.is_set():
            try:
                self._drain_once()
            except PoolUnavailable:
                self.errors += 1
            self._halt.wait(0.05)
        # final sweep: the fleet has stopped, drain to empty
        for _ in range(1000):
            try:
                if self._drain_once() == 0:
                    break
            except PoolUnavailable:
                self.errors += 1
                time.sleep(0.1)

    def stop(self) -> None:
        self._halt.set()

    def ledger(self) -> Dict[str, Any]:
        cursors = (self.cursor if isinstance(self.cursor, list)
                   else [self.cursor])
        covered = sum(c + 1 for c in cursors)
        return {"delivered": self.delivered, "dropped": self.dropped,
                "duplicates": self.duplicates, "cursor": cursors,
                "drain_errors": self.errors,
                "exactly_once_ok": (self.duplicates == 0
                                    and covered == self.delivered
                                    + self.dropped)}


def _spawn_server(spec: Dict[str, Any], spool: str) -> "subprocess.Popen":
    cmd = [sys.executable, "-m", "repro.server", "--port", "0",
           "--spool", spool, "--shards", str(spec["shards"]),
           "--capacity", str(spec["capacity"]),
           "--rate", "200", "--burst", "400", "--max-queue", "512"]
    env = dict(os.environ)
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.Popen(cmd, stdout=subprocess.PIPE, env=env, text=True)


def _await_url(proc: "subprocess.Popen") -> str:
    line = proc.stdout.readline()
    if "listening on" not in line:
        raise RuntimeError(f"server failed to start: {line!r}")
    return line.rsplit(" ", 1)[-1].strip()


def run_scenario(name: str, url: Optional[str] = None,
                 seed: int = 0) -> Dict[str, Any]:
    from repro.server.client import RemotePoolServer

    spec = SCENARIOS[name]
    experiment = f"bench-{name}"
    proc = spool_ctx = None
    try:
        if url is None:
            spool_ctx = tempfile.TemporaryDirectory(prefix="server_load_")
            proc = _spawn_server(spec, spool_ctx.name)
            url = _await_url(proc)
        admin = RemotePoolServer(url, experiment=experiment,
                                 client_id="bench-admin", timeout=30.0)
        admin.create(capacity=spec["capacity"], shards=spec["shards"],
                     seed=1)
        drainer = Drainer(url, experiment, spec["shards"])
        drainer.start()

        worker_cfgs = []
        per = spec["clients"] // spec["workers"]
        for w in range(spec["workers"]):
            n = per + (spec["clients"] % spec["workers"]
                       if w == spec["workers"] - 1 else 0)
            worker_cfgs.append({
                "url": url, "experiment": experiment, "clients": n,
                "duration": spec["duration"], "ramp": spec["ramp"],
                "seed": seed + w, "worker_id": w,
                "genome_len": spec["genome_len"],
                "think_min": spec["think_min"],
                "think_max": spec["think_max"]})
        env = dict(os.environ)
        env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
        t0 = time.perf_counter()
        procs = [subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--worker", json.dumps(cfg)],
            stdout=subprocess.PIPE, env=env, text=True)
            for cfg in worker_cfgs]
        results = []
        for p in procs:
            out, _ = p.communicate()
            if p.returncode != 0:
                raise RuntimeError(f"load worker failed (rc={p.returncode})")
            results.append(json.loads(out.strip().splitlines()[-1]))
        wall = time.perf_counter() - t0

        drainer.stop()
        drainer.join(timeout=120.0)
        stats = admin.stats()
        # the bare endpoint now serves Prometheus text; the harness wants
        # the structured legacy dict
        metricz = admin._verb("GET", "/metricz?format=json")
        admin.close()
        drainer.client.close()

        hist = [0] * HIST_BINS
        for r in results:
            for i, c in enumerate(r["hist"]):
                hist[i] += c
        agg = {k: sum(r[k] for r in results)
               for k in ("puts_ok", "puts_failed", "gets_ok", "gets_failed",
                         "responses", "lost", "throttled")}
        ledger = drainer.ledger()
        accepted = stats["puts"] - stats["rejected"]
        row = {
            "scenario": name,
            "clients": spec["clients"], "workers": spec["workers"],
            "shards": spec["shards"], "capacity": spec["capacity"],
            "duration_s": spec["duration"], "ramp_s": spec["ramp"],
            "wall_s": round(wall, 3),
            "requests": agg["responses"] + agg["lost"],
            "requests_per_sec": round(
                (agg["responses"] + agg["lost"]) / wall, 1),
            "p50_ms": round(hist_percentile(hist, 0.50), 2),
            "p99_ms": round(hist_percentile(hist, 0.99), 2),
            **agg,
            "server_puts_accepted": accepted,
            "server_stats": {k: stats[k] for k in
                             ("size", "capacity", "puts", "rejected",
                              "gets", "best_fitness")},
            "frontend_metrics": metricz.get("metrics", {}),
            **ledger,
        }
        return row
    finally:
        if proc is not None:
            proc.send_signal(signal.SIGTERM)
            try:
                proc.wait(timeout=15.0)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
            proc.stdout.close()
        if spool_ctx is not None:
            spool_ctx.cleanup()


def payload(rows: List[Dict[str, Any]]) -> Dict[str, Any]:
    return {
        "benchmark": "server_load",
        "driver": "python -m repro.server subprocess + multiprocess "
                  "asyncio volunteer fleet (pure wire clients, no jax "
                  "in workers) + exactly-once drainer thread",
        "metric": "wire requests per wall-clock second across the fleet; "
                  "p50/p99 from a log-spaced latency histogram merged "
                  "across workers; exactly-once ledger from a named "
                  "get_since cursor (dropped must be 0 at 10k)",
        "cpu_count": os.cpu_count(),
        "rows": rows,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--worker", help=argparse.SUPPRESS)
    ap.add_argument("--scenario", choices=sorted(SCENARIOS), default=None,
                    help="run one scenario (default: smoke, or all with "
                         "--full)")
    ap.add_argument("--full", action="store_true",
                    help="run every scenario including the 10k fleet")
    ap.add_argument("--url", default=None,
                    help="attack an already-running server instead of "
                         "spawning one")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", default="BENCH_server.json")
    args = ap.parse_args(argv)

    if args.worker:
        return worker_entry(args.worker)

    names = ([args.scenario] if args.scenario
             else sorted(SCENARIOS) if args.full else ["smoke"])
    rows = []
    for name in names:
        print(f"server_load: scenario {name} "
              f"({SCENARIOS[name]['clients']} clients / "
              f"{SCENARIOS[name]['workers']} workers / "
              f"{SCENARIOS[name]['shards']} shards)...", flush=True)
        row = run_scenario(name, url=args.url, seed=args.seed)
        print(f"server_load: {name}: {row['requests_per_sec']:.0f} req/s, "
              f"p50 {row['p50_ms']:.1f}ms p99 {row['p99_ms']:.1f}ms, "
              f"throttled {row['throttled']}, lost {row['lost']}, "
              f"delivered {row['delivered']}, dropped {row['dropped']}, "
              f"exactly_once={'OK' if row['exactly_once_ok'] else 'BROKEN'}",
              flush=True)
        rows.append(row)

    from benchmarks import hostmeta
    with open(args.json, "w") as fh:
        json.dump(hostmeta.stamp(payload(rows)), fh, indent=2)
    print(f"wrote {args.json}")
    bad = [r["scenario"] for r in rows if not r["exactly_once_ok"]]
    if bad:
        print(f"server_load: FAIL — exactly-once ledger broken in {bad}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())

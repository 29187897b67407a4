"""Networked pool service: golden wire fixtures, rate limiting,
backpressure, namespace isolation, sharded exactly-once, client/bridge
equivalence, spool resume.

The golden transcript (``tests/data/server_wire_golden.json``) pins
every verb's request AND response shape — any wire drift (renamed field,
changed status code, reordered cursor semantics) fails here before a
deployed volunteer ever sees it. Regenerate deliberately after a wire
change with:

    PYTHONPATH=src python tests/test_server.py --regen
"""
import http.client
import json
import os
import sys
import threading

import numpy as np
import pytest

from repro.core.async_pool import PoolServer, PoolUnavailable
from repro.server import wire
from repro.server.http import PoolHTTPServer, background_server
from repro.server.client import RemotePoolServer
from repro.server.ratelimit import RateLimiter, TokenBucket
from repro.server.service import (ExperimentConfig, HashRing, PoolService,
                                  check_name)

GOLDEN_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "data", "server_wire_golden.json")


def _raw(server, method, path, body=None, client_id="golden"):
    """One raw HTTP round trip -> (status, headers-dict, parsed-json)."""
    conn = http.client.HTTPConnection(server.host, server.port, timeout=10)
    try:
        payload = (json.dumps(body, separators=(",", ":"))
                   if body is not None else None)
        conn.request(method, path, body=payload,
                     headers={"Content-Type": "application/json",
                              "X-Client-Id": client_id})
        resp = conn.getresponse()
        raw = resp.read()
        return (resp.status, {k.lower(): v for k, v in resp.getheaders()},
                json.loads(raw) if raw else {})
    finally:
        conn.close()


# ---------------------------------------------------------------------------
# golden wire transcript
# ---------------------------------------------------------------------------
def _golden_items():
    return wire.put_request([
        wire.put_item(np.array([1, 0, 1, 1], np.int8), 3.0, uuid=0),
        wire.put_item(np.array([0, 0, 0, 1], np.int8), 1.0, uuid=1),
        wire.put_item(np.array([1, 1, 1, 1], np.int8), 4.0, uuid=2),
        wire.put_item(np.array([0.5, -0.5], np.float32), 2.5, uuid=3),
    ])


#: (method, path, body) — every verb, happy paths and canonical errors.
#: All responses are deterministic: experiment RNGs are seeded, routing
#: is blake2b (process-stable), counters depend only on this sequence.
GOLDEN_STEPS = [
    ("GET", "/healthz", None),
    ("POST", "/v1/experiment/golden",
     {"capacity": 8, "shards": 2, "seed": 3}),
    ("POST", "/v1/experiment/golden",
     {"capacity": 8, "shards": 2, "seed": 3}),          # idempotent re-create
    ("POST", "/v1/experiment/golden", {"capacity": 4}),  # config conflict
    ("PUT", "/v1/experiment/golden/chromosomes", _golden_items()),
    ("GET", "/v1/experiment/golden/chromosomes/random?n=2", None),
    ("GET", "/v1/experiment/golden/chromosomes/since"
            "?seq=-1&limit=10&cursor_id=gold", None),
    # same named cursor, cold seq: the server-side position wins — an
    # amnesiac consumer never re-sees an entry
    ("GET", "/v1/experiment/golden/chromosomes/since"
            "?seq=-1&limit=10&cursor_id=gold", None),
    ("GET", "/v1/experiment/golden/best", None),
    ("GET", "/v1/experiment/golden/stats", None),
    ("DELETE", "/v1/experiment/golden", None),
    ("GET", "/v1/experiment/golden/best", None),         # 404: empty pool
    ("GET", "/v1/experiments", None),
    ("GET", "/v1/nope", None),                           # 404: no route
    ("POST", "/v1/experiment/golden/best", None),        # 405: wrong method
    ("PUT", "/v1/experiment/golden/chromosomes",
     {"items": "nope"}),                                 # 400: malformed
]


def run_golden_transcript():
    """Execute GOLDEN_STEPS against a fresh server; return the
    transcript as JSON-able dicts."""
    out = []
    with background_server(rate=100000, burst=100000) as server:
        for method, path, body in GOLDEN_STEPS:
            status, _, resp = _raw(server, method, path, body)
            out.append({"method": method, "path": path, "body": body,
                        "status": status, "response": resp})
    return out


def test_golden_wire_transcript():
    assert os.path.isfile(GOLDEN_PATH), (
        f"missing {GOLDEN_PATH} — regenerate with "
        f"`PYTHONPATH=src python tests/test_server.py --regen`")
    with open(GOLDEN_PATH) as fh:
        golden = json.load(fh)
    assert golden["wire_version"] == wire.WIRE_VERSION, (
        "WIRE_VERSION bumped without regenerating the golden fixture")
    live = run_golden_transcript()
    assert len(live) == len(golden["transcript"])
    for i, (want, got) in enumerate(zip(golden["transcript"], live)):
        assert got == want, (
            f"wire drift at step {i} ({want['method']} {want['path']}):\n"
            f"  golden: {json.dumps(want, sort_keys=True)}\n"
            f"  live:   {json.dumps(got, sort_keys=True)}")


# ---------------------------------------------------------------------------
# load shedding
# ---------------------------------------------------------------------------
def test_rate_limit_429_with_retry_after():
    with background_server(rate=0.5, burst=2) as server:
        ok = [_raw(server, "GET", "/v1/experiments", client_id="greedy")[0]
              for _ in range(2)]
        assert ok == [200, 200]
        status, headers, body = _raw(server, "GET", "/v1/experiments",
                                     client_id="greedy")
        assert status == 429
        assert body["error"] == "rate limited"
        assert body["retry_after"] > 0
        assert float(headers["retry-after"]) > 0
        # a different client id is a different bucket
        assert _raw(server, "GET", "/v1/experiments",
                    client_id="patient")[0] == 200
        # liveness bypasses throttling even for the greedy client
        assert _raw(server, "GET", "/healthz", client_id="greedy")[0] == 200


def test_backpressure_queue_depth():
    # max_queue=0: every verb is shed, liveness still answers
    with background_server(max_queue=0) as server:
        status, headers, body = _raw(server, "GET", "/v1/experiments")
        assert status == 429 and body["error"] == "server busy"
        assert "retry-after" in headers
        assert _raw(server, "GET", "/healthz")[0] == 200
        assert _raw(server, "GET", "/metricz?format=json")[2]["metrics"][
            "throttled_queue"] == 1


def _raw_text(server, method, path, client_id="golden"):
    """Raw round trip without JSON-decoding the body (text endpoints)."""
    conn = http.client.HTTPConnection(server.host, server.port, timeout=10)
    try:
        conn.request(method, path, headers={"X-Client-Id": client_id})
        resp = conn.getresponse()
        return (resp.status, {k.lower(): v for k, v in resp.getheaders()},
                resp.read().decode())
    finally:
        conn.close()


def test_metricz_prometheus_text():
    """Bare /metricz serves the Prometheus text format with per-verb
    latency histograms; ?format=json keeps the legacy dict shape."""
    from repro.obs import metrics as obs_metrics

    with background_server() as server:
        c = RemotePoolServer(server.url, experiment="mz")
        c.put(np.ones(4, np.int8), 4.0, uuid=1)
        c.get_best()
        c.close()
        status, headers, text = _raw_text(server, "GET", "/metricz")
        assert status == 200
        assert headers["content-type"].startswith("text/plain; version=0.0.4")
        samples = obs_metrics.parse_prometheus(text)
        assert samples["repro_requests"] >= 2
        assert samples["repro_queue_depth"] == 0
        assert samples["repro_max_queue"] == server.max_queue
        # per-verb histogram: the PUT landed in exactly the bins the
        # cumulative +Inf bucket and _count agree on
        count = samples["repro_verb_put_latency_seconds_count"]
        assert count >= 1
        assert samples[
            'repro_verb_put_latency_seconds_bucket{le="+Inf"}'] == count
        assert samples["repro_verb_put_latency_seconds_sum"] > 0.0
        # legacy JSON view still served, now with latency summaries
        body = _raw(server, "GET", "/metricz?format=json")[2]
        assert body["metrics"]["requests"] >= 2
        assert body["latency"]["put"]["count"] == count


# ---------------------------------------------------------------------------
# namespaces
# ---------------------------------------------------------------------------
def test_namespace_isolation():
    with background_server() as server:
        a = RemotePoolServer(server.url, experiment="exp-a")
        b = RemotePoolServer(server.url, experiment="exp-b")
        a.put(np.ones(4, np.int8), 7.0, uuid=1)
        # b sees nothing from a
        assert b.stats()["size"] == 0
        with pytest.raises(PoolUnavailable):
            b.get_best()
        b.put(np.zeros(4, np.int8), 1.0, uuid=2)
        # resetting b leaves a intact
        assert b.reset() == 1
        assert b.stats()["size"] == 0
        g, f = a.get_best()
        assert f == 7.0 and a.stats()["experiment"] == 0
        np.testing.assert_array_equal(g, np.ones(4, np.int8))
        a.close(), b.close()


def test_create_config_conflict_and_bad_names():
    with background_server() as server:
        c = RemotePoolServer(server.url, experiment="cfg")
        assert c.create(capacity=32, shards=2)["created"] is True
        assert c.create(capacity=32, shards=2)["created"] is False
        with pytest.raises(PoolUnavailable, match="HTTP 409"):
            c.create(capacity=64)
        c.close()
        for bad in ("", "../etc", "a/b", "-lead", "x" * 65):
            with pytest.raises(ValueError):
                check_name(bad)
    with pytest.raises(ValueError, match="no host mirror"):
        ExperimentConfig.from_json({"acceptance": "no-such-policy"})
    with pytest.raises(ValueError):
        ExperimentConfig.from_json({"capacity": 0})


# ---------------------------------------------------------------------------
# sharded exactly-once over the wire
# ---------------------------------------------------------------------------
def test_sharded_drain_is_exactly_once():
    with background_server(rate=100000, burst=100000) as server:
        c = RemotePoolServer(server.url, experiment="sharded")
        c.create(capacity=64, shards=3, seed=1)
        n = 60
        c.put_batch([(np.array([i], np.int8), float(i), i)
                     for i in range(n)])
        seen, cursor, dropped = set(), -1, 0
        while True:
            entries, cursor, d = c.get_since(cursor, limit=7,
                                             cursor_id="drain")
            dropped += d
            for e in entries:
                key = (e.shard, e.seq)
                assert key not in seen, f"duplicate {key}"
                seen.add(key)
            if not entries:
                break
        assert len(seen) == n and dropped == 0
        # the ledger: every seq the cursors passed is delivered or dropped
        assert sum(cc + 1 for cc in cursor) == len(seen) + dropped
        # a second drain under the same cursor_id yields nothing
        entries, _, _ = c.get_since(-1, limit=100, cursor_id="drain")
        assert entries == []
        c.close()


# ---------------------------------------------------------------------------
# client equivalence: the wire surface behaves like the in-process one
# ---------------------------------------------------------------------------
def test_remote_matches_inprocess_semantics():
    puts = [(np.array([i, i + 1], np.int8), float(i % 5), i) for i in range(12)]
    local = PoolServer(capacity=8)
    for g, f, u in puts:
        local.put(g, f, uuid=u)
    with background_server() as server:
        remote = RemotePoolServer(server.url, experiment="equiv")
        remote.create(capacity=8, shards=1)
        remote.put_batch(puts)
        for key in ("size", "puts", "rejected", "best_fitness"):
            assert remote.stats()[key] == local.stats()[key], key
        # full drains agree entry-for-entry (single shard: same order)
        le, lc, ld = local.get_since(-1, limit=100)
        re_, rc, rd = remote.get_since(-1, limit=100)
        assert [e.seq for e in re_] == [e.seq for e in le]
        assert rc == [lc] and rd == ld
        for a, b in zip(re_, le):
            np.testing.assert_array_equal(a.genome, b.genome)
            assert (a.fitness, a.uuid) == (b.fitness, b.uuid)
        assert remote.get_best()[1] == local.get_best()[1]
        # the blocking surface also mirrors misuse guards
        with pytest.raises(ValueError):
            remote.put_with_payload(np.ones(2), 1.0, payload={"x": 1})
        assert remote.up is True
        remote.close()


def test_async_bridge_worker_over_wire():
    # the AsyncHostBridge worker loop (put + exactly-once drain + echo
    # filtering) against a networked service, no device pool needed
    from repro.core.async_migration import AsyncHostBridge
    with background_server() as server:
        feeder = RemotePoolServer(server.url, experiment="bridge")
        feeder.create(capacity=32, shards=2)
        feeder.put(np.array([9, 9, 9], np.int8), 9.0, uuid=1)
        bridge = AsyncHostBridge(server.url, pull=8, uuid=42,
                                 cursor_id="bw", experiment="bridge")
        try:
            bridge._jobs.put((np.array([4, 4, 4], np.int8), 4.0))
            bridge._jobs.join()
            assert bridge.pushed == 1 and bridge.lost == 0
            with bridge._flock:
                fetched = list(bridge._fetched)
            # fetched the feeder's entry; its own push is filtered by uuid
            assert [f for _, f in fetched] == [9.0]
            # the service saw both puts
            assert feeder.stats()["puts"] == 2
        finally:
            bridge.close()
            feeder.close()


# ---------------------------------------------------------------------------
# the server leaves the accelerator alone
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("module", ["repro.server", "repro.server.service",
                                    "repro.server.__main__"])
def test_import_starts_no_jax_backend(module):
    """``python -m repro.server`` runs beside a process that holds the
    chip; a backend started at import would claim the chip itself."""
    import subprocess
    code = (f"import importlib; m = importlib.import_module({module!r}); "
            "[getattr(m, a) for a in getattr(m, '__all__', ())]; "
            "from jax._src import xla_bridge; "
            "print(xla_bridge.backends_are_initialized())")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=os.path.join(repo, "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=repo,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "False"


# ---------------------------------------------------------------------------
# durability: spool resume (in-process; the cross-process leg lives in
# scripts/kill_resume_smoke.py leg 4)
# ---------------------------------------------------------------------------
def test_spool_resume_restores_namespaces_and_cursors(tmp_path):
    spool = str(tmp_path / "spool")
    cfg = ExperimentConfig(capacity=16, shards=2, seed=4)
    svc = PoolService(spool_dir=spool)
    exp, created = svc.ensure("persist", cfg)
    assert created
    exp.put_batch([(np.array([i], np.int8), float(i), i) for i in range(10)])
    items, cursors, dropped = exp.get_since([-1, -1], limit=4,
                                            cursor_id="resume-test")
    first = {(shard, e.seq) for e, shard in items}
    assert len(first) == 4 and dropped == 0
    svc.close()

    svc2 = PoolService(spool_dir=spool, resume=True)
    assert svc2.experiments() == ["persist"]
    exp2, created2 = svc2.ensure("persist", cfg)   # config round-tripped
    assert not created2
    st = exp2.stats()
    assert st["puts"] == 10 and st["size"] == 10 and st["shards"] == 2
    # the named cursor survived: a cold (-1) drain skips the 4 delivered
    items2, cursors2, dropped2 = exp2.get_since([-1, -1], limit=100,
                                                cursor_id="resume-test")
    second = {(shard, e.seq) for e, shard in items2}
    assert not (first & second), "exactly-once violated across resume"
    assert len(first | second) == 10 and dropped2 == 0
    assert sum(c + 1 for c in cursors2) == 10
    svc2.close()


# ---------------------------------------------------------------------------
# unit: HashRing and TokenBucket
# ---------------------------------------------------------------------------
def test_hash_ring_balance_and_stability():
    ring4, ring5 = HashRing(4), HashRing(5)
    keys = range(2000)
    homes4 = [ring4.route(k) for k in keys]
    # balance: no shard owns a wildly disproportionate share
    counts = np.bincount(homes4, minlength=4)
    assert counts.min() > 0.5 * len(homes4) / 4
    # stability: growing 4 -> 5 moves only ~1/5 of the keyspace
    moved = sum(1 for k, h in zip(keys, homes4) if ring5.route(k) != h)
    assert moved / len(homes4) < 0.35
    # process-stable routing (blake2b, not salted hash())
    assert ring4.route("volunteer-7") == ring4.route("volunteer-7")
    with pytest.raises(ValueError):
        HashRing(0)


def test_token_bucket_injectable_clock():
    b = TokenBucket(rate=2.0, burst=2.0, now=0.0)
    assert b.allow(0.0) and b.allow(0.0)
    assert not b.allow(0.0)
    assert b.retry_after(0.0) == pytest.approx(0.5)
    assert b.allow(0.5)                      # one token accrued
    assert not b.allow(0.5)
    b2 = TokenBucket(rate=1.0, burst=5.0, now=0.0)
    for _ in range(5):
        assert b2.allow(100.0)               # refill caps at burst
    assert not b2.allow(100.0)
    with pytest.raises(ValueError):
        TokenBucket(rate=0, burst=1)


def test_rate_limiter_lru_eviction():
    lim = RateLimiter(rate=1.0, burst=1.0, max_clients=2)
    assert lim.allow("a", now=0.0) and lim.allow("b", now=0.0)
    assert not lim.allow("a", now=0.0)       # a's bucket is dry (and MRU now)
    lim.allow("c", now=0.0)                  # evicts LRU ("b")
    assert len(lim) == 2
    assert not lim.allow("a", now=0.0)       # a survived, still dry
    assert lim.allow("b", now=0.0)           # evicted => fresh burst


def test_wire_cursor_codec():
    assert wire.decode_cursor(None, 3) == [-1, -1, -1]
    assert wire.decode_cursor("-1", 3) == [-1, -1, -1]    # scalar broadcast
    assert wire.decode_cursor("4,7,0", 3) == [4, 7, 0]
    assert wire.encode_cursor([4, 7, 0]) == "4,7,0"
    assert wire.encode_cursor(-1) == "-1"
    with pytest.raises(ValueError):
        wire.decode_cursor("1,2", 3)


def test_genome_codec_round_trip():
    for arr in (np.array([1, 0, 1], np.int8),
                np.array([0.25, -1.5], np.float32),
                np.arange(6, dtype=np.float64)):
        out = wire.decode_genome(json.loads(
            json.dumps(wire.encode_genome(arr))))
        assert out.dtype == arr.dtype
        np.testing.assert_array_equal(out, arr)


if __name__ == "__main__":
    if "--regen" in sys.argv:
        os.makedirs(os.path.dirname(GOLDEN_PATH), exist_ok=True)
        payload = {"wire_version": wire.WIRE_VERSION,
                   "transcript": run_golden_transcript()}
        with open(GOLDEN_PATH, "w") as fh:
            json.dump(payload, fh, indent=1)
            fh.write("\n")
        print(f"wrote {GOLDEN_PATH} "
              f"({len(payload['transcript'])} steps)")
    else:
        sys.exit(pytest.main([__file__, "-q"]))

"""Unified observability layer (ISSUE 10).

* :class:`repro.obs.counters.ObsCounters` ride the fused scan carries as
  pure integer accumulation, so harvested totals are bit-for-bit
  invariant to segmentation (sync, async and sharded drivers), identical
  across generation-kernel impls under ``acceptance="always"`` with
  ``inbox_capacity=1`` (availability-driven masks, never fitness-driven),
  and the ledger ``delivered == accepted + rejected`` balances by
  construction — including under churn and rejecting policies;
* :class:`repro.obs.trace.Tracer` records spans thread-safely into a
  bounded ring; the Chrome trace-event export is pinned by a golden
  fixture (``tests/data/golden_trace.json``) built on an injectable
  deterministic clock.  Regenerate deliberately after an export-format
  change with:

      PYTHONPATH=src python tests/test_obs.py --regen

* :mod:`repro.obs.metrics` round-trips the log-binned latency histogram
  through the Prometheus text exposition;
* the ``python -m repro.obs`` timeline CLI merges traces + harvests into
  one summary and exits nonzero on an unbalanced ledger.
"""
import itertools
import json
import os
import subprocess
import sys
import threading

import jax
import numpy as np
import pytest

from repro.core import (AsyncConfig, EAConfig, MigrationConfig, make_onemax,
                        make_rastrigin, run_fused, run_fused_async)
from repro.core.types import AcceptanceConfig
from repro.obs import __main__ as obs_cli
from repro.obs import counters as obs_counters
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.trace import Tracer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GOLDEN_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "data", "golden_trace.json")

CFG = EAConfig(max_pop=32, min_pop=32, generations_per_epoch=3,
               max_evaluations=10**9)
PROBLEM = make_onemax(24)
# never solved at this budget: no early-stop latch, so fired counts can't
# diverge between impls/runs that would otherwise stop at different epochs
HARD = make_rastrigin(dim=16)
KEY = jax.random.key(42)
ACFG = AsyncConfig(min_rate=0.5, max_rate=1.0, staleness=2,
                   churn_fraction=0.3, inbox_capacity=3)


@pytest.fixture(autouse=True)
def _module_tracer_off():
    """Tests that enable() the module tracer must not leak it."""
    yield
    obs_trace.disable()


# ---------------------------------------------------------------------------
# on-device counters: ledger + segmentation/impl invariance
# ---------------------------------------------------------------------------
def balanced(harvest):
    t = harvest["totals"]
    return t["delivered"] == t["accepted"] + t["rejected"]


class TestCountersSync:
    def test_harvest_shape_and_ledger(self):
        *_, obs = run_fused(PROBLEM, CFG, n_islands=6, max_epochs=8, rng=KEY,
                            return_obs=True)
        assert obs["n_islands"] == 6
        assert len(obs["fired"]) == 6
        assert np.asarray(obs["inbox_age_hist"]).shape == (
            6, obs_counters.AGE_BINS)
        assert obs["totals"]["fired"] > 0
        assert balanced(obs)
        # the sync driver never churns and absorbs at delivery (age 0)
        assert obs["totals"]["churn_down"] == 0
        ages = obs["totals"]["inbox_age_hist"]
        assert sum(ages[1:]) == 0 and ages[0] == obs["totals"]["accepted"]

    def test_early_stop_latch(self):
        easy = make_onemax(8)
        *_, obs = run_fused(easy, CFG, n_islands=4, max_epochs=30,
                            rng=jax.random.key(1), return_obs=True)
        assert 1 <= obs["early_stop_epoch"] <= 30

    def test_segmented_matches_monolithic(self, tmp_path):
        mono = run_fused(PROBLEM, CFG, n_islands=6, max_epochs=9, rng=KEY,
                         return_obs=True)[-1]
        seg = run_fused(PROBLEM, CFG, n_islands=6, max_epochs=9, rng=KEY,
                        return_obs=True, snapshot_every=3,
                        snapshot_dir=str(tmp_path))[-1]
        assert seg == mono

    def test_elitist_policy_rejects_and_balances(self):
        mig = MigrationConfig(acceptance=AcceptanceConfig(policy="elitist"))
        *_, obs = run_fused(HARD, CFG, mig, n_islands=6, max_epochs=10,
                            rng=KEY, return_obs=True)
        assert obs["totals"]["rejected"] > 0
        assert obs["totals"]["accepted"] < obs["totals"]["delivered"]
        assert balanced(obs)


class TestCountersAsync:
    def test_churn_is_counted_and_ledger_balances(self):
        churny = AsyncConfig(min_rate=0.4, max_rate=1.0, staleness=2,
                             churn_fraction=0.5, inbox_capacity=3)
        # HARD never early-stops, so the run reaches the churn windows
        # (which open inside [0.25, 0.75) x max_ticks)
        *_, obs = run_fused_async(HARD, CFG, acfg=churny, n_islands=6,
                                  max_ticks=12, rng=KEY, return_obs=True)
        assert obs["totals"]["churn_down"] > 0
        assert balanced(obs)
        # absorb-time re-gate is not double-counted: every absorbed
        # immigrant passed the delivery gate first
        assert sum(obs["totals"]["inbox_age_hist"]) <= obs["totals"]["accepted"]

    def test_segmented_matches_monolithic(self, tmp_path):
        mono = run_fused_async(PROBLEM, CFG, acfg=ACFG, n_islands=6,
                               max_ticks=9, rng=KEY, return_obs=True)[-1]
        seg = run_fused_async(PROBLEM, CFG, acfg=ACFG, n_islands=6,
                              max_ticks=9, rng=KEY, return_obs=True,
                              snapshot_every=3, snapshot_dir=str(tmp_path))[-1]
        assert seg == mono

    def test_degenerate_async_matches_sync(self):
        sync = run_fused(PROBLEM, CFG, n_islands=6, max_epochs=8, rng=KEY,
                         return_obs=True)[-1]
        asyn = run_fused_async(PROBLEM, CFG, acfg=AsyncConfig(), n_islands=6,
                               max_ticks=8, rng=KEY, return_obs=True)[-1]
        assert asyn == sync

    @pytest.mark.parametrize("impl", ["jnp", "pallas", "pallas_tiled"])
    def test_impl_invariant_totals(self, impl):
        """acceptance='always' + inbox_capacity=1: every mask the counters
        accumulate is availability/clock-driven, so totals are identical
        across generation impls even though fitness trajectories differ.
        (capacity>1 + staleness makes the absorbed *pick* fitness-dependent,
        which is why the invariance contract pins capacity=1.)"""
        cfg = EAConfig(max_pop=32, min_pop=32, generations_per_epoch=3,
                       max_evaluations=10**9, impl=impl)
        acfg = AsyncConfig(min_rate=0.5, max_rate=1.0, staleness=2,
                           churn_fraction=0.3, inbox_capacity=1)
        *_, obs = run_fused_async(HARD, cfg, acfg=acfg, n_islands=6,
                                  max_ticks=8, rng=KEY, return_obs=True)
        ref = run_fused_async(
            HARD, EAConfig(max_pop=32, min_pop=32, generations_per_epoch=3,
                           max_evaluations=10**9),
            acfg=acfg, n_islands=6, max_ticks=8, rng=KEY, return_obs=True)[-1]
        assert obs == ref


class TestCountersSharded:
    def _mesh(self):
        from jax.sharding import Mesh
        return Mesh(np.array(jax.devices()), ("islands",))

    def test_sharded_segmented_matches_monolithic(self, tmp_path):
        from repro.core.sharded import run_fused_sharded
        mesh = self._mesh()
        per = max(1, 8 // mesh.shape["islands"])
        mono = run_fused_sharded(mesh, PROBLEM, CFG, islands_per_shard=per,
                                 max_epochs=8, rng=KEY, return_obs=True)[-1]
        seg = run_fused_sharded(mesh, PROBLEM, CFG, islands_per_shard=per,
                                max_epochs=8, rng=KEY, return_obs=True,
                                snapshot_every=3,
                                snapshot_dir=str(tmp_path))[-1]
        assert seg == mono
        assert balanced(mono)

    def test_sharded_async_segmented_matches_monolithic(self, tmp_path):
        from repro.core.sharded import run_fused_sharded_async
        mesh = self._mesh()
        per = max(1, 8 // mesh.shape["islands"])
        mono = run_fused_sharded_async(
            mesh, HARD, CFG, acfg=ACFG, islands_per_shard=per, max_ticks=9,
            rng=KEY, return_obs=True)[-1]
        seg = run_fused_sharded_async(
            mesh, HARD, CFG, acfg=ACFG, islands_per_shard=per, max_ticks=9,
            rng=KEY, return_obs=True, snapshot_every=4,
            snapshot_dir=str(tmp_path))[-1]
        assert seg == mono
        assert balanced(mono)


# ---------------------------------------------------------------------------
# host tracer
# ---------------------------------------------------------------------------
def _golden_trace():
    """Deterministic trace: counter clock (1ms per reading), main thread."""
    ticks = itertools.count()
    tracer = Tracer(clock=lambda: next(ticks) * 1e-3)
    with tracer.span("driver.segment", segment=0):
        with tracer.span("driver.tick", tick=0):
            pass
        with tracer.span("driver.tick", tick=1):
            pass
    with tracer.span("checkpoint.snapshot", step=2):
        with tracer.span("checkpoint.write"):
            pass
    tracer.instant("server.down")
    return tracer.to_chrome()


class TestTracer:
    def test_span_records_complete_event(self):
        tracer = Tracer()
        with tracer.span("pool.put", n=3):
            pass
        (ev,) = tracer.events()
        assert ev["ph"] == "X" and ev["name"] == "pool.put"
        assert ev["dur"] >= 0 and ev["args"] == {"n": 3}
        assert ev["pid"] == 1 and ev["tid"] == 1

    def test_ring_keeps_the_tail(self):
        tracer = Tracer(maxlen=16)
        for i in range(100):
            with tracer.span("s", i=i):
                pass
        evs = tracer.events()
        assert len(evs) == 16
        assert [e["args"]["i"] for e in evs] == list(range(84, 100))

    def test_thread_safety_under_concurrent_spans(self):
        tracer = Tracer()
        n_threads, n_spans = 8, 200
        start = threading.Barrier(n_threads)

        def worker(k):
            start.wait()
            for i in range(n_spans):
                with tracer.span("worker.op", k=k, i=i):
                    pass

        threads = [threading.Thread(target=worker, args=(k,), name=f"w{k}")
                   for k in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        evs = tracer.events()
        assert len(evs) == n_threads * n_spans
        # stable small-int tids, one per recording thread, names captured
        assert {e["tid"] for e in evs} == set(range(1, n_threads + 1))
        chrome = tracer.to_chrome()
        names = {ev["args"]["name"] for ev in chrome["traceEvents"]
                 if ev["ph"] == "M"}
        assert names == {f"w{k}" for k in range(n_threads)}
        # per-thread event order is preserved in the ring
        for k in range(n_threads):
            mine = [e["args"]["i"] for e in evs if e["args"]["k"] == k]
            assert mine == list(range(n_spans))

    def test_module_level_span_is_noop_when_disabled(self):
        obs_trace.disable()
        assert obs_trace.span("x") is obs_trace.span("y")
        tracer = obs_trace.enable()
        with obs_trace.span("pool.get_random"):
            pass
        obs_trace.instant("mark")
        assert [e["name"] for e in tracer.events()] == ["pool.get_random",
                                                        "mark"]
        obs_trace.disable()
        obs_trace.instant("dropped")
        assert len(tracer.events()) == 2

    def test_trace_module_is_jax_free(self):
        """Spans work, with and without a tracer, in a process that never
        imports jax; the profiler annotation is only looked up."""
        code = ("import sys\n"
                "from repro.obs import trace\n"
                "with trace.span('driver.init', n_islands=2):\n"
                "    pass\n"
                "t = trace.enable()\n"
                "with trace.span('pool.put', n=1):\n"
                "    pass\n"
                "assert [e['name'] for e in t.events()] == ['pool.put']\n"
                "assert 'jax' not in sys.modules, 'obs.trace pulled in jax'\n")
        env = dict(os.environ,
                   PYTHONPATH=os.path.join(REPO, "src") + os.pathsep
                   + os.environ.get("PYTHONPATH", ""))
        proc = subprocess.run([sys.executable, "-c", code], env=env,
                              capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr[-2000:]

    def test_driver_spans_carry_host_epochs(self):
        """run_fused's spans in order, one init, then a segment and a wait
        per scan segment, whose epoch args are the plan's host ints."""
        tracer = obs_trace.enable()
        run_fused(PROBLEM, CFG, MigrationConfig(), n_islands=2,
                  max_epochs=5, rng=KEY, w2=True, snapshot_every=2)
        evs = tracer.events()
        assert [e["name"] for e in evs] == (
            ["driver.init"] + ["driver.segment", "driver.wait"] * 3)
        assert evs[0]["args"] == {"n_islands": 2, "resume": False}
        segs = [e["args"] for e in evs if e["name"] == "driver.segment"]
        assert segs == [{"seg_len": 2, "epoch": 0},
                        {"seg_len": 2, "epoch": 2},
                        {"seg_len": 1, "epoch": 4}]
        waits = [e["args"] for e in evs if e["name"] == "driver.wait"]
        assert waits == [{"epoch": 0}, {"epoch": 2}, {"epoch": 4}]
        assert all(type(a["epoch"]) is int for a in segs + waits)

    def test_golden_chrome_trace(self):
        assert os.path.isfile(GOLDEN_PATH), (
            f"missing {GOLDEN_PATH} — regenerate with "
            f"`PYTHONPATH=src python tests/test_obs.py --regen`")
        with open(GOLDEN_PATH) as fh:
            golden = json.load(fh)
        live = _golden_trace()
        assert live == golden, (
            "Chrome trace export drifted from tests/data/golden_trace.json "
            "— if the format change is deliberate, regenerate with "
            "`PYTHONPATH=src python tests/test_obs.py --regen`")
        # and the fixture itself is a valid Chrome trace object
        assert golden["displayTimeUnit"] == "ms"
        xs = [e for e in golden["traceEvents"] if e["ph"] == "X"]
        assert all({"name", "ts", "dur", "pid", "tid"} <= set(e) for e in xs)


# ---------------------------------------------------------------------------
# metrics: histogram + Prometheus text round-trip
# ---------------------------------------------------------------------------
class TestMetrics:
    def test_hist_index_value_consistent(self):
        for ms in (0.01, 0.05, 1.0, 15.0, 1000.0, 500_000.0):
            i = obs_metrics.hist_index(ms)
            assert 0 <= i < obs_metrics.HIST_BINS
            assert obs_metrics.hist_value(i) <= obs_metrics.hist_upper(i)

    def test_percentiles(self):
        h = obs_metrics.hist_new()
        for ms in [1.0] * 98 + [1000.0] * 2:
            h[obs_metrics.hist_index(ms)] += 1
        assert obs_metrics.hist_percentile(h, 0.50) == pytest.approx(1.0,
                                                                     rel=0.1)
        assert obs_metrics.hist_percentile(h, 0.99) == pytest.approx(1000.0,
                                                                     rel=0.1)

    def test_prometheus_round_trip(self):
        h = obs_metrics.hist_new()
        samples = [0.2, 1.5, 1.5, 80.0, 2500.0]
        for ms in samples:
            h[obs_metrics.hist_index(ms)] += 1
        text = obs_metrics.render_prometheus(
            counters={"requests": 17}, gauges={"queue_depth": 3.5},
            histograms={"verb_put_latency": (h, sum(samples))})
        parsed = obs_metrics.parse_prometheus(text)
        assert parsed["repro_requests"] == 17
        assert parsed["repro_queue_depth"] == 3.5
        assert parsed['repro_verb_put_latency_seconds_bucket{le="+Inf"}'] \
            == len(samples)
        assert parsed["repro_verb_put_latency_seconds_count"] == len(samples)
        assert parsed["repro_verb_put_latency_seconds_sum"] == pytest.approx(
            sum(samples) / 1e3)
        # cumulative buckets are monotone and end at the total count
        buckets = [v for k, v in parsed.items() if "_bucket{" in k]
        assert buckets == sorted(buckets)
        assert buckets[-1] == len(samples)

    def test_prometheus_type_lines(self):
        text = obs_metrics.render_prometheus(counters={"a": 1},
                                             gauges={"b": 2})
        assert "# TYPE repro_a counter" in text
        assert "# TYPE repro_b gauge" in text


# ---------------------------------------------------------------------------
# timeline CLI
# ---------------------------------------------------------------------------
def _fake_harvest(fired=10, delivered=8, accepted=6, rejected=2,
                  churn=3, n=2):
    return {"n_islands": n, "fired": [fired // n] * n,
            "delivered": [delivered // n] * n,
            "accepted": [accepted // n] * n,
            "rejected": [rejected // n] * n, "churn_down": [churn // n] * n,
            "inbox_age_hist": [[0] * obs_counters.AGE_BINS] * n,
            "early_stop_epoch": -1,
            "totals": {"fired": fired, "delivered": delivered,
                       "accepted": accepted, "rejected": rejected,
                       "churn_down": churn,
                       "inbox_age_hist": [0] * obs_counters.AGE_BINS}}


class TestTimelineCLI:
    def test_span_summary(self):
        events = _golden_trace()["traceEvents"]
        spans = obs_cli.span_summary(events)
        assert spans["driver.tick"]["count"] == 2
        assert spans["driver.segment"]["count"] == 1
        assert spans["checkpoint.write"]["count"] == 1
        assert spans["driver.segment"]["total_ms"] \
            >= spans["driver.tick"]["total_ms"]
        assert spans["driver.tick"]["p50_ms"] <= spans["driver.tick"]["p99_ms"]

    def test_ledger_rates(self):
        rates = obs_cli.ledger_rates(_fake_harvest(), n_ticks=10)
        assert rates["ledger_balanced"]
        assert rates["delivery_rate"] == pytest.approx(0.8)
        assert rates["rejection_rate"] == pytest.approx(0.25)
        assert rates["churn_occupancy"] == pytest.approx(3 / 20)
        broken = obs_cli.ledger_rates(_fake_harvest(rejected=1))
        assert not broken["ledger_balanced"]

    def test_merge_traces_repids(self, tmp_path):
        for i in range(2):
            with open(tmp_path / f"t{i}.json", "w") as fh:
                json.dump(_golden_trace(), fh)
        merged = obs_cli.merge_traces([str(tmp_path / "t0.json"),
                                       str(tmp_path / "t1.json")])
        assert {e["pid"] for e in merged} == {1, 2}

    def _write_inputs(self, tmp_path, harvest):
        trace = tmp_path / "trace.json"
        obsj = tmp_path / "obs.json"
        with open(trace, "w") as fh:
            json.dump(_golden_trace(), fh)
        with open(obsj, "w") as fh:
            json.dump(harvest, fh)
        return str(trace), str(obsj)

    def test_cli_end_to_end_and_stamp(self, tmp_path):
        trace, obsj = self._write_inputs(tmp_path, _fake_harvest())
        out = tmp_path / "summary.json"
        rc = obs_cli.main([trace, "--obs", obsj, "--json", str(out)])
        assert rc == 0
        with open(out) as fh:
            summary = json.load(fh)
        assert summary["counters"]["ledger_balanced"]
        assert summary["events"] == 6   # 5 spans + 1 instant marker

    def test_cli_fails_on_unbalanced_ledger(self, tmp_path):
        trace, obsj = self._write_inputs(tmp_path,
                                         _fake_harvest(accepted=9))
        assert obs_cli.main([trace, "--obs", obsj]) == 1

    def test_cli_is_jax_free(self):
        """The timeline tool must import on the jax-free server tier."""
        code = ("import sys, repro.obs.__main__, repro.obs.metrics, "
                "repro.obs.trace; "
                "assert 'jax' not in sys.modules, 'obs CLI pulled in jax'")
        env = dict(os.environ,
                   PYTHONPATH=os.path.join(REPO, "src") + os.pathsep
                   + os.environ.get("PYTHONPATH", ""))
        proc = subprocess.run([sys.executable, "-c", code], env=env,
                              capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr[-2000:]


if __name__ == "__main__":
    if "--regen" in sys.argv:
        os.makedirs(os.path.dirname(GOLDEN_PATH), exist_ok=True)
        with open(GOLDEN_PATH, "w") as fh:
            json.dump(_golden_trace(), fh, indent=1, sort_keys=True)
            fh.write("\n")
        print(f"wrote {GOLDEN_PATH}")
    else:
        sys.exit(pytest.main([__file__, "-q"]))

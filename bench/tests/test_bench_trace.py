"""The reduction from a profiler trace to the per-layer metrics: interval
arithmetic on synthetic events, and every reader on a small trace of
``trap40.solve`` recorded on a v5e chip."""
from pathlib import Path

import pytest

from harness import experiments, peaks, spec
from harness import trace as T

DATA = Path(__file__).resolve().parent / "data"
SMALL = DATA / "trap40_solve_small.xplane.pb.gz"


def _ev(name, start, dur):
    return T.Event(name, start, dur)


def _trace(device, host):
    return T.Trace(device={0: sorted(device, key=lambda e: e.start)},
                   host=sorted(host, key=lambda e: e.start))


def test_busy_idle_and_gaps_with_nesting_and_clipping():
    # a loop op holding two ops, one op sticking out of the window
    dev = [_ev("%while.1 = s32[] while(x)", 1.0, 2.0),
           _ev("%fusion.2 = f32[] fusion(x)", 1.5, 0.5),
           _ev("%fusion.3 = f32[] fusion(x)", 2.5, 0.5),
           _ev("%copy.4 = f32[] copy(x)", 4.5, 1.0)]
    host = [_ev("window", 0.0, 5.0), _ev("experiment", 0.5, 3.0)]
    tr = _trace(dev, host)
    assert T.busy_s(tr, 0, 0.0, 5.0) == pytest.approx(2.5)
    assert T.mean_busy_s(tr) == pytest.approx(2.5)
    assert T.gaps(tr, 0, 0.0, 5.0) == [(0.0, 1.0), (3.0, 4.5)]
    assert T.gaps(tr, 0, 0.5, 3.5) == [(0.5, 1.0), (3.0, 3.5)]
    reader = spec.metric_reader("device_idle_share")
    assert reader.read({"trace": tr}) == pytest.approx(50.0)
    gap = spec.metric_reader("driver_gap_ms")
    assert gap.read({"trace": tr}) == pytest.approx(1e3 * (0.5 + 0.5))


def test_breakdown_leaves_out_loops_and_names_gaps():
    dev = [_ev("%while.1 = s32[] while(x)", 1.0, 2.0),
           _ev("%fusion.2 = f32[] fusion(x)", 1.5, 0.5),
           _ev("%fusion.7 = f32[] fusion(x)", 2.5, 0.25),
           _ev("%closed_call.3 = (s8[8,4,2]) custom-call(y), "
               'custom_call_target="tpu_custom_call"', 3.5, 0.1)]
    host = [_ev("window", 0.0, 5.0), _ev("experiment", 3.2, 1.0)]
    b = T.breakdown(_trace(dev, host))
    assert b["device_ops"] == [["fusion:fusion", 0.75],
                               ["closed_call:custom-call", 0.1]]
    assert b["idle_gaps"] == [["window", pytest.approx(1.4)],
                              ["window", pytest.approx(1.0)],
                              ["experiment", pytest.approx(0.5)]]


def test_collectives_are_found_by_name():
    dev = [_ev("%all-gather.1 = s8[32,160] all-gather(x)", 1.0, 0.2),
           _ev("%fusion.2 = f32[] fusion(x)", 1.1, 0.5)]
    tr = T.Trace(device={0: dev, 1: dev},
                 host=[_ev("window", 0.0, 2.0)])
    assert T.collective_s(tr, 0) == pytest.approx(0.2)
    assert T.collective_s(tr, 1) == pytest.approx(0.2)


@pytest.fixture(scope="module")
def small():
    return T.load(SMALL)


def test_recorded_trace_is_read(small):
    assert sorted(small.device) == [0]
    lo, hi = small.window()
    assert hi > lo > 0
    assert small.spans("experiment")
    assert all(lo <= s.start and s.end <= hi + 1e-9
               for s in small.spans("experiment"))
    assert 0 < T.mean_busy_s(small) < hi - lo


def test_recorded_trace_metrics(small):
    bench = spec.load_benchmark()
    # the trace was recorded with 8 islands of the trap40 configuration
    cfg = dict(spec.config(bench, "trap40"), islands=8)
    ctx = {"trace": small, "peaks": peaks.peaks("TPU v5 lite"),
           "shape": experiments.kernel_shape(cfg)}
    kern = T.matching(small, spec.work_count("gen_untiled").MATCH)
    # 100 generations an epoch, one vmapped kernel call per generation
    assert len(kern) % 100 == 0 and kern
    assert not T.matching(small, spec.work_count("gen_tiled").MATCH)
    assert not T.matching(small, spec.work_count("f15_eval").MATCH)
    # the readings the chip's own run of this trace reported
    assert len(kern) == 300
    read = lambda m: spec.metric_reader(m).read(ctx)  # noqa: E731
    assert read("gen_untiled_roofline") == pytest.approx(0.894156075452396)
    assert read("device_idle_share") == pytest.approx(56.66558089067678)
    assert read("driver_gap_ms") == pytest.approx(72.94017299999979)
    assert spec.metric_reader("gen_tiled_roofline").read(ctx) is None
    assert T.collective_s(small, 0) == 0.0
    b = T.breakdown(small)
    assert b["device_ops"][0][0] == "closed_call:custom-call"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10

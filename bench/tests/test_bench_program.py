"""The program's own spans in a profiler trace (``harness/program.py``):
the split of the chip's idle time inside experiments on synthetic events,
the spans of a real ``run_fused`` under the CPU profiler, and the split of
two small ``trap40.solve`` traces recorded on a v5e chip, one from before
the program had spans."""
from pathlib import Path

import pytest

from harness import program as P
from harness import spec
from harness import trace as T

DATA = Path(__file__).resolve().parent / "data"
SMALL = DATA / "trap40_solve_small.xplane.pb.gz"
# the same cell and size as SMALL (8 islands, 3 experiments), recorded on a
# v5e chip with the program's spans: bench/run.py's run_cell on trap40 with
# islands=8, seconds=0.3, trace_dir kept
SPANS = DATA / "trap40_solve_spans_small.xplane.pb.gz"


def _ev(name, start, dur):
    return T.Event(name, start, dur)


def _trace(device, host):
    return T.Trace(device={0: sorted(device, key=lambda e: e.start)},
                   host=sorted(host, key=lambda e: e.start))


def _prog(spans, dispatches=()):
    return P.Program(spans=sorted(spans, key=lambda e: e.start),
                     dispatches=list(dispatches))


# two experiments; the chip runs [1.5, 3.0) and [6.5, 8.0)
DEV = [_ev("%fusion.1 = f32[] fusion(x)", 1.5, 1.5),
       _ev("%fusion.2 = f32[] fusion(x)", 6.5, 1.5)]
HOST = [_ev("window", 0.0, 10.0),
        _ev("experiment", 0.5, 3.0), _ev("experiment", 5.5, 3.0)]
DRIVER = [_ev("driver.init", 0.6, 0.7), _ev("driver.segment", 1.3, 0.4),
         _ev("driver.wait", 1.7, 1.6),
         _ev("driver.init", 5.6, 0.6), _ev("driver.segment", 6.2, 0.2),
         _ev("driver.wait", 6.4, 1.8),
         # a checkpoint write nested in the second wait, while idle
         _ev("checkpoint.write", 8.05, 0.1)]


def test_outermost_drops_nested_launches():
    evs = [_ev("PjitFunction(add)", 1.0, 0.5),
           _ev("PjitFunction(add)", 1.1, 0.3),
           _ev("PjitFunction(iota)", 2.0, 0.1),
           _ev("PjitFunction(iota)", 2.0, 0.1)]
    assert [(e.name, e.start) for e in T.outermost(evs)] == [
        ("PjitFunction(add)", 1.0), ("PjitFunction(iota)", 2.0)]


def test_idle_split_adds_up_to_driver_gap():
    tr = _trace(DEV, HOST)
    split = P.idle_by_span(tr, _prog(DRIVER))
    # first experiment: idle [0.5, 1.5) and [3.0, 3.5); second: [5.5, 6.5)
    # and [8.0, 8.5)
    want = {"unattributed": (0.1 + 0.2 + 0.1 + 0.3) / 2,
            "driver.init": (0.7 + 0.6) / 2,
            "driver.segment": (0.2 + 0.2) / 2,
            "driver.wait": (0.3 + 0.1 + 0.05 + 0.05) / 2,
            "checkpoint.write": 0.1 / 2}
    assert split.keys() == want.keys()
    for k, v in want.items():
        assert split[k] == pytest.approx(1e3 * v), k
    gap = spec.metric_reader("driver_gap_ms").read({"trace": tr})
    assert sum(split.values()) == pytest.approx(gap)


def test_split_is_empty_without_experiments_or_device():
    assert P.idle_by_span(_trace(DEV, HOST[:1]), _prog(DRIVER)) == {}
    assert P.idle_by_span(T.Trace(device={}, host=HOST), _prog(DRIVER)) == {}
    assert P.dispatches_by_span(_trace(DEV, HOST[:1]), _prog(DRIVER)) == {}


def test_dispatches_by_span():
    launches = [_ev("PjitFunction(split)", t, 0.01)
                for t in (0.4, 0.55, 0.7, 0.9, 1.4, 3.4, 5.7, 5.8, 9.0)]
    got = P.dispatches_by_span(_trace(DEV, HOST), _prog(DRIVER, launches))
    # 0.4 and 9.0 fall outside every experiment
    assert got == {"driver.init": 2.0, "driver.segment": 0.5,
                   "unattributed": 1.0}


def test_idle_gaps_named_by_program_spans():
    gaps = P.idle_gaps(_trace(DEV, HOST), _prog(DRIVER), top=3)
    assert gaps == [("window", pytest.approx(3.5)),
                    ("window", pytest.approx(2.0)),
                    ("driver.init", pytest.approx(1.5))]
    # the harness's own naming is left as it was
    assert T.breakdown(_trace(DEV, HOST))["idle_gaps"][2][0] == "experiment"


def test_trace_without_program_spans():
    """A trace of a program without spans: everything unattributed, the
    sum still the accepted reading, and the harness's Trace unchanged."""
    tr, prog = T.load(SMALL), P.load(SMALL)
    assert prog.spans == []
    assert {h.name for h in tr.host} == {"window", "experiment"}
    split = P.idle_by_span(tr, prog)
    assert split == {"unattributed": pytest.approx(72.94017299999979)}
    assert P.dispatches_by_span(tr, prog) == {"unattributed": 93.0}
    assert [n for n, _ in P.idle_gaps(tr, prog)] == ["experiment"] * 10


def test_program_spans_reach_the_profiler(tmp_path):
    """A profiler session around a small run_fused holds the driver's
    spans on its host plane, in order, inside the enclosing annotation,
    and a tracer installed at the same time records them too."""
    import jax

    from repro.core import EAConfig, MigrationConfig, make_onemax, run_fused
    from repro.obs import trace as obs_trace

    problem = make_onemax(16)
    cfg = EAConfig(max_pop=16, min_pop=16, generations_per_epoch=2,
                   impl="jnp", max_evaluations=10**6)

    def run():
        return run_fused(problem, cfg, MigrationConfig(), n_islands=2,
                         max_epochs=3, rng=jax.random.key(1), w2=True)

    jax.block_until_ready(run())   # compiled outside the session
    tracer = obs_trace.enable()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("window"):
            with jax.profiler.TraceAnnotation("experiment"):
                jax.block_until_ready(run())
    finally:
        jax.profiler.stop_trace()
        obs_trace.disable()
    tr, prog = T.load(tmp_path), P.load(tmp_path)
    names = ["driver.init", "driver.segment", "driver.wait"]
    assert [s.name for s in prog.spans] == names
    assert [e["name"] for e in tracer.events()] == names
    (x,) = tr.spans("experiment")
    assert all(x.start <= s.start and s.end <= x.end for s in prog.spans)
    assert all(a.end <= b.start for a, b in zip(prog.spans, prog.spans[1:]))
    assert dict(prog.spans[0].stats)["n_islands"] == "2"
    assert dict(prog.spans[1].stats) == {"seg_len": "3", "epoch": "0"}
    # the eager set-up launches programs; the harness's Trace is unchanged
    assert P.dispatches_by_span(tr, prog)["driver.init"] > 0
    assert {h.name for h in tr.host} == {"window", "experiment"}


def test_recorded_trace_with_program_spans():
    """The split on a chip's trace: one init, segment and wait per
    experiment, the parts adding up to driver_gap_ms, and the untiled
    kernel under its name reading the roofline share it read unnamed."""
    from harness import experiments, peaks

    tr, prog = T.load(SPANS), P.load(SPANS)
    exps = tr.spans("experiment")
    assert len(exps) == 3
    for x in exps:
        assert [s.name for s in prog.spans if x.start <= s.start < x.end] \
            == ["driver.init", "driver.segment", "driver.wait"]
    assert {h.name for h in tr.host} == {"window", "experiment"}
    bench = spec.load_benchmark()
    cfg = dict(spec.config(bench, "trap40"), islands=8)
    ctx = {"trace": tr, "peaks": peaks.peaks("TPU v5 lite"),
           "shape": experiments.kernel_shape(cfg)}
    read = lambda m: spec.metric_reader(m).read(ctx)  # noqa: E731
    # the readings the chip's own run of this trace reported
    assert read("driver_gap_ms") == pytest.approx(76.5471699999999)
    assert read("device_idle_share") == pytest.approx(57.8073691131765)
    assert read("gen_untiled_roofline") == pytest.approx(0.8941654883076036)
    split = P.idle_by_span(tr, prog)
    assert split == {"driver.init": pytest.approx(73.227696),
                     "driver.segment": pytest.approx(0.011003),
                     "driver.wait": pytest.approx(2.1234193333),
                     "unattributed": pytest.approx(1.1850516667)}
    assert sum(split.values()) == pytest.approx(read("driver_gap_ms"))
    assert P.dispatches_by_span(tr, prog) == {"driver.init": 91.0,
                                              "driver.segment": 2.0}
    assert T.breakdown(tr)["device_ops"][0][0] == "gen_untiled:custom-call"
    assert {n for n, _ in P.idle_gaps(tr, prog)} == {"driver.init",
                                                     "driver.wait"}

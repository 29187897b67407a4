"""Traffic entries, drivers and the program's spans are found by name, from
files of their own: a later change adds a cell with a new entry or driver
by adding files alone, a missing one is reported, the one parse of a trace
keeps the program's spans without changing what the harness read before,
and the driver's split reads as ``harness.program`` reads it."""
import copy
import json
import re
import shutil
import time
from pathlib import Path

import jax
import pytest

import run as bench_run
from harness import experiments, peaks, spec
from harness import program as P
from harness import trace as T

DATA = Path(__file__).resolve().parent / "data"
FIXTURES = ("trap40_solve_small", "trap40_solve_spans_small")
# the harness's readings of the two fixtures before its trace kept the
# program's spans: host spans [name, start, dur], the breakdown, readers
BEFORE = json.loads((DATA / "fixture_readings.json").read_text())
READERS = ("device_idle_share", "driver_gap_ms", "gen_untiled_roofline",
           "gen_tiled_roofline", "f15_eval_roofline")

# a driver that is not in the benchmark: the asynchronous fused driver in
# its degenerate configuration
ASYNC_DRIVER = '''
def make(problem, ea, mig, cfg, epochs, devices):
    from repro.core import AsyncConfig, run_fused_async

    assert len(devices) == 1
    return lambda key: run_fused_async(problem, ea, mig, AsyncConfig(),
                                       n_islands=cfg["islands"],
                                       max_ticks=epochs, rng=key)
'''

# an entry that is not in the benchmark: a fixed number of experiments per
# window through the mix's driver, checked against the problem's reference
ENTRY = '''
import time
from pathlib import Path

import numpy as np

from . import experiments, spec

BENCH_DIR = Path(__file__).resolve().parents[1]


def make(cfg, mix, seed, devices):
    return Fixed(cfg, mix, seed, devices)


class Fixed:
    def __init__(self, cfg, mix, seed, devices):
        import jax

        p = cfg["problem"]
        self.prob = spec.problem(p["kind"], BENCH_DIR)
        self.problem, _ = self.prob.build(p)
        ea, mig = experiments.build_ea(cfg, mix["impl"])
        self.drive = spec.driver(mix["driver"], BENCH_DIR).make(
            self.problem, ea, mig, cfg, experiments.max_epochs(cfg), devices)
        self.cfg, self.mix, self.key = cfg, mix, jax.random.key(seed)
        self.done, self.gap = 0, None

    def warm_up(self):
        self.drive(self.key)

    def window(self, seconds, annotate, traced=False):
        t0 = time.perf_counter()
        with annotate("window"):
            for _ in range(self.mix["experiments"]):
                isl, _, _ = self.drive(self.key)
                self.done += 1
        n = int(isl.pop_size[0])
        pop, fit = np.asarray(isl.pop[0, :n]), np.asarray(isl.fitness[0, :n])
        ref = self.prob.reference(self.cfg["problem"], None, pop)
        self.gap = float(np.max(np.abs(fit - ref)))
        return time.perf_counter() - t0

    def metrics(self, window_s):
        return {"evals_per_s": self.done / window_s}

    def counts(self):
        return {"attempted": self.done, "failed": 0}

    def verify(self, control=False):
        return {"fit_gap": self.gap}

    def shape(self):
        return {}

    def notes(self):
        return {}

    def close(self):
        pass
'''

ONEMAX = '''
def build(p):
    from repro.core import make_onemax

    return make_onemax(p["length"]), None


def reference(p, consts, pop):
    return pop.sum(-1)
'''


def _checkout(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(spec.BENCH_DIR, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(spec.ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    return root


def _small_trap(bench):
    cfg = copy.deepcopy(spec.config(bench, "trap40"))
    cfg["problem"]["n_traps"], cfg["genome"]["length"] = 8, 32
    cfg["population"], cfg["islands"] = 256, 2
    cfg["ea"]["generations_per_epoch"] = 50
    cfg["budget_evals"] = 256 * 50 * 4
    return cfg


@pytest.mark.parametrize("added", ["driver", "entry_and_driver"])
def test_entry_and_driver_added_as_files(tmp_path, added):
    """A new driver under the ``experiments`` entry, or a new entry with a
    new driver and a problem of its own, each added as files alone, runs a
    cell to its result line on the CPU."""
    root = _checkout(tmp_path)
    bench_dir = root / "bench"
    (bench_dir / "drivers/run_fused_async.py").write_text(ASYNC_DRIVER)
    bench = spec.load_benchmark(root)
    cfg = _small_trap(bench)
    mix = {"entry": "experiments", "driver": "run_fused_async",
           "impl": "pallas_ref", "until": "solution"}
    if added == "entry_and_driver":
        (bench_dir / "harness/fixed.py").write_text(ENTRY)
        (bench_dir / "problems/onemax.py").write_text(ONEMAX)
        cfg["problem"] = {"kind": "onemax", "length": 32}
        cfg["limits"] = {"fit_gap": 0.0}
        mix.update(entry="fixed", experiments=2)
    cfg["name"] = "small"
    (bench_dir / "configs/small.json").write_text(json.dumps(cfg))
    (bench_dir / "traffic/added.json").write_text(json.dumps(mix))
    bench["configs"].append({"name": "small", "source": "x", "reduced": [],
                             "file": "bench/configs/small.json", "why": "x"})
    cell = {"name": "small.added", "config": "small", "traffic": "added",
            "chips": 1, "why": "x"}
    bench["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    assert spec.check_consistent(bench, root, bench_dir) == []

    res = bench_run.run_cell(
        bench, cell, spec.config(bench, "small", root),
        spec.traffic("added", bench_dir), seed=2**33 + 11, seconds=0.3,
        trace=False, t_start=time.perf_counter(),
        devs_used=jax.devices()[:1], bench_dir=bench_dir)
    assert res["correct"], res["check"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"evals_per_s", "setup_s"}
    assert list(res)[-1] == "check"


@pytest.mark.parametrize("fault", ["entry", "driver", "no_make"])
def test_missing_entry_or_driver_is_reported(tmp_path, fault):
    root = _checkout(tmp_path)
    bench_dir = root / "bench"
    bench = spec.load_benchmark(root)
    mix = spec.traffic("solve", bench_dir)
    if fault == "entry":
        mix["entry"] = "no_such_entry"
        missing = bench_dir / "harness/no_such_entry.py"
    elif fault == "driver":
        mix["driver"] = "no_such_driver"
        missing = bench_dir / "drivers/no_such_driver.py"
    else:
        # a harness module that is not an entry
        mix["entry"] = "trace"
        missing = bench_dir / "harness/trace.py"
    (bench_dir / "traffic/solve.json").write_text(json.dumps(mix))
    with pytest.raises(spec.SpecError, match=re.escape(str(missing))):
        if fault == "driver":
            spec.driver(mix["driver"], bench_dir)
        else:
            spec.entry(mix["entry"], bench_dir)
    errs = spec.check_consistent(bench, root, bench_dir)
    assert errs and all(e.startswith("trap40.solve: ") for e in errs), errs
    assert str(missing) in errs[0]


@pytest.mark.parametrize("fixture", FIXTURES)
def test_fixtures_read_as_before(fixture):
    tr = T.load(DATA / f"{fixture}.xplane.pb.gz")
    before = BEFORE[fixture]
    assert [[e.name, e.start, e.dur] for e in tr.host] == before["host"]
    assert json.loads(json.dumps(T.breakdown(tr))) == before["breakdown"]
    bench = spec.load_benchmark()
    # both fixtures were recorded with 8 islands of the trap40 configuration
    cfg = dict(spec.config(bench, "trap40"), islands=8)
    ctx = {"trace": tr, "peaks": peaks.peaks("TPU v5 lite"),
           "shape": experiments.kernel_shape(cfg)}
    assert {m: spec.metric_reader(m).read(ctx) for m in READERS} \
        == before["readers"]


def test_driver_split_readers():
    """The three driver readers read the split ``harness.program`` prints,
    and with the idle under ``driver.segment`` and ``driver.wait`` they add
    up to ``driver_gap_ms``."""
    path = DATA / "trap40_solve_spans_small.xplane.pb.gz"
    summary = P.summary(path)
    idle = summary["idle_ms_per_experiment"]
    ctx = {"trace": T.load(path)}
    read = lambda m: spec.metric_reader(m).read(ctx)  # noqa: E731
    assert read("driver_init_ms") == idle["driver.init"]
    assert read("driver_unattributed_ms") == idle["unattributed"]
    assert read("driver_init_dispatches") \
        == summary["dispatches_per_experiment"]["driver.init"] == 1.0 * 91
    parts = (read("driver_init_ms") + idle["driver.segment"]
             + idle["driver.wait"] + read("driver_unattributed_ms"))
    assert abs(parts - read("driver_gap_ms")) < 1e-9


def test_driver_readers_without_program_spans():
    """A trace of a program that wrote no span has nothing to split: the
    readers leave their metrics out. So does a span that never occurs
    inside an experiment, renamed or not recorded; one that is there with
    the chip busy all through it reads 0."""
    tr = T.load(DATA / "trap40_solve_small.xplane.pb.gz")
    for m in ("driver_init_ms", "driver_unattributed_ms",
              "driver_init_dispatches"):
        assert spec.metric_reader(m).read({"trace": tr}) is None
    spans = T.load(DATA / "trap40_solve_spans_small.xplane.pb.gz")
    ctx = {"trace": spans}
    assert P.reading(ctx, P.idle_by_span, "checkpoint.write") is None
    assert P.reading(ctx, P.dispatches_by_span, "checkpoint.write") is None
    # worked out once and kept for the other parts
    assert P.reading(ctx, P.idle_by_span, "driver.init") is not None
    assert ctx["program.idle_by_span"] == P.idle_by_span(spans,
                                                         P.from_trace(spans))
    assert ctx["program.names"] == {"driver.init", "driver.segment",
                                    "driver.wait"}
    no_device = T.Trace(device={}, host=spans.host, program=spans.program)
    assert P.reading({"trace": no_device}, P.idle_by_span,
                     "driver.init") is None
    # a span inside an experiment while the chip runs [1.5, 3.0)
    busy = T.Trace(device={0: [T.Event("%f.1 = f32[] fusion(x)", 1.5, 1.5)]},
                   host=[T.Event("window", 0.0, 4.0),
                         T.Event("experiment", 0.5, 3.0)],
                   program=[T.Event("driver.segment", 2.0, 0.5)])
    assert P.reading({"trace": busy}, P.idle_by_span,
                     "driver.segment") == 0.0
    assert P.reading({"trace": busy}, P.idle_by_span,
                     "driver.init") is None


@pytest.mark.parametrize("fixture", FIXTURES)
def test_gaps_worked_out_once_match_each_experiment(fixture):
    """``driver_gap_ms`` and the split cut chip 0's gaps, worked out once,
    to each experiment: exactly the gaps :func:`trace.gaps` gives for each
    experiment alone."""
    tr = T.load(DATA / f"{fixture}.xplane.pb.gz")
    exps = tr.spans("experiment")
    chip = min(tr.device)
    assert T.gaps_within(tr, chip, exps) \
        == [T.gaps(tr, chip, x.start, x.end) for x in exps]
    assert T.gaps_within(tr, chip, []) == []


def test_one_parse_keeps_the_program_spans():
    """``trace.load`` keeps the program's spans and JAX's outermost
    launches in the same parse; ``program.load`` is built from it."""
    path = DATA / "trap40_solve_spans_small.xplane.pb.gz"
    tr, prog = T.load(path), P.load(path)
    assert prog.spans == tr.program and prog.dispatches == tr.dispatches
    assert {s.name for s in tr.program} == {"driver.init", "driver.segment",
                                           "driver.wait"}
    assert all(d.name.startswith(T.DISPATCH) for d in tr.dispatches)
    assert tr.dispatches == T.outermost(tr.dispatches)
    assert {h.name for h in tr.host} == set(T.HOST_SPANS)

"""BENCHMARK.json is whole, and the harness finds what a later change adds
as new files alone: a configuration, its problem, a traffic mix and a
metric reader."""
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

from harness import spec, tiles

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


def test_benchmark_is_consistent(bench):
    assert spec.check_consistent(bench) == []


def test_names_units_and_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for e in bench["configs"] + bench["workloads"]:
        assert NAME.match(e["name"]), e["name"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    setup = [m for m in bench["end_to_end"] if m["name"] == "setup_s"]
    assert setup and setup[0]["bound"] <= 0.25


def test_every_cell_reports_enough(bench):
    for w in bench["workloads"]:
        e2e = {m["name"] for m in spec.cell_metrics(bench, w["name"],
                                                    "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        per = spec.cell_metrics(bench, w["name"], "per_layer")
        assert per, w["name"]
        for m in per:
            assert m["moves"] in e2e, (w["name"], m["name"])


def test_metrics_of_one_layer_agree_on_its_name(bench):
    for m in bench["per_layer"]:
        # without a list a metric is read in every cell, later ones too
        assert m.get("workloads", ["every cell"]), m["name"]
        assert m["layer"] and "\n" not in m["layer"]
    # no two spellings of one layer
    layers = {m["layer"] for m in bench["per_layer"]}
    assert len({" ".join(x.lower().split()) for x in layers}) == len(layers)


def test_new_files_are_found(tmp_path, bench):
    """A later change adds a config with a problem of its own, a mix and a
    metric as files plus entries; the harness finds each by its name."""
    root = tmp_path / "checkout"
    shutil.copytree(spec.BENCH_DIR, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    new = json.loads(json.dumps(bench))
    cfg = json.loads((root / "bench/configs/trap40.json").read_text())
    cfg["name"] = "trap80"
    cfg["problem"] = {"kind": "onemax", "length": 80}
    (root / "bench/configs/trap80.json").write_text(json.dumps(cfg))
    (root / "bench/problems/onemax.py").write_text(
        "def reference(p, consts, pop):\n    return pop.sum(-1)\n")
    (root / "bench/traffic/burst.json").write_text(json.dumps(
        {"entry": "experiments", "driver": "run_fused", "impl": "pallas",
         "until": "budget"}))
    (root / "bench/metrics/new_share.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    new["configs"].append({"name": "trap80", "source": "x",
                           "file": "bench/configs/trap80.json",
                           "reduced": [], "why": "x"})
    new["workloads"].append({"name": "trap80.burst", "config": "trap80",
                             "traffic": "burst", "chips": 1, "why": "x"})
    new["per_layer"].append({"name": "new_share", "unit": "%",
                             "better": "higher", "source": "device_trace",
                             "layer": "device", "moves": "evals_per_s",
                             "workloads": ["trap80.burst"]})
    (root / "BENCHMARK.json").write_text(json.dumps(new))

    loaded = spec.load_benchmark(root)
    bench_dir = root / "bench"
    assert spec.check_consistent(loaded, root, bench_dir) == []
    assert spec.config(loaded, "trap80", root)["problem"]["length"] == 80
    assert spec.problem("onemax", bench_dir).reference(
        {}, None, np.ones((2, 80))).tolist() == [80.0, 80.0]
    assert spec.traffic("burst", bench_dir)["entry"] == "experiments"
    assert spec.metric_reader("new_share", bench_dir).read({}) == 42.0
    names = [m["name"] for m in spec.cell_metrics(loaded, "trap80.burst",
                                                  "per_layer")]
    assert "new_share" in names
    assert "new_share" not in [m["name"] for m in spec.cell_metrics(
        loaded, "trap40.solve", "per_layer")]


def test_missing_problem_is_reported(tmp_path, bench):
    root = tmp_path / "checkout"
    shutil.copytree(spec.BENCH_DIR, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "bench/problems/f15.py").unlink()
    shutil.copy(spec.ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    errs = spec.check_consistent(bench, root, root / "bench")
    assert any("no problem 'f15'" in e for e in errs), errs


def test_tile_pin(tmp_path, monkeypatch, bench):
    """The pinned tile is written under the key the program looks up, the
    program reads it back, and a sweep of its own is caught."""
    from repro.kernels.ga import autotune

    cfg = spec.config(bench, "f15_d1000")
    path = tmp_path / "autotune_ga.json"
    # restored when the test ends: pin() points the program at ``path``
    monkeypatch.setenv("REPRO_GA_AUTOTUNE_CACHE", str(path))
    monkeypatch.setattr(autotune, "device_kind", lambda: "TPU v5 lite")
    tiles.pin(cfg, "TPU v5 lite", path)
    assert autotune.best_tiles(10000, 1000, "float") == (512, 256)
    tiles.check(cfg, "TPU v5 lite", path)
    autotune.best_tiles(64, 128, "float")   # a shape of its own: a sweep
    with pytest.raises(RuntimeError, match="pin not taken"):
        tiles.check(cfg, "TPU v5 lite", path)
    trap = spec.config(bench, "trap40")
    assert tiles.entries(trap, "TPU v5 lite") == {}


def test_inconsistent_moves_is_reported(bench):
    bad = json.loads(json.dumps(bench))
    bad["per_layer"][0]["moves"] = "no_such_metric"
    bad["per_layer"][1]["workloads"] = ["no.such.cell"]
    errs = spec.check_consistent(bad)
    assert any("no_such_metric" in e for e in errs)
    assert any("no.such.cell" in e for e in errs)


def test_refuses_without_the_program(tmp_path):
    """In a directory that holds only BENCHMARK.json and bench/, the run
    prints no result and exits non-zero."""
    shutil.copytree(spec.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "trap40.solve",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_refuses_without_a_chip():
    """On the CPU the run prints no result and exits 3."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(spec.BENCH_DIR / "run.py"), "--workload",
         "trap40.solve", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, env=env)
    assert p.returncode == 3, p.stderr[-2000:]
    assert p.stdout.strip() == ""

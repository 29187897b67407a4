"""Find what BENCHMARK.json names: cells, configurations, traffic mixes,
the entries and drivers the mixes name, problems, metric readers and
kernel work counts, each by its name in a file of its own.

Everything that belongs to one configuration, traffic mix, entry, driver,
per-layer metric or kernel sits in its own file, so a later change adds a
cell or a metric by adding files and entries, never by editing one that is
there:

* ``configs/<config>.json``   the deployment as it is run
* ``traffic/<mix>.json``      the mix: the ``entry`` it drives (and, for
                              the ``experiments`` entry, the ``driver``),
                              with what
* ``harness/<entry>.py``      ``make(cfg, mix, seed, devices)``: one run's
                              set-up, window and answers on the chips the
                              cell asks for; the object has ``warm_up()``,
                              ``window(seconds, annotate, traced)`` (the
                              window's seconds), ``metrics(window_s)``,
                              ``counts()`` (``attempted``, ``failed``),
                              ``verify(control)`` (the numbers compared),
                              ``answers()`` and ``numbers(answers,
                              control)`` (the same in two steps, for
                              ``control.py``), ``shape()`` (what the work
                              counts need), ``notes()`` and ``close()``
* ``drivers/<driver>.py``     ``make(problem, ea, mig, cfg, epochs,
                              devices)``: a function from an experiment's
                              key to its ``(islands, pool, epochs)``
* ``problems/<kind>.py``      a configuration's problem: ``build(p)`` (the
                              program's problem and the reference's
                              constants), ``reference``, ``reference_bf16``
                              (the control) and ``random_population``
* ``metrics/<metric>.py``     ``read(ctx) -> float | None``; ``ctx`` holds
                              the ``trace`` (:mod:`.trace`: device ops,
                              harness and program spans, launches), the
                              entry's ``shape``, the chip's ``peaks`` and
                              the ``entry``
* ``work/<kernel>.py``        ``MATCH`` (its events in the device trace)
                              and ``work(shape) -> {"ops", "bytes"}``
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


class SpecError(Exception):
    """BENCHMARK.json or a file it names is missing or inconsistent."""


def load_benchmark(root: Path = ROOT) -> Dict[str, Any]:
    path = Path(root) / "BENCHMARK.json"
    if not path.is_file():
        raise SpecError(f"no BENCHMARK.json at {root}")
    return json.loads(path.read_text())


def _by_name(entries: List[Dict[str, Any]], name: str, what: str):
    for e in entries:
        if e["name"] == name:
            return e
    raise SpecError(f"no {what} named {name!r} in BENCHMARK.json")


def workload(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    return _by_name(bench["workloads"], name, "workload")


def config(bench: Dict[str, Any], name: str,
           root: Path = ROOT) -> Dict[str, Any]:
    entry = _by_name(bench["configs"], name, "config")
    path = Path(root) / entry["file"]
    if not path.is_file():
        raise SpecError(f"config file {entry['file']} is missing")
    return json.loads(path.read_text())


def traffic(name: str, bench_dir: Path = BENCH_DIR) -> Dict[str, Any]:
    path = Path(bench_dir) / "traffic" / f"{name}.json"
    if not path.is_file():
        raise SpecError(f"no traffic mix {name!r} ({path})")
    return json.loads(path.read_text())


def _module(path: Path, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _maker(path: Path, modname: str, what: str, name: str) -> ModuleType:
    """The module at ``path``, which has to define ``make``."""
    if not path.is_file():
        raise SpecError(f"no {what} {name!r} ({path})")
    mod = _module(path, modname)
    if not callable(getattr(mod, "make", None)):
        raise SpecError(f"{what} {name!r} ({path}) defines no make()")
    return mod


def entry(name: str, bench_dir: Path = BENCH_DIR) -> ModuleType:
    """The traffic entry ``harness/<name>.py``: ``make(cfg, mix, seed,
    devices)`` gives one run's entry object. Loaded as a module of the
    harness package, so that it imports its siblings relatively."""
    return _maker(Path(bench_dir) / "harness" / f"{name}.py",
                  f"harness.{name}", "traffic entry", name)


def driver(name: str, bench_dir: Path = BENCH_DIR) -> ModuleType:
    """The experiment driver ``drivers/<name>.py``: ``make(problem, ea,
    mig, cfg, epochs, devices)`` gives a function from a key to
    ``(islands, pool, epochs)``."""
    return _maker(Path(bench_dir) / "drivers" / f"{name}.py",
                  "bench_driver_" + name.replace(".", "_"), "driver", name)


def problem(kind: str, bench_dir: Path = BENCH_DIR) -> ModuleType:
    path = Path(bench_dir) / "problems" / f"{kind}.py"
    if not path.is_file():
        raise SpecError(f"no problem {kind!r} ({path})")
    return _module(path, "bench_problem_" + kind.replace(".", "_"))


def metric_reader(name: str, bench_dir: Path = BENCH_DIR) -> ModuleType:
    path = Path(bench_dir) / "metrics" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no reader for metric {name!r} ({path})")
    return _module(path, "bench_metric_" + name.replace(".", "_"))


def work_count(kernel: str, bench_dir: Path = BENCH_DIR) -> ModuleType:
    path = Path(bench_dir) / "work" / f"{kernel}.py"
    if not path.is_file():
        raise SpecError(f"no work count for kernel {kernel!r} ({path})")
    return _module(path, "bench_work_" + kernel.replace(".", "_"))


def cell_metrics(bench: Dict[str, Any], cell: str,
                 kind: str) -> List[Dict[str, Any]]:
    """The ``end_to_end`` or ``per_layer`` metrics a cell reports: those
    without a ``workloads`` key, and those that list the cell."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def check_consistent(bench: Dict[str, Any], root: Path = ROOT,
                     bench_dir: Path = BENCH_DIR) -> List[str]:
    """Every problem with the files and names BENCHMARK.json refers to; an
    empty list when the benchmark is whole."""
    errs: List[str] = []
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        try:
            cfg = config(bench, w["config"], root)
            mix = traffic(w["traffic"], bench_dir)
            entry(mix["entry"], bench_dir)
            if "driver" in mix:
                driver(mix["driver"], bench_dir)
            problem(cfg["problem"]["kind"], bench_dir)
        except SpecError as e:
            errs.append(f"{w['name']}: {e}")
    for m in bench["end_to_end"] + bench["per_layer"]:
        for c in m.get("workloads", []):
            if c not in cells:
                errs.append(f"{m['name']}: lists unknown cell {c!r}")
    for m in bench["per_layer"]:
        moved = e2e.get(m["moves"])
        if moved is None:
            errs.append(f"{m['name']}: moves unknown metric {m['moves']!r}")
            continue
        for c in m.get("workloads", sorted(cells)):
            if c not in cells:
                continue
            if m["moves"] not in {x["name"] for x in
                                  cell_metrics(bench, c, "end_to_end")}:
                errs.append(f"{m['name']}: cell {c} does not report "
                            f"{m['moves']}")
        if not (Path(bench_dir) / "metrics" / f"{m['name']}.py").is_file():
            errs.append(f"{m['name']}: no reader metrics/{m['name']}.py")
    return errs


"""The program's own host spans in a profiler trace, and the chip's idle
time inside the harness's experiments split by them.

The program names its host work with dotted ``component.verb`` spans
(``repro.obs.trace``): ``driver.init`` builds an experiment's state,
``driver.segment`` enqueues a jitted scan, ``driver.wait`` blocks on its
result; ``bridge.*``, ``checkpoint.*``, ``pool.*`` and ``server.*`` do the
rest. Each span enters a ``jax.profiler.TraceAnnotation``, so it sits on
the trace's host plane beside the harness's own spans, on the clock of the
device events. JAX marks each eager program it launches with a
``PjitFunction(<name>)`` host event.

This module reads both from the ``.xplane.pb`` that :mod:`.trace` reads;
:mod:`.trace` and its ``Trace`` are left as they are.

    cd bench && python3 -m harness.program <trace dir or .xplane.pb[.gz]>

prints the split of one trace as JSON (``bench/run.py --trace 1
--trace-dir <dir>`` keeps a run's trace).
"""
from __future__ import annotations

import dataclasses
import gzip
import json
import sys
from pathlib import Path
from typing import Dict, List, Tuple

from . import trace as trace_lib

Event = trace_lib.Event
# the program's span names start with one of these (repro.obs.trace)
PREFIXES = ("driver.", "bridge.", "checkpoint.", "pool.", "server.")
# JAX's host event for each program it launches
DISPATCH = "PjitFunction("
UNATTRIBUTED = "unattributed"


@dataclasses.dataclass
class Program:
    spans: List[Event]        # the program's spans, sorted by start
    dispatches: List[Event]   # outermost PjitFunction events, by start


def _outermost(events: List[Event]) -> List[Event]:
    """Events of one thread not nested in an earlier one (JAX writes each
    launch as a PjitFunction event inside another of the same name)."""
    out: List[Event] = []
    for e in sorted(events, key=lambda e: e.start):
        if not out or e.start >= out[-1].end:
            out.append(e)
    return out


def load(path) -> Program:
    """The program's spans and JAX's launches from one ``.xplane.pb``,
    gzipped or not, or the profiler's log directory."""
    from jax.profiler import ProfileData

    path = Path(path)
    if path.is_dir():
        path = trace_lib.find_xplane(path)
    if path.suffix == ".gz":
        data = ProfileData.from_serialized_xspace(
            gzip.decompress(path.read_bytes()))
    else:
        data = ProfileData.from_file(str(path))
    spans: List[Event] = []
    dispatches: List[Event] = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            launched = []
            for e in line.events:
                name = e.name
                if name.startswith(PREFIXES):
                    spans.append(Event(name, e.start_ns * 1e-9,
                                       e.duration_ns * 1e-9,
                                       trace_lib._stats(e)))
                elif name.startswith(DISPATCH):
                    launched.append(Event(name, e.start_ns * 1e-9,
                                          e.duration_ns * 1e-9))
            dispatches += _outermost(launched)
    spans.sort(key=lambda e: e.start)
    dispatches.sort(key=lambda e: e.start)
    return Program(spans=spans, dispatches=dispatches)


def _innermost(spans: List[Event], t: float, default: str) -> str:
    inner = [s for s in spans if s.start <= t < s.end]
    return min(inner, key=lambda s: s.dur).name if inner else default


def idle_by_span(tr: trace_lib.Trace, prog: Program) -> Dict[str, float]:
    """Chip-0 idle time inside the harness's ``experiment`` spans, in ms
    per experiment, by the innermost program span that covers it; the
    time no program span covers is ``unattributed``. The parts add up to
    the ``driver_gap_ms`` reading of the same trace. Empty when the trace
    has no experiments or no device."""
    exps = tr.spans("experiment")
    if not exps or not tr.device:
        return {}
    chip = min(tr.device)
    out: Dict[str, float] = {}
    for x in exps:
        inside = [s for s in prog.spans
                  if s.start < x.end and s.end > x.start]
        for a, b in trace_lib.gaps(tr, chip, x.start, x.end):
            cuts = sorted({a, b} | {t for s in inside
                                    for t in (s.start, s.end) if a < t < b})
            for p, q in zip(cuts, cuts[1:]):
                name = _innermost(inside, 0.5 * (p + q), UNATTRIBUTED)
                out[name] = out.get(name, 0.0) + (q - p)
    return {k: 1e3 * v / len(exps) for k, v in sorted(out.items())}


def dispatches_by_span(tr: trace_lib.Trace,
                       prog: Program) -> Dict[str, float]:
    """JAX launches that start inside the harness's ``experiment`` spans,
    per experiment, by the innermost program span they start in; those in
    none are ``unattributed``. Empty when the trace has no experiments."""
    exps = tr.spans("experiment")
    out: Dict[str, float] = {}
    for x in exps:
        inside = [s for s in prog.spans
                  if s.start < x.end and s.end > x.start]
        for d in prog.dispatches:
            if x.start <= d.start < x.end:
                name = _innermost(inside, d.start, UNATTRIBUTED)
                out[name] = out.get(name, 0.0) + 1
    return {k: v / len(exps) for k, v in sorted(out.items())}


def idle_gaps(tr: trace_lib.Trace, prog: Program,
              top: int = 10) -> List[Tuple[str, float]]:
    """The longest idle gaps of the first chip inside the window, each
    named by the innermost harness or program span that covers its middle
    (:func:`.trace.breakdown` names them by the harness's spans alone)."""
    lo, hi = tr.window()
    chip = min(tr.device) if tr.device else 0
    spans = tr.host + prog.spans
    named = [(_innermost(spans, 0.5 * (s + e), "none"), e - s)
             for s, e in trace_lib.gaps(tr, chip, lo, hi)]
    named.sort(key=lambda x: -x[1])
    return named[:top]


def summary(path) -> Dict[str, object]:
    tr = trace_lib.load(path)
    prog = load(path)
    return {"experiments": len(tr.spans("experiment")),
            "idle_ms_per_experiment": idle_by_span(tr, prog),
            "dispatches_per_experiment": dispatches_by_span(tr, prog),
            "idle_gaps": idle_gaps(tr, prog)}


if __name__ == "__main__":
    print(json.dumps(summary(sys.argv[1]), indent=1))

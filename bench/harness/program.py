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

:func:`.trace.load` keeps both in its one parse of the ``.xplane.pb``:
``Trace.program`` (names starting with ``trace.PREFIXES``) and
``Trace.dispatches`` (the outermost ``PjitFunction`` events).
:func:`from_trace` hands them to the split here; the ``driver_*`` metric
readers take their part of it through :func:`reading`.

    cd bench && python3 -m harness.program <trace dir or .xplane.pb[.gz]>

prints the split of one trace as JSON (``bench/run.py --trace 1
--trace-dir <dir>`` keeps a run's trace).
"""
from __future__ import annotations

import dataclasses
import json
import sys
from typing import Dict, List, Optional, Set, Tuple

from . import trace as trace_lib

Event = trace_lib.Event
UNATTRIBUTED = "unattributed"


@dataclasses.dataclass
class Program:
    spans: List[Event]        # the program's spans, sorted by start
    dispatches: List[Event]   # outermost PjitFunction events, by start


def from_trace(tr: trace_lib.Trace) -> Program:
    return Program(spans=tr.program, dispatches=tr.dispatches)


def load(path) -> Program:
    """The program's spans and JAX's launches from one ``.xplane.pb``,
    gzipped or not, or the profiler's log directory."""
    return from_trace(trace_lib.load(path))


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
    out: Dict[str, float] = {}
    for x, idle in zip(exps, trace_lib.gaps_within(tr, min(tr.device),
                                                   exps)):
        inside = [s for s in prog.spans
                  if s.start < x.end and s.end > x.start]
        for a, b in idle:
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


def in_experiments(tr: trace_lib.Trace) -> Set[str]:
    """Names of the program's spans that overlap an ``experiment`` span."""
    exps = tr.spans("experiment")
    return {s.name for s in tr.program
            if any(s.start < x.end and s.end > x.start for x in exps)}


def reading(ctx, split, name: str) -> Optional[float]:
    """Part ``name`` (a span, or ``unattributed``) of ``split``
    (:func:`idle_by_span` or :func:`dispatches_by_span`) of the trace in a
    metric reader's ``ctx``, as the reader reports it. None when there is
    nothing to read: no experiment, no device, no program span inside an
    experiment, or none named ``name`` (so a span renamed or not recorded
    leaves its metric out rather than reading 0). A span that is there
    with no idle time or launch under it reads 0. Each split, and the
    names of the spans inside experiments, are worked out once per
    ``ctx`` and kept there for the other parts."""
    tr = ctx["trace"]
    if not tr.spans("experiment") or not tr.device:
        return None
    if "program.names" not in ctx:
        ctx["program.names"] = in_experiments(tr)
    names = ctx["program.names"]
    if not names or (name != UNATTRIBUTED and name not in names):
        return None
    key = "program." + split.__name__
    if key not in ctx:
        ctx[key] = split(tr, from_trace(tr))
    return ctx[key].get(name, 0.0)


def summary(path) -> Dict[str, object]:
    tr = trace_lib.load(path)
    prog = from_trace(tr)
    return {"experiments": len(tr.spans("experiment")),
            "idle_ms_per_experiment": idle_by_span(tr, prog),
            "dispatches_per_experiment": dispatches_by_span(tr, prog),
            "idle_gaps": idle_gaps(tr, prog)}


if __name__ == "__main__":
    print(json.dumps(summary(sys.argv[1]), indent=1))

"""Reduce a JAX profiler trace (``.xplane.pb``) to what the metric readers
need: device operations per chip, the harness's own host spans, the
program's spans and JAX's launches, busy and idle time, kernel and
collective time, and the breakdown that goes into the result line.

The file is read once, with ``jax.profiler.ProfileData`` alone. Device
planes are named ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per executed HLO operation, a Pallas kernel among them. On the host
planes, on the same clock, sit the ``jax.profiler.TraceAnnotation``
events the harness writes (``HOST_SPANS``), the program's own dotted
``component.verb`` spans (``PREFIXES``, ``repro.obs.trace``) and the
``PjitFunction(<name>)`` event JAX writes for each program it launches,
so a device gap can be laid against what the host was doing.
:mod:`.program` splits idle time by the program's spans.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
# the harness's own spans: names it writes with TraceAnnotation
HOST_SPANS = ("window", "experiment")
# the program's span names start with one of these (repro.obs.trace)
PREFIXES = ("driver.", "bridge.", "checkpoint.", "pool.", "server.")
# JAX's host event for each program it launches
DISPATCH = "PjitFunction("
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|collective-permute|all-to-all|reduce-scatter"
    r"|allgather|allreduce|collective", re.I)


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float          # seconds, on the trace's common clock
    dur: float            # seconds
    stats: Tuple[Tuple[str, str], ...] = ()

    @property
    def end(self) -> float:
        return self.start + self.dur

    def text(self) -> str:
        """Name and every stat value: what a kernel matcher searches."""
        return " ".join([self.name] + [v for _, v in self.stats])


@dataclasses.dataclass
class Trace:
    device: Dict[int, List[Event]]   # chip -> ops sorted by start
    host: List[Event]                # harness spans sorted by start
    # the program's spans, sorted by start
    program: List[Event] = dataclasses.field(default_factory=list)
    # outermost PjitFunction events of each host thread, sorted by start
    dispatches: List[Event] = dataclasses.field(default_factory=list)

    def spans(self, name: str) -> List[Event]:
        return [e for e in self.host if e.name == name]

    def window(self) -> Tuple[float, float]:
        w = self.spans("window")
        if not w:
            raise ValueError("trace holds no 'window' span")
        return w[0].start, w[0].end


def _stats(ev) -> Tuple[Tuple[str, str], ...]:
    try:
        return tuple((str(k), str(v)) for k, v in ev.stats)
    except (TypeError, ValueError):
        return ()


def _event(e) -> Event:
    return Event(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9, _stats(e))


def outermost(events: List[Event]) -> List[Event]:
    """Events of one thread not nested in an earlier one (JAX writes each
    launch as a PjitFunction event inside another of the same name)."""
    out: List[Event] = []
    for e in sorted(events, key=lambda e: e.start):
        if not out or e.start >= out[-1].end:
            out.append(e)
    return out


def find_xplane(logdir: Path) -> Path:
    files = sorted(Path(logdir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return files[-1]


def load(path: Path) -> Trace:
    """Read one ``.xplane.pb``, gzipped or not, or the profiler's log
    directory."""
    import gzip

    from jax.profiler import ProfileData

    path = Path(path)
    if path.is_dir():
        path = find_xplane(path)
    if path.suffix == ".gz":
        data = ProfileData.from_serialized_xspace(
            gzip.decompress(path.read_bytes()))
    else:
        data = ProfileData.from_file(str(path))
    device: Dict[int, List[Event]] = {}
    host: List[Event] = []
    program: List[Event] = []
    dispatches: List[Event] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m is not None:
                if line.name != OPS_LINE:
                    continue
                device.setdefault(int(m.group(1)), []).extend(
                    map(_event, line.events))
            elif plane.name.startswith("/host:"):
                launched = []
                for e in line.events:
                    name = e.name
                    if name in HOST_SPANS:
                        host.append(_event(e))
                    elif name.startswith(PREFIXES):
                        program.append(_event(e))
                    elif name.startswith(DISPATCH):
                        launched.append(_event(e))
                dispatches += outermost(launched)
    for evs in (*device.values(), host, program, dispatches):
        evs.sort(key=lambda e: e.start)
    return Trace(device=device, host=host, program=program,
                 dispatches=dispatches)


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------
def merged(events: Iterable[Event], lo: float = float("-inf"),
           hi: float = float("inf")) -> List[Tuple[float, float]]:
    """Union of the events' intervals, clipped to [lo, hi]."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted((max(ev.start, lo), min(ev.end, hi))
                       for ev in events):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def covered(intervals: Sequence[Tuple[float, float]], lo: float,
            hi: float) -> float:
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in intervals)


def busy_s(trace: Trace, chip: int, lo: float, hi: float) -> float:
    """Seconds of [lo, hi] in which some operation ran on ``chip``."""
    return covered(merged(trace.device.get(chip, []), lo, hi), lo, hi)


def mean_busy_s(trace: Trace) -> float:
    lo, hi = trace.window()
    chips = sorted(trace.device)
    if not chips:
        return 0.0
    return sum(busy_s(trace, c, lo, hi) for c in chips) / len(chips)


def gaps(trace: Trace, chip: int, lo: float,
         hi: float) -> List[Tuple[float, float]]:
    """Idle intervals of ``chip`` inside [lo, hi]."""
    out, at = [], lo
    for s, e in merged(trace.device.get(chip, []), lo, hi):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def gaps_within(trace: Trace, chip: int,
                spans: Sequence[Event]) -> List[List[Tuple[float, float]]]:
    """Idle intervals of ``chip`` inside each of ``spans`` (sorted and
    disjoint, as the harness's experiments are): what :func:`gaps` gives
    for each span alone, with the device's events merged once for all."""
    if not spans:
        return []
    every = gaps(trace, chip, spans[0].start, max(x.end for x in spans))
    ends = [b for _, b in every]
    out = []
    for x in spans:
        inside = []
        for i in range(bisect.bisect_right(ends, x.start), len(every)):
            a, b = every[i]
            if a >= x.end:
                break
            a, b = max(a, x.start), min(b, x.end)
            if b > a:
                inside.append((a, b))
        out.append(inside)
    return out


# ---------------------------------------------------------------------------
# kernels and collectives
# ---------------------------------------------------------------------------
def matching(trace: Trace, pattern: str, chip: Optional[int] = None,
             ) -> List[Event]:
    """Device events inside the window whose name or stats match
    ``pattern`` (a regular expression)."""
    rx = re.compile(pattern)
    lo, hi = trace.window()
    chips = sorted(trace.device) if chip is None else [chip]
    return [e for c in chips for e in trace.device.get(c, [])
            if lo <= e.start < hi and rx.search(e.text())]


def collective_s(trace: Trace, chip: int) -> float:
    lo, hi = trace.window()
    return covered(merged([e for e in trace.device.get(chip, [])
                           if COLLECTIVE.search(e.name)], lo, hi), lo, hi)


# an XLA Ops event is named by its HLO instruction: "%name.12 = type op(...)"
_HLO = re.compile(r"^%?([\w\-]+?)(?:\.\d+)*(?:\.\w+)* = .*?([a-z][\w\-]*)\(")
# ops that hold other ops: their time is their body's
CONTAINERS = ("while", "conditional", "call")


def op_label(ev: Event) -> Tuple[str, str]:
    """(label, opcode) of an operation: the HLO instruction's name without
    the numbers XLA appends, and its opcode (``%fusion.105 = ... fusion(``
    -> ``("fusion", "fusion")``)."""
    m = _HLO.match(ev.name)
    if m is None:
        return ev.name[:64], ""
    return f"{m.group(1)}:{m.group(2)}", m.group(2)


def breakdown(trace: Trace, top: int = 10) -> Dict[str, list]:
    """The device operations that took most time (summed over chips,
    loops and branches left out since their bodies are listed) and the
    longest idle gaps of the first chip, each named by the innermost
    harness span that covers the gap's middle."""
    lo, hi = trace.window()
    ops: Dict[str, float] = {}
    for evs in trace.device.values():
        for e in evs:
            label, opcode = op_label(e)
            if lo <= e.start < hi and opcode not in CONTAINERS:
                ops[label] = ops.get(label, 0.0) + e.dur
    chip = min(trace.device) if trace.device else 0
    named: List[Tuple[str, float]] = []
    for s, e in gaps(trace, chip, lo, hi):
        mid = 0.5 * (s + e)
        inner = [h for h in trace.host if h.start <= mid < h.end]
        label = min(inner, key=lambda h: h.dur).name if inner else "none"
        named.append((label, e - s))
    named.sort(key=lambda x: -x[1])
    return {"device_ops": [[k, v] for k, v in
                           sorted(ops.items(), key=lambda x: -x[1])[:top]],
            "idle_gaps": [[k, v] for k, v in named[:top]]}

"""Device-idle time inside each experiment span of the harness (from the
run_fused* call to its result on the host), per experiment, in ms: the
host driver's eager set-up, dispatch and the result's trip to the host.
Chip 0's gaps; on four chips the driver dispatches to all at once."""
from harness import trace as trace_lib


def read(ctx):
    tr = ctx["trace"]
    spans = tr.spans("experiment")
    if not spans or not tr.device:
        return None
    chip = min(tr.device)
    idle = 0.0
    for s in spans:
        idle += sum(e - b for b, e in trace_lib.gaps(tr, chip, s.start,
                                                       s.end))
    return 1e3 * idle / len(spans)

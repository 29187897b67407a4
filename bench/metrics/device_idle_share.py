"""Share of the traced window in which no operation ran on the device,
averaged over the chips the cell uses."""
from harness import trace as trace_lib


def read(ctx):
    tr = ctx["trace"]
    lo, hi = tr.window()
    if hi <= lo or not tr.device:
        return None
    return 100.0 * (1.0 - trace_lib.mean_busy_s(tr) / (hi - lo))

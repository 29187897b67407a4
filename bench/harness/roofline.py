"""A kernel's share of its roofline, from the device trace.

For every event of the kernel in the traced window, the least time the
chip could take for the work it must do is the larger of its operations
over the compute peak and its bytes over the HBM bandwidth (the work
count lives in ``work/<kernel>.py``). The share is the sum of those least
times over the summed device time of the events. A share above 100%
means the work is counted too high or the time misses part of the work;
it is reported as read, never clipped.
"""
from __future__ import annotations

from typing import Optional

from . import spec
from . import trace as trace_lib


def share(ctx, kernel: str) -> Optional[float]:
    mod = spec.work_count(kernel)
    events = trace_lib.matching(ctx["trace"], mod.MATCH)
    spent = sum(e.dur for e in events)
    if not events or spent <= 0:
        return None
    w = mod.work(ctx["shape"])
    pk = ctx["peaks"]
    least = max(w["ops"] / pk["bf16_flops"],
                w["bytes"] / pk["hbm_bytes_per_s"])
    return 100.0 * least * len(events) / spent

"""A kernel's share of its roofline from the trace and the work counts."""
from __future__ import annotations

from bench.counts import least_seconds


def kernel_share(ctx, kind: str, scope: str):
    """100 * (least time of the ``kind`` layers over the traced window's
    calls) / (device time under ``scope``); ``None`` where the trace
    shows no such op or the run is not a closed loop."""
    t, r = ctx.trace, ctx.record
    if t is None or ctx.peak is None or r["loop"] != "closed":
        return None
    busy = t.scope_seconds(scope)
    if busy <= 0:
        return None
    least = sum(least_seconds(l["flops"], l["bytes"], ctx.peak)
                for l in ctx.work if l["kind"] == kind)
    return 100.0 * r["calls"] * least / busy

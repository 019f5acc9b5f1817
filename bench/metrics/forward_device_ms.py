"""Device-busy milliseconds per ``predict`` call: the traced window's
busy time over its calls (closed loop)."""


def read(ctx):
    t, r = ctx.trace, ctx.record
    if t is None or r["loop"] != "closed" or not r["calls"] or not t.ops:
        return None
    return 1e3 * t.busy_s / r["calls"]

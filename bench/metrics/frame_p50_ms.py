"""Median time of every ``predict`` call in the window, numpy frames in
to numpy answers out (closed loop)."""
from bench.traffic import quantile


def read(ctx):
    r = ctx.record
    if r["loop"] != "closed" or not r["durations_s"]:
        return None
    return 1e3 * quantile(r["durations_s"], 0.50)

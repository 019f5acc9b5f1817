"""Frames returned to the host per second, over all calls of the window
and all of its time (closed loop)."""


def read(ctx):
    r = ctx.record
    if r["loop"] != "closed" or r["window_s"] <= 0:
        return None
    return r["frames"] / r["window_s"]

"""The whole forward's share of the chip's peak while the device works:
the net's conv operations over every frame of the traced window, over
the window's device-busy seconds (``bench/trace.py``: every op of the
step, transfers' relayouts and pads included), over the peak table's
FLOP/s (closed loop)."""


def read(ctx):
    t, r = ctx.trace, ctx.record
    if t is None or ctx.peak is None or r["loop"] != "closed" \
            or not r["frames"] or t.busy_s <= 0:
        return None
    flops = ctx.forward_flops * r["frames"]
    return 100.0 * flops / t.busy_s / ctx.peak["flops_per_s"]

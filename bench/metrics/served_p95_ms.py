"""95th percentile, over every frame due in the window, of the time from
when the frame was due to when its result was ready. A frame refused,
timed out, failed or never answered counts as beyond any limit (open
loop)."""
from bench.traffic import served_tail_ms


def read(ctx):
    r = ctx.record
    if r["loop"] != "open" or r["attempted"] == 0:
        return None
    return served_tail_ms(r["latency_ms"], 0.95)

"""95th percentile of the server's queue wait (``dequeue - submit`` of
``InferenceResult.timestamps``) over the window's requests that reached
a worker."""
import numpy as np

from bench.traffic import quantile


def read(ctx):
    r = ctx.record
    if r["loop"] != "open":
        return None
    w = r["queue_wait_ms"][np.isfinite(r["queue_wait_ms"])]
    return quantile(w, 0.95) if w.size else None

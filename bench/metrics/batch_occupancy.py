"""Mean frames per batch the server executed in the window
(``InferenceResult.batch_size``): a batch of b frames is counted once,
through its b requests at 1/b each."""
import numpy as np


def read(ctx):
    r = ctx.record
    if r["loop"] != "open":
        return None
    b = r["batch_size"][r["batch_size"] > 0]
    return float(b.size / np.sum(1.0 / b)) if b.size else None

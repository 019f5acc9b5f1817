"""``predict_batch`` of the jit-compiled backends writes three profiler
spans per call (``nncg.to_device``, ``nncg.launch``, ``nncg.to_host``),
in order and apart, and the answers are bit for bit
those of a call with no profiler running."""
import glob
import os

import numpy as np
import pytest

from repro.core.graph import CNNGraph, Conv2D, Input, MaxPool, Softmax
from repro.engine import InferenceSession, SessionConfig

STAGES = ["nncg.to_device", "nncg.launch", "nncg.to_host"]
CALLS = 3


def _net() -> CNNGraph:
    r = np.random.default_rng(0)
    return CNNGraph([
        Input(shape=(8, 8, 1)),
        Conv2D(weights=r.normal(0, 0.5, (3, 3, 1, 4)).astype(np.float32),
               bias=r.normal(0, 0.1, (4,)).astype(np.float32),
               padding="same", activation="relu"),
        MaxPool(size=(2, 2)),
        Conv2D(weights=r.normal(0, 0.5, (2, 2, 4, 2)).astype(np.float32),
               bias=r.normal(0, 0.1, (2,)).astype(np.float32),
               padding="valid"),
        Softmax(),
    ])


def _program_spans(trace_dir) -> list:
    """``(start, end, name)`` of every ``nncg.*`` host event, by start."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(str(trace_dir), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            out += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for e in line.events if e.name.startswith("nncg.")]
    return sorted(out)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_each_call_writes_three_spans_in_order(backend, tmp_path):
    import jax

    sess = InferenceSession(_net(), config=SessionConfig(backend=backend))
    x = np.random.default_rng(1).normal(size=(4, 8, 8, 1)).astype(np.float32)
    untraced = sess.predict(x)  # also compiles, before the trace starts
    with jax.profiler.trace(str(tmp_path)):
        traced = [sess.predict(x) for _ in range(CALLS)]
    for y in traced:
        assert y.dtype == untraced.dtype
        np.testing.assert_array_equal(y, untraced)

    spans = _program_spans(tmp_path)
    assert [n for _, _, n in spans] == STAGES * CALLS
    assert all(s <= e for s, e, _ in spans)
    assert all(a[1] <= b[0] for a, b in zip(spans, spans[1:]))


"""The comparison that decides ``correct``.

Every answer the harness kept from the window (a seeded sample of what
the timed path returned) is compared with the plain reference run over
the same frames once the window has closed. The number compared is the
widest gap of a frame: max |answer - reference| over the frame's
outputs, as a share of max |reference| over that frame.
"""
from __future__ import annotations

import numpy as np


def frame_gaps(got: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Per frame (leading axis): max |got - ref| / max |ref|; ``inf``
    where ``got`` is not finite or has the wrong shape."""
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    if got.shape != ref.shape:
        return np.full(len(ref), np.inf)
    n = len(ref)
    scale = np.abs(ref).reshape(n, -1).max(axis=1)
    gap = np.abs(got - ref).reshape(n, -1).max(axis=1) / scale
    return np.where(np.isfinite(got).reshape(n, -1).all(axis=1), gap, np.inf)


def compare(kept, inputs, reference, block: int) -> np.ndarray:
    """Gaps of every kept frame. ``kept`` holds ``(index, answer)`` pairs,
    ``inputs(index)`` the frames an answer was computed from (one frame,
    or a batch), and ``reference`` maps a batch of ``block`` frames to
    their reference outputs."""
    if not kept:
        return np.zeros(0)
    xs = [np.asarray(inputs(i)) for i, _ in kept]
    ys = [np.asarray(y) for _, y in kept]
    single = xs[0].ndim == 3
    if single:
        xs = [x[None] for x in xs]
        ys = [y[None] for y in ys]
    x = np.concatenate(xs)
    ref = []
    for s in range(0, len(x), block):
        part = x[s:s + block]
        pad = block - len(part)
        if pad:  # one program for every block
            part = np.concatenate([part, np.repeat(part[-1:], pad, 0)])
        ref.append(np.asarray(reference(part))[:block - pad])
    return frame_gaps(np.concatenate(ys), np.concatenate(ref))


def checks(gaps: np.ndarray, limit: float, unanswered: int) -> dict:
    """The numbers compared, each beside its limit."""
    return {
        "out_gap": {"value": float(gaps.max()) if gaps.size else None,
                    "limit": limit, "sense": "<="},
        "compared": {"value": int(gaps.size), "limit": 1, "sense": ">="},
        "unanswered": {"value": int(unanswered), "limit": 0, "sense": "<="},
    }


def passed(c: dict) -> bool:
    for v in c.values():
        if v["value"] is None:
            return False
        if v["sense"] == "<=" and not v["value"] <= v["limit"]:
            return False
        if v["sense"] == ">=" and not v["value"] >= v["limit"]:
            return False
    return True

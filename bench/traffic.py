"""The one traffic generator. A mix is a data file
(``bench/traffic/<name>.json``) of parameters; its ``"loop"`` picks one
of two shapes of load:

* ``"closed"``: one caller sends ``batch`` frames per ``predict`` call
  and sends the next call when the last returns, cycling a pool of
  ``pool_batches`` seeded batches (a fleet of cameras read together, or
  one camera at batch 1).
* ``"open"``: ``cameras`` independent cameras at ``fps`` frames per
  second each, every frame submitted on its own when it is due, whether
  or not earlier frames have come back. Camera ``k`` starts at a phase
  in its own slot of ``phase_spread_ms / cameras`` (jittered from the
  seed), so every seed offers the same load in another order;
  ``phase_spread_ms`` of one frame period is a steady mix, a short one
  a hardware trigger (bursts).

Both take their clock and sleep as arguments, so the tests drive them
on a fake clock. Every result names what it kept for the correctness
check: a seeded sample of the answers the window produced.
"""
from __future__ import annotations

import contextlib
import math
import time

import numpy as np

def quantile(values, q: float) -> float:
    """Nearest-rank ``q`` quantile (0 < q <= 1): the smallest value with
    at least a share ``q`` of all values at or below it. ``inf`` values
    (failed frames) sort last."""
    v = np.sort(np.asarray(values, dtype=np.float64))
    if v.size == 0:
        raise ValueError("quantile of no values")
    return float(v[max(0, math.ceil(q * v.size) - 1)])


class Closed:
    """Closed loop from one caller."""

    def __init__(self, params: dict, rng, frames_fn):
        self.batch = int(params["batch"])
        self.keep = int(params["check_calls"])
        n = int(params["pool_batches"])
        pool = frames_fn(n * self.batch)
        self.pool = pool.reshape((n, self.batch) + pool.shape[1:])
        self.order = rng.permutation(n)
        # reservoir draws for the kept sample, made before the window
        self._u = rng.random(1 << 20)

    def shapes(self) -> list:
        """Batch sizes the window will use."""
        return [self.batch]

    def warm(self, predict) -> None:
        predict(self.pool[self.order[0]])

    def run(self, predict, seconds: float, clock=time.perf_counter,
            span=contextlib.nullcontext) -> dict:
        """Call ``predict`` back to back for ``seconds``; returns every
        call's duration and the kept ``(pool index, output)`` pairs."""
        durations, kept = [], []
        n = len(self.order)
        t_start = clock()
        t_end = t_start + seconds
        i = 0
        now = t_start
        while now < t_end:
            b = int(self.order[i % n])
            with span("bench.predict"):
                y = predict(self.pool[b])
            t = clock()
            durations.append(t - now)
            now = t
            # reservoir sample of ``keep`` calls, uniform over the window
            if len(kept) < self.keep:
                kept.append((b, y))
            else:
                j = int(self._u[i % self._u.size] * (i + 1))
                if j < self.keep:
                    kept[j] = (b, y)
            i += 1
        return {"loop": "closed", "batch": self.batch, "calls": i,
                "frames": i * self.batch, "window_s": now - t_start,
                "durations_s": durations, "kept": kept}

    def inputs(self, index) -> np.ndarray:
        return self.pool[index]


class Open:
    """Open loop: cameras at a fixed frame rate, each frame due on a
    schedule drawn from the seed."""

    def __init__(self, params: dict, rng, frames_fn, seconds: float):
        self.cameras = int(params["cameras"])
        self.fps = float(params["fps"])
        self.deadline_ms = float(params["deadline_ms"])
        self.max_batch = int(params["server"]["max_batch"])
        self.drain_s = float(params.get("drain_s", 60.0))
        period = 1.0 / self.fps
        spread = float(params.get("phase_spread_ms", 1e3 * period)) / 1e3
        slot = spread / self.cameras
        phase = (np.arange(self.cameras) + rng.random(self.cameras)) * slot
        phase = phase[rng.permutation(self.cameras)]
        per_cam = int(math.ceil(seconds * self.fps)) + 1
        due = (phase[:, None] + period * np.arange(per_cam)[None, :]).ravel()
        self.due = np.sort(due[due < seconds], kind="stable")
        npool = int(params["pool_frames"])
        self.pool = frames_fn(npool)
        self.frame = rng.integers(0, npool, self.due.size)
        n_check = min(int(params["check_frames"]), self.due.size)
        self.check = np.zeros(self.due.size, bool)
        self.check[rng.choice(self.due.size, n_check, replace=False)] = True

    def shapes(self) -> list:
        return list(range(1, self.max_batch + 1))

    def warm(self, predict) -> None:
        for b in self.shapes():
            predict(self.pool[:b])

    def run(self, submit, seconds: float, clock=time.perf_counter,
            sleep=time.sleep, span=contextlib.nullcontext) -> dict:
        """Submit every frame when it is due, then wait up to ``drain_s``
        past the window for the answers. Latency runs from when a frame
        was due to when its result was ready; a frame refused at submit,
        failed by the server or never answered has latency ``inf``."""
        n = self.due.size
        handles = [None] * n
        submit_t = np.full(n, np.nan)
        t0 = clock()
        for i in range(n):
            due = t0 + self.due[i]
            wait = due - clock()
            if wait > 0:
                sleep(wait)
            submit_t[i] = clock()
            try:
                with span("bench.submit"):
                    handles[i] = submit(self.pool[self.frame[i]])
            except Exception:  # refused: counted as failed below
                pass
        close = t0 + seconds
        give_up = max(clock(), close) + self.drain_s
        done_t = np.full(n, np.inf)
        dequeue_t = np.full(n, np.nan)
        batch = np.zeros(n)
        errors, never, kept = {}, 0, []
        with span("bench.wait"):
            for i, h in enumerate(handles):
                if h is None:
                    errors["refused"] = errors.get("refused", 0) + 1
                    continue
                try:
                    y = h.result(timeout=max(0.0, give_up - clock()))
                except TimeoutError:
                    never += 1
                    continue
                except Exception as e:  # the server's own failure
                    name = type(e).__name__
                    errors[name] = errors.get(name, 0) + 1
                    dequeue_t[i] = h.timestamps.get("dequeue", np.nan)
                    continue
                done_t[i] = h.timestamps["done"]
                dequeue_t[i] = h.timestamps["dequeue"]
                batch[i] = h.batch_size
                if self.check[i]:
                    kept.append((int(self.frame[i]), y))
        due_abs = t0 + self.due
        latency_ms = (done_t - due_abs) * 1e3
        return {"loop": "open", "attempted": n, "window_s": seconds,
                "latency_ms": latency_ms,
                "queue_wait_ms": (dequeue_t - submit_t) * 1e3,
                "late_ms": (submit_t - due_abs) * 1e3,
                "batch_size": batch, "errors": errors, "never": never,
                "backlog_mid": _backlog(submit_t, done_t, t0 + seconds / 2),
                "backlog_close": _backlog(submit_t, done_t, close),
                "kept": kept}

    def inputs(self, index) -> np.ndarray:
        return self.pool[index]


def _backlog(submit_t, done_t, t) -> int:
    """Frames submitted by ``t`` and not yet answered at ``t``."""
    return int(np.sum((submit_t <= t) & (done_t > t)))


def served_tail_ms(latency_ms, q: float = 0.95) -> float:
    """The ``q`` tail of due-to-ready latency over every frame due in the
    window; failed frames (``inf``) count as beyond any limit."""
    return quantile(latency_ms, q)


def make(params: dict, rng, frames_fn, seconds: float):
    loop = params["loop"]
    if loop == "closed":
        return Closed(params, rng, frames_fn)
    if loop == "open":
        return Open(params, rng, frames_fn, seconds)
    raise ValueError(f"unknown loop {loop!r}")

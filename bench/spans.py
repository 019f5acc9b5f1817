"""The program's own spans in a traced window: the three stages of each
``predict_batch`` call, which the program writes on the profiler's host
clock:

* ``nncg.to_device``: the numpy input made a device buffer, the copy in
  issued (the runtime relayouts the input on its own threads);
* ``nncg.launch``: the jitted call, which returns once the program is
  enqueued;
* ``nncg.to_host``: ``np.asarray`` of the answer: the wait for the rest
  of the copy in, the device's run and its completion, then the copy
  back.

Spans are named by string: the benchmark imports nothing of the
program, and reads 0.0 from a program that writes no such span. Each
reading is a mean per call of the window's closed loop, like
``forward_device_ms``; ``None`` without a trace or in an open loop.

Every reading is a sum of durations, each on one clock. None compares a
host time with a device time: on a TPU v5e a traced call's program
(``XLA Modules``) can sit before the host's launch of it, so the two
clocks cannot place a device op inside a host span.
"""
from __future__ import annotations

TO_DEVICE = "nncg.to_device"
LAUNCH = "nncg.launch"
TO_HOST = "nncg.to_host"


def spans(t, name: str) -> list:
    """``(start, end)`` of every ``name`` span inside the window, in
    order of start (ns)."""
    w0, w1 = t.window
    return sorted((s, e) for s, e, n in t.host
                  if n == name and s >= w0 and e <= w1)


def span_ms(ctx, name: str):
    """Milliseconds per call inside ``name`` spans."""
    t, r = ctx.trace, ctx.record
    if t is None or r["loop"] != "closed" or not r["calls"]:
        return None
    return sum(e - s for s, e in spans(t, name)) / 1e6 / r["calls"]


def host_out_ms(ctx):
    """Milliseconds per call of ``nncg.to_host`` in which the device ran
    nothing: the rest of the copy in and its relayout, the completion
    signalled back and the copy back (``nncg.to_host`` less the
    device's busy time, both per call)."""
    held = span_ms(ctx, TO_HOST)
    if not held:
        return held
    return held - 1e3 * ctx.trace.busy_s / ctx.record["calls"]

"""From a JAX profiler trace to device busy time, kernel time and the
idle gaps, by what the host was doing in them.

The profiler writes an ``.xplane.pb`` under ``<dir>/plugins/profile/``.
On a TPU its ``/device:TPU:<n>`` planes hold a line ``XLA Ops``: one
event per executed HLO op, named by the op's HLO text
(``%conv2d_pallas.5 = f32[...] custom-call(f32[...] %pad.10, ...)``),
with start and duration in nanoseconds on the host's clock. The
``/host:CPU`` plane holds the host threads' spans: the benchmark's own
``bench.*`` annotations and the JAX runtime's events.

``load`` reads the file into plain lists (``Trace``); everything after
that is arithmetic on those lists, checked in ``tests/bench`` on a trace
recorded on the chip.
"""
from __future__ import annotations

import glob
import heapq
import os
import re
from dataclasses import dataclass, field

WINDOW_SPAN = "bench.window"
_HLO_NAME = re.compile(r"^%([\w.\-]+) = ")
_OPERAND = re.compile(r"%([\w.\-]+)")


@dataclass
class Op:
    start: float  # ns
    end: float
    name: str     # HLO instruction name, e.g. "conv2d_pallas.5"
    operands: tuple
    device: int = 0  # the n of its ``/device:TPU:<n>`` plane


@dataclass
class Trace:
    """Device ops per device, host spans, and the traced window."""
    ops: list = field(default_factory=list)          # ops of every device
    devices: int = 1                                 # chips the cell uses
    host: list = field(default_factory=list)         # (start, end, name)
    window: tuple = (0.0, 0.0)                       # ns

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_intervals(self, device=None) -> list:
        """Merged intervals of activity inside the window on ``device``,
        or on any device where it is None."""
        return union([(max(o.start, self.window[0]),
                       min(o.end, self.window[1])) for o in self.ops
                      if o.end > self.window[0] and o.start < self.window[1]
                      and device in (None, o.device)])

    @property
    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the cell's chips:
        each device's busy union, summed, over ``devices``. A device
        the profile shows but the cell does not use adds nothing."""
        busy = sum(e - s for d in {o.device for o in self.ops}
                   for s, e in self.busy_intervals(d))
        return busy / 1e9 / self.devices

    def scope_seconds(self, kernel: str) -> float:
        """Device seconds of the ``kernel`` custom calls (HLO name
        ``<kernel>`` or ``<kernel>.<n>``, from the jitted function that
        makes the call) and of the ``pad`` ops that feed them: the
        padding the kernel's wrapper does before its ``pallas_call``."""
        pat = re.compile(rf"^{re.escape(kernel)}(\.\d+)?$")
        names = set()
        for o in self.ops:
            if pat.match(o.name):
                names.add(o.name)
                names.update(a for a in o.operands if a.startswith("pad"))
        return sum(o.end - o.start for o in self._in_window()
                   if o.name in names) / 1e9

    def _in_window(self):
        return [o for o in self.ops
                if o.start >= self.window[0] and o.end <= self.window[1]]

    def top_ops(self, n: int = 10) -> list:
        """``[[op, seconds], ...]``: the ops that took most device time,
        summed over their runs (the HLO name without its number)."""
        tot = {}
        for o in self._in_window():
            key = o.name.rsplit(".", 1)[0] if o.name[-1:].isdigit() \
                else o.name
            tot[key] = tot.get(key, 0.0) + (o.end - o.start) / 1e9
        return sorted(([k, v] for k, v in tot.items()),
                      key=lambda kv: -kv[1])[:n]

    def idle_gaps(self, n=10) -> list:
        """``[[host activity, seconds], ...]``: the window's idle time
        by what the host was doing. Each instant of a gap between device
        ops goes to the innermost (shortest) host span open at that
        instant, or to ``"no host span"``; summed by the span's name,
        largest first, the first ``n`` (all where ``n`` is None)."""
        w0, w1 = self.window
        busy = self.busy_intervals()
        host = sorted(h for h in self.host
                      if h[2] != WINDOW_SPAN and h[1] > w0 and h[0] < w1)
        # one sweep over every edge: each piece between two edges is
        # either busy or idle, and has one innermost open host span
        cuts = sorted({w0, w1} | {x for iv in busy for x in iv}
                      | {x for h in host for x in h[:2] if w0 < x < w1})
        tot = {}
        open_ = []  # heap of (duration, start, end, name); ended ones lazily
        nxt = bi = 0
        for a, b in zip(cuts, cuts[1:]):
            while bi < len(busy) and busy[bi][1] <= a:
                bi += 1
            if bi < len(busy) and busy[bi][0] <= a:
                continue  # the device is busy over [a, b]
            while nxt < len(host) and host[nxt][0] <= a:
                s, e, name = host[nxt]
                heapq.heappush(open_, (e - s, s, e, name))
                nxt += 1
            while open_ and open_[0][2] < b:
                heapq.heappop(open_)
            name = open_[0][3] if open_ else "no host span"
            tot[name] = tot.get(name, 0.0) + (b - a) / 1e9
        return sorted(([k, v] for k, v in tot.items()),
                      key=lambda kv: -kv[1])[:n]

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}


def union(intervals) -> list:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [tuple(iv) for iv in out]


def parse_op(text: str, start: float, duration: float,
             device: int = 0) -> Op:
    m = _HLO_NAME.match(text)
    name = m.group(1) if m else text
    rhs = text[m.end():] if m else ""
    return Op(start, start + duration, name, tuple(_OPERAND.findall(rhs)),
              device)


def from_profile(pd, devices: int) -> Trace:
    """A ``Trace`` from a ``jax.profiler.ProfileData`` of a run on
    ``devices`` chips (the cell's, not every chip the host shows)."""
    t = Trace(devices=devices)
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = int(plane.name.rsplit(":", 1)[1])
            for line in plane.lines:
                if line.name == "XLA Ops":
                    t.ops.extend(parse_op(e.name, e.start_ns, e.duration_ns,
                                          dev) for e in line.events)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    t.host.append((e.start_ns, e.start_ns + e.duration_ns,
                                   e.name))
    spans = [h for h in t.host if h[2] == WINDOW_SPAN]
    if spans:
        t.window = (spans[0][0], spans[0][1])
    return t


def newest_file(trace_dir) -> str:
    files = glob.glob(os.path.join(str(trace_dir), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def load(trace_dir, devices: int) -> Trace:
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(newest_file(trace_dir)),
                        devices)

"""The readers of the program's ``predict_batch`` spans (``bench/spans.py``
and ``bench/metrics/{host_in,host_out,launch}_ms.py``): exact values on
hand-built traces, 0.0 on the recorded traces of a program that wrote no
such span, ``None`` without a trace, and on a trace recorded on a TPU
v5e with the spans, each call's pieces checked."""
import functools
import json
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench import spans, trace
from bench.spec import Spec
from bench.trace import Op, Trace

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
NAMES = [m["name"] for m in json.loads(
    (ROOT / "BENCHMARK.json").read_text())["per_layer"]
    if m["source"] == "program_span" and m["name"].split(".")[0] in
    ("host_in_ms", "host_out_ms", "launch_ms")]
NO_SPANS = ["robot_cam1_v5e.xplane.pb", "pedestrian_fleet256_v5e.xplane.pb"]
WITH_SPANS = "robot_cam1_spans_v5e.xplane.pb"
STAGES = [spans.TO_DEVICE, spans.LAUNCH, spans.TO_HOST]


def hand_trace():
    """Two calls in a window of 10 us; a span before the window counts
    for nothing."""
    host = [(0, 10_000, trace.WINDOW_SPAN),
            (-500, -100, spans.TO_DEVICE),
            (100, 300, spans.TO_DEVICE), (300, 400, spans.LAUNCH),
            (400, 1100, spans.TO_HOST),
            (2000, 2100, spans.TO_DEVICE), (2100, 2150, spans.LAUNCH),
            (2150, 2900, spans.TO_HOST)]
    ops = [Op(500, 600, "pad.1", ()), Op(550, 800, "conv2d_pallas.1", ()),
           Op(2300, 2400, "conv2d_pallas.1", ()),
           Op(2450, 2500, "maxpool2d_pallas.1", ()),
           Op(5000, 5100, "copy.1", ())]
    return Trace(ops=ops, host=host, window=(0.0, 10_000.0))


# ms per call over the two calls of ``hand_trace``: device busy 300 +
# 150 ns in the calls and 100 ns between them (the window's busy time)
EXPECTED = {"host_in_ms": (200 + 100) / 2e6, "launch_ms": (100 + 50) / 2e6,
            "host_out_ms": ((700 + 750) - (300 + 150 + 100)) / 2e6}


def ctx(t, calls, loop="closed"):
    return NS(record={"loop": loop, "calls": calls}, trace=t)


def test_the_readers_cover_the_three_metrics():
    assert sorted(n.split(".")[0] for n in NAMES) == sorted(
        ["host_in_ms"] * 2 + ["host_out_ms"] * 2 + ["launch_ms"])


@pytest.mark.parametrize("name", NAMES)
def test_reader_on_a_hand_built_trace(name):
    read = Spec(ROOT).reader(name)
    assert read(ctx(hand_trace(), 2)) == pytest.approx(
        EXPECTED[name.split(".")[0]], rel=1e-12)
    assert read(ctx(hand_trace(), 2, loop="open")) is None
    assert read(ctx(None, 2)) is None


@functools.cache
def profile(name):
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(HERE / name))


@functools.cache
def recorded(name):
    return trace.from_profile(profile(name), 1)


def calls_in(t):
    w0, w1 = t.window
    return sum(1 for s, e, n in t.host
               if n == "bench.predict" and s >= w0 and e <= w1)


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("fixture", NO_SPANS)
def test_reader_reads_zero_without_program_spans(name, fixture):
    t = recorded(fixture)
    assert Spec(ROOT).reader(name)(ctx(t, calls_in(t))) == 0.0


def test_recorded_spans_account_for_a_call():
    """The three spans and the device's time per call, as the readers
    give them, leave under 15 % of a traced call to the benchmark's loop
    and to ``InferenceSession.predict`` around ``predict_batch``."""
    t = recorded(WITH_SPANS)
    calls = calls_in(t)
    assert calls >= 3
    starts = sorted((s, n) for s, e, n in t.host if n.startswith("nncg.")
                    and s >= t.window[0] and e <= t.window[1])
    assert [n for _, n in starts] == STAGES * calls
    c = ctx(t, calls)
    spec = Spec(ROOT)
    pieces = {n: spec.reader(n + ".cam1")(c) for n in
              ("host_in_ms", "launch_ms", "forward_device_ms",
               "host_out_ms")}
    assert all(v > 0 for v in pieces.values()), pieces
    call_ms = 1e3 * t.window_s / calls
    assert sum(pieces.values()) == pytest.approx(call_ms, rel=0.15), pieces


def programs(name):
    """``(start, end)`` of each run of the program, in order: the
    device's ``XLA Modules`` line, one event per call."""
    return sorted((e.start_ns, e.start_ns + e.duration_ns)
                  for p in profile(name).planes
                  if p.name.startswith("/device:TPU:")
                  for line in p.lines if line.name == "XLA Modules"
                  for e in line.events)


def test_each_recorded_call_waits_out_its_device_time():
    """Per call, ``nncg.to_host`` holds at least the device-busy time of
    that call's program (matched by order): ``host_out_ms`` is no
    negative piece of any call."""
    t = recorded(WITH_SPANS)
    held = spans.spans(t, spans.TO_HOST)
    runs = programs(WITH_SPANS)
    assert len(held) == len(runs) == calls_in(t)
    for (s, e), (m0, m1) in zip(held, runs):
        busy = sum(b - a for a, b in trace.union(
            (o.start, o.end) for o in t.ops if m0 <= o.start < m1))
        assert 0 < busy < e - s, (busy, e - s)


def test_recorded_programs_start_before_their_launch():
    """The device's planes sit off the host's clock: a call's program
    starts before the host's ``nncg.launch`` of it, which cannot happen
    on one clock. So no reader places a device op inside a host span."""
    t = recorded(WITH_SPANS)
    launched = spans.spans(t, spans.LAUNCH)
    runs = programs(WITH_SPANS)
    assert len(launched) == len(runs) == calls_in(t)
    assert any(m0 < l0 for (m0, _), (l0, _) in zip(runs, launched))

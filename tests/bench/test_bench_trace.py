"""The reduction from a profiler trace to busy time, idle share, time
under a kernel's scope and the breakdown: by hand on small traces, and
on a short trace recorded on a TPU v5e (``*.xplane.pb`` beside this
file)."""
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench import trace
from bench.trace import Op, Trace

HERE = Path(__file__).resolve().parent

CONV_TEXT = (
    "%conv2d_pallas.5 = f32[256,60,80,8]{3,2,1,0:T(8,128)} custom-call("
    "f32[256,62,82,3]{3,2,1,0:T(8,128)} %pad.10, f32[3,3,3,8]{3,2,1,0:"
    "T(4,128)S(1)} %copy-done.2, f32[1,8]{1,0:T(1,128)S(1)} %copy-done.5),"
    " custom_call_target=\"tpu_custom_call\"")


def test_parse_op_reads_the_hlo_name_and_operands():
    op = trace.parse_op(CONV_TEXT, 1000.0, 50.0)
    assert op.name == "conv2d_pallas.5"
    assert op.operands == ("pad.10", "copy-done.2", "copy-done.5")
    assert (op.start, op.end) == (1000.0, 1050.0)


def test_union_merges_overlapping_and_touching_intervals():
    assert trace.union([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 6)]
    assert trace.union([]) == []


def small_trace():
    ops = [Op(0, 100, "pad.1", ("x",)),
           Op(50, 150, "conv2d_pallas.1", ("pad.1", "w")),  # overlaps
           Op(300, 400, "maxpool2d_pallas.1", ("conv2d_pallas.1",)),
           Op(450, 500, "pad.2", ("y",)),                   # feeds no kernel
           Op(900, 1200, "conv2d_pallas.1", ("pad.1", "w"))]  # runs past
    host = [(0, 1000, "bench.window"),
            (0, 980, "bench.predict"),
            (150, 300, "np.asarray(jax.Array)"),
            (150, 700, "PjitFunction(f)")]
    return Trace(ops=ops, host=host, window=(0.0, 1000.0))


def test_busy_is_the_union_inside_the_window():
    t = small_trace()
    # [0,150] + [300,400] + [450,500] + [900,1000] = 400 ns
    assert t.busy_s == pytest.approx(400e-9)
    assert t.window_s == pytest.approx(1000e-9)
    assert 100 * (1 - t.busy_s / t.window_s) == pytest.approx(60.0)


def test_busy_is_averaged_over_devices():
    t = small_trace()
    t.devices = 2
    assert t.busy_s == pytest.approx(200e-9)


def fake_profile(idle_planes):
    """A profile of one busy TPU and ``idle_planes`` more that ran
    nothing, as on a host that shows more chips than the cell uses."""
    def ev(name, start, dur):
        return NS(name=name, start_ns=start, duration_ns=dur)

    busy = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=[
        ev("%conv2d_pallas.1 = f32[8] custom-call(f32[8] %pad.1)", 100, 300),
        ev("%pad.1 = f32[8] pad(f32[8] %x)", 0, 100)])])
    idle = [NS(name=f"/device:TPU:{n}", lines=[NS(name="XLA Ops",
                                                  events=[])])
            for n in range(1, 1 + idle_planes)]
    host = NS(name="/host:CPU", lines=[NS(name="main", events=[
        ev("bench.window", 0, 1000)])])
    return NS(planes=[busy, *idle, host])


@pytest.mark.parametrize("idle_planes", [0, 3])
def test_busy_counts_only_the_cells_chips(idle_planes):
    t = trace.from_profile(fake_profile(idle_planes), devices=1)
    assert t.window_s == pytest.approx(1000e-9)
    assert t.busy_s == pytest.approx(400e-9)
    assert 100 * (1 - t.busy_s / t.window_s) == pytest.approx(60.0)


def test_busy_is_each_devices_union_averaged_over_the_chips():
    t = small_trace()
    t.devices = 2
    t.ops.append(Op(0, 1000, "conv2d_pallas.1", ("pad.1", "w"), device=1))
    # device 0 busy 400 ns, device 1 the whole 1000 ns
    assert t.busy_s == pytest.approx(700e-9)


def test_scope_time_counts_the_kernel_and_the_pad_that_feeds_it():
    t = small_trace()
    # conv2d_pallas.1 inside the window once (100 ns) + pad.1 (100 ns);
    # the run that crosses the window's end is left out
    assert t.scope_seconds("conv2d_pallas") == pytest.approx(200e-9)
    assert t.scope_seconds("maxpool2d_pallas") == pytest.approx(100e-9)
    assert t.scope_seconds("no_such_kernel") == 0.0


def test_breakdown_names_ops_and_the_host_work_in_each_gap():
    t = small_trace()
    ops = dict(t.top_ops())
    assert ops == pytest.approx({"pad": 150e-9, "conv2d_pallas": 100e-9,
                                 "maxpool2d_pallas": 100e-9})
    gaps = dict(t.idle_gaps())
    # gap 150-300: np.asarray is the innermost span open; 400-450 and
    # 500-700: PjitFunction; 700-900: only bench.predict
    assert gaps == pytest.approx({"np.asarray(jax.Array)": 150e-9,
                                  "PjitFunction(f)": 250e-9,
                                  "bench.predict": 200e-9})
    assert sum(gaps.values()) == pytest.approx(t.window_s - t.busy_s)


@pytest.fixture(scope="module")
def chip_traces():
    files = sorted(HERE.glob("*.xplane.pb"))
    if not files:
        pytest.fail("no recorded chip trace beside the test")
    from jax.profiler import ProfileData
    return {f.name: trace.from_profile(ProfileData.from_file(str(f)), 1)
            for f in files}


def test_recorded_chip_trace_reduces(chip_traces):
    for name, t in chip_traces.items():
        assert t.devices == 1, name
        assert t.window_s > 0 and 0 < t.busy_s < t.window_s, name
        assert t.scope_seconds("conv2d_pallas") > 0, name
        assert t.scope_seconds("maxpool2d_pallas") > 0, name
        ops = [n for n, _ in t.top_ops()]
        assert "conv2d_pallas" in ops and "maxpool2d_pallas" in ops, name
        gaps = t.idle_gaps(n=None)
        assert sum(s for _, s in gaps) == pytest.approx(
            t.window_s - t.busy_s, rel=1e-6), name
        assert all(label != "bench.window" for label, _ in gaps), name


def _gaps_by_instant(t):
    """``idle_gaps`` the slow way: every nanosecond of the window on its
    own, for traces on a small integer clock."""
    w0, w1 = (int(x) for x in t.window)
    busy = t.busy_intervals()
    host = [h for h in t.host if h[2] != trace.WINDOW_SPAN]
    tot = {}
    for x in range(w0, w1):
        if any(s <= x and x + 1 <= e for s, e in busy):
            continue
        open_ = [h for h in host if h[0] <= x and x + 1 <= h[1]]
        name = min(open_, key=lambda h: (h[1] - h[0], h[0], h[1], h[2]))[2] \
            if open_ else "no host span"
        tot[name] = tot.get(name, 0.0) + 1e-9
    return tot


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_idle_gaps_match_a_count_by_instant(seed):
    import random
    r = random.Random(seed)
    ops = []
    for _ in range(12):
        s = r.randrange(0, 380)
        ops.append(Op(s, s + r.randrange(1, 30), "pad.1", ()))
    host = [(0, 400, trace.WINDOW_SPAN)]
    for i in range(40):
        s = r.randrange(-20, 400)
        host.append((s, s + r.randrange(1, 120), f"span{i % 5}"))
    t = Trace(ops=ops, host=host, window=(0.0, 400.0))
    got = dict(t.idle_gaps(n=None))
    assert got == pytest.approx(_gaps_by_instant(t))


def test_idle_gaps_keep_pace_with_a_long_traced_window():
    # a window with no device op and 40,000 host spans (a traced run of
    # the batch-1 cell makes about as many): one sweep, not a scan of
    # every open span for every piece of every gap
    import time
    host = [(0.0, 4e9, trace.WINDOW_SPAN)]
    for i in range(20_000):
        host += [(i * 2e5, i * 2e5 + 1.5e5, "bench.predict"),
                 (i * 2e5 + 1e4, i * 2e5 + 1e5, "PjitFunction(f)")]
    t = Trace(host=host, window=(0.0, 4e9))
    t0 = time.perf_counter()
    gaps = dict(t.idle_gaps(n=None))
    assert time.perf_counter() - t0 < 10
    assert sum(gaps.values()) == pytest.approx(4.0)
    assert gaps["PjitFunction(f)"] == pytest.approx(20_000 * 9e4 / 1e9)

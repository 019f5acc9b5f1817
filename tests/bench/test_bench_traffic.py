"""The traffic generator on a fake clock, and the due-time tail
arithmetic: latency runs from when a frame was due, and a failed frame
counts as beyond any limit."""
import math

import numpy as np
import pytest

from bench import traffic

SHAPE = (4, 3, 1)


def frames(n):
    return np.arange(n * 12, dtype=np.float32).reshape((n,) + SHAPE)


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, dt):
        self.t += dt


class Handle:
    def __init__(self, value, error, timestamps, batch_size):
        self.value, self.error = value, error
        self.timestamps, self.batch_size = timestamps, batch_size

    def result(self, timeout=None):
        if self.error is not None:
            raise self.error
        return self.value


def open_params(**kw):
    p = {"loop": "open", "cameras": 4, "fps": 10.0, "deadline_ms": 100.0,
         "pool_frames": 8, "check_frames": 5, "drain_s": 1.0,
         "server": {"max_batch": 4}}
    p.update(kw)
    return p


def test_quantile_is_nearest_rank():
    v = list(range(1, 101))
    assert traffic.quantile(v, 0.95) == 95
    assert traffic.quantile(v, 0.5) == 50
    assert traffic.quantile([7.0], 0.95) == 7.0
    with pytest.raises(ValueError):
        traffic.quantile([], 0.5)


def test_failed_frames_count_beyond_any_limit():
    ok = [1.0] * 95
    assert traffic.served_tail_ms(ok + [math.inf] * 5) == 1.0
    assert traffic.served_tail_ms(ok + [math.inf] * 6) == math.inf


def test_open_schedule_is_seeded_and_offers_the_same_load():
    def make(seed):
        return traffic.Open(open_params(), np.random.default_rng(seed),
                            frames, seconds=2.0)
    a, b, c = make(1), make(1), make(2)
    assert np.array_equal(a.due, b.due) and np.array_equal(a.frame, b.frame)
    # 4 cameras x 10 frames/s x 2 s, whatever the seed
    assert a.due.size == c.due.size == 80
    assert not np.array_equal(a.due, c.due)
    assert (np.diff(a.due) >= 0).all() and a.due.max() < 2.0
    # one phase slot per camera: at most one first frame in each 25 ms
    first = np.sort(a.due[:4])
    assert (np.floor(first / 0.025) == np.arange(4)).all()
    assert a.check.sum() == 5


def test_open_loop_times_from_due_and_counts_failures():
    clock = FakeClock()
    load = traffic.Open(open_params(), np.random.default_rng(3), frames,
                        seconds=1.0)
    n = load.due.size
    served = []

    def submit(x):
        i = len(served)
        served.append(x)
        if i == 0:
            raise RuntimeError("queue full")          # refused
        ts = {"submit": clock(), "dequeue": clock() + 0.001,
              "done": clock() + 0.004}
        if i == 1:
            return Handle(None, TimeoutError(), ts, None)  # never answered
        if i == 2:
            return Handle(None, ValueError("boom"), ts, None)  # failed
        return Handle(x * 2, None, ts, 2)

    rec = load.run(submit, 1.0, clock=clock, sleep=clock.sleep)
    assert rec["attempted"] == n == 40
    assert rec["errors"] == {"refused": 1, "ValueError": 1}
    assert rec["never"] == 1
    lat = rec["latency_ms"]
    assert np.isinf(lat[:3]).all()
    # each answered frame: due, submitted on time, done 4 ms later
    assert np.allclose(lat[3:], 4.0)
    assert np.allclose(rec["queue_wait_ms"][3:], 1.0)
    # 3 of 40 failed: 7.5 %, so the 95th percentile is beyond any limit,
    # and the 92.5th is the answered frames' 4 ms
    assert traffic.served_tail_ms(lat, 0.95) == math.inf
    assert traffic.served_tail_ms(lat, 0.925) == pytest.approx(4.0)
    assert traffic.served_tail_ms(lat, 0.93) == math.inf
    for i, y in rec["kept"]:
        assert np.array_equal(y, frames(8)[i] * 2)
    assert len(rec["kept"]) <= 5


def test_open_loop_lateness_is_measured_from_due():
    clock = FakeClock()
    load = traffic.Open(open_params(cameras=1, fps=4.0), np.random.default_rng(0),
                        frames, seconds=1.0)

    def slow_submit(x):
        clock.t += 0.3  # each submit takes 300 ms: the generator falls behind
        ts = {"submit": clock(), "dequeue": clock(), "done": clock()}
        return Handle(x, None, ts, 1)

    rec = load.run(slow_submit, 1.0, clock=clock, sleep=clock.sleep)
    late = rec["late_ms"]
    assert late[0] == pytest.approx(0.0)
    assert (np.diff(late) > 0).all()  # the backlog grows
    # due-to-done latency carries the lateness, not the submit-to-done time
    assert (rec["latency_ms"] >= late).all()


def test_closed_loop_on_a_fake_clock():
    clock = FakeClock()
    p = {"loop": "closed", "batch": 2, "pool_batches": 3, "check_calls": 2}
    load = traffic.Closed(p, np.random.default_rng(5), frames)

    def predict(x):
        clock.t += 0.25
        return x + 1

    rec = load.run(predict, 1.0, clock=clock)
    assert rec["calls"] == 4 and rec["frames"] == 8
    assert rec["window_s"] == pytest.approx(1.0)
    assert rec["durations_s"] == pytest.approx([0.25] * 4)
    assert len(rec["kept"]) == 2
    for b, y in rec["kept"]:
        assert np.array_equal(y, load.inputs(b) + 1)

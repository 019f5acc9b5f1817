"""What decides ``correct``, driven through the harness at a size a test
run holds: the program passes; the control (the reference at three bf16
passes, put in the program's place) fails; and so does a run whose timed
path alters an answer where it is produced or never answers a frame.

The harness's look for a chip is skipped (``require_tpu=False``); the
Pallas kernels run in interpret mode on the CPU. Each run is a cell made
here of new traffic files at a tiny size, through the same run as on the
chip."""
import json
import os
import shutil
import time
from pathlib import Path

import numpy as np
import pytest

from bench import readings, run

ROOT = Path(__file__).resolve().parents[2]
SEED = 2**31 + 12345

TINY = {
    "tiny_fleet": {"loop": "closed", "batch": 32, "pool_batches": 2,
                   "check_calls": 4},
    "tiny_burst": {"loop": "open", "cameras": 4, "fps": 30,
                   "phase_spread_ms": 0.5, "deadline_ms": 33.3,
                   "pool_frames": 8, "check_frames": 60, "drain_s": 5,
                   "server": {"workers": 1, "max_batch": 4,
                              "batch_deadline_ms": 5.0,
                              "request_timeout_ms": 1000}},
    "tiny_serve": {"loop": "open", "cameras": 3, "fps": 30,
                   "deadline_ms": 33.3, "pool_frames": 8,
                   "check_frames": 12, "drain_s": 5,
                   "server": {"workers": 2, "max_batch": 4,
                              "batch_deadline_ms": 2.0,
                              "request_timeout_ms": 1000}},
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    d = tmp_path_factory.mktemp("checkout")
    shutil.copytree(ROOT / "bench", d / "bench")
    os.symlink(ROOT / "src", d / "src")
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    for name, params in TINY.items():
        (d / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(params))
    for cfg in ("robot", "pedestrian"):
        for t in TINY:
            manifest["workloads"].append(
                {"name": f"{cfg}.{t}", "config": cfg, "traffic": t,
                 "chips": 1, "why": "a test's cell"})
    (d / "BENCHMARK.json").write_text(json.dumps(manifest))
    return d


def go(root, cell, hook=None, seconds=0.5):
    return run.run(root, cell, SEED, seconds, False, require_tpu=False,
                   t_start=time.monotonic(), hook=hook)


@pytest.mark.parametrize("cfg", ["robot", "pedestrian"])
def test_program_is_correct_and_the_control_is_not(root, cfg):
    ok = go(root, f"{cfg}.tiny_fleet")
    assert ok["correct"], ok["checks"]
    ctl = go(root, f"{cfg}.tiny_fleet", hook=readings.control_hook)
    assert not ctl["correct"], ctl["checks"]
    gap = ctl["checks"]["out_gap"]
    assert gap["value"] > gap["limit"]


def alter_one_answer(sess, cfg, weights):
    """Every batch's first answer is off by a thousandth of its scale."""
    backend = sess.backend
    produce = backend.predict_batch

    def predict_batch(x):
        y = np.array(produce(x))
        y[0].flat[0] += 1e-3 * np.abs(y[0]).max()
        return y

    backend.predict_batch = predict_batch


@pytest.mark.parametrize("cell", ["robot.tiny_fleet", "pedestrian.tiny_serve"])
def test_an_altered_answer_is_not_correct(root, cell):
    r = go(root, cell, hook=alter_one_answer)
    assert not r["correct"]
    assert r["checks"]["out_gap"]["value"] > r["checks"]["out_gap"]["limit"]
    assert r["failed"] >= 1


def swap_answers_in_a_batch(sess, cfg, weights):
    """Each batch of two or more frames gets its answers rolled by one:
    every answer is right, but for another frame of the batch."""
    backend = sess.backend
    produce = backend.predict_batch

    def predict_batch(x):
        return np.roll(np.asarray(produce(x)), 1, axis=0)

    backend.predict_batch = predict_batch


def test_a_batching_mix_up_is_not_correct(root):
    r = go(root, "pedestrian.tiny_burst", hook=swap_answers_in_a_batch)
    assert not r["correct"]
    assert r["checks"]["out_gap"]["value"] > r["checks"]["out_gap"]["limit"]


def test_a_frame_never_answered_is_not_correct(root, monkeypatch):
    from repro.serve.server import InferenceServer

    finish_many = InferenceServer._finish_many
    seen, dropped = [], []

    def drop_one(self, reqs):
        # past the set-up's warm-up requests, one answer is never sent
        seen.extend(reqs)
        if not dropped and len(seen) > 20:
            dropped.append(reqs[0])
            reqs = reqs[1:]
        finish_many(self, reqs)

    monkeypatch.setattr(InferenceServer, "_finish_many", drop_one)
    r = go(root, "pedestrian.tiny_serve")
    assert dropped
    assert not r["correct"]
    assert r["checks"]["unanswered"]["value"] >= 1


def test_server_cell_is_correct(root):
    r = go(root, "pedestrian.tiny_serve")
    assert r["correct"], r["checks"]
    assert r["failed"] == 0
    assert r["attempted"] == 3 * 15  # 3 cameras, 30 frames/s, 0.5 s


def test_frame_gap_is_per_frame_and_relative():
    from bench import check

    ref = np.array([[1.0, -4.0], [0.5, 0.25]])
    got = ref + np.array([[0.0, 0.04], [0.005, 0.0]])
    assert check.frame_gaps(got, ref) == pytest.approx([0.01, 0.01])
    bad = got.copy()
    bad[1, 0] = np.nan
    assert check.frame_gaps(bad, ref)[1] == np.inf
    assert (check.frame_gaps(got[:, :1], ref) == np.inf).all()


def test_compare_runs_the_reference_in_equal_blocks():
    from bench import check

    seen = []

    def reference(x):
        seen.append(x.shape)
        return x * 2

    pool = np.arange(10, dtype=np.float32).reshape(10, 1, 1, 1)
    kept = [(i, pool[i] * 2) for i in (3, 1, 4)]
    kept[1] = (1, pool[1] * 2 + 1)
    gaps = check.compare(kept, lambda i: pool[i], reference, block=2)
    assert seen == [(2, 1, 1, 1), (2, 1, 1, 1)]
    assert gaps == pytest.approx([0.0, 0.5, 0.0])


def test_checks_pass_only_within_every_limit():
    from bench import check

    assert check.passed(check.checks(np.array([1e-7]), 1e-6, 0))
    assert not check.passed(check.checks(np.array([1e-5]), 1e-6, 0))
    assert not check.passed(check.checks(np.array([1e-7]), 1e-6, 1))
    assert not check.passed(check.checks(np.zeros(0), 1e-6, 0))

"""``BENCHMARK.json`` as data: every cell's configuration, traffic and
metric readers are found by name and run here on a fake clock at a tiny
size; a new cell made only of new files is found; the entry point
refuses to run without a TPU."""
import json
import math
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from bench import counts, model, traffic
from bench.spec import Spec
from bench.trace import Op, Trace

ROOT = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in MANIFEST["workloads"]]
# The server cell as its manifest entries read once a knee sweep on the
# chip has fixed its camera count in bench/traffic/serve.json; where
# BENCHMARK.json does not hold it yet, the tests add it, from data alone.
SERVE = "robot.serve"
SERVE_ENTRIES = {
    "workloads": [{"name": SERVE, "config": "robot", "traffic": "serve",
                   "chips": 1, "why": "InferenceServer under open-loop "
                   "cameras at 30 frames/s, at 4/5 of the knee"}],
    "end_to_end": [{"name": "served_p95_ms", "unit": "ms",
                    "better": "lower", "bound": 0.1, "source": "host_clock",
                    "workloads": [SERVE]}],
    "per_layer": [
        {"name": f"{name}.serve", "unit": unit, "better": better,
         "source": source, "layer": layer, "moves": "served_p95_ms",
         "workloads": [SERVE]}
        for name, unit, better, source, layer in [
            ("device_idle_pct", "%", "lower", "device_trace", "device"),
            ("queue_wait_p95_ms", "ms", "lower", "program_span", "server"),
            ("batch_occupancy", "frames", "higher", "program_counter",
             "server")]],
}


def with_server_cell(manifest: dict) -> dict:
    """``manifest`` with the server cell's entries, where it lacks them."""
    if SERVE in [w["name"] for w in manifest["workloads"]]:
        return manifest
    return {k: v + SERVE_ENTRIES.get(k, []) if isinstance(v, list) else v
            for k, v in manifest.items()}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """The benchmark's files with the server cell in the manifest."""
    d = tmp_path_factory.mktemp("checkout")
    shutil.copytree(ROOT / "bench", d / "bench")
    (d / "BENCHMARK.json").write_text(json.dumps(with_server_cell(MANIFEST)))
    return d


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, dt):
        self.t += dt


class Handle:
    def __init__(self, y, t):
        self.y = y
        self.timestamps = {"submit": t, "dequeue": t + 1e-3,
                           "done": t + 3e-3}
        self.batch_size = 2

    def result(self, timeout=None):
        return self.y


def tiny(params):
    p = dict(params)
    if p["loop"] == "closed":
        p.update(batch=min(p["batch"], 2), pool_batches=2, check_calls=2)
    else:
        p.update(cameras=3, pool_frames=4, check_frames=4)
    return p


def fake_trace(calls):
    """Two calls' worth of device ops: a pad feeding a conv kernel, a
    pool kernel, and idle time in between."""
    ops = []
    for c in range(calls):
        t = c * 1e6
        ops += [Op(t, t + 1e5, "pad.1", ("copy",)),
                Op(t + 1e5, t + 4e5, "conv2d_pallas.3", ("pad.1", "w")),
                Op(t + 4e5, t + 5e5, "maxpool2d_pallas.2",
                   ("conv2d_pallas.3",))]
    host = [(0.0, calls * 1e6, "bench.window")] + [
        (c * 1e6, c * 1e6 + 9e5, "bench.predict") for c in range(calls)]
    return Trace(ops=ops, host=host, window=(0.0, calls * 1e6))


def test_manifest_is_well_formed():
    m = MANIFEST
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert all((ROOT / p).is_dir() for p in m["paths"])
    assert 1 <= m["run_seconds"] <= 51
    names = [x["name"] for g in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in m[g]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {x["name"]: x for x in m["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= x["bound"] <= 0.25 for x in e2e.values())
    for cell in CELLS:
        reported = [n for n, x in e2e.items()
                    if cell in x.get("workloads", [cell])]
        assert "setup_s" in reported and len(reported) >= 2, cell
        layer = [x for x in m["per_layer"]
                 if cell in x.get("workloads", [cell])]
        assert layer, cell
        for x in layer:
            assert x["moves"] in reported, (cell, x["name"])
    for w in m["workloads"]:
        assert w["chips"] == 1 and len(w["why"]) <= 200


@pytest.mark.parametrize("cell", CELLS + [SERVE] * (SERVE not in CELLS))
def test_cell_resolves_and_runs_on_a_fake_clock(cell, checkout):
    spec = Spec(checkout)
    w = spec.cell(cell)
    cfg = spec.config(w["config"])
    params = tiny(spec.traffic(w["traffic"]))
    rng = np.random.default_rng(1)
    shape = cfg["input_shape"]

    def frames(n):
        return model.camera_frames(n, shape, 1)

    clock = FakeClock()
    load = traffic.make(params, rng, frames, 0.5)
    if params["loop"] == "closed":
        def predict(x):
            clock.t += 0.05
            return x.sum(axis=(1, 2, 3))
        rec = load.run(predict, 0.5, clock=clock)
    else:
        rec = load.run(lambda x: Handle(x.sum(), clock()), 0.5,
                       clock=clock, sleep=clock.sleep)
    rec.pop("kept")
    batch = rec.get("batch", 1)
    ctx = SimpleNamespace(
        record=rec, setup_s=12.5, trace=fake_trace(rec.get("calls", 2)),
        cfg=cfg, params=params, peak={"flops_per_s": 1.97e14,
                                      "hbm_bytes_per_s": 8.19e11},
        work=counts.layer_work(cfg, batch),
        forward_flops=counts.forward_flops(cfg))
    for trace in (False, True):
        for m in spec.metrics(cell, trace):
            v = spec.reader(m["name"])(ctx)
            assert isinstance(v, float) and math.isfinite(v), m["name"]
            if m["unit"] == "%":
                assert 0 < v <= 100, m["name"]


def test_readers_read_nothing_without_a_trace():
    spec = Spec(ROOT)
    rec = {"loop": "closed", "calls": 3, "frames": 3, "window_s": 1.0,
           "durations_s": [0.3] * 3, "batch": 1}
    ctx = SimpleNamespace(record=rec, setup_s=1.0, trace=None, peak=None,
                          cfg=None, params=None, work=[], forward_flops=1)
    for m in MANIFEST["per_layer"]:
        assert spec.reader(m["name"])(ctx) is None, m["name"]


def test_a_new_cell_made_only_of_new_files_is_found(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp_path / "bench" / "traffic" / "fleet8.json").write_text(json.dumps(
        {"loop": "closed", "batch": 8, "pool_batches": 2,
         "check_calls": 2}))
    (tmp_path / "bench" / "metrics" / "calls_made.py").write_text(
        "def read(ctx):\n    return float(ctx.record['calls'])\n")
    manifest["workloads"].append(
        {"name": "pedestrian.fleet8", "config": "pedestrian",
         "traffic": "fleet8", "chips": 1, "why": "a test's cell"})
    manifest["per_layer"].append(
        {"name": "calls_made", "unit": "calls", "better": "higher",
         "source": "host_clock", "layer": "CNN forward",
         "moves": "frames_per_s", "workloads": ["pedestrian.fleet8"]})
    manifest["end_to_end"][0]["workloads"].append("pedestrian.fleet8")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    spec = Spec(tmp_path)
    assert spec.traffic(spec.cell("pedestrian.fleet8")["traffic"])[
        "batch"] == 8
    assert [m["name"] for m in spec.metrics("pedestrian.fleet8", True)] \
        == ["calls_made"]
    assert spec.reader("calls_made")(SimpleNamespace(
        record={"calls": 5})) == 5.0
    assert {m["name"] for m in spec.metrics("pedestrian.fleet8", False)} \
        == {"frames_per_s", "setup_s"}


def _run_entry(cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", "robot.cam1",
         "--seed", str(2**31 + 11), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_entry_point_refuses_the_cpu_backend():
    r = _run_entry(ROOT)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert not any(line.startswith("{") for line in r.stdout.splitlines())


def test_entry_point_fails_without_the_program(tmp_path):
    """A checkout that holds only the benchmark's own files."""
    for p in MANIFEST["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p)
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    r = _run_entry(tmp_path)
    assert r.returncode != 0
    assert "No module named 'repro'" in r.stderr
    assert not any(line.startswith("{") for line in r.stdout.splitlines())


def test_sweep_makes_one_cell_per_camera_count(checkout):
    from bench import sweep

    d, params = sweep.scratch_checkout(checkout, SERVE, [8, 16])
    try:
        spec = Spec(d)
        for n in (8, 16):
            cell = spec.cell(f"{SERVE}.sweep_{n}")
            t = spec.traffic(cell["traffic"])
            assert t["cameras"] == n
            assert {k: v for k, v in t.items() if k != "cameras"} == {
                k: v for k, v in params.items() if k != "cameras"}
            names = {m["name"] for m in spec.metrics(cell["name"], False)}
            assert names == {"served_p95_ms", "setup_s"}
    finally:
        shutil.rmtree(d)

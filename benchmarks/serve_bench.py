"""Serving benchmark: open-loop load against :class:`repro.serve`.

The paper benchmarks single-image latency (the deployment artifact's
inner loop); this drives the *server* built on top of it the way a
robot-side camera would — frames arriving on a clock, not a closed
request/response loop:

* **Open-loop rates** — for each net, a paced generator submits
  synthetic camera frames at fixed arrival rates (fractions of the
  net's measured single-image capacity), records what the client
  feels: achieved QPS, p50/p99 end-to-end latency, drops, batch
  occupancy.  Open-loop means the schedule never waits for results —
  late responses do not slow down arrivals, so queueing shows up in
  the tail instead of hiding in the offered rate.
* **Saturated throughput** — for the pedestrian net, submit-as-fast-
  as-possible with retry-on-backpressure, compared against a plain
  sequential ``session.predict()`` loop on the same host.  Continuous
  batching must *win* this even single-core: a batch of 64 costs one
  GIL-releasing foreign call where the sequential loop pays Python
  dispatch per image.

Rows are merged into ``BENCH_engine.json`` under a ``"serving"`` key
(read-modify-write — the latency tables owned by ``run.py`` are
preserved).  ``--quick`` shrinks durations for CI smoke use.
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.configs.cnn_paper import EXTRA_CNNS, PAPER_CNNS  # noqa: E402
from repro.core import runtime  # noqa: E402
from repro.data.pipeline import camera_frame_batch  # noqa: E402
from repro.engine import InferenceSession, SessionConfig  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.serve import (InferenceServer, ServeError,  # noqa: E402
                         ServerConfig, ServerOverloaded)

ALL_CNNS = {**PAPER_CNNS, **EXTRA_CNNS}
NETS = ["ball", "pedestrian", "robot", "residual"]
BENCH_JSON = os.path.join(os.path.dirname(__file__), "..",
                          "BENCH_engine.json")

# fractions of the net's measured single-image capacity offered by the
# open-loop generator; the pacer itself costs ~15µs/submit single-core,
# so the offered rate is additionally capped to keep the operating
# point sustainable (above it the queue grows without bound and p99
# measures test duration, not the server)
RATE_FRACTIONS = (0.25, 0.75)
MAX_OFFERED_QPS = 8000.0


def _percentiles(us):
    a = np.asarray(us, dtype=np.float64)
    return (float(np.percentile(a, 50)), float(np.percentile(a, 99)))


def _open_loop(srv: InferenceServer, frames: np.ndarray,
               rate_qps: float, duration_s: float) -> dict:
    n = max(int(rate_qps * duration_s), 32)
    interval = 1.0 / rate_qps
    nf = len(frames)
    # warm the server before the paced clock starts: the first requests
    # through a cold worker pay thread spin-up, page faults and branch
    # training, which at a low offered rate (few total requests) used to
    # dominate p99 — a cold-start artifact, not queueing behavior.
    # These warmup round trips are excluded from the percentile stats.
    for i in range(32):
        try:
            srv.submit(frames[i % nf]).result(timeout=30.0)
        except (ServerOverloaded, ServeError):
            pass
    handles, dropped = [], 0
    t0 = time.perf_counter()
    for i in range(n):
        target = t0 + i * interval
        now = time.perf_counter()
        if target > now:
            time.sleep(target - now)
        try:
            handles.append(srv.submit(frames[i % nf]))
        except ServerOverloaded:
            dropped += 1
    lat_us, t_last = [], t0
    for h in handles:
        try:
            h.result(timeout=30.0)
        except ServeError:
            dropped += 1
            continue
        ts = h.timestamps
        lat_us.append((ts["done"] - ts["submit"]) * 1e6)
        t_last = max(t_last, ts["done"])
    span = max(t_last - t0, 1e-9)
    p50, p99 = _percentiles(lat_us) if lat_us else (float("nan"),) * 2
    occ = srv.stats().get("batch_occupancy", float("nan"))
    return {
        "offered_qps": round(rate_qps, 1),
        "achieved_qps": round(len(lat_us) / span, 1),
        "p50_us": round(p50, 1),
        "p99_us": round(p99, 1),
        "completed": len(lat_us),
        "dropped": dropped,
        "batch_occupancy": round(occ, 3),
    }


def _saturated(sess: InferenceSession, frames: np.ndarray,
               n_requests: int) -> dict:
    """Submit-as-fast-as-possible vs a sequential predict() loop."""
    nf = len(frames)
    for i in range(200):                      # warm both paths
        sess.predict(frames[i % nf])
    t0 = time.perf_counter()
    for i in range(n_requests):
        sess.predict(frames[i % nf])
    seq_qps = n_requests / (time.perf_counter() - t0)

    cfg = ServerConfig(workers=1, max_batch=64, max_queue=8192,
                       batch_deadline_ms=5.0, request_timeout_ms=None)
    srv = InferenceServer(sess, config=cfg)
    for i in range(200):
        srv.submit(frames[i % nf])
    time.sleep(0.1)                           # warm the batch path
    t0 = time.perf_counter()
    handles = []
    for i in range(n_requests):
        while True:
            try:
                handles.append(srv.submit(frames[i % nf]))
                break
            except ServerOverloaded:
                time.sleep(0.0005)
    for h in handles:
        h.result(timeout=60.0)
    sat_qps = n_requests / (time.perf_counter() - t0)
    occ = srv.stats().get("batch_occupancy", float("nan"))
    srv.close()
    return {
        "server_qps": round(sat_qps, 1),
        "sequential_qps": round(seq_qps, 1),
        "speedup_vs_sequential": round(sat_qps / seq_qps, 3),
        "batch_occupancy": round(occ, 3),
        "requests": n_requests,
        # the serving topology the numbers were taken under — a row
        # without these is unreproducible (a 1-worker and a 4-worker
        # saturated run are different experiments)
        "workers": cfg.workers,
        "max_batch": cfg.max_batch,
    }


def bench_net(name: str, *, duration_s: float, quick: bool) -> dict:
    g = ALL_CNNS[name]()
    sess = InferenceSession(g, config=SessionConfig(
        backend="c", autotune=not quick, simd=runtime.best_isa(),
        tune_iters=200))
    frames = camera_frame_batch(64, tuple(g.input_shape), seed=7)

    lat_us = sess.benchmark(frames[0], iters=200 if quick else 1000)
    capacity = 1e6 / lat_us
    rows = []
    open_cfg = ServerConfig(workers=1, max_batch=16, max_queue=4096,
                            batch_deadline_ms=2.0,
                            request_timeout_ms=5000.0)
    for frac in RATE_FRACTIONS:
        rate = min(frac * capacity, MAX_OFFERED_QPS)
        srv = InferenceServer(sess, config=open_cfg)
        row = _open_loop(srv, frames, rate, duration_s)
        srv.close()
        row["capacity_fraction"] = frac
        row["workers"] = open_cfg.workers
        row["max_batch"] = open_cfg.max_batch
        rows.append(row)
        print(f"serve_{name}_rate{frac},{row['p50_us']:.1f},"
              f"p99={row['p99_us']:.1f},qps={row['achieved_qps']:.0f}")

    out = {"single_image_us": round(lat_us, 3),
           "capacity_qps": round(capacity, 1),
           "pipeline_stages": sess.backend.describe().get(
               "pipeline_stages", 1),
           "rates": rows}
    if name == "pedestrian":
        out["saturated"] = _saturated(
            sess, frames, n_requests=2000 if quick else 8000)
        print(f"serve_{name}_saturated,"
              f"{out['saturated']['server_qps']:.0f},"
              f"sequential={out['saturated']['sequential_qps']:.0f},"
              f"x{out['saturated']['speedup_vs_sequential']:.2f}")
    return out


def _persist(serving: dict) -> None:
    merged = {}
    if os.path.exists(BENCH_JSON):
        try:
            with open(BENCH_JSON) as f:
                merged = json.load(f)
        except (OSError, ValueError):
            merged = {}
    merged["serving"] = serving
    with open(BENCH_JSON, "w") as f:
        json.dump(merged, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"# wrote {os.path.normpath(BENCH_JSON)}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="short durations, no autotune (CI smoke)")
    ap.add_argument("--nets", nargs="*", default=NETS,
                    choices=NETS, help="subset of nets to drive")
    ap.add_argument("--no-persist", action="store_true",
                    help="don't touch BENCH_engine.json")
    args = ap.parse_args(argv)
    enable_compile_cache()

    duration = 0.5 if args.quick else 2.0
    print("name,p50_us,derived,qps")
    serving: dict = {"meta": {
        "isa": runtime.best_isa(),
        "machine": platform.machine(),
        "python": platform.python_version(),
        "cpus": os.cpu_count(),
        "quick": bool(args.quick),
    }}
    for name in args.nets:
        serving[name] = bench_net(name, duration_s=duration,
                                  quick=args.quick)
    if not args.no_persist:
        _persist(serving)


if __name__ == "__main__":
    main()

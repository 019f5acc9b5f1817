"""Run one benchmark cell once, on the chip, and print its result.

    python3 -m bench.run --workload robot.fleet256 --seed 7 --seconds 20 --trace 0

The cell, its configuration, traffic and metrics are all found by name
from ``BENCHMARK.json`` (see ``bench/spec.py``). The run refuses to
start unless JAX's backend is a TPU with as many chips as the cell asks
for and the Pallas kernels compile for it (no interpret mode). Every
line it prints names the device. The last line of standard output is
the result: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer ones),
``device`` and, last, ``checks``: each number compared beside its limit.
"""
import time

T_START = time.monotonic()  # set-up is timed from the first line

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# a traced run records this much of its window: enough calls or frames
# for every per-layer metric, and a trace that reads back in seconds
TRACE_SECONDS = 3.0
# the profiler's host events: 1 keeps the benchmark's spans and the JAX
# runtime's dispatch, transfer and execute events
HOST_TRACER_LEVEL = 1


class Refused(RuntimeError):
    """No chip to measure on: the run prints no result."""


class CompileMeter:
    """XLA backend compiles (persistent-cache reads included) and
    persistent-cache hits since the last ``take``. Each jitted program
    is one such compile, however many jitted functions it inlines."""

    def __init__(self):
        import jax.monitoring as mon
        self.count, self.seconds, self.cache_hits = 0, 0.0, 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def take(self) -> dict:
        out = {"backend_compiles": self.count,
               "backend_compile_s": self.seconds,
               "cache_hits": self.cache_hits}
        self.count, self.seconds, self.cache_hits = 0, 0.0, 0
        return out


def device_info(jax) -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def emit(record: dict, device: dict) -> None:
    print(json.dumps({**record, "device": device}), flush=True)


def run(root, workload: str, seed: int, seconds: float, trace: bool, *,
        require_tpu: bool = True, t_start: float = T_START,
        hook=None) -> dict:
    """One run of ``workload``; returns the result line's object.

    ``hook(session, cfg, weights)``, where given, may put something else
    in the program's place before the run warms up: the control of
    ``bench/readings.py``, or a fault planted by a test."""
    root = Path(root)
    sys.path.insert(0, str(root / "src"))
    from repro.engine import InferenceSession, SessionConfig

    from bench import check, counts, model, peaks, reference, traffic
    from bench import trace as tracing
    from bench.spec import Spec

    spec = Spec(root)
    cell = spec.cell(workload)
    cfg = spec.config(cell["config"])
    params = spec.traffic(cell["traffic"])
    wanted = spec.metrics(workload, trace)
    readers = {m["name"]: spec.reader(m["name"]) for m in wanted}

    import jax
    # the compile cache lives in the checkout, at a fixed path: the path
    # is part of a cache entry's key
    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    dev = device_info(jax)
    if require_tpu and dev["platform"] != "tpu":
        raise Refused(f"JAX found no TPU (backend {dev['platform']!r})")
    if dev["count"] < cell["chips"]:
        raise Refused(f"{workload} needs {cell['chips']} chip(s), JAX "
                      f"sees {dev['count']}")
    peak = peaks.peak(dev["kind"]) if require_tpu else None
    meter = CompileMeter()

    weights = model.make_weights(cfg)
    sess = InferenceSession(model.program_graph(cfg, weights),
                            config=SessionConfig(backend="pallas"))
    desc = sess.backend.describe()
    if require_tpu and desc["interpret"]:
        raise Refused("the Pallas kernels would run in interpret mode")
    if hook is not None:
        hook(sess, cfg, weights)

    window = min(seconds, TRACE_SECONDS) if trace else seconds
    load = traffic.make(
        params, model.traffic_rng(seed),
        lambda n: model.camera_frames(n, cfg["input_shape"], seed), window)
    load.warm(sess.predict)
    server = None
    if params["loop"] == "open":
        from repro.serve import InferenceServer, ServerConfig
        server = InferenceServer(sess, config=ServerConfig(
            **params["server"]))
        # every worker has started and answered before the window
        for h in [server.submit(load.pool[0])
                  for _ in range(2 * params["server"]["max_batch"])]:
            h.result(timeout=600)
    emit({"phase": "setup", "workload": workload, "seed": seed,
          "shapes": load.shapes(), **meter.take()}, dev)

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    span = contextlib.nullcontext
    if trace:
        import jax.profiler as prof
        opts = prof.ProfileOptions()
        opts.python_tracer_level = 0  # host spans and runtime events only
        opts.host_tracer_level = HOST_TRACER_LEVEL
        prof.start_trace(trace_dir, profiler_options=opts)
        span = prof.TraceAnnotation
    setup_s = time.monotonic() - t_start
    with span("bench.window"):
        if server is None:
            rec = load.run(sess.predict, window, span=span)
        else:
            rec = load.run(server.submit, window, span=span)
    if trace:
        prof.stop_trace()
    in_window = meter.take()
    mem = jax.devices()[0].memory_stats() or {}
    memory_peak = int(mem.get("peak_bytes_in_use", 0))

    window_line = {"phase": "window", "workload": workload,
                   "compiles_in_window": in_window["backend_compiles"],
                   "compile_s_in_window": in_window["backend_compile_s"]}
    if server is None:
        # how the window's time fell: a host stall shows as calls far
        # over the median
        d = np.asarray(rec["durations_s"]) * 1e3
        med = float(np.median(d))
        window_line.update(calls=rec["calls"], call_ms_p50=med,
                           call_ms_max=float(d.max()),
                           slow_call_s=float(d[d > 2 * med].sum() / 1e3))
    else:
        server.close()
        late = rec["late_ms"]
        window_line.update(
            late_ms_p50=traffic.quantile(late, 0.5),
            late_ms_p95=traffic.quantile(late, 0.95),
            late_ms_max=float(late.max()), errors=rec["errors"],
            unanswered=rec["never"], backlog_mid=rec["backlog_mid"],
            backlog_close=rec["backlog_close"])
    emit(window_line, dev)

    # the program's state goes before the reference runs
    kept = rec.pop("kept")
    sess.close()
    del sess, server
    gc.collect()
    block = max(64, rec.get("batch", 1))
    gaps = check.compare(kept, load.inputs,
                         reference.make(cfg, weights, "highest"), block)
    del kept
    limit = float(cfg["out_gap_limit"])
    checks = check.checks(gaps, limit, rec.get("never", 0))
    correct = check.passed(checks)

    trace_summary = None
    if trace:
        trace_summary = tracing.load(trace_dir, cell["chips"])
        shutil.rmtree(trace_dir, ignore_errors=True)
    batch = rec.get("batch", 1)
    ctx = SimpleNamespace(record=rec, setup_s=setup_s, trace=trace_summary,
                          cfg=cfg, params=params, peak=peak,
                          work=counts.layer_work(cfg, batch),
                          forward_flops=counts.forward_flops(cfg))
    metrics = {}
    for m in wanted:
        v = readers[m["name"]](ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    attempted = rec.get("attempted", rec.get("frames", 0))
    failed = (sum(rec.get("errors", {}).values()) + rec.get("never", 0)
              + int((gaps > limit).sum()))
    device = {**dev, "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": int(attempted),
              "failed": int(failed), "metrics": metrics, "device": device}
    if trace_summary is not None:
        device.update(busy_s=trace_summary.busy_s,
                      window_s=trace_summary.window_s)
        result["breakdown"] = trace_summary.breakdown()
    result["checks"] = checks
    tag = f"[{dev['platform']} {dev['kind']} x{dev['count']}]"
    for name, c in checks.items():
        print(f"{tag} check {name} = {c['value']} (limit {c['sense']} "
              f"{c['limit']})", file=sys.stderr, flush=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(ROOT, args.workload, args.seed, args.seconds,
                     bool(args.trace))
    except Refused as e:
        print(f"bench: refused: {e}", file=sys.stderr)
        return 2
    bad = [k for k, v in result["metrics"].items()
           if not math.isfinite(v["value"])]
    if bad:
        # more than 5 % of the frames failed: a tail beyond any limit
        print(f"bench: no result: {bad} read beyond any limit",
              file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

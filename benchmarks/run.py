"""Paper-table benchmarks (one function per table), on the engine API.

Reproduces the NNCG evaluation on the container CPU:
  * Tables IV/V/VI — per-image inference latency of the generated C
    (compiled with the host cc, the paper's deployment path) vs. the XLA
    baseline (jax.jit == today's TF-XLA stack, the paper's main rival).
    The C build is *autotuned*: the engine benchmarks every per-layer
    codegen variant and keeps the fastest (paper Table VII selection),
    caching the result on disk so reruns compile nothing.
  * residual — the DAG workload (depthwise + residual Add + Concat),
    same comparison; unrepresentable before the graph IR.
  * int8 — every network also runs through the post-training-quantized
    C build (per-channel int8 weights, int8 intermediates, int32
    accumulators): latency vs the float C path, top-1 agreement with
    the float oracle on the calibration set, and the byte-planned
    arena (~4x smaller than the float arena).  Calibration runs on
    synthetic *camera-like* frames (bounded, spatially smooth — the
    input domain the paper's nets actually see) with histogram-
    percentile range selection; the recorded ``int8_top1_agreement``
    is a hard >= 0.99 gate on every net.
  * Table VII — feature ablation: generic scalar C -> SSE layout ->
    SSE + full unroll -> autotuned per-layer selection.
  * lm — the LM workload behind the same session surface (PR 9):
    prefill tokens/s and decode ms/token of the reduced gemma3-4b
    through the ``"pallas-lm"`` backend with its autotuned Pallas
    kernel-variant policy, persisted as the ``"lm"`` section.

Prints ``name,us_per_call,derived,arena_bytes`` CSV rows; ``derived``
is the speed-up over the XLA baseline (Tables IV-VI) or over the
generic build (Table VII); ``arena_bytes`` is the liveness-planned
workspace of the C build (empty for non-C rows).

Results are also persisted to ``BENCH_engine.json`` at the repo root so
the perf/memory trajectory is tracked across PRs.
"""
from __future__ import annotations

import json
import os
import platform
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.configs.cnn_paper import EXTRA_CNNS, PAPER_CNNS  # noqa: E402
from repro.core import runtime  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.data.pipeline import camera_frame_batch  # noqa: E402
from repro.engine import (CalibrationConfig, InferenceSession,  # noqa: E402
                          SessionConfig)

ITERS = {"ball": 20000, "pedestrian": 3000, "robot": 800, "residual": 5000}
ALL_CNNS = {**PAPER_CNNS, **EXTRA_CNNS}
BENCH_JSON = os.path.join(os.path.dirname(__file__), "..",
                          "BENCH_engine.json")

# histogram-observer calibration: percentile range selection on
# representative frames (minmax on noise was the robot-net accuracy
# regression — agreement 0.94; see core/quantize.py)
CALIBRATION_METHOD = "percentile"
INT8_AGREEMENT_GATE = 0.99
# perf ratchet: a new run's int8_speedup_vs_c may not fall below this
# fraction of the value persisted in BENCH_engine.json (the slack
# absorbs scheduler noise; a kernel regression is far larger)
INT8_RATCHET_TOLERANCE = 0.90
# layer pipelining: batch-1 stream through the k-stage build vs the
# monolithic build.  The >1.15x win requires a second core — on a
# single-core host the ratio is < 1 by construction (every hand-off is
# pure overhead), so the gate only arms when the host can express the
# parallelism; the measured ratio is recorded honestly either way and
# ratcheted like the int8 speedup.
PIPELINE_GATE = 1.15
PIPELINE_GATE_MIN_NETS = 2
PIPELINE_RATCHET_TOLERANCE = 0.90

RESULTS: dict = {"cnns": {}, "ablation": {}, "lm": {}}

# the LM rows: reduced gemma3-4b through the unified session (Pallas
# variants autotuned exactly like C unroll levels, winner cached)
LM_ARCH = "gemma3-4b"
LM_BATCH, LM_PROMPT, LM_NEW = 4, 24, 16


def _prior_results() -> dict:
    if os.path.exists(BENCH_JSON):
        try:
            with open(BENCH_JSON) as f:
                return json.load(f).get("cnns", {})
        except (OSError, ValueError):
            pass
    return {}


def _check_int8_ratchet(name: str, speedup: float, t_q: float) -> None:
    prior = _prior_results().get(name, {})
    ps = prior.get("int8_speedup_vs_c")
    if ps is None:
        return
    floor = float(ps) * INT8_RATCHET_TOLERANCE
    if speedup >= floor:
        return
    # the ratio also falls when the float *denominator* improves (e.g.
    # a better tuning under a new schedule) — that is a win, and this
    # run's own _persist re-baselines it.  Blame the kernels only when
    # the absolute int8 time itself rose past the same tolerance.
    pq = prior.get("c_int8_us")
    assert pq is not None and t_q <= float(pq) / INT8_RATCHET_TOLERANCE, (
        f"{name}: int8_speedup_vs_c regressed to {speedup:.3f} "
        f"(persisted {ps:.3f}, ratchet floor {floor:.3f}) and c_int8_us "
        f"rose to {t_q:.2f} (persisted {pq}) — the tiled kernels got "
        f"slower; fix the regression or consciously re-baseline "
        f"BENCH_engine.json")
    print(f"# {name}: int8_speedup_vs_c {speedup:.3f} below floor "
          f"{floor:.3f} but c_int8_us {t_q:.2f} holds (persisted {pq}): "
          f"float denominator improved, re-baselining")


def _check_pipeline_ratchet(name: str, speedup: float,
                            t_pipe: float) -> None:
    prior = _prior_results().get(name, {})
    ps = prior.get("pipeline_speedup_batch1")
    if ps is None:
        return
    floor = float(ps) * PIPELINE_RATCHET_TOLERANCE
    if speedup >= floor:
        return
    # same denominator guard as the int8 ratchet: a faster sequential
    # stream drops the ratio without the pipelined build regressing
    pp = prior.get("pipeline_stream_us")
    assert pp is not None and t_pipe <= float(pp) / \
        PIPELINE_RATCHET_TOLERANCE, (
        f"{name}: pipeline_speedup_batch1 regressed to {speedup:.3f} "
        f"(persisted {ps:.3f}, ratchet floor {floor:.3f}) and "
        f"pipeline_stream_us rose to {t_pipe:.2f} (persisted {pp}) — "
        f"the pipelined stream got slower; fix the regression or "
        f"consciously re-baseline BENCH_engine.json")
    print(f"# {name}: pipeline_speedup_batch1 {speedup:.3f} below floor "
          f"{floor:.3f} but pipeline_stream_us {t_pipe:.2f} holds "
          f"(persisted {pp}): sequential baseline improved, "
          f"re-baselining")


def _pipeline_stream_us(g, simd, *, frames: int = 64,
                        repeats: int = 3):
    """Batch-1 stream latency of the monolithic vs the layer-pipelined
    build of the same fused schedule: the pipeline's target workload is
    a camera stream (one frame in flight per stage), so the honest
    comparison is per-frame time of ``predict_batch`` over a frame
    stream, not single-call latency.  Returns
    ``(seq_us_per_frame, pipe_us_per_frame, nstages_timed)``."""
    from repro.core import cgen
    from repro.core.schedule import make_schedule
    from repro.engine.autotune import pipeline_stage_candidates

    # time a real 2-stage build even on a single-core host (where the
    # candidate list is just [1]) — the recorded ratio documents what
    # pipelining costs/buys on *this* machine
    nstages = max(pipeline_stage_candidates() + [2])
    # rolled loops: both builds share the emission style, so the ratio
    # isolates the schedule; the default full unroll would cost minutes
    # of -O3 compile per net for a column about threading
    opts = cgen.CodegenOptions(simd=simd, unroll=None)
    base = runtime.build(g, opts,
                         schedule=make_schedule(g, nstages=1))
    pipe = runtime.build(g, opts,
                         schedule=make_schedule(g, nstages=nstages))
    x = camera_frame_batch(frames, g.input_shape, seed=3)

    def stream_us(net) -> float:
        net.predict_batch(x[:8])          # warm arena pages + threads
        best = None
        for _ in range(repeats):          # min: scheduler-noise guard
            t0 = time.perf_counter()
            net.predict_batch(x)
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        return best / frames * 1e6

    return stream_us(base), stream_us(pipe), nstages


def _check_pipeline_gate() -> None:
    cpus = os.cpu_count() or 1
    if cpus < 2:
        print(f"# pipeline gate skipped: single-core host (cpus={cpus}) "
              f"— stage parallelism needs a second core; ratios "
              f"recorded as measured")
        return
    wins = [n for n, r in RESULTS["cnns"].items()
            if r.get("pipeline_speedup_batch1", 0.0) > PIPELINE_GATE]
    assert len(wins) >= PIPELINE_GATE_MIN_NETS, (
        f"pipeline_speedup_batch1 > {PIPELINE_GATE} on only "
        f"{len(wins)} net(s) ({wins}) with {cpus} cores — expected "
        f">= {PIPELINE_GATE_MIN_NETS}")


def _bench_cnn(name: str):
    simd = runtime.best_isa()
    iters = ITERS[name]
    tune_iters = max(200, iters // 20)
    if name == "ball":
        # the ROADMAP accuracy gate: calibrate and evaluate the ball
        # net *trained* on its dataset, on real frames of that dataset
        # (a random-weight 2-class softmax is a coin flip — its top-1
        # agreement measures tie-breaking luck, not calibration)
        from repro.configs.cnn_paper import trained_ball_classifier
        from repro.data.pipeline import ball_image_batch
        g, _ = trained_ball_classifier(steps=150, seed=0)
        calib = ball_image_batch(32, seed=1)[0]
    else:
        g = ALL_CNNS[name]()
        calib = camera_frame_batch(32, g.input_shape, seed=1)
    x = np.random.default_rng(0).normal(
        size=g.input_shape).astype(np.float32)

    tuned = InferenceSession(g, config=SessionConfig(
        backend="c", autotune=True, simd=simd, tune_iters=tune_iters))
    untuned = InferenceSession(g, config=SessionConfig(backend="c",
                                                       simd=simd))
    int8 = InferenceSession(g, config=SessionConfig(
        backend="c", precision="int8", autotune=True,
        tune_iters=tune_iters,
        calibration=CalibrationConfig(data=calib,
                                      method=CALIBRATION_METHOD)))
    xla = InferenceSession(g, config=SessionConfig(backend="xla"))

    # correctness gates before timing
    ref = xla.predict(x)
    np.testing.assert_allclose(tuned.predict(x), ref, rtol=1e-3, atol=1e-5)
    # the compiled int8 build must match its bit-faithful jax reference
    from repro.core import jax_exec
    from repro.core.quantize import quantization_error
    qref = np.asarray(jax_exec.forward_quantized(int8.qgraph, x[None]))[0]
    np.testing.assert_allclose(int8.predict(x).reshape(qref.shape), qref,
                               rtol=1e-5, atol=1e-6)
    qstats = quantization_error(int8.qgraph, calib)
    assert qstats["top1_agreement"] >= INT8_AGREEMENT_GATE, (
        f"{name}: int8 top-1 agreement "
        f"{qstats['top1_agreement']:.4f} < {INT8_AGREEMENT_GATE} "
        f"(calibration_method={int8.qgraph.method})")

    # min over repeats for the two ratcheted timings: the int8 ratchet
    # asserts on t_c/t_q, and a scheduler-noise spike in either single
    # measurement would fail the gate (or persist a soft baseline)
    t_c = min(tuned.benchmark(x, iters=iters) for _ in range(3))
    t_u = untuned.benchmark(x, iters=iters)
    t_q = min(int8.benchmark(x, iters=iters) for _ in range(3))
    t_x = xla.benchmark(x, iters=max(iters // 10, 100))
    arena = tuned.info["arena_bytes"]
    _check_int8_ratchet(name, t_c / t_q, t_q)
    t_seq_stream, t_pipe_stream, pstages = _pipeline_stream_us(g, simd)
    pipe_speedup = t_seq_stream / t_pipe_stream
    _check_pipeline_ratchet(name, pipe_speedup, t_pipe_stream)

    # fusion record (feeds the README table): what the deployed float
    # schedule fused, whether int8 autotune deployed the fused build,
    # and the arena comparison at the canonical rolled build — the
    # make_schedule invariant (fused arena never grows) re-checked on
    # the real nets every benchmark run
    from repro.core import cgen, codegen
    from repro.core.schedule import make_schedule
    g_opt = tuned.graph
    ropts = cgen.CodegenOptions(simd=simd, unroll=None)
    arena_fused = codegen.compile(
        g_opt, ropts, schedule=make_schedule(g_opt)).arena_bytes
    arena_unfused = codegen.compile(
        g_opt, ropts,
        schedule=make_schedule(g_opt, fusion=False)).arena_bytes
    assert arena_fused <= arena_unfused, name
    sd = tuned.schedule.describe()
    fusion_rec = {
        "fused_adds": len(sd["fused_adds"]),
        "fused_pools": len(sd["fused_pools"]),
        "fused_concats": len(sd["fused_concats"]),
        "arena_bytes_fused": arena_fused,
        "arena_bytes_unfused": arena_unfused,
        "int8_deployed_fused": bool(int8.schedule is not None
                                    and int8.schedule.has_fusion),
    }
    print(f"table_{name}_nncg_c_autotuned,{t_c:.2f},"
          f"speedup_vs_xla={t_x / t_c:.2f},{arena}")
    print(f"table_{name}_nncg_c_untuned,{t_u:.2f},"
          f"autotune_gain={t_u / t_c:.2f},{untuned.info['arena_bytes']}")
    print(f"table_{name}_nncg_c_int8,{t_q:.2f},"
          f"speedup_vs_c={t_c / t_q:.2f},"
          f"variant={int8.simd},{int8.info['arena_bytes']}")
    print(f"table_{name}_xla_jit,{t_x:.2f},baseline=1.0,")
    print(f"table_{name}_nncg_c_pipelined,{t_pipe_stream:.2f},"
          f"pipeline_speedup_batch1={pipe_speedup:.2f},"
          f"stages={pstages}")
    RESULTS["cnns"][name] = {
        "c_autotuned_us": round(t_c, 3),
        "c_untuned_us": round(t_u, 3),
        "c_int8_us": round(t_q, 3),
        "xla_us": round(t_x, 3),
        "speedup_vs_xla": round(t_x / t_c, 3),
        "int8_speedup_vs_c": round(t_c / t_q, 3),
        "int8_kernel_variant": int8.simd,
        "int8_arena_bytes": int8.info["arena_bytes"],
        "int8_top1_agreement": round(qstats["top1_agreement"], 4),
        "int8_max_abs_err": round(qstats["max_abs_err"], 6),
        "calibration_method": int8.qgraph.method,
        "arena_bytes": arena,
        "arena_buffer_sum_bytes": tuned.info["arena_buffer_sum_bytes"],
        "peak_live_bytes": tuned.info["peak_live_bytes"],
        "pipeline_speedup_batch1": round(pipe_speedup, 3),
        "pipeline_stages_timed": pstages,
        "pipeline_stream_us": round(t_pipe_stream, 3),
        "sequential_stream_us": round(t_seq_stream, 3),
        "simd": simd,
        "fusion": fusion_rec,
    }
    return t_c, t_u, t_x


def bench_table4_ball():
    return _bench_cnn("ball")


def bench_table5_pedestrian():
    return _bench_cnn("pedestrian")


def bench_table6_robot():
    return _bench_cnn("robot")


def bench_residual_dag():
    """The DAG workload — depthwise separable block, residual Add,
    Concat — through the same autotuned C vs. XLA comparison."""
    return _bench_cnn("residual")


def bench_table7_features():
    name = "ball"
    iters = ITERS[name]
    g = PAPER_CNNS[name]()
    x = np.random.default_rng(0).normal(
        size=g.input_shape).astype(np.float32)
    sse = "sse" if runtime.host_supports_ssse3() else "structured"

    sessions = {
        "general": InferenceSession(g, config=SessionConfig(
            backend="c", simd="generic", unroll=None)),
        "simd": InferenceSession(g, config=SessionConfig(
            backend="c", simd=sse, unroll=None)),
        "simd_full_unroll": InferenceSession(g, config=SessionConfig(
            backend="c", simd=sse, unroll="auto")),
        "simd_autotuned": InferenceSession(g, config=SessionConfig(
            backend="c", simd=sse, autotune=True,
            tune_iters=max(200, iters // 20))),
    }
    if runtime.host_supports_avx2():  # the paper's named future work
        sessions["avx_fma_autotuned"] = InferenceSession(
            g, config=SessionConfig(backend="c", simd="avx", autotune=True,
                                    tune_iters=max(200, iters // 20)))

    rows = {}
    t_gen = None
    for label, sess in sessions.items():
        t = sess.benchmark(x, iters=iters)
        t_gen = t_gen if t_gen is not None else t
        arena = sess.info["arena_bytes"]  # each build plans its own arena
        print(f"table7_{label},{t:.2f},speedup={t_gen / t:.2f},{arena}")
        rows[f"{label}_us"] = round(t, 3)
        rows[f"{label}_arena_bytes"] = arena
    RESULTS["ablation"] = rows


def bench_lm():
    """The LM workload through the same engine surface: prefill
    throughput (tokens/s) and decode latency (ms/token) of the
    ``"pallas-lm"`` backend with its autotuned kernel policy."""
    from repro.engine import LMConfig, LMSession

    sess = LMSession(config=SessionConfig(
        backend="pallas-lm", autotune=True,
        lm=LMConfig(arch=LM_ARCH, max_context=LM_PROMPT + LM_NEW,
                    decode_batch=LM_BATCH)))
    prompts = np.random.default_rng(0).integers(
        0, sess.model_cfg.vocab_size,
        (LM_BATCH, LM_PROMPT)).astype(np.int32)

    logits, _ = sess.prefill(prompts)       # warm: jit compile both steps
    tok0 = np.argmax(logits, -1).astype(np.int32)

    t_prefill = None
    for _ in range(3):                      # min: scheduler-noise guard
        t0 = time.perf_counter()
        logits, handle = sess.prefill(prompts)
        dt = time.perf_counter() - t0
        t_prefill = dt if t_prefill is None else min(t_prefill, dt)
    sess.decode(handle, tok0)               # warm the decode program
    t_decode = None
    for _ in range(3):
        _, handle = sess.prefill(prompts)
        tok = tok0
        t0 = time.perf_counter()
        for _ in range(LM_NEW):
            tok = np.argmax(sess.decode(handle, tok), -1).astype(np.int32)
        dt = time.perf_counter() - t0
        t_decode = dt if t_decode is None else min(t_decode, dt)

    prefill_tok_s = LM_BATCH * LM_PROMPT / t_prefill
    decode_ms_tok = t_decode / LM_NEW * 1e3  # per step (batch rides free)
    pol = dict(sess.kernel_policy._asdict())
    print(f"lm_{LM_ARCH}_prefill,{t_prefill * 1e6:.0f},"
          f"prefill_tokens_per_s={prefill_tok_s:.0f},")
    print(f"lm_{LM_ARCH}_decode,{t_decode * 1e6:.0f},"
          f"decode_ms_per_token={decode_ms_tok:.2f},")
    RESULTS["lm"][LM_ARCH] = {
        "arch": sess.model_cfg.name,
        "batch": LM_BATCH,
        "prompt_tokens": LM_PROMPT,
        "new_tokens": LM_NEW,
        "prefill_tokens_per_s": round(prefill_tok_s, 1),
        "decode_ms_per_token": round(decode_ms_tok, 3),
        "kernel_policy": pol,
        "tuned_from_cache": bool(sess.tuned.from_cache),
        "n_params": sess.backend.describe()["n_params"],
    }


def _persist() -> None:
    RESULTS["meta"] = {
        "cc": runtime.cc_fingerprint(),
        "isa": runtime.best_isa(),
        "machine": platform.machine(),
        "python": platform.python_version(),
        "cpus": os.cpu_count(),
    }
    # read-modify-write: other benchmarks (serve_bench) own their own
    # top-level sections — don't clobber them
    merged = {}
    if os.path.exists(BENCH_JSON):
        try:
            with open(BENCH_JSON) as f:
                merged = json.load(f)
        except (OSError, ValueError):
            merged = {}
    merged.update(RESULTS)
    with open(BENCH_JSON, "w") as f:
        json.dump(merged, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"# wrote {os.path.normpath(BENCH_JSON)}")


def main() -> None:
    enable_compile_cache()
    print("name,us_per_call,derived,arena_bytes")
    bench_table4_ball()
    bench_table5_pedestrian()
    bench_table6_robot()
    bench_residual_dag()
    bench_table7_features()
    bench_lm()
    _check_pipeline_gate()
    _persist()


if __name__ == "__main__":
    main()

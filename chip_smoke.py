#!/usr/bin/env python3
"""Smoke run of the main paths on one TPU chip, through the user API.

    python chip_smoke.py               # CNN, server and LM phases, 1 chip
    python chip_smoke.py --chips 4     # only the LM mesh prefill, 4 chips

Phases (one process; any failure exits non-zero and prints no result):

* cnn    — ball, pedestrian, robot and residual, seeded weights from
  ``configs/cnn_paper.py``, through ``InferenceSession`` on ``"pallas"``
  and ``"xla"`` at batch 1 and 256. Each output is checked against
  ``jax_exec.forward`` of the unoptimized graph at ``highest`` matmul
  precision, and the compiled Pallas program must hold one
  ``tpu_custom_call`` per conv and valid-MaxPool layer.
* server — ``InferenceServer`` on the robot net's ``"pallas"`` session
  answers 32 frames; every result is checked against the reference.
* lm     — gemma3-4b at its published bf16 widths (seeded weights) on
  ``"pallas-lm"``: the Pallas flash kernel pinned, 512-token prompts,
  prefill and 4 greedy decode steps, against the ``"reference"``
  attention variant fed the same tokens.
* lm_mesh (``--chips 4`` only) — the same model with
  ``LMConfig(mesh_shape=(4, 1))`` data-parallel prefill and decode,
  against the same session without a mesh.

The script refuses to run anywhere but on a TPU, and refuses Pallas
interpret mode. Each phase prints JSON lines with its results, the wall
seconds of each first call (tracing, compiling and running) and the
seconds of XLA backend compiles (persistent-cache reads included); the
last line of standard output is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# The sessions and the reference both multiply in f32 (HIGHEST precision)
# and accumulate in f32; they differ only in summation order, which over
# at most five conv layers moves an output by ~1e-6 of max|reference|.
# Each output must lie within this share of max|reference|: 100x room.
CNN_TOL = 1e-4
ARGMAX_MIN = 0.99   # argmax agreement over (image, position), per run
CNN_BATCHES = (1, 256)
SERVER_FRAMES = 32
SERVER_MAX_BATCH = 8
SEED = 0
LM_SMOKE = False    # gemma3-4b at its published widths, bf16
LM_BATCH = 2
LM_PROMPT = 512     # fills the flash kernel's default 512x512 blocks
LM_NEW_TOKENS = 4
LM_PROMPT_SEEDS = 4  # prompt batches compared per phase
# Two correct attention paths in bf16 differ by their roundings, which 34
# layers of random weights carry to the logits: flash_pallas against the
# reference read 0.0077 to 0.0121 of max|logit| on a v5e, over 2 weight
# seeds x 4 prompt batches. The bound is 2.6x the largest reading; with
# the kernel's causal mask switched off the readings were 0.27 and 0.35.
LM_TOL = 2**-5


class CompileMeter:
    """Seconds of XLA backend compiles (persistent-cache reads included)
    and persistent-cache hits since ``reset``. Each jitted program is one
    such compile, however many jitted functions it inlines, so nothing is
    counted twice; tracing and lowering are not counted."""

    def __init__(self):
        import jax.monitoring as mon
        self.reset()
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def reset(self):
        self.seconds = 0.0
        self.cache_hits = 0

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def take(self) -> dict:
        out = {"backend_compile_s": self.seconds,
               "cache_hits": self.cache_hits}
        self.reset()
        return out


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def custom_calls(jitted, *args) -> int:
    """``tpu_custom_call`` ops (one per Pallas kernel) in the compiled
    program of ``jitted`` at ``args``."""
    text = jitted.lower(*args).compile().as_text()
    return text.count('custom_call_target="tpu_custom_call"')


def compare(got: np.ndarray, ref: np.ndarray) -> dict:
    """Raises unless ``got`` matches ``ref`` within ``CNN_TOL`` of
    max|ref| everywhere and in the argmax over channels at
    ``ARGMAX_MIN`` of the (image, position) pairs."""
    if got.shape != ref.shape or not np.isfinite(got).all():
        raise AssertionError(f"output {got.shape} (finite: "
                             f"{np.isfinite(got).all()}) vs {ref.shape}")
    scale = float(np.abs(ref).max())
    err = float(np.abs(got - ref).max())
    agree = float((got.argmax(-1) == ref.argmax(-1)).mean())
    stats = {"max_abs_err": err, "rel_err": err / scale,
             "argmax_agree": agree}
    if not np.allclose(got, ref, rtol=0.0, atol=CNN_TOL * scale):
        raise AssertionError(f"outside tolerance {CNN_TOL}: {stats}")
    if agree < ARGMAX_MIN:
        raise AssertionError(f"argmax agreement below {ARGMAX_MIN}: {stats}")
    return stats


def reference_fn(graph):
    import jax

    from repro.core import jax_exec

    fn = jax.jit(lambda x: jax_exec.forward(graph, x))

    def run(x):
        with jax.default_matmul_precision("highest"):
            return np.asarray(fn(x))
    return run


def cnn_phase(meter: CompileMeter) -> None:
    import jax.numpy as jnp

    from repro.configs.cnn_paper import EXTRA_CNNS, PAPER_CNNS
    from repro.data.pipeline import camera_frame_batch
    from repro.engine import InferenceSession, SessionConfig

    for name, build in {**PAPER_CNNS, **EXTRA_CNNS}.items():
        graph = build(SEED)
        ref = reference_fn(graph)
        for backend in ("pallas", "xla"):
            sess = InferenceSession(graph,
                                    config=SessionConfig(backend=backend))
            desc = sess.backend.describe()
            for n in CNN_BATCHES:
                x = camera_frame_batch(n, tuple(graph.input_shape),
                                       seed=SEED)
                expect = ref(x)
                meter.reset()
                t0 = time.perf_counter()
                got = sess.predict(x)
                first_call_s = time.perf_counter() - t0
                rec = {"phase": "cnn", "net": name, "backend": backend,
                       "batch": n, "first_call_s": first_call_s,
                       **meter.take(), **compare(got, expect)}
                if backend == "pallas":
                    want = sum(v == "pallas"
                               for v in desc["layers"].values())
                    have = custom_calls(sess.backend._fn, jnp.asarray(x))
                    if have != want or desc["interpret"]:
                        raise AssertionError(
                            f"{name}: {have} tpu_custom_call for {want} "
                            f"kernel layers (interpret={desc['interpret']})")
                    rec["kernels"] = have
                emit(rec)
            sess.close()


def server_phase(meter: CompileMeter) -> None:
    from repro.configs.cnn_paper import robot_detector
    from repro.data.pipeline import camera_frame_batch
    from repro.engine import InferenceSession, SessionConfig
    from repro.serve import InferenceServer, ServerConfig

    graph = robot_detector(SEED)
    frames = camera_frame_batch(SERVER_FRAMES, tuple(graph.input_shape),
                                seed=SEED + 1)
    expect = reference_fn(graph)(frames)
    sess = InferenceSession(graph, config=SessionConfig(backend="pallas"))
    meter.reset()
    # compile every batch size the server can aggregate before it starts,
    # so no request waits on a compile
    for n in range(1, SERVER_MAX_BATCH + 1):
        sess.predict(frames[:n])
    warm = meter.take()
    cfg = ServerConfig(workers=2, max_batch=SERVER_MAX_BATCH,
                       request_timeout_ms=None)
    with InferenceServer(sess, config=cfg) as srv:
        handles = [srv.submit(f) for f in frames]
        got = np.stack([h.result(timeout=120) for h in handles])
    stats = srv.stats()  # after close: every batch has been counted
    if stats["completed"] != SERVER_FRAMES or stats["failed"]:
        raise AssertionError(f"server stats: {stats}")
    emit({"phase": "server", "net": "robot", "backend": "pallas",
          "frames": SERVER_FRAMES, "completed": int(stats["completed"]),
          "batches": int(stats["batches"]), **warm,
          "backend_compile_s_while_serving":
              meter.take()["backend_compile_s"],
          **compare(got, expect)})


def lm_session(params=None, **lm):
    """An ``LMSession`` on ``"pallas-lm"`` at the script's LM shape."""
    from repro.engine import LMConfig, LMSession, SessionConfig

    return LMSession(config=SessionConfig(backend="pallas-lm", lm=LMConfig(
        smoke=LM_SMOKE, max_context=LM_PROMPT + LM_NEW_TOKENS, seed=SEED,
        **lm)), params=params)


def lm_prompts(vocab: int, batch: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.integers(0, vocab, (batch, LM_PROMPT), dtype=np.int32)


def greedy_logits(sess, prompts, forced=None) -> np.ndarray:
    """Logits ``(steps, B, V)`` of the prefill and of each greedy decode
    step. With ``forced`` ``(B, steps)`` tokens those are decoded instead
    of the session's own argmax, so two sessions see the same inputs."""
    logits, handle = sess.prefill(prompts)
    steps = [logits]
    for i in range(LM_NEW_TOKENS - 1):
        tok = steps[-1].argmax(-1) if forced is None else forced[:, i]
        steps.append(sess.decode(handle, tok.astype(np.int32)))
    return np.stack(steps)


def lm_compare(got: np.ndarray, ref: np.ndarray) -> dict:
    """Raises unless the logits ``got`` of every step lie within
    ``LM_TOL`` of max|ref| of the reference ``ref``, and their greedy
    tokens equal the reference's."""
    scale = float(np.abs(ref).max())
    err = float(np.abs(got - ref).max())
    top2 = np.partition(ref, -2, axis=-1)[..., -2:]
    stats = {"max_abs_err": err, "rel_err": err / scale,
             "min_top2_margin_rel": float(
                 (top2[..., 1] - top2[..., 0]).min() / scale),
             "tokens": got.argmax(-1).T.tolist(),
             "reference_tokens": ref.argmax(-1).T.tolist()}
    if not err <= LM_TOL * scale:
        raise AssertionError(f"logits outside tolerance {LM_TOL}: {stats}")
    if stats["tokens"] != stats["reference_tokens"]:
        raise AssertionError(f"tokens differ from the reference: {stats}")
    return stats


def lm_phase(meter: CompileMeter) -> None:
    import jax.numpy as jnp

    # one copy of the weights (about 8 GB in bf16) serves both sessions
    ref_sess = lm_session(attn_variant="reference")
    sess = lm_session(params=ref_sess.backend.params,
                      attn_variant="flash_pallas")
    vocab = sess.model_cfg.vocab_size
    for i in range(LM_PROMPT_SEEDS):
        prompts = lm_prompts(vocab, LM_BATCH, SEED + i)
        ref = greedy_logits(ref_sess, prompts)
        meter.reset()
        t0 = time.perf_counter()
        got = greedy_logits(sess, prompts, forced=ref.argmax(-1).T)
        rec = {"phase": "lm", "arch": sess.model_cfg.name,
               "attention": "flash_pallas", "batch": LM_BATCH,
               "prompt": LM_PROMPT, "prompt_seed": SEED + i,
               "first_greedy_s": time.perf_counter() - t0, **meter.take()}
        if i == 0:
            rec["kernels"] = custom_calls(
                sess.backend._prefill_fn, sess.backend.params,
                {"tokens": jnp.asarray(prompts)})
            if rec["kernels"] < 1:
                raise AssertionError("flash_pallas prefill has no "
                                     "tpu_custom_call")
        emit({**rec, **lm_compare(got, ref)})


def lm_mesh_phase(meter: CompileMeter, chips: int) -> None:
    single = lm_session()
    meshed = lm_session(params=single.backend.params,
                        mesh_shape=(chips, 1))
    if meshed.mesh is None or meshed.mesh.devices.size != chips:
        raise AssertionError(f"no {chips}-device mesh: {meshed.mesh}")
    vocab = single.model_cfg.vocab_size
    prompts = [lm_prompts(vocab, chips, SEED + i)
               for i in range(LM_PROMPT_SEEDS)]
    refs = [greedy_logits(single, p) for p in prompts]
    # the unmeshed weights leave device 0 before the meshed program runs
    single.close()
    del single
    for i, (p, ref) in enumerate(zip(prompts, refs)):
        meter.reset()
        t0 = time.perf_counter()
        got = greedy_logits(meshed, p, forced=ref.argmax(-1).T)
        emit({"phase": "lm_mesh", "arch": meshed.model_cfg.name,
              "mesh": meshed.info["mesh"], "batch": chips,
              "prompt": LM_PROMPT, "prompt_seed": SEED + i,
              "first_greedy_s": time.perf_counter() - t0, **meter.take(),
              **lm_compare(got, ref)})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the LM mesh phase across 4 chips")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    import jax

    from repro.kernels import ops

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r}); "
              f"refusing to run", file=sys.stderr)
        return 1
    if ops._default_interpret():
        print("chip_smoke: Pallas kernels would run in interpret mode",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    emit({"phase": "start", "kind": dev.device_kind, "count": len(devices),
          "jax": jax.__version__, "compile_cache": cache_dir})

    meter = CompileMeter()
    phases = ([("lm_mesh", lambda: lm_mesh_phase(meter, args.chips))]
              if args.chips > 1 else
              [("cnn", lambda: cnn_phase(meter)),
               ("server", lambda: server_phase(meter)),
               ("lm", lambda: lm_phase(meter))])
    for name, run in phases:
        t0 = time.perf_counter()
        try:
            run()
        except Exception:
            traceback.print_exc()
            print(f"chip_smoke: phase {name} failed", file=sys.stderr)
            return 1
        emit({"phase": name, "done": True,
              "wall_s": time.perf_counter() - t0})
    emit({"ok": True, "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(devices)}})
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The LM workload behind the unified session surface: the
``"pallas-lm"`` registry entry, SessionConfig.lm round-trips, the
kernel-variant autotuner + on-disk tuning cache, prefill/decode greedy
equality against the direct :mod:`repro.models.lm` call, mesh fallback,
and token-level serving through the bounded-queue server machinery."""
import glob
import json

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.engine import (  # noqa: E402
    InferenceSession, LMConfig, LMSession, SessionConfig, TuningCache,
    available_backends, get_backend, tune_lm_variants,
)
from repro.engine.backends import LMBackend  # noqa: E402
from repro.models import make_decode_step, make_prefill_step  # noqa: E402
from repro.models.stack import DEFAULT_PAR  # noqa: E402

MAX_CTX, PROMPT, BATCH, STEPS = 32, 12, 2, 4


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    return str(tmp_path_factory.mktemp("lmtune"))


@pytest.fixture(scope="module")
def sess(cache_dir):
    """One autotuned session shared by the module (builds jit programs
    once; the variant timing itself is the slow part)."""
    return LMSession(config=SessionConfig(
        backend="pallas-lm", autotune=True, tune_cache=cache_dir,
        lm=LMConfig(arch="gemma3-4b", max_context=MAX_CTX,
                    decode_batch=BATCH)))


def _prompts(n=BATCH, t=PROMPT, vocab=256, seed=3):
    rng = np.random.default_rng(seed)
    return rng.integers(0, vocab, size=(n, t)).astype(np.int32)


# ------------------------------------------------------ registry seam ----

def test_registry_lists_lm_backend():
    assert "pallas-lm" in available_backends()
    cls = get_backend("pallas-lm")
    assert issubclass(cls, LMBackend)
    assert cls.workload == "lm"
    assert get_backend("c").workload == "cnn"


def test_cnn_session_rejects_lm_config():
    from repro.configs.cnn_paper import PAPER_CNNS
    g = PAPER_CNNS["ball"]()
    with pytest.raises(TypeError, match="LMSession"):
        InferenceSession(g, config=SessionConfig(lm=LMConfig()))
    # mixed legacy kwarg + config stays an error with lm in the mix
    with pytest.raises(TypeError, match="not both"):
        InferenceSession(g, config=SessionConfig(lm=LMConfig()),
                         backend="xla")
    with pytest.raises(TypeError, match="needs SessionConfig.lm"):
        LMSession(config=SessionConfig())
    with pytest.raises(ValueError, match="LM contract"):
        LMSession(config=SessionConfig(backend="xla", lm=LMConfig()))


def test_session_config_lm_round_trip():
    cfg = SessionConfig(backend="pallas-lm", autotune=True,
                        lm=LMConfig(arch="gemma3-4b", max_context=64,
                                    decode_batch=2,
                                    attn_variant="reference",
                                    block_q=128, mesh_shape=(1, 1)))
    d = json.loads(json.dumps(cfg.to_dict()))  # JSON-safe
    assert d["lm"]["mesh_shape"] == [1, 1]
    assert SessionConfig(**d) == cfg.portable() == cfg
    assert SessionConfig.from_dict(d) == cfg
    # shorthand spellings coerce to the same LMConfig
    assert SessionConfig(lm="gemma3-4b").lm == LMConfig(arch="gemma3-4b")
    assert SessionConfig(lm={"arch": "gemma3-4b"}).lm == LMConfig()
    assert SessionConfig().lm is None


def test_lm_config_validates():
    with pytest.raises(ValueError, match="arch"):
        LMConfig(arch="nope")
    with pytest.raises(ValueError, match="attn_variant"):
        LMConfig(attn_variant="fast")
    with pytest.raises(ValueError, match="scan_variant"):
        LMConfig(scan_variant="nope")
    with pytest.raises(ValueError, match="max_context"):
        LMConfig(max_context=0)
    with pytest.raises(ValueError, match="mesh_shape"):
        LMConfig(mesh_shape=(0, 2))
    with pytest.raises(TypeError, match="lm must be"):
        SessionConfig(lm=3)


# ------------------------------------------------------ the CPU smoke ----

def test_prefill_decode_matches_direct_model(sess):
    """Prefill + 4 decode steps through the session equal the greedy
    loop over the direct models/lm.py step functions (same params,
    same kernel policy)."""
    toks = _prompts(vocab=sess.model_cfg.vocab_size)
    logits, handle = sess.prefill(toks)
    assert logits.shape == (BATCH, sess.model_cfg.vocab_size)
    got = [np.argmax(logits, -1).astype(np.int32)]
    for _ in range(STEPS):
        step = sess.decode(handle, got[-1])
        assert step.shape == (BATCH, sess.model_cfg.vocab_size)
        got.append(np.argmax(step, -1).astype(np.int32))
    got = np.stack(got, axis=1)

    cfg = sess.model_cfg
    par = DEFAULT_PAR.with_kernels(sess.kernel_policy)
    prefill = jax.jit(make_prefill_step(cfg, max_len=MAX_CTX, par=par))
    decode = jax.jit(make_decode_step(cfg, par=par))
    lg, caches, pos = prefill(sess.backend.params,
                              {"tokens": jnp.asarray(toks)})
    tok = jnp.argmax(lg, -1)[:, None]
    ref = [np.asarray(tok[:, 0], np.int32)]
    for _ in range(STEPS):
        lg, caches, pos = decode(sess.backend.params, caches, tok, pos)
        tok = jnp.argmax(lg, -1)[:, None]
        ref.append(np.asarray(tok[:, 0], np.int32))
    np.testing.assert_array_equal(got, np.stack(ref, axis=1))

    # generate() is exactly that loop
    np.testing.assert_array_equal(
        sess.generate(toks, STEPS + 1), got)


def test_predict_full_sequence_agrees_with_prefill(sess):
    toks = _prompts(vocab=sess.model_cfg.vocab_size)
    full = sess.predict(toks)
    assert full.shape == (BATCH, PROMPT, sess.model_cfg.vocab_size)
    last, _ = sess.prefill(toks)
    np.testing.assert_array_equal(full[:, -1].argmax(-1),
                                  last.argmax(-1))


def test_prompt_longer_than_context_rejected(sess):
    with pytest.raises(ValueError, match="max_context"):
        sess.prefill(_prompts(t=MAX_CTX + 1))


def test_session_info(sess):
    info = sess.info
    assert info["workload"] == "lm"
    assert info["backend"] == "pallas-lm"
    assert info["arch"] == "gemma3-4b-smoke"
    assert info["kernel_policy"]["attention"] in (
        "flash_jax", "flash_pallas", "reference")
    assert info["n_params"] > 0
    json.dumps(info["config"])  # reconstructible + serializable
    assert SessionConfig(**info["config"]) == sess.config.portable()


# --------------------------------------------- autotune + tuning cache ----

def test_autotune_persists_winner(sess, cache_dir):
    assert sess.tuned is not None and not sess.tuned.from_cache
    assert sess.tuned.prefill_us > 0
    files = glob.glob(cache_dir + "/*.json")
    assert files, "autotuned winner must land in the on-disk cache"
    rec = json.load(open(files[0]))
    assert rec["policy"]["attention"] == sess.kernel_policy.attention
    assert rec["arch"] == "gemma3-4b-smoke"


def test_second_session_loads_policy_from_cache(sess, cache_dir):
    s2 = LMSession(config=sess.config)
    assert s2.tuned.from_cache
    assert s2.kernel_policy == sess.kernel_policy
    toks = _prompts(vocab=sess.model_cfg.vocab_size)
    np.testing.assert_array_equal(s2.generate(toks, 3),
                                  sess.generate(toks, 3))


def test_tuning_cache_keys_unique_across_variants(sess, tmp_path):
    """Every pinned Pallas-variant combination keys its own cache entry
    — one variant's measurement can never answer for another's."""
    cache = TuningCache(str(tmp_path))
    cfg, params = sess.model_cfg, sess.backend.params
    pins = [
        dict(attention="flash_jax", scan="chunked",
             block_q=128, block_k=128),
        dict(attention="reference", scan="chunked",
             block_q=128, block_k=128),
        dict(attention="flash_jax", scan="chunked",
             block_q=256, block_k=128),
    ]
    for n, fixed in enumerate(pins, start=1):
        r = tune_lm_variants(cfg, params, max_context=16, prompt=8,
                             cache=cache, iters=1, fixed=fixed)
        assert not r.from_cache
        assert r.policy.attention == fixed["attention"]
        assert len(glob.glob(str(tmp_path) + "/*.json")) == n
    # and a repeat of the first pin is a pure cache hit
    r = tune_lm_variants(cfg, params, max_context=16, prompt=8,
                         cache=cache, iters=1, fixed=pins[0])
    assert r.from_cache
    assert len(glob.glob(str(tmp_path) + "/*.json")) == len(pins)


def test_pinned_variants_skip_autotuning(cache_dir):
    s = LMSession(config=SessionConfig(
        backend="pallas-lm",
        lm=LMConfig(max_context=16, attn_variant="reference",
                    scan_variant="chunked", block_q=128, block_k=128)))
    assert s.tuned is None
    assert s.kernel_policy.attention == "reference"
    out = s.generate(_prompts(t=8), 2)
    assert out.shape == (BATCH, 2)


# ----------------------------------------------------------- mesh path ----

def test_mesh_fallback_on_undersized_host():
    cfg = SessionConfig(backend="pallas-lm",
                        lm=LMConfig(max_context=16, mesh_shape=(8, 8),
                                    attn_variant="flash_jax"))
    with pytest.raises(ValueError, match="mesh_shape"):
        LMSession(config=cfg)


def test_mesh_single_device_matches_unmeshed():
    lm = LMConfig(max_context=16, attn_variant="flash_jax",
                  scan_variant="chunked", block_q=128, block_k=128)
    s0 = LMSession(config=SessionConfig(backend="pallas-lm", lm=lm))
    s1 = LMSession(config=SessionConfig(
        backend="pallas-lm",
        lm=LMConfig(**{**lm.to_dict(), "mesh_shape": (1, 1)})))
    assert s1.mesh is not None
    toks = _prompts(t=8)
    np.testing.assert_array_equal(s1.generate(toks, 3),
                                  s0.generate(toks, 3))


# ------------------------------------------------------- token serving ----

def test_lm_token_server_end_to_end(sess):
    from repro.serve import LMTokenServer, ServerConfig
    toks = _prompts(vocab=sess.model_cfg.vocab_size)
    want = sess.generate(toks, 6)
    with LMTokenServer(sess, config=ServerConfig(
            workers=1, max_batch=4, request_timeout_ms=None)) as srv:
        futs = [srv.submit(toks[i], max_new=6) for i in range(BATCH)]
        got = np.stack([f.result(timeout=120.0) for f in futs])
        # mixed shapes ride the same queue: a shorter prompt with a
        # different max_new still comes back in order
        other = srv.generate(toks[0, :6], max_new=3, timeout=120.0)
        stats = srv.stats()
    np.testing.assert_array_equal(got, want)
    assert other.shape == (3,)
    assert stats["completed"] == BATCH + 1
    with pytest.raises(TypeError, match="serves tokens"):
        srv.predict(toks[0])


def test_lm_token_server_validates(sess):
    from repro.serve import LMTokenServer
    with pytest.raises(TypeError, match="LMSession or LMBackend"):
        LMTokenServer(object())
    with LMTokenServer(sess.backend, workers=1) as srv:
        with pytest.raises(ValueError, match="1-D int"):
            srv.submit(np.zeros((2, 3), np.int32))
        with pytest.raises(ValueError, match="max_new"):
            srv.submit(np.zeros(3, np.int32), max_new=0)

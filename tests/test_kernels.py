"""Per-kernel allclose sweeps against the ref.py oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.cnn_paper import EXTRA_CNNS, PAPER_CNNS
from repro.core.graph import Conv2D
from repro.kernels import ops, ref
from repro.kernels.conv2d import conv_tap_groups

jax.config.update("jax_enable_x64", False)


def rnd(key, shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(key), shape, dtype=jnp.float32
                             ).astype(dtype)


# ---------------------------------------------------------------- conv2d ----

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,h,w,ci,co,kh,kw,stride,padding,act", [
    (1, 16, 16, 1, 8, 5, 5, 2, "same", "relu"),
    (2, 9, 7, 3, 4, 3, 3, 1, "same", "leaky_relu"),
    (1, 8, 8, 8, 12, 3, 3, 1, "valid", None),
    (2, 6, 6, 4, 16, 2, 2, 2, "valid", "relu"),
    (1, 12, 10, 2, 6, 1, 1, 1, "valid", None),
    (1, 60, 80, 3, 8, 3, 3, 1, "same", "leaky_relu"),  # robot detector L1
    # across the tap-group boundaries of conv_tap_groups
    (1, 7, 9, 16, 20, 3, 3, 1, "same", "leaky_relu"),  # 2 groups: K 128+16
    (1, 9, 4, 32, 64, 3, 3, 1, "same", "relu"),  # 3 groups
    (2, 4, 2, 64, 2, 4, 2, 1, "valid", None),  # pedestrian's last, 4 groups
    (1, 6, 5, 128, 8, 3, 3, 1, "valid", None),  # g = 1: one dot per tap
    (2, 20, 14, 1, 8, 5, 5, 2, "same", "relu"),  # ball L1, non-square
])
def test_conv2d(n, h, w, ci, co, kh, kw, stride, padding, act, dtype):
    x = rnd(0, (n, h, w, ci), dtype)
    wt = rnd(1, (kh, kw, ci, co), dtype) * 0.2
    b = rnd(2, (co,), jnp.float32)
    y = ops.conv2d(x, wt, b, strides=(stride, stride), padding=padding,
                   act=act)
    y_ref = ref.conv2d_ref(x.astype(jnp.float32), wt.astype(jnp.float32), b,
                           strides=(stride, stride), padding=padding, act=act)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(y_ref),
                               rtol=tol, atol=tol)


def test_conv2d_blocked_cout():
    """c_out tiling across lane blocks is seam-free."""
    x = rnd(0, (1, 8, 8, 4))
    wt = rnd(1, (3, 3, 4, 8)) * 0.2
    b = rnd(2, (8,))
    y1 = ops.conv2d(x, wt, b, padding="same", block_cout=4)
    y2 = ref.conv2d_ref(x, wt, b, padding="same")
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-5,
                               atol=1e-5)


def test_conv_tap_groups_of_the_nets():
    """One dot per grid step for every layer with c_in <= 14; the groups
    cover the taps in order and fill at most 128 lanes of K."""
    groups = {
        name: [conv_tap_groups(kh, kw, ci)
               for kh, kw, ci, _ in (l.weights.shape for l in make(0).layers
                                     if isinstance(l, Conv2D))]
        for name, make in {**PAPER_CNNS, **EXTRA_CNNS}.items()}
    assert groups == {
        "robot": [(9,), (9,), (9,), (9,), (8, 1)],
        "pedestrian": [(9,), (9,), (4, 4, 1), (2, 2, 2, 2)],
        "ball": [(25,), (9,), (4,)],
        "residual": [(9,), (1,), (1,), (9,), (1,)],
    }
    assert conv_tap_groups(1, 1, 8) == (1,)
    assert conv_tap_groups(3, 3, 128) == (1,) * 9
    assert conv_tap_groups(3, 3, 300) == (1,) * 9
    for kh, kw, ci in [(3, 3, 20), (5, 5, 6), (7, 1, 100), (2, 3, 43)]:
        gs = conv_tap_groups(kh, kw, ci)
        assert sum(gs) == kh * kw and max(gs) * ci <= 128
        assert all(g == gs[0] for g in gs[:-1]) and gs[-1] <= gs[0]


# ------------------------------------------------------------- maxpool2d ----

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape,size,stride", [
    ((1, 8, 8, 8), (2, 2), None),
    ((2, 9, 9, 4), (3, 3), (2, 2)),
    ((1, 16, 8, 12), (2, 2), (2, 2)),
])
def test_maxpool(shape, size, stride, dtype):
    x = rnd(3, shape, dtype)
    y = ops.maxpool2d(x, size=size, strides=stride)
    y_ref = ref.maxpool2d_ref(x, size=size, strides=stride)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32), rtol=0, atol=0)


# -------------------------------------------------------- flash attention ----

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,hq,hkv,t,d,causal,window,bq,bk", [
    (1, 4, 4, 128, 32, True, None, 64, 64),
    (2, 8, 2, 128, 64, True, None, 128, 64),    # GQA 4:1
    (1, 4, 1, 256, 32, True, 64, 64, 64),       # sliding window (MQA)
    (1, 2, 2, 128, 32, False, None, 64, 64),    # bidirectional (encoder)
    (1, 4, 2, 192, 64, True, 100, 64, 64),      # window not block-aligned
])
def test_flash_attention(b, hq, hkv, t, d, causal, window, bq, bk, dtype):
    q = rnd(4, (b, hq, t, d), dtype)
    k = rnd(5, (b, hkv, t, d), dtype)
    v = rnd(6, (b, hkv, t, d), dtype)
    y = ops.flash_attention(q, k, v, causal=causal, window=window,
                            block_q=bq, block_k=bk)
    y_ref = ref.attention_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                              v.astype(jnp.float32), causal=causal,
                              window=window)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_matches_block_sizes():
    """Result is independent of the chosen tiling."""
    q, k, v = (rnd(i, (1, 2, 256, 32)) for i in (7, 8, 9))
    outs = [np.asarray(ops.flash_attention(q, k, v, block_q=bq, block_k=bk))
            for bq, bk in [(64, 64), (128, 128), (256, 64), (64, 256)]]
    for o in outs[1:]:
        np.testing.assert_allclose(outs[0], o, rtol=2e-5, atol=2e-5)


# ------------------------------------------------------------ linear scan ----

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,t,h,n,m,chunk", [
    (1, 64, 2, 8, 16, 32),
    (2, 128, 4, 16, 16, 128),
    (1, 96, 1, 4, 8, 32),
])
def test_linear_scan(b, t, h, n, m, chunk, dtype):
    decay = jax.nn.sigmoid(rnd(10, (b, t, h, n), jnp.float32)) * 0.5 + 0.5
    k = rnd(11, (b, t, h, n), dtype) * 0.3
    v = rnd(12, (b, t, h, m), dtype) * 0.3
    r = rnd(13, (b, t, h, n), dtype) * 0.3
    s0 = rnd(14, (b, h, n, m), jnp.float32) * 0.1
    y, sT = ops.linear_scan(decay.astype(dtype), k, v, r, s0, chunk=chunk)
    y_ref, sT_ref = ref.linear_scan_ref(decay, k, v, r, s0)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32), rtol=tol,
                               atol=tol)
    np.testing.assert_allclose(np.asarray(sT), np.asarray(sT_ref),
                               rtol=tol, atol=tol)


def test_linear_scan_state_carry():
    """Chunk boundaries carry state exactly: two half scans == one scan."""
    b, t, h, n, m = 1, 64, 2, 4, 8
    decay = jnp.full((b, t, h, n), 0.9)
    k = rnd(15, (b, t, h, n)) * 0.2
    v = rnd(16, (b, t, h, m)) * 0.2
    r = rnd(17, (b, t, h, n)) * 0.2
    s0 = jnp.zeros((b, h, n, m))
    y_full, s_full = ops.linear_scan(decay, k, v, r, s0, chunk=16)
    y1, s1 = ops.linear_scan(decay[:, :32], k[:, :32], v[:, :32], r[:, :32],
                             s0, chunk=16)
    y2, s2 = ops.linear_scan(decay[:, 32:], k[:, 32:], v[:, 32:], r[:, 32:],
                             s1, chunk=16)
    np.testing.assert_allclose(np.asarray(y_full),
                               np.concatenate([y1, y2], axis=1),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s_full), np.asarray(s2),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------------------- interpret policy ----

@pytest.mark.parametrize("platform,interpret", [
    ("cpu", True), ("tpu", False), ("gpu", None)])
def test_interpret_only_on_cpu(platform, interpret, monkeypatch):
    """Interpret mode on the CPU backend, Mosaic on the TPU, and an error
    on any other platform rather than a silent interpreted run."""
    monkeypatch.setattr(ops.jax, "default_backend", lambda: platform)
    if interpret is None:
        with pytest.raises(RuntimeError, match="target the TPU"):
            ops._default_interpret()
    else:
        assert ops._default_interpret() is interpret

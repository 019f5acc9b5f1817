"""Pallas TPU kernel: fused Conv2D + bias + (leaky-)ReLU.

This is the paper's compute hot spot (§II-B.1) rebuilt TPU-native instead
of ported: the CPU version vectorizes over output channels with SSE
(groups of 4); here ``c_out`` lives on the 128-wide lane dimension and
each kernel invocation computes the convolution as an **implicit GEMM**
over **tap groups**: the windows of ``g`` filter taps are stacked along
the contraction axis in VMEM (an im2col that never reaches HBM) and each
group is one MXU ``dot`` with K = ``g * c_in`` <= 128. The paper's nets
have ``c_in`` of 1 to 64, so a per-tap dot would use a few of the MXU's
128 rows of K while paying a full pass over the output pixels for each of
the 9 taps; grouping pays that pass once per group
(``conv_tap_groups``).

NNCG principle mapping:
  * P1 (unroll/caching): the group and tap loops are *static* Python
    loops — fully unrolled at trace time; the whole padded image tile
    stays resident in VMEM across taps (the cache-residency side of the
    trade-off).
  * P2 (cond-move):     activation is a ``jnp.where`` → VPU select.
  * P3 (constants):     shapes/taps/strides/groups are compile-time
    constants; BN is folded into weights/bias *before* the call
    (passes.py), and the weights are regrouped per tap group in the
    wrapper, which XLA folds into the constant.
  * P4 (SIMD layout):   NHWC with ``c_out`` blocked on lanes,
    ``block_cout`` a multiple of 128 where the layer allows; each tap
    group fills at most one 128-lane patch of the contraction.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The whole padded image tile, its double buffer and the f32 (HIGHEST)
# split of each tap group's patch stay in VMEM: the robot net's 60x80 first
# layer needs more than the 16 MiB default scoped limit of a v5e, whose
# VMEM holds 128 MiB.
_VMEM_LIMIT_BYTES = 32 * 2**20


def conv_tap_groups(kh: int, kw: int, ci: int) -> Tuple[int, ...]:
    """Taps per MXU dot, in tap order, for a ``kh x kw`` filter over
    ``ci`` input channels: as many taps as fit ``g * ci <= 128`` lanes of
    the contraction (at least one), the last group holding the rest."""
    taps = kh * kw
    g = min(max(1, 128 // ci), taps)
    return (g,) * (taps // g) + ((taps % g,) if taps % g else ())


def _conv_kernel(x_ref, w_ref, b_ref, o_ref, *, kh: int, kw: int,
                 sh: int, sw: int, oh: int, ow: int,
                 act: Optional[str], alpha: float):
    ci = x_ref.shape[-1]
    tc = o_ref.shape[-1]
    taps = [(n, m) for n in range(kh) for m in range(kw)]
    acc = jnp.zeros((oh * ow, tc), jnp.float32)
    start = 0
    # P1: static group and tap loops, unrolled at trace
    for j, g in enumerate(conv_tap_groups(kh, kw, ci)):
        # (OH, OW, CI) tap windows, read from the VMEM-resident padded
        # tile with strided Ref loads (Mosaic refuses a strided slice of
        # a loaded value), stacked along the contraction
        wins = [x_ref[0, pl.ds(n, oh, stride=sh), pl.ds(m, ow, stride=sw), :]
                for n, m in taps[start:start + g]]
        start += g
        xs = wins[0] if g == 1 else jnp.concatenate(wins, axis=-1)
        acc += jnp.dot(xs.reshape(oh * ow, g * ci),
                       w_ref[j, :g * ci].astype(xs.dtype),
                       # f32 operands in full f32, not one bf16 pass
                       precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
    acc = acc + b_ref[0][None, :].astype(jnp.float32)
    if act == "relu":
        acc = jnp.maximum(acc, 0.0)
    elif act == "leaky_relu":
        acc = jnp.where(acc > 0, acc, alpha * acc)  # P2: select, no branch
    o_ref[0] = acc.reshape(oh, ow, tc).astype(o_ref.dtype)


def conv2d_pallas(x: jax.Array, w: jax.Array, b: jax.Array, *,
                  strides: Tuple[int, int] = (1, 1),
                  padding: str = "valid",
                  act: Optional[str] = None, alpha: float = 0.1,
                  block_cout: Optional[int] = None,
                  interpret: bool = True) -> jax.Array:
    """x: (N,H,W,CI) NHWC; w: (KH,KW,CI,CO) HWIO; b: (CO,)."""
    n, h, wd, ci = x.shape
    kh, kw, wci, co = w.shape
    assert wci == ci
    sh, sw = strides
    if padding == "same":
        out_h, out_w = -(-h // sh), -(-wd // sw)
        ph = max((out_h - 1) * sh + kh - h, 0)
        pw = max((out_w - 1) * sw + kw - wd, 0)
        x = jnp.pad(x, ((0, 0), (ph // 2, ph - ph // 2),
                        (pw // 2, pw - pw // 2), (0, 0)))
        h, wd = h + ph, wd + pw
    oh = (h - kh) // sh + 1
    ow = (wd - kw) // sw + 1
    tc = block_cout or min(co, 128)
    if co % tc:
        tc = co
    b2 = b.reshape(1, co)
    # one (g * ci, co) slab per tap group, in tap order; the last group's
    # missing taps are zero rows the kernel never reads
    groups = conv_tap_groups(kh, kw, ci)
    gk = groups[0] * ci
    w3 = jnp.pad(w.reshape(kh * kw * ci, co),
                 ((0, len(groups) * gk - kh * kw * ci), (0, 0))
                 ).reshape(len(groups), gk, co)
    kern = functools.partial(_conv_kernel, kh=kh, kw=kw, sh=sh, sw=sw,
                             oh=oh, ow=ow, act=act, alpha=alpha)
    return pl.pallas_call(
        kern,
        grid=(n, co // tc),
        in_specs=[
            pl.BlockSpec((1, h, wd, ci), lambda i, j: (i, 0, 0, 0)),
            pl.BlockSpec((len(groups), gk, tc), lambda i, j: (0, 0, j)),
            pl.BlockSpec((1, tc), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, oh, ow, tc), lambda i, j: (i, 0, 0, j)),
        out_shape=jax.ShapeDtypeStruct((n, oh, ow, co), x.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(x, w3, b2)

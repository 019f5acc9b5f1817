"""Pallas TPU kernel: 2-D max pooling (paper §II-B.2).

Channels ride the lane dimension (P4); the window tap loop is static and
unrolled at trace time (P1); the max is a VPU ``jnp.maximum`` — the
vector analogue of the paper's ``_mm_max_ps`` / ternary emission (P2).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _pool_kernel(x_ref, o_ref, *, kh, kw, sh, sw, oh, ow):
    out = None
    for n in range(kh):
        for m in range(kw):
            # strided load from the Ref: Mosaic refuses a strided slice
            # of a loaded value (vector.extract_strided_slice)
            xs = x_ref[0, pl.ds(n, oh, stride=sh), pl.ds(m, ow, stride=sw), :]
            out = xs if out is None else jnp.maximum(out, xs)
    o_ref[0] = out


def maxpool2d_pallas(x: jax.Array, *, size: Tuple[int, int] = (2, 2),
                     strides: Optional[Tuple[int, int]] = None,
                     block_c: Optional[int] = None,
                     interpret: bool = True) -> jax.Array:
    n, h, w, c = x.shape
    kh, kw = size
    sh, sw = strides or size
    oh = (h - kh) // sh + 1
    ow = (w - kw) // sw + 1
    tc = block_c or min(c, 128)
    if c % tc:
        tc = c
    kern = functools.partial(_pool_kernel, kh=kh, kw=kw, sh=sh, sw=sw,
                             oh=oh, ow=ow)
    return pl.pallas_call(
        kern,
        grid=(n, c // tc),
        in_specs=[pl.BlockSpec((1, h, w, tc), lambda i, j: (i, 0, 0, j))],
        out_specs=pl.BlockSpec((1, oh, ow, tc), lambda i, j: (i, 0, 0, j)),
        out_shape=jax.ShapeDtypeStruct((n, oh, ow, c), x.dtype),
        interpret=interpret,
    )(x)

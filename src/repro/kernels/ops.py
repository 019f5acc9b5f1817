"""Jit'd public wrappers for the Pallas kernels.

The kernels target the TPU, where each call compiles to Mosaic. On the
CPU backend (``JAX_PLATFORMS=cpu``, where the tests run) they run in
Pallas interpret mode. Any other platform raises: a kernel never runs
interpreted where a device was expected.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax

from .conv2d import conv2d_pallas
from .flash_attention import flash_attention_pallas
from .linear_scan import linear_scan_pallas
from .maxpool2d import maxpool2d_pallas


def _default_interpret() -> bool:
    """Interpret on the CPU backend, compile on the TPU, raise elsewhere."""
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(
        f"the Pallas kernels target the TPU; JAX's default backend is "
        f"{platform!r} (use JAX_PLATFORMS=cpu for interpret mode)")


# ``interpret`` is a static argument of each jitted kernel, so a trace
# made in one mode is never reused in the other
_conv2d = jax.jit(conv2d_pallas, static_argnames=(
    "strides", "padding", "act", "alpha", "block_cout", "interpret"))
_maxpool2d = jax.jit(maxpool2d_pallas, static_argnames=(
    "size", "strides", "block_c", "interpret"))
_flash_attention = jax.jit(flash_attention_pallas, static_argnames=(
    "causal", "window", "scale", "block_q", "block_k", "interpret"))
_linear_scan = jax.jit(linear_scan_pallas,
                       static_argnames=("chunk", "interpret"))


def conv2d(x, w, b, *, strides: Tuple[int, int] = (1, 1),
           padding: str = "valid", act: Optional[str] = None,
           alpha: float = 0.1, block_cout: Optional[int] = None):
    return _conv2d(x, w, b, strides=strides, padding=padding, act=act,
                   alpha=alpha, block_cout=block_cout,
                   interpret=_default_interpret())


def maxpool2d(x, *, size: Tuple[int, int] = (2, 2),
              strides: Optional[Tuple[int, int]] = None,
              block_c: Optional[int] = None):
    return _maxpool2d(x, size=size, strides=strides, block_c=block_c,
                      interpret=_default_interpret())


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128):
    return _flash_attention(
        q, k, v, causal=causal, window=window, scale=scale,
        block_q=block_q, block_k=block_k, interpret=_default_interpret())


def linear_scan(decay, k, v, r, s0, *, chunk: int = 128):
    return _linear_scan(decay, k, v, r, s0, chunk=chunk,
                        interpret=_default_interpret())

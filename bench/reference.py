"""The plain reference: the configuration's layers, unoptimized, in
straightforward ``jax.numpy`` at float32.

It imports nothing of the program and takes nothing the program made:
batch norm is applied as its own layer (the program folds it into the
conv), every activation is its own op, and the weights are the arrays
``bench/model.py`` drew from the seed.

``precision`` is ``"highest"`` (what the configuration states: float32
products) or ``"high"``, the control: each product in three bf16
passes, ``hi*hi + hi*lo + lo*hi`` of the operands split into bf16
halves, the step below float32 that a later change might take. The
split is done here explicitly, so the control reads the same on any
backend; each pass multiplies bf16 values at ``HIGHEST``, which is
exact.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST


def _bf16(a):
    """``a`` rounded to the nearest bfloat16 (ties to even), kept as
    float32. Done on the bits: a compiler that may keep excess precision
    is free to drop a float32 -> bfloat16 -> float32 round trip, and on
    the TPU it does."""
    bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
    bits = (bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))) \
        & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _split(a):
    hi = _bf16(a)
    return hi, _bf16(a - hi)


def _conv(x, w, padding, precision):
    def conv(a, b):
        return jax.lax.conv_general_dilated(
            a, b, window_strides=(1, 1), padding=padding.upper(),
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=_HIGHEST)

    if precision == "highest":
        return conv(x, w)
    if precision == "high":
        xh, xl = _split(x)
        wh, wl = _split(w)
        return conv(xh, wh) + (conv(xh, wl) + conv(xl, wh))
    raise ValueError(f"unknown precision {precision!r}")


def forward(layers, weights, x, precision="highest"):
    """``x`` (N, H, W, C) float32 through ``layers`` (the configuration's
    list) with ``weights`` (one dict per layer)."""
    for layer, p in zip(layers, weights):
        kind = layer["kind"]
        if kind == "conv":
            x = _conv(x, p["w"], layer["padding"], precision) + p["b"]
        elif kind == "batchnorm":
            x = (x - p["mean"]) / jnp.sqrt(p["var"] + layer["eps"]) \
                * p["gamma"] + p["beta"]
        elif kind == "relu":
            x = jnp.maximum(x, 0.0)
        elif kind == "leaky_relu":
            x = jnp.where(x > 0, x, layer["alpha"] * x)
        elif kind == "maxpool":
            kh, kw = layer["size"]
            x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                      (1, kh, kw, 1), (1, kh, kw, 1),
                                      "VALID")
        elif kind == "dropout":
            pass  # identity at inference
        elif kind == "softmax":
            x = jax.nn.softmax(x, axis=-1)
        else:
            raise ValueError(f"unknown layer kind {kind!r}")
    return x


def make(cfg: dict, weights: list, precision: str = "highest"):
    """A jitted ``x -> reference output`` for the configuration."""
    layers = cfg["layers"]
    w = jax.tree.map(jnp.asarray, weights)
    return jax.jit(functools.partial(forward, layers, w,
                                     precision=precision))

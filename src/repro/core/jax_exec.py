"""Pure-JAX executor for :class:`repro.core.graph.CNNGraph`.

Serves two roles:
  1. the numerical *oracle* the generated C is validated against, and
  2. the **XLA baseline** for the paper's speed-up tables — the paper's
     main comparison is TensorFlow XLA; ``jax.jit`` is the same compiler
     stack, so ``jit(forward)`` is the modern equivalent of the tfcompile
     object file.

Evaluation is a topological walk keyed by layer name: each layer reads
its producers from the value environment, so branching DAGs (residual
Adds, Concats) run through the same path as sequential nets — and the
``vmap`` batch oracle and the Pallas kernel path inherit DAG support for
free.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .graph import (
    Add,
    AvgPool,
    BatchNorm,
    CNNGraph,
    Concat,
    Conv2D,
    Dense,
    DepthwiseConv2D,
    Dropout,
    Flatten,
    GlobalAvgPool,
    Input,
    LeakyReLU,
    MaxPool,
    ReLU,
    Softmax,
    pool_window_counts,
)

_DIMS = ("NHWC", "HWIO", "NHWC")
# The graphs are float32 models. On the TPU a matmul or conv at default
# precision multiplies f32 operands in one bf16 pass (~1e-2 relative
# error, enough to flip argmaxes), so the float paths ask for f32.
_F32 = jax.lax.Precision.HIGHEST


def _activation(x: jnp.ndarray, kind: Optional[str], alpha: float) -> jnp.ndarray:
    if kind is None:
        return x
    if kind == "relu":
        return jnp.maximum(x, 0.0)
    if kind == "leaky_relu":
        # branch-free select — the paper's P2 (conditional move) principle
        return jnp.where(x > 0, x, alpha * x)
    if kind == "softmax":
        return jax.nn.softmax(x, axis=-1)
    raise ValueError(f"unknown activation {kind!r}")


def _pool(x: jnp.ndarray, size, strides, op, init,
          pads=(0, 0, 0, 0)) -> jnp.ndarray:
    kh, kw = size
    sh, sw = strides
    pt, pb, pl, pr = pads
    return jax.lax.reduce_window(
        x, init, op,
        window_dimensions=(1, kh, kw, 1),
        window_strides=(1, sh, sw, 1),
        padding=((0, 0), (pt, pb), (pl, pr), (0, 0)),
    )


def _apply(layer, ins: Sequence[jnp.ndarray]) -> jnp.ndarray:
    """One batched-NHWC layer application; ``ins`` are the producer
    outputs in edge order."""
    x = ins[0] if ins else None
    if isinstance(layer, Conv2D):
        pt, pb, pl, pr = layer.pad_amounts(x.shape[1:])
        y = jax.lax.conv_general_dilated(
            x, jnp.asarray(layer.weights),
            window_strides=layer.strides,
            padding=((pt, pb), (pl, pr)),
            dimension_numbers=_DIMS,
            precision=_F32,
        ) + jnp.asarray(layer.bias)
        return _activation(y, layer.activation, layer.alpha)
    if isinstance(layer, DepthwiseConv2D):
        pt, pb, pl, pr = layer.pad_amounts(x.shape[1:])
        kh, kw = layer.kh, layer.kw
        # HWCM -> HWIO with I=1, O=c*mult (group-major, matches XLA)
        w = jnp.asarray(layer.weights).reshape(kh, kw, 1, layer.c_out)
        y = jax.lax.conv_general_dilated(
            x, w,
            window_strides=layer.strides,
            padding=((pt, pb), (pl, pr)),
            dimension_numbers=_DIMS,
            feature_group_count=layer.c_in,
            precision=_F32,
        ) + jnp.asarray(layer.bias)
        return _activation(y, layer.activation, layer.alpha)
    if isinstance(layer, Dense):
        y = jnp.matmul(x.reshape(x.shape[0], -1),
                       jnp.asarray(layer.weights), precision=_F32)
        y = y + jnp.asarray(layer.bias)
        y = _activation(y, layer.activation, layer.alpha)
        return y.reshape(y.shape[0], 1, 1, -1)
    if isinstance(layer, MaxPool):
        pads = layer.pad_amounts(x.shape[1:])
        return _pool(x, layer.size, layer.strides, jax.lax.max, -jnp.inf,
                     pads)
    if isinstance(layer, AvgPool):
        pads = layer.pad_amounts(x.shape[1:])
        s = _pool(x, layer.size, layer.strides, jax.lax.add, 0.0, pads)
        counts = pool_window_counts(x.shape[1:], layer.size, layer.strides,
                                    pads)
        return s / jnp.asarray(counts[None, :, :, None], jnp.float32)
    if isinstance(layer, GlobalAvgPool):
        return jnp.mean(x, axis=(1, 2), keepdims=True)
    if isinstance(layer, Add):
        y = ins[0]
        for other in ins[1:]:
            y = y + other
        return _activation(y, layer.activation, layer.alpha)
    if isinstance(layer, Concat):
        return jnp.concatenate(list(ins), axis=-1)
    if isinstance(layer, ReLU):
        return jnp.maximum(x, 0.0)
    if isinstance(layer, LeakyReLU):
        return jnp.where(x > 0, x, layer.alpha * x)
    if isinstance(layer, Softmax):
        return jax.nn.softmax(x, axis=-1)
    if isinstance(layer, BatchNorm):
        scale, shift = layer.scale_shift()
        return x * jnp.asarray(scale) + jnp.asarray(shift)
    if isinstance(layer, Dropout):
        return x  # identity at inference
    if isinstance(layer, Flatten):
        return x.reshape(x.shape[0], 1, 1, -1)
    raise TypeError(f"unhandled layer {type(layer).__name__}")  # pragma: no cover


def forward(graph: CNNGraph, x: jnp.ndarray) -> jnp.ndarray:
    """Run the graph on a batched NHWC input ``x`` (topo-order walk)."""
    assert x.ndim == 4, "expected NHWC batch"
    vals: Dict[str, jnp.ndarray] = {}
    for layer in graph.layers:
        if isinstance(layer, Input):
            assert x.shape[1:] == tuple(layer.shape), (
                f"input shape {x.shape[1:]} != {layer.shape}"
            )
            vals[layer.name] = x
        else:
            vals[layer.name] = _apply(
                layer, [vals[n] for n in layer.inputs])
    return vals[graph.sink.name]


def make_jit_forward(graph: CNNGraph):
    """Compile the graph with XLA — weights are baked as constants
    (paper P3: the trained model is fully known at compile time)."""

    @jax.jit
    def f(x):
        return forward(graph, x)

    return f


def make_vmap_forward(graph: CNNGraph):
    """Batched oracle: ``vmap`` of the single-image forward, jitted.

    The serving-side counterpart of the generated C batch entry point —
    one trace of the per-image program mapped over the batch axis."""

    def single(xi):
        return forward(graph, xi[None])[0]

    return jax.jit(jax.vmap(single))


def _runs_as_kernel(layer) -> bool:
    return isinstance(layer, Conv2D) or (
        isinstance(layer, MaxPool) and layer.padding == "valid")


def pallas_layer_plan(graph: CNNGraph) -> Dict[str, str]:
    """Per layer, how :func:`forward_pallas` runs it: ``"pallas"`` (one
    Pallas kernel) or ``"jnp"`` (plain jnp ops)."""
    return {layer.name: "pallas" if _runs_as_kernel(layer) else "jnp"
            for layer in graph.layers if not isinstance(layer, Input)}


def forward_pallas(graph: CNNGraph, x: jnp.ndarray) -> jnp.ndarray:
    """Run the CNN through the Pallas TPU kernels (conv2d fused with
    bias+activation, maxpool) — the TPU-native deployment path of the
    generated-C artifact. Mosaic on the TPU, interpret mode on the CPU
    (see :mod:`repro.kernels.ops`). Expects an optimized graph (BN
    folded, activations fused); DAG merges and the non-kernel layers
    fall back to jnp ops (:func:`pallas_layer_plan`)."""
    from repro.kernels import ops
    assert x.ndim == 4
    vals: Dict[str, jnp.ndarray] = {}
    for layer in graph.layers:
        if isinstance(layer, Input):
            vals[layer.name] = x
            continue
        ins = [vals[n] for n in layer.inputs]
        xi = ins[0]
        if not _runs_as_kernel(layer):
            if isinstance(layer, (Dropout, BatchNorm, Dense, Flatten)):
                raise NotImplementedError(
                    f"run passes.optimize first ({type(layer).__name__})")
            y = _apply(layer, ins)
        elif isinstance(layer, Conv2D):
            act = layer.activation if layer.activation != "softmax" else None
            y = ops.conv2d(xi, jnp.asarray(layer.weights),
                           jnp.asarray(layer.bias), strides=layer.strides,
                           padding=layer.padding, act=act,
                           alpha=layer.alpha)
            if layer.activation == "softmax":
                y = jax.nn.softmax(y, axis=-1)
        else:
            y = ops.maxpool2d(xi, size=layer.size, strides=layer.strides)
        vals[layer.name] = y
    return vals[graph.sink.name]


def forward_quantized(qg, x: jnp.ndarray) -> jnp.ndarray:
    """Int8 reference forward — bit-faithful to the generated C.

    Every intermediate tensor is an int8 code (held as int32 here; the
    values are clipped to [-128, 127]), accumulation is exact int32,
    and requantization is ``floor(float32(acc) * M + 0.5) + zp`` — the
    identical IEEE-754 single-precision op sequence the C emits, so the
    integer path agrees with the compiled net *exactly*, not just
    within tolerance.  Input is float32 NHWC; output is the dequantized
    float32 result (softmax, when fused on the sink, runs in float).

    ``qg`` is a :class:`repro.core.quantize.QuantizedGraph`.
    """
    g = qg.graph
    assert x.ndim == 4, "expected NHWC batch"
    sink = g.sink
    smap = g.shape_map()
    half = jnp.float32(0.5)

    def affine_out(layer, acc, is_sink: bool):
        """Requantize an int32 accumulator of a weighted layer (or
        dequantize it, on the sink) — float32 multiplier path."""
        act = layer.activation
        if is_sink:
            t = acc.astype(jnp.float32) * jnp.asarray(
                qg.dequant_scales(layer))
            if act == "relu":
                t = jnp.where(t > 0, t, jnp.float32(0.0))
            elif act == "leaky_relu":
                t = jnp.where(t > 0, t, jnp.float32(layer.alpha) * t)
            elif act == "softmax":
                t = jax.nn.softmax(t, axis=-1)
            return t
        t = acc.astype(jnp.float32) * jnp.asarray(qg.requant_scales(layer))
        if act == "relu":
            t = jnp.where(t > 0, t, jnp.float32(0.0))
        elif act == "leaky_relu":
            t = jnp.where(t > 0, t, jnp.float32(layer.alpha) * t)
        cq = qg.channel_qp(layer.name)  # per-channel output zps, or None
        zp = (jnp.asarray(cq.zero_point, jnp.int32) if cq is not None
              else qg.out_qp(layer).zero_point)
        q = jnp.floor(t + half).astype(jnp.int32) + zp
        return jnp.clip(q, -128, 127)

    def requant_codes(layer, t):
        """float32 value (already in s_out units) -> int8 codes."""
        q = jnp.floor(t + half).astype(jnp.int32) \
            + qg.out_qp(layer).zero_point
        return jnp.clip(q, -128, 127)

    vals: Dict[str, jnp.ndarray] = {}
    for layer in g.layers:
        name = layer.name
        is_sink = layer is sink
        if isinstance(layer, Input):
            qp = qg.acts[name]
            t = x.astype(jnp.float32) * qp.inv_scale
            q = jnp.floor(t + half).astype(jnp.int32) + qp.zero_point
            vals[name] = jnp.clip(q, -128, 127)
            continue
        ins = [vals[n] for n in layer.inputs]
        qi = ins[0]
        in_shape = smap[layer.inputs[0]]
        if isinstance(layer, (Conv2D, DepthwiseConv2D)):
            lq = qg.weights[name]
            cin = qg.in_channel_qp(layer)
            zp_in = (jnp.asarray(cin.zero_point, jnp.int32)
                     if cin is not None  # eligibility forbids padding
                     else qg.in_qp(layer).zero_point)
            pt, pb, pl, pr = layer.pad_amounts(in_shape)
            xin = qi - zp_in  # zero-padded by conv == C's zp-code fill
            wq = jnp.asarray(lq.w_q, jnp.int32)
            if isinstance(layer, DepthwiseConv2D):
                wq = wq.reshape(layer.kh, layer.kw, 1, layer.c_out)
                acc = jax.lax.conv_general_dilated(
                    xin, wq, layer.strides, ((pt, pb), (pl, pr)),
                    dimension_numbers=_DIMS,
                    feature_group_count=layer.c_in)
            else:
                acc = jax.lax.conv_general_dilated(
                    xin, wq, layer.strides, ((pt, pb), (pl, pr)),
                    dimension_numbers=_DIMS)
            acc = acc + jnp.asarray(lq.b_q, jnp.int32)
            vals[name] = affine_out(layer, acc, is_sink)
        elif isinstance(layer, Dense):
            lq = qg.weights[name]
            cin = qg.in_channel_qp(layer)
            zp_in = (jnp.asarray(cin.zero_point, jnp.int32)
                     if cin is not None  # subtract over channels first,
                     else qg.in_qp(layer).zero_point)  # then flatten
            flat = (qi - zp_in).reshape(qi.shape[0], -1)
            acc = flat @ jnp.asarray(lq.w_q, jnp.int32) \
                + jnp.asarray(lq.b_q, jnp.int32)
            vals[name] = affine_out(
                layer, acc.reshape(acc.shape[0], 1, 1, -1), is_sink)
        elif isinstance(layer, MaxPool):
            # same qparams in/out (forced at calibration): pure int8 max;
            # the -128 init/pad value never wins (>=1 valid tap/window)
            pads = layer.pad_amounts(in_shape)
            vals[name] = _pool(qi, layer.size, layer.strides, jax.lax.max,
                               jnp.int32(-128), pads)
        elif isinstance(layer, AvgPool):
            zp_in = qg.in_qp(layer).zero_point
            pads = layer.pad_amounts(in_shape)
            acc = _pool(qi - zp_in, layer.size, layer.strides, jax.lax.add,
                        jnp.int32(0), pads)
            minv = qg.pool_scales(layer, in_shape)  # (oh, ow) float32
            t = acc.astype(jnp.float32) * jnp.asarray(minv)[None, :, :, None]
            vals[name] = requant_codes(layer, t)
        elif isinstance(layer, GlobalAvgPool):
            zp_in = qg.in_qp(layer).zero_point
            acc = jnp.sum(qi - zp_in, axis=(1, 2), keepdims=True,
                          dtype=jnp.int32)
            t = acc.astype(jnp.float32) * qg.pool_scales(layer, in_shape)
            vals[name] = requant_codes(layer, t)
        elif isinstance(layer, Add):
            t = (ins[0] - qg.in_qp(layer, 0).zero_point).astype(
                jnp.float32) * qg.rescale(layer, 0)
            for i in range(1, len(ins)):
                t = t + (ins[i] - qg.in_qp(layer, i).zero_point).astype(
                    jnp.float32) * qg.rescale(layer, i)
            if layer.activation == "relu":
                t = jnp.where(t > 0, t, jnp.float32(0.0))
            elif layer.activation == "leaky_relu":
                t = jnp.where(t > 0, t, jnp.float32(layer.alpha) * t)
            vals[name] = requant_codes(layer, t)
        elif isinstance(layer, Concat):
            parts = []
            for i, q in enumerate(ins):
                t = (q - qg.in_qp(layer, i).zero_point).astype(
                    jnp.float32) * qg.rescale(layer, i)
                parts.append(requant_codes(layer, t))
            vals[name] = jnp.concatenate(parts, axis=-1)
        elif isinstance(layer, ReLU):
            t = (qi - qg.in_qp(layer).zero_point).astype(
                jnp.float32) * qg.rescale(layer)
            t = jnp.where(t > 0, t, jnp.float32(0.0))
            vals[name] = requant_codes(layer, t)
        elif isinstance(layer, LeakyReLU):
            t = (qi - qg.in_qp(layer).zero_point).astype(
                jnp.float32) * qg.rescale(layer)
            t = jnp.where(t > 0, t, jnp.float32(layer.alpha) * t)
            vals[name] = requant_codes(layer, t)
        elif isinstance(layer, Softmax):
            assert is_sink, "standalone Softmax only supported as sink"
            qp = qg.in_qp(layer)
            deq = (qi - qp.zero_point).astype(jnp.float32) \
                * jnp.float32(qp.scale)
            vals[name] = jax.nn.softmax(deq, axis=-1)
        elif isinstance(layer, (Dropout, Flatten)):
            vals[name] = qi if isinstance(layer, Dropout) \
                else qi.reshape(qi.shape[0], 1, 1, -1)
        else:
            raise TypeError(
                f"forward_quantized: unhandled layer {type(layer).__name__}")
    return vals[sink.name]


def make_jit_forward_quantized(qg):
    """XLA-compiled int8 reference (the quantized parity oracle)."""

    @jax.jit
    def f(x):
        return forward_quantized(qg, x)

    return f


def extract_params(graph: CNNGraph) -> dict:
    """Trainable weights as a pytree keyed by layer name."""
    out = {}
    for layer in graph.layers:
        if isinstance(layer, (Conv2D, DepthwiseConv2D, Dense)):
            out[layer.name] = {"w": jnp.asarray(layer.weights),
                               "b": jnp.asarray(layer.bias)}
    return out


def insert_params(graph: CNNGraph, params: dict) -> CNNGraph:
    """Write trained weights back into a copy of the graph — the
    'trained Keras model' NNCG consumes, produced by our own trainer."""
    g = graph.copy()
    for layer in g.layers:
        if layer.name in params:
            layer.weights = np.asarray(params[layer.name]["w"], np.float32)
            layer.bias = np.asarray(params[layer.name]["b"], np.float32)
    return g


def forward_with_params(graph: CNNGraph, params: dict,
                        x: jnp.ndarray) -> jnp.ndarray:
    """Differentiable forward: like :func:`forward` but weights come from
    the ``params`` pytree (training path)."""
    import dataclasses as _dc
    layers = []
    for layer in graph.layers:
        if layer.name in params:
            layer = _dc.replace(layer, weights=params[layer.name]["w"],
                                bias=params[layer.name]["b"],
                                inputs=list(layer.inputs))
        layers.append(layer)
    return forward(CNNGraph(layers), x)


def predict(graph: CNNGraph, x: np.ndarray) -> np.ndarray:
    squeeze = x.ndim == 3
    if squeeze:
        x = x[None]
    y = make_jit_forward(graph)(jnp.asarray(x, dtype=jnp.float32))
    y = np.asarray(y)
    return y[0] if squeeze else y

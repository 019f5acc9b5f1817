"""Weights, frames and the program's graph for one run.

A configuration file (``bench/configs/<name>.json``) lists the paper's
layers at their published shapes. The benchmark draws every weight here,
on the host (the robot net has 6,296 parameters: a few milliseconds
of numpy), and hands the same arrays to the program (as a ``CNNGraph``)
and to the plain reference (``bench/reference.py``).

The weights come from the configuration's own ``weights_seed``, like
the checkpoint of a deployment, and not from ``--seed``: the program
compiles its weights into the program as constants (the paper's P3), so
a new set of weights is a new program, and weights drawn per run would
put a whole compile into every run's set-up. Frames, the traffic's
order and arrivals, and the sample that is checked come from ``--seed``.
"""
from __future__ import annotations

import numpy as np

# independent streams of one seed
_WEIGHTS, _FRAMES, _TRAFFIC = 1, 2, 3


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def make_weights(cfg: dict) -> list:
    """One dict of float32 arrays per layer of ``cfg["layers"]``, drawn
    from ``cfg["weights_seed"]``."""
    r = rng(cfg["weights_seed"], _WEIGHTS)
    c = cfg["input_shape"][2]
    out = []
    for layer in cfg["layers"]:
        kind = layer["kind"]
        p = {}
        if kind == "conv":
            kh, kw = layer["kernel"]
            co = layer["c_out"]
            std = np.sqrt(2.0 / (kh * kw * c))
            p["w"] = r.normal(0.0, std, (kh, kw, c, co)).astype(np.float32)
            p["b"] = r.normal(0.0, 0.01, co).astype(np.float32)
            c = co
        elif kind == "batchnorm":
            p["mean"] = r.normal(0.0, 0.5, c).astype(np.float32)
            p["var"] = r.uniform(0.5, 1.5, c).astype(np.float32)
            p["gamma"] = r.uniform(0.8, 1.2, c).astype(np.float32)
            p["beta"] = r.normal(0.0, 0.1, c).astype(np.float32)
        out.append(p)
    return out


def program_graph(cfg: dict, weights: list):
    """The configuration as the program's ``CNNGraph`` (unoptimized:
    the session folds batch norm and fuses activations itself)."""
    from repro.core.graph import (BatchNorm, CNNGraph, Conv2D, Dropout,
                                  Input, LeakyReLU, MaxPool, ReLU, Softmax)

    layers = [Input(shape=tuple(cfg["input_shape"]))]
    for layer, p in zip(cfg["layers"], weights):
        kind = layer["kind"]
        if kind == "conv":
            layers.append(Conv2D(weights=p["w"], bias=p["b"],
                                 padding=layer["padding"]))
        elif kind == "batchnorm":
            layers.append(BatchNorm(mean=p["mean"], var=p["var"],
                                    gamma=p["gamma"], beta=p["beta"],
                                    eps=layer["eps"]))
        elif kind == "relu":
            layers.append(ReLU())
        elif kind == "leaky_relu":
            layers.append(LeakyReLU(alpha=layer["alpha"]))
        elif kind == "maxpool":
            layers.append(MaxPool(size=tuple(layer["size"])))
        elif kind == "dropout":
            layers.append(Dropout(rate=layer["rate"]))
        elif kind == "softmax":
            layers.append(Softmax())
        else:
            raise ValueError(f"unknown layer kind {kind!r}")
    return CNNGraph(layers)


def camera_frames(n: int, shape, seed: int, *, blur_passes: int = 2,
                  blur_k: int = 5) -> np.ndarray:
    """Camera-like frames: spatially smooth, bounded [0, 1], with
    per-frame gain and offset. A copy of the generator the program keeps
    in ``repro.data.pipeline.camera_frame_batch``, so that a change there
    cannot change the benchmark's inputs."""
    r = rng(seed, _FRAMES)
    h, w, c = shape
    imgs = r.uniform(0, 1, (n, h, w, c)).astype(np.float32)
    half = blur_k // 2
    for _ in range(blur_passes):
        # separable box blur by padded cumulative sums
        s = np.cumsum(np.pad(imgs, ((0, 0), (half + 1, half), (0, 0),
                                    (0, 0)), mode="edge"), axis=1)
        imgs = (s[:, blur_k:] - s[:, :-blur_k]) / blur_k
        s = np.cumsum(np.pad(imgs, ((0, 0), (0, 0), (half + 1, half),
                                    (0, 0)), mode="edge"), axis=2)
        imgs = (s[:, :, blur_k:] - s[:, :, :-blur_k]) / blur_k
    mn = imgs.min(axis=(1, 2, 3), keepdims=True)
    mx = imgs.max(axis=(1, 2, 3), keepdims=True)
    imgs = (imgs - mn) / np.maximum(mx - mn, 1e-6)
    gain = r.uniform(0.6, 1.0, (n, 1, 1, 1)).astype(np.float32)
    offset = r.uniform(0.0, 0.3, (n, 1, 1, 1)).astype(np.float32)
    return np.clip(imgs * gain + offset, 0.0, 1.0).astype(np.float32)


def traffic_rng(seed: int) -> np.random.Generator:
    return rng(seed, _TRAFFIC)

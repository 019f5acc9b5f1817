"""Operations and bytes of each layer, from its logical shapes.

The count is of the work the layer needs, whatever implements it:
unpadded input, weights, bias and output, all float32 (4 bytes), each
read or written once. A conv counts 2 operations per multiply-add, and
its bias and activation none; a max pool counts one compare per window
element after the first. Batch norm, activations and dropout are folded
or fused away by the program and count nothing here.
"""
from __future__ import annotations

F32 = 4


def layer_work(cfg: dict, batch: int) -> list:
    """Per layer of ``cfg["layers"]``: ``{"kind", "flops", "bytes",
    "out_shape"}`` for one call on ``batch`` frames."""
    h, w, c = cfg["input_shape"]
    out = []
    for layer in cfg["layers"]:
        kind = layer["kind"]
        flops = nbytes = 0
        if kind == "conv":
            kh, kw = layer["kernel"]
            co = layer["c_out"]
            oh, ow = (h, w) if layer["padding"] == "same" else (
                h - kh + 1, w - kw + 1)
            flops = 2 * batch * oh * ow * kh * kw * c * co
            nbytes = F32 * (batch * (h * w * c + oh * ow * co)
                            + kh * kw * c * co + co)
            h, w, c = oh, ow, co
        elif kind == "maxpool":
            kh, kw = layer["size"]
            oh, ow = (h - kh) // kh + 1, (w - kw) // kw + 1
            flops = batch * oh * ow * c * (kh * kw - 1)
            nbytes = F32 * batch * (h * w * c + oh * ow * c)
            h, w = oh, ow
        out.append({"kind": kind, "flops": flops, "bytes": nbytes,
                    "out_shape": (h, w, c)})
    return out


def forward_flops(cfg: dict) -> int:
    """Operations of one frame through the net, as model-FLOP
    utilization counts them: the conv layers' multiply-adds."""
    return sum(l["flops"] for l in layer_work(cfg, 1) if l["kind"] == "conv")


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The roofline: the larger of compute time and memory time."""
    return max(flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])

"""Operation and byte counts of the benchmark's nets, and the peak
table, against hand counts."""
import json
from pathlib import Path

import pytest

from bench import counts, peaks

ROOT = Path(__file__).resolve().parents[2]


def cfg(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())


@pytest.mark.parametrize("name,flops", [
    # robot: 2*(4800*27*8 + 1200*72*12 + 1200*108*8 + 300*72*16
    #           + 300*144*20)
    ("robot", 8_640_000),
    # pedestrian: 2*(648*9*12 + 162*108*32 + 36*288*64 + 1*512*2)
    ("pedestrian", 2_588_864),
])
def test_forward_flops_match_hand_count(name, flops):
    assert counts.forward_flops(cfg(name)) == flops


def test_robot_layers_by_hand():
    work = counts.layer_work(cfg("robot"), batch=1)
    conv1, pool1 = work[0], work[3]
    assert conv1["kind"] == "conv" and pool1["kind"] == "maxpool"
    assert conv1["flops"] == 2 * 60 * 80 * 3 * 3 * 3 * 8
    # input 60x80x3, output 60x80x8, weights 3x3x3x8, bias 8, float32
    assert conv1["bytes"] == 4 * (14_400 + 38_400 + 216 + 8)
    # 30x40x8 outputs, 3 compares each; reads 60x80x8, writes 30x40x8
    assert pool1["flops"] == 30 * 40 * 8 * 3
    assert pool1["bytes"] == 4 * (38_400 + 9_600)
    assert work[-1]["out_shape"] == (15, 20, 20)


def test_pedestrian_shapes_and_floor_pooling():
    work = counts.layer_work(cfg("pedestrian"), batch=1)
    pools = [w for w in work if w["kind"] == "maxpool"]
    # 36x18 -> 18x9 -> 9x4 -> 4x2: valid pooling floors odd sizes
    assert [p["out_shape"] for p in pools] == [(18, 9, 12), (9, 4, 32),
                                               (4, 2, 64)]
    assert pools[2]["flops"] == 4 * 2 * 64 * 3
    assert work[-1]["out_shape"] == (1, 1, 2)


def test_batch_scales_activations_not_weights():
    one = counts.layer_work(cfg("robot"), batch=1)[0]
    many = counts.layer_work(cfg("robot"), batch=256)[0]
    weights = 4 * (216 + 8)
    assert many["flops"] == 256 * one["flops"]
    assert many["bytes"] - weights == 256 * (one["bytes"] - weights)


def test_least_seconds_takes_the_binding_bound():
    peak = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert counts.least_seconds(50, 100, peak) == 10.0   # memory-bound
    assert counts.least_seconds(5000, 100, peak) == 50.0  # compute-bound


def test_peak_table_has_the_v5e():
    p = peaks.peak("TPU v5 lite")
    assert p["flops_per_s"] == 1.97e14
    assert p["hbm_bytes_per_s"] == 8.19e11


def test_peak_table_refuses_an_unknown_device():
    with pytest.raises(KeyError, match="no peaks"):
        peaks.peak("TPU v99 imaginary")

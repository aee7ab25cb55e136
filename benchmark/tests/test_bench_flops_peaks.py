"""Model FLOPs against counts worked out by hand; the table of peaks."""

import importlib
import json
import os

import pytest

import peaks

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def flops(config_name, **cell_args):
    c = config(config_name)
    fn = importlib.import_module("flops." + c["flops"]).flops_per_example
    return fn(**dict(c["build_args"], **cell_args))


@pytest.mark.parametrize("seq_len", [256, 2048])
def test_transformer_base_by_hand(seq_len):
    # weights a position passes through: 6 encoder layers of 4*512^2 +
    # 2*512*2048 = 3,145,728; 6 decoder layers of 8*512^2 + 2*512*2048 =
    # 4,194,304; the output projection 512*30000 = 15,360,000
    weights = 6 * 3_145_728 + 6 * 4_194_304 + 15_360_000
    assert weights == 59_400_192
    # attention: per block 2 products of T*T*512 multiply-adds; 6 encoder
    # self + 6 cross + 6 causal decoder self at one half = 15 blocks
    t = seq_len
    forward = 2 * weights * t + 15 * 2 * 2 * t * t * 512
    got = flops("transformer_base", seq_len=seq_len)
    assert got["forward"] == forward
    assert got["forward_backward"] == 3 * forward
    per_position = got["forward_backward"] / t / 1e9
    assert per_position == pytest.approx({256: 0.380, 2048: 0.545}[seq_len],
                                         abs=5e-4)


def test_resnet50_by_hand():
    # multiply-adds of ResNet-50 at 224x224, stride in the first 1x1:
    stem = 112 * 112 * 3 * 64 * 49                         # 7x7/2
    s1_first = 56 * 56 * (64 * 256 + 64 * 64 + 64 * 64 * 9 + 64 * 256)
    s1_rest = 56 * 56 * (256 * 64 + 64 * 64 * 9 + 64 * 256)
    total = stem + s1_first + 2 * s1_rest
    hw, c_in = 56, 256
    for ch, count in ((128, 4), (256, 6), (512, 3)):
        hw //= 2
        first = hw * hw * (c_in * 4 * ch + c_in * ch + ch * ch * 9
                           + ch * 4 * ch)
        rest = hw * hw * (4 * ch * ch + ch * ch * 9 + ch * 4 * ch)
        total += first + (count - 1) * rest
        c_in = 4 * ch
    total += 2048 * 1000                                    # the classifier
    got = flops("resnet50")
    assert got["forward"] == 2 * total
    assert got["forward_backward"] == 6 * total
    assert got["forward"] / 2 / 1e9 == pytest.approx(3.86, abs=0.01)


def test_peaks_known_and_unknown_kind():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="TPU v9"):
        peaks.peaks_for("TPU v9")
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")

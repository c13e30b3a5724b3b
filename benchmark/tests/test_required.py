"""What a step requires, against values worked out by hand."""
import json
import os

import pytest

from benchmark import harness

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cfg(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


def test_biglstm_parameter_count():
    cfg = _cfg("biglstm-lm1b-f32")
    m = harness.load_module("configs", "biglstm-lm1b-f32")
    v = 793471
    layer = 2 * 32768 * 1024 + 2 * 32768 + 1024 * 8192      # 75,563,008
    want = v * 1024 + 2 * layer + v * 1024 + v
    assert want == 1_776_948_095
    assert m.param_count(cfg) == want
    assert m.matmul_param_count(cfg) == 2 * (layer - 2 * 32768) + v * 1024
    assert m.param_count(cfg) * 4 / 1e9 == pytest.approx(7.1, abs=0.05)


def test_biglstm_step_is_bound_by_the_weight_read():
    cfg = _cfg("biglstm-lm1b-f32")
    m = harness.load_module("configs", "biglstm-lm1b-f32")
    need = m.step_required(cfg, 128, 128)
    assert need["flops"] == 2.0 * 963_509_248 * 128
    assert need["bytes"] / 1e9 == pytest.approx(3.88, abs=0.02)
    peaks = harness.load_json("peaks.json")["device_kinds"]["TPU v5 lite"]
    assert need["bytes"] / peaks["hbm_bytes_per_s"] \
        > 3 * need["flops"] / peaks["flops_per_s_bf16"]


def test_resnet50_forward_flops_and_parameters():
    cfg = _cfg("resnet50-imagenet-bf16")
    m = harness.load_module("configs", "resnet50-imagenet-bf16")
    need = m.step_required(cfg, 1)
    # the stem alone: 112 x 112 outputs x 64 filters x (7 x 7 x 3) taps
    stem = 2 * 112 * 112 * 64 * 147
    assert stem == 236_027_904
    # 4.09 G multiply-adds a 224 x 224 image is the figure every table
    # of ResNet-50 gives
    assert need["forward_flops"] / 2 / 1e9 == pytest.approx(4.09, abs=0.01)
    assert need["flops"] == 3 * need["forward_flops"]
    assert m.param_count(cfg) == 25_549_486 \
        and m.param_count(cfg) / 1e6 == pytest.approx(25.5, abs=0.1)
    # bytes grow with the batch, the weights' share does not
    b1, b2 = need["bytes"], m.step_required(cfg, 2)["bytes"]
    assert b2 - b1 == pytest.approx(b1 - 6 * 4 * m.param_count(cfg))

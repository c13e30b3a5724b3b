"""The control of each configuration, the plain reference put in the
program's place and computed in the precision below the one the
configuration states (bfloat16 weights, state and activations for the
float32 LSTM; float8 operands for ResNet-50, which is held to
bfloat16-grade arithmetic), comes out as not correct under the limits
the cells are held to, at a size a test can hold; so does half the batch
left out.  (On the chip, at the cells' own sizes:
PERF.md section 2.)"""
import numpy as np
import pytest

from benchmark import harness


def _limits(cell):
    return harness.load_json("correct", cell + ".json")["limits"]


@pytest.mark.parametrize("seed", [3000000051, 7, 8])
def test_bfloat16_control_of_the_lstm_is_not_correct(seed):
    conf = harness.load_module("configs", "biglstm-lm1b-f32")
    ref = harness.load_module("reference", "biglstm-lm1b-f32")
    cfg = {"vocab_size": 5000, "embed_dim": 32, "num_layers": 2,
           "lstm_cells": 64, "proj_dim": 32}
    params = conf.init_params(cfg, seed)
    rng = np.random.default_rng(seed)
    requests = [(rng.integers(1, 5000, 8).tolist(),
                 rng.integers(1, 5000, 56).tolist()) for _ in range(8)]
    got = ref.served_gaps(params, cfg, requests, width=64,
                          precision="bfloat16")
    for cell in ("biglstm-decode-saturated", "biglstm-decode-steady"):
        assert float(got["gaps"].max()) \
            > _limits(cell)["served_token_gap_max"]


def test_fp8_control_of_resnet_training_is_not_correct():
    fit = harness.load_module("drivers", "fit")
    conf = harness.load_module("configs", "resnet50-imagenet-bf16")
    ref = harness.load_module("reference", "resnet50-imagenet-bf16")
    limits = _limits("resnet50-train-b256")
    cfg = {"num_layers": 50, "image_size": 64, "num_classes": 10}
    args, _aux = conf.init_params(cfg, 3000000053)
    data, label = conf.make_batch(cfg, 3000000053, 16)
    sound = ref.first_steps(args, data, label, 1e-4, 0.9)
    control = ref.first_steps(args, data, label, 1e-4, 0.9, precision="fp8")
    assert fit.compare(sound, sound, limits).correct
    assert not fit.compare(control, sound, limits).correct
    half = ref.first_steps(args, data, label, 1e-4, 0.9, keep=0.5)
    assert not fit.compare(half, sound, limits).correct

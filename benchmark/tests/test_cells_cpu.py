"""Each cell end to end at a tiny size on the CPU, through the override
only tests can reach: the result line has the contract's keys, a device
metric is refused, and a timed path broken underneath comes out with
``correct`` false.  The look for a chip is all that is skipped."""
import contextlib
import io
import json

import numpy as np
import pytest

from benchmark import run

CPU = {"platform": "cpu", "device_kind": "TPU v5 lite"}
# 16 images of 64 x 64 leave the last stage's batch norm 64 positions a
# channel: float32 against float32 reads gaps near 1e-3 there, which the
# cell's own limits, set at 256 images of 224 x 224, do not allow for
RESNET = dict(CPU, config={"image_size": 64, "num_classes": 10},
              traffic={"batch": 16, "learning_rate": 0.0001},
              correct={"limits": {
                  "loss_step2_rel_gap": 0.02, "loss_step3_rel_gap": 0.02,
                  "grad1_median_leaf_gap": 0.02,
                  "delta_median_leaf_gap": 0.02}})
LSTM = dict(CPU, config={"vocab_size": 500, "embed_dim": 32,
                         "lstm_cells": 64, "proj_dim": 32})
LENGTHS = {"prompt_len": {"dist": "lognormal", "median": 6, "sigma": 0.8,
                          "min": 2, "max": 16},
           "new_tokens": {"dist": "lognormal", "median": 12, "sigma": 0.7,
                          "min": 4, "max": 40}}
ENGINE = {"engine": {"num_slots": 4, "max_len": 64, "max_queue": 64}}
# the four-chip cell is not in BENCHMARK.json yet (PERF.md section 7);
# the plan's path through the ``fit`` driver is held here meanwhile, on
# four virtual devices, under the one-chip cell's limits and metrics
DP4 = {"name": "resnet50-train-dp4-b1024",
       "config": "resnet50-imagenet-bf16", "traffic": "fit-dp4-b1024",
       "chips": 4, "why": "tests only"}
OVERRIDES = {
    "resnet50-train-b256": RESNET,
    "resnet50-train-dp4-b1024": dict(
        RESNET, workloads=[DP4], like="resnet50-train-b256"),
    "biglstm-decode-saturated": dict(
        LSTM, traffic=dict(LENGTHS, clients=8, **ENGINE)),
    "biglstm-decode-steady": dict(
        LSTM, traffic=dict(
            LENGTHS, arrivals={"process": "exponential_stratified",
                               "rate_per_s": 30.0}, **ENGINE)),
}


def drive(cell, seed=3000000029, seconds=1.0, trace=0):
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                       str(seconds), "--trace", str(trace)],
                      overrides=OVERRIDES[cell])
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None), err.getvalue()


@pytest.mark.parametrize("cell", sorted(OVERRIDES))
def test_cell_end_to_end(cell):
    rc, res, err = drive(cell)
    assert rc == 0, err
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert res["correct"] is True, err
    assert res["attempted"] > 0 and res["failed"] == 0
    assert "setup_s" in res["metrics"] and len(res["metrics"]) >= 2
    for m in res["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert set(res["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert res["device"]["count"] == (4 if "dp4" in cell else 1)
    # each number compared is printed beside its limit, last on stderr
    tail = [l for l in err.strip().splitlines() if l.startswith("check ")]
    assert len(tail) == len(res["checks"]) >= 3
    assert err.strip().splitlines()[-1].startswith("check ")


def test_a_device_metric_is_refused_off_the_chip():
    rc, res, err = drive("biglstm-decode-saturated", trace=1)
    assert rc != 0 and res is None and "only a TPU" in err


def test_no_chip_no_result():
    out = io.StringIO()
    with contextlib.redirect_stdout(out), \
            contextlib.redirect_stderr(io.StringIO()):
        rc = run.main(["--workload", "biglstm-decode-saturated", "--seed",
                       "1", "--seconds", "1", "--trace", "0"])
    assert rc != 0 and out.getvalue() == ""
    with contextlib.redirect_stdout(out), \
            contextlib.redirect_stderr(io.StringIO()):
        rc = run.main(["--workload", "biglstm-decode-saturated", "--seed",
                       "1", "--seconds", "1", "--trace", "0"],
                      overrides=dict(OVERRIDES["biglstm-decode-saturated"],
                                     device_kind="TPU v9 imaginary"))
    assert rc != 0 and out.getvalue() == ""


# ---- the timed path broken underneath

def test_altered_token_is_not_correct(monkeypatch):
    """A token altered where it is produced: every 7th step the sampled
    id of slot 0 is moved by one."""
    from mxnet_tpu.serving import decode
    inner = decode.StepProgram.step
    calls = [0]

    def step(self, tokens, pos, valid, states, reset=None):
        sampled, new_states = inner(self, tokens, pos, valid, states,
                                    reset=reset)
        calls[0] += 1
        if calls[0] % 7 == 0:
            sampled = np.array(sampled)
            sampled[0] = (sampled[0] + 1) % 500
        return sampled, new_states
    monkeypatch.setattr(decode.StepProgram, "step", step)
    rc, res, err = drive("biglstm-decode-saturated", seconds=2.0)
    assert rc == 0 and res["correct"] is False, err
    gap = res["checks"]["served_token_gap_max"]
    assert gap["value"] > gap["limit"]


def test_inherited_rows_are_not_correct(monkeypatch):
    """A joining request that inherits the previous occupant's rows: the
    reset vector is dropped on the way into the step."""
    from mxnet_tpu.serving import decode
    inner = decode.StepProgram.step
    monkeypatch.setattr(
        decode.StepProgram, "step",
        lambda self, tokens, pos, valid, states, reset=None: inner(
            self, tokens, pos, valid, states, reset=None))
    rc, res, err = drive("biglstm-decode-saturated", seconds=2.0)
    assert rc == 0 and res["correct"] is False, err


def _fit_fault(monkeypatch, fault):
    import mxnet_tpu as mx
    from mxnet_tpu.ndarray.ndarray import _wrap
    if fault == "state_unchanged":
        monkeypatch.setattr(mx.mod.Module, "update", lambda self: None)
        return
    keep = {"half_batch": 2, "no_exchange": 4}[fault]
    inner = mx.mod.Module.forward_backward

    def forward_backward(self, batch):
        # the first 1/keep of the rows stand in for all: the mean and
        # the batch statistics are taken over them alone
        def first(a):
            rows = a._data[:a.shape[0] // keep]
            import jax.numpy as jnp
            return _wrap(jnp.concatenate([rows] * keep), a.context)
        return inner(self, mx.io.DataBatch(
            data=[first(a) for a in batch.data],
            label=[first(a) for a in batch.label]))
    monkeypatch.setattr(mx.mod.Module, "forward_backward", forward_backward)


@pytest.mark.parametrize("cell,fault", [
    ("resnet50-train-b256", "state_unchanged"),
    ("resnet50-train-b256", "half_batch"),
    ("resnet50-train-dp4-b1024", "no_exchange"),
])
def test_fit_fault_is_not_correct(monkeypatch, cell, fault):
    _fit_fault(monkeypatch, fault)
    rc, res, err = drive(cell)
    assert rc == 0 and res["correct"] is False, err

"""Static memory planner tests (mxnet_tpu/analysis/memory.py).

Coverage per the issue contract: hand-computed liveness units on a
graph small enough to price by hand (alias ops cost zero bytes),
predicted peak vs XLA's own ``memory_analysis()`` on the model-zoo
exemplars (tolerance pinned at 25%), the donation soundness gate
(library verdict + a seeded-unsound spec refused at DecodeEngine
construction with the violating node named), bitwise-identical
serving with the planner on vs off at zero warm retraces, the OOM
preflight (impossible slot-pool config warns — strict raises —
naming the program and bytes BEFORE any compile), the stats()/gauge
surface, and ``graph_lint --memory``'s exit contract.
"""
import json
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.analysis import (AnalysisError, check_donation,
                                plan_memory, predict_peak_bytes)
from mxnet_tpu.serving import DecodeEngine, ServingEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# model builders
# ---------------------------------------------------------------------------

def _sum_step(vocab=16, d=8, seed=0, sound=True):
    """Additive-state decode step: s' = s + emb(token); logits over
    s' (sound: every read of s is ordered before its aliasing write)
    or over the RAW s (unsound: out_fc reads the donated buffer via a
    node not ordered before the in-place next-state write)."""
    tok = mx.sym.Variable("token")
    s = mx.sym.Variable("s")
    emb = mx.sym.Embedding(tok, input_dim=vocab, output_dim=d,
                           name="emb")
    s2 = s + emb
    logits = mx.sym.FullyConnected(s2 if sound else s, num_hidden=vocab,
                                   name="out_fc")
    rng = np.random.default_rng(seed)
    params = {
        "emb_weight": mx.nd.array(
            rng.standard_normal((vocab, d)).astype(np.float32)),
        "out_fc_weight": mx.nd.array(
            rng.standard_normal((vocab, d)).astype(np.float32)),
        "out_fc_bias": mx.nd.zeros((vocab,)),
    }
    return mx.sym.Group([logits, s2]), params, \
        [{"name": "s", "shape": (d,)}]


def _zoo(name):
    if name == "mlp":
        from mxnet_tpu.models.lenet import get_mlp
        return get_mlp(), {"data": (8, 784)}
    if name == "lenet":
        from mxnet_tpu.models.lenet import get_lenet
        return get_lenet(), {"data": (8, 1, 28, 28)}
    from mxnet_tpu.models.resnet import get_resnet_symbol
    return get_resnet_symbol(num_classes=10, num_layers=18,
                             image_shape=(3, 32, 32)), \
        {"data": (4, 3, 32, 32)}


# ---------------------------------------------------------------------------
# liveness units, by hand
# ---------------------------------------------------------------------------

def test_liveness_watermark_hand_computed():
    """data(4,8)=128B -> fc1(16)=256B out -> relu=256B out.
    Params: weight 512B + bias 64B = 576B.  Arguments stay resident
    (128B floor); fc1's output dies once relu consumes it, so the
    transient high-water is 128+256+256=640B at the relu node, and
    the program peak is params + transient = 1216B."""
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=16,
                                name="fc1")
    net = mx.sym.Activation(net, act_type="relu", name="relu1")
    plan, report = plan_memory(net, {"data": (4, 8)})
    assert not report.errors
    assert plan["param_bytes"] == 576
    assert plan["input_bytes"] == 128
    assert plan["output_bytes"] == 256
    assert plan["transient_peak_bytes"] == 640
    assert plan["peak_bytes"] == 1216
    assert predict_peak_bytes(net, {"data": (4, 8)}) == 1216


def test_alias_ops_cost_zero_bytes():
    """Reshape is metadata-only under XLA: the planner prices its
    output at 0 new bytes, so a pure reshape program peaks at exactly
    its input."""
    r = mx.sym.Reshape(mx.sym.Variable("x"), shape=(8, 4), name="rs")
    plan, _report = plan_memory(r, {"x": (4, 8)})
    assert plan["peak_bytes"] == 128
    assert plan["transient_peak_bytes"] == 128


def test_sharded_bytes_divide_along_plan_axes():
    """Under a batch-partitioning plan the activations halve; params
    (unmatched by any rule) replicate."""
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=16,
                                name="fc1")
    spec = {"axes": {"dp": 2}, "batch_axis": "dp"}
    plain, _r1 = plan_memory(net, {"data": (4, 8)})
    shard, _r2 = plan_memory(net, {"data": (4, 8)}, sharding=spec)
    assert shard["sharded"] and not plain["sharded"]
    assert shard["param_bytes"] == plain["param_bytes"]
    assert shard["input_bytes"] == plain["input_bytes"] // 2
    assert shard["transient_peak_bytes"] \
        < plain["transient_peak_bytes"]


# ---------------------------------------------------------------------------
# predicted peak vs XLA memory_analysis (the calibration pin)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["mlp", "lenet", "resnet18"])
def test_predicted_peak_within_25pct_of_xla(name):
    """The planner's watermark vs the CPU compiler's memory_analysis()
    for the same inference program (arguments + outputs + temporaries).

    What the CPU compiler can hold: the resident part (params + inputs +
    outputs) must agree with XLA's arguments + outputs to 2%, and the
    whole prediction may exceed XLA's total by at most 25% — a planner
    regression that double-counts or leaks liveness blows well past
    that.  The two-sided 25% pin runs against the v5e compiler, the
    number the preflight exists to predict
    (tests/test_chip_compile.py::test_planner_peak_within_25pct_of_v5e):
    the installed XLA:CPU (jax 0.9.0) counts a repacked copy of the
    convolution weights among its temporaries (resnet18: 47.7 MB of
    temp beside 44.8 MB of arguments, against a planned transient of
    3.2 MB; the planner's side is a function of the shapes and did not
    move), so from below the CPU total says nothing about the
    planner."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.executor import build_graph_fn

    net, shapes = _zoo(name)
    plan, report = plan_memory(net, shapes)
    assert plan is not None and not report.errors

    arg_names = net.list_arguments()
    aux_names = net.list_auxiliary_states()
    g = build_graph_fn(net, arg_names, aux_names)
    arg_shapes, _, aux_shapes = net.infer_shape(**shapes)
    rng = np.random.RandomState(0)
    args = tuple(jnp.asarray(rng.randn(*s).astype(np.float32))
                 for s in arg_shapes)
    auxs = tuple(jnp.asarray(rng.randn(*s).astype(np.float32))
                 for s in aux_shapes)
    ma = jax.jit(lambda a, x: g(a, x, None, False)[0]) \
        .lower(args, auxs).compile().memory_analysis()
    resident = ma.argument_size_in_bytes + ma.output_size_in_bytes
    xla = resident + ma.temp_size_in_bytes
    planned = (plan["param_bytes"] + plan["input_bytes"]
               + plan["output_bytes"])
    assert resident > 0
    assert abs(planned - resident) / resident < 0.02, \
        "planner resident %d vs XLA %d" % (planned, resident)
    assert resident <= plan["peak_bytes"] < 1.25 * xla, \
        "planner %d vs XLA %d (resident %d)" % (plan["peak_bytes"], xla,
                                                resident)


# ---------------------------------------------------------------------------
# donation soundness gate
# ---------------------------------------------------------------------------

def test_donation_sound_spec_accepted():
    step, _params, _si = _sum_step(sound=True)
    check = check_donation(step, {"token": (4,), "s": (4, 8)},
                           {"s": 1})
    assert check.accepted
    assert check.per_input["s"]["sound"]


def test_donation_unsound_spec_rejected_naming_node():
    """out_fc reads the raw state s but is not ordered before s's
    aliasing next-state write: the in-place update could clobber the
    buffer before its last read.  The verdict pins the violating
    node by name."""
    step, _params, _si = _sum_step(sound=False)
    check = check_donation(step, {"token": (4,), "s": (4, 8)},
                           {"s": 1})
    assert not check.accepted
    assert check.per_input["s"]["node"] == "out_fc"
    assert "out_fc" in check.reasons[0]


def test_donation_shape_mismatch_rejected():
    # a donated input whose bytes differ from the output's cannot
    # alias it, whatever the ordering says
    tok = mx.sym.Variable("token")
    s = mx.sym.Variable("s")
    emb = mx.sym.Embedding(tok, input_dim=16, output_dim=8, name="emb")
    logits = mx.sym.FullyConnected(s + emb, num_hidden=16,
                                   name="out_fc")
    g = mx.sym.Group([logits, s + emb])
    check = check_donation(g, {"token": (4,), "s": (4, 8)},
                           {"token": 1})
    assert not check.accepted


# ---------------------------------------------------------------------------
# engine preflight: refusal, budget, bitwise parity
# ---------------------------------------------------------------------------

def test_decode_engine_refuses_unsound_donation(monkeypatch):
    step, params, si = _sum_step(sound=False)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        eng = DecodeEngine(step, params, {}, si, num_slots=2,
                           max_len=8, ctx=mx.cpu(), start=False)
        eng.close()
    msgs = [str(x.message) for x in w]
    hits = [m for m in msgs if "UNSOUND" in m]
    assert hits and "out_fc" in hits[0]
    # strict refuses construction outright
    monkeypatch.setenv("MXNET_ANALYSIS_STRICT", "1")
    with pytest.raises(AnalysisError, match="out_fc"):
        DecodeEngine(step, params, {}, si, num_slots=2, max_len=8,
                     ctx=mx.cpu(), start=False)


def test_decode_engine_oom_preflight_names_program_and_bytes(
        monkeypatch):
    """An impossible slot-pool config is priced BEFORE any compile:
    the warning names the offending program and the bytes, carries
    the max-slots-that-fit advisory, and strict mode raises."""
    step, params, si = _sum_step(sound=True)
    monkeypatch.setenv("MXNET_MEMORY_BUDGET_BYTES", "256")
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        eng = DecodeEngine(step, params, {}, si, num_slots=4,
                           max_len=8, ctx=mx.cpu(), start=False)
        # priced at construction, before any compile
        assert eng.compile_count == 0
        mem = eng.stats()["decode"]["memory"]
        eng.close()
    msgs = [str(x.message) for x in w]
    hit = [m for m in msgs if "memory preflight" in m]
    assert hit
    assert "'step'" in hit[0] and "slots fit" in hit[0]
    assert "B" in hit[0]                       # formatted bytes
    assert mem["budget_ok"] is False
    assert mem["budget_bytes"] == 256
    assert mem["max_slots_fit"] is not None
    monkeypatch.setenv("MXNET_ANALYSIS_STRICT", "1")
    with pytest.raises(AnalysisError, match="memory preflight"):
        DecodeEngine(step, params, {}, si, num_slots=4, max_len=8,
                     ctx=mx.cpu(), start=False)


def test_serving_engine_oom_preflight_warns(monkeypatch):
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=16,
                                name="fc1")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    rng = np.random.default_rng(0)
    params = {"fc1_weight": mx.nd.array(
        rng.standard_normal((16, 6)).astype(np.float32)),
        "fc1_bias": mx.nd.zeros((16,))}
    monkeypatch.setenv("MXNET_MEMORY_BUDGET_BYTES", "64")
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        eng = ServingEngine(net, params, {}, {"data": (6,)},
                            ctx=mx.cpu(), start=False)
        mem = eng.stats()["memory"]
        eng.close()
    msgs = [str(x.message) for x in w]
    assert any("memory preflight" in m and "budget is 64B" in m
               for m in msgs)
    assert mem["budget_ok"] is False
    assert mem["offender"] in {p["program"] for p in mem["programs"]}


def test_decode_bitwise_identical_planner_on_vs_off(monkeypatch):
    """The planner only diagnoses: same tokens, zero warm retraces,
    with MXNET_MEMORY_PLAN on vs off."""
    def run(enabled):
        monkeypatch.setenv("MXNET_MEMORY_PLAN",
                           "1" if enabled else "0")
        step, params, si = _sum_step(sound=True)
        eng = DecodeEngine(step, params, {}, si, num_slots=2,
                           max_len=8, ctx=mx.cpu())
        try:
            eng.warmup()
            warm = eng.compile_count
            toks = [eng.submit([t], max_new_tokens=4)
                    .result(timeout=60).tokens for t in (1, 5, 9)]
            assert eng.compile_count == warm, "warm retrace"
            assert (eng.memory_plan is not None) == enabled
            return toks
        finally:
            eng.close()

    on, off = run(True), run(False)
    assert len(on) == len(off)
    for a, b in zip(on, off):
        assert np.array_equal(a, b)


def test_memory_stats_block_and_digest():
    step, params, si = _sum_step(sound=True)
    eng = DecodeEngine(step, params, {}, si, num_slots=2, max_len=8,
                       ctx=mx.cpu(), start=False)
    mem = eng.stats()["decode"]["memory"]
    eng.close()
    assert mem["enabled"]
    for key in ("programs", "predicted_peak_bytes", "pool_bytes",
                "per_slot_bytes", "offender", "donation", "digest",
                "measured_peak_bytes"):
        assert key in mem, key
    assert mem["donation"]["step"]["accepted"]
    assert mem["pool_bytes"] == 2 * mem["per_slot_bytes"]
    # the digest is a content address of the prediction, not the host:
    # a second identical engine reproduces it bitwise
    eng2 = DecodeEngine(step, params, {}, si, num_slots=2, max_len=8,
                        ctx=mx.cpu(), start=False)
    digest2 = eng2.memory_plan["digest"]
    eng2.close()
    assert digest2 == mem["digest"]


def test_memory_gauges_published_and_reclaimed():
    telemetry.reset()
    step, params, si = _sum_step(sound=True)
    eng = DecodeEngine(step, params, {}, si, num_slots=2, max_len=8,
                       ctx=mx.cpu())
    reg = telemetry.registry()
    reg.collect()
    fam = reg.get("mxnet_serve_memory_predicted_peak_bytes")
    series = {tuple(v): inst.value for v, inst in fam.series()}
    assert series and all(val > 0 for val in series.values())
    eng.close()
    assert fam.series() == []
    telemetry.reset()


# ---------------------------------------------------------------------------
# graph_lint --memory exit contract
# ---------------------------------------------------------------------------

def _lint(*argv):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "graph_lint.py")]
        + list(argv), capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))


def test_graph_lint_memory_section_and_exit_contract(tmp_path):
    good, _p, _si = _sum_step(sound=True)
    bad, _p2, _si2 = _sum_step(sound=False)
    gpath, bpath = tmp_path / "good.json", tmp_path / "bad.json"
    good.save(str(gpath))
    bad.save(str(bpath))
    common = ["--decode-step", "--memory", "--shapes", "token=4",
              "--shapes", "s=4,8", "--decode-state", "s", "--json"]
    r = _lint(str(gpath), *common)
    assert r.returncode == 0, r.stdout + r.stderr
    mem = json.loads(r.stdout)["graphs"][str(gpath)]["memory"]
    assert mem["donation"]["accepted"]
    assert mem["peak_bytes"] > 0 and mem["per_node_top"]
    # unsound donation exits 1 even WITHOUT --strict
    r = _lint(str(bpath), *common)
    assert r.returncode == 1, r.stdout + r.stderr
    mem = json.loads(r.stdout)["graphs"][str(bpath)]["memory"]
    assert not mem["donation"]["accepted"]
    assert "out_fc" in mem["donation"]["reasons"][0]


def test_graph_lint_memory_serve_mode_advisory():
    # zoo sweep: the memory report is advisory — exit stays 0
    r = _lint("mlp", "--memory")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "memory: predicted peak" in r.stdout
    assert "in-place candidates" in r.stdout

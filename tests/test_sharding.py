"""Model-parallel (pjit-sharded replica) serving tests — ROADMAP item 1
(parallel/mesh.py ShardingPlan spec, serving sharded program caches,
analysis.check_sharding_plan verdict gate, AOT sharding key component,
MXNET_AOT_XLA_CACHE auto default, SSE decode.token streaming,
graph_lint --sharding-plan, shard_bench).

In-process tests run plans over ONE-device meshes (``{"tp": 1}``) —
the full pjit path (NamedSharding placement, sharded jax.export round
trip, plan-keyed AOT entries) is device-count-independent, so the
suite needs no XLA_FLAGS except in the subprocess bench smoke, which
exercises 2 replicas x 2-device plans under a forced host device
count (bitwise vs unsharded, 0 retraces, sharded failover, warm
restart).
"""
import importlib.util
import json
import os
import subprocess
import sys
import time
import warnings

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.parallel.mesh import (ShardingPlan, normalize_plan_spec,
                                     plan_group_size, load_plan_spec)
from mxnet_tpu.serving import (DecodeEngine, ServingEngine, StepProgram,
                               greedy_decode)
from mxnet_tpu.serving.replica import resolve_replica_placements

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _import_tool(name):
    path = os.path.join(REPO, "tools", "%s.py" % name)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _mlp(feature=6, hidden=16, classes=4, seed=0):
    net = mx.sym.FullyConnected(mx.sym.Variable("data"),
                                num_hidden=hidden, name="fc1")
    net = mx.sym.Activation(net, act_type="relu", name="relu1")
    net = mx.sym.FullyConnected(net, num_hidden=classes, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    rng = np.random.default_rng(seed)
    params = {
        "fc1_weight": mx.nd.array(
            rng.standard_normal((hidden, feature)).astype(np.float32)),
        "fc1_bias": mx.nd.zeros((hidden,)),
        "fc2_weight": mx.nd.array(
            rng.standard_normal((classes, hidden)).astype(np.float32)),
        "fc2_bias": mx.nd.zeros((classes,)),
    }
    return net, params


def _lstm_step(vocab=16, embed=8, hidden=16, seed=0):
    from mxnet_tpu.rnn.rnn_cell import LSTMCell
    tok = mx.sym.Variable("token")
    emb = mx.sym.Embedding(tok, input_dim=vocab, output_dim=embed,
                           name="emb")
    cell = LSTMCell(hidden, prefix="lstm_")
    out, (h2, c2) = cell(emb, [mx.sym.Variable("h"),
                               mx.sym.Variable("c")])
    logits = mx.sym.FullyConnected(out, num_hidden=vocab, name="out_fc")
    rng = np.random.default_rng(seed)

    def w(*shape, scale=0.5):
        return mx.nd.array(
            rng.standard_normal(shape).astype(np.float32) * scale)

    params = {
        "emb_weight": w(vocab, embed, scale=1.0),
        "lstm_i2h_weight": w(4 * hidden, embed),
        "lstm_i2h_bias": mx.nd.zeros((4 * hidden,)),
        "lstm_h2h_weight": w(4 * hidden, hidden),
        "lstm_h2h_bias": mx.nd.zeros((4 * hidden,)),
        "out_fc_weight": w(vocab, hidden, scale=1.0),
        "out_fc_bias": mx.nd.zeros((vocab,)),
    }
    step = mx.sym.Group([logits, h2, c2])
    state_info = [{"name": "h", "shape": (hidden,)},
                  {"name": "c", "shape": (hidden,)}]
    return step, params, state_info


def _cross_slot_step(vocab=16, d=8):
    """A step whose state pools over the SLOT axis: cross-position
    under pad-dirty seeding — the graph every rejection test uses."""
    tok = mx.sym.Variable("token")
    s = mx.sym.Variable("s")
    emb = mx.sym.Embedding(tok, input_dim=vocab, output_dim=d,
                           name="emb")
    s2 = s + emb
    mixed = mx.sym.broadcast_add(
        s2, mx.sym.sum(s2, axis=0, keepdims=True))
    logits = mx.sym.FullyConnected(mixed, num_hidden=vocab,
                                   name="out_fc")
    params = {"emb_weight": mx.nd.zeros((vocab, d)),
              "out_fc_weight": mx.nd.zeros((vocab, d)),
              "out_fc_bias": mx.nd.zeros((vocab,))}
    return (mx.sym.Group([logits, s2]), params,
            [{"name": "s", "shape": (d,)}])


TP1 = {"axes": {"tp": 1}, "param_rules": [["weight$", ["tp", None]]]}
TP1_SLOT = {"axes": {"tp": 1}, "state_rules": [[".*", ["tp"]]]}


@pytest.fixture
def _fresh_telemetry():
    telemetry.set_enabled(None)
    telemetry.reset()
    telemetry.stop_server()
    telemetry.stop_recorder()
    yield
    telemetry.stop_server()
    telemetry.stop_recorder()
    telemetry.set_enabled(None)
    telemetry.reset()


# ---------------------------------------------------------------------------
# plan spec layer
# ---------------------------------------------------------------------------

def test_plan_spec_validation_and_roundtrip(tmp_path):
    spec = normalize_plan_spec(
        {"axes": {"tp": 2}, "batch_axis": "tp",
         "param_rules": [["fc.*weight$", [None, "tp"]]]})
    assert spec["axes"] == {"tp": 2} and spec["batch_axis"] == "tp"
    assert spec["state_rules"] == []
    assert plan_group_size(spec) == 2
    # JSON string and file path both resolve
    assert load_plan_spec(json.dumps(spec)) == spec
    p = tmp_path / "plan.json"
    p.write_text(json.dumps(spec))
    assert load_plan_spec(str(p)) == spec
    with pytest.raises(MXNetError):
        load_plan_spec(str(tmp_path / "missing.json"))
    # malformed specs are named errors, never mystery crashes
    for bad in ({}, {"axes": {}}, {"axes": {"tp": -1}},
                {"axes": {"tp": 2.5}},      # truncation would serve a
                #                             placement nobody wrote
                {"axes": {"tp": 1}, "nope": 1},
                {"axes": {"tp": 1}, "batch_axis": "dp"},
                {"axes": {"tp": 1}, "param_rules": [["(", ["tp"]]]},
                {"axes": {"tp": 1}, "param_rules": [["x", ["dp"]]]},
                "not json"):
        with pytest.raises(MXNetError):
            normalize_plan_spec(bad)
    # live plan over one device: spec round-trips canonically and the
    # placement helpers produce NamedShardings on the mesh
    import jax
    spec1 = normalize_plan_spec(
        {"axes": {"tp": 1}, "batch_axis": "tp",
         "param_rules": [["fc.*weight$", [None, "tp"]]]})
    plan = ShardingPlan.from_spec(spec1, devices=jax.devices()[:1])
    assert plan.spec() == spec1
    assert len(plan.devices()) == 1
    assert plan.digest() == ShardingPlan.from_spec(
        spec1, devices=jax.devices()[:1]).digest()
    assert plan.digest() != ShardingPlan.from_spec(
        TP1, devices=jax.devices()[:1]).digest()
    sh = plan.param_sharding("fc1_weight", (16, 6))
    from jax.sharding import NamedSharding
    assert isinstance(sh, NamedSharding)


def test_replica_placement_resolution():
    # sharding=None is byte-for-byte replica_contexts
    assert resolve_replica_placements(None, None, None) == [(None, None)]
    # 1 replica x 1-device plan on this one-device box
    [(ctx, plan)] = resolve_replica_placements(1, None, TP1)
    assert plan is not None and len(plan.devices()) == 1
    assert ctx is not None
    # the plan owns placement: an explicit ctx is refused
    with pytest.raises(MXNetError):
        resolve_replica_placements(1, mx.cpu(), TP1)
    # never a silent clamp: too few devices raises
    import jax
    have = len(jax.devices())
    with pytest.raises(MXNetError):
        resolve_replica_placements(have + 1, None, TP1)
    with pytest.raises(MXNetError):
        resolve_replica_placements(
            1, None, {"axes": {"tp": have + 1}})


def test_check_sharding_plan_gate():
    from mxnet_tpu import analysis
    ok = analysis.check_sharding_plan(
        {"axes": {"tp": 2}, "batch_axis": "tp"},
        verdicts={"batch": "row-local"}, kind="serve")
    assert ok.accepted and not ok.reasons
    assert any(r.get("padded_axis") == "batch" for r in ok.partitioned)
    # cross-position partition rejects with a reason
    bad = analysis.check_sharding_plan(
        {"axes": {"tp": 2}, "batch_axis": "tp"},
        verdicts={"batch": "cross-position"}, kind="serve")
    assert not bad.accepted and "cross-position" in bad.reasons[0]
    # fails CLOSED: a partitioned axis with no verdict rejects too
    closed = analysis.check_sharding_plan(
        {"axes": {"tp": 2}, "seq_axis": "tp"}, verdicts={},
        kind="serve")
    assert not closed.accepted
    # decode: a state rule sharding axis 0 IS a slot-axis partition
    leak = analysis.check_sharding_plan(
        {"axes": {"tp": 2}, "state_rules": [["s", ["tp"]]]},
        verdicts={"slot": "cross-position"}, kind="decode")
    assert not leak.accepted and "slot axis" in leak.reasons[0]
    # param rules are placement-only whatever the verdicts
    par = analysis.check_sharding_plan(
        {"axes": {"tp": 2}, "param_rules": [["w", ["tp"]]]},
        verdicts={}, kind="serve")
    assert par.accepted
    assert par.partitioned[0]["verdict"] == "placement-only"
    # a decode plan has no gated data axes at all: batch_axis would
    # partition the unanalyzed prefill batch, seq_axis has no dim-1 —
    # both reject outright whatever the verdicts
    for field in ("batch_axis", "seq_axis"):
        nod = analysis.check_sharding_plan(
            {"axes": {"tp": 2}, field: "tp"},
            verdicts={"slot": "row-local"}, kind="decode")
        assert not nod.accepted and "state_rules" in nod.reasons[0]
    # the slot pool's own partition (state_rules axis 0) is ACCEPTED
    # exactly when the step verdict is row-local
    slot_ok = analysis.check_sharding_plan(
        {"axes": {"tp": 2}, "state_rules": [[".*", ["tp"]]]},
        verdicts={"slot": "row-local"}, kind="decode")
    assert slot_ok.accepted


def test_engine_rejects_unsound_plan():
    step, params, state_info = _cross_slot_step()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        with pytest.raises(MXNetError, match="sharding plan rejected"):
            DecodeEngine(step, params, {}, state_info, num_slots=2,
                         max_len=8, start=False, sharding=TP1_SLOT)
    # the same step WITHOUT a slot partition constructs fine (tensor-
    # parallel param rules are placement-only)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        eng = DecodeEngine(step, params, {}, state_info, num_slots=2,
                           max_len=8, start=False, sharding=TP1)
        eng.close()


# ---------------------------------------------------------------------------
# sharded engines: bitwise + compile-once + identity
# ---------------------------------------------------------------------------

def test_serve_sharded_bitwise_and_identity(_fresh_telemetry):
    net, params = _mlp()
    ref = ServingEngine(net, params, {}, {"data": (6,)})
    eng = ServingEngine(net, params, {}, {"data": (6,)}, sharding=TP1)
    ref.warmup()
    eng.warmup()
    c0 = eng.compile_count
    rng = np.random.default_rng(1)
    for _ in range(4):
        x = rng.standard_normal((6,)).astype(np.float32)
        assert np.array_equal(eng.predict(x, timeout=30),
                              ref.predict(x, timeout=30))
    assert eng.compile_count == c0          # zero warm retraces
    st = eng.stats()
    assert st["sharding"]["axes"] == {"tp": 1}
    rep = st["replicas"][0]
    assert rep["shards"] == 1 and rep["shard_devices"]
    assert rep["sharding"] == st["replicas"][0]["sharding"]
    # per-shard identity rides the replica label in the registry
    fam = telemetry.registry().get("mxnet_serve_replica_shards")
    label = eng._tm.engine_label
    vals = {values: inst.value for values, inst in fam.series()}
    assert vals.get((label, "0")) == 1.0
    # ... and in the /healthz per-replica block
    import urllib.request
    srv = telemetry.start_server(0, host="127.0.0.1")
    try:
        with urllib.request.urlopen(
                "http://127.0.0.1:%d/healthz" % srv.port,
                timeout=10) as r:
            hz = json.loads(r.read().decode())
        row = hz["replicas"]["engines"][label][0]
        assert row["shards"] == 1
    finally:
        telemetry.stop_server()
    eng.close()
    ref.close()
    # reclaim at close: no orphaned shard series
    assert not any(values[0] == label for values, _ in fam.series())


def test_decode_sharded_staggered_bitwise():
    step, params, state_info = _lstm_step()
    prog = StepProgram(step, params, {}, state_info, 4)
    prompts = [[3, 5], [2], [7, 1, 4], [9]]
    wants = [greedy_decode(prog, p, 6, max_len=16) for p in prompts]
    eng = DecodeEngine(step, params, {}, state_info, num_slots=4,
                       max_len=16, sharding=TP1_SLOT)
    eng.warmup()
    c0 = eng.compile_count
    futs = []
    for p in prompts:                       # staggered joins
        futs.append(eng.submit(p, 6))
        time.sleep(0.01)
    for f, w in zip(futs, wants):
        assert np.array_equal(f.result(60).tokens, w)
    assert eng.compile_count == c0
    d = eng.stats()["decode"]
    assert d["sharding"]["state_rules"] == [[".*", ["tp"]]]
    assert d["replicas"][0]["shards"] == 1
    eng.close()


# ---------------------------------------------------------------------------
# AOT: sharding key component (residual b2)
# ---------------------------------------------------------------------------

def test_aot_sharding_key_component(tmp_path, monkeypatch):
    from mxnet_tpu.serving.aot_cache import iter_entries
    monkeypatch.setenv("MXNET_AOT_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("MXNET_AOT_XLA_CACHE", "0")
    net, params = _mlp()
    eng = ServingEngine(net, params, {}, {"data": (6,)}, sharding=TP1)
    eng.warmup()
    rng = np.random.default_rng(2)
    xs = [rng.standard_normal((6,)).astype(np.float32)
          for _ in range(3)]
    wants = [eng.predict(x, timeout=30) for x in xs]
    assert eng.stats()["aot"]["writes"] > 0
    eng.close()
    # every entry's metadata carries the plan spec verbatim
    metas = [m for _k, _mp, _bp, m in iter_entries(str(tmp_path))]
    assert metas and all(m["sharding"]["axes"] == {"tp": 1}
                         for m in metas)
    # warm restart of the SAME plan: zero traces, bitwise
    eng = ServingEngine(net, params, {}, {"data": (6,)}, sharding=TP1)
    eng.warmup()
    assert eng.compile_count == 0
    for x, w in zip(xs, wants):
        assert np.array_equal(eng.predict(x, timeout=30), w)
    st = eng.stats()["aot"]
    assert st["hits"] > 0 and st["rejects"] == 0
    eng.close()
    # a DIFFERENT plan — and the unsharded twin — MISS, never hit
    other = {"axes": {"tp": 1}, "param_rules": [["bias$", ["tp"]]]}
    for sharding in (other, None):
        eng = ServingEngine(net, params, {}, {"data": (6,)},
                            sharding=sharding)
        eng.warmup()
        st = eng.stats()["aot"]
        assert st["hits"] == 0 and st["rejects"] == 0 \
            and st["misses"] > 0, (sharding, st)
        eng.close()
    # decode: a slot-sharded step program (step + prefill-free path +
    # row kernels) also restarts warm with zero traces, bitwise
    step, sparams, sinfo = _lstm_step()
    d = DecodeEngine(step, sparams, {}, sinfo, num_slots=2,
                     max_len=16, sharding=TP1_SLOT)
    d.warmup()
    assert d.compile_count > 0
    want = d.generate([3, 2], 4, timeout=30).tokens
    d.close()
    d = DecodeEngine(step, sparams, {}, sinfo, num_slots=2,
                     max_len=16, sharding=TP1_SLOT)
    d.warmup()
    assert d.compile_count == 0
    assert np.array_equal(d.generate([3, 2], 4, timeout=30).tokens,
                          want)
    d.close()
    # the CLI renders the sharding key component (satellite contract)
    tool = _import_tool("aot_cache")
    import io
    import contextlib
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = tool.main(["--dir", str(tmp_path), "list", "--json"])
    assert rc == 0
    doc = json.loads(buf.getvalue())
    shardings = {e["sharding"] for e in doc["entries"]}
    assert "none" in shardings                      # unsharded twin
    assert any(s.startswith("tp=1") for s in shardings)
    assert any(e["sharding_spec"] == normalize_plan_spec(TP1)
               for e in doc["entries"])


# ---------------------------------------------------------------------------
# MXNET_AOT_XLA_CACHE auto default (residual b1) — process-global jax
# config, so each scenario runs in its own subprocess
# ---------------------------------------------------------------------------

def _run_py(code, **env_extra):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("MXNET_TELEMETRY_PORT", None)
    env.update(env_extra)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=240)
    assert out.returncode == 0, (out.stdout, out.stderr)
    return out.stdout


def test_aot_xla_cache_auto_owns_bringup(tmp_path):
    # engine constructed before any compile: auto turns the jax
    # persistent compilation cache on under <dir>/xla
    code = """
import sys, warnings
sys.path.insert(0, %r); sys.path.insert(0, %r)
warnings.simplefilter("ignore")
from test_sharding import _mlp
from mxnet_tpu.serving import ServingEngine
net, params = _mlp()
eng = ServingEngine(net, params, {}, {"data": (6,)})
import jax
d = jax.config.jax_compilation_cache_dir
assert d and d.endswith("xla"), d
eng.warmup(); eng.close()
import os
assert os.path.isdir(d)
print("AUTO_ON_OK")
""" % (REPO, os.path.join(REPO, "tests"))
    out = _run_py(code, MXNET_AOT_CACHE_DIR=str(tmp_path),
                  MXNET_AOT_XLA_CACHE="auto")
    assert "AUTO_ON_OK" in out


def test_aot_xla_cache_auto_declines_then_explicit_latches(tmp_path):
    # a process that compiled FIRST: auto declines (the library must
    # not flip process-global config out from under the app), the
    # explicit opt-out stays off, and an explicit "1" still latches
    # late via compilation_cache.reset_cache
    code = """
import sys, os, warnings
sys.path.insert(0, %r); sys.path.insert(0, %r)
warnings.simplefilter("ignore")
import jax, jax.numpy as jnp
from test_sharding import _mlp
from mxnet_tpu.serving import ServingEngine
net, params = _mlp()
# the app compiles first (through the library's own counter)
eng0 = ServingEngine(net, params, {}, {"data": (6,)})
eng0.warmup(); eng0.close()
os.environ["MXNET_AOT_CACHE_DIR"] = %r
eng = ServingEngine(net, params, {}, {"data": (6,)})
assert not jax.config.jax_compilation_cache_dir, \\
    jax.config.jax_compilation_cache_dir
eng.close()
os.environ["MXNET_AOT_XLA_CACHE"] = "0"
eng = ServingEngine(net, params, {}, {"data": (6,)})
assert not jax.config.jax_compilation_cache_dir
eng.close()
os.environ["MXNET_AOT_XLA_CACHE"] = "1"
eng = ServingEngine(net, params, {}, {"data": (6,)})
d = jax.config.jax_compilation_cache_dir
assert d and d.endswith("xla"), d
eng.warmup()
import numpy as np
eng.predict(np.zeros((6,), np.float32), timeout=60)
eng.close()
assert os.path.isdir(d) and os.listdir(d), "late latch wrote nothing"
print("LATE_LATCH_OK")
""" % (REPO, os.path.join(REPO, "tests"), str(tmp_path))
    out = _run_py(code, MXNET_AOT_XLA_CACHE="auto")
    assert "LATE_LATCH_OK" in out


# ---------------------------------------------------------------------------
# SSE per-request token stream (ROADMAP item 4 residual)
# ---------------------------------------------------------------------------

def test_sse_decode_token_stream(_fresh_telemetry):
    step, params, state_info = _lstm_step()
    hub = telemetry.server.event_hub()
    q, _replayed, _reset = hub.subscribe()
    try:
        eng = DecodeEngine(step, params, {}, state_info, num_slots=2,
                           max_len=16)
        prog = StepProgram(step, params, {}, state_info, 2)
        want = greedy_decode(prog, [3, 5], 6, max_len=16)
        got = eng.submit([3, 5], 6, request_id="req-42").result(30)
        # a request WITHOUT an id publishes nothing
        eng.submit([2], 2).result(30)
        # EVERY terminal outcome closes the stream: a request killed by
        # its own raising callback still gets a done frame (error)
        def boom(tok):
            raise RuntimeError("stream consumer exploded")
        with pytest.raises(RuntimeError):
            eng.submit([4], 3, request_id="req-err",
                       on_token=boom).result(30)
        eng.close()
        assert np.array_equal(got.tokens, want)
        all_evs = []
        while not q.empty():
            ev = q.get_nowait()
            if ev is None:
                break
            seq, name, payload = ev
            if name == "decode.token":
                all_evs.append((seq, json.loads(payload)))
        err_done = [e for _s, e in all_evs
                    if e["request_id"] == "req-err" and e.get("done")]
        assert len(err_done) == 1 \
            and err_done[0]["finish_reason"] == "error"
        evs = [(s, e) for s, e in all_evs if e["request_id"] == "req-42"]
        toks = [e["token"] for _s, e in evs if "token" in e]
        assert toks == [int(t) for t in want]   # exact greedy prefix
        done = [e for _s, e in evs if e.get("done")]
        assert len(done) == 1 \
            and done[0]["finish_reason"] == "length" \
            and done[0]["tokens"] == len(want)
        # Last-Event-ID resume: replay everything after the first token
        first_seq = evs[0][0]
        q2, replayed, reset = hub.subscribe(last_event_id=first_seq)
        hub.unsubscribe(q2)
        assert not reset
        replay_toks = [json.loads(p)["token"] for _s, n, p in replayed
                       if n == "decode.token"
                       and json.loads(p).get("request_id") == "req-42"
                       and "token" in json.loads(p)]
        assert replay_toks == toks[1:]
    finally:
        hub.unsubscribe(q)


# ---------------------------------------------------------------------------
# graph_lint --sharding-plan
# ---------------------------------------------------------------------------

def test_graph_lint_sharding_plan_cli(tmp_path, capsys):
    lint = _import_tool("graph_lint")
    net, _ = _mlp()
    gpath = tmp_path / "mlp.json"
    gpath.write_text(net.tojson())
    plan = tmp_path / "plan.json"
    plan.write_text(json.dumps({"axes": {"tp": 2},
                                "batch_axis": "tp"}))
    rc = lint.main([str(gpath), "--shapes", "data=8,6", "--max-batch",
                    "8", "--sharding-plan", str(plan), "--json"])
    doc = json.loads(capsys.readouterr().out)["graphs"][str(gpath)]
    assert rc == 0
    audit = doc["sharding_plan"]
    assert audit["accepted"]
    assert audit["partitioned"][0]["verdict"] == "row-local"
    assert "fc1" in audit["nodes"]["<data>"]
    # cross-position graph: the same plan is REJECTED, exit 1 even
    # without --strict (the engine-construction gate, offline)
    x = mx.sym.Variable("data")
    bad = mx.sym.Group([mx.sym.softmax(x, axis=0)])
    bpath = tmp_path / "cross.json"
    bpath.write_text(bad.tojson())
    rc = lint.main([str(bpath), "--shapes", "data=8,6", "--max-batch",
                    "8", "--sharding-plan", str(plan), "--json"])
    doc = json.loads(capsys.readouterr().out)["graphs"][str(bpath)]
    assert rc == 1
    assert not doc["sharding_plan"]["accepted"]
    assert "cross-position" in doc["sharding_plan"]["reasons"][0]
    # decode mode: state-rule slot partition of a cross-slot step
    step, _p, _si = _cross_slot_step()
    spath = tmp_path / "step.json"
    spath.write_text(step.tojson())
    dplan = tmp_path / "dplan.json"
    dplan.write_text(json.dumps(TP1_SLOT))
    rc = lint.main([str(spath), "--decode-step", "--shapes",
                    "token=4", "--shapes", "s=4,8",
                    "--decode-state", "s",
                    "--sharding-plan", str(dplan), "--json"])
    doc = json.loads(capsys.readouterr().out)["graphs"][str(spath)]
    assert rc == 1
    assert not doc["sharding_plan"]["accepted"]
    # a malformed plan is a usage error (exit 2), not a crash
    badplan = tmp_path / "bad.json"
    badplan.write_text("{\"axes\": {}}")
    rc = lint.main([str(gpath), "--shapes", "data=8,6",
                    "--sharding-plan", str(badplan)])
    capsys.readouterr()
    assert rc == 2


# ---------------------------------------------------------------------------
# bench smoke under a forced host device count (tier-1, subprocess:
# XLA_FLAGS must be set before jax initializes) — 2 replicas x
# 2-device plans: bitwise, 0 retraces, sharded failover, AOT warm
# restart of sharded programs
# ---------------------------------------------------------------------------

def test_shard_bench_smoke_forced_devices():
    code = """
import sys, os, time, warnings
sys.path.insert(0, %r)
sys.path.insert(0, %r)
warnings.simplefilter("ignore")
import numpy as np
import shard_bench
row = shard_bench.run_serve_shard_sweep(
    requests=24, repeats=1, feature=32, hidden=32, layers=1,
    replicas=2, group=2)
assert row["device_count"] >= 4, row
assert row["matches_unsharded_few_ulp"], row
assert row["retraces"] == 0, row
assert row["replica_shards"] == [2, 2], row
row2 = shard_bench.run_decode_shard_sweep(
    requests=6, slots=2, max_len=16, mean_new=4, hidden=16,
    layers=1, repeats=1, replicas=2, group=2)
assert row2["bitwise_identical"], row2
assert row2["retraces"] == 0, row2
assert row2["replica_shards"] == [2, 2], row2
row3 = shard_bench.run_shard_aot_gate(feature=16, hidden=16,
                                      layers=1, replicas=2, group=2)
assert row3["warm_compiles"] == 0, row3
assert row3["bitwise_identical"], row3
assert row3["warm_hits"] > 0 and row3["warm_rejects"] == 0, row3
# failover: a fault plan kills replica 0's first dispatch; the
# SHARDED sibling keeps serving bitwise.  The reference outputs are
# computed BEFORE the plan is installed — it must fire on the sharded
# fleet, not the reference engine
from shard_bench import build_model, serve_plan
from mxnet_tpu import serving
from mxnet_tpu.test_utils import assert_almost_equal, few_ulp_tol
net, params = build_model(feature=32, hidden=32, layers=1)
ref = serving.ServingEngine(net, params, {}, {"data": (32,)})
ref.warmup()
rng = np.random.default_rng(9)
xs = [rng.standard_normal((32,)).astype(np.float32)
      for _ in range(6)]
wants = [ref.predict(x, timeout=120) for x in xs]
ref.close()
os.environ["MXNET_FAULT_PLAN"] = \\
    "serve.dispatch:raise:on=1,replica=0,times=1"
eng = serving.ServingEngine(net, params, {}, {"data": (32,)},
                            replicas=2, sharding=serve_plan(2))
eng.warmup()
failed = 0
for x, w in zip(xs, wants):
    try:
        got = eng.predict(x, timeout=120)
    except Exception:
        failed += 1
        continue
    # sharded fleet vs the unsharded reference engine: another
    # partition, another program
    assert_almost_equal(got, w, *few_ulp_tol(w))
health = [r["healthy"] for r in eng.stats()["replicas"]]
assert failed == 1 and health == [False, True], (failed, health)
eng.close()
print("SHARD_SMOKE_OK")
""" % (REPO, os.path.join(REPO, "perf"))
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["MXNET_TELEMETRY_ON"] = "0"
    env.pop("MXNET_TELEMETRY_PORT", None)
    env.pop("MXNET_AOT_CACHE_DIR", None)
    env.pop("MXNET_FAULT_PLAN", None)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=540)
    assert out.returncode == 0, (out.stdout, out.stderr)
    assert "SHARD_SMOKE_OK" in out.stdout

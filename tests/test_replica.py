"""Data-parallel replica routing tests (mxnet_tpu/serving/replica.py).

Coverage per the issue contract: least-loaded routing with responses
BITWISE-identical to the single-replica engine (one-shot) and to
single-request greedy decode (decode, wherever a request seats),
replica failover — an induced dispatch failure drains the replica,
evicts its seated decode requests with PARTIAL output, keeps
co-resident replicas serving bitwise-identically, and dumps a flight
bundle — the reload-loop leak gate at N replicas (series, rules,
heartbeats, recorder refs all reclaimed at close()), the per-replica
``/healthz`` block + ``telemetry_dump healthz`` rendering, the
pluggable decode sampler (greedy bitwise-pinned, temperature/top-k on
the rng-key plumbing), the declarative alert-rules file, the
training-loop watchdog heartbeat, and the ``--replicas`` bench smokes
under a forced host device count.

Multi-replica engines here run their replicas on ONE device
(``ctx=[cpu(0), cpu(0)]``) — routing, failover, and telemetry are
device-count-independent, so the suite needs no XLA_FLAGS except in
the subprocess bench smoke.
"""
import importlib.util
import json
import os
import subprocess
import sys
import time
import urllib.request
import warnings

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import serving, telemetry
from mxnet_tpu.test_utils import assert_almost_equal, few_ulp_tol
from mxnet_tpu.serving import (DecodeEngine, ServingEngine, StepProgram,
                               greedy_decode, GreedySampler,
                               TemperatureSampler, replica_contexts)

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
# replica_contexts resolution
# ---------------------------------------------------------------------------

def test_replica_contexts_resolution():
    # default single replica touches nothing
    assert replica_contexts(None, None) == [None]
    ctx = mx.cpu()
    assert replica_contexts(1, ctx) == [ctx]
    # explicit list IS the replica set (same device twice is legal)
    ctxs = replica_contexts(None, [mx.cpu(0), mx.cpu(0)])
    assert len(ctxs) == 2
    with pytest.raises(mx.base.MXNetError):
        replica_contexts(3, [mx.cpu(0), mx.cpu(0)])    # disagreement
    with pytest.raises(mx.base.MXNetError):
        replica_contexts(0, None)
    # explicit replicas beyond the device count refuse (this test env
    # has one CPU device unless XLA_FLAGS forced more)
    import jax
    n = jax.device_count()
    with pytest.raises(mx.base.MXNetError):
        replica_contexts(n + 1, None)


def test_env_replicas_clamp_warns(monkeypatch):
    import jax
    n = jax.device_count()
    monkeypatch.setenv("MXNET_SERVE_REPLICAS", str(n + 3))
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        ctxs = replica_contexts(None, None)
    assert len(ctxs) == n
    assert any("clamping" in str(x.message) for x in w)


# ---------------------------------------------------------------------------
# one-shot engine: routing, bitwise identity, failover
# ---------------------------------------------------------------------------

def test_serving_replicas_route_and_match_single():
    """Sequential requests through one replica (batch 1 each) against
    the same requests offered at once to two (coalesced batches):
    different batch extents, so equal to a few ulp."""
    net, params = _mlp()
    rng = np.random.default_rng(1)
    X = rng.standard_normal((24, 6)).astype(np.float32)
    e1 = ServingEngine(net, params, {}, {"data": (6,)}, ctx=mx.cpu())
    e1.warmup()
    e2 = ServingEngine(net, params, {}, {"data": (6,)},
                       ctx=[mx.cpu(0), mx.cpu(0)])
    w2 = e2.warmup()
    ref = [e1.predict(x, timeout=60) for x in X]
    futs = [e2.submit(x) for x in X]
    got = [f.result(timeout=60) for f in futs]
    for a, b in zip(ref, got):
        assert_almost_equal(b, a, *few_ulp_tol(a))
    st = e2.stats()
    assert len(st["replicas"]) == 2
    assert all(r["healthy"] for r in st["replicas"])
    # both replicas actually dispatched (least-loaded routing spreads
    # a stream of single-request batches)
    assert all(r["batches"] >= 1 for r in st["replicas"])
    assert sum(r["batches"] for r in st["replicas"]) == st["batches"]
    assert e2.compile_count == w2 and st["retraces"] == 0
    e1.close()
    e2.close()


def test_serving_replica_failover(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_FLIGHT_RECORDER_DIR", str(tmp_path))
    net, params = _mlp()
    eng = ServingEngine(net, params, {}, {"data": (6,)},
                        ctx=[mx.cpu(0), mx.cpu(0)])
    eng.warmup()
    x = np.ones((6,), np.float32)
    want = eng.predict(x, timeout=60)          # healthy baseline

    boom = RuntimeError("induced dispatch failure")
    real_run = eng._replicas[0].cache.run

    def bad_run(feeds, _record=True):
        raise boom
    eng._replicas[0].cache.run = bad_run
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        # an idle fleet routes to replica 0 first (index breaks the
        # tie) — this request eats the failure
        with pytest.raises(RuntimeError, match="induced dispatch"):
            eng.predict(x, timeout=60)
        # replica 0 is drained + unhealthy; traffic re-routes and the
        # co-resident replica keeps serving bitwise-identically
        for _ in range(3):
            np.testing.assert_array_equal(
                eng.predict(x, timeout=60), want)
    st = eng.stats()
    assert [r["healthy"] for r in st["replicas"]] == [False, True]
    assert st["replicas"][0]["failures"] == 1
    hb = eng._heartbeat()
    assert hb["replicas"][0]["healthy"] is False
    # the flight recorder dumped on the unhealthy transition — on the
    # REPLICA thread, after the client's future already failed, so
    # give the (registry-size-dependent) bundle write a bounded wait
    deadline = time.monotonic() + 30
    bundles = []
    while not bundles and time.monotonic() < deadline:
        bundles = [p for p in os.listdir(str(tmp_path))
                   if p.startswith("flight_")]
        if not bundles:
            time.sleep(0.02)
    assert bundles, "no flight bundle written on replica failure"
    doc = json.load(open(os.path.join(str(tmp_path), bundles[0])))
    assert "replica_failed" in doc["reason"]
    eng._replicas[0].cache.run = real_run
    eng.close()


def test_serving_all_replicas_unhealthy_fails_fast():
    net, params = _mlp()
    eng = ServingEngine(net, params, {}, {"data": (6,)},
                        ctx=[mx.cpu(0), mx.cpu(0)])
    eng.warmup()
    for rep in eng._replicas:
        rep.cache.run = lambda feeds, _record=True: (
            (_ for _ in ()).throw(RuntimeError("dead")))
    x = np.ones((6,), np.float32)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        with pytest.raises(RuntimeError, match="dead"):
            eng.predict(x, timeout=60)
        with pytest.raises(RuntimeError, match="dead"):
            eng.predict(x, timeout=60)
        # with every replica drained, new work fails fast instead of
        # wedging the queue
        with pytest.raises(mx.base.MXNetError, match="unhealthy"):
            eng.predict(x, timeout=60)
    eng.close()


def test_serving_replica_router_keeps_backpressure():
    """The router's per-replica in-flight cap keeps overload backlog in
    the ADMISSION queue, where max_queue backpressure still applies —
    an unbounded replica pending queue would silently disable
    QueueFullError/shed/deadline sweeps for every routed request."""
    net, params = _mlp()
    eng = ServingEngine(net, params, {}, {"data": (6,)},
                        ctx=[mx.cpu(0), mx.cpu(0)],
                        max_queue=4, batch_timeout_ms=0.0)
    eng.warmup()
    gate = __import__("threading").Event()
    real = {r.index: r.cache.run for r in eng._replicas}

    def slow_run(feeds, _record=True, _i=0):
        gate.wait(timeout=30)
        return real[_i](feeds, _record=_record)
    for rep in eng._replicas:
        rep.cache.run = (lambda feeds, _record=True, _i=rep.index:
                         slow_run(feeds, _record, _i))
    futs, rejected = [], 0
    for i in range(64):
        try:
            futs.append(eng.submit(np.full((6,), i, np.float32)))
        except serving.QueueFullError:
            rejected += 1
    assert rejected > 0, ("router drained the admission queue "
                          "unboundedly — backpressure never engaged")
    gate.set()
    for f in futs:
        f.result(timeout=60)
    eng.close()


# ---------------------------------------------------------------------------
# decode engine: pinning, bitwise identity, failover with partial output
# ---------------------------------------------------------------------------

def test_decode_replicas_bitwise_vs_greedy_reference():
    step, params, state_info = _lstm_step()
    ref_prog = StepProgram(step, params, {}, state_info, num_slots=1)
    want = {p: list(greedy_decode(ref_prog, [p], 6)) for p in range(4)}
    eng = DecodeEngine(step, params, {}, state_info, num_slots=2,
                       max_len=32, default_deadline_ms=0,
                       ctx=[mx.cpu(0), mx.cpu(0)])
    warm = eng.warmup()
    futs = [eng.submit([p], max_new_tokens=6) for p in range(4)]
    res = [f.result(timeout=120) for f in futs]
    for p, r in enumerate(res):
        assert r.finish_reason == "length"
        assert list(r.tokens) == want[p], "replica routing changed tokens"
    assert eng.compile_count == warm        # zero retraces across churn
    st = eng.stats()["decode"]
    assert st["slots"] == 4 and st["slots_per_replica"] == 2
    assert len(st["replicas"]) == 2
    assert st["joins"] == 4 and st["leaves"] == 4
    eng.close()


def test_decode_replica_failover_partial_output(tmp_path, monkeypatch):
    """An induced step failure on one replica evicts its seated
    requests with PARTIAL output (finish_reason 'error'); co-resident
    replicas keep serving bitwise-identically; the engine keeps
    accepting work afterwards."""
    monkeypatch.setenv("MXNET_FLIGHT_RECORDER_DIR", str(tmp_path))
    step, params, state_info = _lstm_step()
    ref_prog = StepProgram(step, params, {}, state_info, num_slots=1)
    want = {p: list(greedy_decode(ref_prog, [p], 30)) for p in (1, 2, 5)}
    eng = DecodeEngine(step, params, {}, state_info, num_slots=1,
                       max_len=64, default_deadline_ms=0,
                       ctx=[mx.cpu(0), mx.cpu(0)])
    eng.warmup()
    # one slot per replica: the router seats request 1 on replica 0,
    # request 2 on replica 1 (most-free, index-tied).  Replica 0's step
    # fails at its tenth call, so its request has a few tokens by then
    # whatever the host's load (looking for the moment both replicas
    # were seated lost to a 30-token request on a busy host)
    inner = eng._replicas[0].program.step
    calls = []

    def bad_step(tokens, pos, valid, states, reset=None):
        calls.append(1)
        if len(calls) >= 10:
            raise RuntimeError("induced step failure")
        return inner(tokens, pos, valid, states, reset=reset)
    eng._replicas[0].program.step = bad_step
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        f1 = eng.submit([1], max_new_tokens=30)
        f2 = eng.submit([2], max_new_tokens=30)
        r1 = f1.result(timeout=120)
        r2 = f2.result(timeout=120)
    # the victim: partial output, eviction reason, not an exception
    assert r1.finish_reason == "error"
    assert 0 < len(r1.tokens) < 30
    assert list(r1.tokens) == want[1][:len(r1.tokens)], \
        "partial output must be a prefix of the greedy reference"
    # the co-resident replica finished bitwise-identically
    assert r2.finish_reason == "length" and list(r2.tokens) == want[2]
    assert [r.healthy for r in eng._replicas] == [False, True]
    # new work lands on the survivor
    r3 = eng.submit([5], max_new_tokens=30).result(timeout=120)
    assert list(r3.tokens) == want[5]
    # bounded wait: the bundle is written on the failed replica's
    # thread, concurrent with the survivor serving the asserts above
    deadline = time.monotonic() + 30
    bundles = []
    while not bundles and time.monotonic() < deadline:
        bundles = [p for p in os.listdir(str(tmp_path))
                   if p.startswith("flight_")]
        if not bundles:
            time.sleep(0.02)
    assert bundles and "replica_failed" in json.load(
        open(os.path.join(str(tmp_path), bundles[0])))["reason"]
    eng.close()


def test_decode_routed_requests_reroute_off_failed_replica():
    """Requests routed to (but not yet seated on) a failing replica
    re-route to its siblings instead of being lost."""
    step, params, state_info = _lstm_step()
    eng = DecodeEngine(step, params, {}, state_info, num_slots=2,
                       max_len=32, default_deadline_ms=0,
                       ctx=[mx.cpu(0), mx.cpu(0)], start=False)
    eng.warmup()
    futs = [eng.submit([p % 8], max_new_tokens=3) for p in range(8)]
    calls = [0]
    real_step = eng._replicas[0].program.step

    def flaky_step(tokens, pos, valid, states, reset=None):
        calls[0] += 1
        if calls[0] >= 2:
            raise RuntimeError("late step failure")
        return real_step(tokens, pos, valid, states, reset=reset)
    eng._replicas[0].program.step = flaky_step
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        eng.start()
        res = [f.result(timeout=120) for f in futs]
    by_reason = {}
    for r in res:
        by_reason.setdefault(r.finish_reason, 0)
        by_reason[r.finish_reason] += 1
    # every future resolved: the evicted ones with "error", everything
    # else (including re-routed pendings) ran to completion
    assert sum(by_reason.values()) == 8
    assert by_reason.get("length", 0) >= 6
    eng.close()


# ---------------------------------------------------------------------------
# reload-loop leak gate at N replicas
# ---------------------------------------------------------------------------

def test_reload_loop_leak_gate_with_replicas(_fresh_telemetry):
    """Counts are taken against what the process held when the test
    began: the training loops' default StepTimers (telemetry/step.py)
    live for the process by design, so an earlier test file in this
    worker that ran fit() leaves its ``train.*`` heartbeats and
    watchdog rules behind, and they are not this gate's to judge."""
    reg = telemetry.registry()
    mgr = telemetry.default_manager()
    net, params = _mlp()
    step, sparams, state_info = _lstm_step()
    rules0 = len(mgr)
    heartbeats0 = set(telemetry.heartbeats())
    for _ in range(3):
        se = ServingEngine(net, params, {}, {"data": (6,)},
                           ctx=[mx.cpu(0), mx.cpu(0)])
        de = DecodeEngine(step, sparams, {}, state_info, num_slots=2,
                          max_len=32, default_deadline_ms=0,
                          ctx=[mx.cpu(0), mx.cpu(0)])
        se.warmup()
        de.warmup()
        se.predict(np.ones((6,), np.float32), timeout=60)
        de.generate([1], max_new_tokens=2, timeout=120)
        se.close()
        de.close()
        # timeline plane (ISSUE 20): both engines drop their ring
        # reference at close(); the bounded ring itself is process-
        # wide and must never exceed its capacity across reloads
        assert se._tl is None and de._tl is None
        tl = telemetry.timeline.peek()
        assert tl is None or len(tl.events()) <= tl.capacity
    # every per-engine AND per-replica series reclaimed
    for fam_name in ("mxnet_serve_replica_healthy",
                     "mxnet_serve_replica_inflight",
                     "mxnet_serve_replica_failures_total",
                     "mxnet_serve_replica_batches_total",
                     "mxnet_serve_replicas",
                     "mxnet_serve_dispatch_ms",
                     "mxnet_serve_batch_occupancy",
                     "mxnet_serve_retraces_total",
                     "mxnet_serve_decode_slots",
                     "mxnet_serve_decode_slots_occupied",
                     "mxnet_serve_decode_step_ms",
                     "mxnet_serve_memory_predicted_peak_bytes",
                     "mxnet_serve_memory_measured_peak_bytes",
                     "mxnet_serve_queue_depth",
                     # serving efficiency plane (ISSUE 18): every
                     # engine-labeled ledger/gauge/tenant series
                     "mxnet_serve_flops_total",
                     "mxnet_serve_flops_useful_total",
                     "mxnet_serve_flops_padding_total",
                     "mxnet_serve_flops_dead_slot_total",
                     "mxnet_serve_flops_spec_rejected_total",
                     "mxnet_serve_unpriced_dispatches_total",
                     "mxnet_serve_mfu",
                     "mxnet_serve_goodput_ratio",
                     "mxnet_serve_tenant_useful_flops_total",
                     "mxnet_serve_tenant_tokens_total",
                     "mxnet_serve_tenant_requests_total",
                     "mxnet_serve_tenant_latency_ms",
                     "mxnet_serve_tenant_overflow_total"):
        fam = reg.get(fam_name)
        assert fam is None or fam.series() == [], fam_name
    assert reg._callbacks == []
    assert len(mgr) == rules0
    assert set(telemetry.heartbeats()) == heartbeats0
    assert telemetry.get_recorder() is None
    # second, independent gate (PR 19): the STATIC reclaim-pairing
    # lint must agree that every dynamic-label series has a close()-
    # reachable reclaim — a series-without-reclaim regression now
    # fails here even if the runtime loop above misses its family
    from mxnet_tpu.analysis import analyze_concurrency
    model = analyze_concurrency()
    leaks = [d for d in model.report.to_list()
             if d["pass"] == "lifecycle"
             and d["node"] != "telemetry.sampling:SamplerChain"]
    assert leaks == [], leaks


# ---------------------------------------------------------------------------
# healthz per-replica block + telemetry_dump healthz
# ---------------------------------------------------------------------------

def test_healthz_replica_block_and_cli(_fresh_telemetry, capsys):
    net, params = _mlp()
    srv = telemetry.start_server(0, host="127.0.0.1")
    eng = ServingEngine(net, params, {}, {"data": (6,)},
                        ctx=[mx.cpu(0), mx.cpu(0)])
    eng.warmup()
    for i in range(4):
        eng.predict(np.full((6,), i, np.float32), timeout=60)
    url = "http://127.0.0.1:%d" % srv.port
    with urllib.request.urlopen(url + "/healthz", timeout=10) as r:
        hz = json.loads(r.read().decode())
    el = eng._tm.engine_label
    block = hz["replicas"]
    assert block["total"] == 2 and block["unhealthy"] == 0
    rows = block["engines"][el]
    assert [r["replica"] for r in rows] == ["0", "1"]
    assert all(r["healthy"] for r in rows)
    assert sum(r.get("batches", 0) for r in rows) == eng.stats()["batches"]
    # the CLI renders the same block
    telemetry_dump = _import_tool("telemetry_dump")
    assert telemetry_dump.main(["healthz", "--url", url]) == 0
    out = capsys.readouterr().out
    assert "replicas: 2 total, 0 unhealthy" in out
    assert "engine" in out and "ok" in out
    eng.close()
    # reclaimed with the engine: the block disappears
    with urllib.request.urlopen(url + "/healthz", timeout=10) as r:
        hz = json.loads(r.read().decode())
    assert "replicas" not in hz
    telemetry.stop_server()


# ---------------------------------------------------------------------------
# pluggable decode sampler
# ---------------------------------------------------------------------------

def test_sampler_topk1_is_argmax_bitwise():
    step, params, state_info = _lstm_step()
    ref = StepProgram(step, params, {}, state_info, num_slots=1)
    want = list(greedy_decode(ref, [3], 8))
    sp = StepProgram(step, params, {}, state_info, num_slots=1,
                     sampler=TemperatureSampler(temperature=2.0,
                                                top_k=1, seed=123))
    got = list(greedy_decode(sp, [3], 8))
    assert got == want, "top_k=1 must degenerate to argmax"


def test_sampler_seeded_replay_and_zero_retraces():
    step, params, state_info = _lstm_step()

    def run_once():
        eng = DecodeEngine(step, params, {}, state_info, num_slots=2,
                           max_len=32, default_deadline_ms=0,
                           sampler=TemperatureSampler(1.3, top_k=4,
                                                      seed=11))
        warm = eng.warmup()
        futs = [eng.submit([p], max_new_tokens=6) for p in (1, 2, 3)]
        toks = [list(f.result(timeout=120).tokens) for f in futs]
        assert eng.compile_count == warm    # churn never retraces
        st = eng.stats()["decode"]
        assert st["sampler"]["kind"] == "temperature"
        eng.close()
        return toks
    a = run_once()
    b = run_once()
    assert a == b, "fixed seed must replay bitwise"
    flat = [t for toks in a for t in toks]
    assert all(0 <= t < 16 for t in flat)
    assert len(flat) == 18


def test_sampler_greedy_default_describes():
    step, params, state_info = _lstm_step()
    eng = DecodeEngine(step, params, {}, state_info, num_slots=1,
                       max_len=32, default_deadline_ms=0)
    assert eng.stats()["decode"]["sampler"] == {"kind": "greedy"}
    assert isinstance(eng._sampler, GreedySampler)
    eng.close()
    with pytest.raises(mx.base.MXNetError):
        TemperatureSampler(temperature=0.0)
    with pytest.raises(mx.base.MXNetError):
        TemperatureSampler(top_k=0)


# ---------------------------------------------------------------------------
# declarative alert rules file
# ---------------------------------------------------------------------------

def test_alert_rules_file_loads_and_is_idempotent(tmp_path, monkeypatch,
                                                  _fresh_telemetry):
    rules = [
        {"name": "ops_queue_depth_high", "kind": "threshold",
         "series": "mxnet_serve_queue_depth", "query": "latest",
         "op": ">", "threshold": 100.0, "severity": "ticket",
         "annotations": {"summary": "queue building"}},
        {"name": "broken_rule", "kind": "no_such_kind"},
    ]
    path = tmp_path / "rules.json"
    path.write_text(json.dumps(rules))
    monkeypatch.setenv("MXNET_TELEMETRY_ALERT_RULES", str(path))
    mgr = telemetry.default_manager()
    # rules other subsystems own (a neighbour test file's training
    # watchdogs live for the process) are not this test's: count from here
    rules0 = len(mgr)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        added = telemetry.load_rules_file()
    assert [r.name for r in added] == ["ops_queue_depth_high"]
    assert any("invalid" in str(x.message) for x in w)
    rule = added[0]
    assert rule.annotations["source"] == str(path)
    assert len(mgr) == rules0 + 1
    # idempotent reload (every engine-driven recorder rebuild re-runs it)
    assert telemetry.load_rules_file() == []
    assert len(mgr) == rules0 + 1
    mgr.remove_rule("ops_queue_depth_high")

    # the recorder build path loads it too — operator SLOs are live the
    # moment something starts evaluating
    rec = telemetry.start_recorder(interval_s=30.0, window=10)
    try:
        assert any(r.name == "ops_queue_depth_high"
                   for r in mgr.rules())
        assert rec.alerts is mgr
    finally:
        telemetry.stop_recorder()
        mgr.remove_rule("ops_queue_depth_high")


def test_alert_rules_file_malformed_warns_not_raises(tmp_path,
                                                     monkeypatch):
    path = tmp_path / "broken.json"
    path.write_text("{not json")
    monkeypatch.setenv("MXNET_TELEMETRY_ALERT_RULES", str(path))
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert telemetry.load_rules_file() == []
    assert any("cannot load" in str(x.message) for x in w)
    monkeypatch.setenv("MXNET_TELEMETRY_ALERT_RULES",
                       str(tmp_path / "absent.json"))
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert telemetry.load_rules_file() == []
    assert any("cannot load" in str(x.message) for x in w)


# ---------------------------------------------------------------------------
# training-loop watchdog
# ---------------------------------------------------------------------------

def test_steptimer_heartbeat_and_watchdog(_fresh_telemetry):
    from mxnet_tpu.telemetry.step import StepTimer
    mgr = telemetry.default_manager()
    st = StepTimer(loop="wdtest")
    try:
        hbs = telemetry.heartbeats()
        assert "train.wdtest" in hbs
        assert hbs["train.wdtest"]["busy"] is False    # no step open
        rules = {r.name: r for r in mgr.rules()}
        assert "train_wdtest_stalled" in rules
        st.begin_step()
        hb = telemetry.heartbeats()["train.wdtest"]
        assert hb["busy"] is True and hb["kind"] == "train"
        # the watchdog rule reads the same heartbeat: a wedged open
        # step (no progress past the threshold) is active
        rule = rules["train_wdtest_stalled"]
        active, _, _ = rule.evaluate(
            None, heartbeats={"train.wdtest": {"busy": True,
                                               "age_s": 1e9}})
        assert active is True
        active, _, _ = rule.evaluate(
            None, heartbeats={"train.wdtest": {"busy": False,
                                               "age_s": 1e9}})
        assert active is False              # idle loop never pages
        st.end_step()
        assert telemetry.heartbeats()["train.wdtest"]["busy"] is False
    finally:
        st.close()
    assert "train.wdtest" not in telemetry.heartbeats()
    assert not any(r.name == "train_wdtest_stalled" for r in mgr.rules())


def test_steptimer_shared_watchdog_refcounts(_fresh_telemetry):
    from mxnet_tpu.telemetry.step import StepTimer
    mgr = telemetry.default_manager()
    a = StepTimer(loop="wdshare")
    b = StepTimer(loop="wdshare")       # same loop label: one rule
    assert sum(1 for r in mgr.rules()
               if r.name == "train_wdshare_stalled") == 1
    a.close()
    assert any(r.name == "train_wdshare_stalled" for r in mgr.rules())
    b.close()
    assert not any(r.name == "train_wdshare_stalled"
                   for r in mgr.rules())


# ---------------------------------------------------------------------------
# bench smoke under a forced host device count (tier-1, subprocess:
# XLA_FLAGS must be set before jax initializes)
# ---------------------------------------------------------------------------

def test_replica_bench_smoke_forced_devices():
    code = """
import sys
sys.path.insert(0, %r)
sys.path.insert(0, %r)
import serve_bench, decode_bench
row = serve_bench.run_replica_sweep(
    requests=48, repeats=1, replica_counts=(1, 2), hidden=32, layers=1)
assert row["device_count"] >= 2, row
assert row["retraces"] == 0, row
assert row["bitwise_identical"], row
assert [r["replicas"] for r in row["rows"]] == [1, 2]
row2 = decode_bench.run_replica_sweep(
    requests=8, slots=2, max_len=16, mean_new=4, hidden=8,
    repeats=1, replica_counts=(1, 2))
assert row2["retraces"] == 0, row2
assert row2["bitwise_identical"], row2
print("REPLICA_SMOKE_OK")
""" % (REPO, os.path.join(REPO, "perf"))
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("MXNET_TELEMETRY_PORT", None)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, (out.stdout, out.stderr)
    assert "REPLICA_SMOKE_OK" in out.stdout

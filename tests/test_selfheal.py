"""Self-healing serving tests (ISSUE 12): deterministic fault
injection (serving/faults.py), the automatic probation supervisor
(serving/supervisor.py), the SLO-driven overload regulator
(serving/regulator.py), and the riding satellites — cross-replica
decode work stealing, AOT write-path auto-prune, and the binary
ring-file flight-recorder window.

The two acceptance anchors:

- **chaos acceptance**: a seeded randomized-but-deterministic fault
  schedule (replica kills on both engine kinds + a prefill failure +
  one AOT-entry corruption) over a concurrent serve+decode run — no
  wedge, every offered request resolves (result, partial, or clean
  error), survivors bitwise vs the uninjected references, and the
  supervisor re-admits killed replicas with ZERO traces (AOT-drawn
  re-warm);
- **regulator acceptance**: synthetic overload drives the real
  ``serve_deadline_miss_burn`` rule to firing, the regulator tightens
  admission (cost-aware shed) until the rule resolves, then relaxes
  back to steady-state — observable via the rule states and the
  ``mxnet_serve_regulator_*`` gauges — and with faults + regulator
  DISABLED the engines are byte-for-byte the PR 11 stack.

Multi-replica engines run their replicas on one device
(``ctx=[cpu(0), cpu(0)]``), the test_replica idiom — self-healing is
device-count-independent.
"""
import importlib.util
import json
import os
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import serving, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.serving import (DecodeEngine, ServingEngine, StepProgram,
                               FaultInjected, FaultPlan, Regulator,
                               Supervisor, greedy_decode)
from mxnet_tpu.serving import faults, supervisor as supervisor_mod
from mxnet_tpu.serving.decode import DecodeRequest
from mxnet_tpu.telemetry import recorder as trec

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _import_path(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _import_tool(name):
    return _import_path(name, os.path.join(REPO, "tools", "%s.py" % name))


def _drain_default_manager():
    mgr = telemetry.default_manager()
    with mgr._lock:
        mgr._states.clear()
    with trec._HB_LOCK:
        trec._HEARTBEATS.clear()
    with trec._ENG_LOCK:
        trec._ENGINES.clear()


@pytest.fixture(autouse=True)
def _fresh_selfheal_plane(monkeypatch):
    """No fault plan, no supervisor singleton, clean telemetry plane —
    and verify no control-plane thread outlives its test."""
    for var in ("MXNET_FAULT_PLAN", "MXNET_SUPERVISOR",
                "MXNET_REGULATOR", "MXNET_AOT_CACHE_DIR",
                "MXNET_AOT_CACHE_MAX_MB", "MXNET_FLIGHT_RECORDER_DIR"):
        monkeypatch.delenv(var, raising=False)
    faults.clear()
    telemetry.set_enabled(None)
    telemetry.stop_recorder()
    _drain_default_manager()
    telemetry.reset()
    telemetry.stop_server()
    yield
    faults.clear()
    sup = supervisor_mod.get_supervisor()
    if sup is not None:
        sup.stop()
        supervisor_mod._SUP = None
        supervisor_mod._REFS = 0
    telemetry.stop_server()
    telemetry.stop_recorder()
    _drain_default_manager()
    telemetry.set_enabled(None)
    telemetry.reset()
    for name in ("mxnet-serve-supervisor",):
        assert not [t for t in threading.enumerate() if t.name == name]


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


def _sum_state_model(vocab=16, d=8, seed=0):
    """The test_decode prefill fixture: additive state, so prefill in
    one masked-sum dispatch matches teacher forcing at TOKEN level."""
    tok = mx.sym.Variable("token")
    s = mx.sym.Variable("s")
    emb = mx.sym.Embedding(tok, input_dim=vocab, output_dim=d,
                           name="emb")
    s2 = s + emb
    logits = mx.sym.FullyConnected(s2, num_hidden=vocab, name="out_fc")
    step = mx.sym.Group([logits, s2])
    prompt = mx.sym.Variable("prompt")
    plen = mx.sym.Variable("plen")
    pemb = mx.sym.Embedding(prompt, input_dim=vocab, output_dim=d,
                            name="emb")
    masked = mx.sym.SequenceMask(pemb, use_sequence_length=True,
                                 sequence_length=plen, axis=1)
    srow = mx.sym.sum(masked, axis=1)
    plogits = mx.sym.FullyConnected(srow, num_hidden=vocab,
                                    name="out_fc")
    prefill = mx.sym.Group([plogits, srow])
    rng = np.random.default_rng(seed)
    params = {
        "emb_weight": mx.nd.array(
            rng.standard_normal((vocab, d)).astype(np.float32)),
        "out_fc_weight": mx.nd.array(
            rng.standard_normal((vocab, d)).astype(np.float32)),
        "out_fc_bias": mx.nd.zeros((vocab,)),
    }
    return step, prefill, params, [{"name": "s", "shape": (d,)}]


# ---------------------------------------------------------------------------
# fault-plan grammar + determinism
# ---------------------------------------------------------------------------

def test_fault_plan_grammar():
    p = FaultPlan.from_spec(
        "decode.step:raise:on=5,replica=1;aot.load:corrupt:on=1;"
        "serve.dispatch:hang:hang_s=0.01,every=3")
    d = p.describe()
    assert [c["site"] for c in d["clauses"]] == \
        ["decode.step", "aot.load", "serve.dispatch"]
    assert d["clauses"][0]["labels"] == {"replica": "1"}
    assert d["clauses"][0]["times"] == 1        # bare on=N is one-shot
    # JSON form parses to the same clauses
    j = FaultPlan.from_spec(json.dumps([
        {"site": "decode.step", "action": "raise", "on": 5,
         "replica": 1}]))
    assert j.describe()["clauses"][0]["labels"] == {"replica": "1"}
    # typos are refused, not silently ignored
    with pytest.raises(MXNetError):
        FaultPlan.from_spec("decode.stp:raise:on=1")
    with pytest.raises(MXNetError):
        FaultPlan.from_spec("decode.step:explode:on=1")
    with pytest.raises(MXNetError):
        FaultPlan.from_spec("decode.step:corrupt:on=1")  # aot.load only
    with pytest.raises(MXNetError):
        FaultPlan.from_spec("decode.step")


def test_fault_trigger_determinism():
    """The same spec over the same hit sequence fires the same hits —
    counting triggers and the seeded coin both."""
    def run(spec, hits=64):
        faults.install(spec)
        fired = []
        for i in range(hits):
            try:
                faults.trip("serve.dispatch", replica="0")
            except FaultInjected:
                fired.append(i)
        faults.clear()
        return fired

    spec = "serve.dispatch:raise:p=0.25,seed=7,times=0"
    a, b = run(spec), run(spec)
    assert a and a == b                         # seeded coin replays
    c = run("serve.dispatch:raise:every=5,times=0")
    assert c == list(range(4, 64, 5))
    d = run("serve.dispatch:raise:after=60,times=0")
    assert d == list(range(60, 64))
    # label filter: hits on another replica do not advance the clause
    faults.install("serve.dispatch:raise:on=2,replica=1")
    faults.trip("serve.dispatch", replica="0")
    faults.trip("serve.dispatch", replica="1")
    with pytest.raises(FaultInjected):
        faults.trip("serve.dispatch", replica="1")
    faults.clear()


def test_admission_hang_stalls_submit():
    faults.install("admission.admit:hang:hang_s=0.15,on=1")
    adm = serving.AdmissionController(max_queue=4)
    from concurrent.futures import Future
    from mxnet_tpu.serving import Request
    t0 = time.monotonic()
    adm.admit(Request({}, ("g",), Future()))
    assert time.monotonic() - t0 >= 0.14        # the stall happened
    t0 = time.monotonic()
    adm.admit(Request({}, ("g",), Future()))    # one-shot clause spent
    assert time.monotonic() - t0 < 0.1
    faults.clear()


# ---------------------------------------------------------------------------
# inert when disabled: byte-for-byte the PR 11 stack
# ---------------------------------------------------------------------------

def test_inert_when_disabled():
    """No plan, no regulator, no supervisor: the sites are predicate
    no-ops, admission carries no pressure, and a multi-replica run is
    bitwise-identical to the single-replica reference — the PR 11
    contract intact under the new code."""
    assert faults.ACTIVE is False
    net, params = _mlp()
    ref = ServingEngine(net, params, {}, {"data": (6,)}, ctx=mx.cpu())
    eng = ServingEngine(net, params, {}, {"data": (6,)},
                        ctx=[mx.cpu(0), mx.cpu(0)])
    ref.warmup()
    eng.warmup()
    rng = np.random.default_rng(3)
    X = rng.standard_normal((16, 6)).astype(np.float32)
    try:
        # grouped submits: identical bucket composition on both
        # engines (the test_replica bitwise discipline)
        for lo in range(0, 16, 8):
            fr = [ref.submit(X[i]) for i in range(lo, lo + 8)]
            fe = [eng.submit(X[i]) for i in range(lo, lo + 8)]
            want = [f.result(timeout=60) for f in fr]
            got = [f.result(timeout=60) for f in fe]
            for w, g in zip(want, got):
                assert np.array_equal(w, g)
        st = eng.stats()
        assert eng._adm.pressure is None
        assert st["pressure"] is None
        assert st["supervisor"] == {"enabled": False}
        assert st["regulator"] == {"enabled": False}
        assert st["faults"] == {"active": False}
        assert eng._regulator is None and not eng._sup_owner
        # no fault series exists until a fault actually fires
        assert telemetry.registry().get(
            "mxnet_serve_faults_injected_total") is None
        assert supervisor_mod.get_supervisor() is None
    finally:
        ref.close()
        eng.close()


# ---------------------------------------------------------------------------
# regulator: cost-aware shedding + the closed SLO loop
# ---------------------------------------------------------------------------

def test_cost_aware_pressure_shed():
    from concurrent.futures import Future
    from mxnet_tpu.serving import Request, ServerOverloadError
    adm = serving.AdmissionController(max_queue=32)
    reqs = []
    for cost in (10, 500, 20, 300, 5):
        r = Request({}, ("g",), Future(), cost=cost)
        adm.admit(r)
        reqs.append(r)
    adm.apply_pressure(3)
    shed = [i for i, r in enumerate(reqs) if r.future.done()]
    assert shed == [1, 3]                       # highest costs first
    for i in shed:
        with pytest.raises(ServerOverloadError):
            reqs[i].future.result(timeout=0)
    assert adm.stats()["pressure"] == 3
    # pressure sheds are counted SEPARATELY from policy sheds: the
    # saturation burn rule's numerator includes mxnet_serve_shed_total,
    # and the regulator's own sheds must not re-fire the rule it is
    # resolving (positive-feedback guard)
    assert adm.stats()["pressure_shed"] == 2
    assert adm.stats()["shed"] == 0
    # at the limit, admit sheds cost-aware — an incoming request that
    # is itself the most expensive is the victim (rejected cleanly)
    with pytest.raises(ServerOverloadError):
        adm.admit(Request({}, ("g",), Future(), cost=10**6))
    assert len(adm) == 3
    # a cheap incoming one displaces the priciest queued instead
    cheap = Request({}, ("g",), Future(), cost=1)
    adm.admit(cheap)
    assert not cheap.future.done() and len(adm) == 3
    # withdrawing pressure restores the unregulated behavior
    adm.apply_pressure(None)
    assert adm.stats()["pressure"] is None
    for _ in range(29):
        adm.admit(Request({}, ("g",), Future(), cost=1))
    assert len(adm) == 32
    adm.close(drain=False)


def test_regulator_closes_slo_loop():
    """The acceptance loop: synthetic overload fires the REAL
    serve_deadline_miss_burn rule, the regulator tightens admission
    until the burn resolves, then relaxes back to steady-state — all
    visible in the rule states and the regulator gauges."""
    net, params = _mlp()
    eng = ServingEngine(net, params, {}, {"data": (6,)}, start=False,
                        max_queue=64)
    rec = telemetry.HistoryRecorder(interval_s=1.0, window=600,
                                    start=False)
    mgr = telemetry.default_manager()
    assert mgr.state_of("serve_deadline_miss_burn") == "inactive"
    reg = Regulator(eng._adm, engine_label=eng._tm.engine_label,
                    name="reg-test", manager=mgr,
                    recorder_fn=lambda: rec, floor=4, relax_after=1,
                    rules=("serve_deadline_miss_burn",), start=False)
    x = np.ones((6,), np.float32)
    try:
        rec.sample_now(evaluate=False)
        # overload: every queued request blows its deadline (the
        # worker is never started, so the admit-path sweep expires
        # them), burning the latency budget at ratio ~1
        doomed = [eng.submit(x, deadline_ms=1) for _ in range(6)]
        time.sleep(0.03)
        eng._adm.sweep()
        for f in doomed:
            with pytest.raises(serving.DeadlineExceededError):
                f.result(timeout=5)
        mgr.evaluate(rec, now=rec.sample_now(evaluate=False))
        assert mgr.state_of("serve_deadline_miss_burn") == "firing"
        d = reg.evaluate_once()
        assert d["action"] == "tighten"
        assert eng._adm.pressure == 32
        reg.evaluate_once()
        assert eng._adm.pressure == 16
        fam = telemetry.registry().get("mxnet_serve_regulator_limit")
        vals = {v[0]: inst.value for v, inst in fam.series()}
        assert vals[eng._tm.engine_label] == 16
        # recovery: enough successful traffic that the windowed miss
        # ratio falls back inside budget -> the rule resolves
        backlog = [eng.submit(x) for _ in range(60)]
        mgr.evaluate(rec, now=rec.sample_now(evaluate=False))
        assert mgr.state_of("serve_deadline_miss_burn") == "inactive"
        seen_relax = False
        for _ in range(6):
            d = reg.evaluate_once()
            seen_relax = seen_relax or d["action"] == "relax"
            if eng._adm.pressure is None:
                break
        assert seen_relax
        assert eng._adm.pressure is None        # steady state restored
        vals = {v[0]: inst.value for v, inst in fam.series()}
        assert vals[eng._tm.engine_label] == 64
        adj = telemetry.registry().get(
            "mxnet_serve_regulator_adjustments_total")
        directions = {v[1]: inst.value for v, inst in adj.series()
                      if v[0] == eng._tm.engine_label}
        assert directions["tighten"] >= 2 and directions["relax"] >= 1
        # anti-feedback guard: the tightening shed the 60-deep backlog
        # down to the limit, but those sheds land on the regulator's
        # OWN counter — mxnet_serve_shed_total (the saturation burn
        # numerator) must not move, or the regulator would re-fire the
        # rule it is resolving and ratchet to the floor forever
        assert eng._adm.stats()["pressure_shed"] > 0
        shed_fam = telemetry.registry().get("mxnet_serve_shed_total")
        assert sum(inst.value for _v, inst in shed_fam.series()) == 0
        rshed = telemetry.registry().get(
            "mxnet_serve_regulator_shed_total")
        assert sum(inst.value for _v, inst in rshed.series()) > 0
        for f in backlog:
            f.cancel()
    finally:
        reg.close()
        eng.close(drain=False)
    # close reclaimed this engine's regulator series
    fam = telemetry.registry().get("mxnet_serve_regulator_limit")
    assert all(v[0] != eng._tm.engine_label for v, _ in fam.series())


def test_regulator_env_wiring(monkeypatch):
    monkeypatch.setenv("MXNET_REGULATOR", "1")
    net, params = _mlp()
    eng = ServingEngine(net, params, {}, {"data": (6,)})
    label = eng._tm.engine_label
    assert eng._regulator is not None
    assert eng.stats()["regulator"]["enabled"] is True
    fam = telemetry.registry().get("mxnet_serve_regulator_limit")
    assert any(v[0] == label for v, _ in fam.series())
    eng.close()
    fam = telemetry.registry().get("mxnet_serve_regulator_limit")
    assert all(v[0] != label for v, _ in fam.series())
    assert not [t for t in threading.enumerate()
                if t.name.startswith("mxnet-serve-regulator")]


# ---------------------------------------------------------------------------
# supervisor: backoff ladder, permanent retirement, refcounting
# ---------------------------------------------------------------------------

class _StubReplica(object):
    def __init__(self, index):
        self.index = index
        self.healthy = True


class _StubEngine(object):
    """Scripted rehabilitate(): pops one ok/fail outcome per call."""
    _tm = None

    def __init__(self, n=2, script=()):
        self._replicas = [_StubReplica(i) for i in range(n)]
        self.script = list(script)
        self.calls = []

    def rehabilitate(self, replicas=None):
        idx = sorted(replicas)[0]
        self.calls.append(idx)
        ok = self.script.pop(0) if self.script else True
        if ok:
            self._replicas[idx].healthy = True
        return [{"replica": str(idx), "ok": ok,
                 "reason": None if ok else "probe diverged"}]


def test_supervisor_backoff_and_retirement():
    sup = Supervisor(backoff_s=1.0, backoff_max_s=64.0, max_attempts=3,
                     jitter=0.0, start=False)
    eng = _StubEngine(script=[False, False, False])
    sup.register(eng, name="stub")
    eng._replicas[0].healthy = False
    assert sup.poll_once(now=0.0) == []         # record created, waits
    assert sup.poll_once(now=0.5) == []         # not due yet
    out = sup.poll_once(now=1.0)                # first attempt: fail
    assert out and out[0]["ok"] is False and eng.calls == [0]
    assert sup.poll_once(now=2.9) == []         # backoff doubled to 2s
    out = sup.poll_once(now=3.0)                # second attempt: fail
    assert out and eng.calls == [0, 0]
    out = sup.poll_once(now=7.0)                # third: fail -> retired
    assert out and eng.calls == [0, 0, 0]
    st = sup.engine_state(eng)
    assert st["probations"]["0"]["state"] == "retired"
    assert sup.poll_once(now=1000.0) == []      # gives up for good
    assert eng.calls == [0, 0, 0]
    assert sup.state()["retired"] == 1
    # an operator rehabilitate() that heals the replica clears the
    # record: the next failure starts a fresh ladder
    eng._replicas[0].healthy = True
    sup.poll_once(now=1001.0)
    assert sup.engine_state(eng)["probations"] == {}
    eng._replicas[0].healthy = False
    sup.poll_once(now=1002.0)
    out = sup.poll_once(now=1003.0)             # base backoff again
    assert out and out[0]["ok"] is True
    assert eng._replicas[0].healthy


def test_supervisor_backoff_jitter_deterministic():
    a = Supervisor(backoff_s=1.0, jitter=0.25, seed=3, start=False)
    b = Supervisor(backoff_s=1.0, jitter=0.25, seed=3, start=False)
    for attempt in range(4):
        assert a._backoff("e", 0, attempt) == b._backoff("e", 0, attempt)
    assert a._backoff("e", 0, 1) != a._backoff("e", 1, 1)
    assert abs(a._backoff("e", 0, 2) / 4.0 - 1.0) <= 0.25


def test_supervisor_env_refcount(monkeypatch):
    """MXNET_SUPERVISOR=1: engines share one supervisor thread, the
    retirement rule registers once, and the last close() reclaims
    thread + rule + healthz section (reload loops leak nothing)."""
    from mxnet_tpu.telemetry import server as tserver
    monkeypatch.setenv("MXNET_SUPERVISOR", "1")
    net, params = _mlp()
    mgr = telemetry.default_manager()
    for _ in range(2):
        e1 = ServingEngine(net, params, {}, {"data": (6,)})
        e2 = ServingEngine(net, params, {}, {"data": (6,)})
        sup = supervisor_mod.get_supervisor()
        assert sup is not None
        assert e1.stats()["supervisor"]["enabled"] is True
        assert mgr.state_of(supervisor_mod._RETIRED_RULE) is not None
        with tserver._SECTIONS_LOCK:
            assert "supervisor" in tserver._HEALTHZ_SECTIONS
        e1.close()
        assert supervisor_mod.get_supervisor() is sup   # e2 still holds
        e2.close()
        assert supervisor_mod.get_supervisor() is None
        assert mgr.state_of(supervisor_mod._RETIRED_RULE) is None
        with tserver._SECTIONS_LOCK:
            assert "supervisor" not in tserver._HEALTHZ_SECTIONS
    assert not [t for t in threading.enumerate()
                if t.name == "mxnet-serve-supervisor"]


# ---------------------------------------------------------------------------
# cross-replica decode work stealing (ROADMAP a3)
# ---------------------------------------------------------------------------

def test_decode_work_stealing():
    """One saturated and one idle replica: a request pinned behind the
    full pool is stolen by the idle sibling on its next iteration
    instead of waiting out the long generation."""
    from concurrent.futures import Future
    step, params, state_info = _lstm_step()
    ref = StepProgram(step, params, {}, state_info, num_slots=1)
    truth = greedy_decode(ref, [3], 6, max_len=2048).tolist()
    eng = DecodeEngine(step, params, {}, state_info, num_slots=1,
                       max_len=2048, ctx=[mx.cpu(0), mx.cpu(0)])
    eng.warmup()
    try:
        fa = eng.submit([1], max_new_tokens=2000)   # saturates replica 0
        time.sleep(0.05)
        fb = eng.submit([2], max_new_tokens=2)      # replica 1, leaves fast
        time.sleep(0.05)
        # the steal window staged directly: a request pinned to the
        # SATURATED replica's pending queue (the failure-re-route
        # overflow producer, without needing a three-replica failure)
        c = DecodeRequest([3], 6, Future())
        with eng._dr_lock:
            eng._replicas[0].pending.append(c)
        rc = c.future.result(timeout=60)
        assert not fa.done()            # stolen, not waited out
        assert rc.finish_reason == "length"
        assert rc.tokens.tolist() == truth      # bitwise wherever seated
        st = eng.stats()["decode"]
        assert st["steals"] == 1
        fam = telemetry.registry().get("mxnet_serve_decode_steals_total")
        assert fam is not None and fam.series()[0][1].value == 1
        fb.result(timeout=60)
    finally:
        eng.close(drain=False)


# ---------------------------------------------------------------------------
# AOT cache: write-path auto-prune (ROADMAP b3)
# ---------------------------------------------------------------------------

def test_aot_auto_prune(tmp_path, monkeypatch):
    from mxnet_tpu.serving.aot_cache import AOTCache, iter_entries
    monkeypatch.setenv("MXNET_AOT_CACHE_MAX_MB",
                       str(3000.0 / (1 << 20)))    # ~3 KB budget
    cache = AOTCache(str(tmp_path))
    payload = b"x" * 700                           # ~1 KB with metadata
    for i in range(5):
        assert cache.store("k%d" % i, payload)
        time.sleep(0.01)                           # distinct created
    keys = [k for k, _m, _b, _meta in iter_entries(str(tmp_path))]
    assert cache.prunes > 0
    assert "k4" in keys                            # newest survives
    assert "k0" not in keys                        # oldest pruned
    total = sum(os.path.getsize(os.path.join(str(tmp_path), n))
                for n in os.listdir(str(tmp_path)))
    assert total <= 3000
    assert cache.stats()["prunes"] == cache.prunes
    # concurrent-writer tolerance: files vanishing mid-prune (another
    # writer's janitor won the race) must not raise or miscount
    for n in os.listdir(str(tmp_path)):
        os.unlink(os.path.join(str(tmp_path), n))
    cache._auto_prune()                            # nothing to do, no raise
    assert cache.store("fresh", payload)           # store still works


def test_aot_prune_protects_just_written_entry(tmp_path, monkeypatch):
    from mxnet_tpu.serving.aot_cache import AOTCache, iter_entries
    monkeypatch.setenv("MXNET_AOT_CACHE_MAX_MB", str(10.0 / (1 << 20)))
    cache = AOTCache(str(tmp_path))                # budget ~10 bytes
    assert cache.store("only", b"y" * 500)         # over budget alone
    keys = [k for k, _m, _b, _meta in iter_entries(str(tmp_path))]
    assert keys == ["only"]                        # never self-evicts


# ---------------------------------------------------------------------------
# binary ring-file flight-recorder window (ROADMAP 5c residual)
# ---------------------------------------------------------------------------

def test_ring_file_window(tmp_path, monkeypatch):
    """Writer + reader round trip through the recorder: every sample
    lands a record; a torn slot (the crash victim) is skipped; a
    process restart ADOPTS the file and extends the sequence; the
    standalone tool reader agrees with the library reader."""
    monkeypatch.setenv("MXNET_FLIGHT_RECORDER_DIR", str(tmp_path))
    rec = telemetry.HistoryRecorder(interval_s=1.0, window=16,
                                    start=False)
    c = telemetry.counter("mxnet_test_ring_total", "x")
    for i in range(5):
        c.inc()
        rec.sample_now(evaluate=False)
    path = os.path.join(str(tmp_path), "ring.bin")
    records = telemetry.RingFile.read_records(path)
    assert [r["seq"] for r in records] == [1, 2, 3, 4, 5]
    key = "mxnet_test_ring_total"
    assert [r["scalars"][key] for r in records] == [1, 2, 3, 4, 5]
    assert all("wall" in r and "t" in r for r in records)
    # torn slot: flip payload bytes of record 3 -> crc drops exactly it
    ring = trec.ring_file()
    with open(path, "r+b") as f:
        f.seek(telemetry.RingFile.HEADER + 2 * ring.slot_size
               + telemetry.RingFile.SLOT_HEADER)
        f.write(b"\xff\xff\xff")
    records = telemetry.RingFile.read_records(path)
    assert [r["seq"] for r in records] == [1, 2, 4, 5]
    # the standalone tool reader sees the same window and renders it
    td = _import_tool("telemetry_dump")
    assert [r["seq"] for r in td.read_ring(path)] == [1, 2, 4, 5]
    out = td.format_ring(td.read_ring(path), series=key)
    assert "delta=4" in out                 # 1 -> 5 across survivors
    rc = td.main(["ring", str(tmp_path), "--series", key])
    assert rc == 0
    # adoption: a "restarted process" (fresh writer) continues the seq
    trec._RINGFILE = None
    trec._RING_PATH = None
    rec2 = telemetry.HistoryRecorder(interval_s=1.0, window=16,
                                     start=False)
    rec2.sample_now(evaluate=False)
    records = telemetry.RingFile.read_records(path)
    assert records[-1]["seq"] == 6


def test_ring_file_wraparound(tmp_path):
    ring = telemetry.RingFile(str(tmp_path / "r.bin"), slot_size=512,
                              nslots=4)
    for i in range(10):
        assert ring.append({"t": float(i), "wall": 0.0,
                            "scalars": {"s": i}})
    records = telemetry.RingFile.read_records(str(tmp_path / "r.bin"))
    assert [r["seq"] for r in records] == [7, 8, 9, 10]
    # preallocated: the file never grows past its fixed geometry
    assert os.path.getsize(str(tmp_path / "r.bin")) == 16 + 4 * 512


def test_ring_file_oversized_sample_truncates_explicitly(tmp_path):
    ring = telemetry.RingFile(str(tmp_path / "r.bin"), slot_size=512,
                              nslots=2)
    big = {"series_%04d" % i: float(i) for i in range(400)}
    assert ring.append({"t": 0.0, "wall": 0.0, "scalars": big})
    rec = telemetry.RingFile.read_records(str(tmp_path / "r.bin"))[0]
    assert rec["truncated"] > 0
    assert 0 < len(rec["scalars"]) < 400


# ---------------------------------------------------------------------------
# chaos acceptance: the seeded fault schedule
# ---------------------------------------------------------------------------

def test_chaos_acceptance(tmp_path, monkeypatch):
    """The ISSUE 12 acceptance drill: a seeded fault schedule (serve
    replica kill + decode replica kill + one prefill failure + one
    AOT-entry corruption) over a concurrent serve+decode run.  No
    wedge, every request resolves, survivors bitwise, and the
    supervisor re-admits both killed replicas with zero traces."""
    monkeypatch.setenv("MXNET_AOT_CACHE_DIR", str(tmp_path))
    net, params = _mlp()
    dstep, dprefill, dparams, dstate = _sum_state_model()

    # cold pass populates the AOT cache so the injected engines (and
    # every supervisor re-warm) load with zero traces
    cold_s = ServingEngine(net, params, {}, {"data": (6,)})
    cold_s.warmup()
    cold_s.close()
    cold_d = DecodeEngine(dstep, dparams, {}, dstate, num_slots=2,
                          max_len=32, prefill_sym=dprefill)
    cold_d.warmup()
    cold_d.close()

    # ground truths, uninjected: batch-1 serve outputs + greedy decode
    rng = np.random.default_rng(0xC405)
    X = rng.standard_normal((40, 6)).astype(np.float32)
    ref_eng = ServingEngine(net, params, {}, {"data": (6,)})
    ref_eng.warmup()
    serve_truth = [ref_eng.predict(X[i], timeout=60) for i in range(40)]
    ref_eng.close()
    ref_prog = StepProgram(dstep, dparams, {}, dstate, num_slots=1)
    prompts = [[1], [2, 3], [4, 5, 6], [1, 2], [5], [3, 1, 2], [2],
               [4, 4], [1, 5, 2], [3]]
    decode_truth = {
        tuple(p): greedy_decode(ref_prog, p, 10, max_len=32).tolist()
        for p in prompts}

    # the seeded randomized-but-deterministic schedule
    serve_kill = int(rng.integers(3, 7))
    decode_kill = int(rng.integers(4, 9))
    prefill_hit = int(rng.integers(2, 5))
    plan = (";".join([
        "serve.dispatch:raise:on=%d,replica=0" % serve_kill,
        "decode.step:raise:on=%d,replica=0" % decode_kill,
        "decode.prefill:raise:on=%d" % prefill_hit,
        "aot.load:corrupt:on=1"]))
    faults.install(plan)

    eng_s = ServingEngine(net, params, {}, {"data": (6,)},
                          ctx=[mx.cpu(0), mx.cpu(0)])
    eng_d = DecodeEngine(dstep, dparams, {}, dstate, num_slots=2,
                         max_len=32, prefill_sym=dprefill,
                         ctx=[mx.cpu(0), mx.cpu(0)])
    sup = Supervisor(interval_s=0.05, backoff_s=0.05, jitter=0.0,
                     max_attempts=5)
    try:
        eng_s.warmup()
        eng_d.warmup()
        c_serve = eng_s.compile_count
        c_decode = eng_d.compile_count
        sup.register(eng_s, name="serve")
        sup.register(eng_d, name="decode")

        # concurrent serve + decode traffic under the schedule.  Serve
        # submits are single-file (bucket-1 batches: bucket
        # composition is the one legitimate float-divergence source,
        # so it must match the reference run's).
        serve_out = [None] * 40
        serve_err = []

        def serve_client():
            for i in range(40):
                try:
                    serve_out[i] = eng_s.predict(X[i], timeout=120)
                except (FaultInjected, MXNetError) as e:
                    serve_err.append((i, e))

        t = threading.Thread(target=serve_client)
        t.start()
        decode_futs = [(p, eng_d.submit(p, max_new_tokens=10))
                       for p in prompts]
        decode_res, decode_err = [], []
        for p, f in decode_futs:
            try:
                decode_res.append((p, f.result(timeout=120)))
            except (FaultInjected, MXNetError) as e:
                decode_err.append((p, e))
        t.join(timeout=180)
        assert not t.is_alive()                 # no wedge

        # every offered request resolved: result, partial, or clean error
        assert len(serve_err) + sum(o is not None for o in serve_out) == 40
        assert len(decode_res) + len(decode_err) == len(prompts)
        # the schedule actually fired everything it promised
        injected = faults.stats()["injected"]
        assert injected.get("serve.dispatch:raise") == 1
        assert injected.get("decode.step:raise") == 1
        assert injected.get("decode.prefill:raise") == 1
        assert injected.get("aot.load:corrupt") == 1
        assert len(serve_err) >= 1              # the killed dispatch
        assert len(decode_err) == 1             # the prefill victim
        # exactly one AOT reject across both engines, self-healed
        rejects = (eng_s.stats()["aot"]["rejects"]
                   + eng_d.stats()["decode"]["aot"]["rejects"])
        assert rejects == 1
        # survivors bitwise: serve vs the uninjected reference...
        for i, out in enumerate(serve_out):
            if out is not None:
                assert np.array_equal(out, serve_truth[i]), i
        # ...and decode vs greedy ground truth (partials are prefixes)
        for p, res in decode_res:
            want = decode_truth[tuple(p)]
            if res.finish_reason in ("length", "eos"):
                assert res.tokens.tolist() == want, p
            else:
                assert res.finish_reason == "error"
                assert res.tokens.tolist() == want[:len(res.tokens)], p

        # the supervisor re-admits both killed replicas (attempts
        # visible in its state), with ZERO compile-counter movement —
        # the re-warm is AOT-drawn
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            # (the supervisor books a rehab after the replica it
            # re-admitted reads healthy)
            if all(r.healthy for r in eng_s._replicas) and \
                    all(r.healthy for r in eng_d._replicas) and \
                    sup.state()["rehabs_ok"] >= 2:
                break
            time.sleep(0.05)
        st_s, st_d = eng_s.stats(), eng_d.stats()
        assert all(r["healthy"] for r in st_s["replicas"])
        assert all(r["healthy"] for r in st_d["decode"]["replicas"])
        assert st_s["replicas"][0]["probations"] == 1
        assert st_d["decode"]["replicas"][0]["probations"] == 1
        assert sup.state()["rehabs_ok"] >= 2
        assert eng_s.compile_count <= c_serve       # zero NEW traces
        assert st_s["replicas"][0]["compile_count"] == 0
        assert st_d["decode"]["replicas"][0]["compile_count"] == 0

        # the healed fleet serves bitwise again, still without a trace
        c_s2, c_d2 = eng_s.compile_count, eng_d.compile_count
        for i in range(8):
            assert np.array_equal(eng_s.predict(X[i], timeout=60),
                                  serve_truth[i])
        for p in prompts[:4]:
            res = eng_d.generate(p, max_new_tokens=10, timeout=60)
            assert res.tokens.tolist() == decode_truth[tuple(p)], p
        assert eng_s.compile_count == c_s2
        assert eng_d.compile_count == c_d2
    finally:
        sup.stop()
        faults.clear()
        eng_s.close(drain=False)
        eng_d.close(drain=False)


# ---------------------------------------------------------------------------
# bench smoke: availability == 1.0 under a replica-kill schedule
# ---------------------------------------------------------------------------

def test_serve_bench_faults_smoke():
    sb = _import_path("serve_bench",
                      os.path.join(REPO, "perf", "serve_bench.py"))
    row = sb.run_fault_availability(
        "serve.dispatch:raise:on=6,replica=0", requests=48,
        offered_batch=4, feature=32, hidden=32, classes=4, layers=1)
    assert row["availability"] == 1.0
    assert row["faults_injected"].get("serve.dispatch:raise") == 1
    assert row["client_retries"] >= 1           # the killed batch retried
    assert row["retraces"] == 0
    assert any(not r["healthy"] for r in row["replicas"])
    assert faults.ACTIVE is False               # bench cleans up

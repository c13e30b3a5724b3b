"""Unified fleet timeline tests (ISSUE 20): the wall-aligned event
plane (telemetry/timeline.py), Chrome trace_event export, the
``/timeline`` route, flight-bundle/rank-snapshot embedding, the
cross-rank merge in tools/telemetry_dump.py, the per-request autopsy
CLI (tools/request_autopsy.py), the metrics-doc drift gate
(tools/metrics_doc.py), and the SSE wall-clock ``ts`` satellite.

The two acceptance anchors:

- **chaos timeline**: a seeded PR-12-style fault schedule (serve
  replica kill + AOT-entry corruption + a decode-step hang) over a
  2-replica serve+decode fleet exports a Chrome trace that parses as
  valid trace_event JSON with per-replica lanes and injected-fault
  instant events — and ``request_autopsy`` on the hang-affected
  request names the fault-overlapped interval as the dominant cause;
- **discipline**: with the plane off, serving is bitwise-identical,
  the ring appends NOTHING, and (telemetry off entirely) the
  zero-instrument-call pin still holds — the PR 3/18 contract
  extended over the timeline.

Multi-replica engines run their replicas on one device
(``ctx=[cpu(0), cpu(0)]``), the test_replica idiom.
"""
import importlib.util
import json
import os
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.serving import DecodeEngine, ServingEngine, faults
from mxnet_tpu.telemetry import timeline

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


@pytest.fixture(autouse=True)
def _fresh_timeline(monkeypatch):
    for var in ("MXNET_FAULT_PLAN", "MXNET_TELEMETRY_TIMELINE",
                "MXNET_TELEMETRY_TIMELINE_CAP"):
        monkeypatch.delenv(var, raising=False)
    faults.clear()
    telemetry.set_enabled(None)
    telemetry.reset()
    timeline.reset()
    telemetry.stop_server()
    telemetry.stop_recorder()
    yield
    faults.clear()
    telemetry.stop_server()
    telemetry.stop_recorder()
    telemetry.set_enabled(None)
    telemetry.reset()
    timeline.reset()


# ---------------------------------------------------------------------------
# the ring
# ---------------------------------------------------------------------------

def test_ring_records_dual_stamps_and_evicts():
    tl = timeline.Timeline(capacity=4)
    t0 = time.perf_counter()
    tl.complete("serve.dispatch", "serve", "replica:0", t0, t0 + 0.25,
                args={"bucket": 8})
    ev = tl.events()[0]
    assert ev["ph"] == "X" and ev["dur"] == pytest.approx(0.25)
    assert ev["mono"] == t0                       # native stamp kept
    # wall stamp = anchor conversion of the SAME monotonic stamp
    assert ev["wall"] == pytest.approx(timeline.wall_of_perf(t0))
    assert abs(ev["wall"] - time.time()) < 5.0    # sane epoch seconds
    tl.instant("fault:decode.step", "faults", "faults")
    tl.counter("serve.queue_depth", "serve", "serve", 3)
    assert [e["ph"] for e in tl.events()] == ["X", "i", "C"]
    # bounded: 6 appends into capacity 4 evicts the oldest 2
    for i in range(3):
        tl.instant("mark%d" % i, "serve", "serve")
    assert tl.appended() == 6
    assert tl.dropped() == 2
    assert len(tl.events()) == 4
    names = [e["name"] for e in tl.events()]
    assert names == ["serve.queue_depth", "mark0", "mark1", "mark2"]
    # seq is strictly increasing across the whole lifetime
    seqs = [e["seq"] for e in tl.events()]
    assert seqs == sorted(seqs) and len(set(seqs)) == 4


def test_window_filter_and_snapshot_shape():
    tl = timeline.Timeline(capacity=64)
    old = time.perf_counter() - 120.0             # 2 minutes ago
    tl.complete("old", "serve", "serve", old, old + 0.001)
    tl.instant("new", "serve", "serve")
    assert [e["name"] for e in tl.events(window_s=60.0)] == ["new"]
    snap = tl.snapshot(window_s=60.0)
    assert snap["format"] == "mxnet_tpu.telemetry/timeline-1"
    assert snap["appended"] == 2 and snap["dropped"] == 0
    assert [e["name"] for e in snap["events"]] == ["new"]
    json.dumps(snap)                              # JSON-able end to end
    # limit keeps the NEWEST events
    tl2 = timeline.Timeline(capacity=64)
    for i in range(10):
        tl2.instant("m%d" % i, "serve", "serve")
    assert [e["name"] for e in tl2.snapshot(limit=3)["events"]] \
        == ["m7", "m8", "m9"]


def test_mono_clock_feed_aligns_with_perf_feed():
    """Lock holds measure with time.monotonic, spans with
    perf_counter — both convert onto ONE wall axis through the import
    anchor, so cross-plane ordering inside a process is coherent."""
    tl = timeline.Timeline(capacity=16)
    p = time.perf_counter()
    m = time.monotonic()
    tl.complete("span", "serve", "serve", p - 0.010, p)
    tl.complete_mono("lock:x", "locks", "locks", m - 0.010, m)
    a, b = tl.events()
    assert abs(a["wall"] - b["wall"]) < 0.05


def test_module_feeds_self_gate(monkeypatch):
    telemetry.set_enabled(True)
    timeline.instant("alert.firing", "alerts", "alerts")
    assert timeline.get().appended() == 1
    # plane kill switch: feeds append nothing, ring untouched
    monkeypatch.setenv("MXNET_TELEMETRY_TIMELINE", "0")
    timeline.instant("alert.firing", "alerts", "alerts")
    timeline.counter("c", "serve", "serve", 1)
    timeline.complete("x", "serve", "serve", 0.0, 1.0)
    assert timeline.get().appended() == 1
    # telemetry master switch wins over the plane var
    monkeypatch.setenv("MXNET_TELEMETRY_TIMELINE", "1")
    telemetry.set_enabled(False)
    timeline.instant("alert.firing", "alerts", "alerts")
    assert timeline.get().appended() == 1


def test_lock_feed_thresholds_and_never_materializes():
    telemetry.set_enabled(True)
    # no singleton yet: the sanitizer feed must not create one (its
    # record path runs where even creation-lock acquisition is banned)
    assert timeline.peek() is None
    timeline.lock_feed("engine.state", 0.5)
    assert timeline.peek() is None
    tl = timeline.get()
    timeline.lock_feed("engine.state", 0.5)       # above 1 ms default
    timeline.lock_feed("engine.state", 0.0001)    # micro-hold: skipped
    evs = tl.events()
    assert len(evs) == 1
    assert evs[0]["name"] == "lock:engine.state"
    assert evs[0]["dur"] == pytest.approx(0.5, rel=1e-3)


# ---------------------------------------------------------------------------
# Chrome trace_event export
# ---------------------------------------------------------------------------

def test_chrome_trace_export_shape():
    tl = timeline.Timeline(capacity=64)
    t = time.perf_counter()
    tl.complete("serve.dispatch", "serve", "replica:0", t, t + 0.010,
                args={"bucket": 8})
    tl.complete("serve.dispatch", "serve", "replica:1", t + 0.002,
                t + 0.005)
    tl.instant("fault:serve.dispatch", "faults", "faults",
               args={"site": "serve.dispatch"})
    tl.counter("regulator.limit", "regulator", "regulator", 64)
    doc = timeline.export_chrome_trace(tl.events(), rank=3)
    # valid trace_event JSON end to end
    doc = json.loads(json.dumps(doc))
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    assert all(e["pid"] == 3 for e in evs)
    # B/E pairing balances per (tid, name)
    b = sum(1 for e in evs if e["ph"] == "B")
    e_ = sum(1 for e in evs if e["ph"] == "E")
    assert b == e_ == 2
    # each lane got a thread_name metadata event
    lanes = {e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert lanes == {"replica:0", "replica:1", "faults", "regulator"}
    # instants carry thread scope; counters carry their value
    inst = [e for e in evs if e["ph"] == "i"]
    assert inst and inst[0]["s"] == "t"
    cnt = [e for e in evs if e["ph"] == "C"]
    assert cnt and cnt[0]["args"] == {"value": 64}
    # ts is absolute wall microseconds (cross-rank concatenation key)
    t0 = min(e["ts"] for e in evs if "ts" in e)
    assert abs(t0 / 1e6 - time.time()) < 10.0


# ---------------------------------------------------------------------------
# engine feeds + discipline pins
# ---------------------------------------------------------------------------

def test_serve_and_decode_feed_lanes():
    telemetry.set_enabled(True)
    net, params = _mlp()
    eng = ServingEngine(net, params, {}, {"data": (6,)},
                        ctx=[mx.cpu(0), mx.cpu(0)])
    eng.warmup()
    for _ in range(3):
        eng.predict(np.ones((6,), np.float32), timeout=60)
    step, sparams, state_info = _lstm_step()
    de = DecodeEngine(step, sparams, {}, state_info, num_slots=2,
                      max_len=32)
    de.submit([1, 2], max_new_tokens=3,
              request_id="tl-req").result(timeout=120)
    tl = timeline.get()
    names = {e["name"] for e in tl.events()}
    assert {"serve.dispatch", "serve.batch_occupancy",
            "serve.queue_depth", "decode.step", "decode.join",
            "decode.leave", "decode.token"} <= names
    lanes = {e["lane"] for e in tl.events()}
    assert "replica:0" in lanes and "decode.tokens" in lanes
    assert any(l.startswith("decode:") for l in lanes)
    # dispatch events carry the batch context autopsies need
    disp = [e for e in tl.events() if e["name"] == "serve.dispatch"]
    assert disp and {"bucket", "live", "compiled"} \
        <= set(disp[0]["args"])
    # token instants are tagged with the request id
    toks = [e for e in tl.events() if e["name"] == "decode.token"]
    assert toks and all(e["args"]["request"] == "tl-req" for e in toks)
    eng.close()
    de.close()
    assert eng._tl is None and de._tl is None


def test_disabled_plane_is_bitwise_and_appends_nothing(monkeypatch):
    """The PR 3/18 discipline over the timeline: plane off => same
    bytes out, zero ring appends, no engine-held reference."""
    telemetry.set_enabled(True)
    net, params = _mlp()
    x = np.ones((6,), np.float32)

    marks = []
    inner = timeline._annotation
    monkeypatch.setattr(timeline, "_annotation",
                        lambda name: marks.append(name) or inner(name))
    step, sparams, state_info = _lstm_step()

    def decode():
        de = DecodeEngine(step, sparams, {}, state_info, num_slots=2,
                          max_len=32)
        try:
            futs = [de.submit([1, 2, 3], max_new_tokens=5),
                    de.submit([4], max_new_tokens=4)]
            return de, [list(f.result(timeout=120).tokens) for f in futs]
        finally:
            de.close()

    monkeypatch.setenv("MXNET_TELEMETRY_TIMELINE", "0")
    timeline.reset()
    eng = ServingEngine(net, params, {}, {"data": (6,)}, ctx=mx.cpu())
    eng.warmup()
    off = eng.predict(x, timeout=60)
    assert eng._tl is None
    de, toks_off = decode()
    assert all(r.program._tl is None and r.program.last_split is None
               for r in de._replicas)
    # no append and no profiler annotation anywhere on the serving path
    assert timeline.peek() is None or timeline.peek().appended() == 0
    assert marks == []
    eng.close()

    monkeypatch.setenv("MXNET_TELEMETRY_TIMELINE", "1")
    timeline.reset()
    eng = ServingEngine(net, params, {}, {"data": (6,)}, ctx=mx.cpu())
    eng.warmup()
    on = eng.predict(x, timeout=60)
    assert eng._tl is not None
    assert timeline.get().appended() > 0
    _de, toks_on = decode()
    assert "mx:decode.step" in marks and "mx:serve.dispatch" in marks
    eng.close()
    np.testing.assert_array_equal(off, on)
    assert toks_off == toks_on


def test_telemetry_off_zero_instrument_calls_and_zero_appends():
    """Telemetry off entirely: the engine makes ZERO registry
    instrument calls (the PR 3 pin) and the timeline ring never
    materializes — the new plane rides the same discipline."""
    telemetry.set_enabled(False)
    reg = telemetry.registry()
    base = reg.instrument_calls()
    net, params = _mlp()
    eng = ServingEngine(net, params, {}, {"data": (6,)}, ctx=mx.cpu())
    eng.warmup()
    eng.predict(np.ones((6,), np.float32), timeout=60)
    eng.close()
    assert reg.instrument_calls() == base
    assert timeline.peek() is None


# ---------------------------------------------------------------------------
# the span seam (ISSUE 24): one interval, every live sink
# ---------------------------------------------------------------------------

def test_span_feeds_ring_chrome_ring_and_current_trace(tmp_path):
    from mxnet_tpu import profiler
    telemetry.set_enabled(True)
    profiler.clear()
    profiler.profiler_set_config(filename=str(tmp_path / "p.json"))
    profiler.profiler_set_state("run")
    try:
        with telemetry.trace("by-hand") as tc:
            with timeline.span("executor.forward", "executor", "executor",
                               chrome=("forward", "forward")) as sp:
                sp.args = {"k": 1}
    finally:
        profiler.profiler_set_state("stop")
    # (a kept trace mirrors its tree into the ring too, on lane "trace")
    ev = [e for e in timeline.get().events()
          if e["name"] == "executor.forward" and e["lane"] == "executor"]
    assert len(ev) == 1 and ev[0]["args"] == {"k": 1}
    # every sink got the stamps the span read
    assert ev[0]["mono"] == sp.t0
    assert ev[0]["dur"] == pytest.approx(sp.t1 - sp.t0)
    chrome = [e for e in json.loads(profiler.dumps())["traceEvents"]
              if e["name"] == "forward"]
    assert len(chrome) == 1 and chrome[0]["cat"] == "forward"
    assert chrome[0]["dur"] == pytest.approx((sp.t1 - sp.t0) * 1e6)
    kids = telemetry.get_trace(tc.trace_id)["root"]["children"]
    assert [k["name"] for k in kids] == ["executor.forward"]
    profiler.clear()


def test_span_drop_and_plane_off_record_nothing(monkeypatch):
    telemetry.set_enabled(True)
    tl = timeline.get()
    with timeline.span("decode.step", "decode", "decode:0", tl=tl) as sp:
        sp.drop()
    assert tl.appended() == 0 and sp.t1 is not None
    monkeypatch.setenv("MXNET_TELEMETRY_TIMELINE", "0")
    marks = []
    monkeypatch.setattr(timeline, "_annotation", marks.append)
    with timeline.span("fit.optimizer", "train", "train:fit") as sp:
        with sp.child("update/w"):
            pass
    # plane off: still a context manager with stamps, and nothing else
    assert sp.t1 >= sp.t0 and marks == [] and tl.appended() == 0


def test_decode_ring_splits_the_step_and_the_first_token():
    """One ``decode.step`` a step, its host round trip split where it
    happens, and one ``decode.first_token`` a request whose two parts
    add up to first token minus enqueue."""
    telemetry.set_enabled(True)
    step, sparams, state_info = _lstm_step()
    de = DecodeEngine(step, sparams, {}, state_info, num_slots=2,
                      max_len=32)
    de.warmup()
    base = timeline.get().appended()
    steps0 = de.stats()["decode"]["steps"]
    firsts = {}
    subs = {}
    futs = []
    for i, prompt in enumerate(([1, 2, 3], [4, 5], [6])):
        def on_token(tok, _i=i):
            firsts.setdefault(_i, time.perf_counter())
        subs[i] = time.perf_counter()
        futs.append(de.submit(prompt, max_new_tokens=3, on_token=on_token))
    for f in futs:
        f.result(timeout=120)
    steps = de.stats()["decode"]["steps"] - steps0
    ttft = telemetry.registry().collect()[
        "mxnet_serve_decode_ttft_seconds"]["series"][0]
    de.close()
    # (a request trace the sampler kept mirrors its tree on lane "trace")
    evs = [e for e in timeline.get().events()
           if e["seq"] > base and e["cat"] == "decode"
           and e["lane"] != "trace"]
    by = {}
    for e in evs:
        by.setdefault(e["name"], []).append(e)
    assert len(by["decode.step"]) == steps
    for e in by["decode.step"]:
        a = e["args"]
        assert {"live", "tokens", "dispatch_ms", "read_ms", "ahead",
                "discarded"} <= set(a)
        assert a["dispatch_ms"] > 0 and a["read_ms"] > 0
        assert a["read_ms"] <= e["dur"] * 1e3
    # one event a step, written by the iteration that reads it; that
    # iteration also dispatched the step after, if that one went out
    # ahead: an event's interval then holds its own read and the next
    # event's dispatch
    for e, after in zip(by["decode.step"], by["decode.step"][1:]):
        if after["args"]["ahead"]:
            assert e["args"]["read_ms"] + after["args"]["dispatch_ms"] \
                <= e["dur"] * 1e3
    # ahead of a read, but for the first step of a burst (one burst,
    # unless this thread was held up between its three submits)
    ahead = [e["args"]["ahead"] for e in by["decode.step"]]
    assert ahead[0] == 0 and sum(ahead) >= steps - 3
    # the ring's budget: one event a step, three a request
    assert len(by["decode.first_token"]) == 3
    assert len(by["decode.join"]) == len(by["decode.leave"]) == 3
    assert set(by) == {"decode.step", "decode.first_token", "decode.join",
                       "decode.leave"}
    total = 0.0
    for e, i in zip(sorted(by["decode.first_token"],
                           key=lambda e: e["args"]["enqueued"]), range(3)):
        a = e["args"]
        assert a["prompt_len"] == 3 - i
        # enqueue stamp and first-token stamp bracket the caller's own
        assert subs[i] <= a["enqueued"]
        first = a["enqueued"] + (a["queue_wait_ms"]
                                 + a["prompt_feed_ms"]) / 1e3
        assert a["enqueued"] <= first <= firsts[i]
        assert a["queue_wait_ms"] >= 0 and a["prompt_feed_ms"] > 0
        total += (a["queue_wait_ms"] + a["prompt_feed_ms"]) / 1e3
    # the same interval the TTFT histogram observed, on one clock
    assert ttft["count"] == 3
    assert total == pytest.approx(ttft["sum"], rel=1e-6)


def _host_spans(trace_dir):
    """One list of ``(name, start_ns, end_ns)`` a thread line: the
    program's ``mx:`` annotations on the trace's ``/host:`` planes."""
    import glob
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    assert files, "the profiler wrote no .xplane.pb"
    out = []
    for plane in ProfileData.from_file(files[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in line.events if e.name.startswith("mx:")]
            if evs:
                out.append(evs)
    return out


@pytest.mark.parametrize("built_on", [True, False])
def test_rehabilitated_replica_times_with_the_engines_gate(monkeypatch,
                                                           built_on):
    """A replica rebuilt after the plane's variable flipped follows the
    engine that owns it: its step program splits its steps iff
    ``_step_once`` reads the split, so the rebuilt replica serves."""
    import warnings
    telemetry.set_enabled(True)
    monkeypatch.setenv("MXNET_TELEMETRY_TIMELINE", "1" if built_on else "0")
    timeline.reset()
    step, sparams, state_info = _lstm_step()
    de = DecodeEngine(step, sparams, {}, state_info, num_slots=2,
                      max_len=32, default_deadline_ms=0,
                      ctx=[mx.cpu(0), mx.cpu(0)])
    de.warmup()
    want = list(de.generate([1, 2], max_new_tokens=4, timeout=120).tokens)
    bad = de._replicas[0]
    bad.program.step = lambda *a, **k: (
        (_ for _ in ()).throw(RuntimeError("induced step failure")))
    monkeypatch.setenv("MXNET_TELEMETRY_TIMELINE", "0" if built_on else "1")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        for _ in range(10):
            if not bad.healthy:
                break
            de.generate([1], max_new_tokens=2, timeout=120)
        assert not bad.healthy
        assert de.rehabilitate() == [{"replica": "0", "ok": True,
                                      "reason": None}]
        assert all((r.program._tl is not None) == built_on
                   and r.program._tl is de._tl for r in de._replicas)
        base = timeline.get().appended() if built_on else 0
        ticks0 = [r.program._tick for r in de._replicas]
        # more requests at once than one replica has slots: both step
        futs = [de.submit([1, 2], max_new_tokens=4) for _ in range(6)]
        for f in futs:
            assert list(f.result(timeout=120).tokens) == want
    st = de.stats()["decode"]
    ticks = [r.program._tick for r in de._replicas]
    de.close()
    assert [r["healthy"] for r in st["replicas"]] == [True, True]
    assert all(t > t0 for t, t0 in zip(ticks, ticks0))
    if built_on:
        steps = [e for e in timeline.get().events()
                 if e["seq"] > base and e["name"] == "decode.step"]
        assert {e["lane"] for e in steps} == {"decode:0", "decode:1"}
        assert all(e["args"]["dispatch_ms"] > 0 for e in steps)
    else:
        # (feeds that gate per call, as a kept request trace's mirror,
        # follow the variable; the engine's own do not)
        tl = timeline.peek()
        assert tl is None or not [e for e in tl.events()
                                  if e["cat"] == "decode"
                                  and e["lane"] != "trace"]


@pytest.mark.parametrize("optimizer, update_marks", [
    ("nag", ["mx:update/fc1_weight", "mx:update/fc1_bias",
             "mx:update/fc2_weight", "mx:update/fc2_bias"]),
    ("sgd", ["mx:update/multi_tensor"])])
def test_profiler_trace_holds_the_programs_spans(tmp_path, optimizer,
                                                 update_marks):
    """A ``jax.profiler`` trace around a few decode steps and a short
    ``fit`` holds the program's spans on a ``/host:`` plane — the
    device's clock — children inside their parents."""
    import jax
    telemetry.set_enabled(True)
    step, sparams, state_info = _lstm_step()
    de = DecodeEngine(step, sparams, {}, state_info, num_slots=2,
                      max_len=32)
    de.warmup()
    X = np.random.RandomState(0).randn(16, 6).astype(np.float32)
    Y = np.array([0, 1, 2, 3] * 4, np.float32)
    it = mx.io.NDArrayIter(X, Y, batch_size=8)
    mod = mx.mod.Module(_mlp()[0], context=mx.cpu())
    jax.profiler.start_trace(str(tmp_path))
    try:
        de.submit([1, 2, 3], max_new_tokens=3).result(timeout=120)
        mod.fit(it, num_epoch=1, optimizer=optimizer,
                optimizer_params={"learning_rate": 0.1})
    finally:
        jax.profiler.stop_trace()
        de.close()
    lines = _host_spans(str(tmp_path))
    names = {n for evs in lines for n, _s, _e in evs}
    assert {"mx:decode.step", "mx:decode.step.scan",
            "mx:decode.step.dispatch", "mx:decode.step.read",
            "mx:decode.step.deliver", "mx:fit.step", "mx:fit.fwd_bwd",
            "mx:executor.forward_backward", "mx:fit.optimizer"} <= names

    def inside(child, parent):
        """Every ``child`` event lies inside a ``parent`` event of its
        own thread."""
        n = 0
        for evs in lines:
            parents = [(s, e) for name, s, e in evs if name == parent]
            for name, s, e in evs:
                if name == child:
                    n += 1
                    assert any(ps <= s and e <= pe for ps, pe in parents), \
                        "%s outside every %s" % (child, parent)
        assert n, "no %s event" % child
    inside("mx:decode.step.dispatch", "mx:decode.step")
    inside("mx:decode.step.read", "mx:decode.step")
    inside("mx:fit.optimizer", "mx:fit.step")
    for name in update_marks:
        inside(name, "mx:fit.optimizer")
    inside("mx:executor.forward_backward", "mx:fit.fwd_bwd")
    # two steps of two batches: one mark an update program, which is one
    # a parameter a step, or one a step where the optimizer takes them all
    upd = [n for evs in lines for n, _s, _e in evs
           if n.startswith("mx:update/")]
    assert sorted(upd) == sorted(2 * update_marks)


# ---------------------------------------------------------------------------
# /timeline route, flight bundles, rank snapshots
# ---------------------------------------------------------------------------

def test_http_timeline_route_window_and_chrome():
    telemetry.set_enabled(True)
    tl = timeline.get()
    t = time.perf_counter()
    tl.complete("serve.dispatch", "serve", "replica:0", t - 200.0,
                t - 199.9)
    tl.instant("alert.firing", "alerts", "alerts")
    srv = telemetry.start_server(0, host="127.0.0.1")
    base = "http://127.0.0.1:%d" % srv.port
    doc = json.load(urllib.request.urlopen(base + "/timeline"))
    assert doc["format"] == "mxnet_tpu.telemetry/timeline-1"
    assert len(doc["events"]) == 2
    # scrape stamps ride every response: the cross-rank skew anchors
    assert abs(doc["scrape_ts"] - time.time()) < 5.0
    assert "scrape_monotonic" in doc
    # trailing window drops the 200 s old dispatch
    win = json.load(urllib.request.urlopen(base + "/timeline?window=60"))
    assert [e["name"] for e in win["events"]] == ["alert.firing"]
    # chrome export straight off the endpoint
    ch = json.load(urllib.request.urlopen(
        base + "/timeline?format=chrome&rank=2"))
    assert ch["otherData"]["rank"] == 2
    assert any(e["ph"] == "i" for e in ch["traceEvents"])
    # bad window is a 400, not a 500
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(base + "/timeline?window=nope")
    assert ei.value.code == 400


def test_timeline_disabled_route_503(monkeypatch):
    telemetry.set_enabled(True)
    monkeypatch.setenv("MXNET_TELEMETRY_TIMELINE", "0")
    srv = telemetry.start_server(0, host="127.0.0.1")
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(
            "http://127.0.0.1:%d/timeline" % srv.port)
    assert ei.value.code == 503


def test_flight_bundle_and_rank_snapshot_carry_timeline(tmp_path):
    telemetry.set_enabled(True)
    timeline.get().instant("fault:serve.dispatch", "faults", "faults")
    fr = telemetry.FlightRecorder(str(tmp_path), min_interval_s=0.0)
    path = fr.dump("test")
    with open(path) as f:
        bundle = json.load(f)
    assert bundle["timeline"]["events"]
    names = [e["name"] for e in bundle["timeline"]["events"]]
    assert "fault:serve.dispatch" in names
    # the dump itself leaves a mark on the timeline (visible in the
    # NEXT bundle / live scrapes)
    assert any(e["name"] == "flight.dump"
               for e in timeline.get().events())
    # dump_state snapshots embed the same section
    snap_path = os.path.join(str(tmp_path), "snap.json")
    telemetry.dump_state(snap_path)
    with open(snap_path) as f:
        snap = json.load(f)
    assert snap["timeline"]["events"]


# ---------------------------------------------------------------------------
# cross-rank merge + CLI
# ---------------------------------------------------------------------------

def _rank_doc(rank, names, wall0, scrape_ts):
    evs = [{"seq": i + 1, "ph": "i", "name": n, "cat": "serve",
            "lane": "serve", "wall": wall0 + i * 0.010,
            "mono": i * 0.010} for i, n in enumerate(names)]
    return {"format": "mxnet_tpu.telemetry/1",
            "rank": rank, "scrape_ts": scrape_ts,
            "metrics": {},
            "timeline": {"format": "mxnet_tpu.telemetry/timeline-1",
                         "capacity": 64, "appended": len(evs),
                         "dropped": 1, "window_s": None,
                         "wall_anchor": [wall0, 0.0, 0.0],
                         "events": evs}}


def test_merge_timelines_wall_orders_and_estimates_skew(tmp_path):
    td = _import_tool("telemetry_dump")
    w = time.time()
    d0 = _rank_doc(0, ["a0", "b0"], w, scrape_ts=w + 1.0)
    d1 = _rank_doc(1, ["a1", "b1"], w + 0.005, scrape_ts=w + 3.5)
    merged = td.merge_timelines([("0", d0), ("1", d1)])
    assert merged["skew_est_s"] == pytest.approx(2.5, abs=0.01)
    assert merged["dropped"] == 2
    # wall-interleaved: a0(w) a1(w+5ms) b0(w+10ms) b1(w+15ms)
    assert [e["name"] for e in merged["events"]] \
        == ["a0", "a1", "b0", "b1"]
    assert [e["rank"] for e in merged["events"]] == ["0", "1", "0", "1"]

    # the CLI merges files, exports chrome with one pid per rank
    p0 = tmp_path / "telemetry_rank0.json"
    p1 = tmp_path / "telemetry_rank1.json"
    p0.write_text(json.dumps(d0))
    p1.write_text(json.dumps(d1))
    out = tmp_path / "fleet.json"
    rc = td.main(["timeline", str(p0), str(p1), "--chrome", str(out)])
    assert rc == 0
    chrome = json.loads(out.read_text())
    pids = {e["pid"] for e in chrome["traceEvents"]}
    assert len(pids) == 2
    pnames = {e["args"]["name"] for e in chrome["traceEvents"]
              if e["ph"] == "M" and e["name"] == "process_name"}
    assert pnames == {"rank 0", "rank 1"}


def test_aggregate_carries_timeline_and_skew(tmp_path, capsys):
    td = _import_tool("telemetry_dump")
    w = time.time()
    (tmp_path / "telemetry_rank0.json").write_text(
        json.dumps(_rank_doc(0, ["a0"], w, scrape_ts=w)))
    (tmp_path / "telemetry_rank1.json").write_text(
        json.dumps(_rank_doc(1, ["a1"], w, scrape_ts=w + 2.0)))
    out = tmp_path / "merged.json"
    # directory source: aggregate expands telemetry_rank*.json itself
    rc = td.main(["aggregate", str(tmp_path), "--out", str(out)])
    assert rc == 0
    merged = json.loads(out.read_text())
    assert merged["timeline_skew_s"] == pytest.approx(2.0, abs=0.01)
    assert {e["name"] for e in merged["timeline"]["events"]} \
        == {"a0", "a1"}
    assert {e["rank"] for e in merged["timeline"]["events"]} \
        == {"0", "1"}


# ---------------------------------------------------------------------------
# SSE ts satellite
# ---------------------------------------------------------------------------

def test_sse_frames_stamped_with_publish_ts():
    from mxnet_tpu.telemetry.server import _EventHub
    hub = _EventHub(replay=8, sub_capacity=8)
    before = time.time()
    first = hub.publish("alert", {"n": 1})
    after = time.time()
    q, _, _ = hub.subscribe()
    hub.publish("alert", {"n": 2})
    _, _, payload = q.get_nowait()
    ts = json.loads(payload)["ts"]
    assert before <= ts <= time.time()
    # replay hands back the ORIGINAL publish stamp, not replay time
    q2, replayed, reset = hub.subscribe(last_event_id=0)
    hub.unsubscribe(q2)
    assert not reset
    ts_replay = json.loads(replayed[0][2])["ts"]
    assert before <= ts_replay <= after
    # a publisher's own ts wins (the stamp is additive, never clobbers)
    hub.publish("alert", {"n": 3, "ts": 123.0})
    q3, replayed3, _ = hub.subscribe(last_event_id=first + 1)
    hub.unsubscribe(q3)
    assert json.loads(replayed3[-1][2])["ts"] == 123.0
    hub.unsubscribe(q)


# ---------------------------------------------------------------------------
# metrics-doc drift gate (satellite: docs/metrics.md is a contract)
# ---------------------------------------------------------------------------

def test_metrics_doc_covers_live_registry():
    """A new metric family landing without a regenerated
    docs/metrics.md fails tier-1 — run `python tools/metrics_doc.py`
    and commit the result when this trips."""
    import subprocess
    r = subprocess.run(
        [os.sys.executable, os.path.join(REPO, "tools",
                                         "metrics_doc.py"), "--check"],
        capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr or r.stdout


# ---------------------------------------------------------------------------
# request autopsy
# ---------------------------------------------------------------------------

def test_request_autopsy_names_hang_fault(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY_TRACE_SAMPLE", "1")
    telemetry.set_enabled(True)
    step, sparams, state_info = _lstm_step()
    de = DecodeEngine(step, sparams, {}, state_info, num_slots=2,
                      max_len=32)
    de.warmup()
    faults.install("decode.step:hang:hang_s=0.08,on=2")
    de.submit([1, 2, 3], max_new_tokens=4,
              request_id="req-7").result(timeout=120)
    faults.clear()
    path = str(tmp_path / "telemetry.json")
    telemetry.dump_state(path)
    de.close()

    ra = _import_tool("request_autopsy")
    doc = ra._td.load_doc(path)
    rec = ra.autopsy(doc, "req-7")
    assert rec["request_id"] == "req-7"
    assert rec["dominant"]["name"] == "decode"
    # the injected fault overlapped the dominant interval and is
    # named as the dominant cause
    assert "injected fault 'fault:decode.step'" in rec["verdict"]
    overl = {e["name"] for e in rec["concurrent_events"]}
    assert "fault:decode.step" in overl
    # ...and its own spans are NOT their own concurrent cause
    assert not any((e.get("args") or {}).get("trace")
                   == rec["trace_id"]
                   for e in rec["concurrent_events"])
    text = ra.render(rec)
    assert "dominant cause: injected fault" in text
    # trace-id prefix lookup resolves to the same trace
    assert ra.autopsy(doc, rec["trace_id"][:8])["trace_id"] \
        == rec["trace_id"]
    # unknown ids fail with a LookupError naming the store size
    with pytest.raises(LookupError):
        ra.autopsy(doc, "no-such-request")


# ---------------------------------------------------------------------------
# chaos acceptance: fleet trace under the PR-12 schedule
# ---------------------------------------------------------------------------

@pytest.mark.filterwarnings("ignore::UserWarning")
def test_chaos_timeline_acceptance(tmp_path, monkeypatch):
    """The ISSUE 20 acceptance drill: a seeded chaos run (serve
    replica kill + AOT corruption + decode-step hang) on a 2-replica
    serve+decode fleet exports a Chrome trace that parses as valid
    trace_event JSON with per-replica lanes and injected-fault instant
    events; request_autopsy on an affected request names the
    fault-overlapped interval as the dominant cause."""
    monkeypatch.setenv("MXNET_AOT_CACHE_DIR", str(tmp_path / "aot"))
    monkeypatch.setenv("MXNET_FLIGHT_RECORDER_DIR",
                       str(tmp_path / "flight"))
    monkeypatch.setenv("MXNET_TELEMETRY_TRACE_SAMPLE", "1")
    telemetry.set_enabled(True)
    net, params = _mlp()
    step, sparams, state_info = _lstm_step()

    # cold pass populates the AOT cache (the corrupt clause needs a
    # warm entry to corrupt)
    cold = ServingEngine(net, params, {}, {"data": (6,)})
    cold.warmup()
    cold.close()

    faults.install(";".join([
        "serve.dispatch:raise:on=3,replica=0",
        "aot.load:corrupt:on=1",
        "decode.step:hang:hang_s=0.08,on=4"]))

    eng = ServingEngine(net, params, {}, {"data": (6,)},
                        ctx=[mx.cpu(0), mx.cpu(0)])
    de = DecodeEngine(step, sparams, {}, state_info, num_slots=2,
                      max_len=32, ctx=[mx.cpu(0), mx.cpu(0)])
    eng.warmup()
    de.warmup()
    rng = np.random.default_rng(0xF1E7)
    X = rng.standard_normal((12, 6)).astype(np.float32)
    serve_errs = 0
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("ignore")
        for i in range(12):
            try:
                eng.predict(X[i], timeout=120)
            except Exception:
                serve_errs += 1
        victim = de.submit([1, 2, 3], max_new_tokens=6,
                           request_id="chaos-req")
        victim.result(timeout=120)
    assert serve_errs >= 1                       # the kill landed
    injected = faults.stats()["injected"]
    assert injected.get("serve.dispatch:raise") == 1
    assert injected.get("aot.load:corrupt") == 1
    assert injected.get("decode.step:hang") == 1
    faults.clear()

    # ---- the Chrome trace: valid, per-replica lanes, fault instants
    doc = timeline.export_chrome_trace(timeline.get().events(), rank=0)
    doc = json.loads(json.dumps(doc))            # parses end to end
    evs = doc["traceEvents"]
    lanes = {e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {"replica:0", "replica:1"} <= lanes   # per-replica lanes
    fault_instants = [e for e in evs if e["ph"] == "i"
                      and e["name"].startswith("fault:")]
    assert {e["name"] for e in fault_instants} \
        >= {"fault:serve.dispatch", "fault:aot.load",
            "fault:decode.step"}
    # the replica failure is visible as an instant on ITS lane
    fail = [e for e in evs
            if e["name"] == "serve.replica_failed" and e["ph"] == "i"]
    assert fail
    # B/E balance — Perfetto rejects unbalanced duration pairs
    assert sum(1 for e in evs if e["ph"] == "B") \
        == sum(1 for e in evs if e["ph"] == "E")

    # ---- the autopsy names the fault-overlapped interval
    snap = str(tmp_path / "telemetry.json")
    telemetry.dump_state(snap)
    ra = _import_tool("request_autopsy")
    rec = ra.autopsy(ra._td.load_doc(snap), "chaos-req")
    assert rec["dominant"]["name"] == "decode"
    assert "injected fault 'fault:decode.step'" in rec["verdict"]
    eng.close()
    de.close()

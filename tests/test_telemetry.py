"""Unified runtime telemetry tests (mxnet_tpu/telemetry).

No reference analog — the reference's only runtime signal is the
profiler file dump.  Coverage per the subsystem contract: exact
registry semantics and exporter formats, request-scoped span trees
that survive the client->worker thread hop, built-in serving/kvstore/
io/monitor instrumentation with totals that cross-check against
``ServingEngine.stats()``, the overhead discipline (zero instrument
calls on the disabled hot path, bitwise-stable histograms on
deterministic series), and the ``tools/telemetry_dump.py`` CLI.
"""
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import serving, telemetry
from mxnet_tpu.test_utils import assert_almost_equal, few_ulp_tol
from mxnet_tpu.telemetry import metrics as tmetrics


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    """Each test sees an empty default registry/trace store and
    env-var-controlled enablement."""
    telemetry.set_enabled(None)
    telemetry.reset()
    yield
    telemetry.set_enabled(None)
    telemetry.reset()


def _mlp(feature=6, hidden=16, classes=3, seed=0):
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


def _engine(net, params, **kw):
    kw.setdefault("ctx", mx.cpu())
    kw.setdefault("batch_timeout_ms", 5.0)
    return serving.ServingEngine(net, params, {}, {"data": (6,)}, **kw)


def _prom_values(text):
    """{'name{labels}': value} for every non-comment exposition line."""
    vals = {}
    for line in text.splitlines():
        if not line.strip() or line.startswith("#"):
            continue
        key, v = line.rsplit(" ", 1)
        vals[key] = float(v)
    return vals


def _import_tool(name):
    tooldir = os.path.join(os.path.dirname(__file__), os.pardir, "tools")
    sys.path.insert(0, tooldir)
    try:
        return __import__(name)
    finally:
        sys.path.remove(tooldir)


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------

def test_counter_gauge_histogram_basics():
    reg = telemetry.Registry()
    c = reg.counter("c_total", "a counter")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(mx.MXNetError):
        c.inc(-1)                       # counters are monotonic
    g = reg.gauge("g")
    g.set(7)
    g.dec(3)
    assert g.value == 4.0
    h = reg.histogram("h_ms", buckets=(1.0, 10.0))
    for v in (0.5, 1.0, 5.0, 100.0):
        h.observe(v)
    counts, total, count = h.series()[0][1].snapshot()
    assert counts == [2, 1, 1]          # le=1 inclusive; +Inf tail
    assert count == 4 and total == pytest.approx(106.5)


def test_labeled_series_and_idempotent_registration():
    reg = telemetry.Registry()
    fam = reg.counter("req_total", "requests", labelnames=("route",))
    fam.labels(route="a").inc(2)
    fam.labels("a").inc()               # positional resolves same child
    fam.labels(route="b").inc()
    assert fam.labels(route="a").value == 3
    assert reg.counter("req_total", "requests",
                       labelnames=("route",)) is fam
    with pytest.raises(mx.MXNetError):
        reg.gauge("req_total")          # kind clash
    with pytest.raises(mx.MXNetError):
        fam.inc()                       # labeled family needs .labels()
    with pytest.raises(mx.MXNetError):
        fam.labels(route="a", extra="x")


def test_prometheus_rendering_format():
    reg = telemetry.Registry()
    reg.counter("c_total", 'say "hi"', labelnames=("k",)) \
        .labels(k='v"q').inc(2)
    h = reg.histogram("lat_ms", "latency", buckets=(1.0, 10.0))
    h.observe(0.5)
    h.observe(50.0)
    text = telemetry.render_prometheus(reg)
    assert '# TYPE c_total counter' in text
    assert 'c_total{k="v\\"q"} 2' in text
    assert 'lat_ms_bucket{le="1"} 1' in text
    assert 'lat_ms_bucket{le="10"} 1' in text      # cumulative
    assert 'lat_ms_bucket{le="+Inf"} 2' in text
    assert 'lat_ms_sum 50.5' in text
    assert 'lat_ms_count 2' in text


def test_collect_callback_refreshes_gauges():
    reg = telemetry.Registry()
    g = reg.gauge("derived")
    state = {"v": 1}
    reg.register_callback(lambda r: g.set(state["v"]))
    assert reg.collect()["derived"]["series"][0]["value"] == 1
    state["v"] = 42
    assert reg.collect()["derived"]["series"][0]["value"] == 42


def test_instrument_calls_probe():
    reg = telemetry.Registry()
    assert reg.instrument_calls() == 0
    reg.counter("a").inc()
    reg.gauge("b").set(1)
    reg.histogram("c").observe(1)
    assert reg.instrument_calls() == 3


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------

def test_trace_span_tree_and_store():
    with telemetry.trace("step") as tc:
        with tc.span("outer", "x"):
            with telemetry.maybe_span("inner", "y"):
                pass
        assert telemetry.current_trace() is tc
    assert telemetry.current_trace() is None
    tree = telemetry.get_trace(tc.trace_id)
    root = tree["root"]
    assert root["name"] == "step" and root["dur_ms"] >= 0
    outer = root["children"][0]
    assert outer["name"] == "outer"
    assert outer["children"][0]["name"] == "inner"
    assert tc.trace_id in telemetry.recent_trace_ids()


def test_maybe_span_without_active_trace_is_noop():
    with telemetry.maybe_span("orphan") as sp:
        assert sp is None
    assert telemetry.recent_trace_ids() == []


def test_trace_store_eviction(monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY_TRACE_CAPACITY", "3")
    ids = []
    for _ in range(5):
        with telemetry.trace("t") as tc:
            ids.append(tc.trace_id)
    stored = telemetry.recent_trace_ids()
    assert stored == ids[-3:]           # oldest evicted
    assert telemetry.get_trace(ids[0]) is None


def test_trace_bridges_into_profiler_ring(tmp_path):
    from mxnet_tpu import profiler
    profiler.clear()
    profiler.profiler_set_config(filename=str(tmp_path / "t.json"))
    profiler.profiler_set_state("run")
    try:
        with telemetry.trace("req", "serve") as tc:
            with tc.span("stage", "serve"):
                pass
    finally:
        profiler.profiler_set_state("stop")
    doc = json.load(open(profiler.dump_profile()))
    tagged = [e for e in doc["traceEvents"]
              if e.get("args", {}).get("trace_id") == tc.trace_id]
    assert {e["name"] for e in tagged} == {"req", "stage"}
    assert all(e["ph"] == "X" and e["dur"] >= 0 for e in tagged)


# ---------------------------------------------------------------------------
# serving acceptance: metrics + span tree + bitwise-unchanged outputs
# ---------------------------------------------------------------------------

def test_serving_telemetry_acceptance(monkeypatch, tmp_path, capsys):
    """The PR acceptance run: a concurrent engine with telemetry on
    yields (a) a Prometheus snapshot whose queue-depth / program-cache
    / retrace / padding-waste totals cross-check against stats(), and
    (b) a complete span tree for a sampled request retrievable by
    trace id through tools/telemetry_dump.py — while outputs stay
    bitwise identical to a telemetry-off engine."""
    monkeypatch.setenv("MXNET_TELEMETRY_TRACE_SAMPLE", "1")
    net, params = _mlp()
    rng = np.random.default_rng(1)
    X = rng.standard_normal((48, 6)).astype(np.float32)

    # reference run, telemetry hard-off
    telemetry.set_enabled(False)
    eng_off = _engine(net, params)
    assert eng_off._tm is None
    eng_off.warmup()
    ref = [eng_off.predict(X[i], timeout=30) for i in range(len(X))]
    eng_off.close()
    assert telemetry.registry().instrument_calls() == 0
    telemetry.set_enabled(None)
    telemetry.reset()

    # measured run: 16 concurrent clients
    eng = _engine(net, params)
    eng.warmup()
    results = [None] * len(X)

    def client(tid):
        for i in range(tid, len(X), 16):
            results[i] = eng.predict(X[i], timeout=30)

    threads = [threading.Thread(target=client, args=(t,))
               for t in range(16)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    st = eng.stats()
    prom = telemetry.render_prometheus()
    telemetry.dump_state(str(tmp_path / "telemetry.json"))
    eng.close()

    for i in range(len(X)):   # vs telemetry-off: other batch extents
        assert_almost_equal(results[i], ref[i], *few_ulp_tol(ref[i]))

    vals = _prom_values(prom)
    el = eng._tm.engine_label           # point-in-time gauges are
    #                                     labeled per engine
    assert vals['mxnet_serve_queue_depth{engine="%s"}' % el] \
        == st["queue_depth"] == 0
    assert vals["mxnet_serve_admitted_total"] == st["admitted"] == len(X)
    assert vals["mxnet_serve_requests_total"] == len(X)
    assert vals["mxnet_serve_batches_total"] == st["batches"]
    assert vals['mxnet_serve_retraces_total{engine="%s",replica="0",hazards="none"}'
                % el] == st["retraces"] == 0
    assert vals['mxnet_serve_program_cache_hits{engine="%s"}' % el] \
        == st["program_cache"]["hits"]
    assert vals['mxnet_serve_program_cache_misses{engine="%s"}' % el] \
        == st["program_cache"]["misses"]
    assert vals['mxnet_serve_compile_count{engine="%s"}' % el] \
        == st["compile_count"]
    assert vals["mxnet_serve_request_latency_ms_count"] \
        == st["requests_served"] == len(X)
    assert vals["mxnet_serve_rejected_total"] == st["rejected"] == 0
    assert vals["mxnet_serve_shed_total"] == st["shed"] == 0
    # padding-waste: one histogram sample per dispatched batch, summed
    # over the per-bucket series; live <= padded element counters
    waste_counts = sum(v for k, v in vals.items()
                       if k.startswith(
                           "mxnet_serve_padding_waste_ratio_count"))
    assert waste_counts == st["batches"]
    live = sum(v for k, v in vals.items()
               if k.startswith("mxnet_serve_live_elements_total"))
    padded = sum(v for k, v in vals.items()
                 if k.startswith("mxnet_serve_padded_elements_total"))
    assert live == len(X) * 6 and live <= padded

    # sampled request: complete span tree via the CLI, by trace id
    tids = telemetry.recent_trace_ids()
    assert len(tids) == len(X)          # sample period 1
    telemetry_dump = _import_tool("telemetry_dump")
    rc = telemetry_dump.main(
        ["trace", tids[-1], str(tmp_path / "telemetry.json")])
    assert rc == 0
    out = capsys.readouterr().out
    for stage in ("serve.request", "queue-wait", "coalesce", "pad",
                  "dispatch", "unpad"):
        assert stage in out, "span %r missing from:\n%s" % (stage, out)
    rc = telemetry_dump.main(
        ["snapshot", str(tmp_path / "telemetry.json")])
    assert rc == 0
    snap_out = capsys.readouterr().out
    assert "mxnet_serve_queue_depth" in snap_out


def test_runtime_retrace_counted_under_hazard_label(monkeypatch):
    """A post-warmup XLA trace on an already-dispatched bucket is the
    compile-once contract breaking at runtime: it must land on
    mxnet_serve_retraces_total under the engine's hazard label and in
    stats()['retraces']."""
    monkeypatch.setenv("MXNET_TELEMETRY_TRACE_SAMPLE", "0")
    net, params = _mlp()
    eng = _engine(net, params)
    eng.warmup()
    eng.predict(np.zeros((6,), np.float32), timeout=30)
    assert eng.stats()["retraces"] == 0
    # force a genuine retrace: drop the jitted kernels AND the
    # dispatch plans so the next (warm-key) dispatch re-traces
    eng._cache._op._jit.clear()
    eng._cache._plans.clear()
    eng.predict(np.zeros((6,), np.float32), timeout=30)
    st = eng.stats()
    # scrape BEFORE close: the per-engine retrace series (engine +
    # hazards labels) is reclaimed with the other engine series
    vals = _prom_values(telemetry.render_prometheus())
    el = eng._tm.engine_label
    eng.close()
    assert st["retraces"] == 1
    assert vals['mxnet_serve_retraces_total{engine="%s",replica="0",hazards="none"}'
                % el] == 1
    assert vals["mxnet_serve_compiles_total"] == st["compile_count"]
    vals2 = _prom_values(telemetry.render_prometheus())
    assert not any(k.startswith("mxnet_serve_retraces_total{engine=\"%s\""
                                % el) for k in vals2)


def test_retrace_bookkeeping_survives_telemetry_off(monkeypatch):
    """stats()['retraces'] is an engine-health signal, not a telemetry
    feature: a compile storm must be visible even with the registry
    disabled."""
    monkeypatch.setenv("MXNET_TELEMETRY_ON", "0")
    net, params = _mlp()
    eng = _engine(net, params)
    assert eng._tm is None
    eng.warmup()
    eng.predict(np.zeros((6,), np.float32), timeout=30)
    eng._cache._op._jit.clear()
    eng._cache._plans.clear()
    eng.predict(np.zeros((6,), np.float32), timeout=30)
    st = eng.stats()
    eng.close()
    assert st["retraces"] == 1
    assert telemetry.registry().families() == []    # still zero calls


def test_shape_entropy_gauge(monkeypatch):
    """Two distinct seq-bucketed signatures at equal traffic = 1 bit of
    shape entropy (the ROADMAP's observed-shape-entropy signal)."""
    net = mx.sym.Activation(mx.sym.Variable("data"), act_type="tanh",
                            name="act")
    policy = serving.BucketPolicy(max_batch=2, seq_axis=0,
                                  seq_buckets=(4, 8))
    eng = serving.ServingEngine(net, {}, {}, {"data": (8, 4)},
                                ctx=mx.cpu(), policy=policy,
                                batch_timeout_ms=2.0)
    rng = np.random.default_rng(2)
    for L in (3, 7, 4, 8):              # pads to buckets 4,8,4,8
        eng.predict(rng.standard_normal((L, 4)).astype(np.float32),
                    timeout=30)
    vals = _prom_values(telemetry.render_prometheus())
    key = ('mxnet_serve_shape_entropy_bits{engine="%s"}'
           % eng._tm.engine_label)
    eng.close()
    assert vals[key] == pytest.approx(1.0)
    sigs = [k for k in vals
            if k.startswith("mxnet_serve_shape_signature_total")]
    assert len(sigs) == 2 and all(vals[k] == 2 for k in sigs)


def test_failed_requests_still_leave_traces(monkeypatch):
    """Rejected / shed / expired requests are exactly the traffic an
    operator debugs: their sampled traces must finish (with a 'failed'
    reason span) instead of vanishing from the store."""
    monkeypatch.setenv("MXNET_TELEMETRY_TRACE_SAMPLE", "1")
    net, params = _mlp()
    eng = _engine(net, params, start=False, max_queue=1,
                  overload_policy="shed-oldest")
    shed = eng.submit(np.zeros((6,), np.float32))
    eng.submit(np.ones((6,), np.float32))      # sheds the first
    with pytest.raises(serving.ServerOverloadError):
        shed.result(timeout=5)
    eng.close()
    reasons = set()
    for tid in telemetry.recent_trace_ids():
        root = telemetry.get_trace(tid)["root"]
        for child in root.get("children", ()):
            if child["name"] == "failed":
                reasons.add(child["meta"]["reason"])
    assert "ServerOverloadError" in reasons


def test_engine_close_unregisters_collect_callback():
    net, params = _mlp()
    reg = telemetry.registry()
    engines = [_engine(net, params, start=False) for _ in range(3)]
    assert len(reg._callbacks) == 3
    qd = reg.get("mxnet_serve_queue_depth")
    assert len(qd.series()) == 3        # one labeled series per engine
    for eng in engines:
        eng.close()
    assert reg._callbacks == []         # no dead bundles left behind
    # per-engine gauge series are reclaimed too: reload-in-a-loop
    # must not grow scrape output without bound
    assert qd.series() == []
    assert reg.get("mxnet_serve_compile_count").series() == []


def test_histogram_bucket_mismatch_raises():
    reg = telemetry.Registry()
    reg.histogram("h_ms", buckets=(1.0, 10.0))
    reg.histogram("h_ms", buckets=(1.0, 10.0))      # same: idempotent
    with pytest.raises(mx.MXNetError):
        reg.histogram("h_ms", buckets=(2.0, 20.0))


def test_shape_signature_memo_stays_bounded(monkeypatch):
    """Past the label-cardinality cap, new distinct signatures share
    one 'other' series AND must not grow the per-engine memo dict."""
    from mxnet_tpu.serving import engine as engine_mod
    monkeypatch.setattr(engine_mod, "_MAX_SIG_LABELS", 2)
    net = mx.sym.Activation(mx.sym.Variable("data"), act_type="tanh",
                            name="act")
    eng = serving.ServingEngine(net, {}, {}, {"data": (4, 3)},
                                ctx=mx.cpu(), batch_timeout_ms=2.0,
                                policy=serving.BucketPolicy(
                                    max_batch=1, seq_axis=0),
                                start=False)
    rng = np.random.default_rng(4)
    for L in (1, 2, 3, 4, 5):           # 5 distinct exact-length sigs
        eng.submit(rng.standard_normal((L, 3)).astype(np.float32))
    assert len(eng._sig_labels) == 2
    vals = _prom_values(telemetry.render_prometheus())
    assert vals['mxnet_serve_shape_signature_total{engine="%s",'
                'sig="other"}' % eng._tm.engine_label] == 3
    eng.close()
    # close() reclaims this engine's sig series along with its gauges
    fam = telemetry.registry().get("mxnet_serve_shape_signature_total")
    assert fam.series() == []
    # and a post-close submit cannot resurrect them
    with pytest.raises(serving.EngineClosedError):
        eng.submit(rng.standard_normal((2, 3)).astype(np.float32))
    assert fam.series() == []


# ---------------------------------------------------------------------------
# overhead discipline
# ---------------------------------------------------------------------------

def test_disabled_hot_path_makes_zero_instrument_calls(monkeypatch):
    """MXNET_TELEMETRY_ON=0: the engine binds no instruments and a
    full submit->dispatch->result round trip performs zero registry
    calls (and registers zero families)."""
    monkeypatch.setenv("MXNET_TELEMETRY_ON", "0")
    net, params = _mlp()
    eng = _engine(net, params)
    assert eng._tm is None and eng._adm._telemetry is None
    eng.warmup()
    reg = telemetry.registry()
    before = reg.instrument_calls()
    for i in range(10):
        eng.predict(np.full((6,), i, np.float32), timeout=30)
    eng.close()
    assert reg.instrument_calls() == before == 0
    assert reg.families() == []


def test_histograms_bitwise_stable_across_identical_runs(monkeypatch):
    """Fixed bucket boundaries + deterministic series: two identical
    staged runs must produce bitwise-identical padding-waste /
    occupancy / element-count series (latency histograms are
    explicitly excluded — they measure wall time)."""
    monkeypatch.setenv("MXNET_TELEMETRY_TRACE_SAMPLE", "0")
    deterministic = ("mxnet_serve_padding_waste_ratio",
                     "mxnet_serve_batch_occupancy",
                     "mxnet_serve_live_elements_total",
                     "mxnet_serve_padded_elements_total",
                     "mxnet_serve_requests_total",
                     "mxnet_serve_shape_signature_total")
    net, params = _mlp()
    rng = np.random.default_rng(3)
    X = rng.standard_normal((5, 6)).astype(np.float32)

    def one_run():
        telemetry.reset()
        eng = _engine(net, params, start=False)
        eng.warmup()
        futs = [eng.submit(X[i]) for i in range(len(X))]
        eng.start()
        for f in futs:
            f.result(timeout=30)
        eng.close()
        doc = telemetry.registry().collect()
        return {k: doc[k] for k in deterministic}

    assert one_run() == one_run()


def test_serve_bench_telemetry_overhead_smoke():
    """Fast tier-1 smoke of perf/serve_bench.py --telemetry: the
    machinery — engines, HTTP server, /metrics-hammering scraper, the
    off-on-off centered-median estimator with its A/A noise floor —
    runs end to end and stays within a smoke-scale tolerance (tiny
    loads are scheduler-noise-dominated; the honest 2%+floor gate
    runs at full bench scale)."""
    perf_dir = os.path.join(os.path.dirname(__file__), os.pardir, "perf")
    sys.path.insert(0, perf_dir)
    try:
        import serve_bench
    finally:
        sys.path.remove(perf_dir)
    res = serve_bench.run_telemetry_overhead(
        requests=48, offered_batch=8, feature=6, hidden=16, classes=3,
        repeats=3, tol=0.75)
    assert res["noise_floor"] >= 0 and res["metrics_scrapes"] >= 0
    assert res["rps_telemetry_off"] > 0 and res["rps_telemetry_on"] > 0
    assert res["ok"], "telemetry overhead %.1f%% blew even the smoke " \
        "tolerance" % (res["regression"] * 1e2)
    # the gate restores env-var control of the master switch
    assert telemetry._FORCED is None


# ---------------------------------------------------------------------------
# satellites: stats() zeros, profiler metadata, monitor, kvstore, io
# ---------------------------------------------------------------------------

def test_stats_empty_latency_window_returns_zeros():
    net, params = _mlp()
    eng = _engine(net, params, start=False)
    st = eng.stats()
    eng.close()
    assert st["latency_ms"] == {"count": 0, "mean": 0.0,
                                "p50": 0.0, "p99": 0.0, "p999": 0.0}
    assert st["queue_depth"] == 0
    assert st["rejected"] == 0 and st["shed"] == 0 and st["expired"] == 0
    assert st["retraces"] == 0
    assert st["program_cache"] == {"hits": 0, "misses": 0}
    assert st["batch_occupancy"] == 0.0
    # the optimizer block is always present; a graph with nothing to
    # rewrite reports zero applied/rejected and equal node counts
    assert st["optimizer"]["applied"] == 0
    assert st["optimizer"]["rejected"] == 0
    assert st["optimizer"]["reason"] is None
    assert st["optimizer"]["nodes_before"] == st["optimizer"]["nodes_after"]


def test_profiler_dumps_self_describing(tmp_path):
    from mxnet_tpu import profiler
    profiler.clear()
    profiler.set_max_events(8)
    try:
        profiler.profiler_set_config(filename=str(tmp_path / "p.json"))
        profiler.profiler_set_state("run")
        for i in range(12):
            profiler.instant("e%d" % i)
        profiler.profiler_set_state("stop")
        doc = json.loads(profiler.dumps())
        assert doc["otherData"]["dropped_events"] == 4
        assert doc["otherData"]["max_events"] == 8
        fdoc = json.load(open(profiler.dump_profile()))
        assert fdoc["otherData"]["max_events"] == 8
        assert fdoc["otherData"]["dropped_events"] == 4
    finally:
        profiler.set_max_events(mx.config.get("MXNET_PROFILER_MAX_EVENTS"))
        profiler.clear()


def test_monitor_stats_flow_into_registry():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=4, name="fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mon = mx.Monitor(interval=1, pattern=".*output")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", (2, 6))],
             label_shapes=[("softmax_label", (2,))])
    mod.init_params()
    mod.install_monitor(mon)
    from mxnet_tpu.io import DataBatch
    b = DataBatch(data=[mx.nd.array(np.random.rand(2, 6)
                                    .astype(np.float32))],
                  label=[mx.nd.array(np.zeros((2,), np.float32))])
    mon.tic()
    mod.forward(b, is_train=False)
    rows = mon.toc()
    assert rows
    fam = telemetry.registry().get("mxnet_monitor_tensor_stat")
    assert fam is not None
    by_tensor = {labels[0]: inst.value for labels, inst in fam.series()}
    for _, name, stat in rows:
        assert by_tensor[name] == pytest.approx(float(stat))


def test_kvstore_push_pull_metrics():
    kv = mx.kv.create("local")
    kv.init("w", mx.nd.zeros((4, 4)))
    kv.push("w", mx.nd.array(np.ones((4, 4), np.float32)))
    out = mx.nd.zeros((4, 4))
    kv.pull("w", out=out)
    vals = _prom_values(telemetry.render_prometheus())
    assert vals['mxnet_kvstore_ops_total{direction="push"}'] == 1
    assert vals['mxnet_kvstore_ops_total{direction="pull"}'] == 1
    assert vals['mxnet_kvstore_bytes_total{direction="push"}'] == 64
    assert vals['mxnet_kvstore_bytes_total{direction="pull"}'] == 64
    assert vals['mxnet_kvstore_latency_ms_count{direction="push"}'] == 1
    assert vals['mxnet_kvstore_latency_ms_count{direction="pull"}'] == 1


def test_io_batch_latency_histograms():
    X = np.random.rand(8, 6).astype(np.float32)
    Y = np.zeros((8,), np.float32)
    it = mx.io.NDArrayIter(X, Y, batch_size=4)
    for _ in it:
        pass
    from mxnet_tpu.gluon.data import ArrayDataset, DataLoader
    for _ in DataLoader(ArrayDataset(X, Y), batch_size=4):
        pass
    vals = _prom_values(telemetry.render_prometheus())
    assert vals['mxnet_io_batch_latency_ms_count{iter="NDArrayIter"}'] == 2
    assert vals['mxnet_io_batch_latency_ms_count{iter="DataLoader"}'] == 2


def test_wrapper_iterators_do_not_double_count():
    """ResizeIter consumes its inner iterator's instrumented next():
    each batch must land in mxnet_io_batch_latency_ms exactly once
    (under the inner label), or summed counts read 2x throughput."""
    X = np.random.rand(8, 6).astype(np.float32)
    it = mx.io.ResizeIter(
        mx.io.NDArrayIter(X, np.zeros((8,), np.float32), batch_size=4),
        size=3)
    n = sum(1 for _ in it)
    assert n == 3
    vals = _prom_values(telemetry.render_prometheus())
    total = sum(v for k, v in vals.items()
                if k.startswith("mxnet_io_batch_latency_ms_count"))
    assert total == 3


def test_executor_dispatch_counter_and_xla_traces():
    net, params = _mlp()
    pred = mx.predict.Predictor(net, params, {}, {"data": (1, 6)},
                                ctx=mx.cpu())
    pred.forward(data=np.zeros((1, 6), np.float32))
    vals = _prom_values(telemetry.render_prometheus())
    assert vals['mxnet_executor_dispatch_total{kind="forward"}'] >= 1
    # a fresh CachedOp dispatch traces exactly once; a warm one never
    op = mx.CachedOp(mx.sym.Activation(mx.sym.Variable("x"),
                                       act_type="tanh"))
    x = mx.nd.array(np.ones((2, 2), np.float32))
    op(x)
    v1 = _prom_values(telemetry.render_prometheus())[
        "mxnet_xla_traces_total"]
    op(x)
    v2 = _prom_values(telemetry.render_prometheus())[
        "mxnet_xla_traces_total"]
    assert v2 == v1                     # warm dispatch: no new trace


# ---------------------------------------------------------------------------
# exporters / snapshot thread / config / CLI formats
# ---------------------------------------------------------------------------

def test_snapshotter_writes_atomic_file(tmp_path):
    telemetry.counter("snap_probe_total").inc(3)
    path = str(tmp_path / "snap.prom")
    telemetry.start_snapshotter(0.05, path, "prom")
    try:
        time.sleep(0.2)
    finally:
        telemetry.stop_snapshotter()
    text = open(path).read()
    assert "snap_probe_total 3" in text
    assert not [p for p in os.listdir(str(tmp_path))
                if ".tmp." in p]        # atomic replace leaves no temps


def test_snapshotter_disabled_at_zero_interval():
    assert telemetry.start_snapshotter(0) is None


def test_snapshotter_rejects_unknown_format_up_front():
    """A typo'd format must fail fast at start, not silently write
    nothing for the life of the process (the thread swallows per-tick
    errors by design)."""
    with pytest.raises(mx.MXNetError):
        telemetry.start_snapshotter(30, "/tmp/x", "promtext")


def test_exact_length_cold_compiles_are_not_retraces(monkeypatch):
    """Post-warmup compiles on first-sight signatures are legitimate in
    exact-length seq mode (cross-position graphs degrade to one program
    per length): stats()['retraces'] must stay 0 for them.  Repair is
    pinned off — with it on (the PR 4 default) this graph would serve
    repaired from the bucket grid instead of degrading."""
    import warnings as _w
    monkeypatch.setenv("MXNET_SERVE_REPAIR", "0")
    data = mx.sym.Variable("data")
    net = mx.sym.softmax(data, axis=1, name="sm_seq")   # cross-pos seq
    policy = serving.BucketPolicy(max_batch=2, seq_axis=0,
                                  seq_buckets=(4,))
    with _w.catch_warnings():
        _w.simplefilter("ignore")
        eng = serving.ServingEngine(net, {}, {}, {"data": (4, 3)},
                                    ctx=mx.cpu(), policy=policy,
                                    batch_timeout_ms=2.0)
    assert eng._policy.seq_buckets == ()    # degraded to exact lengths
    eng.warmup()
    rng = np.random.default_rng(9)
    for L in (2, 3, 4):                     # three cold exact lengths
        eng.predict(rng.standard_normal((L, 3)).astype(np.float32),
                    timeout=30)
    st = eng.stats()
    eng.close()
    assert st["retraces"] == 0
    assert st["compile_count"] > 0


def test_config_knobs_registered():
    doc = mx.config.describe()
    for name in ("MXNET_TELEMETRY_ON", "MXNET_TELEMETRY_SNAPSHOT_SECS",
                 "MXNET_TELEMETRY_SNAPSHOT_PATH",
                 "MXNET_TELEMETRY_SNAPSHOT_FORMAT",
                 "MXNET_TELEMETRY_TRACE_SAMPLE",
                 "MXNET_TELEMETRY_TRACE_CAPACITY"):
        assert name in doc
        mx.config.get(name)             # typed read succeeds
    assert mx.config.get("MXNET_TELEMETRY_ON") is True


def test_enabled_env_and_override(monkeypatch):
    assert telemetry.enabled()
    monkeypatch.setenv("MXNET_TELEMETRY_ON", "0")
    assert not telemetry.enabled()
    telemetry.set_enabled(True)
    assert telemetry.enabled()          # override beats env
    telemetry.set_enabled(None)
    assert not telemetry.enabled()


def test_json_export_is_strict_rfc8259(tmp_path):
    """A NaN gauge (diverging model via Monitor) must not make the
    JSON snapshot unparseable to strict consumers: non-finite values
    export as null."""
    telemetry.gauge("diverged_stat").set(float("nan"))
    telemetry.gauge("overflow_stat").set(float("inf"))
    text = telemetry.render_json()
    assert "NaN" not in text and "Infinity" not in text
    doc = json.loads(text)
    assert doc["metrics"]["diverged_stat"]["series"][0]["value"] is None
    assert doc["metrics"]["overflow_stat"]["series"][0]["value"] is None
    # the prom exposition spells them per the text-format convention
    prom = telemetry.render_prometheus()
    assert "diverged_stat NaN" in prom
    assert "overflow_stat +Inf" in prom
    # and the CLI renders nulls instead of crashing mid-incident
    path = str(tmp_path / "nan.json")
    telemetry.dump_state(path)
    telemetry_dump = _import_tool("telemetry_dump")
    out = telemetry_dump.format_metrics(
        telemetry_dump.load_doc(path)["metrics"])
    assert "null" in out


def test_pad_probe_does_not_double_count_plan_hits(monkeypatch):
    """MXNET_SERVE_PAD_CHECK dispatches every batch twice through the
    ProgramCache; hit/miss accounting must count logical dispatches."""
    monkeypatch.setenv("MXNET_SERVE_PAD_CHECK", "1")
    net, params = _mlp()
    eng = _engine(net, params)
    eng.warmup()
    hits0 = eng._cache.plan_hits
    for _ in range(4):
        eng.predict(np.ones((6,), np.float32), timeout=30)
    st = eng.stats()
    eng.close()
    assert st["program_cache"]["hits"] - hits0 == 4


def test_dump_cli_prom_text_passthrough(tmp_path, capsys):
    telemetry.counter("cli_probe_total").inc()
    path = str(tmp_path / "live.prom")
    telemetry.write_snapshot(path, "prom")
    telemetry_dump = _import_tool("telemetry_dump")
    assert telemetry_dump.main(["snapshot", path]) == 0
    assert "cli_probe_total 1" in capsys.readouterr().out


def test_dump_cli_unknown_trace_id(tmp_path, capsys):
    telemetry.dump_state(str(tmp_path / "d.json"))
    telemetry_dump = _import_tool("telemetry_dump")
    assert telemetry_dump.main(
        ["trace", "deadbeef", str(tmp_path / "d.json")]) == 1

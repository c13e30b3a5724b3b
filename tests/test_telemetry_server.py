"""Live observability plane tests (telemetry/server.py + sampling.py).

Coverage per the issue contract: HTTP routes end-to-end against a
concurrently-serving engine with /metrics totals cross-checked against
``stats()``; tail-biased trace retention (a forcibly-slow request is
retroactively kept and retrievable via /traces/<id> with its full
queue-wait -> dispatch span tree, while uniform fast traffic retains
only the baseline floor); error-triggered keeps; concurrent
scrape-vs-mutate never yields a torn exposition document; server
shutdown leaks neither port nor thread across engine-reload loops; the
metric-name lint gate; cross-host rank-snapshot aggregation; and the
``telemetry_dump`` top / --url satellites.
"""
import json
import os
import socket
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import serving, telemetry


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    """Empty registry/trace store, env-controlled enablement, and NO
    process-wide HTTP server bleeding between tests."""
    telemetry.set_enabled(None)
    telemetry.reset()
    telemetry.stop_server()
    yield
    telemetry.stop_server()
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


def _get(port, path):
    with urllib.request.urlopen(
            "http://127.0.0.1:%d%s" % (port, path), timeout=10) as r:
        return r.read().decode()


def _get_json(port, path):
    return json.loads(_get(port, path))


def _parse_prom(text):
    """Strict exposition parse: every sample line must split into a
    series key and a float — a torn document fails here."""
    vals = {}
    assert text.endswith("\n")
    for line in text.splitlines():
        if not line.strip() or line.startswith("#"):
            continue
        key, v = line.rsplit(" ", 1)
        vals[key] = float(v)
    return vals


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _import_tool(name):
    tooldir = os.path.join(os.path.dirname(__file__), os.pardir, "tools")
    sys.path.insert(0, tooldir)
    try:
        return __import__(name)
    finally:
        sys.path.remove(tooldir)


# ---------------------------------------------------------------------------
# routes end-to-end + /metrics cross-check against stats()
# ---------------------------------------------------------------------------

def test_routes_and_metrics_cross_check(monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY_TRACE_SAMPLE", "1")
    srv = telemetry.start_server(0, host="127.0.0.1")
    net, params = _mlp()
    eng = _engine(net, params)
    eng.warmup()
    X = np.random.default_rng(1).standard_normal((32, 6)).astype(np.float32)
    results = [None] * len(X)

    def client(tid):
        for i in range(tid, len(X), 8):
            results[i] = eng.predict(X[i], timeout=30)

    threads = [threading.Thread(target=client, args=(t,)) for t in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert all(r is not None for r in results)
    st = eng.stats()

    # /metrics cross-checks stats() (the live analog of the PR 3
    # snapshot acceptance)
    vals = _parse_prom(_get(srv.port, "/metrics"))
    el = eng._tm.engine_label
    assert vals["mxnet_serve_requests_total"] == st["admitted"] == len(X)
    assert vals["mxnet_serve_batches_total"] == st["batches"]
    assert vals['mxnet_serve_queue_depth{engine="%s"}' % el] \
        == st["queue_depth"] == 0
    assert vals["mxnet_serve_request_latency_ms_count"] \
        == st["requests_served"] == len(X)

    # /metrics.json is the same self-contained document dump_state writes
    doc = _get_json(srv.port, "/metrics.json")
    assert doc["format"] == "mxnet_tpu.telemetry/1"
    assert doc["metrics"]["mxnet_serve_batches_total"]["series"][0][
        "value"] == st["batches"]

    # /traces lists every retained trace (floor=1 keeps all of them);
    # /traces/<id> returns the full span tree
    idx = _get_json(srv.port, "/traces")
    assert idx["count"] == len(X)
    tid = idx["traces"][-1]["trace_id"]
    tree = _get_json(srv.port, "/traces/%s" % tid)
    names = [c["name"] for c in tree["root"]["children"]]
    for stage in ("queue-wait", "coalesce", "pad", "dispatch", "unpad"):
        assert stage in names

    # /healthz: liveness + engine aggregates
    hz = _get_json(srv.port, "/healthz")
    assert hz["status"] == "ok" and hz["uptime_s"] >= 0
    assert hz["engines"] == 1 and hz["queue_depth"] == 0
    assert hz["traces_stored"] == len(X)
    assert 0 < hz["batch_occupancy"] <= 1.0

    # unknown routes and unknown trace ids are clean 404 JSON
    for path in ("/nope", "/traces/deadbeef"):
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(srv.port, path)
        assert ei.value.code == 404
        assert "error" in json.loads(ei.value.read().decode())
    eng.close()


# ---------------------------------------------------------------------------
# tail-biased retention
# ---------------------------------------------------------------------------

def test_tail_sampler_retains_slow_request_only_floor_for_fast(monkeypatch):
    """The acceptance scenario: a forcibly-slow request (deadline-
    margin queue wait) is retroactively kept by the tail sampler and
    retrievable via /traces/<id> with a full queue-wait->dispatch span
    tree, while uniform fast traffic retains only the baseline floor
    (plus the bounded top-K reservoir), never everything."""
    monkeypatch.setenv("MXNET_TELEMETRY_TRACE_SAMPLE", "50")
    monkeypatch.setenv("MXNET_TELEMETRY_TRACE_TAIL_K", "2")
    srv = telemetry.start_server(0, host="127.0.0.1")
    net, params = _mlp()
    eng = _engine(net, params, start=False)
    eng.warmup()

    # the slow request: queued against a stopped worker, so its e2e
    # latency is dominated by a deliberate ~80 ms queue wait
    slow_fut = eng.submit(np.zeros((6,), np.float32))
    time.sleep(0.08)
    eng.start()
    slow_fut.result(timeout=30)

    X = np.random.default_rng(2).standard_normal((60, 6)).astype(np.float32)
    for i in range(len(X)):
        eng.predict(X[i], timeout=30)
    st = eng.stats()
    idx = _get_json(srv.port, "/traces")
    eng.close()

    # tail retention caught the straggler: the slowest retained trace
    # is the queue-delayed one, tagged tail_topk, with the full tree
    rows = [r for r in idx["traces"] if r["dur_ms"] is not None]
    slowest = max(rows, key=lambda r: r["dur_ms"])
    assert slowest["dur_ms"] >= 80
    assert slowest["retained_by"].startswith("tail")
    tree = _get_json(srv.port, "/traces/%s" % slowest["trace_id"])
    children = {c["name"]: c for c in tree["root"]["children"]}
    assert children["queue-wait"]["dur_ms"] >= 80
    assert "dispatch" in children
    # ... and its latency is the stats() tail the sampler exists for
    assert st["latency_ms"]["p999"] >= 80

    # uniform fast traffic did NOT all stick: 61 requests, floor keeps
    # ~2, the K=2 reservoir plus early fills keep a handful more
    assert idx["count"] < len(X) // 2
    reg = telemetry.registry()
    retained = reg.get("mxnet_telemetry_traces_retained_total")
    by_reason = {lv[0]: inst.value for lv, inst in retained.series()}
    assert by_reason.get("periodic", 0) >= 1
    assert by_reason.get("tail_topk", 0) >= 1
    assert reg.get("mxnet_telemetry_traces_dropped_total").value > 0


def test_error_triggered_keep(monkeypatch):
    """A shed request's trace must be retained by the error sampler
    even when the periodic floor would never have picked it."""
    monkeypatch.setenv("MXNET_TELEMETRY_TRACE_SAMPLE", "1000000")
    monkeypatch.setenv("MXNET_TELEMETRY_TRACE_TAIL_K", "0")
    net, params = _mlp()
    eng = _engine(net, params, start=False, max_queue=1,
                  overload_policy="shed-oldest")
    shed = eng.submit(np.zeros((6,), np.float32))
    eng.submit(np.ones((6,), np.float32))      # sheds the first
    with pytest.raises(serving.ServerOverloadError):
        shed.result(timeout=5)
    eng.close()
    kept = [telemetry.get_trace(t) for t in telemetry.recent_trace_ids()]
    errors = [t for t in kept if t.get("retained_by") == "error"]
    assert errors, "shed request's trace was sampled away"
    reasons = {c["meta"]["reason"] for t in errors
               for c in t["root"]["children"] if c["name"] == "failed"}
    assert "ServerOverloadError" in reasons


def test_trace_sample_zero_still_disables_everything(monkeypatch):
    """MXNET_TELEMETRY_TRACE_SAMPLE=0 stays the tracing kill switch:
    no per-request TraceContext, regardless of the tail knobs."""
    monkeypatch.setenv("MXNET_TELEMETRY_TRACE_SAMPLE", "0")
    monkeypatch.setenv("MXNET_TELEMETRY_TRACE_TAIL_K", "8")
    net, params = _mlp()
    eng = _engine(net, params)
    assert eng._trace_chain is None
    eng.warmup()
    eng.predict(np.zeros((6,), np.float32), timeout=30)
    eng.close()
    assert telemetry.recent_trace_ids() == []


def test_explicit_trace_api_keeps_unconditionally(monkeypatch):
    """telemetry.trace(...) has no retention chain: a hand-traced
    region is stored even when the engine chain would drop it."""
    with telemetry.trace("step") as tc:
        pass
    assert telemetry.get_trace(tc.trace_id) is not None
    assert "retained_by" not in telemetry.get_trace(tc.trace_id)


# ---------------------------------------------------------------------------
# concurrency: scrape-vs-mutate, shutdown leaks
# ---------------------------------------------------------------------------

def test_concurrent_scrape_never_torn(monkeypatch):
    """A thread pounding /metrics and /metrics.json while an engine
    serves must parse EVERY response — no torn exposition documents,
    no 5xx, under ~1 s of sustained mutation (longer on a loaded
    host: the mutation goes on until the scrapers have got their six
    rounds in, so the count below does not assume idle cores)."""
    monkeypatch.setenv("MXNET_TELEMETRY_TRACE_SAMPLE", "4")
    srv = telemetry.start_server(0, host="127.0.0.1")
    net, params = _mlp()
    eng = _engine(net, params, batch_timeout_ms=1.0)
    eng.warmup()
    stop = threading.Event()
    failures = []
    counts = {"prom": 0, "json": 0}

    def scraper():
        while not stop.is_set():
            try:
                vals = _parse_prom(_get(srv.port, "/metrics"))
                assert vals, "empty exposition"
                doc = _get_json(srv.port, "/metrics.json")
                assert "metrics" in doc
                counts["prom"] += 1
                counts["json"] += 1
            except Exception as e:                  # noqa: BLE001
                failures.append(repr(e))
                return

    scrapers = [threading.Thread(target=scraper) for _ in range(2)]
    for s in scrapers:
        s.start()
    X = np.random.default_rng(3).standard_normal((64, 6)).astype(np.float32)
    t_end = time.monotonic() + 1.0
    t_give_up = t_end + 30.0
    i = 0
    while time.monotonic() < t_end or (
            counts["prom"] <= 5 and not failures
            and time.monotonic() < t_give_up):
        eng.predict(X[i % len(X)], timeout=30)
        i += 1
    stop.set()
    for s in scrapers:
        s.join(timeout=10)
    eng.close()
    assert not failures, failures
    assert counts["prom"] > 5               # the hammer actually hammered
    assert i > 0


def test_engine_reload_loop_leaks_neither_port_nor_thread(monkeypatch):
    """The engine-owned server (MXNET_TELEMETRY_PORT with no explicit
    start) must release the port AND the acceptor thread at close(), so
    an engine-reload loop can rebind the same fixed port every time."""
    port = _free_port()
    monkeypatch.setenv("MXNET_TELEMETRY_PORT", str(port))
    net, params = _mlp()
    for _ in range(3):
        eng = _engine(net, params)
        assert eng._owns_http_server
        assert telemetry.server_address() == ("0.0.0.0", port)
        assert "mxnet_serve_requests_total" in _get(port, "/metrics")
        eng.close()
        assert telemetry.server_address() is None
        with pytest.raises(urllib.error.URLError):
            _get(port, "/metrics")
        assert not [t for t in threading.enumerate()
                    if t.name == "mxnet-telemetry-http"]


def test_engine_refcount_and_manual_server_ownership(monkeypatch):
    """Co-resident engines share one engine-acquired server (last one
    out stops it); an operator-started server survives engine close."""
    port = _free_port()
    monkeypatch.setenv("MXNET_TELEMETRY_PORT", str(port))
    net, params = _mlp()
    e1 = _engine(net, params, start=False)
    e2 = _engine(net, params, start=False)
    assert e1._owns_http_server and e2._owns_http_server
    e1.close()
    assert telemetry.server_address() == ("0.0.0.0", port)   # e2 holds it
    e2.close()
    assert telemetry.server_address() is None

    srv = telemetry.start_server(port, host="127.0.0.1")
    e3 = _engine(net, params, start=False)
    assert not e3._owns_http_server          # operator-owned: hands off
    e3.close()
    assert telemetry.server_address() == ("127.0.0.1", srv.port)


# ---------------------------------------------------------------------------
# metric-name lint gate
# ---------------------------------------------------------------------------

def test_every_live_metric_name_is_namespaced(monkeypatch):
    """CI drift gate: every family exposed at /metrics after driving
    serving + kvstore + io + executor instrumentation must match
    ^mxnet_[a-z0-9_]+$ (the namespace the README documents)."""
    monkeypatch.setenv("MXNET_TELEMETRY_TRACE_SAMPLE", "4")
    srv = telemetry.start_server(0, host="127.0.0.1")
    net, params = _mlp()
    eng = _engine(net, params)
    eng.warmup()
    eng.predict(np.zeros((6,), np.float32), timeout=30)
    kv = mx.kv.create("local")
    kv.init("w", mx.nd.zeros((2, 2)))
    kv.push("w", mx.nd.ones((2, 2)))
    kv.pull("w", out=mx.nd.zeros((2, 2)))
    X = np.random.rand(4, 6).astype(np.float32)
    for _ in mx.io.NDArrayIter(X, np.zeros((4,), np.float32),
                               batch_size=2):
        pass
    text = _get(srv.port, "/metrics")
    eng.close()
    assert "mxnet_serve_requests_total" in text     # gate has teeth
    assert telemetry.lint_metric_names(text) == []


def test_lint_catches_out_of_namespace_names():
    reg = telemetry.Registry()
    reg.counter("mxnet_good_total").inc()
    reg.counter("rogue_total").inc()
    reg.gauge("mxnet_Bad_Case").set(1)
    bad = telemetry.lint_metric_names(
        telemetry.render_prometheus(reg))
    assert sorted(bad) == ["mxnet_Bad_Case", "rogue_total"]


# ---------------------------------------------------------------------------
# cross-host aggregation
# ---------------------------------------------------------------------------

def _rank_registry(rank, depth):
    reg = telemetry.Registry()
    reg.counter("mxnet_kvstore_ops_total", "ops",
                labelnames=("direction",)).labels(
                    direction="push").inc(10 * (rank + 1))
    reg.gauge("mxnet_serve_queue_depth", "depth",
              labelnames=("engine",)).labels(engine="0").set(depth)
    h = reg.histogram("mxnet_kvstore_latency_ms", "lat",
                      buckets=(1.0, 10.0))
    h.observe(0.5)
    h.observe(5.0 * (rank + 1))
    return reg


def test_kvstore_dist_rank_snapshotter_and_aggregate(monkeypatch,
                                                     tmp_path, capsys):
    """The cross-host acceptance path: rank-tagged snapshots under a
    shared dir (the single-process KVStoreDist writes rank 0 through
    the real wiring), merged by `telemetry_dump aggregate` into one
    document with per-rank labels, summed counters, merged histograms,
    and per-rank gauge spread naming the straggler."""
    shared = str(tmp_path / "shared")
    monkeypatch.setenv("MXNET_TELEMETRY_SHARED_DIR", shared)
    telemetry.counter("mxnet_kvstore_ops_total", "ops",
                      labelnames=("direction",)).labels(
                          direction="push").inc(10)
    telemetry.gauge("mxnet_serve_queue_depth", "depth",
                    labelnames=("engine",)).labels(engine="0").set(1)
    kv = mx.kv.create("dist_sync")       # no DMLC env: 1-process, rank 0
    assert kv.rank == 0
    kv._stop_rank_telemetry()            # final snapshot written
    rank0 = os.path.join(shared, "telemetry_rank0.json")
    assert json.load(open(rank0))["rank"] == 0

    # fabricate a straggling rank 1 (8x the queue depth, its own counts)
    telemetry.write_snapshot(
        os.path.join(shared, "telemetry_rank1.json"), "json",
        registry=_rank_registry(1, depth=8), meta={"rank": 1})

    telemetry_dump = _import_tool("telemetry_dump")
    out_path = str(tmp_path / "agg.json")
    rc = telemetry_dump.main(
        ["aggregate", rank0,
         os.path.join(shared, "telemetry_rank1.json"), "--out", out_path])
    assert rc == 0
    text = capsys.readouterr().out
    merged = json.load(open(out_path))

    ops = merged["metrics"]["mxnet_kvstore_ops_total"]["series"]
    by_rank = {s["labels"]["rank"]: s["value"] for s in ops
               if s["labels"].get("direction") == "push"}
    assert by_rank["0"] == 10 and by_rank["1"] == 20    # per-rank labels
    assert by_rank["all"] == 30                         # summed counter
    assert "rank" in merged["metrics"]["mxnet_kvstore_ops_total"][
        "labelnames"]

    lat = merged["metrics"]["mxnet_kvstore_latency_ms"]["series"]
    lat_all = [s for s in lat if s["labels"]["rank"] == "all"]
    assert lat_all and lat_all[0]["count"] == 2         # merged histogram

    spread = merged["gauge_spread"]["mxnet_serve_queue_depth"]
    row = spread['{engine=0}']
    assert row["max"] == 8 and row["max_rank"] == "1"   # straggler named
    assert row["min"] == 1 and row["min_rank"] == "0"
    assert "rank 1" in text and "spread" in text


def test_aggregate_dedupes_colliding_ranks(tmp_path):
    telemetry_dump = _import_tool("telemetry_dump")
    doc = {"metrics": {"mxnet_x_total": {
        "kind": "counter", "doc": "", "labelnames": [],
        "series": [{"labels": {}, "value": 1}]}}, "rank": 0}
    merged = telemetry_dump.aggregate_docs([("0", doc), ("0.1", doc)])
    vals = {s["labels"]["rank"]: s["value"]
            for s in merged["metrics"]["mxnet_x_total"]["series"]}
    assert vals == {"0": 1, "0.1": 1, "all": 2}


# ---------------------------------------------------------------------------
# satellites: p999, telemetry_dump top / --url, hazard_rank --url
# ---------------------------------------------------------------------------

def test_stats_p999_contract():
    net, params = _mlp()
    eng = _engine(net, params, start=False)
    st = eng.stats()
    # empty-window zero contract extends to p999
    assert st["latency_ms"] == {"count": 0, "mean": 0.0, "p50": 0.0,
                                "p99": 0.0, "p999": 0.0}
    eng.start()
    eng.warmup()
    for i in range(8):
        eng.predict(np.full((6,), i, np.float32), timeout=30)
    st = eng.stats()
    eng.close()
    lat = st["latency_ms"]
    assert lat["count"] == 8
    assert lat["p50"] <= lat["p99"] <= lat["p999"]
    assert lat["p999"] > 0


def test_dump_top_lists_slowest_with_dominant_span(monkeypatch, tmp_path,
                                                   capsys):
    monkeypatch.setenv("MXNET_TELEMETRY_TRACE_SAMPLE", "1")
    net, params = _mlp()
    eng = _engine(net, params, start=False)
    eng.warmup()
    fut = eng.submit(np.zeros((6,), np.float32))
    time.sleep(0.05)                     # queue-wait dominates this one
    eng.start()
    fut.result(timeout=30)
    for i in range(6):
        eng.predict(np.full((6,), i, np.float32), timeout=30)
    path = str(tmp_path / "t.json")
    telemetry.dump_state(path)
    eng.close()
    telemetry_dump = _import_tool("telemetry_dump")
    assert telemetry_dump.main(["top", "--k", "3", path]) == 0
    out = capsys.readouterr().out
    lines = [ln for ln in out.splitlines()[1:] if ln.strip()]
    assert len(lines) == 3
    # slowest first, and the straggler's dominant span is queue-wait
    assert "queue-wait" in lines[0]
    durs = [float(ln.split()[1]) for ln in lines]
    assert durs == sorted(durs, reverse=True)
    assert durs[0] >= 50


def test_dump_and_hazard_rank_scrape_live_url(monkeypatch, tmp_path,
                                              capsys):
    """--url makes the live endpoint a first-class snapshot source for
    both CLIs (no dump file needed mid-incident)."""
    monkeypatch.setenv("MXNET_TELEMETRY_TRACE_SAMPLE", "1")
    srv = telemetry.start_server(0, host="127.0.0.1")
    net, params = _mlp()
    eng = _engine(net, params)
    eng.warmup()
    eng.predict(np.zeros((6,), np.float32), timeout=30)
    url = "http://127.0.0.1:%d" % srv.port

    telemetry_dump = _import_tool("telemetry_dump")
    assert telemetry_dump.main(["snapshot", "--url", url]) == 0
    assert "mxnet_serve_requests_total" in capsys.readouterr().out
    assert telemetry_dump.main(["top", "--url", url, "--k", "1"]) == 0
    assert "dominant span" in capsys.readouterr().out
    # an explicit path scrapes raw text (prom passthrough)
    assert telemetry_dump.main(["snapshot", url + "/metrics"]) == 0
    assert "# TYPE" in capsys.readouterr().out

    lint = str(tmp_path / "lint.json")
    json.dump({"graphs": {}}, open(lint, "w"))
    hazard_rank = _import_tool("hazard_rank")
    assert hazard_rank.main([lint, "--url", url]) == 0
    assert "nothing to rank" in capsys.readouterr().out
    eng.close()

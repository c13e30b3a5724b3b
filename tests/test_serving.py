"""Serving engine tests (mxnet_tpu/serving).

No reference analog — the reference stops at the single-client
c_predict_api.  Coverage per the subsystem contract: concurrent clients
must get bitwise the answers a single-request Predictor gives, deadlines
expire queued work, the bounded queue backpressures / sheds under
overload, and warm traffic over the bucket grid never retraces.
"""
import os
import sys
import threading

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import serving
from mxnet_tpu.test_utils import assert_almost_equal, few_ulp_tol
from mxnet_tpu.serving import (BucketPolicy, DeadlineExceededError,
                               EngineClosedError, QueueFullError,
                               ServerOverloadError)


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


def _engine(net, params, data_shapes, **kw):
    kw.setdefault("ctx", mx.cpu())
    kw.setdefault("batch_timeout_ms", 5.0)
    return serving.ServingEngine(net, params, {}, data_shapes, **kw)


def test_bucket_policy_grid():
    p = BucketPolicy(max_batch=8, seq_axis=0, seq_buckets=(4, 8))
    assert p.batch_buckets() == [1, 2, 4, 8]
    assert [p.batch_bucket(n) for n in (1, 2, 3, 5, 8)] == [1, 2, 4, 8, 8]
    assert p.seq_bucket(3) == 4 and p.seq_bucket(4) == 4
    assert p.example_shape((5, 7)) == (8, 7)
    with pytest.raises(mx.MXNetError):
        p.batch_bucket(9)
    with pytest.raises(mx.MXNetError):
        p.seq_bucket(9)
    # max_batch rounds up to a power of two; no seq axis = identity
    assert BucketPolicy(max_batch=6).max_batch == 8
    assert BucketPolicy(max_batch=4).example_shape((5, 7)) == (5, 7)
    with pytest.raises(mx.MXNetError):
        BucketPolicy(seq_buckets=(4,))


def test_concurrent_clients_bitwise_match_predictor():
    """16 threads hammer one engine; every answer must be what a
    single-request Predictor computes for that example — to a few ulp:
    the engine answers from whichever batch bucket the request rode in,
    a different XLA program from the batch-1 Predictor."""
    net, params = _mlp()
    rng = np.random.default_rng(1)
    X = rng.standard_normal((64, 6)).astype(np.float32)
    results = [None] * len(X)

    with _engine(net, params, {"data": (6,)}) as eng:
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
    pred = mx.predict.Predictor(net, params, {}, {"data": (1, 6)},
                                ctx=mx.cpu())
    for i in range(len(X)):
        ref = pred.forward(data=X[i][None]).get_output(0)[0]
        assert_almost_equal(results[i], ref, *few_ulp_tol(ref))
    assert st["requests_served"] == len(X)
    assert st["batches"] <= len(X)          # some coalescing happened


def test_staged_batch_coalesces_and_pads():
    """Requests staged against a stopped engine go out as ONE padded
    batch: 5 requests -> bucket 8, occupancy 5/8.  The reference is a
    Predictor bound at that padded shape — the same program, so the
    comparison stays bitwise."""
    net, params = _mlp()
    rng = np.random.default_rng(2)
    X = rng.standard_normal((5, 6)).astype(np.float32)
    eng = _engine(net, params, {"data": (6,)}, start=False)
    eng.warmup()
    futs = [eng.submit(X[i]) for i in range(5)]
    eng.start()
    outs = [f.result(timeout=30) for f in futs]
    st = eng.stats()
    eng.close()
    pred = mx.predict.Predictor(net, params, {}, {"data": (8, 6)},
                                ctx=mx.cpu())
    padded = np.zeros((8, 6), np.float32)
    padded[:5] = X
    ref = pred.forward(data=padded).get_output(0)
    for i in range(5):
        np.testing.assert_array_equal(outs[i], ref[i])
    assert st["batches"] == 1
    assert st["batch_occupancy"] == pytest.approx(5 / 8)


def test_mixed_seq_shapes_bucketed():
    """Length-polymorphic traffic: seq buckets pad (L, 4) examples up to
    L in {4, 8}; outputs come back unpadded and bitwise equal to a
    Predictor bound at each exact shape."""
    net = mx.sym.Activation(mx.sym.Variable("data"), act_type="tanh",
                            name="act")
    rng = np.random.default_rng(3)
    lens = [3, 5, 8, 2, 4, 7]
    xs = [rng.standard_normal((L, 4)).astype(np.float32) for L in lens]
    policy = BucketPolicy(max_batch=4, seq_axis=0, seq_buckets=(4, 8))
    eng = serving.ServingEngine(net, {}, {}, {"data": (8, 4)},
                                ctx=mx.cpu(), policy=policy,
                                batch_timeout_ms=5.0, start=False)
    eng.warmup()
    futs = [eng.submit(x) for x in xs]
    eng.start()
    outs = [f.result(timeout=30) for f in futs]
    st = eng.stats()
    eng.close()
    for x, out in zip(xs, outs):
        assert out.shape == x.shape
        pred = mx.predict.Predictor(net, {}, {}, {"data": (1,) + x.shape},
                                    ctx=mx.cpu())
        ref = pred.forward(data=x[None]).get_output(0)[0]
        np.testing.assert_array_equal(out, ref)
    # program grid is (seq buckets) x (batch buckets), nothing off-grid
    assert st["bucket_keys"] <= \
        len(policy.seq_buckets) * len(policy.batch_buckets())
    assert st["compile_count"] == eng.compile_count


def test_deadline_expiry():
    net, params = _mlp()
    eng = _engine(net, params, {"data": (6,)}, start=False)
    doomed = eng.submit(np.zeros((6,), np.float32), deadline_ms=10)
    ok = eng.submit(np.ones((6,), np.float32))
    import time
    time.sleep(0.05)
    eng.start()
    with pytest.raises(DeadlineExceededError):
        doomed.result(timeout=30)
    assert ok.result(timeout=30).shape == (3,)
    st = eng.stats()
    eng.close()
    assert st["expired"] == 1


def test_backpressure_reject():
    net, params = _mlp()
    eng = _engine(net, params, {"data": (6,)}, start=False, max_queue=4)
    futs = [eng.submit(np.zeros((6,), np.float32)) for _ in range(4)]
    with pytest.raises(QueueFullError):
        eng.submit(np.zeros((6,), np.float32))
    eng.start()
    for f in futs:
        assert f.result(timeout=30).shape == (3,)
    st = eng.stats()
    eng.close()
    assert st["rejected"] == 1 and st["shed"] == 0


def test_overload_shed_oldest():
    net, params = _mlp()
    eng = _engine(net, params, {"data": (6,)}, start=False, max_queue=2,
                  overload_policy="shed-oldest")
    first = eng.submit(np.zeros((6,), np.float32))
    keep = [eng.submit(np.ones((6,), np.float32)) for _ in range(2)]
    with pytest.raises(ServerOverloadError):
        first.result(timeout=5)             # already failed, no worker
    eng.start()
    for f in keep:
        assert f.result(timeout=30).shape == (3,)
    st = eng.stats()
    eng.close()
    assert st["shed"] == 1 and st["rejected"] == 0


def test_zero_retrace_after_warmup():
    """The compile-once contract: warmup traces every bucket program;
    arbitrary warm traffic must add ZERO traces."""
    net, params = _mlp()
    eng = _engine(net, params, {"data": (6,)}, start=False)
    compiles = eng.warmup()
    assert compiles == len(BucketPolicy(max_batch=8).batch_buckets())
    rng = np.random.default_rng(4)
    futs = [eng.submit(rng.standard_normal((6,)).astype(np.float32))
            for _ in range(20)]
    eng.start()
    for f in futs:
        f.result(timeout=30)
    # a second wave against the live engine, varied arrival sizes
    for n in (1, 3, 8, 5):
        waves = [eng.submit(rng.standard_normal((6,)).astype(np.float32))
                 for _ in range(n)]
        for f in waves:
            f.result(timeout=30)
    assert eng.compile_count == compiles, \
        "warm traffic retraced: %d -> %d" % (compiles, eng.compile_count)
    eng.close()


def test_cancelled_future_does_not_kill_worker():
    """A client cancel()ing its pending future must not poison the
    batch or kill the worker thread — cancelled requests drop out of
    the dispatch, expiry sweeps tolerate them, and the engine keeps
    serving."""
    net, params = _mlp()
    eng = _engine(net, params, {"data": (6,)}, start=False)
    gone = eng.submit(np.zeros((6,), np.float32))
    doomed = eng.submit(np.zeros((6,), np.float32), deadline_ms=10)
    doomed.cancel()
    live = eng.submit(np.ones((6,), np.float32))
    assert gone.cancel()                    # pending -> cancelled
    import time
    time.sleep(0.05)                        # let the deadline lapse
    eng.start()
    assert live.result(timeout=30).shape == (3,)
    # the worker survived both the cancelled-expired sweep and the
    # cancelled in-batch request: new traffic still flows
    assert eng.predict(np.ones((6,), np.float32), timeout=30).shape == (3,)
    st = eng.stats()
    eng.close()
    assert st["requests_served"] == 2


def test_close_drains_without_worker():
    """close(drain=True) on a never-started engine must still resolve
    queued futures (drained inline) instead of leaving them pending."""
    net, params = _mlp()
    eng = _engine(net, params, {"data": (6,)}, start=False)
    futs = [eng.submit(np.ones((6,), np.float32)) for _ in range(3)]
    eng.close()
    for f in futs:
        assert f.result(timeout=30).shape == (3,)
    eng2 = _engine(net, params, {"data": (6,)}, start=False)
    dropped = eng2.submit(np.ones((6,), np.float32))
    eng2.close(drain=False)
    with pytest.raises(EngineClosedError):
        dropped.result(timeout=5)


def test_seq_unpad_spares_coincident_output_axis():
    """An output whose axis size merely COINCIDES with the seq pad
    length must pass through unsliced: unpad follows the shapes the
    graph infers at the unpadded input, not axis-size guessing.  Here a
    sum over the bucketed axis yields a pad-invariant (4,) row — the
    same size as the seq bucket — while the elementwise output still
    gets sliced back to the request's true length."""
    data = mx.sym.Variable("data")
    net = mx.sym.Group([mx.sym.sum(data, axis=1, name="pooled"),
                        mx.sym.Activation(data, act_type="tanh",
                                          name="act")])
    policy = BucketPolicy(max_batch=2, seq_axis=0, seq_buckets=(4,))
    eng = serving.ServingEngine(net, {}, {}, {"data": (4, 4)},
                                ctx=mx.cpu(), policy=policy,
                                batch_timeout_ms=5.0, start=False)
    rng = np.random.default_rng(5)
    x = rng.standard_normal((3, 4)).astype(np.float32)   # pads to (4, 4)
    fut = eng.submit(x)
    eng.start()
    pooled, act = fut.result(timeout=30)
    eng.close()
    assert pooled.shape == (4,)             # NOT truncated to (3,)
    np.testing.assert_allclose(pooled, x.sum(axis=0), rtol=1e-6)
    assert act.shape == x.shape             # elementwise: sliced back
    np.testing.assert_allclose(act, np.tanh(x), rtol=1e-6, atol=1e-7)


def test_program_cache_key_per_dispatch_when_stochastic():
    """Deterministic graphs freeze one rng key into the dispatch plan;
    stochastic graphs must fold a fresh key per run() or every batch
    replays identical draws."""
    net, params = _mlp()
    from mxnet_tpu.serving import ProgramCache
    pc = ProgramCache(net, {k: v for k, v in params.items()}, {},
                      ["data"], ctx=mx.cpu())
    x = np.zeros((2, 6), np.float32)
    pc.run({"data": x})
    det_plan = pc._plans[tuple(sorted({"data": x.shape}.items()))]
    assert det_plan[2] is not None          # key frozen into the plan
    # flip the graph's stochastic flag: fresh signature must plan key=None
    pc._op._graph_fn.stochastic = True
    try:
        y = np.zeros((4, 6), np.float32)
        pc.run({"data": y})
        sto_plan = pc._plans[tuple(sorted({"data": y.shape}.items()))]
        assert sto_plan[2] is None          # re-keyed on every dispatch
        keys = [pc._op._key(), pc._op._key()]
        assert not np.array_equal(np.asarray(keys[0]), np.asarray(keys[1]))
    finally:
        pc._op._graph_fn.stochastic = False


def test_retry_from_done_callback_does_not_deadlock():
    """concurrent.futures runs done-callbacks synchronously in the
    completing thread — a callback that re-enters the engine (the
    standard submit-on-failure retry pattern) must not deadlock on the
    admission lock when its future is shed or expired."""
    net, params = _mlp()
    eng = _engine(net, params, {"data": (6,)}, start=False, max_queue=1,
                  overload_policy="shed-oldest")
    retried = []
    first = eng.submit(np.zeros((6,), np.float32))
    first.add_done_callback(
        lambda f: retried.append(eng.submit(np.ones((6,), np.float32))))

    sheds = threading.Thread(
        target=lambda: eng.submit(np.full((6,), 2, np.float32)))
    sheds.start()
    sheds.join(timeout=10)
    assert not sheds.is_alive(), "admit deadlocked on a retry callback"
    assert len(retried) == 1                 # the callback ran and re-entered
    with pytest.raises(ServerOverloadError):
        first.result(timeout=5)
    eng.start()
    assert retried[0].result(timeout=30).shape == (3,)
    eng.close()


def test_submit_rejects_positional_and_named():
    net, params = _mlp()
    with _engine(net, params, {"data": (6,)}) as eng:
        with pytest.raises(mx.MXNetError):
            eng.submit(np.zeros((6,), np.float32),
                       data=np.ones((6,), np.float32))


def test_closed_engine_rejects_submit():
    net, params = _mlp()
    eng = _engine(net, params, {"data": (6,)})
    eng.close()
    with pytest.raises(EngineClosedError):
        eng.submit(np.zeros((6,), np.float32))
    with pytest.raises(EngineClosedError):
        eng.start()                 # closing is permanent, never a
        #                             silently-dead respawn


def test_submit_validates_shapes():
    net, params = _mlp()
    with _engine(net, params, {"data": (6,)}) as eng:
        with pytest.raises(mx.MXNetError):
            eng.submit(np.zeros((7,), np.float32))   # wrong feature dim
        with pytest.raises(mx.MXNetError):
            eng.submit(np.zeros((2, 6), np.float32))  # stray batch dim
        with pytest.raises(mx.MXNetError):
            eng.submit(other=np.zeros((6,), np.float32))


def test_serving_profiler_spans(tmp_path):
    """Enqueue/coalesce/dispatch emit Chrome-trace spans + counters on
    the 'serve' lane through the existing profiler."""
    import json
    from mxnet_tpu import profiler
    net, params = _mlp()
    profiler.clear()
    profiler.profiler_set_config(filename=str(tmp_path / "serve.json"))
    profiler.profiler_set_state("run")
    try:
        with _engine(net, params, {"data": (6,)}) as eng:
            eng.warmup()
            for _ in range(3):
                eng.predict(np.zeros((6,), np.float32), timeout=30)
    finally:
        profiler.profiler_set_state("stop")
    doc = json.load(open(profiler.dump_profile()))
    names = [e["name"] for e in doc["traceEvents"]]
    cats = {e["cat"] for e in doc["traceEvents"]}
    assert "serve" in cats
    assert any(n == "serve.enqueue" for n in names)
    assert any(n.startswith("serve.dispatch[") for n in names)
    assert any(n == "serve.queue_depth" for n in names)      # counter
    assert any(n == "serve.batch_occupancy" for n in names)  # counter


def test_serve_bench_smoke():
    """Fast non-slow variant of perf/serve_bench.py: tiny offered load,
    asserts the sweep machinery + the zero-retrace contract (the 3x
    speedup acceptance gate runs in the full bench, not here)."""
    perf_dir = os.path.join(os.path.dirname(__file__), os.pardir, "perf")
    sys.path.insert(0, perf_dir)
    try:
        import serve_bench
    finally:
        sys.path.remove(perf_dir)   # NOT pop(0): the import itself
        #                             prepends the repo root
    res = serve_bench.run_bench(requests=48, offered_batch=8, feature=6,
                                hidden=16, classes=3)
    assert res["retraces"] == 0
    assert res["engine_rps"] > 0 and res["serial_rps"] > 0
    assert res["requests"] == 48
    assert 0 < res["batch_occupancy"] <= 1.0
    assert res["p99_ms"] >= res["p50_ms"] >= 0
    # ISSUE 18 advisory efficiency fields priced from the FLOPs ledger
    assert res["analytic_gflops_per_s"] is None \
        or res["analytic_gflops_per_s"] > 0
    assert 0 < res["goodput_ratio"] <= 1.0
    assert "serve_mfu" in res           # honest None on CPU


# ---------------------------------------------------------------------------
# padding-soundness guards (analysis wiring + runtime probe)
# ---------------------------------------------------------------------------

def test_cross_position_batch_head_served_uncontaminated():
    """Satellite regression (ROADMAP padded-axis item): a head that
    normalizes over the BATCH axis.  Batch padding (and coalescing
    itself) would blend requests; the construction-time padding pass
    must catch it, warn, and degrade to per-request dispatch so every
    answer still matches a batch-1 Predictor bitwise."""
    import warnings as _w
    data = mx.sym.Variable("data")
    net = mx.sym.softmax(data, axis=0, name="sm_batch")
    rng = np.random.default_rng(7)
    X = rng.standard_normal((5, 6)).astype(np.float32)
    with _w.catch_warnings(record=True) as caught:
        _w.simplefilter("always")
        eng = serving.ServingEngine(net, {}, {}, {"data": (6,)},
                                    ctx=mx.cpu(), batch_timeout_ms=2.0,
                                    start=False)
    assert any("BATCH" in str(c.message) for c in caught)
    assert eng._policy.max_batch == 1        # coalescing disabled
    assert eng.analysis_report is not None
    assert any(d.node == "sm_batch"
               for d in eng.analysis_report.warnings)
    futs = [eng.submit(X[i]) for i in range(len(X))]
    eng.start()
    outs = [f.result(timeout=30) for f in futs]
    eng.close()
    pred = mx.predict.Predictor(net, {}, {}, {"data": (1, 6)},
                                ctx=mx.cpu())
    for i in range(len(X)):
        ref = pred.forward(data=X[i][None]).get_output(0)[0]
        np.testing.assert_array_equal(outs[i], ref)


def test_cross_position_seq_graph_refuses_bucket(monkeypatch):
    """softmax over the bucketed seq axis with the masking repair
    disabled (MXNET_SERVE_REPAIR=0): the engine drops the seq buckets
    (exact-length programs) instead of returning probabilities scaled
    down by the zero pads' exp(0) mass.  (With the repair enabled —
    the default since PR 4 — this graph serves from the bucket grid
    instead; tests/test_rewrite.py covers that path.)"""
    import warnings as _w
    monkeypatch.setenv("MXNET_SERVE_REPAIR", "0")
    data = mx.sym.Variable("data")
    net = mx.sym.softmax(data, axis=1, name="sm_seq")
    policy = BucketPolicy(max_batch=2, seq_axis=0, seq_buckets=(4,))
    with _w.catch_warnings(record=True) as caught:
        _w.simplefilter("always")
        eng = serving.ServingEngine(net, {}, {}, {"data": (4, 3)},
                                    ctx=mx.cpu(), policy=policy,
                                    batch_timeout_ms=2.0, start=False)
    assert any("seq" in str(c.message) for c in caught)
    assert eng._policy.seq_buckets == ()     # bucket refused
    x = np.random.default_rng(8).standard_normal((3, 3)).astype(np.float32)
    fut = eng.submit(x)                      # served at its exact length
    eng.start()
    out = fut.result(timeout=30)
    eng.close()
    pred = mx.predict.Predictor(net, {}, {}, {"data": (1, 3, 3)},
                                ctx=mx.cpu())
    ref = pred.forward(data=x[None]).get_output(0)[0]
    np.testing.assert_array_equal(out, ref)


def test_strict_mode_refuses_cross_position_engine(monkeypatch):
    monkeypatch.setenv("MXNET_ANALYSIS_STRICT", "1")
    net = mx.sym.softmax(mx.sym.Variable("data"), axis=0, name="sm0")
    with pytest.raises(mx.MXNetError):
        serving.ServingEngine(net, {}, {}, {"data": (6,)}, ctx=mx.cpu(),
                              start=False)


def test_runtime_pad_probe_catches_contamination(monkeypatch):
    """MXNET_SERVE_PAD_CHECK (the runtime half of the padding-soundness
    story): with the static pass off, the sentinel-pad probe must catch
    a cross-position graph at dispatch time — and stay silent on a
    row-local one."""
    monkeypatch.setenv("MXNET_ANALYSIS_ON", "0")
    monkeypatch.setenv("MXNET_SERVE_PAD_CHECK", "1")
    bad = mx.sym.softmax(mx.sym.Variable("data"), axis=0, name="sm0")
    eng = serving.ServingEngine(bad, {}, {}, {"data": (6,)}, ctx=mx.cpu(),
                                batch_timeout_ms=2.0, start=False)
    futs = [eng.submit(np.ones((6,), np.float32)) for _ in range(3)]
    eng.start()
    with pytest.raises(mx.MXNetError, match="contamination"):
        futs[0].result(timeout=30)
    eng.close(drain=False)

    net, params = _mlp()
    with _engine(net, params, {"data": (6,)}) as eng2:
        out = eng2.predict(np.ones((6,), np.float32), timeout=30)
    assert out.shape == (3,)


def test_analysis_report_attached_to_clean_engine():
    net, params = _mlp()
    with _engine(net, params, {"data": (6,)}) as eng:
        rep = eng.analysis_report
        assert rep is not None and rep.ok and not rep.warnings

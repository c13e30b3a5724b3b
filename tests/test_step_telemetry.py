"""Training-step attribution plane tests (telemetry/step.py +
analysis/flops.py + tools/step_report.py).

Acceptance contract (ISSUE 6): the exported phase breakdown sums to
>= 95% of measured step wall on a fit() workload with the residual
honest; the analytic-FLOPs count agrees with XLA's own cost analysis
within 10% (same numerator bench.py's MFU uses); aggregation over >= 2
rank snapshots names the straggling rank per phase; zero instrument
calls on the whole training path when telemetry is off; fit() results
bitwise identical telemetry-on vs -off; Monitor gauge series are
reclaimable; the TailSampler p99 window survives a reload.
"""
import json
import os
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.telemetry import step as step_mod


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    telemetry.set_enabled(None)
    telemetry.reset()
    yield
    telemetry.set_enabled(None)
    telemetry.reset()


def _import_tool(name):
    tooldir = os.path.join(os.path.dirname(__file__), os.pardir, "tools")
    sys.path.insert(0, tooldir)
    try:
        return __import__(name)
    finally:
        sys.path.remove(tooldir)


def _mlp(feature=6, hidden=16, classes=3):
    net = mx.sym.FullyConnected(mx.sym.Variable("data"),
                                num_hidden=hidden, name="fc1")
    net = mx.sym.Activation(net, act_type="relu", name="relu1")
    net = mx.sym.FullyConnected(net, num_hidden=classes, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _toy_fit(num_epoch=1, kvstore=None, batch=8, n=24, feature=6,
             monitor=None, seed=0, optimizer="sgd"):
    """3-steps-per-epoch toy fit; returns the fitted Module."""
    np.random.seed(seed)
    mx.random.seed(seed)
    rng = np.random.RandomState(seed)
    X = rng.randn(n, feature).astype(np.float32)
    Y = rng.randint(0, 3, (n,)).astype(np.float32)
    it = mx.io.NDArrayIter(X, Y, batch_size=batch)
    mod = mx.mod.Module(_mlp(feature=feature), context=mx.cpu())
    mod.fit(it, num_epoch=num_epoch, optimizer=optimizer,
            optimizer_params={"learning_rate": 0.1},
            kvstore=kvstore if kvstore is not None else "local",
            monitor=monitor)
    return mod


def _hist(doc, name):
    return {tuple(sorted(s["labels"].items())): s
            for s in doc.get(name, {}).get("series", [])}


# ---------------------------------------------------------------------------
# phase attribution on fit()
# ---------------------------------------------------------------------------

def test_fit_phase_breakdown_covers_step_wall(monkeypatch):
    """ISSUE acceptance: phases sum to >= 95% of measured step wall,
    every expected phase series exists, and counts equal steps."""
    monkeypatch.setenv("MXNET_TELEMETRY_TRACE_SAMPLE", "1")
    _toy_fit(kvstore=mx.kvstore.create("local"))
    doc = telemetry.registry().collect()

    steps = doc["mxnet_train_steps_total"]["series"][0]["value"]
    assert steps == 3
    step_h = doc["mxnet_train_step_seconds"]["series"][0]
    assert step_h["count"] == 3
    wall = step_h["sum"]
    assert wall > 0

    phases = doc["mxnet_train_step_phase_seconds"]["series"]
    names = {s["labels"]["phase"] for s in phases}
    # the kvstore path exercises every phase in the vocabulary
    assert {"data_wait", "h2d", "fwd_bwd", "kv_push", "kv_pull",
            "optimizer", "metric"} <= names
    for s in phases:
        assert s["labels"]["loop"] == "fit"
        assert s["count"] == 3, s["labels"]
    attributed = sum(s["sum"] for s in phases)
    # disjoint self-times: the sum can never exceed the wall, and the
    # acceptance bar demands it explains >= 95% of it
    assert attributed <= wall * 1.0001
    assert attributed >= 0.95 * wall, \
        "phases cover only %.1f%% of step wall" % (attributed / wall * 100)


def test_fit_steps_without_kvstore_have_optimizer_phase():
    _toy_fit()       # kvstore='local' + 1 device -> no store, updater path
    doc = telemetry.registry().collect()
    names = {s["labels"]["phase"]
             for s in doc["mxnet_train_step_phase_seconds"]["series"]}
    assert "optimizer" in names and "fwd_bwd" in names
    assert "kv_push" not in names       # no store on this path


def test_step_traces_retained_with_phase_spans(monkeypatch):
    """Per-step span trees ride the tail-biased store: with the
    periodic floor at 1 every step is retained, children carry the
    phase intervals, meta carries compile accounting."""
    monkeypatch.setenv("MXNET_TELEMETRY_TRACE_SAMPLE", "1")
    _toy_fit()
    trees = [t for t in telemetry.all_traces().values()
             if t["root"]["name"] == "train.step[fit]"]
    assert len(trees) == 3
    child_names = {c["name"] for c in trees[-1]["root"]["children"]}
    assert {"data_wait", "fwd_bwd", "optimizer", "metric"} <= child_names
    assert trees[-1]["root"]["meta"]["loop"] == "fit"
    # first step compiles, warm steps must not
    assert trees[0]["root"]["meta"]["compiles"] >= 1
    assert trees[-1]["root"]["meta"]["compiles"] == 0
    # io.py production spans annotate the step trace (join with the
    # mxnet_io_batch_latency_ms series) — on the FIRST step; the last
    # step's data_wait produces nothing (lookahead already drained it)
    assert any(c["name"].startswith("io.batch[")
               for c in trees[0]["root"]["children"])


def test_compile_accounting_counts_first_step_only():
    _toy_fit(num_epoch=2)
    doc = telemetry.registry().collect()
    assert doc["mxnet_train_steps_total"]["series"][0]["value"] == 6
    # one XLA trace burst on the first step; the other 5 steps are warm
    assert doc["mxnet_train_step_compiles_total"]["series"][0]["value"] == 1


# ---------------------------------------------------------------------------
# overhead discipline + bitwise parity
# ---------------------------------------------------------------------------

def test_zero_instrument_calls_when_disabled(monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY_ON", "0")
    _toy_fit(kvstore=mx.kvstore.create("local"))
    reg = telemetry.registry()
    assert reg.instrument_calls() == 0
    assert not any(n.startswith("mxnet_train") for n in reg.collect())


def test_fit_results_bitwise_identical_on_vs_off(monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY_TRACE_SAMPLE", "1")

    def run(enabled):
        telemetry.reset()
        telemetry.set_enabled(enabled)
        try:
            mod = _toy_fit(num_epoch=2, kvstore=mx.kvstore.create("local"))
            args, auxs = mod.get_params()
            return {k: v.asnumpy() for k, v in args.items()}
        finally:
            telemetry.set_enabled(None)

    off, on = run(False), run(True)
    assert set(off) == set(on)
    for k in off:
        assert np.array_equal(off[k], on[k]), \
            "param %s differs with telemetry on" % k


# ---------------------------------------------------------------------------
# the span seam (ISSUE 24): step and phases on the timeline ring
# ---------------------------------------------------------------------------

def _ring(name=None):
    from mxnet_tpu.telemetry import timeline
    tl = timeline.peek()
    evs = tl.events() if tl is not None else []
    return [e for e in evs if e["cat"] == "train" and e["lane"] != "trace"
            and (name is None or e["name"] == name)]


# an optimizer without a multi-tensor rule dispatches one update program
# a parameter; `sgd` has the rule and dispatches one a step (ISSUE 26)
_UPDATE_PATHS = pytest.mark.parametrize(
    "optimizer, fused", [("nag", False), ("sgd", True)])


@_UPDATE_PATHS
def test_fit_ring_holds_one_step_event_and_its_phases(monkeypatch, optimizer,
                                                      fused):
    from mxnet_tpu.telemetry import timeline
    monkeypatch.setenv("MXNET_TELEMETRY_TRACE_SAMPLE", "1")
    timeline.reset()
    mod = _toy_fit(optimizer=optimizer)  # 3 steps
    steps = _ring("fit.step")
    assert [e["args"]["step"] for e in steps] == [1, 2, 3]
    assert steps[0]["args"]["compiles"] >= 1
    assert steps[-1]["args"]["compiles"] == 0
    # the updates counter: the update programs dispatched, one a
    # parameter with a gradient or one for them all
    with_grad = [n for n in mod._param_names
                 if mod._exec.grad_dict.get(n) is not None]
    assert len(with_grad) == 4
    opt = _ring("fit.optimizer")
    assert [e["args"]["updates"] for e in opt] == [1 if fused else 4] * 3
    for name in ("fit.fwd_bwd", "fit.h2d", "fit.metric"):
        assert len(_ring(name)) == 3, name
    # the ring's budget: a step and its phases, about 8 events a step
    assert len(_ring()) <= 3 * 8 + 1
    # phases lie inside their step, on the one perf_counter clock
    for st, ph in zip(steps, opt):
        assert st["mono"] <= ph["mono"]
        assert ph["mono"] + ph["dur"] <= st["mono"] + st["dur"]
    # ring, phase histogram and the step's span tree read ONE interval
    hist = {s["labels"]["phase"]: s for s in telemetry.registry().collect()[
        "mxnet_train_step_phase_seconds"]["series"]}
    assert sum(e["dur"] for e in opt) == pytest.approx(
        hist["optimizer"]["sum"], rel=1e-9)
    trees = [t for t in telemetry.all_traces().values()
             if t["root"]["name"] == "train.step[fit]"]
    kid = [c for c in trees[-1]["root"]["children"]
           if c["name"] == "optimizer"][0]
    assert kid["dur_ms"] == pytest.approx(opt[-1]["dur"] * 1e3, abs=1e-3)
    timeline.reset()


@_UPDATE_PATHS
def test_fit_with_the_plane_off_appends_and_annotates_nothing(
        monkeypatch, optimizer, fused):
    """Telemetry on, timeline plane off: the phase histograms still
    fill, the ring never materializes, no profiler annotation is made,
    and the fitted parameters are bitwise those of the plane on."""
    from mxnet_tpu.telemetry import timeline
    marks = []
    inner = timeline._annotation
    monkeypatch.setattr(timeline, "_annotation",
                        lambda name: marks.append(name) or inner(name))

    def run(plane):
        monkeypatch.setenv("MXNET_TELEMETRY_TIMELINE", plane)
        telemetry.reset()
        timeline.reset()
        mod = _toy_fit(num_epoch=2, optimizer=optimizer)
        return {k: v.asnumpy() for k, v in mod.get_params()[0].items()}

    off = run("0")
    assert timeline.peek() is None and marks == []
    doc = telemetry.registry().collect()
    assert doc["mxnet_train_steps_total"]["series"][0]["value"] == 6
    assert {s["labels"]["phase"] for s in
            doc["mxnet_train_step_phase_seconds"]["series"]} >= {
                "fwd_bwd", "optimizer", "metric"}
    on = run("1")
    assert "mx:fit.step" in marks
    updates = [m for m in marks if m.startswith("mx:update/")]
    assert updates == (["mx:update/multi_tensor"] * 6 if fused else [
        "mx:update/" + n for n in ("fc1_weight", "fc1_bias", "fc2_weight",
                                   "fc2_bias")] * 6)
    assert len(_ring("fit.step")) == 6
    for k in off:
        assert np.array_equal(off[k], on[k]), k
    timeline.reset()


def test_update_region_reaches_the_chrome_ring_outside_any_step():
    """``Module.update`` by hand, telemetry off: no step is open and no
    plane is on, and ``mx.profiler`` still gets its "update" region."""
    from mxnet_tpu import profiler
    telemetry.set_enabled(False)
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mod.bind(data_shapes=[("data", (8, 6))],
             label_shapes=[("softmax_label", (8,))])
    mod.init_params()
    mod.init_optimizer(optimizer_params={"learning_rate": 0.1})
    batch = mx.io.DataBatch(data=[mx.nd.ones((8, 6))],
                            label=[mx.nd.zeros((8,))])
    profiler.clear()
    profiler.profiler_set_state("run")
    try:
        mod.forward_backward(batch)
        mod.update()
    finally:
        profiler.profiler_set_state("stop")
    cats = [e["cat"] for e in json.loads(profiler.dumps())["traceEvents"]]
    assert cats.count("update") == 1 and cats.count("backward") == 1
    profiler.clear()
    assert telemetry.registry().instrument_calls() == 0


@pytest.mark.parametrize("program", ["forward", "forward_backward"])
def test_lowered_hlo_names_the_symbol_nodes(program):
    """Each Symbol node lowers under ``jax.named_scope(<node name>)``:
    the bound executor's HLO carries the graph's names (and the
    backward's ops their forward node's), which is what lets a device
    trace say which layer an op belongs to."""
    import jax
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mod.bind(data_shapes=[("data", (8, 6))],
             label_shapes=[("softmax_label", (8,))])
    mod.init_params()
    ex = mod._exec
    if program == "forward":
        low = ex._get_fwd(False).lower(ex._arg_vals(), ex._aux_vals(),
                                       ex._key())
    else:
        fn = ex._get_fwd_bwd(False)
        old = tuple(ex.grad_dict[n]._data for n in ex._dense_grad_names)
        low = fn.lower(ex._arg_vals(), ex._aux_vals(), ex._key(), old)
    text = low.as_text(debug_info=True)
    scope = "/%s/" if program == "forward" else "/jvp(%s)/"
    for node in ("fc1", "relu1", "fc2", "softmax"):
        assert scope % node in text, node
    if program == "forward_backward":
        assert "/transpose(jvp(fc1))/dot_general" in text


# ---------------------------------------------------------------------------
# analytic FLOPs + MFU
# ---------------------------------------------------------------------------

def test_analytic_flops_match_xla_cost_analysis_within_10pct():
    """The MFU-gauge numerator vs XLA's own count for the same
    program (the bench.py cross-check, pinned here on CPU)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.analysis.flops import count_flops
    from mxnet_tpu.executor import build_graph_fn

    net = _mlp(feature=256, hidden=512, classes=10)
    shapes = {"data": (64, 256), "softmax_label": (64,)}
    res = count_flops(net, shapes, training=True)
    assert res["modeled_fraction"] > 0.9

    arg_names = net.list_arguments()
    g = build_graph_fn(net, arg_names, net.list_auxiliary_states())
    arg_shapes, _, _ = net.infer_shape(**shapes)
    rng = np.random.RandomState(0)
    args = tuple(jnp.asarray(rng.randn(*s).astype(np.float32))
                 for s in arg_shapes)

    def ca_flops(compiled):
        ca = compiled.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        return ca["flops"]

    fwd = jax.jit(lambda a: g(a, (), None, False)[0]).lower(args)
    xla_fwd = ca_flops(fwd.compile())
    assert abs(res["fwd"] - xla_fwd) / xla_fwd < 0.10

    didx = [i for i, n in enumerate(arg_names)
            if n not in ("data", "softmax_label")]
    lab = args[arg_names.index("softmax_label")].astype(jnp.int32)

    def loss_fn(*wrt):
        av = list(args)
        for i, w in zip(didx, wrt):
            av[i] = w
        probs = g(tuple(av), (), None, True)[0][0]
        return -jnp.mean(jnp.log(probs[jnp.arange(64), lab] + 1e-8))

    params = tuple(args[i] for i in didx)
    train = jax.jit(lambda p: jax.value_and_grad(
        lambda *w: loss_fn(*w),
        argnums=tuple(range(len(p))))(*p)).lower(params)
    xla_train = ca_flops(train.compile())
    assert abs(res["total"] - xla_train) / xla_train < 0.10, \
        "analytic %g vs xla %g" % (res["total"], xla_train)


def test_deconv_flops_scale_with_input_not_output():
    """Transposed conv contracts per INPUT element; reusing the conv
    formula on the stride-enlarged output would overcount ~stride^2."""
    from mxnet_tpu.analysis.flops import count_flops
    net = mx.sym.Deconvolution(mx.sym.Variable("data"), kernel=(2, 2),
                               stride=(2, 2), num_filter=8, name="up")
    res = count_flops(net, {"data": (1, 4, 8, 8)})
    expect = 2.0 * (1 * 4 * 8 * 8) * 8 * 4      # 2 * in * Cout * K*K
    assert res["by_op"]["Deconvolution"]["fwd_flops"] == expect


def test_mfu_gauge_formula():
    """gauge == flops / (step wall x peak), from the recorded wall."""
    st = step_mod.StepTimer(loop="mfu_test", flops_per_step=1e6,
                            peak_flops=1e9, retention=None)
    with st.step():
        time.sleep(0.01)
    doc = telemetry.registry().collect()
    wall = [s for s in doc["mxnet_train_step_seconds"]["series"]
            if s["labels"]["loop"] == "mfu_test"][0]["sum"]
    mfu = [s for s in doc["mxnet_train_mfu"]["series"]
           if s["labels"]["loop"] == "mfu_test"][0]["value"]
    assert mfu == pytest.approx(1e6 / (wall * 1e9), rel=1e-6)
    assert [s for s in doc["mxnet_train_step_flops"]["series"]
            if s["labels"]["loop"] == "mfu_test"][0]["value"] == 1e6
    st.close()
    doc = telemetry.registry().collect()
    assert not any(s["labels"].get("loop") == "mfu_test"
                   for fam in doc.values() for s in fam["series"])


def test_nested_phases_record_self_time():
    st = step_mod.StepTimer(loop="nest_test", retention=None)
    with st.step():
        with st.phase("optimizer"):
            time.sleep(0.02)
            with st.phase("kv_push"):
                time.sleep(0.02)
    doc = telemetry.registry().collect()
    by_phase = {s["labels"]["phase"]: s["sum"]
                for s in doc["mxnet_train_step_phase_seconds"]["series"]
                if s["labels"]["loop"] == "nest_test"}
    wall = [s for s in doc["mxnet_train_step_seconds"]["series"]
            if s["labels"]["loop"] == "nest_test"][0]["sum"]
    # child subtracts from parent: each phase owns ~20 ms of self-time
    # and their sum must not exceed the step wall (no double counting)
    assert by_phase["kv_push"] >= 0.018
    assert by_phase["optimizer"] >= 0.018
    assert by_phase["optimizer"] + by_phase["kv_push"] <= wall * 1.0001
    st.close()


def test_step_measured_by_the_caller_reaches_every_sink_alike():
    """``begin_step(t0)`` / ``end_step(t1)`` with stamps the caller
    took: the histogram and the ring's ``<loop>.step`` event hold that
    one interval."""
    from mxnet_tpu.telemetry import timeline
    timeline.reset()
    st = step_mod.StepTimer(loop="stamp_test", retention=None)
    t0 = time.perf_counter() - 1.0
    st.begin_step(t0)
    st.end_step(t0 + 0.25)
    wall = [s for s in telemetry.registry().collect()[
        "mxnet_train_step_seconds"]["series"]
        if s["labels"]["loop"] == "stamp_test"]
    st.close()
    ev = [e for e in timeline.get().events()
          if e["name"] == "stamp_test.step"]
    assert len(ev) == 1 and ev[0]["mono"] == t0
    assert ev[0]["dur"] == pytest.approx(0.25, rel=1e-9)
    assert len(wall) == 1 and wall[0]["sum"] == pytest.approx(0.25, rel=1e-9)
    timeline.reset()


# ---------------------------------------------------------------------------
# gluon Trainer + standalone loops
# ---------------------------------------------------------------------------

def test_gluon_trainer_step_counts_as_step():
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn
    net = nn.Dense(1, in_units=4, use_bias=False)
    net.initialize(mx.init.One())
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    x = mx.nd.ones((2, 4))
    for _ in range(2):
        with mx.autograd.record():
            loss = (net(x) ** 2).sum()
        loss.backward()
        trainer.step(batch_size=2)
    doc = telemetry.registry().collect()
    steps = [s for s in doc["mxnet_train_steps_total"]["series"]
             if s["labels"]["loop"] == "trainer"]
    assert steps and steps[0]["value"] == 2
    phases = {s["labels"]["phase"]
              for s in doc["mxnet_train_step_phase_seconds"]["series"]
              if s["labels"]["loop"] == "trainer"}
    assert "optimizer" in phases


def test_pipeline_standalone_step_spans_fb_through_update():
    """Standalone PipelineModule driving: the step opens at
    forward_backward (so the h2d staging is attributed) and closes at
    update — both phases must land on the loop="pipeline" series.
    (Dispatch is stubbed: the real pipeline step needs shard_map.)"""
    from mxnet_tpu.parallel.pipeline import PipelineModule
    pm = PipelineModule.__new__(PipelineModule)     # skip device setup
    pm._hetero = False
    pm._own_step = None
    pm._params = {}
    pm._train_step = lambda params, x, y: (0.5, params)

    class Batch(object):
        data = [mx.nd.ones((4, 2))]
        label = [mx.nd.ones((4,))]

    for _ in range(2):
        pm.forward_backward(Batch())
        pm.update()
    doc = telemetry.registry().collect()
    steps = [s for s in doc["mxnet_train_steps_total"]["series"]
             if s["labels"]["loop"] == "pipeline"]
    assert steps and steps[0]["value"] == 2
    phases = {s["labels"]["phase"]: s["count"]
              for s in doc["mxnet_train_step_phase_seconds"]["series"]
              if s["labels"]["loop"] == "pipeline"}
    assert phases.get("h2d") == 2 and phases.get("fwd_bwd") == 2
    # fb-without-update (user skipped a step) aborts cleanly, and the
    # next full step still records
    pm.forward_backward(Batch())
    pm.forward_backward(Batch())
    pm.update()
    doc = telemetry.registry().collect()
    steps = [s for s in doc["mxnet_train_steps_total"]["series"]
             if s["labels"]["loop"] == "pipeline"]
    assert steps[0]["value"] == 3


# ---------------------------------------------------------------------------
# metric-name lint over the new series
# ---------------------------------------------------------------------------

def test_train_series_pass_metric_name_lint():
    _toy_fit(kvstore=mx.kvstore.create("local"))
    assert telemetry.lint_metric_names() == []
    names = set(telemetry.registry().collect())
    assert {"mxnet_train_step_phase_seconds", "mxnet_train_step_seconds",
            "mxnet_train_steps_total", "mxnet_train_mfu",
            "mxnet_train_step_flops",
            "mxnet_train_step_compiles_total"} <= names


# ---------------------------------------------------------------------------
# monitor gauge reclaim (bugfix)
# ---------------------------------------------------------------------------

def test_monitor_close_reclaims_gauges():
    from mxnet_tpu.monitor import Monitor

    def run_monitor():
        mon = Monitor(interval=1, pattern=".*")
        mon.tic()
        mon.stat_helper("fc1_weight", mx.nd.ones((2, 2)))
        mon.stat_helper("fc1_output", mx.nd.ones((2,)))
        return mon

    mon = run_monitor()
    fam = telemetry.registry().get("mxnet_monitor_tensor_stat")
    assert len(fam.series()) == 2
    mon.close()
    assert len(fam.series()) == 0
    # a reload loop must not regrow orphans: a LATER monitor re-binds
    # fresh, scrape-visible children (the memo cache was invalidated)
    mon2 = run_monitor()
    assert len(fam.series()) == 2
    assert fam.labels(tensor="fc1_weight").value == 1.0
    mon2.close()
    assert len(fam.series()) == 0


# ---------------------------------------------------------------------------
# TailSampler p99 persistence (ROADMAP 5c)
# ---------------------------------------------------------------------------

def test_tail_sampler_state_round_trip(tmp_path, monkeypatch):
    from mxnet_tpu.telemetry import sampling
    path = str(tmp_path / "p99.json")

    ts = sampling.TailSampler(k=2)
    for i in range(150):        # arm the p99 estimate
        ts.decide(float(i % 50), None)
    assert ts._p99 is not None

    # simulate the reload: persist, rebuild via chain_from_config,
    # assert the fresh sampler starts warm instead of re-learning
    sampling._LIVE_TAIL.append(ts)
    assert sampling.persist_tail_state(path) == path
    # the registry holds STRONG refs: a fit()-local StepTimer dying
    # with fit must not make the atexit persist find nothing
    del ts
    import gc
    gc.collect()
    live = sampling._live_tail_sampler()
    assert live is not None
    assert sampling.persist_tail_state(path) == path
    assert sampling.restore_tail_state(path) is not None
    monkeypatch.setenv("MXNET_TELEMETRY_TRACE_SAMPLE", "64")
    monkeypatch.setenv("MXNET_TELEMETRY_TRACE_TAIL_K", "2")
    chain = sampling.chain_from_config()
    fresh = [s for s in chain.samplers
             if isinstance(s, sampling.TailSampler)][0]
    assert fresh._p99 == live._p99
    assert fresh._nobs == live._nobs
    assert sorted(fresh._heap) == sorted(live._heap)
    assert len(fresh._window) == len(live._window)
    # a fast request must NOT be kept by the (restored) p99 rule
    assert fresh.decide(0.5, None) != "tail_p99"
    # adopt-once: a SECOND chain built later in the process must start
    # cold, not re-seed itself from the boot-time sidecar
    chain2 = sampling.chain_from_config()
    fresh2 = [s for s in chain2.samplers
              if isinstance(s, sampling.TailSampler)][0]
    assert fresh2._p99 is None and fresh2._nobs == 0


def test_tail_registry_keeps_most_observed_sampler(monkeypatch):
    """A reload loop churning fresh chains must not evict the warmed
    long-lived window from persistence reach (eviction is by fewest
    observations, and persist picks the most-observed survivor)."""
    from mxnet_tpu.telemetry import sampling
    monkeypatch.setenv("MXNET_TELEMETRY_TRACE_SAMPLE", "64")
    monkeypatch.setenv("MXNET_TELEMETRY_TRACE_TAIL_K", "4")
    del sampling._LIVE_TAIL[:]
    warmed = [s for s in sampling.chain_from_config().samplers
              if isinstance(s, sampling.TailSampler)][0]
    for i in range(500):
        warmed.decide(float(i % 40), None)
    for _ in range(12):                     # churn: 12 cold chains
        sampling.chain_from_config()
    assert warmed in sampling._LIVE_TAIL
    assert sampling._live_tail_sampler() is warmed


def test_tail_state_default_sidecar_path(tmp_path, monkeypatch):
    from mxnet_tpu.telemetry import sampling
    monkeypatch.setenv("MXNET_TELEMETRY_SNAPSHOT_PATH",
                       str(tmp_path / "snap.json"))
    assert sampling.tail_state_path() == \
        str(tmp_path / "snap.json") + ".tailstate.json"
    monkeypatch.delenv("MXNET_TELEMETRY_SNAPSHOT_PATH")
    assert sampling.tail_state_path() is None
    # restoring malformed state must never break retention
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert sampling.restore_tail_state(str(bad)) is None
    ts = sampling.TailSampler(k=2)
    ts.restore({"window": "garbage", "heap": None})
    ts.restore({"p99": "garbage"})                  # bad field types
    ts.restore([1, 2, 3])                           # not even a dict
    assert ts._window == [] and ts._p99 is None     # no partial adopt
    assert ts.decide(1.0, None) == "tail_topk"      # still functional


# ---------------------------------------------------------------------------
# step_report CLI (tier-1 smoke) + cross-rank straggler attribution
# ---------------------------------------------------------------------------

def test_step_report_smoke_on_toy_fit(tmp_path, capsys, monkeypatch):
    """ISSUE CI satellite: report over a 3-step toy fit() snapshot —
    phases sum within tolerance and the residual row is printed."""
    monkeypatch.setenv("MXNET_TELEMETRY_TRACE_SAMPLE", "1")
    _toy_fit(kvstore=mx.kvstore.create("local"))
    snap = str(tmp_path / "steptel.json")
    telemetry.dump_state(snap)

    step_report = _import_tool("step_report")
    assert step_report.main([snap]) == 0
    out = capsys.readouterr().out
    assert "unattributed residual" in out
    assert "loop=fit" in out
    assert "input pipeline" in out
    cov = [ln for ln in out.splitlines() if "phase coverage" in ln]
    assert cov, "coverage line missing"
    pct = float(cov[0].split(":")[1].split("%")[0])
    assert pct >= 95.0

    # machine-readable path agrees
    assert step_report.main([snap, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    row = [r for r in doc["loops"] if r["loop"] == "fit"][0]
    assert row["steps"] == 3
    assert row["coverage"] >= 0.95
    assert row["residual_s"] >= 0.0


def test_step_report_names_straggler_rank_per_phase(tmp_path, capsys):
    """ISSUE acceptance: aggregate over >= 2 rank snapshots reports
    per-phase straggler attribution (rank 1 is made 5x slower in
    fwd_bwd; both tools must name it)."""
    from mxnet_tpu.telemetry import export
    files = []
    for rank, fwd_s in ((0, 0.010), (1, 0.050)):
        reg = telemetry.Registry()
        ph = reg.histogram("mxnet_train_step_phase_seconds", "phases",
                           ("loop", "phase"),
                           buckets=step_mod.STEP_SECONDS_BUCKETS)
        for _ in range(4):
            ph.labels(loop="fit", phase="fwd_bwd").observe(fwd_s)
            ph.labels(loop="fit", phase="data_wait").observe(0.001)
            reg.histogram("mxnet_train_step_seconds", "wall", ("loop",),
                          buckets=step_mod.STEP_SECONDS_BUCKETS) \
                .labels(loop="fit").observe(fwd_s + 0.001)
        reg.gauge("mxnet_train_mfu", "mfu", ("loop",)) \
            .labels(loop="fit").set(0.3 + 0.1 * rank)
        p = str(tmp_path / ("telemetry_rank%d.json" % rank))
        with open(p, "w") as f:
            f.write(export.render_json(reg, meta={"rank": rank}))
        files.append(p)

    dump = _import_tool("telemetry_dump")
    assert dump.main(["aggregate"] + files) == 0
    out = capsys.readouterr().out
    assert "histogram mean spread" in out
    line = [ln for ln in out.splitlines()
            if "mxnet_train_step_phase_seconds" in ln
            and "phase=fwd_bwd" in ln][0]
    assert "max=0.05 (rank 1)" in line

    step_report = _import_tool("step_report")
    assert step_report.main(files) == 0
    out = capsys.readouterr().out
    assert "rank=all" in out                 # fleet-summed table
    # gauges have no rank="all" series; the fleet row still shows the
    # reduced scalar (mean MFU across ranks) instead of dropping it
    assert "mfu=0.3500" in out
    strag = [ln for ln in out.splitlines()
             if "phase=fwd_bwd" in ln and "straggler" in ln][0]
    assert "straggler rank 1" in strag
    # the straggler view also flows through --json for dashboards
    assert step_report.main(files + ["--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    spread = doc["histogram_spread"]["mxnet_train_step_phase_seconds"]
    key = [k for k in spread if "fwd_bwd" in k][0]
    assert spread[key]["max_rank"] == "1"


def test_step_bench_telemetry_gate_smoke():
    """perf/step_bench.py --telemetry protocol runs end to end and
    produces the estimator fields (tiny workload; the gate verdict is
    hardware-dependent and not asserted here — only the math)."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))
    try:
        from perf.step_bench import run_train_telemetry_overhead
    finally:
        sys.path.pop(0)
    row = run_train_telemetry_overhead(steps=6, batch=4, feature=8,
                                       hidden=16, repeats=1)
    assert set(row) >= {"regression", "noise_floor", "tol", "ok",
                        "steps_per_s_telemetry_off",
                        "steps_per_s_telemetry_on"}
    assert row["steps_per_s_telemetry_on"] > 0
    # acceptance: on the step_bench workload too, the exported phase
    # breakdown explains >= 95% of measured step wall
    doc = telemetry.registry().collect()
    wall = doc["mxnet_train_step_seconds"]["series"][0]["sum"]
    attributed = sum(s["sum"] for s in
                     doc["mxnet_train_step_phase_seconds"]["series"])
    assert attributed >= 0.95 * wall

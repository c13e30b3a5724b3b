"""Every hot-path compiled program carries a fixed name, ``jit_<name>``:
what a TPU's trace calls each of its runs (the ``XLA Modules`` line of a
device plane), so that a step, a prefill and a commit can be told apart
on the device.  And the host spans that split the dispatches whose idle
gaps had no name: the parts of a prefill dispatch, the parts of a
forward-and-backward dispatch, and each collection of Python's heap.

A program's name is read from its lowering, ``lower(...).as_text()``,
with the abstract arguments of the trace the program itself made: the
block under :func:`_jit_programs` records them as ``jax.jit`` builds
each program.
"""
import contextlib
import gc
import glob
import inspect
import os
import threading

import numpy as np
import pytest

import jax
import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.serving import DecodeEngine, ServingEngine, faults
from mxnet_tpu.telemetry import timeline

from test_decode import _attn_step, _sum_state_model


def _abstract(x):
    if isinstance(x, jax.core.Tracer):
        return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                    weak_type=x.aval.weak_type)
    return x


@contextlib.contextmanager
def _jit_programs():
    """``{name: [(jitted, record)]}`` of every program ``jax.jit`` builds
    in the block; ``record["args"]`` is ``(args, kwargs)`` of its first
    trace, abstract.  The function handed to the real ``jax.jit`` keeps
    the name and the signature of the one the site handed in."""
    seen = {}
    real_jit = jax.jit

    def jit(fn, *a, **kw):
        record = {}

        def traced(*args, **kwargs):
            if "args" not in record:
                record["args"] = jax.tree_util.tree_map(
                    _abstract, (args, kwargs))
            return fn(*args, **kwargs)
        traced.__name__ = traced.__qualname__ = getattr(
            fn, "__name__", "fn")
        try:
            traced.__signature__ = inspect.signature(fn)
        except (TypeError, ValueError):
            pass
        out = real_jit(traced, *a, **kw)
        seen.setdefault(traced.__name__, []).append((out, record))
        return out
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "jit", jit)
        yield seen


def _module_names(seen, name):
    """The module name of each traced program built under ``name``."""
    out = []
    for fn, record in seen.get(name, []):
        if "args" in record:
            args, kwargs = record["args"]
            out.append(fn.lower(*args, **kwargs).as_text()
                       .split(" ", 2)[1])
    return out


@pytest.fixture(autouse=True)
def _fresh_timeline(monkeypatch):
    for var in ("MXNET_FAULT_PLAN", "MXNET_TELEMETRY_TIMELINE",
                "MXNET_TELEMETRY_TIMELINE_CAP", "MXNET_AOT_CACHE",
                "MXNET_AOT_CACHE_DIR"):
        monkeypatch.delenv(var, raising=False)
    faults.clear()
    telemetry.set_enabled(None)
    telemetry.reset()
    timeline.reset()
    yield
    telemetry.set_enabled(None)
    telemetry.reset()
    timeline.reset()


def _mlp(feature=6, hidden=16, classes=4):
    net = mx.sym.FullyConnected(mx.sym.Variable("data"),
                                num_hidden=hidden, name="fc1")
    net = mx.sym.Activation(net, act_type="relu", name="relu1")
    net = mx.sym.FullyConnected(net, num_hidden=classes, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _fit(optimizer="sgd"):
    X = np.random.RandomState(0).randn(16, 6).astype(np.float32)
    Y = np.array([0, 1, 2, 3] * 4, np.float32)
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mod.fit(mx.io.NDArrayIter(X, Y, batch_size=8), num_epoch=1,
            optimizer=optimizer, optimizer_params={"learning_rate": 0.1,
                                                   "momentum": 0.9})
    return mod


def _decode(model=None, **kw):
    """The one-dispatch prefill toy, served: returns the engine and the
    tokens.  Two engines hit each other's AOT entries only when built
    from one ``model`` (a symbol's digest holds its nodes' names)."""
    step, prefill, params, info = model or _sum_state_model()
    eng = DecodeEngine(step, params, {}, info, num_slots=2, max_len=32,
                       default_deadline_ms=0, prefill_sym=prefill, **kw)
    try:
        eng.warmup()
        futs = [eng.submit(p, max_new_tokens=4)
                for p in ([1, 2, 3], [4, 5], [6])]
        return eng, [list(f.result(timeout=120).tokens) for f in futs]
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# the programs' names
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def decode_programs():
    with _jit_programs() as seen:
        _decode()
    return seen


@pytest.mark.parametrize("name", ["mx_decode_step", "mx_decode_prefill",
                                  "mx_decode_commit", "mx_decode_set_row"])
def test_decode_programs_are_named(decode_programs, name):
    """The engine's step, its prefill at every bucket, the commit of a
    prefill's rows and the row write each lower as ``jit_<name>``."""
    names = _module_names(decode_programs, name)
    assert names and set(names) == {"@jit_" + name}


def test_speculative_step_is_named():
    tstep, tparams, tinfo = _attn_step(seed=0)
    dstep, dparams, dinfo = _attn_step(seed=1)
    for i in tinfo + dinfo:
        i["cache"] = True
    with _jit_programs() as seen:
        eng = DecodeEngine(tstep, tparams, {}, tinfo, num_slots=2,
                           max_len=16, default_deadline_ms=0,
                           draft_sym=dstep, draft_arg_params=dparams,
                           draft_state_info=dinfo, spec_k=2)
        try:
            eng.generate([1, 2], max_new_tokens=3, timeout=120)
        finally:
            eng.close()
    assert set(_module_names(seen, "mx_decode_spec_step")) \
        == {"@jit_mx_decode_spec_step"}
    assert "mx_decode_step" not in seen


@pytest.fixture(scope="module")
def train_programs():
    # the multi-tensor update is built once a process: build it here
    from mxnet_tpu import optimizer
    optimizer._multi_sgd_jit.cache_clear()
    try:
        with _jit_programs() as seen:
            mod = _fit()
            mod.predict(mx.io.NDArrayIter(
                np.ones((8, 6), np.float32), batch_size=8))
    finally:
        optimizer._multi_sgd_jit.cache_clear()
    return seen


@pytest.mark.parametrize("name", ["mx_forward", "mx_forward_backward",
                                  "mx_update_multi_sgd"])
def test_training_programs_are_named(train_programs, name):
    """``Module.fit``'s fused step and its one multi-tensor update, and
    the forward a prediction runs, each lower as ``jit_<name>``."""
    names = _module_names(train_programs, name)
    assert names and set(names) == {"@jit_" + name}


def test_forward_backward_with_head_gradients_is_named():
    ex = _mlp().simple_bind(mx.cpu(), data=(4, 6), softmax_label=(4,))
    with _jit_programs() as seen:
        ex.forward(is_train=True, data=mx.nd.ones((4, 6)))
        ex.backward(out_grads=[mx.nd.ones((4, 4))])
    assert set(_module_names(seen, "mx_forward_backward")) \
        == {"@jit_mx_forward_backward"}


def test_serving_batch_is_named():
    net = _mlp()
    rng = np.random.default_rng(0)
    params = {"fc1_weight": mx.nd.array(rng.standard_normal((16, 6))),
              "fc1_bias": mx.nd.zeros((16,)),
              "fc2_weight": mx.nd.array(rng.standard_normal((4, 16))),
              "fc2_bias": mx.nd.zeros((4,))}
    with _jit_programs() as seen:
        eng = ServingEngine(net, params, {}, {"data": (6,)}, ctx=mx.cpu())
        try:
            eng.warmup()
            eng.predict(np.ones((6,), np.float32), timeout=60)
        finally:
            eng.close()
    assert set(_module_names(seen, "mx_serve_batch")) \
        == {"@jit_mx_serve_batch"}


def test_gluon_keeps_a_generic_name():
    net = mx.gluon.nn.Dense(3, in_units=4)
    net.initialize()
    net.hybridize()
    with _jit_programs() as seen:
        net(mx.nd.ones((2, 4)))
    assert set(_module_names(seen, "mx_cached_op")) \
        == {"@jit_mx_cached_op"}


@pytest.mark.parametrize("source", ["miss", "hit"])
def test_aot_cache_serves_under_the_programs_names(tmp_path, monkeypatch,
                                                   source):
    """A program the AOT cache compiles (a miss) or loads (a hit) runs
    under its own name, as one compiled in process does: the outer
    ``jax.jit`` around the exported program takes the name."""
    monkeypatch.setenv("MXNET_AOT_CACHE_DIR", str(tmp_path / "aot"))
    monkeypatch.setenv("MXNET_AOT_CACHE", "1")
    model = _sum_state_model()
    if source == "hit":
        _eng, want = _decode(model)
    with _jit_programs() as seen:
        eng, got = _decode(model)
    st = eng.stats()["decode"]["aot"]
    if source == "hit":
        assert got == want and st["misses"] == 0 and st["hits"] > 0
    else:
        assert st["hits"] == 0 and st["misses"] > 0
    for name in ("mx_decode_step", "mx_decode_prefill", "mx_decode_commit",
                 "mx_decode_set_row"):
        assert set(_module_names(seen, name)) == {"@jit_" + name}, name


# ---------------------------------------------------------------------------
# the new spans
# ---------------------------------------------------------------------------

def _inside(events, child, parent):
    """Every ``child`` ring event lies inside a ``parent`` event of its
    lane; returns how many there are."""
    parents = [e for e in events if e["name"] == parent]
    kids = [e for e in events if e["name"] == child]
    for k in kids:
        assert any(p["lane"] == k["lane"] and p["mono"] <= k["mono"]
                   and k["mono"] + k["dur"] <= p["mono"] + p["dur"]
                   for p in parents), "%s outside every %s" % (child,
                                                                parent)
    return len(kids)


PREFILL_PARTS = ["decode.prefill.pad", "decode.prefill.dispatch",
                 "decode.prefill.commit", "decode.prefill.read"]
EXECUTOR_PARTS = ["executor.args", "executor.call", "executor.outputs"]


@pytest.mark.parametrize("child", PREFILL_PARTS)
def test_prefill_parts_nest_in_the_ring(child):
    telemetry.set_enabled(True)
    _eng, _toks = _decode()
    events = timeline.get().events()
    n = _inside(events, child, "decode.prefill")
    assert n == len([e for e in events if e["name"] == "decode.prefill"])


@pytest.mark.parametrize("child", EXECUTOR_PARTS)
def test_executor_parts_nest_in_the_ring(child):
    telemetry.set_enabled(True)
    _fit()
    events = timeline.get().events()
    n = _inside(events, child, "executor.forward_backward")
    assert n == 2 == len([e for e in events
                          if e["name"] == "executor.forward_backward"])


def _host_spans(trace_dir):
    """``(name, start_ns, end_ns)`` lists, a thread line each, of the
    ``mx:`` annotations on the trace's ``/host:`` planes."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    assert files, "the profiler wrote no .xplane.pb"
    out = []
    for plane in ProfileData.from_file(files[-1]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events if e.name.startswith("mx:")]
                if evs:
                    out.append(evs)
    return out


def test_new_spans_are_mx_annotations_in_the_profilers_trace(tmp_path):
    """The parts of both dispatches and a forced collection of the heap
    are ``mx:`` annotations in a ``jax.profiler`` trace, each part
    inside its parent on its thread."""
    telemetry.set_enabled(True)
    timeline.get()
    jax.profiler.start_trace(str(tmp_path))
    try:
        _decode()
        _fit()
        gc.collect()
    finally:
        jax.profiler.stop_trace()
    lines = _host_spans(str(tmp_path))
    for parent, kids in (("mx:decode.prefill", PREFILL_PARTS),
                         ("mx:executor.forward_backward", EXECUTOR_PARTS)):
        for kid in kids:
            n = 0
            for evs in lines:
                outer = [(s, e) for name, s, e in evs if name == parent]
                for name, s, e in evs:
                    if name == "mx:" + kid:
                        n += 1
                        assert any(a <= s and e <= b for a, b in outer), kid
            assert n, kid
    assert any(n == "mx:py.gc" for evs in lines for n, _s, _e in evs)


def test_a_forced_collection_is_a_ring_event(monkeypatch):
    telemetry.set_enabled(True)
    tl = timeline.get()
    monkeypatch.setattr(timeline._GC_SPAN, "MIN_S", 0.0)
    base = tl.appended()
    gc.collect()
    evs = [e for e in tl.events() if e["seq"] > base
           and e["name"] == "py.gc"]
    assert evs and evs[-1]["args"]["generation"] == 2
    assert evs[-1]["cat"] == "python" and evs[-1]["dur"] >= 0


def test_a_short_collection_is_annotated_but_not_kept(monkeypatch):
    telemetry.set_enabled(True)
    tl = timeline.get()
    marks = []
    inner = timeline._annotation
    monkeypatch.setattr(timeline, "_annotation",
                        lambda name: marks.append(name) or inner(name))
    monkeypatch.setattr(timeline._GC_SPAN, "MIN_S", 3600.0)
    base = tl.appended()
    gc.collect()
    assert "mx:py.gc" in marks
    assert not [e for e in tl.events() if e["seq"] > base
                and e["name"] == "py.gc"]


def test_gc_span_closes_on_its_own_thread_and_ignores_reentry(monkeypatch):
    """A ``stop`` on another thread leaves the open collection alone; a
    second ``start`` while one is open is ignored; with no ring the
    hook does nothing."""
    telemetry.set_enabled(True)
    tl = timeline.get()
    hook = timeline._GcSpan()
    monkeypatch.setattr(hook, "MIN_S", 0.0)
    info = {"generation": 1, "collected": 0, "uncollectable": 0}
    base = tl.appended()
    hook("start", info)
    hook("start", dict(info, generation=0))
    t = threading.Thread(target=hook, args=("stop", info))
    t.start()
    t.join()
    assert [e for e in tl.events() if e["seq"] > base] == []
    hook("stop", info)
    evs = [e for e in tl.events() if e["seq"] > base]
    assert [(e["name"], e["args"]["generation"]) for e in evs] \
        == [("py.gc", 1)]
    hook("stop", info)                  # nothing open: nothing kept
    timeline.reset()
    hook("start", info)
    hook("stop", info)
    assert hook._thread is None
    assert len([e for e in tl.events() if e["seq"] > base]) == 1


def test_the_hook_is_installed_once_with_the_ring():
    telemetry.set_enabled(True)
    timeline.get()
    timeline.reset()
    timeline.get()
    assert gc.callbacks.count(timeline._GC_SPAN) == 1


@pytest.mark.parametrize("work", ["decode", "fit"])
def test_plane_off_is_untimed_and_bitwise(monkeypatch, work):
    """With the plane off the new parts make no annotation and no ring
    event, and the answers are the plane-on answers."""
    telemetry.set_enabled(True)
    marks = []
    inner = timeline._annotation
    monkeypatch.setattr(timeline, "_annotation",
                        lambda name: marks.append(name) or inner(name))

    def run():
        if work == "decode":
            return _decode()[1]
        mx.random.seed(3)
        np.random.seed(3)
        mod = _fit()
        return {k: v.asnumpy() for k, v in mod.get_params()[0].items()}

    monkeypatch.setenv("MXNET_TELEMETRY_TIMELINE", "0")
    timeline.reset()
    off = run()
    assert timeline.peek() is None
    assert marks == []
    monkeypatch.setenv("MXNET_TELEMETRY_TIMELINE", "1")
    timeline.reset()
    on = run()
    parts = PREFILL_PARTS if work == "decode" else EXECUTOR_PARTS
    assert {"mx:" + p for p in parts} <= set(marks)
    if work == "decode":
        assert off == on
    else:
        assert off.keys() == on.keys()
        for k in off:
            np.testing.assert_array_equal(off[k], on[k])

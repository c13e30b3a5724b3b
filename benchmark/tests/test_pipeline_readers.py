"""The two readers of the decode loop's one step in flight
(``steps_ahead_share``, ``slot_steps_discarded_share``) on a hand-made
ring, with the arguments and without them, and ``step_scheduler_ms_p50``
on the ``decode.step`` events an engine writes now: one a step, written
when it is read, a step after it was dispatched."""
import numpy as np
import pytest

from benchmark import harness
from mxnet_tpu.telemetry import timeline

WINDOW = (100.0, 120.0)
NEW = ["steps_ahead_share", "slot_steps_discarded_share"]


def _read(name, window=WINDOW):
    return harness.load_module("layer_metrics", name).read(
        {"window": window})


@pytest.fixture
def ring(monkeypatch):
    tl = timeline.Timeline(capacity=64)
    monkeypatch.setattr(timeline, "_TL", tl)
    tl.complete("decode.step", "decode", "decode:0", 99.0, 99.5,
                args={"live": 1, "tokens": 1, "dispatch_ms": 400.0,
                      "read_ms": 50.0, "ahead": 0, "discarded": 1})
    return tl


def test_readers_on_a_ring_with_the_arguments(ring):
    # four steps in the window: the first onto an idle pool, three
    # ahead; 2 of 8 + 6 + 6 + 5 = 25 live slot-steps thrown away
    for i, (live, ahead, gone) in enumerate(
            [(8, 0, 0), (6, 1, 2), (6, 1, 0), (5, 1, 0)]):
        t0 = 101.0 + i
        ring.complete("decode.step", "decode", "decode:0", t0, t0 + 0.006,
                      args={"live": live, "tokens": live - gone,
                            "dispatch_ms": 0.7, "read_ms": 4.5,
                            "ahead": ahead, "discarded": gone})
    ring.complete("decode.step", "decode", "decode:0", 121.0, 121.5,
                  args={"live": 1, "tokens": 0, "dispatch_ms": 1.0,
                        "read_ms": 1.0, "ahead": 0, "discarded": 1})
    assert _read("steps_ahead_share") == pytest.approx(75.0)
    assert _read("slot_steps_discarded_share") == pytest.approx(8.0)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_arguments_reads_as_nothing(name, ring,
                                                          monkeypatch):
    assert _read(name) is None         # no event in the window
    # the parent's event: the span and its split, not the two arguments
    ring.complete("decode.step", "decode", "decode:0", 101.0, 101.008,
                  args={"live": 2, "tokens": 2, "dispatch_ms": 1.0,
                        "read_ms": 5.0})
    assert _read(name) is None
    assert _read("step_scheduler_ms_p50") == pytest.approx(2.0)
    monkeypatch.setattr(timeline, "_TL", None)      # no ring at all
    assert _read(name) is None


def test_readers_on_the_events_an_engine_writes(monkeypatch):
    """A plain engine's own ``decode.step`` events carry what the three
    readers take: every step but the first of a burst is ahead, nothing
    is discarded where every finish is by length, and the scheduler's
    share of an iteration is still its duration less the two parts."""
    import time
    import mxnet_tpu as mx
    from mxnet_tpu import serving, telemetry
    from mxnet_tpu.rnn.rnn_cell import LSTMCell
    tl = timeline.Timeline(capacity=4096)
    monkeypatch.setattr(timeline, "_TL", tl)
    telemetry.set_enabled(True)
    try:
        cell = LSTMCell(8, prefix="lstm_")
        emb = mx.sym.Embedding(mx.sym.Variable("token"), input_dim=8,
                               output_dim=8, name="emb")
        out, states = cell(emb, [mx.sym.Variable("h"), mx.sym.Variable("c")])
        logits = mx.sym.FullyConnected(out, num_hidden=8, name="head")
        step = mx.sym.Group([logits] + list(states))
        rng = np.random.RandomState(0)
        shapes, _, _ = step.infer_shape(token=(2,), h=(2, 8), c=(2, 8))
        params = {n: mx.nd.array(rng.randn(*s).astype(np.float32) * 0.3)
                  for n, s in zip(step.list_arguments(), shapes)
                  if n not in ("token", "h", "c")}
        eng = serving.DecodeEngine(
            step, params, {},
            [{"name": "h", "shape": (8,)}, {"name": "c", "shape": (8,)}],
            num_slots=2, max_len=32, default_deadline_ms=0)
        eng.warmup()
        tl.complete("mark", "bench", "bench", time.perf_counter(),
                    time.perf_counter())
        t0 = time.perf_counter()
        futs = [eng.submit([1, 2], max_new_tokens=6) for _ in range(3)]
        for f in futs:
            assert f.result(timeout=120).finish_reason == "length"
        window = (t0, time.perf_counter())
        steps = eng.stats()["decode"]
        eng.close()
    finally:
        telemetry.set_enabled(None)
    evs = [e for e in tl.events() if e["name"] == "decode.step"]
    assert evs and steps["steps_ahead"] > 0
    assert _read("steps_ahead_share", window) == pytest.approx(
        100.0 * sum(e["args"]["ahead"] for e in evs) / len(evs))
    assert _read("steps_ahead_share", window) > 50.0
    assert _read("slot_steps_discarded_share", window) == 0.0
    own = _read("step_scheduler_ms_p50", window)
    assert own is not None and own >= 0.0
    assert _read("step_dispatch_ms_p50", window) > 0.0
    assert _read("step_read_wait_ms_p50", window) > 0.0

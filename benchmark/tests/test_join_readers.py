"""The two readers of the join policy's counters
(``prefill_joins_per_dispatch``, ``slot_steps_held_share``) on a
hand-made ring, with the arguments and without them (a program that
seats every request the moment a slot is free writes neither ``live`` on
``decode.prefill`` nor ``held`` on ``decode.step``), and on the events an
engine writes now."""
import pytest

from benchmark import harness
from mxnet_tpu.telemetry import timeline

WINDOW = (100.0, 120.0)
NEW = ["prefill_joins_per_dispatch", "slot_steps_held_share"]


def _read(name, slots=8):
    return harness.load_module("layer_metrics", name).read(
        {"window": WINDOW, "counts": {"slots": slots}})


@pytest.fixture
def ring(monkeypatch):
    tl = timeline.Timeline(capacity=64)
    monkeypatch.setattr(timeline, "_TL", tl)
    tl.complete("decode.prefill", "decode", "decode:0", 99.0, 99.5,
                args={"bucket": 16, "group": 1, "live": 7})
    tl.complete("decode.step", "decode", "decode:0", 99.6, 99.7,
                args={"live": 1, "tokens": 1, "held": 7})
    return tl


def test_readers_on_a_ring_with_the_arguments(ring):
    # the ramp onto an empty pool (8 in one dispatch, nobody stopped),
    # then dispatches of 4, 2 and 3 that stopped 4-6 decoding slots
    for i, (group, live) in enumerate([(8, 0), (4, 4), (2, 6), (3, 5)]):
        t0 = 101.0 + 2 * i
        ring.complete("decode.prefill", "decode", "decode:0", t0, t0 + 0.03,
                      args={"bucket": 16, "group": group, "live": live})
    # five steps over 8 slots: 0 + 1 + 2 + 3 + 0 = 6 of 40 slot-steps held
    for i, held in enumerate([0, 1, 2, 3, 0]):
        t0 = 110.0 + i
        ring.complete("decode.step", "decode", "decode:0", t0, t0 + 0.006,
                      args={"live": 8 - held, "tokens": 8 - held,
                            "held": held})
    ring.complete("decode.prefill", "decode", "decode:0", 121.0, 121.1,
                  args={"bucket": 16, "group": 1, "live": 7})
    ring.complete("decode.step", "decode", "decode:0", 121.2, 121.3,
                  args={"live": 1, "tokens": 1, "held": 7})
    assert _read("prefill_joins_per_dispatch") == pytest.approx(3.0)
    assert _read("slot_steps_held_share") == pytest.approx(15.0)
    assert _read("slot_steps_held_share", slots=None) is None


def test_only_the_ramp_reads_as_nothing(ring):
    ring.complete("decode.prefill", "decode", "decode:0", 101.0, 101.03,
                  args={"bucket": 16, "group": 8, "live": 0})
    assert _read("prefill_joins_per_dispatch") is None


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_arguments_reads_as_nothing(name, ring,
                                                          monkeypatch):
    assert _read(name) is None         # no event in the window
    # the parent's events: the spans, not the two arguments
    ring.complete("decode.prefill", "decode", "decode:0", 101.0, 101.03,
                  args={"bucket": 16, "group": 2, "tokens": 20,
                        "padded": 32})
    ring.complete("decode.step", "decode", "decode:0", 102.0, 102.008,
                  args={"live": 2, "tokens": 2, "dispatch_ms": 1.0,
                        "read_ms": 5.0, "ahead": 1, "discarded": 0})
    assert _read(name) is None
    assert harness.load_module("layer_metrics", "prefill_padding_share") \
        .read({"window": WINDOW}) == pytest.approx(37.5)
    monkeypatch.setattr(timeline, "_TL", None)      # no ring at all
    assert _read(name) is None


def test_readers_on_the_events_an_engine_writes(monkeypatch):
    """An engine's own events carry both arguments: on an LSTM, whose
    joins ride the step, no request is ever held (0 %) and no prefill
    dispatch exists to count."""
    import time
    import numpy as np
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
        futs = [eng.submit([1, 2], max_new_tokens=5 + i) for i in range(5)]
        for f in futs:
            assert f.result(timeout=120).finish_reason == "length"
        obs = {"window": (t0, time.perf_counter()), "counts": {"slots": 2}}
        stats = eng.stats()["decode"]
        eng.close()
    finally:
        telemetry.set_enabled(None)
    assert harness.load_module("layer_metrics", "slot_steps_held_share") \
        .read(obs) == 0.0
    assert harness.load_module("layer_metrics", "prefill_joins_per_dispatch") \
        .read(obs) is None
    assert stats["slot_steps_held"] == 0 and stats["prefill_cost_ms"] == {}

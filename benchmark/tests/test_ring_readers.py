"""The readers of the program's own spans (``benchmark/ring.py`` and the
seven ``layer_metrics`` that use it) on a hand-made ring: what each
reads, and that a window the ring can no longer show whole, a program
without the seam, and no ring at all read as nothing."""
import pytest

from benchmark import harness
from mxnet_tpu.telemetry import timeline

WINDOW = (100.0, 120.0)
NAMES = ["step_dispatch_ms_p50", "step_read_wait_ms_p50",
         "step_scheduler_ms_p50", "queue_wait_p95_ms", "prompt_feed_p95_ms",
         "fwd_bwd_dispatch_ms_p50", "optimizer_updates_per_step"]


def _read(name):
    return harness.load_module("layer_metrics", name).read(
        {"window": WINDOW})


@pytest.fixture
def ring(monkeypatch):
    tl = timeline.Timeline(capacity=64)
    monkeypatch.setattr(timeline, "_TL", tl)
    tl.complete("decode.step", "decode", "decode:0", 99.0, 99.5,
                args={"live": 1, "tokens": 1, "dispatch_ms": 400.0,
                      "read_ms": 50.0})          # before the window
    return tl


def _decode_steps(tl):
    # three steps in the window: 8 / 10 / 12 ms, dispatch 1 / 2 / 3,
    # read 5 / 6 / 7, so the scheduler's own time is 2 / 2 / 2
    for i, (dur, disp, rd) in enumerate([(8, 1, 5), (10, 2, 6), (12, 3, 7)]):
        t0 = 101.0 + i
        tl.complete("decode.step", "decode", "decode:0", t0,
                    t0 + dur / 1e3,
                    args={"live": 2, "tokens": 2, "dispatch_ms": float(disp),
                          "read_ms": float(rd)})
    tl.complete("decode.step", "decode", "decode:0", 121.0, 121.5,
                args={"live": 1, "tokens": 1, "dispatch_ms": 400.0,
                      "read_ms": 50.0})          # after the window


def test_decode_step_readers(ring):
    _decode_steps(ring)
    assert _read("step_dispatch_ms_p50") == pytest.approx(2.0)
    assert _read("step_read_wait_ms_p50") == pytest.approx(6.0)
    assert _read("step_scheduler_ms_p50") == pytest.approx(2.0)


def test_first_token_readers_judge_by_the_enqueue_stamp(ring):
    for i in range(20):
        ring.instant("decode.first_token", "decode", "decode.tokens",
                     args={"enqueued": 101.0 + i * 0.5,
                           "queue_wait_ms": float(i),
                           "prompt_feed_ms": 100.0 + i, "prompt_len": 3,
                           "request": None})
    # enqueued before the window, first token inside it: not this window's
    ring.instant("decode.first_token", "decode", "decode.tokens",
                 args={"enqueued": 99.9, "queue_wait_ms": 5000.0,
                       "prompt_feed_ms": 5000.0, "prompt_len": 3,
                       "request": None})
    assert _read("queue_wait_p95_ms") == pytest.approx(18.05)
    assert _read("prompt_feed_p95_ms") == pytest.approx(118.05)


def test_fit_readers(ring):
    for i, dur in enumerate([0.011, 0.013, 0.012]):
        ring.complete("fit.fwd_bwd", "train", "train:fit", 101.0 + i,
                      101.0 + i + dur)
        ring.complete("fit.optimizer", "train", "train:fit", 101.5 + i,
                      101.7 + i, args={"updates": 161})
    assert _read("fwd_bwd_dispatch_ms_p50") == pytest.approx(12.0)
    assert _read("optimizer_updates_per_step") == 161


@pytest.mark.parametrize("name", NAMES)
def test_nothing_to_read_is_none(name, ring, monkeypatch):
    # the ring is there and holds none of this metric's events
    assert _read(name) is None
    # a program without the seam: the span is there, its arguments not
    ring.complete("decode.step", "decode", "decode:0", 101.0, 101.008,
                  args={"live": 2, "tokens": 2})
    ring.complete("fit.optimizer", "train", "train:fit", 101.0, 101.2)
    if name != "step_scheduler_ms_p50":
        assert _read(name) is None
    assert _read("step_scheduler_ms_p50") is None
    # no ring at all
    monkeypatch.setattr(timeline, "_TL", None)
    assert _read(name) is None


@pytest.mark.parametrize("name", NAMES)
def test_a_window_the_ring_has_cut_is_none(name, monkeypatch):
    tl = timeline.Timeline(capacity=8)
    monkeypatch.setattr(timeline, "_TL", tl)
    tl.complete("decode.step", "decode", "decode:0", 99.0, 99.5)
    for i in range(12):            # evicts the event before the window
        t0 = 101.0 + i
        tl.complete("decode.step", "decode", "decode:0", t0, t0 + 0.008,
                    args={"live": 2, "tokens": 2, "dispatch_ms": 1.0,
                          "read_ms": 5.0})
        tl.complete("fit.fwd_bwd", "train", "train:fit", t0, t0 + 0.012)
        tl.complete("fit.optimizer", "train", "train:fit", t0, t0 + 0.2,
                    args={"updates": 161})
        tl.instant("decode.first_token", "decode", "decode.tokens",
                   args={"enqueued": t0, "queue_wait_ms": 1.0,
                         "prompt_feed_ms": 100.0})
    assert tl.dropped() > 0
    assert _read(name) is None

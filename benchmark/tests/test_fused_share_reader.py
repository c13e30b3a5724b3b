"""``prefill_attention_fused_share`` on a hand-made ring: the share of
the window's ``decode.prefill`` spans whose every attention node took
the fused kernel; a program without the arguments (the parent's event),
a window with no prefill dispatch, and no ring at all read as nothing,
and the metrics that read the same span still do."""
import pytest

from benchmark import harness
from mxnet_tpu.telemetry import timeline

WINDOW = (100.0, 120.0)
NAME = "prefill_attention_fused_share"


def _read(name=NAME):
    return harness.load_module("layer_metrics", name).read(
        {"window": WINDOW})


@pytest.fixture
def ring(monkeypatch):
    tl = timeline.Timeline(capacity=64)
    monkeypatch.setattr(timeline, "_TL", tl)
    tl.complete("decode.step", "decode", "decode:0", 99.0, 99.5,
                args={"live": 1, "tokens": 1})     # older than the window
    return tl


def _prefill(tl, at, **more):
    args = {"bucket": 8192, "group": 1, "tokens": 6000, "padded": 8192}
    args.update(more)
    tl.complete("decode.prefill", "decode", "decode:0", at, at + 0.2,
                args=args)


@pytest.mark.parametrize("fused,want", [
    ([8, 8, 8, 8], 100.0), ([8, 6, 8, 0], 50.0), ([0, 0, 0, 0], 0.0)])
def test_share_of_dispatches_fused_whole(ring, fused, want):
    for i, n in enumerate(fused):
        _prefill(ring, 101.0 + i, fused_attention=n, attention_nodes=8)
    _prefill(ring, 121.0, fused_attention=0, attention_nodes=8)    # after
    assert _read() == pytest.approx(want)
    assert _read("prefill_ms_p50") == pytest.approx(200.0)


def test_nothing_to_read_is_none(ring, monkeypatch):
    assert _read() is None                 # no dispatch in the window
    _prefill(ring, 101.0)                  # the parent's event
    assert _read() is None
    assert _read("prefill_padding_share") is not None
    _prefill(ring, 102.0, fused_attention=0, attention_nodes=0)
    assert _read() is None                 # a program with no attention
    monkeypatch.setattr(timeline, "_TL", None)
    assert _read() is None                 # no ring at all

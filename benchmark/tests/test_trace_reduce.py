"""The reduction from a trace to numbers, on events small enough to
work out by hand and on a small trace recorded on a v5e."""
import os

import pytest

from benchmark import trace_reduce as tr

MS = 1e6        # nanoseconds


def _trace():
    # one chip, window 0..100 ms:
    #   fusion.1     0-10, 20-30          (20 ms)
    #   all-reduce   25-45                (20 ms)
    #   copy.2       40-60                (20 ms)
    # busy union 0-10, 20-60 = 50 ms; idle 10-20 and 60-100
    ops = [("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 0, 10 * MS),
           ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 20 * MS, 10 * MS),
           ("%all-reduce.3 = f32[8]{0} all-reduce(%x)", 25 * MS, 20 * MS),
           ("%copy.2 = f32[8]{0} copy(%y)", 40 * MS, 20 * MS)]
    spans = [("bench:window", 0, 100 * MS),
             ("bench:Module.update", 8 * MS, 14 * MS),     # covers 10-20
             ("bench:outer", 55 * MS, 45 * MS),            # covers 60-100
             ("bench:inner", 70 * MS, 30 * MS)]            # covers 70-100
    return {"devices": {"/device:TPU:0": ops}, "spans": spans}


def test_busy_union_idle_and_per_op_sums():
    r = tr.reduce(_trace(), window=(0, 100 * MS))
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s"] == pytest.approx(0.050)
    assert 1 - r["busy_s"] / r["window_s"] == pytest.approx(0.5)
    assert dict(map(tuple, r["device_ops"])) == pytest.approx(
        {"fusion.1": 0.020, "all-reduce.3": 0.020, "copy.2": 0.020})
    assert r["n_ops"] == 4


def test_idle_time_is_divided_among_the_host_spans_that_hold_it():
    r = tr.reduce(_trace(), window=(0, 100 * MS))
    gaps = dict(map(tuple, r["idle_gaps"]))
    # 10-20 lies in Module.update; 60-70 in outer alone; 70-100 in outer
    # and inner, and the innermost span takes it
    assert gaps == pytest.approx({"Module.update": 0.010, "outer": 0.010,
                                  "inner": 0.030})
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_idle_time_no_span_holds_lies_outside():
    t = _trace()
    t["spans"] = [("bench:window", 0, 100 * MS),
                  ("bench:Module.update", 8 * MS, 3 * MS)]   # 1 ms of 10-20
    gaps = dict(map(tuple, tr.reduce(t, window=(0, 100 * MS))["idle_gaps"]))
    assert gaps == pytest.approx({"Module.update": 0.001, tr.OUTSIDE: 0.049})


def test_window_clips_events_and_chips_are_averaged():
    t = _trace()
    t["devices"]["/device:TPU:1"] = [("%fusion.9 = f32[] fusion()", 0,
                                      100 * MS)]
    r = tr.reduce(t, window=(5 * MS, 55 * MS))
    # chip 0 in 5..55: 5-10 and 20-55 = 40 ms; chip 1: 50 ms
    assert r["busy_s"] == pytest.approx(0.045)
    assert r["busiest"] == "/device:TPU:1" and r["chips"] == 2


def test_no_device_operation_is_an_error():
    with pytest.raises(RuntimeError):
        tr.reduce({"devices": {}, "spans": []})
    with pytest.raises(RuntimeError):
        tr.reduce({"devices": {"/device:TPU:0": []}, "spans": []},
                  window=(0, 1))


def test_recorded_v5e_trace():
    """48 BIG-LSTM decode steps traced on one v5e chip (PR 23's
    bring-up probe): the file's planes and lines are found, and the
    union agrees with a brute-force sweep over a 1 us grid."""
    path = os.path.join(os.path.dirname(__file__), "data",
                        "decode_small.xplane.pb")
    t = tr.load_file(path)
    assert list(t["devices"]) == ["/device:TPU:0"]
    events = t["devices"]["/device:TPU:0"]
    assert len(events) == 3504
    assert [n for n, _s, _d in t["spans"]] == ["bench:go"]
    r = tr.reduce(t)
    lo = min(s for _n, s, _d in events)
    hi = max(s + d for _n, s, d in events)
    grid = bytearray(int((hi - lo) / 1e3) + 2)
    for _n, s, d in events:
        a, b = int((s - lo) / 1e3), int((s + d - lo) / 1e3)
        grid[a:b + 1] = b"\x01" * (b + 1 - a)
    assert r["busy_s"] == pytest.approx(sum(grid) / 1e6, rel=0.02)
    assert 0.5 < r["busy_s"] / r["window_s"] < 0.9
    assert r["device_ops"][0][0] == "fusion.1"      # the head and argmax

"""``benchmark/trace_programs.py``: the device's time by compiled program
(the "XLA Modules" line of a TPU plane) and the idle gaps under the
program's own ``mx:`` spans, beside ``trace_reduce``'s numbers, which it
leaves as they are."""
import os

import pytest

from benchmark import trace_programs as tp
from benchmark import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data")
MS = 1e6


def test_program_names_drop_the_fingerprint():
    assert tp.program_name("jit_call(8626864232011996728)") == "jit_call"
    assert tp.program_name("jit_mx_decode_step(1)") == "jit_mx_decode_step"
    assert tp.program_name("jit_mx_forward") == "jit_mx_forward"


def test_programs_count_clip_and_take_the_median_of_whole_runs():
    runs = [("jit_mx_decode_step", 0, 5 * MS), ("jit_mx_decode_step",
                                                10 * MS, 5 * MS),
            ("jit_mx_decode_step", 20 * MS, 7 * MS),
            ("jit_mx_decode_prefill", 30 * MS, 50 * MS),
            ("jit_other", 200 * MS, 1 * MS)]
    got = tp.programs(runs, 2 * MS, 60 * MS)
    assert list(got) == ["jit_mx_decode_prefill", "jit_mx_decode_step"]
    step = got["jit_mx_decode_step"]
    assert step["runs"] == 3
    assert step["device_s"] == pytest.approx((3 + 5 + 7) * 1e-3)
    assert step["median_ms"] == pytest.approx(6.0)      # 5 and 7 whole
    prefill = got["jit_mx_decode_prefill"]
    assert prefill == {"runs": 1, "device_s": pytest.approx(0.03),
                       "median_ms": None}


def test_named_busy_share_is_the_busy_time_inside_named_runs():
    ops = [("a", 0, 10), ("b", 5, 10), ("c", 40, 10), ("d", 70, 10)]
    runs = [("jit_mx_decode_step", 0, 20), ("jit_call", 38, 14),
            ("jit_mx_decode_prefill", 75, 30)]
    # busy: [0, 15] [40, 50] [70, 80] = 35; named: 15 + 5
    assert tp.named_busy_share(ops, runs, 0, 100) == pytest.approx(20 / 35)
    assert tp.named_busy_share(ops, runs, 60, 100) == pytest.approx(0.5)
    assert tp.named_busy_share([], runs, 0, 100) is None


def test_idle_gaps_go_to_the_innermost_program_span():
    ops = [("a", 0, 10), ("b", 50, 10)]
    marks = [("mx:decode.prefill", 5, 40),
             ("mx:decode.prefill.read", 30, 15)]
    got = dict(tp.idle_gaps_program(ops, marks, 0, 100))
    assert got == {"mx:decode.prefill": pytest.approx(20e-9),
                   "mx:decode.prefill.read": pytest.approx(15e-9),
                   tp.OUTSIDE: pytest.approx(45e-9)}


def test_recorded_v5e_trace_keeps_every_existing_number():
    """The fixture of ``test_trace_reduce.py`` (48 BIG-LSTM steps from
    before the programs had names): ``trace_reduce`` reads what it
    read, ``trace_programs.reduce`` adds its keys to exactly that, and
    the device's record holds 48 runs of ``jit_call``, 5.43 ms each."""
    path = os.path.join(DATA, "decode_small.xplane.pb")
    before = tr.reduce(tr.load_file(path))
    assert before == {
        "window_s": pytest.approx(0.367788393, abs=1e-12),
        "busy_s": pytest.approx(0.260611612, abs=1e-12),
        "chips": 1, "busiest": "/device:TPU:0", "n_ops": 3407,
        "device_ops": [
            ["fusion.1", pytest.approx(0.219722933, abs=1e-12)],
            ["convolution_add_fusion.2", pytest.approx(0.008646078,
                                                       abs=1e-12)],
            ["convolution_add_fusion.4", pytest.approx(0.008618665,
                                                       abs=1e-12)],
            ["fusion.3", pytest.approx(0.008618157, abs=1e-12)],
            ["fusion.2", pytest.approx(0.008610246, abs=1e-12)],
            ["copy-done.1", pytest.approx(0.001525265, abs=1e-12)],
            ["slice-done.6", pytest.approx(0.001121196, abs=1e-12)],
            ["slice-done.3", pytest.approx(0.000657105, abs=1e-12)],
            ["fusion.32", pytest.approx(0.000652251, abs=1e-12)],
            ["fusion.17", pytest.approx(0.000652001, abs=1e-12)]],
        "idle_gaps": [["go", pytest.approx(0.107176781, abs=1e-12)]]}
    t = tp.load_file(path)
    after = tp.reduce(t)
    assert {k: after[k] for k in before} == before
    assert after["programs"] == {"jit_call": {
        "runs": 48, "device_s": pytest.approx(0.26063068, abs=1e-12),
        "median_ms": pytest.approx(5.43, abs=0.005)}}
    assert after["named_busy_share"] == 0.0       # none was named then
    assert t["marks"] == []
    assert after["idle_gaps_program"] == [
        [tp.OUTSIDE, pytest.approx(0.107176781, abs=1e-12)]]


# what each short trace holds (``perf/program_trace.py --keep`` on one
# v5e; commands in PERF.md section 5): the BIG-LSTM cell's step, and
# LFM2's cell at a tiny size (``--overrides``) with its prefill, both
# trimmed (``trace_programs.py --trim``: 197 KB and 2.0 MB as recorded)
NAMED = {"programs_biglstm_decode": {"jit_mx_decode_step"},
         "programs_lfm2_tiny": {"jit_mx_decode_step",
                                "jit_mx_decode_prefill",
                                "jit_mx_decode_commit"}}


@pytest.mark.parametrize("name", sorted(NAMED))
def test_named_trace_every_busy_microsecond_is_in_an_mx_program(name):
    """Short traces taken by the benchmark with the programs named:
    every microsecond the chip was busy in the window lies inside a run
    named ``jit_mx_*``, and the program's spans are in the trace."""
    path = os.path.join(DATA, name + ".xplane.pb")
    assert os.path.getsize(path) < 1 << 20
    t = tp.load_file(path)
    r = tp.reduce_window(t)
    assert r["busy_s"] > 0
    assert set(r["programs"]) == NAMED[name]
    assert (1.0 - r["named_busy_share"]) * r["busy_s"] < 1e-6
    assert {n for n, _s, _d in t["marks"]} >= {"mx:decode.step"}


@pytest.mark.parametrize("name,smaller", [("decode_small", True),
                                          ("programs_biglstm_decode", False)])
def test_a_trimmed_trace_reads_what_the_recorded_one_read(tmp_path, name,
                                                          smaller):
    """Trimming keeps every number the readers read; a recorded trace
    comes out smaller, a trimmed one (the named fixture) no larger."""
    path = os.path.join(DATA, name + ".xplane.pb")
    out = tmp_path / "trimmed.xplane.pb"
    out.write_bytes(tp.trimmed(path))
    if smaller:
        assert out.stat().st_size < os.path.getsize(path)
    else:
        assert out.stat().st_size <= os.path.getsize(path)
    assert tp.reduce_window(tp.load_file(str(out))) \
        == tp.reduce_window(tp.load_file(path))

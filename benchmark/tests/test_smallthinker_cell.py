"""``smallthinker-decode-longdoc`` on the CPU at a tiny size: the cell
end to end through ``run.main``'s override (the ``serve_prefill`` driver,
one-dispatch prefill into ring and whole caches, the result line's keys);
each of the reference's four controls over a limit that a sound run is
under; ``step_required`` and ``prefill_required`` against hand counts;
the six readers this cell brings, on a hand-made ring."""
import contextlib
import io
import json

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness, run
from mxnet_tpu.telemetry import timeline

CELL = "smallthinker-decode-longdoc"
TINY = dict(hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
            head_dim=8, vocab_size=64, moe_num_primary_experts=8,
            moe_num_active_primary_experts=3, moe_ffn_hidden_size=16,
            sliding_window_size=8, num_hidden_layers=8, dtype="float32")
OVERRIDES = {
    "platform": "cpu", "device_kind": "TPU v5 lite", "config": TINY,
    "traffic": {
        "engine": {"num_slots": 4, "max_len": 64, "max_queue": 64},
        "clients": 8,
        "prompt_len": {"dist": "lognormal", "median": 20, "sigma": 0.3,
                       "min": 17, "max": 32},
        "new_tokens": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                       "min": 3, "max": 16}},
    # float32 against float32: sums in another order alone
    "correct": {"sample_requests": 3,
                "limits": {"served_token_gap_max": 1e-4,
                           "served_token_gap_p99": 1e-4,
                           "served_off_best_share": 0.0,
                           "requests_unanswered_or_cut": 0,
                           "retraces_after_warmup": 0}}}
NAMES = ["prefill_ms_p50", "prefill_window_share", "prefill_padding_share",
         "prefill_mfu", "expert_load_max_over_mean",
         "cache_rows_read_over_required"]


@pytest.fixture(scope="module")
def cfg_mod():
    return harness.load_module("configs", "smallthinker-21ba3b-8l-bf16")


@pytest.fixture(scope="module")
def ref():
    return harness.load_module("reference", "smallthinker-21ba3b-8l-bf16")


def _cfg():
    cfg = harness.load_json("configs", "smallthinker-21ba3b-8l-bf16.json")
    cfg.update(TINY)
    return cfg


def test_cell_end_to_end_on_the_cpu():
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = run.main(["--workload", CELL, "--seed", "3000000029",
                       "--seconds", "2", "--trace", "0"],
                      overrides=OVERRIDES)
    assert rc == 0, err.getvalue()[-2000:]
    res = json.loads(out.getvalue().strip().splitlines()[-1])
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 8
    assert set(res["metrics"]) == {"decode_tokens_per_s", "setup_s"}
    assert set(res) >= {"correct", "attempted", "failed", "metrics",
                        "device", "end_to_end_seen", "counts", "checks"}
    counts = res["counts"]
    assert counts["retraces"] == 0 and counts["prefill_dispatches"] > 0
    assert counts["prefill_programs"] == 3          # 1, 2, 4 x 32
    assert counts["state_rows"]["l0_k"] == 64
    assert counts["state_rows"]["l1_k"] == 8
    assert set(res["checks"]) == {"served_token_gap_max",
                                  "served_token_gap_p99",
                                  "served_off_best_share",
                                  "requests_unanswered_or_cut",
                                  "retraces_after_warmup"}


@pytest.fixture(scope="module")
def sound(cfg_mod, ref):
    """Weights from a seed and four requests decoded greedily by the
    reference itself: what a sound run serves."""
    cfg = _cfg()
    params = cfg_mod.init_params(cfg, 7)
    rng = np.random.default_rng(7)
    requests = []
    for plen in (12, 20, 27, 31):
        seq = rng.integers(1, cfg["vocab_size"], plen).tolist()
        served = []
        for _ in range(12):
            served.append(int(np.argmax(np.asarray(
                ref.forward(params, cfg, seq + served)[-1]))))
        requests.append((seq, served))
    return cfg, params, requests


def test_sound_run_reads_under_the_limit(ref, sound):
    cfg, params, requests = sound
    got = ref.served_gaps(params, cfg, requests, width=48)
    assert got["tokens"] == 48
    assert got["gaps"].max() <= 1e-4


@pytest.mark.parametrize("control", ["fp8", "top5", "no_window", "rope_all"])
def test_each_control_reads_over_the_limit(ref, sound, control):
    """The control's own first choice, read in the reference's logits,
    lies a visible share of a standard deviation under the best at some
    served position (contexts run past the window of 8, so the window
    and the rotation matter; three of eight experts, so the third does)."""
    cfg, params, requests = sound
    got = ref.served_gaps(params, cfg, requests, precision=control, width=48)
    assert got["gaps"].max() > 0.02, got["gaps"].max()
    assert (got["gaps"] > 0).mean() > 0.02


def test_calibrate_reads_every_listed_control_through_the_cell_s_checks():
    """``calibrate.py`` at the tiny size: on the seed where it reads its
    one ``control``, the driver reads every control the ``correct`` file
    lists through ``compare`` and logs a line each; each comes out not
    correct under limits a sound run is under, and the run's own result
    stays correct."""
    from benchmark import calibrate
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = calibrate.main(["--workload", CELL, "--seeds", "3000000031,5",
                             "--seconds", "1.5", "--control", "1"],
                            overrides=OVERRIDES)
    assert rc == 0, err.getvalue()[-2000:]
    recs = [json.loads(line) for line in out.getvalue().splitlines()
            if line.startswith("{")]
    assert [r["correct"] for r in recs] == [True, True]
    assert "control_fp8" in recs[0] and "control_fp8" not in recs[1]
    lines = [json.loads(line.split("control ", 1)[1])
             for line in err.getvalue().splitlines()
             if line.startswith("[bench] control ")]
    assert [c["control"] for c in lines] == harness.load_json(
        "correct", CELL + ".json")["controls"]
    assert {c["seed"] for c in lines} == {3000000031}
    for c in lines:
        assert c["correct"] is False, c
        assert set(c["read"]) == {"served_token_gap_max",
                                  "served_token_gap_p99",
                                  "served_off_best_share"}


def test_device_seconds_are_read_inside_the_program_s_annotation(tmp_path):
    """On the CPU a trace has the host's annotations and no chip: every
    ``mx:decode.prefill`` is found, oldest first, with nothing run
    inside it; a trace directory without a profile reads as nothing."""
    import jax
    driver = harness.load_module("drivers", "serve_prefill")
    assert driver.device_seconds_inside(str(tmp_path), "mx:x") == []
    tl = timeline.Timeline(capacity=8)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(2):
            with tl.annotate("decode.prefill"):
                jnp.ones((8,)).sum().block_until_ready()
        with tl.annotate("decode.step"):
            pass
    finally:
        jax.profiler.stop_trace()
    assert driver.device_seconds_inside(
        str(tmp_path), driver.PREFILL_SPAN) == [0.0, 0.0]


def test_unknown_control_is_refused(ref, sound):
    cfg, params, requests = sound
    with pytest.raises(ValueError):
        ref.served_gaps(params, cfg, requests, precision="float16")


def test_step_required_against_hand_counts(cfg_mod):
    cfg = _cfg()
    # a layer: q 32x32, k and v 16x32, o 32x32, router 8x32 = 3,328;
    # an expert 3 x 32 x 16 = 1,536; head 64 x 32 = 2,048
    # two live slots, contexts 5 and 20, window 8 on six layers:
    # rows (k and v apart) 6 x 2 x (5 + 8) + 2 x 2 x (5 + 20) = 256
    got = cfg_mod.step_required(cfg, 4, [5, 20])
    assert got["cache_rows"] == 256
    weights = 8 * (3328 + 2 * 32 + 6 * 1536) + 32 + 2048
    assert got["bytes"] == 2 * (weights + 2 * 32 + 256 * 16
                                + 2 * 8 * 2 * 16)
    assert got["flops"] == 2 * 2.0 * (8 * (3328 + 3 * 1536) + 2048) \
        + 2.0 * 32 * 256
    # a full pool can hit every expert, and no more than there are
    assert cfg_mod.step_required(cfg, 4, [9] * 4)["bytes"] \
        - cfg_mod.step_required(cfg, 4, [9] * 3)["bytes"] \
        == 2 * (32 + cfg_mod.rows_read(cfg, 9) * 16 + 2 * 8 * 16)


def test_prefill_required_against_hand_counts(cfg_mod):
    cfg = _cfg()
    # 12 positions: global 12 x 13 / 2 = 78 pairs; window 8: 78 less the
    # 4 x 5 / 2 = 10 pairs further back than 8 = 68; 2 x 78 + 6 x 68
    assert cfg_mod.pairs_seen(cfg, 12) == 564
    assert cfg_mod.pairs_seen(cfg, 5) == 8 * 15
    got = cfg_mod.prefill_required(cfg, [12, 5])
    assert got["flops"] == 17 * 2.0 * 8 * (3328 + 3 * 1536) \
        + 4.0 * 32 * (564 + 120) + 2 * 2.0 * 2048
    weights = 8 * (3328 + 2 * 32 + 8 * 1536) + 32 + 2048
    assert got["bytes"] == 2 * (weights + 17 * 32 + 2 * 8 * 17 * 16)


def test_real_size_counts_are_the_issue_s(cfg_mod):
    cfg = harness.load_json("configs", "smallthinker-21ba3b-8l-bf16.json")
    assert cfg_mod.param_count(cfg) * 2 == 7933875200
    step = cfg_mod.step_required(cfg, 32, [6500] * 32)
    assert 9.5e9 < step["bytes"] < 9.7e9
    assert 10.4e12 < cfg_mod.prefill_required(cfg, [8192])["flops"] < 10.6e12


# ---------------------------------------------------------------- readers
WINDOW = (100.0, 120.0)


def _read(name, **obs):
    return harness.load_module("layer_metrics", name).read(
        dict({"window": WINDOW, "window_s": 20.0,
              "peaks": {"flops_per_s_bf16": 100e12}}, **obs))


@pytest.fixture
def ring(monkeypatch):
    tl = timeline.Timeline(capacity=64)
    monkeypatch.setattr(timeline, "_TL", tl)
    tl.complete("decode.prefill", "decode", "decode:0", 99.0, 99.5,
                args={"bucket": 8, "group": 1, "tokens": 1, "padded": 8})
    return tl


def test_readers_on_a_hand_made_ring(ring):
    for i, (dur, tokens) in enumerate([(0.1, 6), (0.2, 10), (0.3, 8)]):
        ring.complete("decode.prefill", "decode", "decode:0", 101.0 + i,
                      101.0 + i + dur,
                      args={"bucket": 16, "group": 1, "tokens": tokens,
                            "padded": 16})
    for i, (mx, mean) in enumerate([(4.0, 2.0), (8.0, 2.0)]):
        ring.complete("decode.step", "decode", "decode:0", 105.0 + i,
                      105.01 + i,
                      args={"live": 2, "tokens": 2, "dispatch_ms": 1.0,
                            "read_ms": 5.0, "expert_load_max": mx,
                            "expert_load_mean": mean})
    assert _read("prefill_ms_p50") == pytest.approx(200.0)
    assert _read("prefill_window_share") == pytest.approx(3.0)
    assert _read("prefill_padding_share") == pytest.approx(50.0)
    assert _read("expert_load_max_over_mean") == pytest.approx(3.0)
    assert _read("prefill_mfu", traced={
        "prefill_flops": 18e12, "prefill_device_s": 0.6}) \
        == pytest.approx(100.0 * 18e12 / (0.6 * 100e12))
    assert _read("prefill_mfu", traced={
        "prefill_flops": 18e12, "prefill_device_s": None}) is None
    assert _read("cache_rows_read_over_required", traced={
        "cache_rows_held": 300, "cache_rows_required": 200}) \
        == pytest.approx(1.5)


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_seam_reads_as_nothing(ring, name):
    """The parent commit: ``decode.prefill`` without ``tokens`` and
    ``padded``, ``decode.step`` without the load, a driver that kept no
    contexts.  Nothing is read and nothing is raised."""
    ring.complete("decode.step", "decode", "decode:0", 105.0, 105.01,
                  args={"live": 2, "tokens": 2, "dispatch_ms": 1.0,
                        "read_ms": 5.0})
    if name in ("prefill_ms_p50", "prefill_window_share"):
        ring.complete("decode.prefill", "decode", "decode:0", 99.7, 99.9,
                      args={"bucket": 16, "group": 1})
        assert _read(name) is None      # no prefill inside the window
    else:
        ring.complete("decode.prefill", "decode", "decode:0", 101.0, 101.1,
                      args={"bucket": 16, "group": 1})
        assert _read(name) is None


def test_the_traffic_is_the_issue_s_and_the_buckets_follow_from_it():
    """``decode-longdoc`` holds ISSUE 27's parameters and no other; the
    driver tells the engine the buckets the prompts can reach."""
    driver = harness.load_module("drivers", "serve_prefill")
    tr = harness.load_json("traffic", "decode-longdoc.json")
    assert tr == {
        "kind": "serve_prefill", "loop": "closed", "clients": 64,
        "engine": {"num_slots": 32, "max_len": 12288, "max_queue": 256},
        "prompt_len": {"dist": "lognormal", "median": 6144, "sigma": 0.15,
                       "min": 4224, "max": 8192},
        "new_tokens": {"dist": "lognormal", "median": 384, "sigma": 0.5,
                       "min": 96, "max": 1024},
        "sampling": "greedy"}
    assert driver.prompt_buckets(tr) == [8192]
    assert driver.prompt_buckets(
        {"prompt_len": {"min": 4, "max": 128}}) == [4, 8, 16, 32, 64, 128]
    assert driver.prompt_buckets(
        {"prompt_len": {"min": 5, "max": 9}}) == [8, 16]


def test_every_seed_is_dealt_the_same_work():
    """The n-th request asks for the same number of tokens whatever the
    seed (what decides when prefill dispatches fall), every pool of 32 is
    the stated distribution's 32 quantiles with each half, quarter and
    eighth of it an even spread, and the seed still draws the prompts."""
    from benchmark import traffic_gen
    driver = harness.load_module("drivers", "serve_prefill")
    tr = harness.load_json("traffic", "decode-longdoc.json")
    a, b = (driver.Dealt(tr, 1000, seed) for seed in (3, 3000000007))
    ra, rb = ([next(s) for _ in range(traffic_gen.ROUND)] for s in (a, b))
    new = [n for _p, n in ra]
    assert new == [n for _p, n in rb]
    q32 = traffic_gen.length_quantiles(tr["new_tokens"], n=32).tolist()
    for lo in range(0, len(new), 32):
        assert sorted(new[lo:lo + 32]) == q32
    for k in (16, 8, 4):
        assert sorted(new[:k]) == q32[::32 // k]
    prompt_q = traffic_gen.length_quantiles(tr["prompt_len"])
    for reqs in (ra, rb):
        assert sorted(len(p) for p, _n in reqs) == sorted(prompt_q.tolist())
    assert [len(p) for p, _n in ra] != [len(p) for p, _n in rb]
    assert ra[0][0] != rb[0][0]
    # a pool that is no power of two is dealt whole too
    odd = driver.Dealt(dict(tr, engine={"num_slots": 6}), 1000, 1)
    assert sorted(next(odd)[1] for _ in range(6)) == \
        traffic_gen.length_quantiles(tr["new_tokens"], n=6).tolist()

"""``lfm2-decode-chat`` on the CPU at a tiny size: the cell end to end
through ``run.main``'s override (the ``serve_prefill`` driver, one
prefill dispatch and one commit into cache states and conv rows side by
side, the result line's keys); a sound run under every limit and each of
the reference's five controls over one; ``step_required``,
``prefill_required`` and ``param_count`` against hand counts; the
traffic file letter for letter; the reader this cell brings, on a
hand-made ring and on a program without the seam."""
import contextlib
import io
import json

import numpy as np
import pytest

from benchmark import harness, run
from mxnet_tpu.telemetry import timeline

CELL = "lfm2-decode-chat"
NAME = "lfm2-8b-a1b-14l-bf16"
# both dense layers and a period and a half: 7 conv, 2 attention,
# 7 expert layers
TINY = dict(hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
            vocab_size=64, num_experts=8, num_experts_per_tok=3,
            moe_intermediate_size=16, intermediate_size=48,
            num_hidden_layers=9, dtype="float32")
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
CONTROLS = ["fp8", "top3", "no_bias", "conv_cold", "no_qk_norm"]


@pytest.fixture(scope="module")
def cfg_mod():
    return harness.load_module("configs", NAME)


@pytest.fixture(scope="module")
def ref():
    return harness.load_module("reference", NAME)


def _cfg():
    cfg = harness.load_json("configs", NAME + ".json")
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
    counts = res["counts"]
    assert counts["retraces"] == 0 and counts["prefill_dispatches"] > 0
    assert counts["prefill_programs"] == 3          # 1, 2, 4 x 32
    # cache states alone have rows to count; the conv rows ride beside
    assert counts["state_rows"] == {
        "l%d_%s_cache" % (i, w): 64 for i in (2, 6) for w in "kv"}
    assert set(res["checks"]) == {"served_token_gap_max",
                                  "served_token_gap_p99",
                                  "served_off_best_share",
                                  "requests_unanswered_or_cut",
                                  "retraces_after_warmup"}
    # the program's events carry what the cell's readers read
    evs = timeline.peek().events()
    steps = [e["args"] for e in evs if e["name"] == "decode.step"]
    assert steps and all(a["expert_products"] == 7 * 4 * 8
                         and a["expert_routed"] == 7 * 3 * a["live"]
                         for a in steps[-20:])
    pre = [e["args"] for e in evs if e["name"] == "decode.prefill"]
    assert pre and all(a["row_states"] == 7 and a["cache_states"] == 4
                       for a in pre[-5:])


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


def test_the_seeded_weights_are_the_configurations(cfg_mod):
    cfg = _cfg()
    params = cfg_mod.init_params(cfg, 3000000011)
    assert set(params) == set(cfg_mod.param_shapes(cfg))
    for name, value in params.items():
        assert value.shape == cfg_mod.param_shapes(cfg)[name]
        assert str(value.dtype) == "float32"
    bias = np.asarray(params["l2_expert_bias"])
    assert 0.03 < bias.std() < 0.3                  # normal at 0.1
    assert abs(np.asarray(params["l0_conv_weight"]).std()
               - 1 / np.sqrt(3)) < 0.15
    assert (np.asarray(params["final_norm_gamma"]) == 1).all()
    assert (np.asarray(params["l3_op_norm_gamma"]) == 1).all()
    # the head norms' gains vary, so that leaving the norm out shows
    for which in "qk":
        gain = np.asarray(params["l2_%s_norm_gamma" % which])
        assert gain.shape == (8,) and 0.15 < gain.std() < 1.0
    # the tied table at 1/sqrt(hidden), like every matrix
    assert abs(np.asarray(params["emb_weight"]).std()
               - 1 / np.sqrt(32)) < 0.03
    other = cfg_mod.init_params(cfg, 3000000012)
    assert not np.array_equal(np.asarray(other["emb_weight"]),
                              np.asarray(params["emb_weight"]))


def test_sound_run_reads_under_the_limit(ref, sound):
    cfg, params, requests = sound
    got = ref.served_gaps(params, cfg, requests, width=48)
    assert got["tokens"] == 48
    assert got["gaps"].max() <= 1e-4
    # the reference does not echo its input: the served tokens vary
    assert len({t for _p, served in requests for t in served}) > 8


@pytest.mark.parametrize("control", CONTROLS)
def test_each_control_reads_over_the_limit(ref, sound, control):
    """The control's own first choice, read in the reference's logits,
    lies a visible share of a standard deviation under the best at some
    served position."""
    cfg, params, requests = sound
    got = ref.served_gaps(params, cfg, requests, precision=control, width=48)
    assert got["gaps"].max() > 0.02, got["gaps"].max()
    assert (got["gaps"] > 0).mean() > 0.02


def test_conv_cold_differs_only_after_the_prompt(ref, sound):
    """Teacher-forced positions inside the prompt read the plain
    convolution; from the first decoded position on the prompt's rows
    are forgotten."""
    cfg, params, requests = sound
    seq, served = requests[1]
    tokens = np.asarray(seq + served[:-1])
    plain = np.asarray(ref.forward(params, cfg, tokens))
    cold = np.asarray(ref.forward(params, cfg, tokens, "conv_cold",
                                  len(seq)))
    assert np.array_equal(plain[:len(seq)], cold[:len(seq)])
    assert np.abs(plain[len(seq):] - cold[len(seq):]).max() > 0.05


def test_the_correct_file_lists_the_reference_s_controls(ref):
    correct = harness.load_json("correct", CELL + ".json")
    assert correct["controls"] == CONTROLS == list(ref.CONTROLS)
    assert correct["control"] == "fp8" and correct["sample_requests"] == 8
    assert set(correct["limits"]) == {
        "served_token_gap_max", "served_token_gap_p99",
        "served_off_best_share", "requests_unanswered_or_cut",
        "retraces_after_warmup"}
    assert correct["limits"]["requests_unanswered_or_cut"] == 0
    assert correct["limits"]["retraces_after_warmup"] == 0


def test_calibrate_reads_every_listed_control_through_the_cell_s_checks():
    from benchmark import calibrate
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = calibrate.main(["--workload", CELL, "--seeds", "3000000031",
                             "--seconds", "1.5", "--control", "1"],
                            overrides=OVERRIDES)
    assert rc == 0, err.getvalue()[-2000:]
    recs = [json.loads(line) for line in out.getvalue().splitlines()
            if line.startswith("{")]
    assert [r["correct"] for r in recs] == [True]
    assert "control_fp8" in recs[0]
    lines = [json.loads(line.split("control ", 1)[1])
             for line in err.getvalue().splitlines()
             if line.startswith("[bench] control ")]
    assert [c["control"] for c in lines] == CONTROLS
    for c in lines:
        assert c["correct"] is False, c


def test_unknown_control_is_refused(ref, sound):
    cfg, params, requests = sound
    with pytest.raises(ValueError):
        ref.served_gaps(params, cfg, requests, precision="top5")


# ------------------------------------------------------------- hand counts
def test_step_required_against_hand_counts(cfg_mod):
    cfg = _cfg()
    # attention: q 32x32, k and v 16x32, o 32x32 = 3,072; conv: in 96x32,
    # out 32x32 = 4,096; dense 3 x 32 x 48 = 4,608; router 8 x 32 = 256;
    # an expert 3 x 32 x 16 = 1,536; head 64 x 32 = 2,048
    # small: 9 x 2 x 32 gains, 2 x 2 x 8 head norms, 7 x 3 x 32 taps,
    # 7 x 2 x 8 bias (float32: two items each), 32 final = 1,424
    assert cfg_mod._small(cfg) == 576 + 32 + 672 + 112 + 32
    # two live slots, contexts 5 and 20, two attention layers: rows (k
    # and v apart) 2 x 2 x (5 + 20) = 100
    got = cfg_mod.step_required(cfg, 4, [5, 20])
    assert got["cache_rows"] == 100
    row = 2 * 3072 + 7 * 4096 + 2 * 4608 + 7 * 256
    weights = row + 7 * 6 * 1536 + 1424 + 2048
    conv_rows = 2 * 7 * 2 * 2 * 32          # read and written, 2 live
    assert got["bytes"] == 2 * (weights + 2 * 32 + 100 * 16
                                + 2 * 2 * 2 * 16 + conv_rows)
    assert got["flops"] == 2 * 2.0 * (row + 7 * 3 * 1536 + 2048) \
        + 2.0 * 32 * 100
    # a full pool can hit every expert, and no more than there are
    assert cfg_mod.step_required(cfg, 4, [9] * 4)["bytes"] \
        - cfg_mod.step_required(cfg, 4, [9] * 3)["bytes"] \
        == 2 * (32 + cfg_mod.rows_read(cfg, 9) * 16 + 2 * 2 * 16
                + 2 * 7 * 2 * 32)


def test_prefill_required_against_hand_counts(cfg_mod):
    cfg = _cfg()
    assert cfg_mod.pairs_seen(cfg, 12) == 2 * 78
    got = cfg_mod.prefill_required(cfg, [12, 5])
    row = 2 * 3072 + 7 * 4096 + 2 * 4608 + 7 * 256
    assert got["flops"] == 17 * 2.0 * (row + 7 * 3 * 1536) \
        + 4.0 * 32 * (2 * 78 + 2 * 15) + 2 * 2.0 * 2048
    weights = row + 7 * 8 * 1536 + 1424 + 2048
    assert got["bytes"] == 2 * (weights + 17 * 32 + 2 * 2 * 17 * 16
                                + 7 * 2 * 2 * 32)


def test_real_size_counts_are_the_issue_s(cfg_mod):
    cfg = harness.load_json("configs", NAME + ".json")
    assert cfg_mod.param_count(cfg) == 4667077376
    assert cfg_mod.param_count(dict(cfg, num_hidden_layers=24)) \
        == 8339930560
    # the program's own shapes are the configuration's
    from mxnet_tpu.models import lfm2
    assert lfm2.param_shapes(cfg) == cfg_mod.param_shapes(cfg)
    info = lfm2.state_info(cfg, 1280)
    assert sum(np.prod(i["shape"]) for i in info if i.get("cache")) * 2 \
        == 1280 * 6144
    assert sum(np.prod(i["shape"]) for i in info
               if not i.get("cache")) * 2 == 11 * 2 * 2048 * 2
    # a full pool at mid-answer: the weight read binds (12-13 ms at
    # 819 GB/s); a prompt of 512 is 0.85 TFLOP
    step = cfg_mod.step_required(cfg, 256, [700] * 256)
    assert 10.3e9 < step["bytes"] < 10.7e9
    assert 0.45e12 < step["flops"] < 0.55e12
    assert 0.84e12 < cfg_mod.prefill_required(cfg, [512])["flops"] < 0.87e12


def test_the_configuration_keeps_every_published_key():
    """Every number of the catalog's config under its own key, the depth
    alone changed and listed."""
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 7168, "max_position_embeddings": 128000,
        "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
        "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_dense_layers": 2,
        "num_experts": 32, "num_experts_per_tok": 4,
        "num_hidden_layers": 24, "num_key_value_heads": 8,
        "rope_theta": 1000000, "routed_scaling_factor": 1,
        "use_expert_bias": True, "vocab_size": 65536}
    cfg = harness.load_json("configs", NAME + ".json")
    differ = [k for k, v in published.items() if cfg.get(k) != v]
    assert differ == cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["published"] == {"num_hidden_layers": 24}
    assert cfg["num_hidden_layers"] == 14
    kinds = cfg["layer_types"]
    assert len(kinds) == 24 and kinds[:14] == (
        ["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 3)
    with open(harness.ROOT + "/BENCHMARK.json") as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    assert entry["source"] == cfg["source"] \
        == "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json"
    assert entry["reduced"] == ["num_hidden_layers"]


# ---------------------------------------------------------------- traffic
def test_the_traffic_is_the_issue_s_and_its_one_bucket_follows_from_it():
    driver = harness.load_module("drivers", "serve_prefill")
    tr = harness.load_json("traffic", "decode-chat.json")
    assert tr == {
        "kind": "serve_prefill", "loop": "closed",
        "engine": {"num_slots": 256, "max_len": 1280, "max_queue": 1024},
        "clients": 384,
        "prompt_len": {"dist": "lognormal", "median": 384, "sigma": 0.25,
                       "min": 264, "max": 512},
        "new_tokens": {"dist": "lognormal", "median": 256, "sigma": 0.6,
                       "min": 48, "max": 768},
        "sampling": "greedy"}
    assert driver.prompt_buckets(tr) == [512]
    # the longest prompt and the longest answer fit a slot
    assert tr["prompt_len"]["max"] + tr["new_tokens"]["max"] \
        <= tr["engine"]["max_len"]


def test_every_seed_is_dealt_the_same_work():
    from benchmark import traffic_gen
    driver = harness.load_module("drivers", "serve_prefill")
    tr = harness.load_json("traffic", "decode-chat.json")
    a, b = (driver.Dealt(tr, 1000, seed) for seed in (3, 3000000007))
    ra, rb = ([next(s) for _ in range(512)] for s in (a, b))
    new = [n for _p, n in ra]
    assert new == [n for _p, n in rb]
    q256 = traffic_gen.length_quantiles(tr["new_tokens"], n=256).tolist()
    assert sorted(new[:256]) == sorted(new[256:]) == q256
    assert q256[0] == 48 and q256[-1] == 768
    assert 280 < np.mean(q256) < 320            # the issue's 299
    for k in (128, 64, 32):
        assert sorted(new[:k]) == q256[::256 // k]
    for reqs in (ra, rb):
        lens = [len(p) for p, _n in reqs]
        assert min(lens) >= 264 and max(lens) <= 512
    assert [len(p) for p, _n in ra] != [len(p) for p, _n in rb]


# ----------------------------------------------------------------- reader
WINDOW = (100.0, 120.0)


def _read(name, **obs):
    return harness.load_module("layer_metrics", name).read(
        dict({"window": WINDOW, "window_s": 20.0,
              "peaks": {"flops_per_s_bf16": 100e12}}, **obs))


@pytest.fixture
def ring(monkeypatch):
    tl = timeline.Timeline(capacity=64)
    monkeypatch.setattr(timeline, "_TL", tl)
    tl.complete("decode.step", "decode", "decode:0", 99.0, 99.01,
                args={"live": 1, "tokens": 1, "expert_products": 1000,
                      "expert_routed": 1})
    return tl


def test_reader_on_a_hand_made_ring(ring):
    """Steps inside the window alone; products and routed pairs are
    summed apart, so a step with few live rows weighs by its rows."""
    for i, live in enumerate((256, 256, 64)):
        ring.complete("decode.step", "decode", "decode:0", 105.0 + i,
                      105.02 + i,
                      args={"live": live, "tokens": live,
                            "expert_products": 12 * 8192,
                            "expert_routed": 12 * 4 * live})
    assert _read("expert_products_over_routed") \
        == pytest.approx(3 * 8192 / (4.0 * 576))
    # a full pool on the plain path: experts over top_k
    ring.complete("decode.step", "decode", "decode:0", 110.0, 110.02,
                  args={"live": 256, "expert_products": 12 * 8192,
                        "expert_routed": 12 * 4 * 256})
    assert 8.0 < _read("expert_products_over_routed") < 11.0


def test_a_program_without_the_seam_reads_as_nothing(ring):
    """The parent commit's ``decode.step`` has no such arguments: one
    event without them and nothing is read, nothing raised; so too a
    window that holds no step, and a process with no ring."""
    assert _read("expert_products_over_routed") is None     # no step inside
    ring.complete("decode.step", "decode", "decode:0", 105.0, 105.01,
                  args={"live": 2, "tokens": 2, "dispatch_ms": 1.0,
                        "read_ms": 5.0, "expert_load_max": 2.0,
                        "expert_load_mean": 1.0})
    assert _read("expert_products_over_routed") is None
    ring.complete("decode.step", "decode", "decode:0", 106.0, 106.01,
                  args={"live": 2, "expert_products": 64,
                        "expert_routed": 0})
    assert _read("expert_products_over_routed") is None


def test_the_new_cell_reports_what_the_issue_lists():
    with open(harness.ROOT + "/BENCHMARK.json") as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["config"] == NAME and cell["traffic"] == "decode-chat"
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    e2e = {m["name"] for m in run.metrics_of(bench, "end_to_end", CELL)}
    assert e2e == {"decode_tokens_per_s", "setup_s"}
    layer = {m["name"] for m in run.metrics_of(bench, "per_layer", CELL)}
    assert layer == {
        "slot_occupancy", "decode_step_ms_p50", "itl_p50_ms.saturated",
        "decode_step_roofline", "decode_step_mfu",
        "device_idle_share.decode", "step_dispatch_ms_p50",
        "step_read_wait_ms_p50", "step_scheduler_ms_p50",
        "steps_ahead_share", "slot_steps_discarded_share",
        "prefill_ms_p50", "prefill_window_share", "prefill_padding_share",
        "prefill_mfu", "expert_load_max_over_mean",
        "cache_rows_read_over_required", "expert_products_over_routed",
        "compile_requests_in_setup", "cache_misses_in_setup"}
    new = bench["per_layer"][-1]
    assert new == {"name": "expert_products_over_routed", "unit": "ratio",
                   "better": "lower", "source": "program_counter",
                   "layer": "kernels", "moves": "decode_tokens_per_s",
                   "workloads": [CELL]}

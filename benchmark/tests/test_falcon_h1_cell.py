"""``falcon-h1-decode-chat`` on the CPU at a tiny size: the cell end to
end through ``run.main``'s override (the ``serve_prefill`` driver, one
prefill dispatch and one commit into cache states, conv rows and state
space rows side by side, the result line's keys); the seeded weights; a
sound run under every limit and each of the reference's five controls
over one; ``pad_advance`` and ``ssm_cold`` differing only where they
should; ``step_required``, ``prefill_required`` and ``param_count``
against hand counts; the reader this cell brings, on a hand-made
``obs`` and without a trace."""
import contextlib
import io
import json
import math

import numpy as np
import pytest

from benchmark import harness, run
from mxnet_tpu.telemetry import timeline

CELL = "falcon-h1-decode-chat"
NAME = "falcon-h1-34b-4l-bf16"
# every layer of one kind: two of them, heads of 8, a state space of 4
# heads of 4 over a state of 64 in 2 groups (over a state of 6 what the
# prompt leaves in it hardly moves a token, and the two controls of the
# state handed over read as a sound run), chunks of 4
TINY = dict(hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
            head_dim=8, mamba_d_ssm=16, mamba_n_heads=4, mamba_d_head=4,
            mamba_n_groups=2, mamba_d_state=64, mamba_chunk_size=4,
            intermediate_size=48, vocab_size=64, num_hidden_layers=2,
            dtype="float32")
OVERRIDES = {
    "platform": "cpu", "device_kind": "TPU v5 lite", "config": TINY,
    "traffic": {
        "engine": {"num_slots": 4, "max_len": 64, "max_queue": 64},
        "clients": 8,
        "prompt_len": {"dist": "lognormal", "median": 20, "sigma": 0.3,
                       "min": 17, "max": 32},
        "new_tokens": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                       "min": 3, "max": 16}},
    # float32 against float32: sums in another order alone (the scan's
    # chunks against the reference's recurrence among them)
    "correct": {"sample_requests": 3,
                "limits": {"served_token_gap_max": 1e-4,
                           "served_token_gap_p99": 1e-4,
                           "served_off_best_share": 0.0,
                           "requests_unanswered_or_cut": 0,
                           "retraces_after_warmup": 0}}}
CONTROLS = ["fp8", "ssm_cold", "pad_advance", "no_gate", "no_ssm_mup"]


@pytest.fixture(scope="module")
def cfg_mod():
    return harness.load_module("configs", NAME)


@pytest.fixture(scope="module")
def ref():
    return harness.load_module("reference", NAME)


def _cfg(**kw):
    cfg = harness.load_json("configs", NAME + ".json")
    cfg.update(TINY, **kw)
    return cfg


def test_cell_end_to_end_on_the_cpu():
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = run.main(["--workload", CELL, "--seed", "3000000041",
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
    assert counts["state_rows"] == {
        "l%d_%s_cache" % (i, w): 64 for i in (0, 1) for w in "kv"}
    assert set(res["checks"]) == {"served_token_gap_max",
                                  "served_token_gap_p99",
                                  "served_off_best_share",
                                  "requests_unanswered_or_cut",
                                  "retraces_after_warmup"}
    pre = [e["args"] for e in timeline.peek().events()
           if e["name"] == "decode.prefill"]
    # a layer's conv row and state space row beside its two caches
    assert pre and all(a["row_states"] == 4 and a["cache_states"] == 4
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
    shapes = cfg_mod.param_shapes(cfg)
    assert set(params) == set(shapes)
    for name, value in params.items():
        assert value.shape == shapes[name] and str(value.dtype) == "float32"
    p = {k: np.asarray(v) for k, v in params.items()}
    # Mamba-2's own initialisation
    a = np.exp(p["l0_A_log"])
    assert (a >= 1.0).all() and (a <= 16.0).all()
    dt = np.log1p(np.exp(p["l1_dt_bias"]))
    assert (dt >= 1e-3 * 0.999).all() and (dt <= 1e-1 * 1.001).all()
    assert (p["l0_D"] == 1).all() and not p["l1_conv_bias"].any()
    assert abs(p["l0_conv_weight"].std() - 0.5) < 0.15
    for name in ("final_norm_gamma", "l0_in_norm_gamma", "l1_ff_norm_gamma",
                 "l0_ssm_norm_gamma"):
        assert (p[name] == 1).all()
    # each matrix at 1/sqrt(fan_in) over the multiplier after it
    d = cfg["hidden_size"]
    for name, want in (
            ("l0_q_weight", 1 / math.sqrt(d)),
            ("l0_k_weight", 1 / (cfg["key_multiplier"] * math.sqrt(d))),
            ("l1_down_weight", 1 / (cfg["mlp_multipliers"][1]
                                    * math.sqrt(48))),
            ("emb_weight", 1 / cfg["embedding_multiplier"])):
        assert abs(p[name].std() / want - 1) < 0.2, name
    # the input projection a block at a time: z, x, B, C, dt
    rows = p["l0_ssm_in_weight"].std(axis=1)
    ms = cfg["ssm_multipliers"]
    for (lo, hi), m in zip(((0, 16), (16, 32), (32, 160), (160, 288),
                            (288, 292)), ms):
        want = 1 / (cfg["ssm_in_multiplier"] * m * math.sqrt(d))
        assert abs(rows[lo:hi].mean() / want - 1) < 0.3, (lo, m)
    other = cfg_mod.init_params(cfg, 3000000012)
    assert not np.array_equal(np.asarray(other["emb_weight"]),
                              p["emb_weight"])


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


@pytest.mark.parametrize("control", ["ssm_cold", "pad_advance"])
def test_a_state_control_differs_only_after_the_prompt(ref, sound, control):
    """Teacher-forced positions inside the prompt read the plain forward
    pass; from the first decoded position on the state handed over is
    another, and the difference fades as the state forgets it."""
    cfg, params, requests = sound
    seq, served = requests[2]                       # 27: bucket 32
    tokens = np.asarray(seq + served[:-1])
    plain = np.asarray(ref.forward(params, cfg, tokens, plen=len(seq)))
    other = np.asarray(ref.forward(params, cfg, tokens, control, len(seq)))
    assert np.array_equal(plain[:len(seq)], other[:len(seq)])
    # (logits carry lm_head_multiplier: a share of their own scale)
    assert np.abs(plain[len(seq)] - other[len(seq)]).max() \
        > 0.01 * np.abs(plain).max()


def test_pad_advance_is_the_plain_pass_where_nothing_is_padded(ref, sound):
    """A prompt of a whole bucket has no padding to carry the state
    through: the control reads the plain forward pass."""
    cfg, params, requests = sound
    seq, served = requests[0]
    seq = (seq + served)[:16]
    tokens = np.asarray(seq + served[:5])
    plain = np.asarray(ref.forward(params, cfg, tokens, plen=16))
    other = np.asarray(ref.forward(params, cfg, tokens, "pad_advance", 16))
    assert np.array_equal(plain, other)


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
        rc = calibrate.main(["--workload", CELL, "--seeds", "3000000043",
                             "--seconds", "1.5", "--control", "1"],
                            overrides=OVERRIDES)
    assert rc == 0, err.getvalue()[-2000:]
    recs = [json.loads(line) for line in out.getvalue().splitlines()
            if line.startswith("{")]
    assert [r["correct"] for r in recs] == [True]
    lines = [json.loads(line.split("control ", 1)[1])
             for line in err.getvalue().splitlines()
             if line.startswith("[bench] control ")]
    assert [c["control"] for c in lines] == CONTROLS
    for c in lines:
        assert c["correct"] is False, c


# ------------------------------------------------------------- hand counts
def test_step_required_against_hand_counts(cfg_mod):
    cfg = _cfg(mamba_d_state=6)
    # a layer: attention 32 x (32 + 16 + 16) + 32 x 32 = 3,072; the
    # mixer's projections 60 x 32 + 32 x 16 = 2,432; MLP 3 x 32 x 48 =
    # 4,608: 10,112 a row; head 64 x 32 = 2,048
    assert cfg_mod._per_layer(cfg) == 10112
    # small, a layer: 2 x 32 gains, conv 4 x 40 taps and 40 bias, 3 x 4
    # head values, a grouped norm gain of 16; the final norm 32
    assert cfg_mod._small(cfg) == 2 * (64 + 160 + 40 + 12 + 16) + 32
    got = cfg_mod.step_required(cfg, 4, [5, 20])
    # every layer has attention: rows (k and v apart) 2 x 2 x (5 + 20)
    assert got["cache_rows"] == 100
    # a slot's plain rows a layer: conv 3 x 40, state 4 x 4 x 6
    state = 2 * 2 * 2 * (120 + 96) * 2           # read and written, 2 live
    assert got["state_bytes"] == state
    weights = 2 * 10112 + 616 + 2048
    assert got["bytes"] == 2 * (weights + 2 * 32 + 100 * 16
                                + 2 * 2 * 2 * 16) + state
    ssd = 5.0 * 96 + 2.0 * 16
    assert got["flops"] == 2 * (2.0 * (2 * 10112 + 2048) + 2 * ssd) \
        + 2.0 * 32 * 100


def test_prefill_required_against_hand_counts(cfg_mod):
    cfg = _cfg(mamba_d_state=6)
    # chunks of 4: a prompt of 5 is a chunk of 4 and one of 1
    def chunk(c):
        return 2.0 * c * c * (12 + 16) + 3.0 * 4 * c * c \
            + 4.0 * c * 96 + 2.0 * 96 + 2.0 * c * 16
    assert cfg_mod.scan_flops(cfg, 5) == 2 * (chunk(4) + chunk(1))
    assert cfg_mod.scan_flops(cfg, 8) == 2 * 2 * chunk(4)
    got = cfg_mod.prefill_required(cfg, [12, 5])
    scan = 2 * (3 * chunk(4)) + 2 * (chunk(4) + chunk(1))
    assert got["scan_flops"] == scan
    assert got["flops"] == 17 * 2.0 * 2 * 10112 \
        + 4.0 * 32 * 2 * (78 + 15) + scan + 2 * 2.0 * 2048
    weights = 2 * 10112 + 616 + 2048
    assert got["bytes"] == 2 * (weights + 17 * 32 + 2 * 2 * 17 * 16
                                + 2 * 2 * 216)


def test_real_size_counts_against_the_arithmetic(cfg_mod):
    cfg = harness.load_json("configs", NAME + ".json")
    assert cfg_mod.param_count(cfg) == 4394354048
    assert cfg_mod.param_count(dict(cfg, num_hidden_layers=1)) \
        - 2 * 261120 * 5120 - 5120 == 430120032
    from mxnet_tpu.models import falcon_h1
    assert falcon_h1.param_shapes(cfg) == cfg_mod.param_shapes(cfg)
    info = falcon_h1.state_info(cfg, 1280)
    plain = sum(np.prod(i["shape"]) for i in info if not i.get("cache"))
    assert plain * 2 == 4 * (3 * 5120 + 32 * 128 * 256) * 2   # 8.4 MB
    # 256 slots at the traffic's mean context: weights 3.44 + head 2.67
    # GB, state read and written 4.36 GB, KV about 1.1 GB
    step = cfg_mod.step_required(cfg, 256, [540] * 256)
    assert 4.3e9 < step["state_bytes"] < 4.4e9
    assert 11.3e9 < step["bytes"] < 11.9e9
    # a prompt of 384: about 1.33 TFLOP, the scan a small part of it
    pre = cfg_mod.prefill_required(cfg, [384])
    assert 1.30e12 < pre["flops"] < 1.38e12
    assert pre["scan_flops"] < 0.01 * pre["flops"]


def test_the_configuration_keeps_every_published_key():
    """Every number of the catalog's config under its own key, the depth
    alone changed and listed; nested groups copied whole."""
    published = {
        "attention_bias": False, "attention_in_multiplier": 1,
        "attention_out_multiplier": 0.0375, "attn_layer_indices": None,
        "embedding_multiplier": 5.656854249492381, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 5120,
        "intermediate_size": 21504,
        "key_multiplier": 0.011048543456039804,
        "lm_head_multiplier": 0.0078125, "mamba_chunk_size": 128,
        "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 128,
        "mamba_d_ssm": 4096, "mamba_d_state": 256, "mamba_expand": 2,
        "mamba_n_groups": 2, "mamba_n_heads": 32,
        "mamba_norm_before_gate": False, "mamba_proj_bias": False,
        "mamba_rms_norm": True, "mamba_use_mlp": True,
        "max_position_embeddings": 262144, "mlp_bias": False,
        "mlp_expansion_factor": 8,
        "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
        "model_type": "falcon_h1", "num_attention_heads": 20,
        "num_hidden_layers": 72, "num_key_value_heads": 4,
        "num_logits_to_keep": 1, "projectors_bias": False,
        "rms_norm_eps": 1e-05, "rope_scaling": None,
        "rope_theta": 100000000000, "ssm_in_multiplier": 0.25,
        "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369,
                            0.5, 0.3535533905932738],
        "ssm_out_multiplier": 0.08838834764831845,
        "tie_word_embeddings": False, "vocab_size": 261120}
    cfg = harness.load_json("configs", NAME + ".json")
    differ = [k for k, v in published.items() if cfg.get(k, "absent") != v]
    assert differ == cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["published"] == {"num_hidden_layers": 72}
    assert cfg["num_hidden_layers"] == 4
    with open(harness.ROOT + "/BENCHMARK.json") as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct/blob/main/"
        "config.json")
    assert entry["reduced"] == ["num_hidden_layers"]


def test_the_new_cell_reports_its_metrics():
    with open(harness.ROOT + "/BENCHMARK.json") as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["config"] == NAME and cell["traffic"] == "decode-chat"
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    e2e = {m["name"] for m in run.metrics_of(bench, "end_to_end", CELL)}
    assert e2e == {"decode_tokens_per_s", "setup_s"}
    lfm2 = {m["name"] for m in run.metrics_of(bench, "per_layer",
                                              "lfm2-decode-chat")}
    layer = {m["name"] for m in run.metrics_of(bench, "per_layer", CELL)}
    assert layer == lfm2 - {"expert_load_max_over_mean",
                            "expert_products_over_routed"} \
        | {"ssm_state_step_share"}
    assert bench["per_layer"][-1] == {
        "name": "ssm_state_step_share", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "kernels",
        "moves": "decode_tokens_per_s", "workloads": [CELL]}


def test_the_traffic_is_lfm2s_decode_chat_unchanged():
    """The two chat cells share one traffic file and differ only in the
    model: 384 clients on 256 slots, one bucket of 512, and every seed
    dealt the same work."""
    with open(harness.ROOT + "/BENCHMARK.json") as f:
        bench = json.load(f)
    lfm2 = next(w for w in bench["workloads"]
                if w["name"] == "lfm2-decode-chat")
    assert lfm2["traffic"] == "decode-chat"
    driver = harness.load_module("drivers", "serve_prefill")
    tr = harness.load_json("traffic", "decode-chat.json")
    assert tr["engine"]["num_slots"] == 256 and tr["clients"] == 384
    assert driver.prompt_buckets(tr) == [512]
    a, b = (driver.Dealt(tr, 1000, seed) for seed in (3, 3000000007))
    ra, rb = ([next(s) for _ in range(512)] for s in (a, b))
    assert [n for _p, n in ra] == [n for _p, n in rb]
    assert sorted(n for _p, n in ra[:256]) == sorted(n for _p, n in ra[256:])


# ----------------------------------------------------------------- reader
def _read(**obs):
    return harness.load_module("layer_metrics", "ssm_state_step_share").read(
        dict({"peaks": {"hbm_bytes_per_s": 800e9}}, **obs))


def test_reader_on_a_hand_made_obs():
    """Two steps of 4 GB of state each (10 ms at 800 GB/s) in 0.05 s of
    step time: 0.1 s of the busy 0.08 s less 0.03 s of prefill; a
    prefill's required entry has no state bytes and is not counted."""
    obs = {"trace": {"busy_s": 0.08, "window_s": 0.1},
           "traced": {"prefill_device_s": 0.03,
                      "required": [{"state_bytes": 4e9, "bytes": 11e9},
                                   {"state_bytes": 4e9, "bytes": 11e9},
                                   {"flops": 1e12, "bytes": 9e9,
                                    "scan_flops": 1e9}]}}
    assert _read(**obs) == pytest.approx(100.0 * 0.01 / 0.05)


def test_the_reader_finds_nothing_without_a_trace_or_state():
    """No trace (an untraced run), no prefill time (an annotation cut by
    the trace's ends), or a configuration that counts no state bytes
    (the parent's, or another model's): nothing read, nothing raised."""
    steps = [{"state_bytes": 4e9}]
    assert _read() is None
    assert _read(traced={"prefill_device_s": 0.0, "required": steps}) is None
    assert _read(trace={"busy_s": 0.08},
                 traced={"prefill_device_s": None, "required": steps}) is None
    assert _read(trace={"busy_s": 0.08},
                 traced={"prefill_device_s": 0.0,
                         "required": [{"bytes": 1e9}]}) is None

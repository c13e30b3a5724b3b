"""The ops LFM2 forced (ops/transformer.py: the gated short convolution
in its step and sequence forms, the per-head norm, sigmoid scores chosen
under a bias, SwiGLU), the builder (models/lfm2.py) and a slot pool that
holds cache states and plain rows side by side, against the plain
reference (benchmark/reference/lfm2-8b-a1b-14l-bf16.py) at small widths
on the CPU.

Tolerances.  In float32 program and reference compute the same products
in different orders (blocked attention against whole rows, a grouped
expert product against one expert at a time), so they agree to float32
rounding of sums a few hundred terms long: ``TOL`` = 2e-5 of the largest
value compared, about a hundred float32 ulps.  A wrong tap, state row,
norm, bias or routing weight moves a logit by a few percent of that
scale, a thousand times the tolerance.  The convolution's two forms do
the same float32 operations in the same order and are held equal bit for
bit, in bfloat16 too; against the reference in bfloat16 they may differ
by the rounding of the one result, an ulp (2^-8 of the largest value).
"""
import importlib.util
import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import serving
from mxnet_tpu.executor import build_graph_fn
from mxnet_tpu.models import lfm2
from mxnet_tpu.ops import invoke_jax
from mxnet_tpu.serving.decode import StepProgram, greedy_decode
from mxnet_tpu.telemetry import timeline

from test_decode_pipeline import _run_dry, _tick

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-5
NAME = "lfm2-8b-a1b-14l-bf16"


def _load(*parts):
    path = os.path.join(REPO, "benchmark", *parts) + ".py"
    spec = importlib.util.spec_from_file_location(
        "bench_" + parts[-1].replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load("reference", NAME)


# both leading dense layers, then a period and a half of the pattern:
# seven conv layers, two attention layers, seven expert layers
KINDS = ["conv", "conv", "full_attention", "conv", "conv", "conv",
         "full_attention", "conv", "conv"]
CFG = dict(hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
           vocab_size=64, num_experts=8, num_experts_per_tok=3,
           moe_intermediate_size=16, intermediate_size=48, conv_L_cache=3,
           norm_eps=1e-5, norm_topk_prob=True, routed_scaling_factor=1,
           use_expert_bias=True, rope_theta=1000000, num_dense_layers=2,
           num_hidden_layers=9, layer_types=KINDS + ["conv"] * 3)
MAX_LEN = 48


def _params(cfg, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    out = {}
    for name, shape in lfm2.param_shapes(cfg).items():
        if name.endswith("gamma"):
            val = 1.0 + 0.1 * rng.standard_normal(shape)
        elif name.endswith("expert_bias"):
            # large enough to change the chosen set in most rows
            out[name] = (0.3 * rng.standard_normal(shape)) \
                .astype(np.float32)
            continue
        else:
            # the tied table too: at unit variance it echoes its input
            val = rng.standard_normal(shape) / np.sqrt(shape[-1])
        out[name] = np.asarray(jnp.asarray(val, dtype))
    return out


@pytest.fixture(scope="module")
def model(ref):
    params = _params(CFG)
    rng = np.random.default_rng(1)
    tokens = rng.integers(1, CFG["vocab_size"], 40)
    want = np.asarray(ref.forward(
        {k: jnp.asarray(v) for k, v in params.items()}, CFG, tokens))
    return params, tokens, want


def _close(got, want, tol=TOL):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= tol * max(np.abs(want).max(), 1.0)


def _graph(symbol):
    args = symbol.list_arguments()
    fn = build_graph_fn(symbol, args, [])

    def run(feed):
        outs, _ = fn([jnp.asarray(feed[a]) for a in args], [],
                     jax.random.PRNGKey(0), False)
        return outs
    return run


# ---------------------------------------------------------------- the ops
def _conv_inputs(dtype, n=3, t=12, d=8, seed=2):
    rng = np.random.default_rng(seed)
    proj = jnp.asarray(rng.standard_normal((n, t, 3 * d)), dtype)
    taps = jnp.asarray(rng.standard_normal((3, d)) / np.sqrt(3), dtype)
    return proj, taps


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_short_conv_step_against_the_reference(ref, dtype):
    """A position a step from a zero state: the reference's convolution
    of the whole sequence, and the state the last two ``u``."""
    proj, taps = _conv_inputs(dtype)
    n, t, d3 = proj.shape
    state = jnp.zeros((n, 2, d3 // 3), dtype)
    outs = []
    for i in range(t):
        out, state = invoke_jax("_short_conv_step", {}, proj[:, i], state,
                                taps)
        assert out.dtype == state.dtype == proj.dtype
        outs.append(out)
    got = jnp.stack(outs, axis=1)
    want = jnp.stack([ref.short_conv(proj[b], taps) for b in range(n)])
    _close(got, want, TOL if dtype == "float32" else 2.0 ** -8)
    b, x = proj[..., :d3 // 3], proj[..., 2 * d3 // 3:]
    u = (b.astype(jnp.float32) * x.astype(jnp.float32)).astype(dtype)
    assert np.array_equal(np.asarray(state, np.float32),
                          np.asarray(u[:, -2:], np.float32))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_short_conv_sequence_returns_the_state_at_each_rows_length(
        ref, dtype):
    """A padded batch with junk behind each row's length: live positions
    are the reference's, and the state is ``(u[plen-2], u[plen-1])`` of
    the row's own length (zeros before the start, at lengths 0, 1 and
    2), bit for bit what stepping through the live positions leaves."""
    proj, taps = _conv_inputs(dtype, n=6)
    n, t, d3 = proj.shape
    lens = [t, 7, 2, 1, 0, 3]
    got, state = invoke_jax("_short_conv_seq", {}, proj,
                            jnp.asarray(lens, jnp.float32), taps)
    assert got.shape == (n, t, d3 // 3) and state.shape == (n, 2, d3 // 3)
    assert got.dtype == state.dtype == proj.dtype
    for b, plen in enumerate(lens):
        if plen:
            _close(got[b, :plen], ref.short_conv(proj[b, :plen], taps),
                   TOL if dtype == "float32" else 2.0 ** -8)
        held = jnp.zeros((1, 2, d3 // 3), dtype)
        for i in range(plen):
            out, held = invoke_jax("_short_conv_step", {},
                                   proj[b:b + 1, i], held, taps)
            assert np.array_equal(np.asarray(out[0], np.float32),
                                  np.asarray(got[b, i], np.float32))
        assert np.array_equal(np.asarray(held[0], np.float32),
                              np.asarray(state[b], np.float32))
    assert not np.asarray(state[4], np.float32).any()       # length 0
    assert not np.asarray(state[3, 0], np.float32).any()    # length 1
    assert np.asarray(state[3, 1], np.float32).any()


def test_conv_cold_control_forgets_the_prompt(ref):
    """The control's convolution equals the plain one before
    ``cold_from`` and from two positions past it, and differs between."""
    proj, taps = _conv_inputs("float32", n=1)
    plain = np.asarray(ref.short_conv(proj[0], taps))
    cold = np.asarray(ref.short_conv(proj[0], taps, 5))
    same = np.abs(plain - cold).max(axis=1) == 0
    assert same.tolist() == [True] * 5 + [False] * 2 + [True] * 5
    restart = np.asarray(ref.short_conv(proj[0, 5:], taps))
    _close(cold[5:], restart)


def test_head_norm_op(ref):
    rng = np.random.default_rng(3)
    g = jnp.asarray(1.0 + 0.1 * rng.standard_normal((8,)), jnp.float32)
    for lead in ((5,), (2, 5)):
        x = jnp.asarray(rng.standard_normal(lead + (4 * 8,)), jnp.float32)
        got, = invoke_jax("RMSNorm", {"eps": 1e-5, "head_dim": 8}, x, g)
        want = ref.head_norm(x.reshape(-1, 32), g, 1e-5).reshape(x.shape)
        _close(got, want)
        whole, = invoke_jax("RMSNorm", {"eps": 1e-5}, x,
                            jnp.tile(g, 4))
        assert np.abs(np.asarray(got - whole)).max() > 0.01


def _expert_inputs(n_exp=32, width=8, hidden=16, rows=16, seed=7):
    rng = np.random.default_rng(seed)
    u = rng.standard_normal((rows, hidden)).astype(np.float32)
    r = rng.standard_normal((rows, n_exp)).astype(np.float32)
    w = [rng.standard_normal((n_exp, width, hidden)).astype(np.float32)
         / np.sqrt(hidden) for _ in range(3)]
    bias = (0.5 * rng.standard_normal((n_exp,))).astype(np.float32)
    return u, r, w, bias


LFM2_RULE = {"top_k": 4, "routing": "sigmoid", "activation": "silu",
             "expert_bias": True}


@pytest.mark.parametrize("block", [256, 4], ids=["dense", "sorted"])
def test_expert_op_under_sigmoid_scores_and_a_bias(ref, block):
    """The bias changes which experts are chosen and not what they
    weigh: weights are the unbiased scores over their sum."""
    u, r, w, bias = _expert_inputs()
    y, route = invoke_jax(
        "_moe_experts", dict(LFM2_RULE, block=block),
        *map(jnp.asarray, [u, r] + w + [bias]))
    with jax.default_matmul_precision("highest"):
        weights = ref.route(jnp.asarray(r), jnp.asarray(bias), 4)
        want = ref.experts(jnp.asarray(u), weights, *map(jnp.asarray, w))
        unbiased = ref.route(jnp.asarray(r), None, 4)
    _close(route, weights)
    _close(y, want)
    chosen = np.asarray(route) > 0
    assert chosen.sum(axis=1).tolist() == [4] * 16
    moved = (chosen != (np.asarray(unbiased) > 0)).any(axis=1)
    assert moved.mean() > 0.5
    s = 1.0 / (1.0 + np.exp(-r))
    want_w = np.where(chosen, s, 0.0)
    want_w /= want_w.sum(axis=1, keepdims=True) + 1e-6
    _close(route, want_w)
    # no bias, no normalisation, a scale: the same op, other attributes
    _y, raw = invoke_jax(
        "_moe_experts", {"top_k": 4, "routing": "sigmoid",
                         "norm_topk": False, "route_scale": 2.0,
                         "block": block},
        *map(jnp.asarray, [u, r] + w))
    top = np.sort(s, axis=1)[:, -4:]
    _close(np.sort(np.asarray(raw), axis=1)[:, -4:], 2.0 * top)


@pytest.mark.parametrize("block", [256, 4], ids=["dense", "sorted"])
def test_expert_shares_add_up_to_the_uncut_layer(ref, block):
    """Four shares of 8 of 32 experts, each scoring all 32 under the
    whole bias and computing its own experts' part: the parts add up to
    the layer (the model-configs guide's one test, under LFM2's rule)."""
    u, r, w, bias = _expert_inputs()
    total = 0.0
    for first in (0, 8, 16, 24):
        part, route = invoke_jax(
            "_moe_experts", dict(LFM2_RULE, first_expert=first,
                                 num_held=8, block=block),
            jnp.asarray(u), jnp.asarray(r),
            *[jnp.asarray(x[first:first + 8]) for x in w],
            jnp.asarray(bias))
        total = total + np.asarray(part)
    with jax.default_matmul_precision("highest"):
        want = ref.experts(
            jnp.asarray(u), ref.route(jnp.asarray(r), jnp.asarray(bias), 4),
            *map(jnp.asarray, w))
    _close(total, want)
    assert route.shape == (16, 32)          # the published router width


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("name,block", [("dense", 256), ("sorted", 4)])
def test_expert_op_defaults_are_bit_for_bit_the_parents(dtype, name, block):
    """SmallThinker's attributes (softmax over the chosen logits, ReLU,
    no bias): outputs pinned from PR 30's tree on these inputs
    (``tests/data/moe_experts_pr30.npz``), whole and as a share."""
    pinned = np.load(os.path.join(REPO, "tests", "data",
                                  "moe_experts_pr30.npz"))
    rng = np.random.default_rng(7)
    u = rng.standard_normal((16, 16)).astype(np.float32)
    r = rng.standard_normal((16, 64)).astype(np.float32)
    w = [rng.standard_normal((64, 8, 16)).astype(np.float32) / np.sqrt(16)
         for _ in range(3)]
    args = [jnp.asarray(u, dtype), jnp.asarray(r)] \
        + [jnp.asarray(x, dtype) for x in w]
    y, route = invoke_jax("_moe_experts", {"top_k": 6, "block": block},
                          *args)
    share, _ = invoke_jax(
        "_moe_experts", {"top_k": 6, "block": block, "first_expert": 16,
                         "num_held": 16},
        args[0], args[1], *[a[16:32] for a in args[2:]])
    key = "%s_%s_" % (dtype, name)
    for tag, got in (("y", y), ("route", route), ("share", share)):
        assert np.array_equal(np.asarray(got, np.float32),
                              pinned[key + tag]), tag


def test_expert_products_follow_the_formulation():
    """What the counter reads: every held expert over every row on the
    plain path; past it the rows the sorted path may pad to.  256 rows
    of 32 experts, 4 a row, are the last shape on the plain path."""
    from mxnet_tpu.ops import transformer as tf
    attrs = {"top_k": 4, "block": 256}
    shapes = lambda rows, held=32: [(rows, 2048), (rows, 32),
                                    (held, 1792, 2048)]
    assert tf.moe_products(attrs, shapes(256)) == 256 * 32 == 8192
    assert tf._moe_padded_rows(attrs, 256, 32) == 9216
    assert tf.moe_products(attrs, shapes(512)) == (8 + 32) * 256
    assert tf.moe_products(attrs, shapes(64, 8)) == 64 * 8
    assert tf.moe_products(attrs, [(2, 512, 2048), (2, 512, 32),
                                   (32, 1792, 2048)]) == (16 + 32) * 256


# ------------------------------------------------- the model, by the graph
def _step_through(params, tokens, states=None, start=0, slot=0, n_slots=2):
    """Feed ``tokens`` one a step into ``slot`` (the others dead,
    holding junk); returns the logits a step, the states and the last
    step's expert load."""
    step, info = lfm2.decode_step(CFG, MAX_LEN)
    run = _graph(step)
    if states is None:
        states = {i["name"]: jnp.full((n_slots,) + tuple(i["shape"]), 3.0)
                  .at[slot].set(0.0) for i in info}
    valid = np.zeros((n_slots,), np.float32)
    valid[slot] = 1.0
    logits = []
    for t, tok in enumerate(tokens):
        feed = dict(params, **states)
        token = np.full((n_slots,), 5.0, np.float32)
        pos = np.full((n_slots,), 2.0, np.float32)
        token[slot], pos[slot] = tok, start + t
        feed.update(token=token, pos=pos, valid=valid)
        outs = run(feed)
        logits.append(np.asarray(outs[0][slot]))
        states = {i["name"]: outs[1 + j] for j, i in enumerate(info)}
    return np.stack(logits), states, np.asarray(outs[-1])


def test_state_info_holds_caches_and_plain_rows_in_layer_order():
    info = lfm2.state_info(CFG, MAX_LEN)
    assert [i["name"] for i in info] == [
        "l0_conv", "l1_conv", "l2_k_cache", "l2_v_cache", "l3_conv",
        "l4_conv", "l5_conv", "l6_k_cache", "l6_v_cache", "l7_conv",
        "l8_conv"]
    for i in info:
        if i["name"].endswith("conv"):
            assert i["shape"] == (2, 32) and not i.get("cache")
        else:
            assert i["shape"] == (MAX_LEN, 16) and i["cache"] is True


def test_step_token_by_token_matches_the_full_forward_pass(model):
    params, tokens, want = model
    got, states, load = _step_through(params, tokens)
    _close(got, want)
    assert {v.shape[1:] for v in states.values()} == {(2, 32),
                                                      (MAX_LEN, 16)}
    # 3 of 8 experts on each of the 7 expert layers for the one live
    # row; the dead slot's row is not counted
    assert load.shape == (7, 8) and load.sum(axis=1).tolist() == [3.0] * 7


@pytest.mark.parametrize("plens,bucket", [((29, 3), 32), ((1, 2), 8),
                                          ((8, 5), 8)],
                         ids=["unequal", "one-and-two", "exact"])
def test_prefill_then_decode_matches_the_full_forward_pass(model, plens,
                                                           bucket):
    """Two prompts of unequal length in one padded dispatch (expert
    pairs sorted into blocks, attention a block of queries at a time,
    junk ids behind each row's length), keys, values and conv rows laid
    into two slots by one commit, then each decoded a token a step:
    logits of the reference's full forward pass at every position."""
    params, tokens, want = model
    pf = lfm2.prefill(CFG, moe_block=4, attn_block=16)(bucket)
    prompt = np.full((2, bucket), 9.0, np.float32)
    for b, plen in enumerate(plens):
        prompt[b, :plen] = tokens[:plen]
    outs = _graph(pf)(dict(params, prompt=prompt,
                           plen=np.array(plens, np.float32)))
    info = lfm2.state_info(CFG, MAX_LEN)
    assert [o.shape[1:] for o in outs[1:]] == [
        (bucket, 16) if i.get("cache") else (2, 32) for i in info]
    for b, plen in enumerate(plens):
        _close(outs[0][b], want[plen - 1])
    step, info = lfm2.decode_step(CFG, MAX_LEN)
    prog = StepProgram(step, {k: mx.nd.array(v) for k, v in params.items()},
                       {}, info, 2)
    junk = {k: v + 3.0 for k, v in prog.init_states().items()}
    states = prog.commit_prefill(junk, outs[1:], [1, 0], list(plens))
    for b, plen in enumerate(plens):
        got, _s, _l = _step_through(params, tokens[plen:plen + 6], states,
                                    start=plen, slot=1 - b)
        _close(got, want[plen:plen + 6])


def test_real_step_graph_is_row_local_along_the_slot_axis():
    """The published widths, 256 slots: shapes only, nothing runs."""
    from mxnet_tpu.analysis import check_decode_step
    import json
    with open(os.path.join(REPO, "benchmark", "configs",
                           NAME + ".json")) as f:
        cfg = json.load(f)
    step, info = lfm2.decode_step(cfg, 1280)
    shapes = {"token": (256,), "pos": (256,), "valid": (256,)}
    shapes.update({i["name"]: (256,) + tuple(i["shape"]) for i in info})
    verdict, report = check_decode_step(
        step, shapes, state_names=[i["name"] for i in info],
        valid_name="valid")
    assert verdict == "row-local", report.format()
    assert [i["name"] for i in info if i.get("cache")] == [
        "l%d_%s_cache" % (i, w) for i in (2, 6, 10) for w in "kv"]
    assert sum(1 for i in info if not i.get("cache")) == 11
    assert {tuple(i["shape"]) for i in info} == {(2, 2048), (1280, 512)}


def test_declared_rules_of_the_conv_ops_reach_the_passes():
    from mxnet_tpu.analysis import classify_padding
    from mxnet_tpu.analysis.flops import count_flops
    from mxnet_tpu.analysis.memory import plan_memory
    proj, plen, w, state = (mx.sym.Variable(n)
                            for n in ("proj", "plen", "w", "state"))
    seq = mx.sym._short_conv_seq(proj, plen, w)
    shapes = {"proj": (2, 16, 24), "plen": (2,)}
    # B * x, three multiply-adds and the gate: 8 a channel a position
    assert count_flops(seq, shapes)["by_op"]["_short_conv_seq"][
        "fwd_flops"] == 8.0 * 2 * 16 * 8
    plan, _ = plan_memory(seq, shapes)
    io = 4 * (2 * 16 * 24 + 2 + 2 * 16 * 8 + 2 * 2 * 8)
    assert plan["transient_peak_bytes"] == io + 2 * (16 + 3) * 8 * 8
    batch, _ = classify_padding(seq, dict(shapes, w=(3, 8)),
                                {"b": {"proj": 0, "plen": 0}})
    along, _ = classify_padding(seq, dict(shapes, w=(3, 8)),
                                {"t": {"proj": 1}})
    assert batch["b"] == "row-local" and along["t"] == "cross-position"
    one = mx.sym._short_conv_step(proj, state, w)
    slot, _ = classify_padding(
        one, {"proj": (4, 24), "state": (4, 2, 8), "w": (3, 8)},
        {"slot": {"proj": 0, "state": 0}})
    assert slot["slot"] == "row-local"


# --------------------------------------------------------------- the pool
def _program(params, n_slots=2, dtype=np.float32):
    step, info = lfm2.decode_step(CFG, MAX_LEN)
    return StepProgram(step, {k: mx.nd.array(v, dtype=v.dtype)
                              for k, v in params.items()}, {}, info,
                       n_slots, dtype=dtype)


def test_a_join_without_prefill_finds_its_conv_rows_zero(model):
    """The plain step's reset zeroes the conv rows of the joining slot
    and leaves its caches as they are (read under a mask by position):
    the slot's first token is that of a fresh pool, its cache rows past
    position 0 are the junk they were, and the other slot's rows of
    either kind are only what its own step made of them."""
    params, _tokens, _want = model
    prog = _program(params)
    assert prog.layout.reset_names() == [
        "l%d_conv" % i for i in (0, 1, 3, 4, 5, 7, 8)]
    junk = {k: v + 3.0 for k, v in prog.init_states().items()}
    one = np.array([1.0, 0.0], np.float32)
    tok = np.array([2.0, 0.0], np.float32)
    fresh, s_fresh = prog.step(tok, 0 * one, one, prog.init_states())
    joined, s_joined = prog.step(tok, 0 * one, one, junk, reset=one)
    assert fresh[0] == joined[0]
    for name in prog.layout.reset_names():
        assert np.array_equal(np.asarray(s_fresh[name])[0],
                              np.asarray(s_joined[name])[0])
    held = np.asarray(s_joined["l2_k_cache"])
    assert (held[0, 1:] == 3.0).all() and (held[0, 0] != 3.0).any()
    # without the reset the junk rows reach the token's logits
    dirty, s_dirty = prog.step(tok, 0 * one, one, junk)
    assert not np.array_equal(np.asarray(s_dirty["l0_conv"])[0],
                              np.asarray(s_fresh["l0_conv"])[0])


def test_a_prefill_commit_replaces_the_row_and_no_other(model):
    params, tokens, _want = model
    pf = lfm2.prefill(CFG, moe_block=4, attn_block=16)(16)
    prompt = np.zeros((1, 16), np.float32)
    prompt[0, :11] = tokens[:11]
    outs = _graph(pf)(dict(params, prompt=prompt,
                           plen=np.array([11.0], np.float32)))
    prog = _program(params, n_slots=3)
    junk = {k: v + 3.0 for k, v in prog.init_states().items()}
    states = prog.commit_prefill(junk, outs[1:], [1], [11])
    for j, s in enumerate(prog.layout.target):
        held = np.asarray(states[s.name])
        assert (held[[0, 2]] == 3.0).all()
        if s.cache:
            assert np.array_equal(held[1, :16], np.asarray(outs[1 + j][0]))
            assert (held[1, 16:] == 3.0).all()
        else:
            assert np.array_equal(held[1], np.asarray(outs[1 + j][0]))


def _engine(params, prefill=True, num_slots=4, dtype=np.float32, **kw):
    step, info = lfm2.decode_step(CFG, MAX_LEN)
    if prefill:
        kw.update(prefill_sym=lfm2.prefill(CFG, moe_block=4, attn_block=16),
                  prefill_buckets=kw.pop("prefill_buckets", (16, 32)))
    return serving.DecodeEngine(
        step, {k: mx.nd.array(v, dtype=v.dtype) for k, v in params.items()},
        {}, info, num_slots=num_slots, max_len=MAX_LEN, dtype=dtype, **kw)


@pytest.mark.parametrize("prefill", [False, True],
                         ids=["fed-by-steps", "prefilled"])
def test_a_discarded_ahead_step_leaves_the_next_occupant_a_clean_row(
        model, prefill):
    """One slot.  A ends on an eos the host sees a step late, so the
    step in flight runs A once more and writes its conv rows and a cache
    row; B is seated in the slot before that step is read.  B's rows are
    zeroed inside its first step (fed by steps) or replaced by its
    prefill's commit, behind the discarded step on the device either
    way: B's tokens are ``greedy_decode``'s, as an LSTM row's are."""
    params, _tokens, _want = model
    ref_prog = _program(params, n_slots=1)
    # a prompt whose second token is not its first: the eos id
    prompt_a, want_a = next(
        (p, w) for p, w in (([t], greedy_decode(
            ref_prog, [t], 8, max_len=MAX_LEN).tolist())
            for t in range(1, 64)) if w[1] != w[0])
    eos = want_a[1]
    eng = _engine(params, prefill=prefill, num_slots=1, eos_id=eos,
                  default_deadline_ms=0, start=False)
    try:
        warm = eng.warmup()
        rep = eng._replicas[0]
        a = eng.submit(prompt_a, max_new_tokens=8)
        prompt_b = [t for t in range(2, 40) if t != eos][:19]
        b = eng.submit(prompt_b, max_new_tokens=6)
        while not a.done():
            _tick(eng, rep)
        assert rep.flight is not None and not rep.occupied_count()
        _tick(eng, rep)         # seats B, reads (and discards) A's step
        assert eng.stats()["decode"]["slot_steps_discarded"] == 1
        _run_dry(eng, rep, limit=400)
        assert a.result(timeout=0).tokens.tolist() == want_a[:2]
        want_b = greedy_decode(ref_prog, prompt_b, 6, eos_id=eos,
                               max_len=MAX_LEN).tolist()
        assert b.result(timeout=0).tokens.tolist() == want_b
        assert eng.compile_count == warm
        assert eng.stats()["decode"]["prefill_dispatches"] \
            == (2 if prefill else 0)
    finally:
        eng.close()


def test_engine_joins_a_mixed_pool_by_one_dispatch_and_one_commit(model):
    """Prompts of unequal length join in coalesced prefill dispatches;
    each dispatch's event says how many states of each kind its commit
    laid, each step's what its expert layers multiplied and what the
    live rows were routed to; ``stats()`` prices the plain rows beside
    the caches; the streams are ``greedy_decode``'s."""
    params, _tokens, _want = model
    eng = _engine(params)
    try:
        assert eng.step_verdict == "row-local"
        warm = eng.warmup()
        t0 = time.perf_counter()
        rng = np.random.default_rng(8)
        prompts = [rng.integers(1, 64, n).tolist()
                   for n in (20, 30, 9, 25, 36, 31)]
        futs = [eng.submit(p, max_new_tokens=10) for p in prompts]
        served = [f.result(timeout=300).tokens for f in futs]
        stats = eng.stats()["decode"]
        assert eng.compile_count == warm
        # 36 is past the largest bucket and is fed through the step
        assert 1 <= stats["prefill_dispatches"] <= 5
        assert stats["state_rows"] == {n: MAX_LEN for n in
                                       ("l2_k_cache", "l2_v_cache",
                                        "l6_k_cache", "l6_v_cache")}
        assert stats["row_state_bytes"] == 7 * 2 * 32 * 4
        rows = 7 * 2 * 32 + 4 * MAX_LEN * 16
        assert eng.memory_plan["per_slot_bytes"] == rows * 4
        assert eng.memory_plan["pool_bytes"] == 4 * rows * 4
        prog = _program(params, n_slots=1)
        for p, got in zip(prompts, served):
            assert list(got) == list(greedy_decode(prog, p, 10,
                                                   max_len=MAX_LEN))
        evs = [e for e in timeline.peek().events() if e["mono"] >= t0]
        pre = [e["args"] for e in evs if e["name"] == "decode.prefill"]
        assert len(pre) == stats["prefill_dispatches"]
        assert sum(e["group"] for e in pre) == 5
        assert any(e["group"] > 1 for e in pre) or len(pre) == 5
        for e in pre:
            assert e["row_states"] == 7 and e["cache_states"] == 4
        steps = [e["args"] for e in evs if e["name"] == "decode.step"]
        assert steps
        for e in steps:
            # the plain path at 4 slots: 8 held experts over 4 rows on
            # each of 7 expert layers; 3 experts a live row a layer
            assert e["expert_products"] == 7 * 4 * 8
            assert e["expert_routed"] == 7 * 3 * e["live"]
            assert e["expert_load_max"] <= e["live"]
    finally:
        eng.close()


def test_a_step_without_experts_carries_no_expert_counts():
    import test_decode as td
    step, params, info = td._lstm_step()
    eng = serving.DecodeEngine(step, params, {}, info, num_slots=2,
                               max_len=16)
    try:
        eng.warmup()
        t0 = time.perf_counter()
        eng.submit([1, 2], max_new_tokens=3).result(timeout=120)
        steps = [e["args"] for e in timeline.peek().events()
                 if e["name"] == "decode.step" and e["mono"] >= t0]
        assert steps and not any("expert_products" in a
                                 or "expert_routed" in a for a in steps)
        assert eng.stats()["decode"]["row_state_bytes"] > 0
        assert eng.stats()["decode"]["state_rows"] == {}
    finally:
        eng.close()


def test_bfloat16_engine_keeps_both_kinds_of_state_bfloat16(model):
    """bfloat16 weights (the bias float32): the pool stays bfloat16
    through prefill commits and steps, and the stream is
    ``greedy_decode``'s on the same program."""
    params = _params(CFG, dtype=jnp.bfloat16)
    eng = _engine(params, dtype=jnp.bfloat16)
    try:
        eng.warmup()
        got = eng.submit(list(range(1, 21)), max_new_tokens=6) \
            .result(timeout=300)
        pool = eng._replicas[0].states
        assert {str(v.dtype) for v in pool.values()} == {"bfloat16"}
        prog = _program(params, n_slots=1, dtype=jnp.bfloat16)
        assert list(got.tokens) == list(greedy_decode(
            prog, list(range(1, 21)), 6, max_len=MAX_LEN))
    finally:
        eng.close()

"""The decoder-block ops (ops/transformer.py), the SmallThinker builder
(models/smallthinker.py) and the engine paths they forced, against the
plain reference (benchmark/reference/smallthinker-21ba3b-8l-bf16.py) at
small widths on the CPU, float32.

Tolerances.  Program and reference compute the same float32 products in
different orders (blocked attention against whole rows, a grouped expert
product against one expert at a time), so they agree to float32 rounding
of sums a few hundred terms long: ``TOL`` = 2e-5 of the largest value
compared, about a hundred float32 ulps.  A wrong mask, rotation, routing
weight or ring row moves a logit by a few percent of that scale, a
thousand times the tolerance.
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
from mxnet_tpu.models import smallthinker as st
from mxnet_tpu.ops import invoke_jax
from mxnet_tpu.serving.decode import StepProgram, greedy_decode

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-5


def _load(*parts):
    path = os.path.join(REPO, "benchmark", *parts) + ".py"
    spec = importlib.util.spec_from_file_location(
        "bench_" + parts[-1].replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load("reference", "smallthinker-21ba3b-8l-bf16")


# window 8, pattern [0, 1, 1, 1] twice: contexts to 40 wrap the ring
# five times on six layers and leave two global ones
CFG = dict(hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
           head_dim=8, vocab_size=64, moe_num_primary_experts=8,
           moe_num_active_primary_experts=3, moe_ffn_hidden_size=16,
           sliding_window_size=8, rope_theta=1.5e6, rms_norm_eps=1e-6,
           num_hidden_layers=8, rope_layout=[0, 1, 1, 1] * 2,
           sliding_window_layout=[0, 1, 1, 1] * 2)
MAX_LEN = 48


def _params(cfg, seed=0):
    rng = np.random.default_rng(seed)
    out = {}
    for name, shape in st.param_shapes(cfg).items():
        if name.endswith("gamma"):
            out[name] = (1.0 + 0.1 * rng.standard_normal(shape)) \
                .astype(np.float32)
        else:
            fan = 1.0 if name == "emb_weight" else shape[-1]
            out[name] = (rng.standard_normal(shape) / np.sqrt(fan)) \
                .astype(np.float32)
    return out


@pytest.fixture(scope="module")
def model(ref):
    params = _params(CFG)
    rng = np.random.default_rng(1)
    tokens = rng.integers(1, CFG["vocab_size"], 40)
    want = np.asarray(ref.forward(
        {k: jnp.asarray(v) for k, v in params.items()}, CFG, tokens))
    return params, tokens, want


def _close(got, want):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= TOL * max(np.abs(want).max(), 1.0)


def _graph(symbol):
    args = symbol.list_arguments()
    fn = build_graph_fn(symbol, args, [])

    def run(feed):
        outs, _ = fn([jnp.asarray(feed[a]) for a in args], [],
                     jax.random.PRNGKey(0), False)
        return outs
    return run


# ---------------------------------------------------------------- the ops
def test_rms_norm_op(ref):
    rng = np.random.default_rng(2)
    x = rng.standard_normal((3, 5, 32)).astype(np.float32)
    g = rng.standard_normal((32,)).astype(np.float32)
    got, = invoke_jax("RMSNorm", {"eps": 1e-6}, jnp.asarray(x),
                      jnp.asarray(g))
    _close(got, ref.rms_norm(jnp.asarray(x), jnp.asarray(g), 1e-6))


def test_dense_op_accumulates_and_returns_float32():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((4, 32)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((7, 32)), jnp.bfloat16)
    got, = invoke_jax("_dense", {"num_hidden": 7}, x, w)
    assert got.dtype == jnp.float32
    want = np.asarray(x, np.float32) @ np.asarray(w, np.float32).T
    _close(got, want)           # bfloat16 products are exact in float32


@pytest.mark.parametrize("lead", [(6,), (2, 6)], ids=["step", "prompt"])
def test_rotary_op(ref, lead):
    rng = np.random.default_rng(4)
    x = rng.standard_normal(lead + (4 * 8,)).astype(np.float32)
    pos = rng.integers(0, 12000, lead[-1])
    got, = invoke_jax("_rotary", {"head_dim": 8, "theta": 1.5e6},
                      jnp.asarray(x),
                      jnp.asarray(pos.reshape((1,) * (len(lead) - 1)
                                              + (-1,)), jnp.float32))
    want = np.stack([np.asarray(ref.rotate(jnp.asarray(r), jnp.asarray(pos),
                                           8, 1.5e6))
                     for r in x.reshape((-1,) + x.shape[-2:])])
    _close(got, want.reshape(x.shape))


@pytest.mark.parametrize("window", [0, 8], ids=["global", "window"])
def test_prefill_attention_op(ref, window):
    rng = np.random.default_rng(5)
    t = 40
    q = rng.standard_normal((2, t, 32)).astype(np.float32)
    k = rng.standard_normal((2, t, 16)).astype(np.float32)
    v = rng.standard_normal((2, t, 16)).astype(np.float32)
    got, = invoke_jax("_gqa_prefill", {"num_heads": 4, "num_kv_heads": 2,
                                       "window": window, "block": 16},
                      *map(jnp.asarray, (q, k, v)))
    with jax.default_matmul_precision("highest"):
        want = np.stack([np.asarray(ref.attention(
            jnp.asarray(q[b]), jnp.asarray(k[b]), jnp.asarray(v[b]), 4, 2,
            window, q_block=16)) for b in range(2)])
    _close(got, want)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("window", [0, 200], ids=["global", "window"])
def test_fused_prefill_attention_kernel(ref, window, dtype):
    """The Pallas kernel in interpret mode against the blockwise path
    and the plain reference: a group of 7 query heads of 128 over each
    of 2 key/value heads, 512 positions in query blocks of 128 and key
    blocks of 256 (so a block is skipped, one is cut by the causal edge
    and, under the window of 200, one by the band's start), batch 2.
    float32 to ``TOL``; in bfloat16 the three round the softmax weights
    at different scales, so they agree to an ulp of the largest result."""
    from mxnet_tpu.ops import transformer as tf
    rng = np.random.default_rng(11)
    b, t, h, kv, d = 2, 512, 14, 2, 128
    q, k, v = (jnp.asarray(rng.standard_normal((b, t, n * d)), dtype)
               for n in (h, kv, kv))
    got = tf.gqa_prefill_fused(q, k, v, num_heads=h, num_kv_heads=kv,
                               window=window, block_q=128, block_k=256,
                               interpret=True)
    assert got.dtype == q.dtype and got.shape == q.shape
    with jax.default_matmul_precision("highest"):
        blockwise = tf.gqa_prefill_blockwise(
            q, k, v, num_heads=h, num_kv_heads=kv, window=window, block=128)
        want = np.stack([np.asarray(ref.attention(
            q[i], k[i], v[i], h, kv, window, q_block=128), np.float32)
            for i in range(b)])
    tol = TOL if dtype == "float32" else 2.0 ** -7      # a bfloat16 ulp
    for other in (np.asarray(blockwise, np.float32), want):
        assert np.abs(np.asarray(got, np.float32) - other).max() \
            <= tol * max(np.abs(other).max(), 1.0)


def test_prefill_attention_path_follows_shape_and_trace_mode():
    """An inference trace at heads of 128 and 1,024 positions holds the
    kernel (in the TPU branch of a platform switch: the CPU lowers the
    blockwise branch); head dimension 4, a ragged or short ``T`` and a
    training trace hold no kernel at all."""
    from mxnet_tpu.ops.registry import get_op
    op = get_op("_gqa_prefill")
    attrs = op.normalize({"num_heads": 2, "num_kv_heads": 1, "window": 300})

    def traced(d, t, training=False, dtype=jnp.bfloat16):
        sds = [jax.ShapeDtypeStruct((1, t, n * d), dtype) for n in (2, 1, 1)]
        return str(jax.make_jaxpr(op.bound(attrs, training))(*sds))
    fused = traced(128, 1024)
    assert "pallas_call" in fused and "platform_index" in fused
    for text in (traced(4, 1024), traced(128, 1024, training=True),
                 traced(128, 512), traced(128, 1024 + 64),
                 traced(128, 1024, dtype=jnp.float16)):
        assert "pallas_call" not in text
    # and the CPU runs it: the switch lowers to the blockwise branch
    x = jnp.ones((1, 1024, 256), jnp.bfloat16)
    out, = jax.jit(op.bound(attrs, False))(x, x[..., :128], x[..., :128])
    assert out.shape == x.shape


@pytest.mark.parametrize("window,rows", [(0, 48), (8, 8)],
                         ids=["global", "ring"])
def test_decode_attention_op(ref, window, rows):
    """One query row a slot against a cache that holds position p in
    row p mod rows: the reference's last row over the same positions."""
    rng = np.random.default_rng(6)
    ctx = [40, 3, 8, 17]
    q = rng.standard_normal((4, 32)).astype(np.float32)
    k = rng.standard_normal((4, 40, 16)).astype(np.float32)
    v = rng.standard_normal((4, 40, 16)).astype(np.float32)
    kc = np.full((4, rows, 16), 7.0, np.float32)    # unwritten rows: junk
    vc = np.full((4, rows, 16), -7.0, np.float32)
    for n, c in enumerate(ctx):
        for p in range(c):
            kc[n, p % rows], vc[n, p % rows] = k[n, p], v[n, p]
    got, = invoke_jax("_gqa_decode", {"num_heads": 4, "num_kv_heads": 2,
                                      "window": window},
                      jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc),
                      jnp.asarray([c - 1 for c in ctx], jnp.float32))
    with jax.default_matmul_precision("highest"):
        for n, c in enumerate(ctx):
            qs = np.zeros((c, 32), np.float32)
            qs[-1] = q[n]
            want = ref.attention(jnp.asarray(qs), jnp.asarray(k[n, :c]),
                                 jnp.asarray(v[n, :c]), 4, 2, window)[-1]
            _close(got[n], want)


def _expert_inputs(n_exp=64, width=8, hidden=16, rows=16, seed=7):
    rng = np.random.default_rng(seed)
    u = rng.standard_normal((rows, hidden)).astype(np.float32)
    r = rng.standard_normal((rows, n_exp)).astype(np.float32)
    w = [rng.standard_normal((n_exp, width, hidden)).astype(np.float32)
         / np.sqrt(hidden) for _ in range(3)]
    return u, r, w


@pytest.mark.parametrize("block", [256, 4], ids=["dense", "sorted"])
def test_expert_op(ref, block):
    u, r, w = _expert_inputs()
    y, route = invoke_jax(
        "_moe_experts", {"top_k": 6, "block": block},
        *map(jnp.asarray, [u, r] + w))
    with jax.default_matmul_precision("highest"):
        weights = ref.route(jnp.asarray(r), 6)
        want = ref.experts(jnp.asarray(u), weights, *map(jnp.asarray, w))
    _close(route, weights)
    _close(y, want)
    assert (np.asarray(route) > 0).sum(axis=1).tolist() == [6] * 16


@pytest.mark.parametrize("block", [256, 4], ids=["dense", "sorted"])
def test_expert_shares_add_up_to_the_uncut_layer(ref, block):
    """Four shares of 16 of 64 experts, each routing over all 64 and
    computing its own experts' part: the parts add up to the layer."""
    u, r, w = _expert_inputs()
    total = 0.0
    for first in (0, 16, 32, 48):
        part, route = invoke_jax(
            "_moe_experts", {"top_k": 6, "first_expert": first,
                             "num_held": 16, "block": block},
            jnp.asarray(u), jnp.asarray(r),
            *[jnp.asarray(x[first:first + 16]) for x in w])
        total = total + np.asarray(part)
    with jax.default_matmul_precision("highest"):
        want = ref.experts(jnp.asarray(u), ref.route(jnp.asarray(r), 6),
                           *map(jnp.asarray, w))
    _close(total, want)
    assert route.shape == (16, 64)          # the published router width


def test_expert_op_chooses_its_formulation_from_the_shapes():
    """Every held expert over every row while that is no more rows
    multiplied than the sorted path may pad to: at 64 experts, 6 a row
    and blocks of 256 a decode step (32 rows) and anything up to 284
    rows take the plain products, a prefill the sorted loop."""
    from mxnet_tpu.ops import transformer as tf
    attrs = {"top_k": 6, "block": 256}
    assert [tf._moe_dense(attrs, rows, 64)
            for rows in (1, 32, 256, 284, 285, 512, 8192)] \
        == [True, True, True, True, False, False, False]
    assert tf._moe_dense(attrs, 32, 16)       # a share of 16 experts
    assert not tf._moe_dense(attrs, 8192, 16)
    assert tf._moe_padded_rows(attrs, 8192, 64) == 65536


def test_expert_formulations_round_at_the_same_point():
    """In bfloat16 both formulations weight the gated activation in
    float32 before it is rounded for the down projection: they differ
    by the rounding of an expert's output alone (the sorted loop stores
    it in bfloat16 before the float32 sum), two bfloat16 ulps of the
    largest output, where a weight applied after the projection would
    also differ in every product's operand."""
    u, r, w = _expert_inputs()
    bf = jnp.bfloat16
    args = [jnp.asarray(u, bf), jnp.asarray(r)] + [jnp.asarray(x, bf)
                                                   for x in w]
    got = [np.asarray(invoke_jax("_moe_experts",
                                 {"top_k": 6, "block": block}, *args)[0],
                      np.float32) for block in (256, 4)]
    assert np.abs(got[0] - got[1]).max() <= 2 * 2.0 ** -8 \
        * np.abs(got[0]).max()


# ------------------------------------------------- the model, by the graph
def _step_through(params, tokens, states=None, start=0, n_slots=2):
    """Feed ``tokens`` one a step into slot 0 (slot 1 dead, holding
    junk); returns the logits a step and the states."""
    step, info = st.decode_step(CFG, MAX_LEN)
    run = _graph(step)
    if states is None:
        states = {i["name"]: jnp.full((n_slots,) + tuple(i["shape"]), 3.0)
                  .at[0].set(0.0) for i in info}
    logits = []
    for t, tok in enumerate(tokens):
        feed = dict(params, **states)
        feed.update(token=np.array([tok, 5], np.float32),
                    pos=np.array([start + t, 2], np.float32),
                    valid=np.array([1, 0], np.float32))
        outs = run(feed)
        logits.append(np.asarray(outs[0][0]))
        states = {i["name"]: outs[1 + j] for j, i in enumerate(info)}
    return np.stack(logits), states, np.asarray(outs[-1])


def test_step_token_by_token_matches_the_full_forward_pass(model):
    params, tokens, want = model
    got, states, load = _step_through(params, tokens)
    _close(got, want)
    # window layers kept 8 rows, global ones the whole context
    assert {v.shape[1] for v in states.values()} == {8, MAX_LEN}
    # 3 of 8 experts a layer for the one live row; the dead slot's row
    # is not counted
    assert load.shape == (8, 8) and load.sum(axis=1).tolist() == [3.0] * 8


@pytest.mark.parametrize("plen,bucket", [(29, 32), (5, 32), (8, 8)],
                         ids=["wrapped", "short", "exact"])
def test_prefill_then_decode_through_the_ring(model, plen, bucket):
    """The prompt in one dispatch (expert pairs sorted into blocks,
    attention a block of queries at a time), its keys and values laid
    into the cache states in ring order, then the rest a token a step:
    logits of the full forward pass at every position."""
    params, tokens, want = model
    pf = st.prefill(CFG, moe_block=4, attn_block=16)(bucket)
    prompt = np.zeros((2, bucket), np.float32)
    prompt[0, :plen] = tokens[:plen]
    prompt[1, :3] = tokens[:3]
    outs = _graph(pf)(dict(params, prompt=prompt,
                           plen=np.array([plen, 3], np.float32)))
    _close(outs[0][0], want[plen - 1])
    _close(outs[0][1], want[2])
    step, info = st.decode_step(CFG, MAX_LEN)
    prog = StepProgram(step, {k: mx.nd.array(v) for k, v in params.items()},
                       {}, info, 2)
    # batch row 1 is dead: it takes row 0's slot and is overwritten
    states = prog.commit_prefill(prog.init_states(), outs[1:], [0, 0],
                                 [plen, plen])
    got, _states, _load = _step_through(params, tokens[plen:], states,
                                        start=plen)
    _close(got, want[plen:])


def test_real_step_graph_is_row_local_along_the_slot_axis():
    """The published widths, 32 slots: shapes only, nothing runs."""
    from mxnet_tpu.analysis import check_decode_step
    import json
    with open(os.path.join(REPO, "benchmark", "configs",
                           "smallthinker-21ba3b-8l-bf16.json")) as f:
        cfg = json.load(f)
    step, info = st.decode_step(cfg, 12288)
    shapes = {"token": (32,), "pos": (32,), "valid": (32,)}
    shapes.update({i["name"]: (32,) + tuple(i["shape"]) for i in info})
    verdict, report = check_decode_step(
        step, shapes, state_names=[i["name"] for i in info],
        valid_name="valid")
    assert verdict == "row-local", report.format()
    rows = {i["name"]: i["shape"][0] for i in info}
    assert rows["l0_k"] == rows["l4_v"] == 12288
    assert {rows["l%d_k" % i] for i in (1, 2, 3, 5, 6, 7)} == {4096}


def test_declared_row_local_op_is_cross_position_on_a_mixed_axis():
    """``_gqa_decode`` declares axis 0 alone independent: padding on
    the cache's row axis is mixed by the softmax."""
    from mxnet_tpu.analysis import classify_padding
    q, k, v, pos = (mx.sym.Variable(n) for n in ("q", "k", "v", "pos"))
    att = mx.sym._gqa_decode(q, k, v, pos, num_heads=4, num_kv_heads=2)
    shapes = {"q": (4, 32), "k": (4, 8, 16), "v": (4, 8, 16), "pos": (4,)}
    slot, _ = classify_padding(att, shapes,
                               {"slot": {n: 0 for n in shapes}})
    rows, _ = classify_padding(att, shapes, {"rows": {"k": 1, "v": 1}})
    assert slot["slot"] == "row-local"
    assert rows["rows"] == "cross-position"


def test_declared_flops_and_temporaries_reach_the_passes():
    from mxnet_tpu.analysis.flops import count_flops
    from mxnet_tpu.analysis.memory import plan_memory
    q, k, v = (mx.sym.Variable(n) for n in "qkv")
    att = mx.sym._gqa_prefill(q, k, v, num_heads=4, num_kv_heads=2,
                              window=8, block=16)
    shapes = {"q": (1, 32, 32), "k": (1, 32, 16), "v": (1, 32, 16)}
    # blocks of 16 queries: keys 0-15, then 9-31 (the band's start)
    pairs = 16 * 16 + 16 * 23
    assert count_flops(att, shapes)["by_op"]["_gqa_prefill"]["fwd_flops"] \
        == 4.0 * 32 * pairs
    plan, _ = plan_memory(att, shapes)
    io = 4 * (32 * 32 * 2 + 2 * 32 * 16)
    assert plan["transient_peak_bytes"] == io + 2 * 4 * 4 * 16 * 23


def test_fused_prefill_attention_declares_no_score_temporaries(monkeypatch):
    """Where the kernel runs, the node's temporaries are the kernel's
    (none in HBM) and under the blockwise block of scores; its FLOPs,
    the band's and causality's count, are whoever computes them."""
    from mxnet_tpu.analysis.flops import count_flops
    from mxnet_tpu.analysis.memory import plan_memory
    from mxnet_tpu.ops import transformer as tf
    q, k, v = (mx.sym.Variable(n) for n in "qkv")
    att = mx.sym._gqa_prefill(q, k, v, num_heads=14, num_kv_heads=2,
                              window=700)
    shapes = {"q": (2, 2048, 1792), "k": (2, 2048, 256),
              "v": (2, 2048, 256)}
    dtypes = {n: np.dtype(jnp.bfloat16) for n in shapes}
    io = 2 * 2 * 2048 * (2 * 1792 + 2 * 256)

    def read():
        plan, _ = plan_memory(att, shapes, dtypes=dtypes)
        return (plan["transient_peak_bytes"],
                count_flops(att, shapes, dtypes=dtypes)["total"])
    blockwise, flops = read()
    assert blockwise == io + 2 * 4 * 2 * 14 * 512 * (700 + 511)
    monkeypatch.setattr(tf, "_lowers_for_tpu", lambda: True)
    fused, fused_flops = read()
    assert fused == io < blockwise
    assert fused_flops == flops > 0
    # a head of 4 keeps the blockwise figure on a TPU too
    small = {"q": (2, 2048, 56), "k": (2, 2048, 8), "v": (2, 2048, 8)}
    plan, _ = plan_memory(att, small)
    assert plan["transient_peak_bytes"] \
        == 4 * 2 * 2048 * (2 * 56 + 2 * 8) + 2 * 4 * 2 * 14 * 512 * 1211


# --------------------------------------------------------------- the engine
def _engine(params, monkeypatch=None, budget=None, **kw):
    step, info = st.decode_step(CFG, MAX_LEN)
    if budget is not None:
        monkeypatch.setenv("MXNET_MEMORY_BUDGET_BYTES", str(budget))
    return serving.DecodeEngine(
        step, {k: mx.nd.array(v) for k, v in params.items()}, {}, info,
        num_slots=4, max_len=MAX_LEN,
        prefill_sym=st.prefill(CFG, moe_block=4, attn_block=16), **kw), \
        step, info


def test_engine_prefills_in_one_dispatch_and_equals_greedy_decode(model):
    params, tokens, _want = model
    eng, step, info = _engine(params, prefill_buckets=(16, 32))
    try:
        assert eng.step_verdict == "row-local"
        warm = eng.warmup()
        rng = np.random.default_rng(8)
        prompts = [rng.integers(1, 64, n).tolist()
                   for n in (20, 30, 9, 25, 36, 31)]
        futs = [eng.submit(p, max_new_tokens=10) for p in prompts]
        served = [f.result(timeout=300).tokens for f in futs]
        stats = eng.stats()["decode"]
        assert eng.compile_count == warm
        # 36 is past the largest bucket and is fed through the step
        assert 1 <= stats["prefill_dispatches"] <= 5
        assert stats["state_rows"]["l1_k"] == 8
        assert stats["state_rows"]["l0_k"] == MAX_LEN
        prog = StepProgram(step, {k: mx.nd.array(v)
                                  for k, v in params.items()}, {}, info, 1)
        for p, got in zip(prompts, served):
            assert list(got) == list(greedy_decode(prog, p, 10,
                                                   max_len=MAX_LEN))
    finally:
        eng.close()


def test_coalesced_prefill_stays_inside_the_token_budget(model, monkeypatch):
    """A budget that leaves room for 64 positions a dispatch: the warm
    set is the (batch, bucket) shapes within it, the bucket past it is
    dropped, and no group is formed over it however many join at once."""
    from mxnet_tpu.telemetry import timeline
    params, _tokens, _want = model
    probe, _s, _i = _engine(params, prefill_buckets=(16, 32, 128),
                            start=False)
    plan = probe.memory_plan
    probe.close()
    per_token = plan["programs"][-1]["transient_peak_bytes"] // (4 * 128)
    step_peak = plan["programs"][0]["peak_bytes"]
    eng, _s, _i = _engine(params, monkeypatch,
                          budget=step_peak + 64 * per_token + per_token // 2,
                          prefill_buckets=(16, 32, 128))
    try:
        stats = eng.stats()["decode"]
        assert stats["prefill_token_budget"] == 64
        assert eng._prefill_grid == {16: (1, 2, 4), 32: (1, 2), 128: ()}
        assert stats["prefill_buckets"] == [16, 32]
        assert stats["prefill_programs"] == 5
        warm = eng.warmup()
        t0 = time.perf_counter()
        rng = np.random.default_rng(9)
        futs = [eng.submit(rng.integers(1, 64, 20).tolist(),
                           max_new_tokens=3) for _ in range(8)]
        for f in futs:
            f.result(timeout=300)
        assert eng.compile_count == warm
        evs = [e for e in timeline.peek().events()
               if e["name"] == "decode.prefill" and e["mono"] >= t0]
        assert sum(e["args"]["group"] for e in evs) == 8
        for e in evs:
            assert e["args"]["padded"] <= 64
            assert e["args"]["tokens"] == 20 * e["args"]["group"]
    finally:
        eng.close()


def test_prefill_event_counts_the_fused_attention_nodes(model, monkeypatch):
    """``decode.prefill`` says how many attention nodes of the
    dispatched program took the fused kernel and ``stats()`` totals
    them: none on the CPU; and with the op's predicate replaced by one
    that takes the window layers, six of a program's eight."""
    from mxnet_tpu.ops import transformer as tf
    from mxnet_tpu.telemetry import timeline
    params, _tokens, _want = model
    eng, _s, _i = _engine(params, prefill_buckets=(16, 32))
    try:
        eng.warmup()
        assert eng._prefill_fused == {
            (b, bb): (0, 8) for b in (16, 32) for bb in (1, 2, 4)}
        nodes = eng._replicas[0].prefill_caches[16].node_inputs(
            "_gqa_prefill", {"prompt": (2, 16), "plen": (2,)})
        assert [(a["window"], s[0], s[1]) for a, s, _d in nodes] == [
            (w, (2, 16, 32), (2, 16, 16)) for w in [0, 8, 8, 8] * 2]
        assert {str(d[0]) for _a, _s, d in nodes} == {"float32"}
        monkeypatch.setattr(
            tf, "prefill_takes_kernel",
            lambda attrs, shapes, dtypes: attrs["window"] > 0)
        eng._prefill_fused.pop((32, 1))
        rng = np.random.default_rng(12)
        t0 = time.perf_counter()
        for n in (20, 9, 30):      # one at a time: buckets 32, 16, 32
            eng.submit(rng.integers(1, 64, n).tolist(),
                       max_new_tokens=2).result(timeout=300)
        evs = [e["args"] for e in timeline.peek().events()
               if e["name"] == "decode.prefill" and e["mono"] >= t0]
        assert [(e["bucket"], e["fused_attention"], e["attention_nodes"])
                for e in evs] == [(32, 6, 8), (16, 0, 8), (32, 6, 8)]
        stats = eng.stats()["decode"]
        assert stats["prefill_dispatches"] == 3
        assert stats["prefill_fused_attention"] == 12
        assert stats["prefill_attention_nodes"] == 24
    finally:
        eng.close()


@pytest.mark.parametrize("builder", ["lstm", "attention"])
def test_bfloat16_engine_keeps_a_bfloat16_pool(builder):
    """``DecodeEngine(dtype=bfloat16)``: the pool is bfloat16 before and
    after steps (the attention fixture's float32 one-hot blend used to
    hand it back float32), and the stream is ``greedy_decode``'s."""
    import test_decode as td
    step, params, info = (td._lstm_step if builder == "lstm"
                          else td._attn_step)()
    params = {k: v.astype("bfloat16") for k, v in params.items()}
    eng = serving.DecodeEngine(step, params, {}, info, num_slots=4,
                               max_len=16, dtype=jnp.bfloat16)
    try:
        eng.warmup()
        got = eng.submit([1, 2, 3], max_new_tokens=6).result(timeout=120)
        pool = eng._replicas[0].states
        assert {str(v.dtype) for v in pool.values()} == {"bfloat16"}
        assert eng.memory_plan["donation"]["step"]["accepted"]
        prog = StepProgram(step, params, {}, info, 1, dtype=jnp.bfloat16)
        assert list(got.tokens) == list(greedy_decode(prog, [1, 2, 3], 6,
                                                      max_len=16))
    finally:
        eng.close()


def test_bfloat16_speculative_engine_keeps_a_bfloat16_pool():
    """The repair holds where a draft proposes too: the target's and
    the draft's cache states stay bfloat16 through speculative steps."""
    import test_decode as td
    graphs = []
    for seed in (0, 1):
        step, params, info = td._attn_step(seed=seed)
        for i in info:
            i["cache"] = True
        graphs.append((step, {k: v.astype("bfloat16")
                              for k, v in params.items()}, info))
    (step, params, info), (draft, dparams, dinfo) = graphs
    eng = serving.DecodeEngine(
        step, params, {}, info, num_slots=4, max_len=16,
        dtype=jnp.bfloat16, draft_sym=draft, draft_arg_params=dparams,
        draft_state_info=dinfo, spec_k=2)
    try:
        eng.warmup()
        got = eng.submit([1, 2, 3], max_new_tokens=6).result(timeout=120)
        assert len(got.tokens) == 6
        pool = eng._replicas[0].states
        assert len(pool) == 4
        assert {str(v.dtype) for v in pool.values()} == {"bfloat16"}
    finally:
        eng.close()


def test_a_cache_state_is_not_zeroed_at_a_join(model):
    """A ``cache`` state is read under a mask by position, so a join
    leaves the previous occupant's rows where they are (zeroing was a
    select over the whole pool in front of every step); a state that is
    not a cache still reads as zeros at its join."""
    import test_decode as td
    step, params, info = td._lstm_step()
    prog = StepProgram(step, params, {}, info, num_slots=2)
    junk = {k: v + 3.0 for k, v in prog.init_states().items()}
    one = np.array([1.0, 0.0], np.float32)
    tok = np.array([2.0, 0.0], np.float32)
    fresh, _ = prog.step(tok, 0 * one, one, prog.init_states())
    joined, _ = prog.step(tok, 0 * one, one, junk, reset=one)
    assert fresh[0] == joined[0]
    params_st, _tokens, _want = model
    step, info = st.decode_step(CFG, MAX_LEN)
    prog = StepProgram(step, {k: mx.nd.array(v)
                              for k, v in params_st.items()}, {}, info, 2)
    junk = {k: v + 3.0 for k, v in prog.init_states().items()}
    _ids, after = prog.step(tok, 0 * one, one, junk, reset=one)
    held = np.asarray(after["l0_k"])
    assert (held[0, 1:] == 3.0).all() and (held[0, 0] != 3.0).any()


def test_states_of_different_rows_are_priced_by_their_own_shape(model):
    params, _tokens, _want = model
    eng, _s, info = _engine(params, start=False)
    try:
        rows = sum(i["shape"][0] for i in info)
        assert rows == 6 * 2 * 8 + 2 * 2 * MAX_LEN
        assert eng.memory_plan["pool_bytes"] == 4 * rows * 16 * 4
        assert eng.memory_plan["per_slot_bytes"] == rows * 16 * 4
    finally:
        eng.close()

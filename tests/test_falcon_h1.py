"""What Falcon-H1 forced (ops/transformer.py: Mamba-2's selective state
space as one recurrence a step and as a chunked scan over a padded
prompt, the short convolution in its plain form with a bias and silu, the
grouped norm's gain a value), the model's graphs (models/falcon_h1.py: both
mixers side by side in every layer, every µP multiplier) and a slot pool
that holds a state space's state beside caches and conv rows, against the
plain reference (benchmark/reference/falcon-h1-34b-4l-bf16.py) at small
widths on the CPU, on the configuration's own seeded weights.

Tolerances.  In float32 program and reference compute the same function
in different orders (the scan's chunks of matrix products against the
reference's recurrence a position at a time, blocked attention against
whole rows), so they agree to float32 rounding of sums a few hundred
terms long: ``TOL`` = 2e-5 of the largest value compared, about a
hundred float32 ulps.  A multiplier off by half, a state carried a step
too far or a gate left out moves a logit by a percent or more of that
scale, hundreds of times the tolerance.  The gated convolution's default
is held bit for bit to the outputs of the tree before the plain form was
added; the plain form against the reference, in bfloat16, may differ by
the rounding of the one result, an ulp (2^-8 of the largest value).
"""
import importlib.util
import json
import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import serving
from mxnet_tpu.executor import build_graph_fn
from mxnet_tpu.models import falcon_h1
from mxnet_tpu.ops import invoke_jax
from mxnet_tpu.serving.decode import StepProgram, greedy_decode
from mxnet_tpu.telemetry import timeline

from test_decode_pipeline import _run_dry, _tick

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-5
NAME = "falcon-h1-34b-4l-bf16"
MAX_LEN = 48


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


@pytest.fixture(scope="module")
def cfg_mod():
    return _load("configs", NAME)


def _published():
    with open(os.path.join(REPO, "benchmark", "configs",
                           NAME + ".json")) as f:
        return json.load(f)


# two layers of the one kind at small widths: 4 query and 2 key/value
# heads of 8; a state space of 4 heads of 4 over a state of 16 in 2
# groups, chunks of 4; every multiplier as published
CFG = dict(_published(), hidden_size=32, num_attention_heads=4,
           num_key_value_heads=2, head_dim=8, mamba_d_ssm=16,
           mamba_n_heads=4, mamba_d_head=4, mamba_n_groups=2,
           mamba_d_state=16, mamba_chunk_size=4, intermediate_size=48,
           vocab_size=64, num_hidden_layers=2, dtype="float32")


@pytest.fixture(scope="module")
def params(cfg_mod):
    return {k: np.asarray(v) for k, v in cfg_mod.init_params(CFG, 5).items()}


@pytest.fixture(scope="module")
def model(ref, params):
    tokens = np.random.default_rng(1).integers(1, CFG["vocab_size"], 40)
    want = np.asarray(ref.forward(_jnp(params), CFG, tokens))
    return tokens, want


def _jnp(params):
    return {k: jnp.asarray(v) for k, v in params.items()}


def _close(got, want, tol=TOL):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= tol * max(np.abs(want).max(), 1e-30)


def _far(got, want, by=100 * TOL):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.abs(got - want).max() > by * np.abs(want).max()


def _graph(symbol):
    args = symbol.list_arguments()
    fn = build_graph_fn(symbol, args, [])

    def run(feed):
        outs, _ = fn([jnp.asarray(feed[a]) for a in args], [],
                     jax.random.PRNGKey(0), False)
        return outs
    return run


# ---------------------------------------------------------------- the ops
def _ssd_inputs(n=3, t=21, h=4, p=8, g=2, s=6, seed=0):
    rng = np.random.default_rng(seed)

    def arr(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)
    heads = (jnp.asarray(np.log(rng.uniform(1, 16, h)), jnp.float32),
             jnp.asarray(np.log(np.expm1(np.exp(rng.uniform(
                 np.log(1e-3), np.log(1e-1), h)))), jnp.float32),
             jnp.asarray(rng.uniform(0.5, 1.5, h), jnp.float32))
    return (arr(n, t, h * p), arr(n, t, h), arr(n, t, g * s),
            arr(n, t, g * s)), heads


def test_ssd_scan_is_the_recurrence_at_each_rows_own_length(ref):
    """A padded batch of 21 positions in chunks of 4 (not a multiple),
    rows of lengths 21, 9, 4, 1 and 0 with junk behind them: ``y`` at
    every live position and the state at the row's own length are the
    reference's recurrence over the row alone."""
    lens = [21, 9, 4, 1, 0]
    (x, dt, b, c), heads = _ssd_inputs(n=len(lens))
    y, state = invoke_jax("_ssd_scan", {"num_groups": 2, "chunk": 4},
                          x, dt, b, c, jnp.asarray(lens, jnp.float32),
                          *heads)
    assert y.shape == x.shape and state.shape == (5, 4, 8, 6)
    with jax.default_matmul_precision("highest"):
        for r, n in enumerate(lens):
            if not n:
                assert not np.asarray(state[r]).any()
                continue
            want_y, want_s = ref.state_space(
                x[r, :n], dt[r, :n], b[r, :n], c[r, :n], *heads, groups=2,
                round_from=n)
            _close(y[r, :n], want_y)
            _close(state[r], want_s)


def test_ssd_step_iterated_is_the_scan():
    """The decode step a position at a time from a zero state reads the
    scan's outputs and leaves its state, row by row."""
    (x, dt, b, c), heads = _ssd_inputs(n=2, t=11)
    lens = [11, 6]
    y, state = invoke_jax("_ssd_scan", {"num_groups": 2, "chunk": 4},
                          x, dt, b, c, jnp.asarray(lens, jnp.float32),
                          *heads)
    s = jnp.zeros((2, 4, 8, 6), jnp.float32)
    for t in range(11):
        out, nxt = invoke_jax("_ssd_step", {"num_groups": 2}, x[:, t],
                              dt[:, t], b[:, t], c[:, t], s, *heads)
        assert out.dtype == x.dtype and nxt.dtype == s.dtype
        live = np.asarray([t < n for n in lens])
        _close(np.asarray(out)[live], np.asarray(y[:, t])[live])
        s = jnp.where(jnp.asarray(live)[:, None, None, None], nxt, s)
    _close(s, state)


def test_ssd_step_rounds_the_state_once_to_its_own_dtype():
    """A bfloat16 state: the arithmetic is float32 and the next state is
    the float32 update rounded once; ``y`` is read off the float32
    state, before that rounding."""
    (x, dt, b, c), heads = _ssd_inputs(n=2, t=1)
    s = jnp.asarray(np.random.default_rng(3).standard_normal((2, 4, 8, 6)),
                    jnp.bfloat16)
    y, nxt = invoke_jax("_ssd_step", {"num_groups": 2}, x[:, 0], dt[:, 0],
                        b[:, 0], c[:, 0], s, *heads)
    y32, nxt32 = invoke_jax("_ssd_step", {"num_groups": 2}, x[:, 0],
                            dt[:, 0], b[:, 0], c[:, 0],
                            s.astype(jnp.float32), *heads)
    assert nxt.dtype == jnp.bfloat16 and y.dtype == jnp.float32
    assert np.array_equal(np.asarray(nxt, np.float32),
                          np.asarray(nxt32.astype(jnp.bfloat16), np.float32))
    assert np.array_equal(np.asarray(y), np.asarray(y32))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_short_conv_gated_default_is_the_parents_bit_for_bit(dtype):
    """LFM2's gated form, the default, on the inputs it was pinned on
    (``tests/data/short_conv_gated.npz``, written by the tree before the
    plain form was added), step and sequence."""
    pinned = np.load(os.path.join(REPO, "tests", "data",
                                  "short_conv_gated.npz"))
    rng = np.random.default_rng(11)
    proj = jnp.asarray(rng.standard_normal((3, 10, 24)), dtype)
    taps = jnp.asarray(rng.standard_normal((3, 8)) / np.sqrt(3), dtype)
    state = jnp.asarray(rng.standard_normal((3, 2, 8)), dtype)
    got = dict(zip(("step_y", "step_state"), invoke_jax(
        "_short_conv_step", {}, proj[:, 0], state, taps)))
    got.update(zip(("seq_y", "seq_state"), invoke_jax(
        "_short_conv_seq", {}, proj, jnp.asarray([10, 4, 1], jnp.float32),
        taps)))
    for tag, value in got.items():
        assert np.array_equal(np.asarray(value, np.float32),
                              pinned[dtype + "_" + tag]), tag


PLAIN = {"taps": 4, "gated": False, "activation": "silu", "has_bias": True}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_plain_conv_with_bias_and_silu_against_the_reference(ref, dtype):
    """Mamba-2's convolution: depthwise, causal, 4 taps, a bias, silu
    after, no gate; the sequence form's state at each row's own length
    is what stepping through the live positions leaves, bit for bit."""
    rng = np.random.default_rng(2)
    u = jnp.asarray(rng.standard_normal((3, 12, 8)), dtype)
    taps = jnp.asarray(rng.standard_normal((4, 8)) / 2, dtype)
    bias = jnp.asarray(rng.standard_normal((8,)), dtype)
    lens = [12, 5, 2]
    got, state = invoke_jax("_short_conv_seq", PLAIN, u,
                            jnp.asarray(lens, jnp.float32), taps, bias)
    assert got.shape == u.shape and state.shape == (3, 3, 8)
    for r, n in enumerate(lens):
        _close(got[r, :n], ref.causal_conv(u[r, :n], taps, bias),
               TOL if dtype == "float32" else 2.0 ** -8)
        held = jnp.zeros((1, 3, 8), dtype)
        for t in range(n):
            out, held = invoke_jax("_short_conv_step", PLAIN, u[r:r + 1, t],
                                   held, taps, bias)
            assert np.array_equal(np.asarray(out[0], np.float32),
                                  np.asarray(got[r, t], np.float32))
        assert np.array_equal(np.asarray(held[0], np.float32),
                              np.asarray(state[r], np.float32))
    assert not np.asarray(state[2, 0], np.float32).any()    # length 2


def test_grouped_norm_takes_a_gain_a_value(ref):
    """``RMSNorm(head_dim=)`` with a gain as wide as the data norms each
    group over its own values under a gain a value; a gain of
    ``head_dim`` stays the one gain of every head."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((5, 16)), jnp.float32)
    g = jnp.asarray(1.0 + 0.3 * rng.standard_normal((16,)), jnp.float32)
    got, = invoke_jax("RMSNorm", {"eps": 1e-5, "head_dim": 8}, x, g)
    _close(got, ref.rms_norm(x, g, 1e-5, group=8))
    whole, = invoke_jax("RMSNorm", {"eps": 1e-5}, x, g)
    assert np.abs(np.asarray(got - whole)).max() > 0.01
    head, = invoke_jax("RMSNorm", {"eps": 1e-5, "head_dim": 8}, x, g[:8])
    _close(head, ref.rms_norm(x, jnp.tile(g[:8], 2), 1e-5, group=8))


def test_declared_rules_of_the_state_space_ops_reach_the_passes():
    """FLOPs and temporaries as declared at registration reach the
    analysis passes, which the memory preflight prices a prefill by; the
    scan is row-local along the batch and crosses positions, the step is
    row-local along the slots."""
    from mxnet_tpu.analysis import classify_padding
    from mxnet_tpu.analysis.flops import count_flops
    from mxnet_tpu.analysis.memory import plan_memory
    names = ("x", "dt", "b", "c", "plen", "state", "a", "dtb", "d")
    x, dt, b, c, plen, state, a, dtb, d = (mx.sym.Variable(n) for n in names)
    heads = {"a": (4,), "dtb": (4,), "d": (4,)}
    scan = mx.sym._ssd_scan(x, dt, b, c, plen, a, dtb, d, num_groups=2,
                            chunk=4)
    shapes = {"x": (2, 10, 32), "dt": (2, 10, 4), "b": (2, 10, 12),
              "c": (2, 10, 12), "plen": (2,)}
    # 3 chunks of 4 a row: C B^T a group and its weights times x a head,
    # each chunk's contribution and read of the state, the state carried
    per_chunk = 2.0 * 16 * (12 + 32) + 3.0 * 4 * 16 + 4.0 * 4 * 192 \
        + 2.0 * 192 + 2.0 * 4 * 32
    assert count_flops(scan, shapes)["by_op"]["_ssd_scan"]["fwd_flops"] \
        == 2 * 3 * per_chunk
    plan, _ = plan_memory(scan, shapes)
    temp = 2 * (4 * (2 * 4 * 16 + 3 * 4 * 8 * 6 + 2 * 4 * 32) + 3 * 4 * 32 * 4)
    io = 4 * (2 * 10 * (32 + 4 + 24) + 2 + 2 * 10 * 32 + 2 * 4 * 8 * 6)
    assert plan["transient_peak_bytes"] == io + temp
    full = dict(shapes, **heads)
    batch, _ = classify_padding(scan, full, {"r": {n: 0 for n in shapes}})
    along, _ = classify_padding(scan, full, {"t": {n: 1 for n in names[:4]}})
    assert batch["r"] == "row-local" and along["t"] == "cross-position"
    step = mx.sym._ssd_step(x, dt, b, c, state, a, dtb, d, num_groups=2)
    one = {"x": (3, 32), "dt": (3, 4), "b": (3, 12), "c": (3, 12),
           "state": (3, 4, 8, 6)}
    assert count_flops(step, one)["by_op"]["_ssd_step"]["fwd_flops"] \
        == 3 * (5.0 * 192 + 2.0 * 32)
    slot, _ = classify_padding(step, dict(one, **heads),
                               {"slot": {n: 0 for n in one}})
    assert slot["slot"] == "row-local"


# ------------------------------------------------- the model, by the graph
def _step_through(params, tokens, states=None, start=0, slot=0, n_slots=2):
    """Feed ``tokens`` one a step into ``slot`` (the others dead,
    holding junk); returns the logits a step and the states."""
    step, info = falcon_h1.decode_step(CFG, MAX_LEN)
    run = _graph(step)
    if states is None:
        states = {i["name"]: jnp.full((n_slots,) + tuple(i["shape"]), 3.0)
                  .at[slot].set(0.0) for i in info}
    valid = np.zeros((n_slots,), np.float32)
    valid[slot] = 1.0
    logits = []
    for t, tok in enumerate(tokens):
        token = np.full((n_slots,), 5.0, np.float32)
        pos = np.full((n_slots,), 2.0, np.float32)
        token[slot], pos[slot] = tok, start + t
        outs = run(dict(params, **states, token=token, pos=pos, valid=valid))
        logits.append(np.asarray(outs[0][slot]))
        states = {i["name"]: outs[1 + j] for j, i in enumerate(info)}
    return np.stack(logits), states


def test_state_info_holds_two_caches_and_two_plain_rows_a_layer():
    info = falcon_h1.state_info(CFG, MAX_LEN)
    assert [i["name"] for i in info] == [
        "l%d_%s" % (i, k) for i in (0, 1)
        for k in ("k_cache", "v_cache", "conv", "ssm")]
    shapes = {i["name"]: (i["shape"], bool(i.get("cache"))) for i in info}
    assert shapes["l0_k_cache"] == ((MAX_LEN, 16), True)
    assert shapes["l1_conv"] == ((3, 16 + 2 * 2 * 16), False)
    assert shapes["l1_ssm"] == ((4, 4, 16), False)


def test_step_token_by_token_matches_the_full_forward_pass(params, model):
    tokens, want = model
    got, _states = _step_through(params, tokens)
    _close(got, want)


@pytest.mark.parametrize("plens,bucket", [((13, 3), 16), ((1, 2), 8),
                                          ((8, 5), 8)],
                         ids=["unequal", "one-and-two", "exact"])
def test_prefill_then_decode_matches_the_full_forward_pass(params, model,
                                                           plens, bucket):
    """Two prompts of unequal length in one padded dispatch (the scan
    over chunks of 4, attention a block of queries at a time, junk ids
    behind each row's length), keys, values, conv rows and state space
    rows laid into two slots by one commit, then each decoded a token a
    step: logits of the reference's full forward pass at every
    position."""
    tokens, want = model
    pf = falcon_h1.prefill(CFG, attn_block=8)(bucket)
    prompt = np.full((2, bucket), 9.0, np.float32)
    for b, plen in enumerate(plens):
        prompt[b, :plen] = tokens[:plen]
    outs = _graph(pf)(dict(params, prompt=prompt,
                           plen=np.array(plens, np.float32)))
    info = falcon_h1.state_info(CFG, MAX_LEN)
    assert [o.shape[1:] for o in outs[1:]] == [
        (bucket, 16) if i.get("cache") else tuple(i["shape"]) for i in info]
    for b, plen in enumerate(plens):
        _close(outs[0][b], want[plen - 1])
    step, info = falcon_h1.decode_step(CFG, MAX_LEN)
    prog = StepProgram(step, {k: mx.nd.array(v) for k, v in params.items()},
                       {}, info, 2)
    junk = {k: v + 3.0 for k, v in prog.init_states().items()}
    states = prog.commit_prefill(junk, outs[1:], [1, 0], list(plens))
    for b, plen in enumerate(plens):
        got, _s = _step_through(params, tokens[plen:plen + 6], states,
                                start=plen, slot=1 - b)
        _close(got, want[plen:plen + 6])


MULTIPLIERS = ["embedding_multiplier", "key_multiplier",
               "attention_in_multiplier", "attention_out_multiplier",
               "ssm_in_multiplier", "ssm_out_multiplier", "lm_head_multiplier",
               "ssm_multipliers.0", "ssm_multipliers.1", "ssm_multipliers.2",
               "ssm_multipliers.3", "ssm_multipliers.4", "mlp_multipliers.0",
               "mlp_multipliers.1"]


@pytest.mark.parametrize("key", MULTIPLIERS)
def test_every_multiplier_is_applied(ref, params, model, key):
    """The program's graph, built from the configuration, is the
    reference with every multiplier as published (the step test) and is
    not the reference with this one multiplier made half again as
    large."""
    tokens, want = model
    cfg = json.loads(json.dumps(CFG))
    name, _, at = key.partition(".")
    if at:
        cfg[name][int(at)] *= 1.5
    else:
        cfg[name] *= 1.5
    other = np.asarray(ref.forward(_jnp(params), cfg, tokens[:12]))
    _far(other, want[:12])


def test_both_mixers_add_to_the_stream_side_by_side(ref, params, model):
    """The parallel block: each mixer reads the one normed input and
    both land in the stream before the MLP; leaving out either (its
    output multiplier at 0) moves the logits."""
    tokens, want = model
    for key in ("ssm_out_multiplier", "attention_out_multiplier"):
        _far(np.asarray(ref.forward(_jnp(params), dict(CFG, **{key: 0.0}),
                                    tokens[:12])), want[:12])


# --------------------------------------------------------------- the pool
def _program(params, n_slots=2, dtype=np.float32):
    step, info = falcon_h1.decode_step(CFG, MAX_LEN)
    return StepProgram(step, {k: mx.nd.array(v, dtype=v.dtype)
                              for k, v in params.items()}, {}, info,
                       n_slots, dtype=dtype)


def test_a_join_without_prefill_finds_both_plain_rows_zero(params):
    """The plain step's reset zeroes the joining slot's conv row and
    state space row and leaves its caches as they are: the slot's first
    token and its next plain rows are those of a fresh pool; without the
    reset the junk reaches the state."""
    prog = _program(params)
    assert prog.layout.reset_names() == [
        "l%d_%s" % (i, k) for i in (0, 1) for k in ("conv", "ssm")]
    junk = {k: v + 3.0 for k, v in prog.init_states().items()}
    one = np.array([1.0, 0.0], np.float32)
    tok = np.array([2.0, 0.0], np.float32)
    fresh, s_fresh = prog.step(tok, 0 * one, one, prog.init_states())
    joined, s_joined = prog.step(tok, 0 * one, one, junk, reset=one)
    assert fresh[0] == joined[0]
    for name in prog.layout.reset_names():
        assert np.array_equal(np.asarray(s_fresh[name])[0],
                              np.asarray(s_joined[name])[0])
    held = np.asarray(s_joined["l0_k_cache"])
    assert (held[0, 1:] == 3.0).all() and (held[0, 0] != 3.0).any()
    _dirty, s_dirty = prog.step(tok, 0 * one, one, junk)
    assert not np.array_equal(np.asarray(s_dirty["l1_ssm"])[0],
                              np.asarray(s_fresh["l1_ssm"])[0])


def _engine(params, prefill=True, num_slots=4, dtype=np.float32, **kw):
    step, info = falcon_h1.decode_step(CFG, MAX_LEN)
    if prefill:
        kw.update(prefill_sym=falcon_h1.prefill(CFG, attn_block=8),
                  prefill_buckets=kw.pop("prefill_buckets", (16, 32)))
    return serving.DecodeEngine(
        step, {k: mx.nd.array(v, dtype=v.dtype) for k, v in params.items()},
        {}, info, num_slots=num_slots, max_len=MAX_LEN, dtype=dtype, **kw)


@pytest.mark.parametrize("prefill", [False, True],
                         ids=["fed-by-steps", "prefilled"])
def test_a_discarded_ahead_step_leaves_the_next_occupant_a_clean_row(
        params, prefill):
    """One slot.  A ends on an eos the host sees a step late, so the
    step in flight runs A once more and writes its state space row, conv
    row and a cache row; B is seated before that step is read.  B's
    plain rows are zeroed inside its first step (fed by steps) or
    replaced by its prefill's commit, behind the discarded step on the
    device either way: B's tokens are ``greedy_decode``'s."""
    ref_prog = _program(params, n_slots=1)
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


def test_engine_joins_by_one_dispatch_and_prices_the_state(params):
    """Prompts of unequal length join in coalesced prefill dispatches
    whose event counts two plain rows and two caches a layer; ``stats()``
    prices a slot's conv and state space rows; the streams are
    ``greedy_decode``'s."""
    eng = _engine(params)
    try:
        assert eng.step_verdict == "row-local"
        warm = eng.warmup()
        t0 = time.perf_counter()
        rng = np.random.default_rng(8)
        prompts = [rng.integers(1, 64, n).tolist() for n in (20, 30, 9, 25)]
        futs = [eng.submit(p, max_new_tokens=8) for p in prompts]
        served = [f.result(timeout=300).tokens for f in futs]
        stats = eng.stats()["decode"]
        assert eng.compile_count == warm
        assert stats["prefill_dispatches"] >= 1
        plain = 2 * (3 * 80 + 4 * 4 * 16)
        assert stats["row_state_bytes"] == plain * 4
        assert eng.memory_plan["per_slot_bytes"] \
            == (plain + 4 * MAX_LEN * 16) * 4
        prog = _program(params, n_slots=1)
        for p, got in zip(prompts, served):
            assert list(got) == list(greedy_decode(prog, p, 8,
                                                   max_len=MAX_LEN))
        pre = [e["args"] for e in timeline.peek().events()
               if e["name"] == "decode.prefill" and e["mono"] >= t0]
        assert pre and all(e["row_states"] == 4 and e["cache_states"] == 4
                           for e in pre)
    finally:
        eng.close()


def test_bfloat16_engine_keeps_every_state_in_its_declared_dtype(cfg_mod):
    """bfloat16 weights: the key/value rows, the conv rows and the state
    space rows stay bfloat16 through prefill commits and steps, and the
    stream is ``greedy_decode``'s on the same program."""
    params = {k: np.asarray(v) for k, v in cfg_mod.init_params(
        dict(CFG, dtype="bfloat16"), 5).items()}
    eng = _engine(params, dtype=jnp.bfloat16)
    try:
        eng.warmup()
        got = eng.submit(list(range(1, 21)), max_new_tokens=6) \
            .result(timeout=300)
        pool = eng._replicas[0].states
        assert {k: str(v.dtype) for k, v in pool.items()} == {
            "l%d_%s" % (i, k): "bfloat16" for i in (0, 1)
            for k in ("k_cache", "v_cache", "conv", "ssm")}
        prog = _program(params, n_slots=1, dtype=jnp.bfloat16)
        assert list(got.tokens) == list(greedy_decode(
            prog, list(range(1, 21)), 6, max_len=MAX_LEN))
    finally:
        eng.close()


def test_real_step_graph_is_row_local_along_the_slot_axis():
    """The published widths, 256 slots: shapes only, nothing runs."""
    from mxnet_tpu.analysis import check_decode_step
    cfg = _published()
    step, info = falcon_h1.decode_step(cfg, 1280)
    shapes = {"token": (256,), "pos": (256,), "valid": (256,)}
    shapes.update({i["name"]: (256,) + tuple(i["shape"]) for i in info})
    verdict, report = check_decode_step(
        step, shapes, state_names=[i["name"] for i in info],
        valid_name="valid")
    assert verdict == "row-local", report.format()
    assert {tuple(i["shape"]) for i in info} == {
        (1280, 512), (3, 5120), (32, 128, 256)}

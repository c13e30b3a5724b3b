"""Continuous-batching decode tests (mxnet_tpu/serving/decode.py).

Coverage per the issue contract: per-sequence BITWISE parity against
single-request greedy decode (LSTM recurrent state AND an attention
block over a fixed-layout per-slot KV cache), join/leave mid-flight
with the compile counter pinned (zero warm retraces), slot exhaustion
-> queue -> admit on free, deadlines re-checked every iteration
(queued expiry AND mid-generation eviction both complete with partial
output + the ``expired`` flag — the multi-step generalization of
admission deadlines), telemetry series reclaimed on close(), the
decode-step soundness lint (library + ``graph_lint --decode-step``),
``BaseRNNCell.begin_state_arrays``, and the bench smoke.
"""
import json
import os
import sys
import threading
import time
import urllib.request
import warnings
from concurrent.futures import Future

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import serving, telemetry
from mxnet_tpu.serving import DecodeEngine, StepProgram, greedy_decode
from mxnet_tpu.serving.admission import (AdmissionController,
                                         DeadlineExceededError, Request)
from mxnet_tpu.serving.decode import DecodeResult

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# model builders
# ---------------------------------------------------------------------------

def _lstm_step(vocab=16, embed=8, hidden=16, seed=0):
    """One LSTM decode step: token + (h, c) -> [logits, h', c']."""
    from mxnet_tpu.rnn.rnn_cell import LSTMCell
    tok = mx.sym.Variable("token")
    emb = mx.sym.Embedding(tok, input_dim=vocab, output_dim=embed,
                           name="emb")
    cell = LSTMCell(hidden, prefix="lstm_")
    out, (h2, c2) = cell(emb, [mx.sym.Variable("h"),
                               mx.sym.Variable("c")])
    logits = mx.sym.FullyConnected(out, num_hidden=vocab, name="out_fc")
    rng = np.random.default_rng(seed)

    def w(*shape, scale=1.0):
        return mx.nd.array(
            rng.standard_normal(shape).astype(np.float32) * scale)

    params = {
        "emb_weight": w(vocab, embed),
        "lstm_i2h_weight": w(4 * hidden, embed, scale=0.5),
        "lstm_i2h_bias": mx.nd.zeros((4 * hidden,)),
        "lstm_h2h_weight": w(4 * hidden, hidden, scale=0.5),
        "lstm_h2h_bias": mx.nd.zeros((4 * hidden,)),
        "out_fc_weight": w(vocab, hidden),
        "out_fc_bias": mx.nd.zeros((vocab,)),
    }
    state_info = [{"name": "h", "shape": (hidden,)},
                  {"name": "c", "shape": (hidden,)}]
    return mx.sym.Group([logits, h2, c2]), params, state_info


def _attn_step(vocab=16, d=8, max_len=16, seed=0):
    """Single-head attention decode step over a fixed-layout per-slot
    KV cache (the O(1) layout of arxiv 2603.09555): caches are
    ``(slots, max_len, d)`` buffers written at ONE position per step
    via a one-hot blend — never grown, never re-laid-out — and reads
    are causally masked to positions <= pos."""
    tok = mx.sym.Variable("token")
    kc = mx.sym.Variable("k_cache")                      # (N, T, D)
    vc = mx.sym.Variable("v_cache")
    pos = mx.sym.Variable("pos")                         # (N,)
    emb = mx.sym.Embedding(tok, input_dim=vocab, output_dim=d,
                           name="emb")
    q = mx.sym.FullyConnected(emb, num_hidden=d, no_bias=True,
                              name="q_fc")
    k = mx.sym.FullyConnected(emb, num_hidden=d, no_bias=True,
                              name="k_fc")
    v = mx.sym.FullyConnected(emb, num_hidden=d, no_bias=True,
                              name="v_fc")
    oh = mx.sym.one_hot(pos, depth=max_len)              # (N, T)
    ohe = mx.sym.expand_dims(oh, axis=2)                 # (N, T, 1)
    k_new = mx.sym.broadcast_mul(kc, 1.0 - ohe) + mx.sym.broadcast_mul(
        mx.sym.expand_dims(k, axis=1), ohe)
    v_new = mx.sym.broadcast_mul(vc, 1.0 - ohe) + mx.sym.broadcast_mul(
        mx.sym.expand_dims(v, axis=1), ohe)
    scores = mx.sym.batch_dot(k_new, mx.sym.expand_dims(q, axis=2))
    scores = mx.sym.reshape(scores, shape=(0, max_len)) \
        * (1.0 / np.sqrt(d))
    steps_r = mx.sym.reshape(mx.sym._arange(start=0, stop=max_len),
                             shape=(1, max_len))
    mask = mx.sym.broadcast_lesser_equal(
        steps_r, mx.sym.reshape(pos, shape=(-1, 1)))     # causal
    scores = scores * mask + (1.0 - mask) * (-1e9)
    attn = mx.sym.softmax(scores, axis=1)
    ctx = mx.sym.batch_dot(mx.sym.expand_dims(attn, axis=1), v_new)
    ctx = mx.sym.reshape(ctx, shape=(0, d))
    logits = mx.sym.FullyConnected(ctx, num_hidden=vocab, name="out_fc")
    rng = np.random.default_rng(seed)

    def w(*shape, scale=1.0):
        return mx.nd.array(
            rng.standard_normal(shape).astype(np.float32) * scale)

    params = {"emb_weight": w(vocab, d),
              "q_fc_weight": w(d, d, scale=0.5),
              "k_fc_weight": w(d, d, scale=0.5),
              "v_fc_weight": w(d, d, scale=0.5),
              "out_fc_weight": w(vocab, d),
              "out_fc_bias": mx.nd.zeros((vocab,))}
    state_info = [{"name": "k_cache", "shape": (max_len, d)},
                  {"name": "v_cache", "shape": (max_len, d)}]
    return mx.sym.Group([logits, k_new, v_new]), params, state_info


def _sum_state_model(vocab=16, d=8, seed=0):
    """Additive-state toy whose prefill is expressible in ONE dispatch:
    s' = s + emb(token); logits = FC(s').  The prefill graph masks the
    padded prompt with the live length and sums — state after the
    prompt equals the teacher-forced rollout up to float summation
    order, so prefill parity is asserted at TOKEN level."""
    tok = mx.sym.Variable("token")
    s = mx.sym.Variable("s")
    emb = mx.sym.Embedding(tok, input_dim=vocab, output_dim=d,
                           name="emb")
    s2 = s + emb
    logits = mx.sym.FullyConnected(s2, num_hidden=vocab, name="out_fc")
    step = mx.sym.Group([logits, s2])

    prompt = mx.sym.Variable("prompt")                   # (1, T)
    plen = mx.sym.Variable("plen")                       # (1,)
    pemb = mx.sym.Embedding(prompt, input_dim=vocab, output_dim=d,
                            name="emb")                  # (1, T, d)
    masked = mx.sym.SequenceMask(pemb, use_sequence_length=True,
                                 sequence_length=plen, axis=1)
    srow = mx.sym.sum(masked, axis=1)                    # (1, d)
    plogits = mx.sym.FullyConnected(srow, num_hidden=vocab,
                                    name="out_fc")
    prefill = mx.sym.Group([plogits, srow])

    rng = np.random.default_rng(seed)
    params = {
        "emb_weight": mx.nd.array(
            rng.standard_normal((vocab, d)).astype(np.float32)),
        "out_fc_weight": mx.nd.array(
            rng.standard_normal((vocab, d)).astype(np.float32)),
        "out_fc_bias": mx.nd.zeros((vocab,)),
    }
    state_info = [{"name": "s", "shape": (d,)}]
    return step, prefill, params, state_info


# ---------------------------------------------------------------------------
# bitwise parity vs single-request greedy decode
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("builder", [_lstm_step, _attn_step],
                         ids=["lstm", "attention"])
def test_bitwise_parity_vs_single_request_greedy(builder):
    """Whatever company a request keeps in the slot pool, its tokens
    must equal the single-request greedy rollout EXACTLY."""
    step, params, state_info = builder()
    max_len = 16
    eng = DecodeEngine(step, params, {}, state_info, num_slots=4,
                       max_len=max_len, default_deadline_ms=0)
    eng.warmup()
    prompts = [[1, 2], [3], [5, 1, 4], [2, 2], [7], [1, 1, 1, 1]]
    futs = [eng.submit(p, max_new_tokens=8) for p in prompts]
    res = [f.result(timeout=120) for f in futs]
    eng.close()

    ref = StepProgram(step, params, {}, state_info, num_slots=1)
    for p, r in zip(prompts, res):
        want = greedy_decode(ref, p, 8, max_len=max_len)
        assert r.finish_reason == "length"
        assert np.array_equal(r.tokens, want), (p, r.tokens, want)


def test_churn_join_leave_zero_retraces():
    """Requests joining and leaving the RUNNING batch never move the
    compile counter: iteration-level scheduling changes no shape."""
    step, params, state_info = _lstm_step()
    eng = DecodeEngine(step, params, {}, state_info, num_slots=2,
                       max_len=64, default_deadline_ms=0)
    c0 = eng.warmup()
    assert c0 > 0
    # staggered mixed lengths force constant churn on 2 slots
    rng = np.random.default_rng(3)
    futs = []
    for i in range(12):
        n = int(rng.integers(1, 12))
        futs.append(eng.submit([int(rng.integers(16))],
                               max_new_tokens=n))
        if i % 3 == 0:
            time.sleep(0.002)
    res = [f.result(timeout=120) for f in futs]
    st = eng.stats()["decode"]
    assert eng.compile_count == c0          # ZERO warm retraces
    assert st["joins"] == 12 and st["leaves"] == 12
    assert all(r.finish_reason == "length" for r in res)
    eng.close()


def test_slot_exhaustion_queues_then_admits_on_free():
    """More requests than slots: the overflow waits in the admission
    queue and is seated the moment a slot frees — nobody is lost, and
    occupancy never exceeds capacity."""
    step, params, state_info = _lstm_step()
    eng = DecodeEngine(step, params, {}, state_info, num_slots=1,
                       max_len=64, max_queue=16, default_deadline_ms=0)
    eng.warmup()
    futs = [eng.submit([i % 16], max_new_tokens=5) for i in range(6)]
    res = [f.result(timeout=120) for f in futs]
    st = eng.stats()
    eng.close()
    assert all(len(r) == 5 and r.finish_reason == "length" for r in res)
    assert st["admitted"] == 6 and st["decode"]["requests_served"] == 6
    # parity holds through the queue too (same slot, serial residency)
    ref = StepProgram(step, params, {}, state_info, num_slots=1)
    for i, r in enumerate(res):
        assert np.array_equal(r.tokens,
                              greedy_decode(ref, [i % 16], 5, max_len=64))


def test_eos_ends_generation_early():
    step, params, state_info = _lstm_step()
    ref = StepProgram(step, params, {}, state_info, num_slots=1)
    want = greedy_decode(ref, [1], 8, max_len=32)
    eos = int(want[2])                  # force a hit on step 3
    eng = DecodeEngine(step, params, {}, state_info, num_slots=2,
                       max_len=32, eos_id=eos, default_deadline_ms=0)
    eng.warmup()
    r = eng.generate([1], max_new_tokens=8, timeout=120)
    eng.close()
    assert r.finish_reason == "eos"
    assert r.tokens[-1] == eos and len(r) <= 8
    assert np.array_equal(r.tokens, want[:len(r)])


# ---------------------------------------------------------------------------
# deadlines: re-checked every iteration, partial results, never failure
# ---------------------------------------------------------------------------

def test_deadline_mid_generation_evicts_with_partial_tokens():
    """A slot-resident request whose deadline passes is EVICTED between
    steps: the future resolves with the partial tokens + expired=True,
    and the freed slot seats queued work."""
    step, params, state_info = _lstm_step()
    eng = DecodeEngine(step, params, {}, state_info, num_slots=1,
                       max_len=200000, max_queue=8,
                       default_deadline_ms=0)
    eng.warmup()
    doomed = eng.submit([1], max_new_tokens=150000, deadline_ms=80)
    follower = eng.submit([2], max_new_tokens=3)
    r = doomed.result(timeout=120)
    assert r.expired and r.finish_reason == "deadline"
    assert 0 < len(r) < 150000          # partial, not empty, not full
    r2 = follower.result(timeout=120)
    assert r2.finish_reason == "length" and len(r2) == 3
    st = eng.stats()["decode"]
    assert st["evictions"] == 1
    eng.close()
    # the partial prefix still matches single-request greedy decode
    ref = StepProgram(step, params, {}, state_info, num_slots=1)
    want = greedy_decode(ref, [1], len(r), max_len=200000)
    assert np.array_equal(r.tokens, want)


def test_deadline_while_queued_completes_with_empty_partial():
    """Queued-past-deadline is the degenerate partial: zero tokens,
    expired=True — resolved by the admission sweep that runs on every
    scheduler iteration, NOT only when a slot frees."""
    step, params, state_info = _lstm_step()
    eng = DecodeEngine(step, params, {}, state_info, num_slots=1,
                       max_len=200000, max_queue=8,
                       default_deadline_ms=0)
    eng.warmup()
    hog = eng.submit([1], max_new_tokens=150000, deadline_ms=2000)
    starved = eng.submit([2], max_new_tokens=5, deadline_ms=50)
    r = starved.result(timeout=10)      # must NOT wait for the hog
    assert r.expired and len(r) == 0
    hog.cancel()
    eng.close(drain=False)


def test_admission_on_expire_generalizes_deadline_accounting():
    """Regression for the multi-step deadline satellite, at the
    AdmissionController level: an expired request WITH ``on_expire``
    resolves with the handler's value; one WITHOUT keeps the original
    fail-fast DeadlineExceededError contract; a buggy handler falls
    back to the exception."""
    adm = AdmissionController(max_queue=8)
    past = time.monotonic() - 0.01
    multi = Request({}, ("g",), Future(), deadline=past)
    multi.on_expire = lambda exc: DecodeResult([7], "deadline")
    oneshot = Request({}, ("g",), Future(), deadline=past)
    buggy = Request({}, ("g",), Future(), deadline=past)
    buggy.on_expire = lambda exc: (_ for _ in ()).throw(ValueError("x"))
    for r in (multi, oneshot, buggy):
        adm.admit(r)
    adm.sweep()
    res = multi.future.result(timeout=5)
    assert isinstance(res, DecodeResult) and res.expired
    assert res.tokens.tolist() == [7]
    with pytest.raises(DeadlineExceededError):
        oneshot.future.result(timeout=5)
    with pytest.raises(DeadlineExceededError):
        buggy.future.result(timeout=5)
    assert adm.stats()["expired"] == 3
    adm.close(drain=False)


def test_admission_poll_is_nonblocking_and_sweeps():
    adm = AdmissionController(max_queue=8)
    assert adm.poll(4) == []            # empty queue: fast path
    live = Request({}, ("g",), Future())
    dead = Request({}, ("g",), Future(),
                   deadline=time.monotonic() - 0.01)
    adm.admit(dead)
    adm.admit(live)
    t0 = time.perf_counter()
    batch = adm.poll(4)
    assert time.perf_counter() - t0 < 0.5
    assert batch == [live]              # the expired one was swept
    with pytest.raises(DeadlineExceededError):
        dead.future.result(timeout=5)
    adm.close(drain=False)


# ---------------------------------------------------------------------------
# lifecycle
# ---------------------------------------------------------------------------

def test_close_without_drain_resolves_partial_as_closed():
    step, params, state_info = _lstm_step()
    eng = DecodeEngine(step, params, {}, state_info, num_slots=1,
                       max_len=200000, default_deadline_ms=0)
    eng.warmup()
    fut = eng.submit([1], max_new_tokens=150000)
    while eng.stats()["decode"]["steps"] < 3:
        time.sleep(0.005)
    eng.close(drain=False)
    r = fut.result(timeout=30)
    assert r.finish_reason == "closed" and len(r) > 0
    with pytest.raises(serving.EngineClosedError):
        eng.submit([1])


def test_close_with_drain_completes_everything():
    step, params, state_info = _lstm_step()
    eng = DecodeEngine(step, params, {}, state_info, num_slots=2,
                       max_len=64, default_deadline_ms=0)
    eng.warmup()
    futs = [eng.submit([i % 16], max_new_tokens=4) for i in range(5)]
    eng.close(drain=True)
    assert all(f.result(timeout=5).finish_reason == "length"
               for f in futs)


def test_submit_validation():
    step, params, state_info = _lstm_step()
    eng = DecodeEngine(step, params, {}, state_info, num_slots=1,
                       max_len=8, default_deadline_ms=0, start=False)
    with pytest.raises(mx.MXNetError):
        eng.submit([])                          # empty prompt
    with pytest.raises(mx.MXNetError):
        eng.submit(list(range(8)))              # no room to generate
    with pytest.raises(mx.MXNetError):
        eng.submit([1], max_new_tokens=0)
    eng.close()


def test_step_program_contract_errors():
    step, params, state_info = _lstm_step()
    with pytest.raises(mx.MXNetError):          # wrong output count
        StepProgram(step[0], params, {}, state_info, num_slots=2)
    with pytest.raises(mx.MXNetError):          # no such state input
        StepProgram(step, params, {},
                    [{"name": "nope", "shape": (4,)}], num_slots=2)
    with pytest.raises(mx.MXNetError):          # missing params
        StepProgram(step, {}, {}, state_info, num_slots=2)
    # stochastic step graphs are refused: greedy parity depends on a
    # deterministic persistent program
    tok = mx.sym.Variable("token")
    emb = mx.sym.Embedding(tok, input_dim=16, output_dim=8, name="emb")
    drop = mx.sym.Dropout(emb, p=0.5)
    h = mx.sym.Variable("h")
    st = h + drop
    logits = mx.sym.FullyConnected(st, num_hidden=16, name="out_fc")
    with pytest.raises(mx.MXNetError):
        StepProgram(mx.sym.Group([logits, st]),
                    {"emb_weight": mx.nd.zeros((16, 8)),
                     "out_fc_weight": mx.nd.zeros((16, 8)),
                     "out_fc_bias": mx.nd.zeros((16,))},
                    {}, [{"name": "h", "shape": (8,)}], num_slots=2)


# ---------------------------------------------------------------------------
# bucketed prefill
# ---------------------------------------------------------------------------

def test_bucketed_prefill_matches_teacher_forcing():
    """With a prefill graph, the whole prompt is consumed in ONE
    bucketed dispatch; generated tokens must match the teacher-forced
    path, and prompt buckets compile once each (warmup pins them)."""
    step, prefill, params, state_info = _sum_state_model()
    eng_tf = DecodeEngine(step, params, {}, state_info, num_slots=2,
                          max_len=16, default_deadline_ms=0)
    eng_pf = DecodeEngine(step, params, {}, state_info, num_slots=2,
                          max_len=16, default_deadline_ms=0,
                          prefill_sym=prefill)
    eng_tf.warmup()
    c0 = eng_pf.warmup()
    prompts = [[1], [2, 3], [4, 5, 6], [1, 2, 3, 4, 5]]
    try:
        for p in prompts:
            a = eng_tf.generate(p, max_new_tokens=4, timeout=120)
            b = eng_pf.generate(p, max_new_tokens=4, timeout=120)
            assert np.array_equal(a.tokens, b.tokens), (p, a.tokens,
                                                        b.tokens)
        assert eng_pf.compile_count == c0       # buckets pre-compiled
        assert eng_pf.stats()["decode"]["prefill"] == "bucket"
        # prefill counts the first sampled token: fewer step dispatches
        assert (eng_pf.stats()["decode"]["steps"]
                < eng_tf.stats()["decode"]["steps"])
    finally:
        eng_tf.close()
        eng_pf.close()


# ---------------------------------------------------------------------------
# soundness lint: the masked step must be row-local along the slot axis
# ---------------------------------------------------------------------------

def _cross_slot_step(vocab=16, d=8):
    """Deliberately unsound: logits see a sum ACROSS slots."""
    tok = mx.sym.Variable("token")
    s = mx.sym.Variable("s")
    emb = mx.sym.Embedding(tok, input_dim=vocab, output_dim=d,
                           name="emb")
    s2 = s + emb
    mixed = mx.sym.broadcast_add(
        s2, mx.sym.sum(s2, axis=0, keepdims=True))
    logits = mx.sym.FullyConnected(mixed, num_hidden=vocab,
                                   name="out_fc")
    params = {"emb_weight": mx.nd.zeros((vocab, d)),
              "out_fc_weight": mx.nd.zeros((vocab, d)),
              "out_fc_bias": mx.nd.zeros((vocab,))}
    return mx.sym.Group([logits, s2]), params, \
        [{"name": "s", "shape": (d,)}]


def test_check_decode_step_verdicts():
    from mxnet_tpu import analysis
    step, _, state_info = _lstm_step()
    shapes = {"token": (4,), "h": (4, 16), "c": (4, 16)}
    verdict, report = analysis.check_decode_step(
        step, shapes, state_names=["h", "c"])
    assert verdict == "row-local" and not report.errors

    bad, _, _ = _cross_slot_step()
    verdict, report = analysis.check_decode_step(
        bad, {"token": (4,), "s": (4, 8)}, state_names=["s"])
    assert verdict == "cross-position"


def test_pad_dirty_state_gets_no_zero_absorption_credit():
    """A sum over the SLOT axis of a state input is cross-position even
    though serving's padding pass would normally credit zero pads as
    exact for sum: dead decode slots hold stale garbage, not zeros."""
    from mxnet_tpu import analysis
    s = mx.sym.Variable("s")
    pooled = mx.sym.broadcast_add(s, mx.sym.sum(s, axis=0,
                                                keepdims=True))
    g = mx.sym.Group([pooled, s])
    dirty, _ = analysis.check_decode_step(
        g, {"s": (4, 8)}, state_names=["s"])
    assert dirty == "cross-position"


def test_engine_preflight_warns_or_raises_on_cross_slot(monkeypatch):
    bad, params, state_info = _cross_slot_step()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        eng = DecodeEngine(bad, params, {}, state_info, num_slots=2,
                           max_len=8, default_deadline_ms=0,
                           start=False)
        eng.close()
    assert any("cross-position" in str(x.message) for x in w)
    monkeypatch.setenv("MXNET_ANALYSIS_STRICT", "1")
    from mxnet_tpu.analysis import AnalysisError
    with pytest.raises(AnalysisError):
        DecodeEngine(bad, params, {}, state_info, num_slots=2,
                     max_len=8, default_deadline_ms=0, start=False)


@pytest.mark.lint_graphs
def test_graph_lint_decode_step_flag(tmp_path, capsys):
    """CLI surface of the same lint: row-local exits 0, cross-position
    exits 1 even without --strict (no degrade path for decode), and
    --decode-step refuses the rewrite flags."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import graph_lint
        step, _, _ = _lstm_step()
        good = str(tmp_path / "step.json")
        step.save(good)
        rc = graph_lint.main(
            [good, "--decode-step", "--shapes", "token=4",
             "--shapes", "h=4,16", "--shapes", "c=4,16",
             "--decode-state", "h,c"])
        assert rc == 0, capsys.readouterr().out
        out = capsys.readouterr().out
        bad, _, _ = _cross_slot_step()
        badp = str(tmp_path / "bad.json")
        bad.save(badp)
        rc = graph_lint.main([badp, "--decode-step", "--shapes",
                              "token=4", "--shapes", "s=4,8",
                              "--decode-state", "s"])
        assert rc == 1
        assert "cross-position" in capsys.readouterr().out
        rc = graph_lint.main([good, "--decode-step", "--fix",
                              "--shapes", "token=4"])
        assert rc == 2
    finally:
        sys.path.remove(os.path.join(REPO, "tools"))


# ---------------------------------------------------------------------------
# rnn satellite: begin_state_arrays
# ---------------------------------------------------------------------------

def test_begin_state_arrays_from_state_info():
    from mxnet_tpu.rnn.rnn_cell import LSTMCell, GRUCell
    cell = LSTMCell(24, prefix="l_")
    arrs = cell.begin_state_arrays(5)
    assert [a.shape for a in arrs] == [(5, 24), (5, 24)]
    assert all(a.dtype == np.float32 and not a.any() for a in arrs)
    half = cell.begin_state_arrays(3, dtype=np.float16)
    assert all(a.dtype == np.float16 for a in half)
    # single source of slot-pool shapes: info order == array order
    gru = GRUCell(8, prefix="g_")
    assert [a.shape for a in gru.begin_state_arrays(2)] == [(2, 8)]


def test_begin_state_arrays_sizes_decode_slot_pool():
    """The decode engine's per-slot state_info is the cell's
    state_info with the batch placeholder dropped — the two shape
    sources must agree."""
    from mxnet_tpu.rnn.rnn_cell import LSTMCell
    cell = LSTMCell(16, prefix="lstm_")
    slots = 4
    arrs = cell.begin_state_arrays(slots)
    step, params, state_info = _lstm_step(hidden=16)
    prog = StepProgram(step, params, {}, state_info, num_slots=slots)
    pool = prog.init_states()
    for arr, info in zip(arrs, state_info):
        assert pool[info["name"]].shape == arr.shape


# ---------------------------------------------------------------------------
# telemetry
def test_prefill_failure_isolated_to_joining_request():
    """One request's broken prefill dispatch fails ONLY that request:
    co-resident mid-generation requests keep their partial output (they
    share no state with the joiner — unlike the one-shot engine, there
    is no shared dispatch to blame)."""
    step, params, state_info = _lstm_step()
    eng = DecodeEngine(step, params, {}, state_info, num_slots=2,
                       max_len=64, default_deadline_ms=0)
    eng.warmup()
    slow = eng.submit([1], max_new_tokens=40)
    time.sleep(0.05)

    class _Boom(object):
        compile_count = 0

        def dispatch(self, feeds):
            raise RuntimeError("prefill boom")

    eng._prefill_buckets = (64,)
    eng._prefill_caches = {64: _Boom()}
    bad = eng.submit([2], max_new_tokens=3)
    with pytest.raises(RuntimeError, match="prefill boom"):
        bad.result(timeout=60)
    eng._prefill_buckets = ()
    eng._prefill_caches = {}
    r = slow.result(timeout=120)            # co-resident survives
    assert r.finish_reason == "length" and len(r) == 40
    assert eng.stats()["decode"]["leaves"] == 2
    eng.close()


def test_cancelled_before_seating_counts_as_leave():
    """A future cancelled while queued never occupies a slot, but it
    IS a leave — stats() and the telemetry leaves series must carry
    the same numbers."""
    step, params, state_info = _lstm_step()
    eng = DecodeEngine(step, params, {}, state_info, num_slots=1,
                       max_len=32, default_deadline_ms=0, start=False)
    eng.warmup()
    f1 = eng.submit([1], max_new_tokens=2)
    f2 = eng.submit([2], max_new_tokens=2)
    assert f2.cancel()
    eng.close(drain=True)                   # drains on this thread
    assert f1.result(timeout=10).finish_reason == "length"
    st = eng.stats()["decode"]
    assert st["joins"] == 1 and st["leaves"] == 2


# ---------------------------------------------------------------------------

@pytest.fixture
def _fresh_telemetry():
    telemetry.set_enabled(None)
    telemetry.reset()
    telemetry.stop_server()
    yield
    telemetry.stop_server()
    telemetry.set_enabled(None)
    telemetry.reset()


def test_decode_telemetry_series_and_reclaim(_fresh_telemetry):
    """mxnet_serve_decode_* series carry the same numbers stats()
    reports, and close() reclaims every per-engine series + the
    collect callback (reload-in-a-loop cannot grow scrapes)."""
    step, params, state_info = _lstm_step()
    reg = telemetry.registry()
    eng = DecodeEngine(step, params, {}, state_info, num_slots=2,
                       max_len=32, default_deadline_ms=0)
    eng.warmup()
    futs = [eng.submit([i % 16], max_new_tokens=4) for i in range(3)]
    [f.result(timeout=120) for f in futs]
    doc = reg.collect()
    st = eng.stats()["decode"]

    def total(name):
        return sum(s["value"] for s in doc[name]["series"])

    assert total("mxnet_serve_decode_tokens_total") == 12
    assert total("mxnet_serve_decode_steps_total") == st["steps"]
    assert total("mxnet_serve_decode_joins_total") == 3
    assert total("mxnet_serve_decode_leaves_total") == 3
    slots_fam = reg.get("mxnet_serve_decode_slots")
    assert [inst.value for _, inst in slots_fam.series()] == [2]
    assert doc["mxnet_serve_decode_step_ms"]["series"][0]["count"] \
        == st["steps"]
    # prometheus rendering passes the repo's metric-name lint
    from mxnet_tpu.telemetry import lint_metric_names
    assert lint_metric_names(telemetry.render_prometheus()) == []
    eng.close()
    assert reg._callbacks == []
    assert slots_fam.series() == []
    assert reg.get("mxnet_serve_decode_slots_occupied").series() == []
    assert reg.get("mxnet_serve_queue_depth").series() == []
    assert reg.get("mxnet_serve_compile_count").series() == []


def test_healthz_decode_block(_fresh_telemetry):
    step, params, state_info = _lstm_step()
    srv = telemetry.start_server(0, host="127.0.0.1")
    eng = DecodeEngine(step, params, {}, state_info, num_slots=2,
                       max_len=32, default_deadline_ms=0)
    eng.warmup()
    eng.generate([1], max_new_tokens=4, timeout=120)
    with urllib.request.urlopen(
            "http://127.0.0.1:%d/healthz" % srv.port, timeout=10) as r:
        hz = json.loads(r.read().decode())
    assert hz["decode"]["engines"] == 1
    assert hz["decode"]["slots"] == 2
    assert hz["decode"]["tokens"] == 4
    assert hz["decode"]["joins"] == 1 and hz["decode"]["leaves"] == 1
    eng.close()
    with urllib.request.urlopen(
            "http://127.0.0.1:%d/healthz" % srv.port, timeout=10) as r:
        hz = json.loads(r.read().decode())
    assert "decode" not in hz           # series reclaimed with engine
    telemetry.stop_server()


def test_disabled_telemetry_binds_no_decode_instruments(monkeypatch,
                                                        _fresh_telemetry):
    monkeypatch.setenv("MXNET_TELEMETRY_ON", "0")
    step, params, state_info = _lstm_step()
    eng = DecodeEngine(step, params, {}, state_info, num_slots=2,
                       max_len=32, default_deadline_ms=0)
    eng.warmup()
    eng.generate([1], max_new_tokens=3, timeout=120)
    eng.close()
    assert telemetry.registry().families() == []
    assert telemetry.registry().instrument_calls() == 0


# ---------------------------------------------------------------------------
# bench smoke (the >=2x acceptance gate runs in perf/decode_bench.py)
# ---------------------------------------------------------------------------

def test_decode_bench_smoke():
    sys.path.insert(0, os.path.join(REPO, "perf"))
    try:
        import decode_bench
        row = decode_bench.run_bench(requests=12, slots=4, max_len=32,
                                     mean_new=6, hidden=16, repeat=1)
    finally:
        sys.path.remove(os.path.join(REPO, "perf"))
    assert row["retraces"] == 0
    assert row["tokens"] > 0
    assert row["continuous_tps"] > 0 and row["static_tps"] > 0
    # scheduling wins on STEP COUNT even when host noise hides the
    # wall-clock win at smoke scale: continuous never steps more
    assert row["continuous_steps"] <= row["static_steps"]
    # ISSUE 18 advisory efficiency fields priced from the FLOPs ledger
    assert row["analytic_gflops_per_s"] is None \
        or row["analytic_gflops_per_s"] > 0
    assert 0 < row["goodput_ratio"] <= 1.0
    assert "serve_mfu" in row           # honest None on CPU

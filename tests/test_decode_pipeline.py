"""The plain decode loop keeps one step in flight (serving/decode.py
``_step_body``): step N+1 is dispatched before step N's ids are read, a
generating slot's token goes from one step's output buffer into the next
step's input on the device (``StepFeed`` / ``FROM_PREVIOUS``), and
delivery goes by who sat where at the dispatch.

Held here: the engine's tokens, finish reasons and ``on_token``
sequences equal ``greedy_decode``'s for every finish reason; a finish
the host cannot foresee (eos, deadline, a raising callback) throws away
exactly the one slot-step in flight and a finish by length none; a slot
seated anew while a step is in flight never gets the old occupant's id;
nothing is left unread by a close, a failing step or a rehabilitation;
a speculative engine stays synchronous; the host-fed and the fed-back
form are one compiled program.
"""
import time
import warnings

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.serving import DecodeEngine, StepProgram, greedy_decode
from mxnet_tpu.serving import faults
from mxnet_tpu.serving.decode import FROM_PREVIOUS, PendingStep, StepFeed
from mxnet_tpu.telemetry import timeline

from test_decode import _attn_step, _lstm_step, _sum_state_model

MAX_LEN = 16


@pytest.fixture(autouse=True)
def _no_faults():
    faults.clear()
    yield
    faults.clear()


def _reference(builder=_lstm_step):
    step, params, info = builder()
    return StepProgram(step, params, {}, info, num_slots=1)


def _want(ref, prompt, max_new, eos=None, max_len=MAX_LEN):
    """``greedy_decode``'s tokens and the reason it stopped for."""
    toks = greedy_decode(ref, prompt, max_new, eos_id=eos,
                         max_len=max_len).tolist()
    reason = "eos" if eos is not None and toks and toks[-1] == eos \
        else "length"
    return toks, reason


def _manual(builder=_lstm_step, num_slots=2, **kw):
    """An engine with no worker: the test makes the loop's iterations
    itself, so it can look between them."""
    step, params, info = builder()
    eng = DecodeEngine(step, params, {}, info, num_slots=num_slots,
                       max_len=kw.pop("max_len", MAX_LEN),
                       default_deadline_ms=0, start=False, **kw)
    eng.warmup()
    return eng, eng._replicas[0]


def _tick(eng, rep):
    """One iteration of ``_single_run``'s busy path: seat what waits in
    the free slots, then a step."""
    free = rep.free_slots()
    if free:
        batch = eng._adm.poll(free)
        if batch:
            eng._join_many(rep, batch)
    eng._step_once(rep)


def _run_dry(eng, rep, limit=200):
    for _ in range(limit):
        if not rep.occupied_count() and rep.flight is None \
                and not len(eng._adm):
            return
        _tick(eng, rep)
    raise AssertionError("the pool did not empty in %d iterations" % limit)


# ---------------------------------------------------------------------------
# the step program: ids fed back on the device, one compiled program
# ---------------------------------------------------------------------------

def test_step_feed_takes_the_previous_ids_on_the_device():
    step, params, info = _lstm_step()
    prog = StepProgram(step, params, {}, info, num_slots=3)
    one = np.ones((3,), np.float32)
    tokens = np.array([1.0, 5.0, 9.0], np.float32)
    # host-fed twice: the second step is fed the first's ids by the host
    ids0, s1 = prog.step(tokens, 0 * one, one, prog.init_states())
    ids1, _ = prog.step(ids0.copy(), one, one, s1)
    traces = prog.trace_count
    # the same two steps with the second fed on the device, slot 1 kept
    # on a host value: nothing is read before both are dispatched
    first, t1 = prog.step(StepFeed(tokens), 0 * one, one,
                          prog.init_states())
    assert isinstance(first, PendingStep)
    fed = np.array([FROM_PREVIOUS, ids0[1], FROM_PREVIOUS], np.float32)
    second, _ = prog.step(StepFeed(fed, first), one, one, t1)
    assert np.array_equal(second.read(), ids1)
    assert np.array_equal(first.read(), ids0)
    assert first.read_s >= 0.0 and first.dispatch_s > 0.0
    # one program, whoever feeds it
    assert prog.trace_count == traces == 1


def test_the_dispatch_keeps_copies_of_the_callers_vectors():
    step, params, info = _lstm_step()
    prog = StepProgram(step, params, {}, info, num_slots=2)
    one = np.ones((2,), np.float32)
    tokens = np.array([3.0, 4.0], np.float32)
    want, _ = prog.step(tokens.copy(), 0 * one, one, prog.init_states())
    pos = 0 * one
    pending, _ = prog.step(StepFeed(tokens), pos, one, prog.init_states())
    tokens[:] = 7.0         # the loop writes the next step's tokens here
    pos[:] = 5.0
    assert np.array_equal(pending.read(), want)


def test_a_wrapper_that_rewrites_the_ids_is_served_and_fed():
    """``step`` wrapped from outside by something that reads the ids and
    changes one (the benchmark's own fault test does): the changed id is
    what the request is given and what the next step is fed."""
    step, params, info = _lstm_step()
    ref = StepProgram(step, params, {}, info, num_slots=1)
    want = greedy_decode(ref, [1], 6, max_len=MAX_LEN).tolist()
    eng, rep = _manual(num_slots=1)
    inner = rep.program.step
    calls = []

    def altering(tokens, pos, valid, states, reset=None):
        sampled, new_states = inner(tokens, pos, valid, states, reset=reset)
        calls.append(1)
        if len(calls) == 3:
            sampled = np.array(sampled)
            sampled[0] = (sampled[0] + 1) % 16
        return sampled, new_states
    rep.program.step = altering
    fut = eng.submit([1], max_new_tokens=6)
    _run_dry(eng, rep)
    got = fut.result(timeout=0).tokens.tolist()
    moved = (want[2] + 1) % 16
    # served, and the steps after it went on from the id it was given
    assert got == want[:2] + [moved] + greedy_decode(
        ref, [1] + want[:2] + [moved], 3, max_len=MAX_LEN).tolist()
    eng.close()


# ---------------------------------------------------------------------------
# (a) same answers as greedy_decode, whatever ends a request
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("with_eos", [False, True], ids=["no_eos", "eos"])
@pytest.mark.parametrize("builder", [_lstm_step, _attn_step],
                         ids=["lstm", "attention"])
def test_mixed_pool_equals_greedy_decode(builder, with_eos):
    """Prompts of 1-9 tokens, 1-12 new tokens, three times as many
    requests as slots, joins and leaves every step."""
    step, params, info = builder()
    ref = StepProgram(step, params, {}, info, num_slots=1)
    rng = np.random.default_rng(7)
    reqs = [([int(t) for t in rng.integers(0, 16, rng.integers(1, 10))],
             int(rng.integers(1, 13))) for _ in range(12)]
    eos = None
    if with_eos:
        # the id that ends nearest to half of the requests early
        outs = [greedy_decode(ref, p, m, max_len=MAX_LEN).tolist()
                for p, m in reqs]
        eos = min(range(16), key=lambda t: abs(
            sum(t in o[:-1] for o in outs) - len(outs) / 2.0))
    eng = DecodeEngine(step, params, {}, info, num_slots=4,
                       max_len=MAX_LEN, eos_id=eos, default_deadline_ms=0)
    c0 = eng.warmup()
    seen = [[] for _ in reqs]
    futs = []
    for i, (p, m) in enumerate(reqs):
        futs.append(eng.submit(p, max_new_tokens=m,
                               on_token=seen[i].append))
        if i % 4 == 3:
            time.sleep(0.003)
    res = [f.result(timeout=120) for f in futs]
    st = eng.stats()["decode"]
    eng.close()
    reasons = set()
    for (p, m), r, got in zip(reqs, res, seen):
        toks, reason = _want(ref, p, min(m, MAX_LEN - len(p)), eos)
        assert r.tokens.tolist() == toks == got, (p, m)
        assert r.finish_reason == reason
        # a step whose id was thrown away is not one of the request's
        assert r.n_steps == len(p) + len(toks) - 1
        reasons.add(reason)
    assert reasons == ({"eos", "length"} if with_eos else {"length"})
    assert st["tokens_generated"] == sum(len(r.tokens) for r in res)
    assert st["steps_ahead"] > 0 and eng.compile_count == c0
    if not with_eos:
        assert st["slot_steps_discarded"] == 0


def _spec_pair():
    tstep, tparams, tinfo = _attn_step(seed=0)
    dstep, dparams, dinfo = _attn_step(seed=1)
    for i in tinfo + dinfo:
        i["cache"] = True
    return (tstep, tparams, tinfo), dict(
        draft_sym=dstep, draft_arg_params=dparams, draft_state_info=dinfo,
        spec_k=2)


@pytest.mark.parametrize("speculative", [False, True],
                         ids=["plain", "speculative"])
def test_a_step_is_booked_before_its_futures_resolve(speculative):
    """A future's done-callback runs in the scheduler's thread, at the
    resolve: ``stats()`` read there counts every token delivered so far,
    the resolving request's last one among them.  (A client woken by its
    last token reads ``stats()`` at once, on its own thread.)"""
    if speculative:
        (step, params, info), kw = _spec_pair()
        eng, rep = _manual(lambda: (step, params, info), num_slots=3, **kw)
    else:
        eng, rep = _manual(num_slots=3)
    delivered, at_resolve = [], []

    def resolved(fut):
        at_resolve.append((eng.stats()["decode"]["tokens_generated"],
                           len(delivered), len(fut.result().tokens)))
    for p, m in (([1, 2, 3], 4), ([5], 2), ([7, 7], 5), ([9], 3)):
        eng.submit(p, max_new_tokens=m, on_token=delivered.append) \
            .add_done_callback(resolved)
    _run_dry(eng, rep)
    eng.close()
    assert sorted(own for _b, _d, own in at_resolve) == [2, 3, 4, 5]
    for booked, so_far, own in at_resolve:
        assert booked == so_far >= own


def test_clients_on_many_threads_get_greedy_answers():
    """Submits, callbacks and the scheduler's two walks interleave at a
    short switch interval: every request still gets ``greedy_decode``'s
    tokens in order, and every delivered token is counted once."""
    import sys
    import threading
    step, params, info = _lstm_step()
    ref = StepProgram(step, params, {}, info, num_slots=1)
    eng = DecodeEngine(step, params, {}, info, num_slots=3,
                       max_len=MAX_LEN, max_queue=256,
                       default_deadline_ms=0)
    eng.warmup()
    results = {}

    def client(k):
        rng = np.random.default_rng(k)
        for j in range(8):
            p = [int(t) for t in rng.integers(0, 16, rng.integers(1, 5))]
            m = int(rng.integers(1, 9))
            seen = []
            r = eng.submit(p, max_new_tokens=m,
                           on_token=seen.append).result(timeout=120)
            results[k, j] = (p, m, r, seen)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=client, args=(k,))
                   for k in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=240)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    st = eng.stats()["decode"]
    eng.close()
    assert len(results) == 48
    for p, m, r, seen in results.values():
        assert r.tokens.tolist() == _want(ref, p, m)[0] == seen
    assert st["tokens_generated"] == sum(
        len(r.tokens) for _p, _m, r, _s in results.values())
    assert st["slot_steps_discarded"] == 0 and st["steps_ahead"] > 0


# ---------------------------------------------------------------------------
# (b) what a finish costs
# ---------------------------------------------------------------------------

def test_a_finish_by_length_costs_no_slot_step():
    eng, rep = _manual(num_slots=3)
    ref = _reference()
    futs = [eng.submit([1, 2, 3], max_new_tokens=4),
            eng.submit([5], max_new_tokens=1),
            eng.submit([7, 7], max_new_tokens=MAX_LEN)]   # by max_len
    _run_dry(eng, rep)
    st = eng.stats()["decode"]
    for (p, m), f in zip([([1, 2, 3], 4), ([5], 1), ([7, 7], MAX_LEN - 2)],
                         futs):
        r = f.result(timeout=0)
        assert r.finish_reason == "length"
        assert r.tokens.tolist() == _want(ref, p, m)[0]
    assert st["slot_steps_discarded"] == 0
    # as many dispatches as the longest request has positions: the step
    # after a request's last is never made for it
    assert st["steps"] == MAX_LEN - 1
    assert st["steps_ahead"] == st["steps"] - 1
    eng.close()


def test_an_eos_discards_the_one_slot_step_in_flight():
    ref = _reference()
    want = greedy_decode(ref, [1], 8, max_len=MAX_LEN).tolist()
    eos = want[2]
    eng, rep = _manual(eos_id=eos)
    seen = []
    fut = eng.submit([1], max_new_tokens=8, on_token=seen.append)
    _run_dry(eng, rep)
    r = fut.result(timeout=0)
    st = eng.stats()["decode"]
    assert r.finish_reason == "eos" and r.tokens.tolist() == want[:3] == seen
    assert st["slot_steps_discarded"] == 1
    assert st["tokens_generated"] == 3 and st["steps"] == 4
    assert r.n_steps == 3
    eng.close()


def test_a_deadline_discards_the_one_slot_step_in_flight():
    ref = _reference()
    eng, rep = _manual()
    seen = []
    fut = eng.submit([1], max_new_tokens=12, deadline_ms=600000,
                     on_token=seen.append)
    for _ in range(4):
        _tick(eng, rep)
    req = rep.slots[0]
    assert rep.flight is not None and req.n_ahead == 1
    req.deadline = time.monotonic() - 1.0       # it passes now
    _tick(eng, rep)             # evicts, reads the step in flight
    r = fut.result(timeout=0)
    st = eng.stats()["decode"]
    assert r.finish_reason == "deadline" and r.expired
    assert r.tokens.tolist() == seen == _want(ref, [1], 3)[0]
    assert st["slot_steps_discarded"] == 1 and st["evictions"] == 1
    assert st["tokens_generated"] == 3
    assert rep.flight is None and not rep.occupied_count()
    eng.close()


def test_a_deadline_does_not_cut_an_answer_whose_last_token_is_in_flight():
    ref = _reference()
    eng, rep = _manual()
    fut = eng.submit([1], max_new_tokens=3, deadline_ms=600000)
    for _ in range(3):
        _tick(eng, rep)
    req = rep.slots[0]
    # the third token is in flight and the slot already dead
    assert len(req.tokens) == 2 and req.n_ahead == 1 \
        and not rep.valid_np[0]
    req.deadline = time.monotonic() - 1.0
    _tick(eng, rep)
    r = fut.result(timeout=0)
    assert r.finish_reason == "length"
    assert r.tokens.tolist() == _want(ref, [1], 3)[0]
    st = eng.stats()["decode"]
    assert st["slot_steps_discarded"] == 0 and st["evictions"] == 0
    eng.close()


def test_a_raising_callback_discards_the_one_slot_step_in_flight():
    ref = _reference()
    eng, rep = _manual()
    seen = []

    def on_token(tok):
        seen.append(tok)
        if len(seen) == 3:
            raise ValueError("the caller hung up")
    doomed = eng.submit([1], max_new_tokens=12, on_token=on_token)
    other = eng.submit([2, 3], max_new_tokens=6)
    _run_dry(eng, rep)
    with pytest.raises(ValueError):
        doomed.result(timeout=0)
    assert seen == _want(ref, [1], 3)[0]
    assert other.result(timeout=0).tokens.tolist() \
        == _want(ref, [2, 3], 6)[0]
    st = eng.stats()["decode"]
    assert st["slot_steps_discarded"] == 1
    assert st["tokens_generated"] == 3 + 6
    eng.close()


# ---------------------------------------------------------------------------
# (c) a slot seated anew while a step is in flight
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("builder", [_lstm_step, _attn_step],
                         ids=["lstm", "attention"])
def test_a_reseated_slot_never_gets_the_old_occupants_id(builder):
    ref = _reference(builder)
    want_a = greedy_decode(ref, [1], 8, max_len=MAX_LEN).tolist()
    eos = want_a[1]
    eng, rep = _manual(builder, num_slots=1, eos_id=eos)
    seen_a, seen_b = [], []
    a = eng.submit([1], max_new_tokens=8, on_token=seen_a.append)
    prompt_b = [t for t in range(16) if t != eos][:3]
    b = eng.submit(prompt_b, max_new_tokens=5, on_token=seen_b.append)
    while not a.done():
        _tick(eng, rep)
    # A ended on the eos id, seen one step late: the step in flight
    # still holds A in slot 0, valid
    assert rep.flight is not None and not rep.occupied_count()
    old = rep.flight[1]
    assert [(i, r.prompt) for i, r, _k in old] == [(0, [1])]
    _tick(eng, rep)             # seats B in slot 0, dispatches, reads A's
    assert rep.slots[0] is not None and rep.slots[0].prompt == prompt_b
    assert eng.stats()["decode"]["slot_steps_discarded"] == 1
    assert seen_b == []         # B is still being fed its prompt
    _run_dry(eng, rep)
    want_b, reason_b = _want(ref, prompt_b, 5, eos)
    assert a.result(timeout=0).tokens.tolist() == want_a[:2] == seen_a
    rb = b.result(timeout=0)
    assert rb.tokens.tolist() == want_b == seen_b
    assert rb.finish_reason == reason_b
    eng.close()


def test_a_prefilled_join_behind_a_step_in_flight():
    """A prefill is enqueued behind the step in flight and its first
    token is a host value for its slot; the requests around it are fed
    on the device."""
    step, prefill, params, info = _sum_state_model()
    eng = DecodeEngine(step, params, {}, info, num_slots=2, max_len=MAX_LEN,
                       default_deadline_ms=0, prefill_sym=prefill,
                       start=False)
    c0 = eng.warmup()
    rep = eng._replicas[0]
    ref = StepProgram(step, params, {}, info, num_slots=1)
    a = eng.submit([1, 2], max_new_tokens=9)
    for _ in range(3):
        _tick(eng, rep)
    assert rep.flight is not None
    b = eng.submit([3, 4, 5], max_new_tokens=4)
    _run_dry(eng, rep)
    assert a.result(timeout=0).tokens.tolist() == _want(ref, [1, 2], 9)[0]
    assert b.result(timeout=0).tokens.tolist() \
        == _want(ref, [3, 4, 5], 4)[0]
    assert eng.compile_count == c0
    assert eng.stats()["decode"]["slot_steps_discarded"] == 0
    eng.close()


# ---------------------------------------------------------------------------
# (d) nothing is left unread
# ---------------------------------------------------------------------------

def _settled(eng):
    return all(r.flight is None for r in eng._replicas)


def test_close_with_drain_reads_every_step():
    step, params, info = _lstm_step()
    ref = StepProgram(step, params, {}, info, num_slots=1)
    eng = DecodeEngine(step, params, {}, info, num_slots=2,
                       max_len=MAX_LEN, default_deadline_ms=0)
    eng.warmup()
    futs = [eng.submit([i], max_new_tokens=5) for i in range(5)]
    eng.close(drain=True)
    assert _settled(eng)
    for i, f in enumerate(futs):
        assert f.result(timeout=0).tokens.tolist() == _want(ref, [i], 5)[0]


def test_close_without_drain_delivers_the_step_in_flight():
    step, params, info = _lstm_step()
    ref = StepProgram(step, params, {}, info, num_slots=1)
    eng = DecodeEngine(step, params, {}, info, num_slots=2,
                       max_len=200000, default_deadline_ms=0)
    eng.warmup()
    fut = eng.submit([1], max_new_tokens=150000)
    while eng.stats()["decode"]["steps"] < 5:
        time.sleep(0.002)
    eng.close(drain=False)
    r = fut.result(timeout=30)
    assert _settled(eng) and r.finish_reason == "closed" and len(r) > 0
    assert r.tokens.tolist() == _want(ref, [1], len(r), max_len=200000)[0]
    st = eng.stats()["decode"]
    # every dispatched step was read, and its token delivered
    assert st["tokens_generated"] == len(r) == st["steps"]
    assert st["slot_steps_discarded"] == 0


def test_a_raising_step_fails_the_pool_and_leaves_nothing_in_flight():
    step, params, info = _lstm_step()
    ref = StepProgram(step, params, {}, info, num_slots=1)
    eng = DecodeEngine(step, params, {}, info, num_slots=2,
                       max_len=MAX_LEN, default_deadline_ms=0, start=False)
    eng.warmup()
    faults.install("decode.step:raise:on=4")
    doomed = [eng.submit([1], max_new_tokens=10),
              eng.submit([2, 3], max_new_tokens=10)]
    eng.start()
    for f in doomed:
        with pytest.raises(faults.FaultInjected):
            f.result(timeout=120)
    # the loop goes on, host-fed again from a fresh pool
    after = eng.submit([4], max_new_tokens=6).result(timeout=120)
    assert after.tokens.tolist() == _want(ref, [4], 6)[0]
    eng.close()
    assert _settled(eng)


def test_a_failed_replica_settles_and_rehabilitates():
    step, params, info = _lstm_step()
    ref = StepProgram(step, params, {}, info, num_slots=1)
    eng = DecodeEngine(step, params, {}, info, num_slots=2,
                       max_len=32, default_deadline_ms=0,
                       ctx=[mx.cpu(0), mx.cpu(0)], start=False)
    c0 = eng.warmup()
    faults.install("decode.step:raise:on=5,replica=0")
    prompts = [[1], [2], [3], [4]]
    futs = [eng.submit(p, max_new_tokens=12) for p in prompts]
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        eng.start()
        res = [f.result(timeout=120) for f in futs]
        assert [r.healthy for r in eng._replicas] == [False, True]
        assert _settled(eng)
        hit = [r for r in res if r.finish_reason == "error"]
        assert hit
        for p, r in zip(prompts, res):
            want = _want(ref, p, 12, max_len=32)[0]
            if r.finish_reason == "error":
                # the step in flight when the next one failed was read:
                # a prefix, and as long as the dispatches that succeeded
                assert r.tokens.tolist() == want[:len(r)] and len(r) > 0
            else:
                assert r.tokens.tolist() == want
        out = eng.rehabilitate()
        assert [o["ok"] for o in out] == [True]
    assert _settled(eng)
    again = [eng.submit(p, max_new_tokens=6) for p in prompts]
    for p, f in zip(prompts, again):
        assert f.result(timeout=120).tokens.tolist() \
            == _want(ref, p, 6, max_len=32)[0]
    assert eng.compile_count >= c0
    eng.close()
    assert _settled(eng)


# ---------------------------------------------------------------------------
# (e) the one fork: a speculative step is read where it is dispatched
# ---------------------------------------------------------------------------

def _step_events(base):
    return [e for e in timeline.get().events()
            if e["seq"] > base and e["name"] == "decode.step"]


def test_ahead_is_zero_on_a_speculative_engine_and_not_on_a_plain_one():
    telemetry.set_enabled(True)
    try:
        (tstep, tparams, tinfo), draft = _spec_pair()
        spec = DecodeEngine(tstep, tparams, {}, tinfo, num_slots=2,
                            max_len=MAX_LEN, default_deadline_ms=0, **draft)
        plain = DecodeEngine(tstep, tparams, {}, tinfo, num_slots=2,
                             max_len=MAX_LEN, default_deadline_ms=0)
        got = {}
        for name, eng in (("spec", spec), ("plain", plain)):
            eng.warmup()
            base = timeline.get().appended()
            res = [f.result(timeout=120) for f in
                   [eng.submit(p, max_new_tokens=8)
                    for p in ([1, 2], [3], [4, 5, 6])]]
            got[name] = ([r.tokens.tolist() for r in res],
                         eng.stats()["decode"], _step_events(base))
            assert all(r.flight is None for r in eng._replicas)
            eng.close()
    finally:
        telemetry.set_enabled(None)
    toks_s, st_s, evs_s = got["spec"]
    toks_p, st_p, evs_p = got["plain"]
    assert toks_s == toks_p                     # greedy either way
    assert st_s["steps_ahead"] == 0 and st_s["slot_steps_discarded"] == 0
    assert evs_s and all(e["args"]["ahead"] == 0
                         and e["args"]["discarded"] == 0
                         and e["args"]["read_ms"] > 0 for e in evs_s)
    assert st_p["steps_ahead"] > 0
    assert sum(e["args"]["ahead"] for e in evs_p) == st_p["steps_ahead"]
    assert len(evs_p) == st_p["steps"]


# ---------------------------------------------------------------------------
# (f) one compiled program for the host-fed and the fed-back step
# ---------------------------------------------------------------------------

def test_no_compile_after_warmup_over_host_fed_and_fed_back_steps():
    import jax
    step, params, info = _lstm_step()
    eng, rep = _manual(num_slots=3)
    c0 = eng.compile_count
    compiles = []

    def listener(name, _secs, **_kw):
        if "backend_compile" in name:
            compiles.append(name)
    jax.monitoring.register_event_duration_secs_listener(listener)
    # a burst from an idle pool (host-fed first step), prompts fed by
    # the host beside slots fed on the device, the pool run dry, and a
    # second burst onto the stepped pool
    for burst in ([([1, 2, 3, 4], 5), ([5], 9), ([6, 7], 2)],
                  [([8], 3), ([9, 10, 11], 6)]):
        futs = [eng.submit(p, max_new_tokens=m) for p, m in burst]
        _run_dry(eng, rep)
        assert all(f.done() for f in futs)
    st = eng.stats()["decode"]
    assert st["steps_ahead"] > 0 and st["steps"] > st["steps_ahead"]
    assert eng.compile_count == c0 == rep.program.trace_count
    # XLA built nothing either (a program built anew for another
    # placement of an argument shows here and not as a trace)
    assert compiles == []
    jax.jit(lambda x: x * 3.0 + 1.0)(np.float32(2.0))
    assert compiles, "the listener sees a compile"
    from jax._src import monitoring
    monitoring.unregister_event_duration_listener(listener)
    eng.close()

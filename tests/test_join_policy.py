"""When a join waits (ISSUE 33): ``serving/join_policy.py`` and its two
callers, ``DecodeEngine._single_run`` and ``_decode_replica_run``.

The decision is a pure function of what the engine has observed, so
nothing here times anything: a cost table is filled through the seam
the engine's own dispatches use (``_prefill_observed``), the step time
is set, and the clock is kept from talking over them (``_observed``).
The six properties of the issue, one test each: a table dominated by a
fixed cost holds for several; a table linear in the batch seats at
once; nothing decoding, nothing held; no prefill program, never held;
a hold is bounded by the policy's own expectation; coalescing off
never holds.  Then the engine: staggered endings on an attention model
take fewer prefill dispatches and serve ``greedy_decode``'s tokens bit
for bit with the compile count pinned; a held request's deadline
expires in the admission queue and a cancelled one never takes a slot;
the replica loop decides the same from ``rep.pending``.
"""
import math
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.serving import DecodeEngine, StepProgram, greedy_decode
from mxnet_tpu.serving import join_policy
from mxnet_tpu.serving.join_policy import (JoinState, cost_table,
                                           dispatch_cost, seats_now)

from test_decode import _lstm_step, _sum_state_model  # noqa: E402

BATCHES = (1, 2, 4, 8, 16, 32)
# a dispatch that costs 19 ms whatever it holds and 11 ms a prompt, under
# steps of 12.5 ms over 256 slots with a request ending every 1.5 steps
FIXED = {n: 0.019 + 0.011 * n for n in BATCHES}
# a dispatch that costs by its rows, under 32 slots and rare endings
BY_ROWS = {1: 0.1718, 2: 0.331}


# ---------------------------------------------------------------------------
# the decision
# ---------------------------------------------------------------------------

def test_a_fixed_cost_holds_for_several():
    """Property 6's other half: where one prompt costs about what two
    do, a lone seatable request waits, and the batch the policy gathers
    is seated whole when it is there."""
    assert seats_now(1, 256, 0.0125, 0.75, FIXED, 0) == 0
    assert seats_now(7, 256, 0.0125, 0.75, FIXED, 5) == 0
    assert seats_now(16, 256, 0.0125, 0.75, FIXED, 12) == 16
    # one over a warm extent is cut back to it: 17 would ride the
    # program of 32, half of it padding
    assert seats_now(17, 256, 0.0125, 0.75, FIXED, 12) == 16
    assert dispatch_cost(FIXED, 40) == FIXED[32] + FIXED[8]


@pytest.mark.parametrize("w", [1, 2, 3])
def test_a_cost_by_the_rows_seats_at_once(w):
    """Property 6: ``c(2n)`` about ``2 c(n)``, nothing to save by
    waiting for anyone: whoever is there is seated, whatever the age."""
    assert seats_now(w, 32, 0.0141, 0.057, BY_ROWS, 0) == w
    linear = {n: 0.011 * n for n in BATCHES}
    # a padded row costs what a prompt does: three go as two and one
    want = {1: 1, 2: 2, 3: 2}[w]
    assert seats_now(w, 256, 0.0125, 0.75, linear, 0) == want
    assert seats_now(w - want + 1, 256, 0.0125, 0.75, linear, 1) \
        == w - want + 1


@pytest.mark.parametrize("w", [1, 5, 300])
def test_nothing_decoding_nothing_held(w):
    """Property 3: the ramp, the warm pool, an idle engine."""
    assert seats_now(w, 0, 0.0125, 0.75, FIXED, 0) == w


def test_a_join_that_rides_the_step_is_never_held():
    """Property 4, the decision's half: no dispatch, no cost."""
    assert seats_now(3, 256, 0.0125, 0.75, None, 0) == 3
    assert seats_now(3, 256, 0.0125, 0.75, {}, 0) == 3
    # nor is anything held before a step has been timed
    assert seats_now(3, 256, None, 0.75, FIXED, 0) == 3


def test_a_hold_is_bounded_by_its_own_expectation():
    """Property 5: a queue shorter than the batch hoped for is seated
    when the steps the policy expected to need have passed, counted by
    ``JoinState`` and not by a clock."""
    st = JoinState()
    st.rate = 0.75                  # what the engine had observed
    target = 16
    held = 0
    while True:
        st.seatable(1)              # one request, and no other comes
        n = seats_now(1, 256, 0.0125, 0.75, FIXED, st.held_steps)
        st.seated(n, 1)
        if n:
            break
        held += 1
        assert st.held == 1
    assert held == math.ceil((target - 1) / 0.75) == 20
    assert st.held == 0 and st.held_steps == 0
    # the observed rate fell meanwhile, as a drain's does
    assert st.rate < 0.75 * (31.0 / 32.0) ** 19


def test_coalescing_off_never_holds():
    """Property 1: ``MXNET_DECODE_COALESCE_PREFILL=0`` leaves batch 1 as
    the only warm batch, and nothing is worth waiting for."""
    for w in (1, 2, 9):
        assert seats_now(w, 256, 0.0125, 0.75, {1: 0.030}, 0) == w


def test_cost_table_fills_what_was_not_timed():
    assert cost_table({}, BATCHES) is None and cost_table(None, (1,)) is None
    # one reading says nothing of a fixed part: cost by the rows
    one = cost_table({32: 0.375}, BATCHES)
    assert one[1] == pytest.approx(0.375 / 32) and one[32] == 0.375
    assert seats_now(1, 256, 0.0125, 0.75, one, 0) == 1
    assert cost_table({1: 0.030}, BATCHES)[8] == pytest.approx(0.24)
    # what was timed keeps its reading; a batch between takes the rows
    # of the cheapest larger one, and no less than the next smaller:
    # the least it can cost, so a hold tries it and then it is known
    two = cost_table({1: 0.030, 32: 0.375}, BATCHES)
    assert two[1] == 0.030 and two[32] == 0.375
    assert two[2] == 0.030 and two[16] == pytest.approx(0.1875)
    assert seats_now(1, 256, 0.0125, 0.75, two, 0) == 0
    # the chip's table with 8 and 16 not timed yet: 8 is worth trying
    # (4 costs 12.5 ms a prompt, 32 costs 11.2), so the hold goes on
    lfm2 = cost_table({1: 0.0277, 2: 0.0338, 4: 0.0500, 32: 0.358}, BATCHES)
    assert lfm2[8] == pytest.approx(8 * 0.358 / 32)
    assert seats_now(4, 250, 0.0193, 0.85, lfm2, 3) == 0
    # timed, 8 stays the batch and 16 is not worth its empty seats
    lfm2.update({8: 0.0897, 16: 0.181})
    assert seats_now(4, 250, 0.0193, 0.85, lfm2, 3) == 0
    assert seats_now(8, 250, 0.0193, 0.85, lfm2, 6) == 8
    # a reading that held a pause of the host: two dispatches of 2
    # would do a dispatch of 4's work for 67 ms, so 118 is not its cost,
    # and 8 is still worth trying
    paused = cost_table({1: 0.0275, 2: 0.0334, 4: 0.1179, 32: 0.3578},
                        BATCHES)
    assert paused[4] == pytest.approx(0.0668)
    assert paused[8] == pytest.approx(8 * 0.3578 / 32)
    # past the largest timed batch: by its rows (and two singles would
    # do a pair's work for 20 ms)
    assert cost_table({1: 0.01, 2: 0.03}, (1, 2, 4))[4] == pytest.approx(0.04)
    assert cost_table({1: 0.01, 2: 0.015}, (1, 2, 4))[4] == pytest.approx(0.03)


def test_join_state_counts_in_steps():
    """The rate is what became seatable a step; the age is the oldest
    held request's; a request that leaves its queue unseated (deadline,
    cancel) is dropped from the newest."""
    st = JoinState()
    st.seatable(2)
    st.seated(0, 2)
    assert st.rate == pytest.approx(2 / 32.0) and st.held == 2
    st.seatable(3)
    st.seated(0, 3)
    assert st.held_steps == 1
    st.seatable(2)                  # one of them expired in the queue
    st.seated(1, 2)                 # the oldest is seated
    assert st.held == 1 and st.held_steps == 2
    st.idle()
    assert st.held == 0 and st.held_steps == 0
    # a step's time: reads with no prefill between them
    st.step_read(10.0, True)
    st.step_read(10.5, True)
    assert st.step_s == 0.5
    st.stalled = True               # a prefill ran: not a step's time
    st.step_read(11.5, True)
    assert st.step_s == 0.5 and st.in_flight_left(11.6) == pytest.approx(0.4)


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

def _observed(eng, monkeypatch, costs, step_s=1e-3):
    """What the engine has observed is what the test says: ``costs``
    (``{(bucket, batch): seconds}``) go in through the seam every live
    dispatch uses, the step time is set, and the clock says no more."""
    for (bucket, batch), seconds in costs.items():
        eng._prefill_observed(bucket, batch, seconds)
    monkeypatch.setattr(eng, "_prefill_observed", lambda *a: None)
    monkeypatch.setattr(JoinState, "step_time", lambda self, s: None)
    for rep in eng._replicas:
        rep.joins.step_s = step_s


def _fixed(bucket, batches=(1, 2, 4)):
    return {(bucket, n): 1.0 + 1e-3 * n for n in batches}


@pytest.fixture
def decisions(monkeypatch):
    """``(w, live, seated)`` of every decision over a seatable request."""
    calls = []
    inner = join_policy.seats_now

    def spy(w, live, step_s, rate, costs, held_steps):
        n = inner(w, live, step_s, rate, costs, held_steps)
        if w:
            calls.append((w, live, n))
        return n
    monkeypatch.setattr(join_policy, "seats_now", spy)
    return calls


def _wait(cond, timeout=60.0):
    t_end = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < t_end
        time.sleep(0.001)


def _slow(_tok):
    time.sleep(0.002)


def test_no_prefill_program_never_holds(monkeypatch, decisions):
    """Property 4, the engine's half: the LSTM's joins ride the step,
    and whatever the engine had observed a free slot is seated in the
    iteration that finds a request for it."""
    step, params, state_info = _lstm_step()
    eng = DecodeEngine(step, params, {}, state_info, num_slots=4,
                       max_len=64, default_deadline_ms=0)
    try:
        eng.warmup()
        hogs = [eng.submit([1], max_new_tokens=60, on_token=_slow)
                for _ in range(3)]
        _wait(lambda: eng.stats()["decode"]["tokens_generated"] >= 6)
        _observed(eng, monkeypatch, _fixed(8))
        eng._replicas[0].joins.rate = 0.75
        late = eng.submit([2, 3], max_new_tokens=2)
        assert len(late.result(timeout=60).tokens) == 2
        assert not any(f.done() for f in hogs)
        for f in hogs:
            f.result(timeout=60)
        st = eng.stats()["decode"]
        assert st["slot_steps_held"] == 0
        assert sorted(st["prefill_cost_ms"][8]) == [1, 2, 4]
        assert st["prefill_cost_ms"][8][4] == pytest.approx(1004.0)
        assert all(n == w for w, _live, n in decisions)
        assert any(live >= 1 for _w, live, _n in decisions)
    finally:
        eng.close()


def test_coalescing_off_engine_seats_every_ending_at_once(monkeypatch):
    """Property 1 on the engine: with the knob off batch 1 is the only
    warm batch, so the same observations hold nothing."""
    step, prefill, params, state_info = _sum_state_model()
    monkeypatch.setenv("MXNET_DECODE_COALESCE_PREFILL", "0")
    eng = DecodeEngine(step, params, {}, state_info, num_slots=4,
                       max_len=32, prefill_sym=prefill,
                       prefill_buckets=(8,), max_queue=32,
                       default_deadline_ms=0, start=False)
    try:
        eng.warmup()
        _observed(eng, monkeypatch, _fixed(8, (1,)))
        futs = [eng.submit([1 + i], max_new_tokens=3 + 2 * (i % 4))
                for i in range(12)]
        eng.start()
        for f in futs:
            f.result(timeout=120)
        st = eng.stats()["decode"]
        assert st["prefill_dispatches"] == 12 and st["slot_steps_held"] == 0
    finally:
        eng.close()


def _churn(eng, prompts, new_tokens):
    """Everything queued before the scheduler starts, so that who is
    seatable at which step follows from the lengths alone."""
    futs = [eng.submit(p, max_new_tokens=n)
            for p, n in zip(prompts, new_tokens)]
    eng.start()
    return [list(f.result(timeout=300).tokens) for f in futs]


def test_staggered_endings_share_dispatches_bit_for_bit(monkeypatch):
    """Requests that end at different steps on the small attention
    model: where a dispatch has a fixed cost their successors are
    seated several at a time, in fewer prefill dispatches than where it
    costs by its rows, and either way every request is served
    ``greedy_decode``'s tokens with no program compiled after
    ``warmup()``."""
    from test_smallthinker import CFG, MAX_LEN, _engine, _params
    from mxnet_tpu.telemetry import timeline
    params = _params(CFG)
    rng = np.random.default_rng(33)
    prompts = [rng.integers(1, 64, int(n)).tolist()
               for n in rng.integers(9, 31, 16)]
    new_tokens = [3 + 2 * (i % 5) for i in range(16)]
    runs = {}
    for name, costs in (
            ("fixed", {(b, n): 1.0 + 1e-3 * n
                       for b in (16, 32) for n in (1, 2, 4)}),
            ("by_rows", {(b, n): 1e-3 * n
                         for b in (16, 32) for n in (1, 2, 4)})):
        eng, step, info = _engine(params, prefill_buckets=(16, 32),
                                  start=False, max_queue=32)
        try:
            warm = eng.warmup()
            _observed(eng, monkeypatch, costs)
            t0 = time.perf_counter()
            served = _churn(eng, prompts, new_tokens)
            st = eng.stats()["decode"]
            assert eng.compile_count == warm and st["joins"] == 16
            evs = [e["args"] for e in timeline.peek().events()
                   if e["mono"] >= t0 and e["name"] == "decode.prefill"]
            steps = [e["args"] for e in timeline.peek().events()
                     if e["mono"] >= t0 and e["name"] == "decode.step"]
            assert sum(e["group"] for e in evs) == 16
            assert sum(s["held"] for s in steps) <= st["slot_steps_held"]
            runs[name] = (served, st, evs)
        finally:
            eng.close()
    prog = StepProgram(step, {k: mx.nd.array(v) for k, v in params.items()},
                       {}, info, 1)
    want = [list(greedy_decode(prog, p, n, max_len=MAX_LEN))
            for p, n in zip(prompts, new_tokens)]
    assert runs["fixed"][0] == want and runs["by_rows"][0] == want
    fixed, by_rows = runs["fixed"][1], runs["by_rows"][1]
    assert fixed["prefill_dispatches"] < by_rows["prefill_dispatches"]
    assert fixed["slot_steps_held"] > 0 == by_rows["slot_steps_held"]
    # ``live``: the slots a dispatch stopped; the first join met none
    assert runs["fixed"][2][0]["live"] == 0
    assert any(e["live"] > 0 for e in runs["by_rows"][2])


def _held_engine(monkeypatch, new_tokens, **kw):
    """Three long answers decoding in four slots, and then a table
    under which the fourth slot's request is held."""
    step, prefill, params, state_info = _sum_state_model()
    eng = DecodeEngine(step, params, {}, state_info, num_slots=4,
                       max_len=512, prefill_sym=prefill,
                       prefill_buckets=(8,), max_queue=32,
                       default_deadline_ms=0, **kw)
    eng.warmup()
    hogs = [eng.submit([1 + i], max_new_tokens=new_tokens, on_token=_slow)
            for i in range(3)]
    _wait(lambda: all(r is not None and len(r.tokens) > 1
                      for r in eng._replicas[0].slots[:3]))
    _observed(eng, monkeypatch, _fixed(8))
    return eng, hogs


def test_a_held_request_expires_in_the_admission_queue(monkeypatch):
    """Property 2: a held request is a queued one.  Its deadline is
    swept where it waits, and it never took a slot."""
    eng, hogs = _held_engine(monkeypatch, 400)
    try:
        late = eng.submit([5, 6], max_new_tokens=4, deadline_ms=150)
        res = late.result(timeout=60)       # long before the hogs end
        assert res.expired and len(res) == 0
        st = eng.stats()
        assert st["expired"] == 1 and st["decode"]["joins"] == 3
        assert st["decode"]["slot_steps_held"] > 0
        assert not any(f.done() for f in hogs)
    finally:
        eng.close(drain=False)


def test_a_request_cancelled_while_held_never_takes_a_slot(monkeypatch):
    eng, hogs = _held_engine(monkeypatch, 150)
    try:
        late = eng.submit([5, 6], max_new_tokens=4)
        _wait(lambda: eng.stats()["decode"]["slot_steps_held"] > 0)
        assert eng.stats()["queue_depth"] == 1      # back-pressure sees it
        assert late.cancel()
        after = eng.submit([7], max_new_tokens=2)
        assert len(after.result(timeout=120).tokens) == 2
        for f in hogs:
            assert len(f.result(timeout=120).tokens) == 150
        st = eng.stats()["decode"]
        assert st["joins"] == 4 and late.cancelled()
    finally:
        eng.close(drain=False)


def test_replica_loop_decides_the_same_from_pending(monkeypatch, decisions):
    """``_decode_replica_run`` hands ``seats_now`` what it observes of
    its own pool and its own routed queue, and a request it leaves
    stays in ``rep.pending`` until the hold ends; then it is served
    ``greedy_decode``'s tokens."""
    step, prefill, params, state_info = _sum_state_model()
    eng = DecodeEngine(step, params, {}, state_info, num_slots=2,
                       max_len=128, prefill_sym=prefill,
                       prefill_buckets=(8,), max_queue=32,
                       default_deadline_ms=0,
                       ctx=[mx.cpu(0), mx.cpu(0)])
    try:
        eng.warmup()
        hogs = [eng.submit([1 + i], max_new_tokens=60, on_token=_slow)
                for i in range(3)]
        _wait(lambda: sum(1 for r in eng._replicas for q in r.slots
                          if q is not None and len(q.tokens) > 1) == 3)
        _observed(eng, monkeypatch, _fixed(8, (1, 2)))
        del decisions[:]
        late = eng.submit([5, 6, 7], max_new_tokens=4)
        seen_pending = False
        while not late.done():
            seen_pending |= any(len(r.pending) == 1 and r.joins.held == 1
                                for r in eng._replicas)
            time.sleep(0.001)
        assert seen_pending
        assert (1, 1, 0) in decisions   # one waiting, one decoding: held
        assert eng.stats()["decode"]["slot_steps_held"] > 0
        ref = StepProgram(step, params, {}, state_info, num_slots=1)
        assert np.array_equal(late.result().tokens,
                              greedy_decode(ref, [5, 6, 7], 4, max_len=128))
        for f in hogs:
            assert len(f.result(timeout=120).tokens) == 60
    finally:
        eng.close()


def test_a_bucket_outside_the_warm_grid_is_decided_on_the_ladder():
    """A prefill bucket the warm grid does not hold (buckets swapped on a
    running engine) is decided over the engine's batch ladder, the one
    ``_prefill_group`` sizes its dispatch from: a joiner that arrives
    while a slot decodes is dispatched, and the decision fails nobody."""
    step, params, state_info = _lstm_step()
    eng = DecodeEngine(step, params, {}, state_info, num_slots=2,
                       max_len=64, default_deadline_ms=0)

    class _Boom(object):
        compile_count = 0

        def dispatch(self, feeds):
            raise RuntimeError("prefill boom")
    try:
        eng.warmup()
        hog = eng.submit([1], max_new_tokens=40,
                         on_token=lambda _tok: time.sleep(0.01))
        _wait(lambda: eng.stats()["decode"]["tokens_generated"] >= 2)
        assert 64 not in eng._prefill_grid
        eng._prefill_buckets = (64,)
        eng._prefill_caches = {64: _Boom()}
        bad = eng.submit([2], max_new_tokens=3)
        with pytest.raises(RuntimeError, match="prefill boom"):
            bad.result(timeout=60)
        eng._prefill_buckets = ()
        eng._prefill_caches = {}
        assert len(hog.result(timeout=120)) == 40
    finally:
        eng.close(drain=False)

"""Traffic kind ``serve``: requests through
``serving.DecodeEngine.submit`` with ``on_token``, then the served tokens
against the plain reference.  The traffic file's ``loop`` is ``closed``
(``clients`` callers, each sending its next request when the last one
completes) or ``open`` (arrivals on a schedule at a fixed rate, each
request timed from when it was due).
"""
import gc
import queue
import threading
import time

import numpy as np

from benchmark import harness, traffic_gen

DRAIN_S = 60.0             # a late answer is late, not wrong: wait for it


class _Request(object):
    __slots__ = ("prompt", "new", "due", "sent", "stamps", "tokens",
                 "future", "error", "finish")

    def __init__(self, prompt, new, due):
        self.prompt, self.new, self.due = prompt, new, due
        self.sent = None
        self.stamps, self.tokens = [], []
        self.future = self.error = self.finish = None


def _submit(eng, req, on_done):
    def on_token(tok, _r=req):
        _r.stamps.append(time.perf_counter())
        _r.tokens.append(int(tok))

    def done(fut, _r=req):
        err = fut.exception()
        if err is not None:
            _r.error = repr(err)
        else:
            _r.finish = fut.result().finish_reason
        on_done(_r)

    req.sent = time.perf_counter()
    try:
        req.future = eng.submit(req.prompt, max_new_tokens=req.new,
                                on_token=on_token)
    except Exception as e:      # refused at the door: counted as failed
        req.error = repr(e)
        on_done(req)
        return
    req.future.add_done_callback(done)


def _closed_loop(eng, source, clients, t_end):
    """``clients`` callers, each sending its next request when the last
    one completes, from this one thread; returns every request sent."""
    sent, finished = [], queue.Queue()
    for _ in range(clients):
        finished.put(None)
    while True:
        now = time.perf_counter()
        if now >= t_end:
            break
        try:
            finished.get(timeout=min(0.05, t_end - now))
        except queue.Empty:
            continue
        prompt, new = next(source)
        req = _Request(prompt, new, time.perf_counter())
        sent.append(req)
        _submit(eng, req, finished.put)
    return sent


def _open_loop(eng, source, due_times, t0):
    """Each request sent at its due time, whatever the engine is doing."""
    sent = []
    for due in due_times:
        prompt, new = next(source)
        req = _Request(prompt, new, t0 + due)
        wait = req.due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        sent.append(req)
        _submit(eng, req, lambda _r: None)
    return sent


def _wait_all(requests, limit_s):
    deadline = time.perf_counter() + limit_s
    for r in requests:
        if r.future is not None:
            try:
                r.future.exception(
                    timeout=max(0.0, deadline - time.perf_counter()))
            except Exception:
                r.error = r.error or "no answer %.0f s after the window" \
                    % limit_s


def _sample(requests, seed, k):
    """``k`` finished requests for the reference to replay: the longest,
    and the rest drawn from the seed."""
    done = [r for r in requests if r.finish is not None and r.tokens]
    if not done:
        return []
    done.sort(key=lambda r: -(len(r.prompt) + len(r.tokens)))
    rest = done[1:]
    pick = np.random.default_rng([seed, 3]).permutation(len(rest))[:k - 1]
    return [done[0]] + [rest[i] for i in pick]


def run(env):
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import serving
    from mxnet_tpu.ndarray import NDArray

    cfg, tr, loop = env.cfg, env.traffic, env.traffic["loop"]
    if loop not in ("closed", "open"):
        raise ValueError("unknown loop %r" % (loop,))
    spans, counters = env.spans, env.counters
    ctx = mx.Context(env.platform, 0)
    params = env.cfg_mod.init_params(cfg, env.seed)
    step, state_info = env.cfg_mod.build_step(cfg)
    eng = serving.DecodeEngine(
        step, {k: NDArray(v, ctx=ctx) for k, v in params.items()}, {},
        state_info, ctx=ctx, num_slots=tr["engine"]["num_slots"],
        max_len=tr["engine"]["max_len"],
        max_queue=tr["engine"]["max_queue"])
    slots = eng.num_slots
    program = eng._replicas[0].program
    live = []                          # live slots of every step

    inner_step = program.step

    def counted_step(tokens, pos, valid, states, reset=None):
        live.append((time.perf_counter(), float(valid.sum())))
        with spans.span("program.step (dispatch and read)"):
            return inner_step(tokens, pos, valid, states, reset=reset)
    program.step = counted_step
    spans.wrap(eng, "_step_once", "DecodeEngine._step_once")

    warm_traces = eng.warmup()
    source = traffic_gen.Requests(tr, cfg["vocab_size"], env.seed)
    # joins, leaves and row resets once through, before the window
    warm = [_Request([1, 2, 3, 4], 4, 0.0) for _ in range(slots)]
    for r in warm:
        _submit(eng, r, lambda _r: None)
    _wait_all(warm, 120.0)
    jax.block_until_ready(list(params.values()))

    tracer = harness.Tracer(env.cell, spans) if env.trace else None
    stats0 = eng.stats()
    compiles0 = counters.snapshot()
    t0 = time.perf_counter()
    setup_s = t0 - env.t_process
    t_end = t0 + env.seconds
    timer = None
    if tracer is not None:
        # the traced part starts a second in, from a thread of its own
        def traced():
            time.sleep(min(1.0, env.seconds / 4.0))
            tracer.start()
            time.sleep(min(harness.TRACE_SECONDS, env.seconds / 2.0))
            tracer.stop()
        timer = threading.Thread(target=traced, name="bench-trace")
        timer.start()
    if loop == "closed":
        sent = _closed_loop(eng, source, tr["clients"], t_end)
    else:
        due = traffic_gen.arrival_times(tr, env.seed, env.seconds)
        sent = _open_loop(eng, source, due, t0)
        time.sleep(max(0.0, t_end - time.perf_counter()))
    t1 = time.perf_counter()
    stats1 = eng.stats()
    compiles_in_window = counters.since(compiles0)["requests"]
    if timer is not None:
        timer.join()
    _wait_all(sent, DRAIN_S)
    window_s = t1 - t0

    # ---- end-to-end, over everything the window held
    tokens_in = sum(1 for r in sent for s in r.stamps if s <= t1)
    failed = [r for r in sent if r.error is not None
              or r.finish not in ("length", "eos")]
    failed_ids = {id(r) for r in failed}
    e2e = {"decode_tokens_per_s": tokens_in / window_s}
    worst = DRAIN_S + env.seconds
    ttft = [(r.stamps[0] - r.due) if (r.stamps and id(r) not in failed_ids)
            else worst for r in sent]
    gaps = [b - a for r in sent for a, b in zip(r.stamps, r.stamps[1:])
            if b <= t1]
    if loop == "open":
        e2e["ttft_p95_ms"] = 1e3 * harness.percentile(ttft, 95)
        e2e["itl_p95_ms"] = 1e3 * harness.percentile(gaps, 95)
        # a backlog shows as a first token that comes later and later
        third = max(1, len(ttft) // 3)
        env.log("ttft median by thirds of the window (ms): %s" % ", ".join(
            "%.0f" % (1e3 * harness.percentile(ttft[i:i + third], 50))
            for i in (0, third, 2 * third)))
    env.log("requests sent %d failed %d; ttft samples %d; token gaps %d; "
            "tokens in window %d" % (len(sent), len(failed), len(ttft),
                                     len(gaps), tokens_in))

    d0, d1 = stats0["decode"], stats1["decode"]
    lost = {k: stats1[k] - stats0[k] for k in
            ("rejected", "shed", "pressure_shed", "expired")
            if stats1.get(k, 0) != stats0.get(k, 0)}
    obs = {
        "setup_s": setup_s, "window_s": window_s, "e2e": e2e,
        "attempted": len(sent), "failed": len(failed),
        "compiles_in_window": compiles_in_window,
        "compile_setup": compiles0,
        "counts": {
            "slots": slots, "steps": d1["steps"] - d0["steps"],
            "tokens_generated": d1["tokens_generated"]
            - d0["tokens_generated"],
            "joins": d1["joins"] - d0["joins"],
            "leaves": d1["leaves"] - d0["leaves"],
            "evictions": d1["evictions"] - d0["evictions"],
            "admission_lost": lost, "warmup_traces": warm_traces,
            "retraces": eng.compile_count - warm_traces,
            "live_positions": sum(n for t, n in live if t0 <= t <= t1),
            "steps_seen": sum(1 for t, _n in live if t0 <= t <= t1)},
        "itl_s": gaps,
        "gen_lag_s": [r.sent - r.due for r in sent] if loop == "open"
        else [],
        "window": (t0, t1),
    }
    if tracer is not None:
        lo, hi = tracer.started, tracer.stopped
        in_trace = [n for t, n in live if lo <= t <= hi]
        obs["traced"] = {
            "steps": len(in_trace), "live_positions": sum(in_trace),
            "required": [env.cfg_mod.step_required(cfg, slots, n)
                         for n in in_trace]}
    obs["memory_peak_bytes"] = harness.memory_peak_bytes(env.devices, env.log)

    # ---- the program's state goes, then the reference replays a sample
    limits = env.correct["limits"]
    picked = _sample(sent, env.seed, env.correct["sample_requests"])
    eng.close()
    del eng, program, inner_step, step
    gc.collect()
    checks = harness.Checks()
    if picked:
        t_ref = time.perf_counter()
        got = env.ref_mod.served_gaps(
            params, cfg, [(r.prompt, r.tokens) for r in picked],
            width=tr["engine"]["max_len"])
        env.log("reference replayed %d requests, %d served tokens, in "
                "%.1f s" % (len(picked), got["tokens"],
                            time.perf_counter() - t_ref))
        checks.add("served_token_gap_max", float(np.max(got["gaps"])),
                   limits["served_token_gap_max"])
        if env.calibrate is not None:
            # benchmark/calibrate.py reads the control here, while the
            # weights and the sample of this seed are at hand
            env.calibrate(ref=got, rerun=lambda **kw: env.ref_mod.served_gaps(
                params, cfg, [(r.prompt, r.tokens) for r in picked],
                width=tr["engine"]["max_len"], **kw))
    # every request must have come back whole
    short = sum(1 for r in sent if r.error is None and r.finish == "length"
                and len(r.tokens) != r.new)
    checks.add("requests_unanswered_or_cut", len(failed) + short,
               limits["requests_unanswered_or_cut"])
    checks.add("retraces_after_warmup", obs["counts"]["retraces"],
               limits["retraces_after_warmup"])
    obs["checks"] = checks
    if tracer is not None:
        obs["trace"] = tracer.reduce()
    return obs

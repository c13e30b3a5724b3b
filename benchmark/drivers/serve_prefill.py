"""Traffic kind ``serve_prefill``: what ``serve`` does (its helpers are
imported, not copied) for a model whose prompts are consumed in one
dispatch.  The engine is built with the configuration's prefill graph
and dtype, and is told the prompt buckets this traffic can reach (the
powers of two from its shortest prompt's to its longest's), not the
whole ladder up to ``max_len``.  Beside each traced step's
``step_required`` (from the contexts its live slots held) the traced
prefill dispatches are priced by ``prefill_required`` and timed on the
device, inside the program's own ``mx:decode.prefill`` annotations.
The window is ``serve``'s: ``warmup()``, one pool of 4-token requests,
then the clients start on an empty pool.

The requests are ``traffic_gen``'s quantiles of the stated distributions,
dealt by ``Dealt`` so that every seed holds the same work (a request
that ends brings a prefill dispatch during which no slot decodes, and a
window holds a few dozen requests, not ``traffic_gen.ROUND`` of them).
"""
import gc
import glob
import json
import os
import threading
import time

import numpy as np

from benchmark import harness, traffic_gen, trace_reduce

_serve = harness.load_module("drivers", "serve")
_Request, _submit, _wait_all = _serve._Request, _serve._submit, \
    _serve._wait_all
DRAIN_S = _serve.DRAIN_S
PREFILL_SPAN = "mx:decode.prefill"


def prompt_buckets(traffic):
    """The padded prompt lengths this traffic can reach."""
    def pow2(n):
        return 1 << max(0, int(n) - 1).bit_length()
    lo, hi = (pow2(traffic["prompt_len"][k]) for k in ("min", "max"))
    return [b for b in (1 << i for i in range(32)) if lo <= b <= hi]


class Dealt(object):
    """An endless stream of (prompt ids, max new tokens) in which the
    seed draws the ids and which prompt length meets which request, and
    never how many tokens the n-th request asks for.  ``new_tokens``
    come in rounds of one pool: the distribution's ``num_slots``
    quantiles in bit-reversed order, the same for every seed and every
    round, so that each pool of requests is the stated distribution,
    any 2**k in a row are an even spread of it, and requests end, and
    their successors' prefill dispatches fall, at the same steps
    whatever the seed."""

    def __init__(self, traffic, vocab, seed):
        self._rng = np.random.default_rng([seed, 1])
        self._vocab = vocab
        self._prompt_q = traffic_gen.length_quantiles(traffic["prompt_len"])
        pool = traffic["engine"]["num_slots"]
        new_q = traffic_gen.length_quantiles(traffic["new_tokens"], n=pool)
        bits = (pool - 1).bit_length()
        self._new = [int(new_q[i]) for i in
                     (int(format(j, "0%db" % bits)[::-1], 2)
                      for j in range(1 << bits)) if i < pool]
        self._sent = 0
        self._prompts = iter(())

    def __iter__(self):
        return self

    def __next__(self):
        try:
            plen = next(self._prompts)
        except StopIteration:
            self._prompts = iter(
                self._rng.permutation(self._prompt_q).tolist())
            plen = next(self._prompts)
        new = self._new[self._sent % len(self._new)]
        self._sent += 1
        return self._rng.integers(1, self._vocab, plen).tolist(), new


def compare(got, limits):
    """What one replay of the sample is held to: the widest gap of a
    served token under the reference's best (one grossly wrong token),
    the gaps' 99th percentile (a fault in a slot, or in a few tokens of
    a hundred), and the share of served tokens that are not the
    reference's first choice (a fault spread thin over all of them)."""
    gaps = np.asarray(got["gaps"])
    checks = harness.Checks()
    checks.add("served_token_gap_max", gaps.max(),
               limits["served_token_gap_max"])
    checks.add("served_token_gap_p99", np.percentile(gaps, 99),
               limits["served_token_gap_p99"])
    checks.add("served_off_best_share", np.mean(gaps > 0),
               limits["served_off_best_share"])
    return checks


def device_seconds_inside(trace_dir, name):
    """For each host annotation ``name`` of the newest trace under
    ``trace_dir``, oldest first, the seconds the busiest chip ran
    operations inside it."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        return []
    marks, chips = [], []
    for plane in ProfileData.from_file(files[-1]).planes:
        for line in plane.lines:
            if plane.name.startswith(trace_reduce.DEVICE_PLANE) \
                    and line.name == trace_reduce.OPS_LINE:
                chips.append([(float(e.start_ns),
                               float(e.start_ns + e.duration_ns))
                              for e in line.events])
            elif plane.name.startswith("/host:"):
                marks += [(float(e.start_ns),
                           float(e.start_ns + e.duration_ns))
                          for e in line.events if e.name == name]
    return [max([trace_reduce.covered(
        [(max(a, lo), min(b, hi)) for a, b in ops if b > lo and a < hi])
        for ops in chips] or [0.0]) / 1e9 for lo, hi in sorted(marks)]


def run(env):
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu import serving
    from mxnet_tpu.ndarray import NDArray

    cfg, tr, loop = env.cfg, env.traffic, env.traffic["loop"]
    if loop not in ("closed", "open"):
        raise ValueError("unknown loop %r" % (loop,))
    spans, counters = env.spans, env.counters
    ctx = mx.Context(env.platform, 0)
    geo = tr["engine"]
    # the graphs first: a program that lacks the model fails here, at
    # once, before eight gigabytes of weights are made
    step, state_info = env.cfg_mod.build_step(cfg, geo["max_len"])
    prefill = env.cfg_mod.build_prefill(cfg)
    params = env.cfg_mod.init_params(cfg, env.seed)
    eng = serving.DecodeEngine(
        step, {k: NDArray(v, ctx=ctx) for k, v in params.items()}, {},
        state_info, ctx=ctx, num_slots=geo["num_slots"],
        max_len=geo["max_len"], max_queue=geo["max_queue"],
        prefill_sym=prefill, prefill_buckets=prompt_buckets(tr),
        dtype=jnp.dtype(cfg["dtype"]))
    slots = eng.num_slots
    program = eng._replicas[0].program
    live = []              # (time, live slots, their contexts) a step
    prefills = []          # (start, end, prompt lengths) a dispatch

    inner_step = program.step

    def counted_step(tokens, pos, valid, states, reset=None):
        on = valid > 0
        live.append((time.perf_counter(), float(on.sum()),
                     pos[on] + 1.0))
        with spans.span("program.step (dispatch and read)"):
            return inner_step(tokens, pos, valid, states, reset=reset)
    program.step = counted_step
    spans.wrap(eng, "_step_once", "DecodeEngine._step_once")
    inner_prefill = eng._prefill_group

    def counted_prefill(rep, bucket, group):
        t_in = time.perf_counter()
        try:
            with spans.span("DecodeEngine._prefill_group"):
                return inner_prefill(rep, bucket, group)
        finally:
            prefills.append((t_in, time.perf_counter(),
                             [len(r.prompt) for r in group]))
    eng._prefill_group = counted_prefill

    warm_traces = eng.warmup()
    source = Dealt(tr, cfg["vocab_size"], env.seed)
    # joins, leaves and prefill commits once through, before the window
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
        # the window opens on an empty pool, and until the first pool of
        # joins is prefilled no slot decodes: the traced part starts at
        # half the window, where steps and prefills alternate
        def traced():
            time.sleep(env.seconds / 2.0)
            tracer.start()
            time.sleep(min(harness.TRACE_SECONDS, env.seconds / 4.0))
            tracer.stop()
        timer = threading.Thread(target=traced, name="bench-trace")
        timer.start()
    if loop == "closed":
        sent = _serve._closed_loop(eng, source, tr["clients"], t_end)
    else:
        due = traffic_gen.arrival_times(tr, env.seed, env.seconds)
        sent = _serve._open_loop(eng, source, due, t0)
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
    first_step = next((t for t, n, _c in live if t >= t0 and n), t1)
    env.log("requests sent %d failed %d, ended in the window %d; token "
            "gaps %d (p50 %.1f, p95 %.1f, p99 %.1f ms); tokens in window "
            "%d; prefill dispatches in window %d; first step %.2f s in"
            % (len(sent), len(failed),
               sum(1 for r in sent if r.stamps and r.finish is not None
                   and r.stamps[-1] <= t1), len(gaps),
               *(1e3 * harness.percentile(gaps or [0.0], q)
                 for q in (50, 95, 99)), tokens_in,
               sum(1 for p in prefills if t0 <= p[0] <= t1),
               first_step - t0))

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
            "live_positions": sum(n for t, n, _c in live if t0 <= t <= t1),
            "steps_seen": sum(1 for t, _n, _c in live if t0 <= t <= t1),
            "prefill_dispatches": d1["prefill_dispatches"]
            - d0["prefill_dispatches"],
            "prefill_token_budget": d1.get("prefill_token_budget"),
            "prefill_programs": d1.get("prefill_programs"),
            "state_rows": d1.get("state_rows"),
            "state_dtypes": sorted({str(v.dtype) for v in
                                    eng._replicas[0].states.values()})},
        "itl_s": gaps,
        "gen_lag_s": [r.sent - r.due for r in sent] if loop == "open"
        else [],
        "window": (t0, t1),
    }
    if tracer is not None:
        lo, hi = tracer.started, tracer.stopped
        in_trace = [(n, c) for t, n, c in live if lo <= t <= hi]
        required = [env.cfg_mod.step_required(cfg, slots, c)
                    for _n, c in in_trace]
        # the rows the program says its cache states hold a slot
        # (``stats()``), for the live slots of the traced steps
        rows_a_slot = sum((d1.get("state_rows") or {}).values())
        whole = [env.cfg_mod.prefill_required(cfg, lens)
                 for a, b, lens in prefills if lo <= a and b <= hi]
        on_device = device_seconds_inside(tracer.dir, PREFILL_SPAN)
        obs["traced"] = {
            "steps": len(in_trace),
            "live_positions": sum(n for n, _c in in_trace),
            "cache_rows_held": sum(n for n, _c in in_trace) * rows_a_slot,
            "cache_rows_required": sum(r["cache_rows"] for r in required),
            "prefills": len(whole),
            # matched by count: an annotation cut by the trace's start
            # or end would be timed short of its work
            "prefill_flops": sum(r["flops"] for r in whole),
            "prefill_device_s": sum(on_device)
            if whole and len(on_device) == len(whole) else None,
            "required": required + [
                env.cfg_mod.prefill_required(cfg, lens)
                for a, _b, lens in prefills if lo <= a <= hi]}
        env.log("traced: %d steps, %d prefill dispatches whole inside, %d "
                "%s annotations, on the device %s s"
                % (len(in_trace), len(whole), len(on_device), PREFILL_SPAN,
                   ["%.4f" % x for x in on_device]))
    obs["memory_peak_bytes"] = harness.memory_peak_bytes(env.devices, env.log)

    # ---- the program's state goes, then the reference replays a sample
    limits = env.correct["limits"]
    picked = _serve._sample(sent, env.seed, env.correct["sample_requests"])
    eng.close()
    del eng, program, inner_step, inner_prefill, step
    gc.collect()
    checks = harness.Checks()
    if picked:
        t_ref = time.perf_counter()
        replays = {}

        def replay(precision="default"):
            if precision not in replays:
                replays[precision] = env.ref_mod.served_gaps(
                    params, cfg, [(r.prompt, r.tokens) for r in picked],
                    width=geo["max_len"], precision=precision)
            return replays[precision]
        got = replay()
        env.log("reference replayed %d requests, %d served tokens, in "
                "%.1f s" % (len(picked), got["tokens"],
                            time.perf_counter() - t_ref))
        checks = compare(got, limits)
        if env.calibrate is not None:
            # benchmark/calibrate.py reads the program and ``control``
            # here; on the seeds where it reads its control, every
            # control the cell's ``correct`` file lists is read through
            # the same comparison, one line each
            env.calibrate(ref=got, rerun=replay)
            for name in env.correct.get("controls", []) \
                    if len(replays) > 1 else []:
                held = compare(replay(name), limits)
                env.log("control " + json.dumps(
                    {"seed": env.seed, "control": name,
                     "correct": held.correct,
                     "read": {k: v["value"]
                              for k, v in held.as_dict().items()}}))
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

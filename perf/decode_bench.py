"""Token-throughput bench for continuous-batching decode
(mxnet_tpu/serving/decode.py).

Compares two schedulers over the SAME job list (one frozen LSTM step
graph, per-request output lengths drawn from a capped geometric
distribution — the mixed-length regime where static batching hurts):

- **static**: the pre-continuous baseline — fill every slot, step the
  pool until the SLOWEST resident request finishes, drain, refill.
  Every finished sequence rides along dead until the batch completes,
  and nobody joins mid-flight; per-batch cost is max(len) while useful
  output is mean(len);
- **continuous**: the ``DecodeEngine`` — iteration-level scheduling,
  requests join/leave the running pool between steps, a finished
  slot's place is re-filled from the queue on the very next
  iteration.

Both paths dispatch the identical compiled step program at the same
slot-pool extent, so the tokens/s ratio isolates the *scheduling*
win; job lists are identical (same seed, eos disabled, per-request
``max_new_tokens`` from the geometric draw), so total generated
tokens match exactly and the compile-once contract is asserted on
both sides (retraces == 0 after warmup).

  python perf/decode_bench.py                      # default sweep
  python perf/decode_bench.py --requests 96 --slots 8 --mean-new 24
  # defaults: hidden=128 so the step is compute-bound (python/thread
  # noise on a small shared host cannot swamp the scheduling signal)
  # and max_len=128 so the geometric tail is NOT truncated — the cap
  # would trim exactly the stragglers static batching chokes on
  python perf/decode_bench.py --check-speedup 2    # exit 1 if < 2x
  python perf/decode_bench.py --record BENCH_decode.json
  python perf/decode_bench.py --prefill --record BENCH_ttft.json
      # concurrent-join TTFT: coalesced vs serial bucketed prefill
      # (MXNET_DECODE_COALESCE_PREFILL) over one job burst, per-request
      # TTFT stamped by the on_token streaming hook, centered-median
      # serial-coalesced-serial triples + A/A noise floor; timings
      # advisory, hard gates bitwise + 0 warm retraces
  python perf/decode_bench.py --telemetry          # exit 1 if the full
      # observability plane costs more than --telemetry-tol tokens/s
      # (off-on-off centered-median + same-session A/A noise floor,
      # the serve_bench/step_bench protocol; --record writes
      # BENCH_decode_telemetry.json)
  XLA_FLAGS=--xla_force_host_platform_device_count=2 \
  python perf/decode_bench.py --replicas 2 --hidden 128 --layers 12 \
      --slots 32 --fixed-len 24 --check-speedup 1.7 \
      --record BENCH_replica.json
      # replica-routed decode sweep (serving/replica.py): same
      # centered-median protocol, bitwise + zero-retrace gates;
      # writes the "decode" section of BENCH_replica.json

A fast smoke variant runs in the tier-1 suite
(tests/test_decode.py::test_decode_bench_smoke; the >=2x acceptance
gate runs here, not there).
"""
import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_model(vocab=32, embed=16, hidden=32, seed=0, layers=1):
    """A ``layers``-deep stacked-LSTM decode step:
    token + per-layer (h, c) -> [logits] + per-layer (h', c').

    Depth is the replica sweep's compute knob (the serve_bench
    argument): XLA CPU multi-threads one LARGE h2h matmul across every
    core — a single replica's step then already eats the host, and
    forced host devices fight instead of scaling — while a stack of
    narrow cells keeps each op single-threaded, so per-step compute
    grows with depth and the forced devices stay independent (what a
    real one-chip-per-replica fleet looks like)."""
    import mxnet_tpu as mx
    from mxnet_tpu.rnn.rnn_cell import LSTMCell
    tok = mx.sym.Variable("token")
    out = mx.sym.Embedding(tok, input_dim=vocab, output_dim=embed,
                           name="emb")
    rng = np.random.default_rng(seed)

    def w(*shape, scale=1.0):
        return mx.nd.array(
            rng.standard_normal(shape).astype(np.float32) * scale)

    params = {"emb_weight": w(vocab, embed)}
    states_out, state_info = [], []
    width = embed
    for i in range(layers):
        prefix = "lstm%d_" % i
        cell = LSTMCell(hidden, prefix=prefix)
        out, (h2, c2) = cell(out, [mx.sym.Variable(prefix + "h"),
                                   mx.sym.Variable(prefix + "c")])
        states_out += [h2, c2]
        state_info += [{"name": prefix + "h", "shape": (hidden,)},
                       {"name": prefix + "c", "shape": (hidden,)}]
        params[prefix + "i2h_weight"] = w(4 * hidden, width, scale=0.5)
        params[prefix + "i2h_bias"] = mx.nd.zeros((4 * hidden,))
        params[prefix + "h2h_weight"] = w(4 * hidden, hidden, scale=0.5)
        params[prefix + "h2h_bias"] = mx.nd.zeros((4 * hidden,))
        width = hidden
    logits = mx.sym.FullyConnected(out, num_hidden=vocab, name="out_fc")
    params["out_fc_weight"] = w(vocab, hidden)
    params["out_fc_bias"] = mx.nd.zeros((vocab,))
    step = mx.sym.Group([logits] + states_out)
    return step, params, state_info


def build_prefill_model(vocab=32, d=32, seed=0):
    """Additive-state decode model whose prefill is expressible in ONE
    bucketed dispatch (the test_decode.py sum-state idiom, sized up):
    ``s' = s + emb(token)``; the prefill graph masks the padded prompt
    with the live length and sums, so a (B, T) batch of prompts
    prefills row-locally — the coalesced-vs-serial comparison is pure
    scheduling, same math both ways."""
    import mxnet_tpu as mx
    tok = mx.sym.Variable("token")
    s = mx.sym.Variable("s")
    emb = mx.sym.Embedding(tok, input_dim=vocab, output_dim=d,
                           name="emb")
    s2 = s + emb
    logits = mx.sym.FullyConnected(s2, num_hidden=vocab, name="out_fc")
    step = mx.sym.Group([logits, s2])

    prompt = mx.sym.Variable("prompt")                   # (B, T)
    plen = mx.sym.Variable("plen")                       # (B,)
    pemb = mx.sym.Embedding(prompt, input_dim=vocab, output_dim=d,
                            name="emb")                  # (B, T, d)
    masked = mx.sym.SequenceMask(pemb, use_sequence_length=True,
                                 sequence_length=plen, axis=1)
    srow = mx.sym.sum(masked, axis=1)                    # (B, d)
    plogits = mx.sym.FullyConnected(srow, num_hidden=vocab,
                                    name="out_fc")
    prefill = mx.sym.Group([plogits, srow])

    import mxnet_tpu as _mx
    rng = np.random.default_rng(seed)
    params = {
        "emb_weight": _mx.nd.array(
            rng.standard_normal((vocab, d)).astype(np.float32)),
        "out_fc_weight": _mx.nd.array(
            rng.standard_normal((vocab, d)).astype(np.float32)),
        "out_fc_bias": _mx.nd.zeros((vocab,)),
    }
    state_info = [{"name": "s", "shape": (d,)}]
    return step, prefill, params, state_info


def build_spec_models(vocab=32, d=16, max_len=64, layers=6, seed=0,
                      tail_scale=0.05):
    """A deep-narrow attention target and its 1-block draft for the
    speculative sweep (ISSUE 15).

    The target stacks ``layers`` single-head attention blocks over
    per-layer fixed-layout KV caches (residual form: ``x +
    scale * proj(attn(x))``); blocks past the first have their output
    projections scaled by ``tail_scale``, so the full stack computes
    approximately what block 0 alone computes — a distilled-by-
    construction draft.  The DRAFT is block 0 + the shared head,
    sharing the target's actual weights: ~1/``layers`` of the
    target's per-token compute with a high (but not perfect) greedy
    agreement rate — the regime speculation exists for.  Both graphs
    declare their caches ``{"cache": True}`` so accepted tokens
    commit through the multi-token scatter path.

    Depth is deliberate per the replica-sweep precedent: narrow ops
    stay single-threaded on XLA CPU, so per-step compute grows with
    depth and the draft/target cost ratio is real, not
    parallelism noise."""
    import mxnet_tpu as mx
    rng = np.random.default_rng(seed)

    def w(*shape, scale=1.0):
        return mx.nd.array(
            rng.standard_normal(shape).astype(np.float32) * scale)

    params = {"emb_weight": w(vocab, d)}
    tok = mx.sym.Variable("token")
    pos = mx.sym.Variable("pos")
    steps_r = mx.sym.reshape(mx.sym._arange(start=0, stop=max_len),
                             shape=(1, max_len))
    mask = mx.sym.broadcast_lesser_equal(
        steps_r, mx.sym.reshape(pos, shape=(-1, 1)))

    def block(x, i, scale):
        prefix = "blk%d_" % i
        kc = mx.sym.Variable(prefix + "k")
        vc = mx.sym.Variable(prefix + "v")
        q = mx.sym.FullyConnected(x, num_hidden=d, no_bias=True,
                                  name=prefix + "q")
        k = mx.sym.FullyConnected(x, num_hidden=d, no_bias=True,
                                  name=prefix + "kf")
        v = mx.sym.FullyConnected(x, num_hidden=d, no_bias=True,
                                  name=prefix + "vf")
        oh = mx.sym.one_hot(pos, depth=max_len)
        ohe = mx.sym.expand_dims(oh, axis=2)
        k_new = mx.sym.broadcast_mul(kc, 1.0 - ohe) \
            + mx.sym.broadcast_mul(mx.sym.expand_dims(k, axis=1), ohe)
        v_new = mx.sym.broadcast_mul(vc, 1.0 - ohe) \
            + mx.sym.broadcast_mul(mx.sym.expand_dims(v, axis=1), ohe)
        scores = mx.sym.batch_dot(k_new,
                                  mx.sym.expand_dims(q, axis=2))
        scores = mx.sym.reshape(scores, shape=(0, max_len)) \
            * (1.0 / np.sqrt(d))
        scores = scores * mask + (1.0 - mask) * (-1e9)
        attn = mx.sym.softmax(scores, axis=1)
        ctx = mx.sym.batch_dot(mx.sym.expand_dims(attn, axis=1),
                               v_new)
        ctx = mx.sym.reshape(ctx, shape=(0, d))
        o = mx.sym.FullyConnected(ctx, num_hidden=d, no_bias=True,
                                  name=prefix + "o")
        params.setdefault(prefix + "q_weight", w(d, d, scale=0.5))
        params.setdefault(prefix + "kf_weight", w(d, d, scale=0.5))
        params.setdefault(prefix + "vf_weight", w(d, d, scale=0.5))
        params.setdefault(prefix + "o_weight", w(d, d, scale=scale))
        info = {"name": prefix + "k", "shape": (max_len, d),
                "cache": True}
        info_v = {"name": prefix + "v", "shape": (max_len, d),
                  "cache": True}
        return x + o, k_new, v_new, [info, info_v]

    params["out_fc_weight"] = w(vocab, d)
    params["out_fc_bias"] = mx.nd.zeros((vocab,))

    def stack(n_blocks):
        x = mx.sym.Embedding(tok, input_dim=vocab, output_dim=d,
                             name="emb")
        outs, infos = [], []
        for i in range(n_blocks):
            x, k_new, v_new, inf = block(
                x, i, 1.0 if i == 0 else tail_scale)
            outs += [k_new, v_new]
            infos += inf
        logits = mx.sym.FullyConnected(x, num_hidden=vocab,
                                       name="out_fc")
        return mx.sym.Group([logits] + outs), infos

    target, t_info = stack(layers)
    draft, d_info = stack(1)
    return target, t_info, draft, d_info, params


def spec_round(eng, jobs):
    """Offer every job up front and drain (the continuous_round
    contract) — returns (token lists, tokens/s)."""
    t0 = time.perf_counter()
    futs = [eng.submit(prompt, max_new_tokens=max_new)
            for prompt, max_new in jobs]
    results = [f.result(timeout=600) for f in futs]
    dt = time.perf_counter() - t0
    bad = [r.finish_reason for r in results
           if r.finish_reason not in ("length", "eos")]
    if bad:
        raise RuntimeError("spec round lost requests: %s" % bad)
    return [list(r.tokens) for r in results], \
        sum(len(r) for r in results) / dt


def run_spec_sweep(requests=32, slots=8, max_len=64, mean_new=16,
                   vocab=32, d=16, layers=6, spec_ks=(2, 4), seed=0,
                   repeats=5, tail_scale=0.05):
    """Speculative draft-k-verify sweep (ISSUE 15): one engine per
    spec width over the SAME deep-narrow attention target, same job
    list, same seed — k=0 is the PR 13 single-token step the ratios
    are taken against.

    HARD gates (the sweep's actual contract on this CPU container):
    every engine's greedy output is bitwise-identical to
    ``greedy_decode`` and to the k=0 engine, zero post-warmup
    retraces per engine, and a warm AOT restart of the widest spec
    engine performs 0 compiles.  Timings ride the host-noise protocol
    (``serve_bench.centered_sweep`` base-k-base triples, median
    centered ratio, A/A floor from a second k=0 engine) and are
    ADVISORY on a shared 2-core host: the speculative win here is
    fused dispatch — one compiled program commits 1+accepted tokens
    per host round-trip (arxiv 2301.13062's boundary argument) —
    which only translates to wall-clock when the draft is genuinely
    cheaper than the target, hence the deep-narrow stack."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import tempfile
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    aot_dir = tempfile.mkdtemp(prefix="bench_spec_aot_")
    old_env = {k: os.environ.get(k)
               for k in ("MXNET_AOT_CACHE_DIR", "MXNET_AOT_CACHE")}
    os.environ["MXNET_AOT_CACHE_DIR"] = aot_dir
    os.environ["MXNET_AOT_CACHE"] = "1"
    try:
        return _run_spec_sweep(requests, slots, max_len, mean_new,
                               vocab, d, layers, spec_ks, seed,
                               repeats, tail_scale)
    finally:
        # a raising round must not leave the PROCESS pointing at the
        # bench's temp AOT volume (the tier-1 smoke shares its
        # process with every later test)
        for k2, v2 in old_env.items():
            if v2 is None:
                os.environ.pop(k2, None)
            else:
                os.environ[k2] = v2


def _run_spec_sweep(requests, slots, max_len, mean_new, vocab, d,
                    layers, spec_ks, seed, repeats, tail_scale):
    from mxnet_tpu.serving.decode import DecodeEngine, StepProgram, \
        greedy_decode
    from serve_bench import centered_sweep
    target, t_info, draft, d_info, params = build_spec_models(
        vocab=vocab, d=d, max_len=max_len, layers=layers, seed=seed,
        tail_scale=tail_scale)
    jobs = make_jobs(requests, mean_new, max_len, vocab, seed + 1)

    def build_eng(k):
        kw = {}
        if k:
            kw = dict(draft_sym=draft, draft_arg_params=params,
                      draft_state_info=d_info, spec_k=k)
        e = DecodeEngine(target, params, {}, t_info, num_slots=slots,
                         max_len=max_len, max_queue=requests + slots,
                         default_deadline_ms=0, **kw)
        e.warmup()
        return e

    labels = ["base", "aa"] + ["k%d" % k for k in spec_ks]
    engines = {"base": build_eng(0), "aa": build_eng(0)}
    for k in spec_ks:
        engines["k%d" % k] = build_eng(k)
    compiles0 = {lb: e.compile_count for lb, e in engines.items()}
    outputs = {}

    def run_one(lb):
        toks, tps = spec_round(engines[lb], jobs)
        if lb not in outputs:
            outputs[lb] = toks
        elif outputs[lb] != toks:
            raise RuntimeError("%s: outputs changed across rounds"
                               % lb)
        return tps

    best, ratios = centered_sweep(labels, run_one, repeats)
    noise_floor = abs(ratios.pop("aa") - 1.0)

    # hard gate: bitwise vs greedy_decode AND vs the k=0 engine
    ref_prog = StepProgram(target, params, {}, t_info, num_slots=1)
    refs = [list(greedy_decode(ref_prog, prompt, max_new,
                               max_len=max_len))
            for prompt, max_new in jobs]
    bitwise = all(outputs[lb] == refs for lb in labels)

    retraces = {lb: engines[lb].compile_count - compiles0[lb]
                for lb in labels}
    spec_stats = {"k%d" % k:
                  engines["k%d" % k].stats()["decode"]["spec"]
                  for k in spec_ks}
    for e in engines.values():
        e.close()

    # hard gate: a warm AOT restart of the widest engine compiles
    # nothing (every program — wider step, row kernels — loads)
    e2 = build_eng(spec_ks[-1])
    aot_warm_compiles = e2.compile_count
    aot_stats = e2.stats()["decode"]["aot"]
    e2.close()

    row = {
        "requests": requests, "slots": slots, "max_len": max_len,
        "mean_new": mean_new, "vocab": vocab, "d": d,
        "layers": layers, "tail_scale": tail_scale,
        "rounds": max(1, repeats),
        "tokens": sum(m for _, m in jobs),
        "base_tps": best["base"],
        "spec": {
            "k%d" % k: {
                "tps": best["k%d" % k],
                "speedup_vs_base": ratios["k%d" % k],
                "accept_rate": spec_stats["k%d" % k]["accept_rate"],
                "tokens_per_step":
                    spec_stats["k%d" % k]["tokens_per_step"],
                "commit_selection":
                    [s["op"] for s in
                     spec_stats["k%d" % k]["commit_selection"]],
            } for k in spec_ks},
        "noise_floor": noise_floor,
        "bitwise_identical": bitwise,
        "retraces": retraces,
        "aot_warm_compiles": aot_warm_compiles,
        "aot_warm_hits": aot_stats["hits"],
        "aot_warm_rejects": aot_stats["rejects"],
    }
    return row


def prefill_round(eng, jobs):
    """Offer every job in one burst (the concurrent-join regime) and
    drain; per-request TTFT is stamped by the ``on_token`` streaming
    hook at the FIRST generated token.  Returns (token lists, ttfts in
    seconds, wall seconds)."""
    t_first = [None] * len(jobs)
    futs = []
    t0 = time.perf_counter()
    for i, (prompt, max_new) in enumerate(jobs):
        def cb(tok, _i=i):
            if t_first[_i] is None:
                t_first[_i] = time.perf_counter()
        futs.append(eng.submit(prompt, max_new_tokens=max_new,
                               on_token=cb))
    results = [f.result(timeout=600) for f in futs]
    wall = time.perf_counter() - t0
    bad = [r.finish_reason for r in results
           if r.finish_reason not in ("length", "eos")]
    if bad:
        raise RuntimeError("prefill round lost requests: %s" % bad)
    if any(t is None for t in t_first):
        raise RuntimeError("a request finished without streaming a "
                           "first token")
    return ([list(r.tokens) for r in results],
            [t - t0 for t in t_first], wall)


def run_prefill_sweep(requests=32, slots=8, max_len=64, max_prompt=24,
                      max_new=4, vocab=32, d=32, seed=0, repeats=5):
    """Concurrent-join TTFT: coalesced vs serial bucketed prefill
    (MXNET_DECODE_COALESCE_PREFILL) over the SAME job list.

    Protocol per the host-noise precedent (README / BENCH_telemetry):
    each repeat times a serial-coalesced-serial TRIPLE whose centered
    ratio cancels linear drift, the median discards bursty outliers,
    and the serial/serial pairs form a same-session A/A null — the
    host's own measurement resolution, reported beside the speedup.
    Timings are ADVISORY; the hard gates are bitwise-identical token
    sequences between the two modes and ZERO warm retraces on both
    engines across every measured round.
    """
    import statistics
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from mxnet_tpu.serving.decode import DecodeEngine

    step, prefill, params, state_info = build_prefill_model(vocab, d,
                                                            seed)
    rng = np.random.default_rng(seed + 1)
    jobs = []
    for _ in range(requests):
        plen = int(rng.integers(1, max_prompt + 1))
        jobs.append(([int(t) for t in rng.integers(vocab, size=plen)],
                     int(max_new)))

    def make_engine(coalesce):
        prev = os.environ.get("MXNET_DECODE_COALESCE_PREFILL")
        os.environ["MXNET_DECODE_COALESCE_PREFILL"] = \
            "1" if coalesce else "0"
        try:
            eng = DecodeEngine(step, params, {}, state_info,
                               num_slots=slots, max_len=max_len,
                               prefill_sym=prefill,
                               max_queue=requests + slots,
                               default_deadline_ms=0)
            eng.warmup()
        finally:
            if prev is None:
                os.environ.pop("MXNET_DECODE_COALESCE_PREFILL", None)
            else:
                os.environ["MXNET_DECODE_COALESCE_PREFILL"] = prev
        return eng

    eng_serial = make_engine(False)
    eng_coal = make_engine(True)
    warm = {"serial": eng_serial.compile_count,
            "coalesced": eng_coal.compile_count}

    centered, nulls = [], []
    bitwise = True
    best = {"serial": None, "coalesced": None}
    try:
        for _ in range(max(1, repeats)):
            toks_a, tt_a, _ = prefill_round(eng_serial, jobs)
            toks_n, tt_n, _ = prefill_round(eng_coal, jobs)
            toks_b, tt_b, _ = prefill_round(eng_serial, jobs)
            if toks_a != toks_n or toks_a != toks_b:
                bitwise = False
            ma = statistics.mean(tt_a)
            mn = statistics.mean(tt_n)
            mb = statistics.mean(tt_b)
            centered.append((ma + mb) / 2.0 / mn)   # >1: coalesced wins
            nulls.append(abs(1.0 - ma / mb))
            for key, tt in (("serial", tt_a), ("serial", tt_b),
                            ("coalesced", tt_n)):
                if best[key] is None \
                        or statistics.mean(tt) < statistics.mean(
                            best[key]):
                    best[key] = tt
        retr_serial = eng_serial.compile_count - warm["serial"]
        retr_coal = eng_coal.compile_count - warm["coalesced"]
        st_serial = eng_serial.stats()["decode"]
        st_coal = eng_coal.stats()["decode"]
    finally:
        eng_serial.close()
        eng_coal.close()

    def _tt_row(tt):
        s = sorted(tt)
        return {"mean_ms": round(statistics.mean(s) * 1e3, 3),
                "p50_ms": round(s[len(s) // 2] * 1e3, 3),
                "p99_ms": round(s[min(len(s) - 1,
                                      int(len(s) * 0.99))] * 1e3, 3)}

    return {
        "requests": requests,
        "slots": slots,
        "max_len": max_len,
        "max_prompt": max_prompt,
        "max_new": max_new,
        "rounds": max(1, repeats),
        "estimator": "centered-median (serial-coalesced-serial triples)",
        "ttft_serial": _tt_row(best["serial"]),
        "ttft_coalesced": _tt_row(best["coalesced"]),
        "ttft_speedup": round(statistics.median(centered), 3),
        "noise_floor": round(statistics.median(nulls), 4),
        "step_p50_ms": {"serial": st_serial["step_ms"]["p50"],
                        "coalesced": st_coal["step_ms"]["p50"]},
        "prefill_dispatches": {
            "serial": st_serial["prefill_dispatches"],
            "coalesced": st_coal["prefill_dispatches"]},
        "joins": {"serial": st_serial["joins"],
                  "coalesced": st_coal["joins"]},
        "bitwise_identical": bitwise,
        "retraces": {"serial": retr_serial, "coalesced": retr_coal},
        "timing": "advisory per the host-noise protocol; hard gates "
                  "are bitwise_identical and zero retraces",
    }


def make_jobs(requests, mean_new, max_len, vocab, seed=1):
    """(prompt, max_new) per request: 1-token prompts, output lengths
    geometric with the given mean, capped into the slot's capacity —
    the mixed regime where one straggler pins a static batch."""
    rng = np.random.default_rng(seed)
    cap = max_len - 1                      # 1 position consumes the BOS
    jobs = []
    for _ in range(requests):
        n = int(min(cap, rng.geometric(1.0 / mean_new)))
        jobs.append(([int(rng.integers(vocab))], max(1, n)))
    return jobs


def static_rebatch_round(program, jobs, max_len):
    """The baseline scheduler: batches of ``num_slots`` run to FULL
    completion before the next batch starts.  Returns (total tokens,
    seconds, step dispatches)."""
    n = program.num_slots
    states = program.init_states()
    total = steps = 0
    t0 = time.perf_counter()
    queue = list(jobs)
    while queue:
        batch, queue = queue[:n], queue[n:]
        tokens = np.zeros((n,), np.float32)
        pos = np.zeros((n,), np.float32)
        valid = np.zeros((n,), np.float32)
        reset = np.zeros((n,), np.float32)
        live = []
        for i, (prompt, max_new) in enumerate(batch):
            reset[i] = 1.0              # same in-step row clear the
            tokens[i] = prompt[0]       # engine's joins use
            valid[i] = 1.0
            live.append({"prompt": list(prompt), "pi": 1,
                         "out": 0, "max_new": max_new})
        while any(r is not None for r in live):
            sampled, states = program.step(tokens, pos, valid, states,
                                           reset=reset)
            reset.fill(0.0)
            steps += 1
            for i, r in enumerate(live):
                if r is None:
                    continue
                pos[i] += 1.0
                if r["pi"] < len(r["prompt"]):
                    tokens[i] = r["prompt"][r["pi"]]
                    r["pi"] += 1
                else:
                    tokens[i] = sampled[i]
                    r["out"] += 1
                    total += 1
                if r["out"] >= r["max_new"] or pos[i] >= max_len:
                    live[i] = None
                    valid[i] = 0.0        # dead weight until the drain
    return total, time.perf_counter() - t0, steps


def continuous_round(eng, jobs):
    """Offer every job up front (deep backlog — the regime continuous
    batching exists for) and drain.  Returns (tokens, seconds)."""
    t0 = time.perf_counter()
    futs = [eng.submit(prompt, max_new_tokens=max_new)
            for prompt, max_new in jobs]
    results = [f.result(timeout=600) for f in futs]
    dt = time.perf_counter() - t0
    total = sum(len(r) for r in results)
    bad = [r.finish_reason for r in results
           if r.finish_reason not in ("length", "eos")]
    if bad:
        raise RuntimeError("continuous round lost requests: %s" % bad)
    return total, dt


def _efficiency_advisory(eng, tps, stats=None):
    """Advisory ISSUE 18 fields for a decode bench row: priced from
    the SAME compile-time FLOPs ledger the serving efficiency plane
    uses (telemetry/goodput.py price_step_program) — NO new timing
    protocol, ``tps`` comes from the round already timed.

    Per-token analytic price is one step dispatch amortized over the
    slot pool (full occupancy yields one token per live slot-step);
    ``serve_mfu`` divides by the device's PEAKS_TFLOPS entry (honest
    None on CPU); ``goodput_ratio`` prefers the engine's exact ledger
    ratio and falls back to tokens/(steps*slots) occupancy when the
    plane is off."""
    row = {"analytic_gflops_per_s": None, "serve_mfu": None,
           "goodput_ratio": None}
    price = None
    try:
        from mxnet_tpu.telemetry import goodput as _goodput
        price = _goodput.price_step_program(eng._replicas[0].program)
    except Exception:
        pass
    n = eng.num_slots
    if price and tps:
        gfs = tps * (price / float(n)) / 1e9
        row["analytic_gflops_per_s"] = round(gfs, 4)
        peak = None
        try:
            import jax
            from mxnet_tpu.telemetry import peak_flops_for
            peak = peak_flops_for(jax.devices()[0])
        except Exception:
            pass
        if peak:
            row["serve_mfu"] = round(gfs * 1e9 / peak, 6)
    eff = (stats or {}).get("efficiency") or {}
    g = eff.get("goodput_ratio")
    if g is None and stats and stats.get("steps"):
        g = (stats.get("tokens_generated", 0)
             / float(stats["steps"] * n))
    if g is not None:
        row["goodput_ratio"] = round(g, 4)
    return row


def run_bench(requests=64, slots=8, max_len=128, mean_new=16, vocab=32,
              embed=16, hidden=128, seed=0, repeat=3):
    """One full comparison at a fixed geometry; returns the result row.

    ``repeat`` rounds run INTERLEAVED (static, continuous, static,
    continuous, ...) over one compiled program / one engine, and each
    scheduler reports its best round — the serve_bench idiom: on a
    shared noisy host the first rounds eat cold caches and frequency
    ramps, and interleaving keeps slow minutes from landing on one
    side of the comparison."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from mxnet_tpu.serving.decode import DecodeEngine, StepProgram

    step, params, state_info = build_model(vocab, embed, hidden, seed)
    jobs = make_jobs(requests, mean_new, max_len, vocab, seed + 1)
    want = sum(m for _, m in jobs)

    prog = StepProgram(step, params, {}, state_info, num_slots=slots)
    # warmup outside the timing; twice — the second step's committed
    # state shardings are their own executable-cache key (see
    # DecodeEngine.warmup)
    st = prog.init_states()
    st = prog.zero_row(st, 0)
    z = np.zeros((slots,), np.float32)
    _, st = prog.step(z, z, z, st)
    prog.step(z, z, z, st)
    eng = DecodeEngine(step, params, {}, state_info, num_slots=slots,
                       max_len=max_len, max_queue=requests + slots,
                       default_deadline_ms=0)
    eng.warmup()
    c0 = prog.trace_count + eng.compile_count

    best_s = best_c = 0.0
    s_steps = steps0 = 0
    for _ in range(max(1, repeat)):
        s_tokens, s_dt, s_steps = static_rebatch_round(prog, jobs,
                                                       max_len)
        c_tokens, c_dt = continuous_round(eng, jobs)
        if s_tokens != want or c_tokens != want:
            raise RuntimeError(
                "token accounting mismatch: want %d, static %d, "
                "continuous %d" % (want, s_tokens, c_tokens))
        best_s = max(best_s, s_tokens / s_dt)
        best_c = max(best_c, c_tokens / c_dt)
    retraces = prog.trace_count + eng.compile_count - c0
    stats = eng.stats()["decode"]
    adv = _efficiency_advisory(eng, best_c, stats)
    eng.close()

    row = {
        "requests": requests,
        "slots": slots,
        "max_len": max_len,
        "mean_new": mean_new,
        "rounds": max(1, repeat),
        "tokens": want,
        "static_tps": best_s,
        "static_steps": s_steps,
        "continuous_tps": best_c,
        "continuous_steps": stats["steps"] // max(1, repeat),
        "speedup": best_c / best_s,
        "retraces": retraces,
        "step_p50_ms": stats["step_ms"]["p50"],
        "step_p99_ms": stats["step_ms"]["p99"],
        # advisory: the static planner's warm-set watermark (step +
        # slot pool + prefill; analysis/memory.py)
        "predicted_peak_bytes":
            stats["memory"].get("predicted_peak_bytes"),
    }
    row.update(adv)     # advisory efficiency fields (ISSUE 18)
    return row


def run_telemetry_overhead(requests=64, slots=8, max_len=128,
                           mean_new=16, vocab=32, embed=16, hidden=128,
                           seed=0, repeats=3, tol=0.02, http=True):
    """Decode-plane telemetry overhead gate — the decode path had no
    recorded telemetry-overhead number (serve_bench gates the one-shot
    engine only, and decode adds per-token instrument writes: TTFT /
    TPOT observations, step histograms, token counters, the history
    recorder + alert evaluation, and heartbeat polling).

    Protocol is the serve_bench/step_bench one verbatim: one engine
    per mode (instruments bind at construction), identical job lists
    drained through :func:`continuous_round`, each repeat timing an
    off-on-off TRIPLE whose centered ratio cancels linear drift, the
    median discarding bursty outliers, and the off/off pairs forming a
    same-session A/A null whose median deviation is the host's own
    measurement resolution (``noise_floor``).  The gate only fails
    when the measured regression exceeds ``tol`` PLUS that floor.
    With ``http`` the FULL plane runs: live endpoint + a background
    scraper hammering ``GET /metrics`` AND ``GET /timeline`` across
    BOTH modes' rounds (so its GIL share cancels in the A/B) — the
    marginal cost measured is the telemetry plane's own, now including
    the fleet-event ring the ON engine feeds per step/token and the
    timeline snapshot+render the scrape pays.  Record the row with
    ``--record BENCH_timeline.json``.
    """
    import statistics
    import threading
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from mxnet_tpu import telemetry
    from mxnet_tpu.serving.decode import DecodeEngine

    step, params, state_info = build_model(vocab, embed, hidden, seed)
    jobs = make_jobs(requests, mean_new, max_len, vocab, seed + 1)

    def make_engine(enabled):
        telemetry.set_enabled(enabled)
        try:
            eng = DecodeEngine(step, params, {}, state_info,
                               num_slots=slots, max_len=max_len,
                               max_queue=requests + slots,
                               default_deadline_ms=0)
            eng.warmup()
        finally:
            telemetry.set_enabled(None)
        return eng

    eng_off = make_engine(False)
    eng_on = make_engine(True)
    # master switch pinned ON for the round phase so /timeline serves
    # (both engines bound their instrument handles at construction, so
    # the pin changes neither hot path); restored in the finally below
    telemetry.set_enabled(True)

    server = scraper = None
    stop_scrape = threading.Event()
    scrapes = [0, 0.0]
    tl_scrapes = [0, 0.0]
    if http:
        import http.client
        server = telemetry.start_server(0, host="127.0.0.1")

        def hammer():
            conn = http.client.HTTPConnection("127.0.0.1", server.port,
                                              timeout=5)
            while not stop_scrape.is_set():
                try:
                    t0 = time.perf_counter()
                    conn.request("GET", "/metrics")
                    body = conn.getresponse().read()
                    assert body.startswith(b"#"), "unparseable scrape"
                    scrapes[0] += 1
                    scrapes[1] += time.perf_counter() - t0
                    # timeline plane end-to-end: snapshot + render of
                    # the per-step/per-token events the ON engine feeds
                    t0 = time.perf_counter()
                    conn.request("GET", "/timeline?window=5")
                    tl = json.loads(conn.getresponse().read())
                    assert tl.get("format") == \
                        "mxnet_tpu.telemetry/timeline-1", tl
                    tl_scrapes[0] += 1
                    tl_scrapes[1] += time.perf_counter() - t0
                except Exception:
                    conn.close()
                    if stop_scrape.is_set():
                        return
                stop_scrape.wait(0.1)
        scraper = threading.Thread(target=hammer, daemon=True,
                                   name="bench-scraper")
        scraper.start()

    off_tps = on_tps = 0.0
    centered, nulls = [], []
    adv = {}
    tl_appended = 0
    try:
        for _ in range(max(1, repeats)):
            ta, dt_a = continuous_round(eng_off, jobs)
            tn, dt_n = continuous_round(eng_on, jobs)
            tb, dt_b = continuous_round(eng_off, jobs)
            assert ta == tn == tb, "token accounting diverged"
            off_tps = max(off_tps, ta / min(dt_a, dt_b))
            on_tps = max(on_tps, tn / dt_n)
            # tokens/s ratios: on/off > 1 means telemetry is FASTER
            centered.append((ta / dt_a + tb / dt_b) / 2.0 / (tn / dt_n))
            nulls.append(abs(1.0 - (ta / dt_a) / (tb / dt_b)))
        adv = _efficiency_advisory(eng_on, on_tps,
                                   eng_on.stats()["decode"])
        tl_ring = telemetry.timeline.peek()
        tl_appended = tl_ring.appended() if tl_ring is not None else 0
    finally:
        telemetry.set_enabled(None)
        stop_scrape.set()
        if scraper is not None:
            scraper.join(timeout=10)
        if server is not None:
            telemetry.stop_server()
        eng_off.close()
        eng_on.close()
    regression = 1.0 - 1.0 / statistics.median(centered)
    noise_floor = statistics.median(nulls)
    return dict(adv, **{
        "requests": requests,
        "slots": slots,
        "mean_new": mean_new,
        "rounds": max(1, repeats),
        "tps_telemetry_off": round(off_tps, 1),
        "tps_telemetry_on": round(on_tps, 1),
        "regression": round(regression, 4),
        "noise_floor": round(noise_floor, 4),
        "tol": tol,
        "http_server": bool(http),
        "metrics_scrapes": scrapes[0],
        "mean_scrape_ms": (round(scrapes[1] / scrapes[0] * 1e3, 3)
                           if scrapes[0] else None),
        "timeline_scrapes": tl_scrapes[0],
        "mean_timeline_scrape_ms": (
            round(tl_scrapes[1] / tl_scrapes[0] * 1e3, 3)
            if tl_scrapes[0] else None),
        "timeline_events": tl_appended,
        "ok": regression < tol + noise_floor,
    })


def _merge_record(path, key, row):
    """Update one section of the shared BENCH_replica.json document —
    one implementation, owned by serve_bench (both benches write
    sections of the same file and must never drift on its format)."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from serve_bench import _merge_record as _shared
    return _shared(path, key, row)


def run_replica_sweep(requests=64, slots=8, max_len=128, mean_new=16,
                      vocab=32, embed=16, hidden=128, seed=0, repeats=5,
                      replica_counts=(1, 2), layers=1, fixed_len=None):
    """Replica-routed decode sweep (serving/replica.py): one
    DecodeEngine per replica count — each replica a full slot pool on
    its own device — drained over the SAME job list, interleaved
    best-of tokens/s per count.

    Greedy decode is routing-invariant (each replica runs the same
    program over the same params), so the sweep also asserts
    bitwise-identical per-request tokens against the single-replica
    engine and the per-replica zero-retrace contract.  Run under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
    """
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.serving.decode import DecodeEngine

    replica_counts = sorted(set(int(k) for k in replica_counts))
    n_dev = jax.device_count()
    if n_dev < max(replica_counts):
        raise RuntimeError(
            "replica sweep needs %d devices but only %d exist — run "
            "under XLA_FLAGS=--xla_force_host_platform_device_count=%d"
            % (max(replica_counts), n_dev, max(replica_counts)))
    step, params, state_info = build_model(vocab, embed, hidden, seed,
                                           layers=layers)
    if fixed_len:
        # uniform output lengths: when requests divide slots x replicas
        # evenly, every pool refills in exact waves and BOTH engines run
        # at full occupancy start to finish — the sweep then measures
        # pure device scaling, not tail-occupancy effects (which the
        # continuous-vs-static sweep's geometric mix exists to show)
        rng = np.random.default_rng(seed + 1)
        jobs = [([int(rng.integers(vocab))], int(fixed_len))
                for _ in range(requests)]
    else:
        jobs = make_jobs(requests, mean_new, max_len, vocab, seed + 1)
    want = sum(m for _, m in jobs)

    engines, warm = {}, {}
    for k in replica_counts:
        eng = DecodeEngine(step, params, {}, state_info,
                           num_slots=slots, max_len=max_len,
                           max_queue=requests + slots * k,
                           default_deadline_ms=0,
                           ctx=[mx.cpu(i) for i in range(k)])
        eng.warmup()
        engines[k] = eng
        warm[k] = eng.compile_count

    # bitwise identity: greedy tokens must not depend on which replica
    # a request seated on
    base_eng = engines[replica_counts[0]]
    base = [list(f.result(timeout=600).tokens) for f in
            [base_eng.submit(p, max_new_tokens=m) for p, m in jobs]]
    bitwise = True
    for k in replica_counts[1:]:
        futs = [engines[k].submit(p, max_new_tokens=m)
                for p, m in jobs]
        got = [list(f.result(timeout=600).tokens) for f in futs]
        if got != base:
            bitwise = False

    # Estimator: the shared base-K-base centered-triple protocol
    # (serve_bench.centered_sweep — one implementation, so the two
    # BENCH_replica.json sections stay comparable).
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from serve_bench import centered_sweep

    def timed(k):
        tokens, dt = continuous_round(engines[k], jobs)
        if tokens != want:
            raise RuntimeError("token accounting mismatch at "
                               "%d replicas: want %d got %d"
                               % (k, want, tokens))
        return tokens / dt

    best, speedups = centered_sweep(replica_counts, timed, repeats)

    rows, retraces_total = [], 0
    for k in replica_counts:
        eng = engines[k]
        retraces = eng.compile_count - warm[k]
        retraces_total += retraces
        st = eng.stats()["decode"]
        row = {
            "replicas": k,
            "tokens_per_s": round(best[k], 1),
            "retraces": retraces,
            "steps": st["steps"],
            "step_p50_ms": st["step_ms"]["p50"],
            # advisory: planner watermark per replica device group
            "predicted_peak_bytes":
                st["memory"].get("predicted_peak_bytes"),
        }
        if k != replica_counts[0]:
            row["speedup_vs_1"] = round(speedups[k], 2)
            row["speedup_best_of"] = round(
                best[k] / best[replica_counts[0]], 2)
        row.update(_efficiency_advisory(eng, best[k], st))
        rows.append(row)
        eng.close()
    return {
        "requests": requests,
        "slots_per_replica": slots,
        "hidden": hidden, "layers": layers,
        "mean_new": mean_new, "fixed_len": fixed_len,
        "tokens": want,
        "rounds": max(1, repeats),
        "estimator": "centered-median (base-K-base triples)",
        "device_count": n_dev,
        "xla_flags": os.environ.get("XLA_FLAGS", ""),
        "bitwise_identical": bitwise,
        "retraces": retraces_total,
        "speedup": rows[-1].get("speedup_vs_1", 1.0),
        "rows": rows,
    }


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="continuous-batching decode throughput bench")
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--mean-new", type=int, default=16,
                    help="mean of the geometric output-length draw")
    ap.add_argument("--vocab", type=int, default=32)
    ap.add_argument("--hidden", type=int, default=128)
    ap.add_argument("--fixed-len", type=int, default=None,
                    help="replica sweep: uniform output length instead "
                         "of the geometric draw (exact refill waves — "
                         "measures device scaling, not tail effects)")
    ap.add_argument("--layers", type=int, default=1,
                    help="stacked LSTM depth (replica sweep: depth "
                         "raises per-step compute without widening any "
                         "single op past XLA CPU's intra-op "
                         "parallelization threshold)")
    ap.add_argument("--repeat", type=int, default=4,
                    help="interleaved best-of-N rounds (scheduling is "
                         "deterministic; repeats absorb host noise)")
    ap.add_argument("--check-speedup", type=float, default=None,
                    metavar="X", help="exit 1 unless continuous/static "
                    "tokens-per-second ratio >= X")
    ap.add_argument("--prefill", action="store_true",
                    help="run the concurrent-join TTFT sweep instead: "
                         "coalesced vs serial bucketed prefill "
                         "(MXNET_DECODE_COALESCE_PREFILL) over one job "
                         "burst, centered-median estimator, timings "
                         "advisory; hard gates bitwise + 0 warm "
                         "retraces; --record writes BENCH_ttft.json")
    ap.add_argument("--max-prompt", type=int, default=24,
                    help="prefill sweep: prompts drawn uniform in "
                         "[1, max_prompt]")
    ap.add_argument("--max-new", type=int, default=4,
                    help="prefill sweep: tokens generated per request "
                         "after prefill (small: the sweep measures "
                         "time-to-FIRST-token, not generation)")
    ap.add_argument("--telemetry", action="store_true",
                    help="run the decode telemetry overhead gate "
                         "instead of the continuous-vs-static sweep: "
                         "exit 1 if tokens/s regresses >= "
                         "--telemetry-tol with the full plane on "
                         "(registry + HTTP endpoint + scraper)")
    ap.add_argument("--telemetry-tol", type=float, default=0.02,
                    help="allowed fractional tokens/s regression with "
                         "telemetry on (default 0.02 = 2%%)")
    ap.add_argument("--no-http", action="store_true",
                    help="telemetry gate without the HTTP server + "
                         "scraper (registry-only overhead)")
    ap.add_argument("--replicas", metavar="N[,M...]",
                    help="run the replica-routed decode sweep instead: "
                         "one engine per replica count (needs that "
                         "many devices; XLA_FLAGS=--xla_force_host_"
                         "platform_device_count=N), interleaved "
                         "best-of tokens/s, records the decode section "
                         "of BENCH_replica.json via --record")
    ap.add_argument("--spec", action="store_true",
                    help="run the speculative draft-k-verify sweep "
                         "instead (ISSUE 15): one engine per spec "
                         "width over a deep-narrow attention target "
                         "with its 1-block draft, tokens/s + "
                         "accept-rate vs the k=0 single-token step "
                         "(centered-median triples + A/A floor, "
                         "timings advisory); HARD gates: greedy "
                         "bitwise vs greedy_decode and the k=0 "
                         "engine, 0 post-warmup retraces, warm AOT "
                         "restart 0 compiles; --record writes "
                         "BENCH_spec.json")
    ap.add_argument("--spec-ks", default="2,4", metavar="K1[,K2...]",
                    help="spec sweep: the draft window widths to "
                         "bench (default 2,4)")
    ap.add_argument("--spec-d", type=int, default=16,
                    help="spec sweep: model width (narrow on purpose "
                         "— see --layers)")
    ap.add_argument("--tail-scale", type=float, default=0.05,
                    help="spec sweep: output-projection scale of the "
                         "target's blocks past the first — smaller "
                         "means the 1-block draft agrees more "
                         "(higher accept rate)")
    ap.add_argument("--record", metavar="PATH",
                    help="append the result row to this JSON file "
                         "(BENCH_*.json bookkeeping)")
    args = ap.parse_args(argv)
    # jax's own cache at the externally placed / fixed in-checkout path:
    # the temporary AOT entry directories below then never carry it
    from mxnet_tpu import config
    config.compile_cache_dir()

    if args.spec:
        ks = tuple(sorted({int(t) for t in args.spec_ks.split(",")
                           if t.strip()}))
        row = run_spec_sweep(
            requests=args.requests, slots=args.slots,
            max_len=args.max_len, mean_new=args.mean_new,
            vocab=args.vocab, d=args.spec_d, layers=args.layers,
            spec_ks=ks, repeats=args.repeat,
            tail_scale=args.tail_scale)
        print(json.dumps(row))
        if args.record:
            with open(args.record, "w") as f:
                json.dump({"spec_decode": row}, f, indent=1,
                          sort_keys=True)
                f.write("\n")
        bad_retr = sum(row["retraces"].values())
        if bad_retr:
            print("FAIL: %d post-warmup retraces (compile-once "
                  "contract across spec widths)" % bad_retr)
            return 1
        if not row["bitwise_identical"]:
            print("FAIL: speculative greedy decode diverged bitwise "
                  "from greedy_decode / the k=0 engine")
            return 1
        if row["aot_warm_compiles"]:
            print("FAIL: warm AOT restart of the spec engine "
                  "compiled %d programs (expected 0)"
                  % row["aot_warm_compiles"])
            return 1
        for k in ks:
            s = row["spec"]["k%d" % k]
            print("k=%d: %.1f tok/s (%.2fx vs single-token, "
                  "advisory; floor %.2f%%), accept %.1f%%, "
                  "%.2f tok/step"
                  % (k, s["tps"], s["speedup_vs_base"],
                     row["noise_floor"] * 1e2,
                     (s["accept_rate"] or 0.0) * 1e2,
                     s["tokens_per_step"] or 1.0))
        print("OK: bitwise + 0 retraces + warm AOT restart 0 "
              "compiles")
        return 0

    if args.replicas:
        counts = sorted({1} | {int(t) for t in args.replicas.split(",")
                               if t.strip()})
        row = run_replica_sweep(
            requests=args.requests, slots=args.slots,
            max_len=args.max_len, mean_new=args.mean_new,
            vocab=args.vocab, hidden=args.hidden,
            repeats=args.repeat, replica_counts=counts,
            layers=args.layers, fixed_len=args.fixed_len)
        print(json.dumps(row))
        if args.record:
            _merge_record(args.record, "decode", row)
        if row["retraces"]:
            print("FAIL: %d post-warmup retraces (compile-once "
                  "contract, per replica)" % row["retraces"])
            return 1
        if not row["bitwise_identical"]:
            print("FAIL: multi-replica decode diverged from the "
                  "single-replica engine")
            return 1
        if args.check_speedup is not None:
            if row["speedup"] < args.check_speedup:
                print("FAIL: %d-replica speedup %.2fx < required %.2fx"
                      % (counts[-1], row["speedup"],
                         args.check_speedup))
                return 1
            print("OK: %d-replica speedup %.2fx >= %.2fx"
                  % (counts[-1], row["speedup"], args.check_speedup))
        return 0

    if args.prefill:
        row = run_prefill_sweep(
            requests=args.requests, slots=args.slots,
            max_len=args.max_len, max_prompt=args.max_prompt,
            max_new=args.max_new, vocab=args.vocab,
            repeats=args.repeat)
        print(json.dumps(row))
        if args.record:
            with open(args.record, "w") as f:
                json.dump({"prefill_ttft": row}, f, indent=1,
                          sort_keys=True)
                f.write("\n")
        bad_retr = sum(row["retraces"].values())
        if bad_retr:
            print("FAIL: %d post-warmup retraces (compile-once "
                  "contract over the coalesced bucket grid)" % bad_retr)
            return 1
        if not row["bitwise_identical"]:
            print("FAIL: coalesced prefill diverged bitwise from the "
                  "serial path")
            return 1
        print("OK: coalesced/serial TTFT speedup %.2fx (advisory; "
              "A/A noise floor %.2f%%), bitwise + 0 retraces"
              % (row["ttft_speedup"], row["noise_floor"] * 1e2))
        return 0

    if args.telemetry:
        row = run_telemetry_overhead(
            requests=args.requests, slots=args.slots,
            max_len=args.max_len, mean_new=args.mean_new,
            vocab=args.vocab, hidden=args.hidden,
            repeats=args.repeat, tol=args.telemetry_tol,
            http=not args.no_http)
        print(json.dumps(row))
        if args.record:
            # section-merge so serve and decode gates can share one
            # BENCH_timeline.json (same discipline as BENCH_replica)
            _merge_record(args.record, "decode_telemetry_overhead", row)
        if not row["ok"]:
            print("FAIL: telemetry costs %.2f%% tokens/s "
                  "(tol %.2f%% + measured noise floor %.2f%%)"
                  % (row["regression"] * 1e2, row["tol"] * 1e2,
                     row["noise_floor"] * 1e2))
            return 1
        print("OK: decode telemetry overhead %.2f%% < %.2f%% tol "
              "+ %.2f%% noise floor"
              % (row["regression"] * 1e2, row["tol"] * 1e2,
                 row["noise_floor"] * 1e2))
        return 0

    best = run_bench(requests=args.requests, slots=args.slots,
                     max_len=args.max_len, mean_new=args.mean_new,
                     vocab=args.vocab, hidden=args.hidden,
                     repeat=args.repeat)
    print(json.dumps(best))
    print("best: %.1f tok/s continuous vs %.1f tok/s static "
          "(%.2fx, %d retraces)"
          % (best["continuous_tps"], best["static_tps"],
             best["speedup"], best["retraces"]))
    if args.record:
        with open(args.record, "w") as f:
            json.dump({"decode": best}, f, indent=1, sort_keys=True)
            f.write("\n")
    if best["retraces"]:
        print("FAIL: %d post-warmup retraces (compile-once contract)"
              % best["retraces"])
        return 1
    if args.check_speedup is not None and \
            best["speedup"] < args.check_speedup:
        print("FAIL: speedup %.2fx < required %.2fx"
              % (best["speedup"], args.check_speedup))
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Cold-vs-warm restart benchmark for the persistent AOT program cache.

The production failure mode ROADMAP item 2 names: a serving process
restart (reload loop, crash recovery, replica N+1 under load) pays the
full retrace storm before it can serve.  With ``MXNET_AOT_CACHE_DIR``
set, compiled programs are deployment artifacts — this bench measures
exactly what that buys:

- **cold**: empty cache directory — engine construction + ``warmup()``
  + first request, every bucket program traced and persisted;
- **warm**: same process, SAME directory — a fresh engine built over
  the now-populated cache: construction + warmup + first request again,
  with the compile counter required to stay at ZERO.

Both phases run for the one-shot ``ServingEngine`` (every pow2 bucket)
and the continuous-batching ``DecodeEngine`` (persistent step program,
prefill buckets, row-write kernels), on the deep-narrow bench models
the README noise protocol prescribes (depth makes Python trace time
the dominant cold cost, exactly like a real model graph).

Gates: the compile-count pin (``warm_compiles == 0 < cold_compiles``)
and output bitwise equality are HARD — they are the correctness
contract and host noise cannot excuse them.  The wall-clock speedup is
**advisory-only** per the README host-noise protocol (shared CI hosts
make single-digit-ms timing gates flaky); the recorded JSON carries
the measured ratios for humans and trend dashboards, not for exit
codes.

  python perf/restart_bench.py
  python perf/restart_bench.py --hidden 256 --layers 12
  python perf/restart_bench.py --record BENCH_aot.json
  python perf/restart_bench.py --cache-dir /var/aot --keep-cache

A fast smoke variant runs in tier-1
(tests/test_aot_cache.py::test_restart_bench_smoke).
"""
import argparse
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from serve_bench import build_model          # noqa: E402 (deep-narrow MLP)


def build_step_model(hidden=64, vocab=32, layers=4, seed=0):
    """A deep-narrow recurrent step graph: ``[logits, next_h]`` over
    ``token`` + state ``h`` — depth stacks FC+tanh blocks so the cold
    trace cost scales like a real decoder's."""
    import mxnet_tpu as mx
    rng = np.random.default_rng(seed)
    params = {}
    tok = mx.sym.Variable("token")
    h = mx.sym.Variable("h")
    x = mx.sym.Embedding(tok, input_dim=vocab, output_dim=hidden,
                         name="emb")
    params["emb_weight"] = mx.nd.array(
        rng.standard_normal((vocab, hidden)).astype(np.float32))
    x = x + h
    width = hidden
    for i in range(layers):
        name = "sfc%d" % i
        x = mx.sym.Activation(
            mx.sym.FullyConnected(x, num_hidden=hidden, name=name),
            act_type="tanh")
        params[name + "_weight"] = mx.nd.array(
            (rng.standard_normal((hidden, width)) * 0.1)
            .astype(np.float32))
        params[name + "_bias"] = mx.nd.zeros((hidden,))
        width = hidden
    logits = mx.sym.FullyConnected(x, num_hidden=vocab, name="sout")
    params["sout_weight"] = mx.nd.array(
        (rng.standard_normal((vocab, hidden)) * 0.1).astype(np.float32))
    params["sout_bias"] = mx.nd.zeros((vocab,))
    return (mx.sym.Group([logits, x]), params,
            [{"name": "h", "shape": (hidden,)}])


def _serve_phase(net, params, feature, requests):
    """One ServingEngine lifetime: construction -> warmup -> first
    request -> a short request stream.  Returns timings + compile
    count + the outputs (for the bitwise gate)."""
    from mxnet_tpu import serving
    rng = np.random.default_rng(7)
    X = rng.standard_normal((requests, feature)).astype(np.float32)
    t0 = time.perf_counter()
    eng = serving.ServingEngine(net, params, {},
                                {"data": (feature,)})
    t1 = time.perf_counter()
    eng.warmup()
    t2 = time.perf_counter()
    first = eng.predict(X[0], timeout=300)
    t3 = time.perf_counter()
    outs = [first] + [eng.predict(x, timeout=300) for x in X[1:]]
    compiles = eng.compile_count
    st = eng.stats()
    aot = st["aot"]
    # advisory: static planner watermark (analysis/memory.py)
    peak = st["memory"].get("predicted_peak_bytes")
    eng.close()
    return {"construct_s": t1 - t0, "warmup_s": t2 - t1,
            "first_request_s": t3 - t2,
            "ready_s": t3 - t0, "compiles": compiles,
            "predicted_peak_bytes": peak,
            "aot": aot, "outputs": outs}


def _decode_phase(step, sparams, state_info, prompts, max_new):
    from mxnet_tpu import serving
    t0 = time.perf_counter()
    eng = serving.DecodeEngine(step, sparams, {}, state_info,
                               num_slots=4, max_len=64,
                               default_deadline_ms=0)
    t1 = time.perf_counter()
    eng.warmup()
    t2 = time.perf_counter()
    first = eng.generate(prompts[0], max_new_tokens=max_new,
                         timeout=600)
    t3 = time.perf_counter()
    toks = [first.tokens] + [
        eng.generate(p, max_new_tokens=max_new, timeout=600).tokens
        for p in prompts[1:]]
    compiles = eng.compile_count
    st = eng.stats()["decode"]
    aot = st["aot"]
    # advisory: static planner watermark (analysis/memory.py)
    peak = st["memory"].get("predicted_peak_bytes")
    eng.close()
    return {"construct_s": t1 - t0, "warmup_s": t2 - t1,
            "first_request_s": t3 - t2,
            "ready_s": t3 - t0, "compiles": compiles,
            "predicted_peak_bytes": peak,
            "aot": aot, "outputs": toks}


def run_bench(feature=128, hidden=256, classes=10, layers=8,
              requests=16, step_hidden=64, step_layers=4, vocab=32,
              decode_requests=4, max_new=8, cache_dir=None,
              keep_cache=False, xla_cache=True):
    """Cold + warm phases for both engine kinds over one cache dir.
    Returns the BENCH_aot document (without host metadata)."""
    import mxnet_tpu  # noqa: F401  (path bootstrap)
    owned = cache_dir is None
    if owned:
        cache_dir = tempfile.mkdtemp(prefix="mxnet_aot_bench_")
    env0 = {k: os.environ.get(k)
            for k in ("MXNET_AOT_CACHE_DIR", "MXNET_AOT_CACHE",
                      "MXNET_AOT_XLA_CACHE")}
    os.environ["MXNET_AOT_CACHE_DIR"] = cache_dir
    os.environ.setdefault("MXNET_AOT_CACHE", "1")
    # the compounding knob: AOT entries remove the Python trace from a
    # warm start; jax's persistent compilation cache removes XLA's
    # compile of the deserialized module too.  It flips process-global
    # jax config, so the tier-1 smoke runs with xla_cache=False and
    # only the standalone bench turns it on.
    os.environ["MXNET_AOT_XLA_CACHE"] = "1" if xla_cache else "0"
    net, params = build_model(feature=feature, hidden=hidden,
                              classes=classes, layers=layers)
    step, sparams, state_info = build_step_model(
        hidden=step_hidden, vocab=vocab, layers=step_layers)
    prompts = [[1 + (i % (vocab - 2)), 2] for i in range(decode_requests)]
    doc = {"serve": {}, "decode": {}, "cache_dir": cache_dir}
    try:
        doc["serve"]["cold"] = _serve_phase(net, params, feature,
                                            requests)
        doc["serve"]["warm"] = _serve_phase(net, params, feature,
                                            requests)
        doc["decode"]["cold"] = _decode_phase(step, sparams, state_info,
                                              prompts, max_new)
        doc["decode"]["warm"] = _decode_phase(step, sparams, state_info,
                                              prompts, max_new)
        for kind in ("serve", "decode"):
            cold, warm = doc[kind]["cold"], doc[kind]["warm"]
            outs_c, outs_w = cold.pop("outputs"), warm.pop("outputs")
            bitwise = (len(outs_c) == len(outs_w)
                       and all(np.array_equal(a, b)
                               for a, b in zip(outs_c, outs_w)))
            doc[kind]["bitwise_equal"] = bool(bitwise)
            doc[kind]["ready_speedup"] = (
                cold["ready_s"] / warm["ready_s"]
                if warm["ready_s"] > 0 else float("inf"))
        n_entries = len([n for n in os.listdir(cache_dir)
                         if n.endswith(".json")])
        doc["cache_entries"] = n_entries
        doc["model"] = {"feature": feature, "hidden": hidden,
                        "layers": layers, "classes": classes,
                        "step_hidden": step_hidden,
                        "step_layers": step_layers, "vocab": vocab,
                        "requests": requests,
                        "decode_requests": decode_requests,
                        "max_new": max_new}
        doc["xla_cache"] = bool(xla_cache)
        return doc
    finally:
        # a bench must not leak env state into its caller's process
        # (the tier-1 smoke imports run_bench)
        for k, v in env0.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        if owned and not keep_cache:
            shutil.rmtree(cache_dir, ignore_errors=True)
            doc.pop("cache_dir", None)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--feature", type=int, default=128)
    ap.add_argument("--hidden", type=int, default=256)
    ap.add_argument("--layers", type=int, default=8,
                    help="deep-narrow depth (README noise protocol): "
                         "trace cost scales with depth like a real "
                         "model graph")
    ap.add_argument("--classes", type=int, default=10)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--step-hidden", type=int, default=64)
    ap.add_argument("--step-layers", type=int, default=4)
    ap.add_argument("--vocab", type=int, default=32)
    ap.add_argument("--decode-requests", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--cache-dir", default=None,
                    help="existing cache directory to reuse (default: "
                         "a fresh temp dir, removed afterwards)")
    ap.add_argument("--keep-cache", action="store_true")
    ap.add_argument("--no-xla-cache", action="store_true",
                    help="measure the jax.export layer alone, without "
                         "jax's persistent XLA compilation cache")
    ap.add_argument("--record", metavar="PATH",
                    help="write the JSON document (BENCH_aot.json)")
    args = ap.parse_args(argv)
    # jax's own cache at the externally placed / fixed in-checkout path:
    # the temporary AOT entry directories below then never carry it
    from mxnet_tpu import config
    config.compile_cache_dir()
    doc = run_bench(feature=args.feature, hidden=args.hidden,
                    classes=args.classes, layers=args.layers,
                    requests=args.requests,
                    step_hidden=args.step_hidden,
                    step_layers=args.step_layers, vocab=args.vocab,
                    decode_requests=args.decode_requests,
                    max_new=args.max_new, cache_dir=args.cache_dir,
                    keep_cache=args.keep_cache,
                    xla_cache=not args.no_xla_cache)
    doc["protocol"] = (
        "cold = empty cache (trace + persist); warm = fresh engine, "
        "same dir, same process.  compile-count pin and bitwise "
        "equality are hard gates; wall-clock ratios are advisory-only "
        "per the README host-noise protocol (single sample, shared "
        "hosts).")
    failures = []
    for kind in ("serve", "decode"):
        cold, warm = doc[kind]["cold"], doc[kind]["warm"]
        print("%s: cold %d compiles, ready %.3fs (construct %.3f / "
              "warmup %.3f / first %.3f)"
              % (kind, cold["compiles"], cold["ready_s"],
                 cold["construct_s"], cold["warmup_s"],
                 cold["first_request_s"]))
        print("%s: warm %d compiles, ready %.3fs, ready speedup "
              "%.2fx (advisory), bitwise_equal=%s"
              % (kind, warm["compiles"], warm["ready_s"],
                 doc[kind]["ready_speedup"],
                 doc[kind]["bitwise_equal"]))
        if not (cold["compiles"] > 0 and warm["compiles"] == 0):
            failures.append("%s: expected cold>0 and warm==0 compiles, "
                            "got cold=%d warm=%d"
                            % (kind, cold["compiles"],
                               warm["compiles"]))
        if not doc[kind]["bitwise_equal"]:
            failures.append("%s: warm outputs diverged from cold"
                            % kind)
    if args.record:
        with open(args.record, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
        print("recorded -> %s" % args.record)
    for f in failures:
        print("FAIL: %s" % f, file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())

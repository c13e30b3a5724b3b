"""Model-parallel serving benchmark — pjit-sharded replicas
(serving + parallel/mesh.py ShardingPlan, ROADMAP item 1).

What it measures: the data-parallel x model-parallel composition —
``replicas`` engine replicas, each compiling every program under a
``group``-device ShardingPlan — against the unsharded single-device
reference, on the deep-narrow bench models the README noise protocol
prescribes.  Three phases:

- **serve**: one-shot batch-axis-sharded serving (the plan partitions
  the pow2 batch bucket over the group; the padding verdict gate
  proves the graph row-local first, which is also why the sharded
  fleet must serve BITWISE vs the unsharded engine — each request's
  row computes on exactly one device with identical arithmetic);
- **decode**: continuous batching over a slot-axis-sharded pool
  (``state_rules`` lay the per-slot state out across the group;
  row-locality of the step makes the partition sound AND bitwise),
  staggered joins included;
- **aot**: a warm restart of the sharded serve engine from the
  persistent AOT cache — the sharded entries must load with ZERO
  traces and serve bitwise (key sharding component, residual b2).

Gates: equality with the unsharded engine (bitwise for decode tokens
and for a warm restart of the same programs; to a few ulp for one-shot
outputs, which the sharded fleet computes at other batch extents and
under another partition — different XLA programs), 0 warm retraces, and
warm-restart 0-compiles are HARD (they are the correctness contract; host noise
cannot excuse them).  Wall-clock ratios are **advisory-only** per the
README host-noise protocol — this forced-host-device CPU container
cannot resolve real multi-chip scaling (the BENCH file records the
measured numbers for humans and trend dashboards, not exit codes);
re-measure on real multi-chip hardware.

Needs ``replicas * group`` addressable devices::

  XLA_FLAGS=--xla_force_host_platform_device_count=4 \
      python perf/shard_bench.py --replicas 2 --group 2
  python perf/shard_bench.py --record BENCH_shard.json

A fast smoke runs in tier-1
(tests/test_sharding.py::test_shard_bench_smoke_forced_devices).
"""
import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from serve_bench import (build_model, closed_loop_round,     # noqa: E402
                         centered_sweep, _merge_record)
from restart_bench import build_step_model                   # noqa: E402


def serve_plan(group):
    """Batch-axis plan over a ``group``-device tp mesh (row-local
    graphs serve bitwise: each request's row lives on one device)."""
    return {"axes": {"tp": int(group)}, "batch_axis": "tp"}


def decode_plan(group):
    """Slot-axis plan: the pool's state buffers shard over the group
    (state_rules axis 0 — the slot-verdict-gated partition), sound and
    bitwise because the step verdict is row-local."""
    return {"axes": {"tp": int(group)},
            "state_rules": [[".*", ["tp"]]]}


def _device_count():
    import jax
    return len(jax.devices())


# ---------------------------------------------------------------------------
# serve phase
# ---------------------------------------------------------------------------

def run_serve_shard_sweep(requests=256, offered_batch=8, feature=256,
                          hidden=512, classes=10, layers=4,
                          batch_timeout_ms=2.0, repeats=3,
                          replicas=2, group=2):
    """Equality + retrace HARD gates, advisory rps ratio sharded (N
    replicas x G-device plans) vs the unsharded single-device engine.
    Equality is to a few ulp (``test_utils.few_ulp_tol``): the
    reference answers one request at a time, the
    fleet from coalesced batches split over each plan's devices."""
    from mxnet_tpu import serving
    net, params = build_model(feature=feature, hidden=hidden,
                              classes=classes, layers=layers)
    rng = np.random.default_rng(3)
    X = rng.standard_normal((requests, feature)).astype(np.float32)

    def build(g, n_replicas):
        eng = serving.ServingEngine(
            net, params, {}, {"data": (feature,)},
            batch_timeout_ms=batch_timeout_ms, replicas=n_replicas,
            sharding=serve_plan(g) if g > 1 else None)
        eng.warmup()
        return eng

    # hard gates first: equal to the unsharded reference, compile
    # counter pinned across the whole request stream
    from mxnet_tpu.test_utils import few_ulp_tol

    def same(got, want):
        return np.allclose(got, want, *few_ulp_tol(want))

    ref = build(1, 1)
    wants = [ref.predict(x, timeout=300) for x in X[:64]]
    ref.close()
    eng = build(group, replicas)
    c0 = eng.compile_count
    futs = [eng.submit(x) for x in X[:64]]
    matches = all(same(f.result(300), w) for f, w in zip(futs, wants))
    retraces = eng.compile_count - c0
    shard_desc = eng.stats()["replicas"]
    eng.close()

    def run_one(g):
        eng = build(g, replicas if g > 1 else 1)
        closed_loop_round(eng, X, min(64, requests), offered_batch)
        t0 = time.perf_counter()
        closed_loop_round(eng, X, requests, offered_batch)
        dt = time.perf_counter() - t0
        eng.close()
        return requests / dt

    best, ratios = centered_sweep((1, group), run_one, repeats)
    return {"kind": "serve", "requests": requests,
            "feature": feature, "hidden": hidden, "layers": layers,
            "replicas": replicas, "group": group,
            "device_count": _device_count(),
            "plan": serve_plan(group),
            "matches_unsharded_few_ulp": bool(matches),
            "retraces": int(retraces),
            "replica_shards": [r.get("shards") for r in shard_desc],
            "rps": {str(k): v for k, v in best.items()},
            "speedup_vs_unsharded": ratios.get(group),
            "timings_advisory": True}


# ---------------------------------------------------------------------------
# decode phase
# ---------------------------------------------------------------------------

def run_decode_shard_sweep(requests=16, slots=4, max_len=32, mean_new=8,
                           hidden=64, vocab=32, layers=2, repeats=2,
                           replicas=2, group=2):
    """Continuous batching over a slot-axis-sharded pool: staggered
    joins bitwise vs greedy_decode, 0 warm retraces; advisory
    tokens/s ratio vs the unsharded engine."""
    from mxnet_tpu import serving
    step, params, state_info = build_step_model(hidden=hidden,
                                                vocab=vocab,
                                                layers=layers)
    rng = np.random.default_rng(5)
    prompts = [[int(t) for t in
                rng.integers(1, vocab, rng.integers(1, 4))]
               for _ in range(requests)]
    budgets = [int(b) for b in
               rng.integers(2, max(3, 2 * mean_new), requests)]
    ref_prog = serving.StepProgram(step, params, {}, state_info, slots)
    wants = [serving.greedy_decode(ref_prog, p, b, max_len=max_len)
             for p, b in zip(prompts, budgets)]

    def build(g, n_replicas):
        eng = serving.DecodeEngine(
            step, params, {}, state_info, num_slots=slots,
            max_len=max_len, replicas=n_replicas,
            sharding=decode_plan(g) if g > 1 else None)
        eng.warmup()
        return eng

    eng = build(group, replicas)
    c0 = eng.compile_count
    futs = []
    for p, b in zip(prompts, budgets):
        futs.append(eng.submit(p, b))
        time.sleep(0.002)               # staggered joins
    bitwise = all(np.array_equal(f.result(600).tokens, w)
                  for f, w in zip(futs, wants))
    retraces = eng.compile_count - c0
    shard_desc = eng.stats()["decode"]["replicas"]
    eng.close()

    def run_one(g):
        eng = build(g, replicas if g > 1 else 1)
        t0 = time.perf_counter()
        futs = [eng.submit(p, b) for p, b in zip(prompts, budgets)]
        toks = sum(len(f.result(600).tokens) for f in futs)
        dt = time.perf_counter() - t0
        eng.close()
        return toks / dt

    best, ratios = centered_sweep((1, group), run_one, repeats)
    return {"kind": "decode", "requests": requests, "slots": slots,
            "max_len": max_len, "hidden": hidden, "layers": layers,
            "replicas": replicas, "group": group,
            "device_count": _device_count(),
            "plan": decode_plan(group),
            "bitwise_identical": bool(bitwise),
            "retraces": int(retraces),
            "replica_shards": [r.get("shards") for r in shard_desc],
            "tokens_per_s": {str(k): v for k, v in best.items()},
            "speedup_vs_unsharded": ratios.get(group),
            "timings_advisory": True}


# ---------------------------------------------------------------------------
# AOT warm-restart phase
# ---------------------------------------------------------------------------

def run_shard_aot_gate(feature=64, hidden=64, layers=2, replicas=2,
                       group=2, cache_dir=None):
    """Warm restart of a SHARDED engine: every entry written under the
    plan's key sharding component must load with zero traces and serve
    bitwise (hard gates)."""
    import shutil
    import tempfile
    from mxnet_tpu import serving
    net, params = build_model(feature=feature, hidden=hidden,
                              layers=layers)
    owned = cache_dir is None
    cache_dir = cache_dir or tempfile.mkdtemp(prefix="shard_aot_")
    old = os.environ.get("MXNET_AOT_CACHE_DIR")
    os.environ["MXNET_AOT_CACHE_DIR"] = cache_dir
    try:
        rng = np.random.default_rng(11)
        X = rng.standard_normal((8, feature)).astype(np.float32)
        eng = serving.ServingEngine(net, params, {},
                                    {"data": (feature,)},
                                    replicas=replicas,
                                    sharding=serve_plan(group))
        eng.warmup()
        wants = [eng.predict(x, timeout=300) for x in X]
        cold_compiles = eng.compile_count
        eng.close()
        eng = serving.ServingEngine(net, params, {},
                                    {"data": (feature,)},
                                    replicas=replicas,
                                    sharding=serve_plan(group))
        eng.warmup()
        warm_compiles = eng.compile_count
        bitwise = all(np.array_equal(eng.predict(x, timeout=300), w)
                      for x, w in zip(X, wants))
        aot = eng.stats()["aot"]
        eng.close()
    finally:
        if old is None:
            os.environ.pop("MXNET_AOT_CACHE_DIR", None)
        else:
            os.environ["MXNET_AOT_CACHE_DIR"] = old
        if owned:
            shutil.rmtree(cache_dir, ignore_errors=True)
    return {"kind": "aot", "replicas": replicas, "group": group,
            "plan": serve_plan(group),
            "cold_compiles": int(cold_compiles),
            "warm_compiles": int(warm_compiles),
            "bitwise_identical": bool(bitwise),
            "warm_hits": aot["hits"], "warm_rejects": aot["rejects"]}


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="model-parallel (pjit-sharded replica) serving "
                    "benchmark; hard gates bitwise + 0 retraces, "
                    "timings advisory per the host-noise protocol")
    ap.add_argument("--requests", type=int, default=256)
    ap.add_argument("--decode-requests", type=int, default=16)
    ap.add_argument("--offered-batch", type=int, default=8)
    ap.add_argument("--feature", type=int, default=256)
    ap.add_argument("--hidden", type=int, default=512)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=32)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--group", type=int, default=2)
    ap.add_argument("--skip-decode", action="store_true")
    ap.add_argument("--record", metavar="PATH",
                    help="merge results into a BENCH_shard.json-style "
                         "document (serve/decode/aot sections)")
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    # jax's own cache at the externally placed / fixed in-checkout path:
    # the temporary AOT entry directories below then never carry it
    from mxnet_tpu import config
    config.compile_cache_dir()

    need = args.replicas * args.group
    if _device_count() < need:
        print("shard_bench: %d devices needed (%d replicas x %d-device "
              "plans) but %d present; set XLA_FLAGS="
              "--xla_force_host_platform_device_count=%d"
              % (need, args.replicas, args.group, _device_count(),
                 need), file=sys.stderr)
        return 2

    rows = {}
    rows["serve"] = run_serve_shard_sweep(
        requests=args.requests, offered_batch=args.offered_batch,
        feature=args.feature, hidden=args.hidden, layers=args.layers,
        repeats=args.repeats, replicas=args.replicas, group=args.group)
    if not args.skip_decode:
        rows["decode"] = run_decode_shard_sweep(
            requests=args.decode_requests, slots=args.slots,
            max_len=args.max_len, hidden=min(args.hidden, 64),
            repeats=max(1, args.repeats - 1),
            replicas=args.replicas, group=args.group)
    rows["aot"] = run_shard_aot_gate(feature=min(args.feature, 64),
                                     hidden=min(args.hidden, 64),
                                     replicas=args.replicas,
                                     group=args.group)

    ok = True
    for name, row in rows.items():
        equal = row["matches_unsharded_few_ulp"] if name == "serve" \
            else row["bitwise_identical"]
        gate_ok = equal and \
            row.get("retraces", 0) == 0 and \
            (name != "aot" or row["warm_compiles"] == 0)
        ok = ok and gate_ok
        print("%-6s  equal=%s  retraces=%s  %s  [%s]"
              % (name, equal,
                 row.get("retraces", "-"),
                 ("speedup=%.2fx (advisory)"
                  % row["speedup_vs_unsharded"])
                 if row.get("speedup_vs_unsharded") else
                 "cold=%s warm=%s" % (row.get("cold_compiles"),
                                      row.get("warm_compiles")),
                 "OK" if gate_ok else "FAIL"))
    if args.record:
        for name, row in rows.items():
            _merge_record(args.record, name, row)
        print("recorded -> %s" % args.record)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

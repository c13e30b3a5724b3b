#!/usr/bin/env python3
"""Bring-up smoke: the program's main paths once, on the TPU, through the
entry points a user calls.

    python chip_smoke.py               # one chip: train, decode, kv
    python chip_smoke.py --four-chips  # four chips: train4, serve4 only

Default run, one chip, three phases:

- ``train``  ResNet-50 at its published width (the symbol
  examples/train_imagenet.py builds) through ``mx.mod.Module``: bind,
  init_params, init_optimizer, then a few forward_backward + update
  steps on one fixed seeded batch — the path ``Module.fit`` drives;
- ``decode`` ``serving.DecodeEngine`` over the stacked-LSTM language
  model of perf/decode_bench.py at vocab 10,000 / hidden 1,500 / 32
  slots, 16 concurrent greedy requests;
- ``kv``     the same engine over the attention step with
  ``{"cache": True}`` states, so the KV-cache write op compiles in the
  implementation ``MXNET_CACHE_SCATTER_IMPL=auto`` picks on the chip,
  compared with the same requests under the XLA implementation.

``--four-chips`` runs only what exists across chips and what it is
compared with: ``train4`` (the ``train`` model data-parallel under a
``ShardingPlan`` against the same steps on chip 0) and ``serve4`` (the
``decode`` engine with four replicas against one).

Each phase prints one JSON line (sizes, seconds, counts, devices); those
seconds say where a run spent its time and are not measurements to quote.
The last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}``.
A failed check or a raising phase ends the run non-zero with no such
line, and so does any backend that is not a TPU: the sizes below are not
reachable from the command line, only through ``main(sizes=...)`` which
the tests use (tests/test_chip_smoke.py).

One process, no child; weights and data come from ``--seed``; the JAX
compile cache lives where ``mxnet_tpu.config.compile_cache_dir`` says.
"""
import argparse
import gc
import json
import os
import sys
import threading
import time

import numpy as np

SIZES = {
    "train": dict(num_layers=50, image=224, num_classes=1000, batch=256,
                  steps=5, steps4=3, lr=0.05, momentum=0.9),
    "decode": dict(vocab=10000, layers=2, hidden=1500, slots=32,
                   max_len=512, requests=16, requests4=32,
                   prompt=(8, 64), new_tokens=32),
    "kv": dict(vocab=32000, d=1024, blocks=4, slots=8, max_len=2048,
               requests=8, prompt=16, new_tokens=16),
}


class SmokeFailure(Exception):
    """A check of a phase was false."""


def _check(ok, what):
    if not ok:
        raise SmokeFailure(what)


class _CompileCache:
    """Compile requests, and hits and misses of JAX's persistent
    compilation cache, read off jax.monitoring: each phase line can say
    whether its programs were compiled or loaded, and a program that XLA
    builds again without a new trace still shows as a request."""
    _EVENTS = {"/jax/compilation_cache/compile_requests_use_cache":
               "requests",
               "/jax/compilation_cache/cache_hits": "hits",
               "/jax/compilation_cache/cache_misses": "misses"}

    def __init__(self):
        import jax
        self.counts = {"requests": 0, "hits": 0, "misses": 0}
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_kw):
        key = self._EVENTS.get(event)
        if key:
            self.counts[key] += 1

    def since(self, before):
        return {k: self.counts[k] - before[k] for k in before}


def _emit(phase, **fields):
    print(json.dumps(dict(phase=phase, **fields)), flush=True)


def _devices_of(arrays, platform):
    """Sorted device names holding ``arrays``; every one must be a
    ``platform`` device — the check that nothing fell to the host."""
    devs = set()
    for a in arrays:
        devs.update(a.devices())
    stray = sorted(str(d) for d in devs if d.platform != platform)
    _check(not stray, "arrays on %s, expected only %s devices"
           % (stray, platform))
    return sorted(str(d) for d in devs)


def _rescaled(params):
    """perf/decode_bench.py draws every weight at a fixed spread, which
    saturates the gates at real widths and would make a logits check
    blind; bring each matrix but the embedding to 1/sqrt(fan_in)."""
    import mxnet_tpu as mx
    out = {}
    for name, v in params.items():
        a = v.asnumpy()
        if a.ndim == 2 and not name.startswith("emb"):
            a = a / np.sqrt(a.shape[1]) / max(float(a.std()), 1e-6)
        out[name] = mx.nd.array(a.astype(np.float32))
    return out


# ---------------------------------------------------------------- train

def bound_train_module(sz, ctx, mesh_devices=None):
    """The ``train`` phase's Module, bound at its batch: the symbol
    examples/train_imagenet.py builds, data-parallel over
    ``mesh_devices`` under a ShardingPlan when they are given."""
    import mxnet_tpu as mx
    from mxnet_tpu.models import get_resnet_symbol
    batch, image = sz["batch"], sz["image"]
    net = get_resnet_symbol(num_classes=sz["num_classes"],
                            num_layers=sz["num_layers"],
                            image_shape=(3, image, image), layout="NHWC")
    mod = mx.mod.Module(net, context=ctx)
    if mesh_devices is not None:
        from mxnet_tpu.parallel.mesh import ShardingPlan, make_mesh
        mod.set_sharding_plan(ShardingPlan(
            make_mesh({"dp": len(mesh_devices)}, devices=mesh_devices),
            batch_axis="dp"))
    mod.bind(data_shapes=[("data", (batch, image, image, 3))],
             label_shapes=[("softmax_label", (batch,))])
    return mod


def _train_run(sz, seed, platform, steps, cache, mesh_devices=None):
    """``steps`` Module training steps on one fixed batch.  Returns the
    phase record and the bound Module."""
    import mxnet_tpu as mx
    from mxnet_tpu import executor

    np.random.seed(seed)          # mx.init draws from numpy's global rng
    mx.random.seed(seed)
    batch, image = sz["batch"], sz["image"]
    ctx = mx.Context(platform, 0)
    mod = bound_train_module(sz, ctx, mesh_devices)
    mod.init_params(mx.init.Xavier(rnd_type="gaussian", factor_type="in",
                                   magnitude=2))
    mod.init_optimizer(optimizer="sgd", optimizer_params=(
        ("learning_rate", sz["lr"]), ("momentum", sz["momentum"])))

    rng = np.random.default_rng(seed)
    data = rng.standard_normal((batch, image, image, 3)).astype(np.float32)
    label = rng.integers(0, sz["num_classes"], batch)
    feed = mx.io.DataBatch(
        data=[mx.nd.array(data, ctx=ctx)],
        label=[mx.nd.array(label.astype(np.float32), ctx=ctx)])

    traces0 = executor.xla_traces_ever()
    losses, secs, traces, compiles = [], [], [], []
    for _ in range(steps):
        requests0 = cache.counts["requests"]
        t0 = time.perf_counter()
        mod.forward_backward(feed)
        mod.update()
        probs = mod.get_outputs()[0].asnumpy()      # waits for the step
        secs.append(time.perf_counter() - t0)
        losses.append(float(-np.mean(np.log(
            probs[np.arange(batch), label] + 1e-8))))
        traces.append(executor.xla_traces_ever() - traces0)
        compiles.append(cache.counts["requests"] - requests0)

    exe = mod._exec
    n_dev = 1 if mesh_devices is None else len(mesh_devices)
    params = [exe.arg_dict[n]._data for n in mod._param_names]
    out = mod.get_outputs()[0]._data
    rec = dict(
        model="resnet-%d" % sz["num_layers"], batch=batch,
        image=[image, image, 3], dtype=str(params[0].dtype),
        steps=steps, losses=[round(v, 5) for v in losses],
        xla_traces_after_step=traces, compile_requests_per_step=compiles,
        step_s=[round(v, 3) for v in secs],
        compile_s=round(sum(secs) - steps * min(secs), 2),
        run_s=round(steps * min(secs), 3),
        param_devices=_devices_of(params, platform),
        output_shape=list(out.shape),
        output_devices=_devices_of([out], platform),
        data_devices=_devices_of([exe.arg_dict["data"]._data], platform))
    _check(all(np.isfinite(losses)), "train: loss not finite: %s" % losses)
    _check(tuple(out.shape) == (batch, sz["num_classes"]),
           "train: output shape %s" % (out.shape,))
    _check(len(rec["param_devices"]) == n_dev
           and len(rec["data_devices"]) == n_dev,
           "train: expected params and data on %d device(s), got %s / %s"
           % (n_dev, rec["param_devices"], rec["data_devices"]))
    _check(traces == [1] * steps,
           "train: XLA traces after each step %s, expected one at the "
           "first step and none after" % traces)
    return rec, mod


def train_step_program(mod):
    """The jitted forward+backward step a bound Module dispatches, and
    the arguments it takes — for ``fn.lower(*args)`` here and, with the
    arguments described on a chip that is not attached, in
    tests/test_chip_compile.py."""
    import jax
    exe = mod._exec
    fn = exe._get_fwd_bwd(False)
    old = tuple(exe.grad_dict[n]._data for n in exe._dense_grad_names)
    return fn, (exe._arg_vals(), exe._aux_vals(), jax.random.PRNGKey(0),
                old)


def phase_train(sizes, seed, platform, cache):
    sz = sizes["train"]
    before = dict(cache.counts)
    rec, _mod = _train_run(sz, seed, platform, sz["steps"], cache)
    _check(rec["losses"][-1] < rec["losses"][0],
           "train: loss did not fall on the fixed batch: %s"
           % rec["losses"])
    _emit("train", compile_cache=cache.since(before), **rec)


def phase_train4(sizes, seed, platform, cache):
    import jax
    sz = sizes["train"]
    before = dict(cache.counts)
    # one chip first: on jax 0.9.0 a single-device trace that follows a
    # sharded one in the same process inherits the mesh in the types of
    # its broadcast constants and traces its step a second time
    rec1, mod1 = _train_run(sz, seed, platform, sz["steps4"], cache)
    del mod1
    gc.collect()                  # frees chip 0 for the sharded run
    rec4, mod4 = _train_run(sz, seed, platform, sz["steps4"], cache,
                            mesh_devices=jax.devices()[:4])
    fn, args = train_step_program(mod4)
    has_all_reduce = "all-reduce" in fn.lower(*args).compile().as_text()
    del fn, args
    del mod4
    rel = [abs(a - b) / abs(b)
           for a, b in zip(rec4["losses"], rec1["losses"])]
    _check(max(rel) <= 1e-2,
           "train4: losses %s under the plan against %s on one chip"
           % (rec4["losses"], rec1["losses"]))
    _check(has_all_reduce, "train4: no all-reduce in the compiled step")
    _emit("train4", compile_cache=cache.since(before), mesh={"dp": 4},
          losses_one_chip=rec1["losses"],
          loss_rel_diff=[float("%.2e" % r) for r in rel],
          all_reduce_in_step=has_all_reduce,
          one_chip={k: rec1[k] for k in ("step_s", "param_devices",
                                         "compile_requests_per_step")},
          **rec4)


# --------------------------------------------------------------- decode

def _lstm_model(sz, seed):
    from perf.decode_bench import build_model
    step, params, state_info = build_model(
        vocab=sz["vocab"], embed=sz["hidden"], hidden=sz["hidden"],
        seed=seed, layers=sz["layers"])
    return step, _rescaled(params), state_info


def _prompts(rng, n, length, vocab):
    lo, hi = (length, length) if isinstance(length, int) else length
    return [rng.integers(1, vocab, int(rng.integers(lo, hi + 1))).tolist()
            for _ in range(n)]


def _serve(eng, prompts, new_tokens, platform, what):
    """Warm ``eng``, offer every prompt at once, drain, and hold the run
    to the engine's own contracts.  Returns the phase record."""
    t0 = time.perf_counter()
    warm = eng.warmup()
    compile_s = time.perf_counter() - t0
    served_by = [set() for _ in prompts]

    def hook(i):
        return lambda _tok: served_by[i].add(
            threading.current_thread().name)

    t0 = time.perf_counter()
    futs = [eng.submit(p, max_new_tokens=new_tokens, on_token=hook(i))
            for i, p in enumerate(prompts)]
    results = [f.result(timeout=600) for f in futs]
    run_s = time.perf_counter() - t0
    st = eng.stats()
    dec = st["decode"]
    lost = {k: st[k] for k in ("rejected", "shed", "pressure_shed",
                               "expired") if st[k]}
    _check(not lost, "%s: admission lost requests: %s" % (what, lost))
    _check(all(r.finish_reason == "length" and len(r.tokens) == new_tokens
               for r in results),
           "%s: not every request finished with %d tokens: %s"
           % (what, new_tokens, results))
    _check(dec["requests_served"] == len(prompts) and not dec["evictions"],
           "%s: served %d of %d, %d evicted" % (
               what, dec["requests_served"], len(prompts),
               dec["evictions"]))
    _check(eng.compile_count == warm,
           "%s: %d program traces after warmup() counted %d"
           % (what, eng.compile_count, warm))
    reps = eng._replicas
    for r in reps:
        _check(r.healthy, "%s: replica %s unhealthy" % (what, r.label))
    return dict(
        slots=eng.num_slots, max_len=eng.max_len, requests=len(prompts),
        prompt_tokens=sum(len(p) for p in prompts),
        new_tokens=new_tokens, steps=dec["steps"],
        tokens_generated=dec["tokens_generated"],
        compile_count=warm, retraces_after_warmup=0,
        compile_s=round(compile_s, 2), run_s=round(run_s, 3),
        param_devices=[_devices_of(
            [a for a in r.program._template if a is not None], platform)
            for r in reps],
        state_devices=[_devices_of(r.states.values(), platform)
                       for r in reps],
        tokens=[r.tokens.tolist() for r in results],
        served_by=[sorted(s) for s in served_by])


def _lstm_reference_logits(params, token, states):
    """One stacked-LSTM step in plain float32 jax.numpy over a dict of
    jax arrays: gate order i, f, c, o; ``states`` is [(h, c)] per
    layer."""
    import jax
    import jax.numpy as jnp
    p = params
    x = p["emb_weight"][jnp.asarray(token)]
    for i, (h, c) in enumerate(states):
        pre = "lstm%d_" % i
        gates = (x @ p[pre + "i2h_weight"].T + p[pre + "i2h_bias"]
                 + jnp.asarray(h) @ p[pre + "h2h_weight"].T
                 + p[pre + "h2h_bias"])
        gi, gf, gc_, go = jnp.split(gates, 4, axis=1)
        c2 = jax.nn.sigmoid(gf) * jnp.asarray(c) \
            + jax.nn.sigmoid(gi) * jnp.tanh(gc_)
        x = jax.nn.sigmoid(go) * jnp.tanh(c2)
    return np.asarray(x @ p["out_fc_weight"].T + p["out_fc_bias"])


def _first_step_logits_error(step, params, state_info, sz, prompt, seed,
                             ctx):
    """Logits of the step graph for one request's first token, through
    ``Predictor`` on ``ctx``, against the plain reference on the same
    weights and the same (seeded, non-zero) incoming state, both at the
    device's default matmul precision.  Returns the largest difference
    over the largest logit."""
    from mxnet_tpu.predict import Predictor
    rng = np.random.default_rng(seed + 1)
    hidden = sz["hidden"]
    feeds = {s["name"]: (0.5 * rng.standard_normal((1, hidden))
                         ).astype(np.float32) for s in state_info}
    shapes = dict({"token": (1,)}, **{k: v.shape for k, v in feeds.items()})
    pred = Predictor(step[0], params, {}, shapes, ctx=ctx)
    token = np.asarray(prompt[:1], np.float32)
    got = pred.forward(token=token, **feeds).get_output(0)
    states = [(feeds["lstm%d_h" % i], feeds["lstm%d_c" % i])
              for i in range(sz["layers"])]
    import jax.numpy as jnp
    want = _lstm_reference_logits(
        {k: jnp.asarray(v.asnumpy()) for k, v in params.items()},
        np.asarray(prompt[:1]), states)
    _check(got.shape == want.shape == (1, sz["vocab"])
           and np.all(np.isfinite(got)),
           "decode: logits shape %s / finite" % (got.shape,))
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def phase_decode(sizes, seed, platform, cache):
    import mxnet_tpu as mx
    from mxnet_tpu import serving
    sz = sizes["decode"]
    before = dict(cache.counts)
    ctx = mx.Context(platform, 0)
    step, params, state_info = _lstm_model(sz, seed)
    prompts = _prompts(np.random.default_rng(seed), sz["requests"],
                       sz["prompt"], sz["vocab"])
    eng = serving.DecodeEngine(step, params, {}, state_info,
                               num_slots=sz["slots"],
                               max_len=sz["max_len"], ctx=ctx)
    try:
        rec = _serve(eng, prompts, sz["new_tokens"], platform, "decode")
    finally:
        eng.close()
    err = _first_step_logits_error(step, params, state_info, sz,
                                   prompts[0], seed, ctx)
    _check(err <= 1e-3, "decode: first-step logits differ from the "
           "jax.numpy reference by %.3g of the largest logit" % err)
    del rec["tokens"], rec["served_by"]
    _emit("decode", model="lstm-lm", vocab=sz["vocab"],
          layers=sz["layers"], hidden=sz["hidden"], dtype="float32",
          logits_rel_err_vs_reference=float("%.3g" % err),
          compile_cache=cache.since(before), **rec)


def phase_serve4(sizes, seed, platform, cache):
    import mxnet_tpu as mx
    from mxnet_tpu import serving
    sz = sizes["decode"]
    before = dict(cache.counts)
    step, params, state_info = _lstm_model(sz, seed)
    prompts = _prompts(np.random.default_rng(seed), sz["requests4"],
                       sz["prompt"], sz["vocab"])
    recs = {}
    for n in (4, 1):
        eng = serving.DecodeEngine(
            step, params, {}, state_info, num_slots=sz["slots"],
            max_len=sz["max_len"], ctx=mx.Context(platform, 0),
            replicas=n)
        try:
            recs[n] = _serve(eng, prompts, sz["new_tokens"], platform,
                             "serve4[replicas=%d]" % n)
        finally:
            eng.close()
    r4, r1 = recs[4], recs[1]
    homes = [d for devs in r4["param_devices"] for d in devs]
    _check(len(homes) == 4 and len(set(homes)) == 4,
           "serve4: replica params on %s, expected four distinct devices"
           % r4["param_devices"])
    workers = set(w for s in r4["served_by"] for w in s)
    _check(len(workers) == 4,
           "serve4: requests were generated by %s, expected all four "
           "replica schedulers" % sorted(workers))
    _check(r4["tokens"] == r1["tokens"],
           "serve4: tokens differ between four replicas and one")
    per_replica = {w: sum(w in s for s in r4["served_by"])
                   for w in sorted(workers)}
    for r in (r4, r1):
        del r["tokens"], r["served_by"]
    _emit("serve4", model="lstm-lm", vocab=sz["vocab"],
          layers=sz["layers"], hidden=sz["hidden"], dtype="float32",
          replicas=4, requests_per_replica=per_replica,
          tokens_equal_single_replica=True,
          one_replica={k: r1[k] for k in ("compile_s", "run_s", "steps",
                                          "param_devices")},
          compile_cache=cache.since(before), **r4)


# ------------------------------------------------------------------- kv

def _kv_model(sz, seed):
    from perf.decode_bench import build_spec_models
    target, t_info, _draft, _d_info, params = build_spec_models(
        vocab=sz["vocab"], d=sz["d"], max_len=sz["max_len"],
        layers=sz["blocks"], seed=seed)
    return target, _rescaled(params), t_info


def decode_step_program(prog):
    """A StepProgram's jitted persistent step and the arguments it takes
    over a fresh pool (see train_step_program)."""
    z = np.zeros((prog.num_slots,), np.float32)
    flat = prog._build_flat(z, z, z, prog.init_states())
    return prog._jit_kernel, (prog._key, np.int32(0), z,
                              prog._ids_like) + tuple(flat)


def phase_kv(sizes, seed, platform, cache):
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import serving
    from mxnet_tpu.ops.cache import _impl_mode
    sz = sizes["kv"]
    before = dict(cache.counts)
    _check("MXNET_CACHE_SCATTER_IMPL" not in os.environ,
           "kv: MXNET_CACHE_SCATTER_IMPL is set; the phase checks what "
           "'auto' picks")
    ctx = mx.Context(platform, 0)
    target, params, t_info = _kv_model(sz, seed)
    prompts = _prompts(np.random.default_rng(seed), sz["requests"],
                       sz["prompt"], sz["vocab"])
    pool = jax.ShapeDtypeStruct((sz["slots"], sz["max_len"], sz["d"]),
                                np.float32)
    recs = {}
    for impl in ("auto", "xla"):
        if impl != "auto":
            os.environ["MXNET_CACHE_SCATTER_IMPL"] = impl
        try:
            resolved = _impl_mode(pool)
            eng = serving.DecodeEngine(
                target, params, {}, t_info, num_slots=sz["slots"],
                max_len=sz["max_len"], ctx=ctx)
            try:
                rec = _serve(eng, prompts, sz["new_tokens"], platform,
                             "kv[%s]" % impl)
                selection = eng.selection
                fn, args = decode_step_program(eng._replicas[0].program)
                text = fn.lower(*args).compile().as_text()
                del fn, args
            finally:
                eng.close()
        finally:
            os.environ.pop("MXNET_CACHE_SCATTER_IMPL", None)
        _check([s["op"] for s in selection or []]
               == ["_cache_write_row"] * (2 * sz["blocks"]),
               "kv[%s]: the step does not write its caches through "
               "_cache_write_row: %s" % (impl, selection))
        rec["impl"] = resolved
        rec["tpu_custom_call_in_step"] = "tpu_custom_call" in text
        recs[impl] = rec
    auto, xla = recs["auto"], recs["xla"]
    _check(auto["tpu_custom_call_in_step"] == (auto["impl"] == "pallas"),
           "kv: 'auto' resolved to %s but tpu_custom_call in the "
           "compiled step is %s" % (auto["impl"],
                                    auto["tpu_custom_call_in_step"]))
    _check(not xla["tpu_custom_call_in_step"],
           "kv: a tpu_custom_call in the step compiled under 'xla'")
    _check(auto["tokens"] == xla["tokens"],
           "kv: tokens under %s differ from tokens under xla"
           % auto["impl"])
    for r in (auto, xla):
        del r["tokens"], r["served_by"]
    _emit("kv", model="attention-step", vocab=sz["vocab"], d=sz["d"],
          blocks=sz["blocks"], dtype="float32",
          auto_resolved_to=auto["impl"],
          tokens_equal_xla=True,
          bfloat16="not run: DecodeEngine(dtype=bfloat16) builds a "
          "bfloat16 pool, but the step graph returns float32 next "
          "states, so the pool is neither donated nor stable in dtype",
          forced_xla={k: xla[k] for k in ("compile_s", "run_s", "steps",
                                          "tpu_custom_call_in_step")},
          compile_cache=cache.since(before), **auto)


# ----------------------------------------------------------------- main

def main(argv=None, sizes=None, platform="tpu"):
    """Run the phases and print the final line; returns the exit code.
    ``sizes`` and ``platform`` are for the CPU rehearsal in the tests —
    no command-line argument reaches them."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run train4 and serve4 on four chips, and no "
                    "other phase")
    args = ap.parse_args(argv)
    sizes = sizes or SIZES

    import jax
    from mxnet_tpu import config
    dev = jax.devices()[0]
    need = 4 if args.four_chips else 1
    if dev.platform != platform or len(jax.devices()) < need:
        print("chip_smoke: needs %d %s device(s); JAX found %s"
              % (need, platform, jax.devices()), file=sys.stderr)
        return 1
    cache_dir = config.compile_cache_dir()
    cache = _CompileCache()
    _emit("start", seed=args.seed, compile_cache_dir=cache_dir,
          jax=jax.__version__, devices=[str(d) for d in jax.devices()])
    phases = (phase_train4, phase_serve4) if args.four_chips \
        else (phase_train, phase_decode, phase_kv)
    for phase in phases:
        phase(sizes, args.seed, platform, cache)
        gc.collect()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The decode step's expert layer alone, on the chip: the plain products
against the grouped kernel (``ops/transformer.py`` ``moe_grouped``) and
against XLA's ``ragged_dot``, at the step shapes of the two expert cells.

    python perf/moe_probe.py                 # one TPU; JSON lines out
    python perf/moe_probe.py --reps 20 --calls 5

Each variant runs ``--reps`` layers in one program (the input of a layer
is the last one's input plus a hundredth of its output, so that nothing
is hoisted) and is called ``--calls`` times after a warm call; a line
gives the median milliseconds a layer, the bytes of the experts it
must read (every hit expert's three matrices once) over that time, the
routed pairs' load (max over mean), and for the grouped variants the
largest difference from the plain path's output in units of the
output's largest value.  Any backend but a TPU exits 1.
"""
import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np      # noqa: E402

# (name, rows, top_k, experts, width, hidden, routing, activation)
SHAPES = [("lfm2_step", 256, 4, 32, 1792, 2048, "sigmoid", "silu"),
          ("smallthinker_step", 32, 6, 64, 768, 2560, "softmax", "relu")]


def _inputs(rows, k, n_exp, f, d, routing, skew, seed=0):
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    bf = jnp.bfloat16
    x = jnp.asarray(rng.standard_normal((rows, d)), bf)
    # a popular expert is a column offset of the logits: ``skew`` 1
    # reads a load max over mean of about 3-4 at LFM2's shape, as the
    # benchmark's routing does
    r = rng.standard_normal((rows, n_exp)) \
        + skew * rng.standard_normal((1, n_exp))
    w = [jnp.asarray(rng.standard_normal((n_exp, f, d)) / np.sqrt(d), bf)
         for _ in range(3)]
    bias = jnp.asarray(rng.standard_normal(n_exp) * 0.1, jnp.float32) \
        if routing == "sigmoid" else None
    return x, jnp.asarray(r, jnp.float32), w, bias


def _ragged(x, top_i, w, wg, wu, wd, act_fn):
    """The pairs sorted by expert, unpadded, through two
    ``ragged_dot_general`` products and back."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    m, d = x.shape
    k = top_i.shape[1]
    held = wg.shape[0]
    e = top_i.T.reshape(-1)
    order = jnp.argsort(e, stable=True)
    sizes = jnp.zeros((held,), jnp.int32).at[e].add(1)
    xs = x[order % m]
    nt = lax.RaggedDotDimensionNumbers(
        dot_dimension_numbers=(((1,), (2,)), ((), ())),
        lhs_ragged_dimensions=(0,), rhs_group_dimensions=(0,))
    nn = lax.RaggedDotDimensionNumbers(
        dot_dimension_numbers=(((1,), (1,)), ((), ())),
        lhs_ragged_dimensions=(0,), rhs_group_dimensions=(0,))
    gate = lax.ragged_dot_general(xs, wg, sizes, nt,
                                  preferred_element_type=jnp.float32)
    up = lax.ragged_dot_general(xs, wu, sizes, nt,
                                preferred_element_type=jnp.float32)
    a = act_fn(gate) * up * w.T.reshape(-1)[order][:, None]
    ys = lax.ragged_dot_general(a.astype(x.dtype), wd, sizes, nn,
                                preferred_element_type=jnp.float32)
    back = jnp.zeros_like(ys).at[order].set(ys)
    return jnp.sum(back.reshape(k, m, d), axis=0)


def _traced(run, inputs, where, reps):
    """One call of ``run`` under the profiler: its device ops' time,
    milliseconds a layer, the largest first."""
    import jax
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    import trace_reduce
    jax.profiler.start_trace(where)
    try:
        run(*inputs).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    red = trace_reduce.reduce(trace_reduce.load(where), top=12)
    return [[n, 1e3 * s / reps] for n, s in red["device_ops"]] \
        + [["busy", 1e3 * red["busy_s"] / reps]]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--skew", default="1,0",
                    help="routings to run, by the spread of the experts' "
                         "popularity (comma-separated)")
    ap.add_argument("--trace", default="",
                    help="directory: trace one call of the plain and the "
                         "default grouped variant and print its device ops")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax import lax
    from mxnet_tpu.ops import transformer as tf
    from mxnet_tpu.ops.registry import get_op
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("moe_probe: no TPU (backend %r)" % dev.platform,
              file=sys.stderr)
        return 1
    op = get_op("_moe_experts")
    for (name, rows, k, n_exp, f, d, routing, act), skew in (
            (s, float(x)) for s in SHAPES for x in args.skew.split(",")):
        attrs = op.normalize({"top_k": k, "routing": routing,
                              "activation": act,
                              "expert_bias": routing == "sigmoid"})
        x, r, w, bias = _inputs(rows, k, n_exp, f, d, routing, skew)
        extra = [] if bias is None else [bias]
        top_i, wt = tf._moe_route(attrs, r, bias)
        load = np.bincount(np.asarray(top_i).ravel(), minlength=n_exp)
        hit = int((load > 0).sum())
        act_fn = tf._ACTIVATIONS[act]
        # the plain products: the op as a training trace lowers it
        plain_op = op.bound(attrs, training=True)

        def plain(x, r, *ws):
            return plain_op(x, r, *ws)[0]

        def grouped(x, r, *ws, **kw):
            ti, wr = tf._moe_route(attrs, r, ws[3] if len(ws) > 3 else None)
            return tf.moe_grouped(x, ti, wr, *ws[:3], activation=act, **kw) \
                .astype(x.dtype)

        def ragged(x, r, *ws):
            ti, wr = tf._moe_route(attrs, r, ws[3] if len(ws) > 3 else None)
            return _ragged(x, ti, wr, *ws[:3], act_fn).astype(x.dtype)

        variants = [("plain", plain), ("grouped_default", grouped)]
        for t in (16, 32):
            for tf_ in (256, tf._moe_width_tile(f, d, 2)):
                variants.append(("grouped_t%d_w%d" % (t, tf_),
                                 functools.partial(grouped, tile=t,
                                                   width_tile=tf_)))
        if skew:
            variants.append(("ragged_dot", ragged))
        want = None
        for vname, fn in variants:
            def many(x, r, *ws, fn=fn):
                def body(i, xc):
                    y = fn(xc, r, *ws)
                    return (xc.astype(jnp.float32)
                            + 0.01 * y.astype(jnp.float32)).astype(xc.dtype)
                return lax.fori_loop(0, args.reps, body, x)
            line = {"shape": name, "variant": vname, "skew": skew,
                    "rows": rows,
                    "top_k": k, "experts": n_exp, "hit": hit,
                    "load_max_over_mean": float(load.max() / load.mean()),
                    "device": dev.device_kind}
            try:
                one = jax.jit(fn)
                y = np.asarray(one(x, r, *w, *extra), np.float32)
                if want is None:
                    want = y
                line["max_diff_over_max"] = float(
                    np.abs(y - want).max() / np.abs(want).max())
                run = jax.jit(many)
                t0 = time.perf_counter()
                run(x, r, *w, *extra).block_until_ready()
                line["compile_s"] = time.perf_counter() - t0
                times = []
                for _ in range(args.calls):
                    t0 = time.perf_counter()
                    run(x, r, *w, *extra).block_until_ready()
                    times.append(time.perf_counter() - t0)
                ms = 1e3 * float(np.median(times)) / args.reps
                line["ms_per_layer"] = ms
                line["hit_expert_gb_per_s"] = \
                    hit * 3 * f * d * 2 / (ms * 1e-3) / 1e9
                if args.trace and vname in ("plain", "grouped_default"):
                    line["device_ops_ms_per_layer"] = _traced(
                        run, (x, r, *w, *extra), os.path.join(
                            args.trace, "%s_%s_%g" % (name, vname, skew)),
                        args.reps)
            except Exception as e:      # a variant that fails is a finding
                line["error"] = repr(e)[:400]
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

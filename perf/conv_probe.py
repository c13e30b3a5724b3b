"""Per-shape conv probe: native XLA conv vs dot_general reformulation.

The calls here are intentionally unchained micro-ops: read the numbers as
a ranking of formulations per shape, and confirm any winner on the whole
step with perf/step_bench.py or an xplane trace.

For each distinct (fwd / dgrad / wgrad) conv in ResNet-50 (batch 256, NHWC,
bf16) this times the lax.conv_general_dilated form XLA autodiff produces
against an explicit MXU-matmul reformulation:

  * 1x1 stride-1 conv  == matmul over (N*H*W, Cin) x (Cin, Cout)
  * 1x1 stride-s fwd   == subsample then matmul
  * 1x1 stride-s dgrad == matmul then interior-dilate (lax.pad)
  * 1x1 stride-s wgrad == subsample x then matmul
  * 3x3 wgrad          == optional im2col matmul (bandwidth-heavy; measured)

Timing: marginal K2-K1 chained-dispatch protocol so the fixed per-sync
cost cancels.  Prints a table + JSON lines.

Usage: python perf/conv_probe.py [--quick]
"""
import argparse
import functools
import json
import time

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

DT = jnp.bfloat16

# (name, H, Cin, Cout, K, stride)  -- batch fixed at 256, square spatial/kernel
RESNET50_CONVS = [
    ("stem7x7",    224,    3,   64, 7, 2),
    ("s1_in1x1",    56,  256,   64, 1, 1),
    ("s1_3x3",      56,   64,   64, 3, 1),
    ("s1_out1x1",   56,   64,  256, 1, 1),
    ("s2_in1x1",    56,  256,  128, 1, 1),
    ("s2_3x3s2",    56,  128,  128, 3, 2),
    ("s2_proj",     56,  256,  512, 1, 2),
    ("s2_in1x1b",   28,  512,  128, 1, 1),
    ("s2_3x3",      28,  128,  128, 3, 1),
    ("s2_out1x1",   28,  128,  512, 1, 1),
    ("s3_in1x1",    28,  512,  256, 1, 1),
    ("s3_3x3s2",    28,  256,  256, 3, 2),
    ("s3_proj",     28,  512, 1024, 1, 2),
    ("s3_in1x1b",   14, 1024,  256, 1, 1),
    ("s3_3x3",      14,  256,  256, 3, 1),
    ("s3_out1x1",   14,  256, 1024, 1, 1),
    ("s4_in1x1",    14, 1024,  512, 1, 1),
    ("s4_3x3s2",    14,  512,  512, 3, 2),
    ("s4_proj",     14, 1024, 2048, 1, 2),
    ("s4_in1x1b",    7, 2048,  512, 1, 1),
    ("s4_3x3",       7,  512,  512, 3, 1),
    ("s4_out1x1",    7,  512, 2048, 1, 1),
]

QUICK = [
    ("s4_in1x1b",    7, 2048,  512, 1, 1),
    ("s4_out1x1",    7,  512, 2048, 1, 1),
    ("s3_in1x1b",   14, 1024,  256, 1, 1),
    ("s3_out1x1",   14,  256, 1024, 1, 1),
]

DN = ("NHWC", "OHWI", "NHWC")


def native_fwd(x, w, stride, pad):
    return lax.conv_general_dilated(
        x, w, window_strides=(stride, stride), padding=[(pad, pad)] * 2,
        dimension_numbers=DN, preferred_element_type=x.dtype)


def native_dgrad(x, w, dy, stride, pad):
    _, vjp = jax.vjp(lambda x_: native_fwd(x_, w, stride, pad), x)
    return vjp(dy)[0]


def native_wgrad(x, w, dy, stride, pad):
    _, vjp = jax.vjp(lambda w_: native_fwd(x, w_, stride, pad), w)
    return vjp(dy)[0]


# --- 1x1 reformulations (pad must be 0) ---

def mm_fwd_1x1(x, w, stride):
    if stride > 1:
        x = x[:, ::stride, ::stride, :]
    n, h, wd, ci = x.shape
    co = w.shape[0]
    y = lax.dot_general(x.reshape(n * h * wd, ci), w.reshape(co, ci),
                        (((1,), (1,)), ((), ())),
                        preferred_element_type=x.dtype)
    return y.reshape(n, h, wd, co)


def mm_dgrad_1x1(dy, w, stride, in_h):
    n, h, wd, co = dy.shape
    ci = w.shape[-1]
    dx = lax.dot_general(dy.reshape(n * h * wd, co), w.reshape(co, ci),
                         (((1,), (0,)), ((), ())),
                         preferred_element_type=dy.dtype)
    dx = dx.reshape(n, h, wd, ci)
    if stride > 1:
        # scatter back to strided positions: interior-dilate + edge pad
        extra = in_h - ((h - 1) * stride + 1)
        dx = lax.pad(dx, jnp.zeros((), dx.dtype),
                     ((0, 0, 0), (0, extra, stride - 1),
                      (0, extra, stride - 1), (0, 0, 0)))
    return dx


def mm_wgrad_1x1(x, dy, stride):
    if stride > 1:
        x = x[:, ::stride, ::stride, :]
    n, h, wd, ci = x.shape
    co = dy.shape[-1]
    dw = lax.dot_general(dy.reshape(n * h * wd, co), x.reshape(n * h * wd, ci),
                         (((0,), (0,)), ((), ())),
                         preferred_element_type=x.dtype)
    return dw.reshape(co, 1, 1, ci)


# --- 3x3 wgrad via im2col matmul ---

def im2col_wgrad(x, dy, k, stride, pad):
    n, h, wd, ci = x.shape
    _, oh, ow, co = dy.shape
    patches = lax.conv_general_dilated_patches(
        x, (k, k), (stride, stride), [(pad, pad)] * 2,
        dimension_numbers=DN, preferred_element_type=x.dtype)
    # patches: (n, oh, ow, ci*k*k) with feature order (ci, kh, kw)
    p2 = patches.reshape(n * oh * ow, ci * k * k)
    dw = lax.dot_general(dy.reshape(n * oh * ow, co), p2,
                         (((0,), (0,)), ((), ())),
                         preferred_element_type=x.dtype)
    dw = dw.reshape(co, ci, k, k).transpose(0, 2, 3, 1)
    return dw


def time_compiled(fn, args, k1=10, k2=40, reps=2):
    c = jax.jit(fn).lower(*args).compile()
    out = c(*args)
    jax.tree_util.tree_map(lambda a: a.block_until_ready(), out)
    marg = []
    for _ in range(reps):
        el = {}
        for K in (k1, k2):
            t0 = time.perf_counter()
            for _i in range(K):
                out = c(*args)
            jax.tree_util.tree_map(lambda a: a.block_until_ready(), out)
            el[K] = time.perf_counter() - t0
        marg.append((el[k2] - el[k1]) / (k2 - k1))
    return min(marg)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--batch", type=int, default=256)
    args = ap.parse_args()
    shapes = QUICK if args.quick else RESNET50_CONVS
    n = args.batch
    dev = jax.devices()[0]
    peak = 197e12 if "v5" in getattr(dev, "device_kind", "") else None
    print(f"device={dev.device_kind if hasattr(dev, 'device_kind') else dev}"
          f" batch={n}")
    rng = np.random.default_rng(0)
    rows = []
    for name, h, ci, co, k, stride in shapes:
        pad = k // 2 if k > 1 else 0
        oh = (h + 2 * pad - k) // stride + 1
        flops = 2 * n * oh * oh * k * k * ci * co
        x = jnp.asarray(rng.standard_normal((n, h, h, ci)), DT)
        w = jnp.asarray(rng.standard_normal((co, k, k, ci)), DT)
        dy = jnp.asarray(rng.standard_normal((n, oh, oh, co)), DT)
        row = {"name": name, "h": h, "ci": ci, "co": co, "k": k, "s": stride,
               "gflop": round(flops / 1e9, 2)}
        cases = {
            "fwd": (lambda x, w, dy: native_fwd(x, w, stride, pad)),
            "dgrad": (lambda x, w, dy: native_dgrad(x, w, dy, stride, pad)),
            "wgrad": (lambda x, w, dy: native_wgrad(x, w, dy, stride, pad)),
        }
        if k == 1:
            cases["mm_fwd"] = lambda x, w, dy: mm_fwd_1x1(x, w, stride)
            cases["mm_dgrad"] = lambda x, w, dy: mm_dgrad_1x1(dy, w, stride, h)
            cases["mm_wgrad"] = lambda x, w, dy: mm_wgrad_1x1(x, dy, stride)
        else:
            cases["im2col_wgrad"] = \
                lambda x, w, dy: im2col_wgrad(x, dy, k, stride, pad)
        for cname, fn in cases.items():
            try:
                dt = time_compiled(fn, (x, w, dy))
                eff = flops / dt / peak if peak else 0.0
                row[cname + "_us"] = round(dt * 1e6, 1)
                row[cname + "_eff"] = round(eff, 3)
            except Exception as e:
                row[cname + "_us"] = None
                print(f"  {name} {cname} FAILED: {e!r}")
        print(json.dumps(row))
        rows.append(row)
    # summary: where does the reformulation win?
    print("\n=== wins (reform faster than native) ===")
    for r in rows:
        for d in ("fwd", "dgrad", "wgrad"):
            alt = ("mm_" + d) if r["k"] == 1 else ("im2col_" + d)
            if r.get(alt + "_us") and r.get(d + "_us") and \
                    r[alt + "_us"] < r[d + "_us"]:
                print(f"{r['name']:12s} {d}: native {r[d+'_us']:8.1f}us "
                      f"(eff {r[d+'_eff']:.2f}) -> {alt} {r[alt+'_us']:8.1f}us "
                      f"(eff {r[alt+'_eff']:.2f})")


if __name__ == "__main__":
    main()

"""Block-scope fused residual unit — the Pallas kernel tier.

An earlier chip record, since deleted, showed the ResNet-50 train step
HBM-bound with XLA already within 1.4% of its per-op roofline; the only
remaining lever is removing PASSES, and the measured failure of the
1x1-scope attempt (ops/nn.py _fused1x1_bwd_pallas) showed a winning
kernel must swallow the surrounding BN/ReLU elementwise chains so the
custom_vjp boundary stops costing materializations.  This is that tier —
the analog of the reference's swappable fused-backend layer
(src/operator/nn/cudnn/cudnn_convolution-inl.h): same op surface, fused
kernels underneath.

Decomposition ("sandwich"): a pre-activation bottleneck unit
    out = conv3(relu(bn3(conv2(relu(bn2(conv1(relu(bn1(data)))))))) + data
materializes ONLY the raw conv outputs (y1, y2) and the unit output —
tensors any schedule must materialize.  Each conv becomes one Pallas
kernel that
  * normalizes+relus its INPUT in the prologue (from the producer's raw
    output + that BN's batch stats, passed as per-channel vectors),
  * runs the matmul / 3x3 tap-sum on the MXU with f32 accumulation,
  * accumulates the batch stats of its OUTPUT in the epilogue
so the normalized activations never cross HBM.  Backward mirrors it:
each kernel computes dgrad AND wgrad from the same resident cotangent
tile, masks through the recomputed ReLU, accumulates the BN reductions
(sum dP, sum dP*xhat) in the epilogue, and the BN-backward correction
(which needs the COMPLETED reductions) is folded into the NEXT kernel's
prologue as three per-channel vectors:
    g_raw = c1*dP + u0 + u1*y_raw,
      c1 = gamma*inv,  u0 = -c1*(dbeta + dgamma*(-mu*inv))/M,
      u1 = -c1*dgamma*inv/M.

Only stride-1 dim-match bottleneck units are fused (transition units
keep the XLA path); the op surface (`_contrib_FusedBottleneckUnit`)
takes the same parameters as the unfused subgraph so checkpoints are
interchangeable.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from .registry import register, P
from ..base import MXNetError

EPS_DEFAULT = 2e-5


def _interpret():
    return jax.devices()[0].platform != "tpu"


def _row_block(rows, ci, co, bwd=False):
    """Largest row tile that divides `rows` and fits VMEM: ~12 bytes per
    row-element across the live bf16 blocks + f32 temporaries, plus the
    resident weight (and, in backward, its f32 gradient block)."""
    fixed = ci * co * (6 if bwd else 2)
    budget = 9 * 1024 * 1024 - fixed
    per_row = (ci + co) * 12
    for br in (4096, 2048, 1024, 512, 256, 128, 64, 32, 16, 8, 4, 2, 1):
        if rows % br == 0 and br * per_row <= budget:
            return br
    return 1


def _batch_tile(n, bytes_per_item, fixed_bytes=0):
    """Largest batch tile whose per-step VMEM footprint fits the ~16MB
    scoped limit with headroom for double-buffering."""
    budget = 10 * 1024 * 1024 - fixed_bytes
    for bn in (16, 8, 4, 2, 1):
        if n % bn == 0 and bn * bytes_per_item <= budget:
            return bn
    return 1


# ---------------------------------------------------------------------------
# Forward kernels
# ---------------------------------------------------------------------------

def _k_matmul_fwd(x_ref, w_ref, sc_ref, sh_ref, y_ref, s_ref, ss_ref,
                  *, with_stats):
    """y = relu(x*sc + sh) @ w; epilogue accumulates sum / sum-of-squares
    of the STORED (output-dtype) y per channel."""
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)
    a = jnp.maximum(x * sc_ref[...] + sh_ref[...], 0).astype(x_ref.dtype)
    y = jnp.dot(a, w_ref[...], preferred_element_type=jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)
    if with_stats:
        # stats from the f32 accumulator already in registers (one less
        # convert pass; bf16 storage rounding is zero-mean noise on the
        # batch statistics)
        ps = jnp.sum(y, axis=0, keepdims=True)
        pss = jnp.sum(y * y, axis=0, keepdims=True)

        @pl.when(i == 0)
        def _():
            s_ref[...] = ps
            ss_ref[...] = pss

        @pl.when(i > 0)
        def _():
            s_ref[...] += ps
            ss_ref[...] += pss


def _k_matmul_skip_fwd(x_ref, w_ref, sc_ref, sh_ref, skip_ref, y_ref):
    """y = relu(x*sc + sh) @ w + skip (the unit-closing 1x1 + residual
    add in one pass)."""
    x = x_ref[...].astype(jnp.float32)
    a = jnp.maximum(x * sc_ref[...] + sh_ref[...], 0).astype(x_ref.dtype)
    y = jnp.dot(a, w_ref[...], preferred_element_type=jnp.float32)
    y_ref[...] = (y + skip_ref[...].astype(jnp.float32)).astype(y_ref.dtype)


def _k_conv3_fwd(x_ref, w_ref, sc_ref, sh_ref, y_ref, s_ref, ss_ref):
    """3x3/s1/p1: y[n,i,j] = sum_taps relu(x*sc+sh) shifted @ w[tap];
    epilogue stats of y."""
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)                    # (BN, H, W, Ci)
    bn_, h, w, ci = x.shape
    co = w_ref.shape[-1]
    a = jnp.maximum(x * sc_ref[...] + sh_ref[...], 0).astype(x_ref.dtype)
    ap = jnp.pad(a, ((0, 0), (1, 1), (1, 1), (0, 0)))
    acc = jnp.zeros((bn_ * h * w, co), jnp.float32)
    for dh in range(3):
        for dw in range(3):
            patch = ap[:, dh:dh + h, dw:dw + w, :].reshape(bn_ * h * w, ci)
            acc += jnp.dot(patch, w_ref[dh, dw],
                           preferred_element_type=jnp.float32)
    y_ref[...] = acc.reshape(bn_, h, w, co).astype(y_ref.dtype)
    ps = jnp.sum(acc, axis=0).reshape(1, co)
    pss = jnp.sum(acc * acc, axis=0).reshape(1, co)

    @pl.when(i == 0)
    def _():
        s_ref[...] = ps
        ss_ref[...] = pss

    @pl.when(i > 0)
    def _():
        s_ref[...] += ps
        ss_ref[...] += pss


# --- 3x3 over the 2D row layout ------------------------------------------
#
# Two things held the 4D 3x3 kernels back on the chip: Mosaic's
# strided spatial slicing of (BN,H,W,C) tiles runs far below line rate,
# and every 4D<->2D crossing between Pallas and XLA pays a relayout
# copy.  These kernels keep the SAME flattened (rows, C) layout the 1x1
# sandwich kernels use: with blocks aligned to whole images, a 3x3 tap
# is a STATIC row shift of (dh*W + dw) (pltpu.roll) gated by a per-row
# validity mask computed from iota (rows where h+dh / w+dw leave the
# image — which also kills roll wrap-around and cross-image leakage).

def _tap_mask(rows, h, w, dh, dw):
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    hh = (r // w) % h + dh
    ww = r % w + dw
    ok = (hh >= 0) & (hh < h) & (ww >= 0) & (ww < w)
    return ok.astype(jnp.float32)


def _k_conv3_fwd_2d(x_ref, w_ref, sc_ref, sh_ref, y_ref, s_ref, ss_ref,
                    *, h, w):
    from jax.experimental.pallas import tpu as pltpu
    i = pl.program_id(0)
    rows, ci = x_ref.shape
    co = w_ref.shape[-1]
    x = x_ref[...].astype(jnp.float32)
    a32 = jnp.maximum(x * sc_ref[...] + sh_ref[...], 0)
    a = a32.astype(x_ref.dtype)
    acc = jnp.zeros((rows, co), jnp.float32)
    for dh in (-1, 0, 1):
        for dw in (-1, 0, 1):
            off = dh * w + dw
            # Mosaic rotate is 32-bit-only: roll the f32 copy, cast after
            shifted = pltpu.roll(a32, (-off) % rows, 0).astype(a.dtype) \
                if off else a
            m = _tap_mask(rows, h, w, dh, dw)
            acc += jnp.dot(shifted, w_ref[dh + 1, dw + 1],
                           preferred_element_type=jnp.float32) * m
    y_ref[...] = acc.astype(y_ref.dtype)
    ps = jnp.sum(acc, axis=0, keepdims=True)
    pss = jnp.sum(acc * acc, axis=0, keepdims=True)

    @pl.when(i == 0)
    def _():
        s_ref[...] = ps
        ss_ref[...] = pss

    @pl.when(i > 0)
    def _():
        s_ref[...] += ps
        ss_ref[...] += pss


def _k_conv3_bwd_2d(dpn_ref, y2_ref, c1_ref, u0_ref, u1_ref,
                    y1_ref, wt_ref, sc_ref, sh_ref, xs_ref, xh_ref,
                    dp_ref, dw_ref, db_ref, dg_ref, *, h, w):
    """2D-row-layout 3x3 backward: finalize g (deferred bn3 vectors),
    per-tap wgrad (dW_t = (M_t . S_t(a))^T g) and dgrad
    (da = sum_t S_{-t}(M_t . (g @ W_t^T))), ReLU mask + BN reductions."""
    from jax.experimental.pallas import tpu as pltpu
    i = pl.program_id(0)
    rows, ci = y1_ref.shape
    co = y2_ref.shape[-1]
    g = c1_ref[...] * dpn_ref[...].astype(jnp.float32) + u0_ref[...] \
        + u1_ref[...] * y2_ref[...].astype(jnp.float32)
    g = g.astype(dpn_ref.dtype)
    x = y1_ref[...].astype(jnp.float32)
    a32 = jnp.maximum(x * sc_ref[...] + sh_ref[...], 0)
    a = a32.astype(y1_ref.dtype)
    da = jnp.zeros((rows, ci), jnp.float32)
    for dh in (-1, 0, 1):
        for dw_ in (-1, 0, 1):
            off = dh * w + dw_
            m = _tap_mask(rows, h, w, dh, dw_)
            sa = pltpu.roll(a32, (-off) % rows, 0).astype(a.dtype) \
                if off else a
            sam = sa * m.astype(sa.dtype)
            part = lax.dot_general(sam, g, (((0,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)

            @pl.when(i == 0)
            def _(part=part, dh=dh, dw_=dw_):
                dw_ref[dh + 1, dw_ + 1] = part

            @pl.when(i > 0)
            def _(part=part, dh=dh, dw_=dw_):
                dw_ref[dh + 1, dw_ + 1] += part
            tmp = jnp.dot(g, wt_ref[dh + 1, dw_ + 1],
                          preferred_element_type=jnp.float32) * m
            da += pltpu.roll(tmp, off % rows, 0) if off else tmp
    mask = (a32 > 0).astype(jnp.float32)
    dp = da * mask
    dp_ref[...] = dp.astype(dp_ref.dtype)
    dbp = jnp.sum(dp, axis=0, keepdims=True)
    xhat = x * xs_ref[...] + xh_ref[...]
    dgp = jnp.sum(dp * xhat, axis=0, keepdims=True)

    @pl.when(i == 0)
    def _():
        db_ref[...] = dbp
        dg_ref[...] = dgp

    @pl.when(i > 0)
    def _():
        db_ref[...] += dbp
        dg_ref[...] += dgp


# ---------------------------------------------------------------------------
# Backward kernels
# ---------------------------------------------------------------------------
#
# Shared shape: the conv has input x_raw (R, Ci) (raw producer output,
# normalized by this conv's prologue BN) and output y_raw (R, Co).  The
# incoming cotangent is either FINAL (g at y_raw, unit boundary) or
# DEFERRED (dP of the consumer's BN + that BN's finalize vectors).

def _k_matmul_bwd(g_ref, yraw_ref, c1_ref, u0_ref, u1_ref,
                  x_ref, wt_ref, sc_ref, sh_ref, xs_ref, xh_ref,
                  dp_ref, dw_ref, db_ref, dg_ref, *, deferred):
    """dgrad + wgrad + ReLU mask + BN reductions, one resident pass.

    g := c1*g_in + u0 + u1*y_raw  (finalize the consumer BN's backward)
         when `deferred`, else g := g_in.
    da = g @ wt ; a = relu(x*sc+sh) recomputed ; dW += a^T @ g
    dP = da * (a > 0) ; db += sum dP ; dg += sum dP * (x*xs + xh).
    wt arrives pre-transposed (Co, Ci) — the conv weight's NATIVE layout
    — so the dgrad matmul is standard orientation (no per-step
    transposes inside the kernel).
    """
    i = pl.program_id(0)
    g = g_ref[...].astype(jnp.float32)
    if deferred:
        g = c1_ref[...] * g + u0_ref[...] \
            + u1_ref[...] * yraw_ref[...].astype(jnp.float32)
    g = g.astype(g_ref.dtype)
    x = x_ref[...].astype(jnp.float32)
    a32 = jnp.maximum(x * sc_ref[...] + sh_ref[...], 0)
    a = a32.astype(x_ref.dtype)
    da = jnp.dot(g, wt_ref[...],
                 preferred_element_type=jnp.float32)           # (BR, Ci)
    dwp = lax.dot_general(a, g, (((0,), (0,)), ((), ())),
                          preferred_element_type=jnp.float32)  # (Ci, Co)
    # f32 compare: Mosaic has no bf16 vector cmp on this target
    mask = (a32 > 0).astype(jnp.float32)
    dp = da * mask
    dp_ref[...] = dp.astype(dp_ref.dtype)
    dbp = jnp.sum(dp, axis=0, keepdims=True)
    xhat = x * xs_ref[...] + xh_ref[...]
    dgp = jnp.sum(dp * xhat, axis=0, keepdims=True)

    @pl.when(i == 0)
    def _():
        dw_ref[...] = dwp
        db_ref[...] = dbp
        dg_ref[...] = dgp

    @pl.when(i > 0)
    def _():
        dw_ref[...] += dwp
        db_ref[...] += dbp
        dg_ref[...] += dgp


def _k_conv3_bwd(dpn_ref, y2_ref, c1_ref, u0_ref, u1_ref,
                 y1_ref, w_ref, sc_ref, sh_ref, xs_ref, xh_ref,
                 dp_ref, dw_ref, db_ref, dg_ref):
    """3x3/s1/p1 backward: finalize g from the consumer BN (deferred
    vectors), dgrad via rot-180 tap sum, wgrad per tap, ReLU mask + BN2
    reductions — all from one residency of (g, y1, y2) tiles."""
    i = pl.program_id(0)
    g = c1_ref[...] * dpn_ref[...].astype(jnp.float32) + u0_ref[...] \
        + u1_ref[...] * y2_ref[...].astype(jnp.float32)
    g = g.astype(dpn_ref.dtype)                           # (BN, H, W, Co)
    bn_, h, w, co = g.shape
    ci = y1_ref.shape[-1]
    x = y1_ref[...].astype(jnp.float32)
    a32 = jnp.maximum(x * sc_ref[...] + sh_ref[...], 0)
    a = a32.astype(y1_ref.dtype)
    ap = jnp.pad(a, ((0, 0), (1, 1), (1, 1), (0, 0)))
    gp = jnp.pad(g, ((0, 0), (1, 1), (1, 1), (0, 0)))
    gf = g.reshape(bn_ * h * w, co)
    da = jnp.zeros((bn_ * h * w, ci), jnp.float32)
    for dh in range(3):
        for dw_ in range(3):
            patch = ap[:, dh:dh + h, dw_:dw_ + w, :] \
                .reshape(bn_ * h * w, ci)
            part = lax.dot_general(patch, gf, (((0,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)
            # static-index ref store: accumulate each tap's wgrad in
            # place, no (3,3,Ci,Co) stack held live
            @pl.when(i == 0)
            def _(part=part, dh=dh, dw_=dw_):
                dw_ref[dh, dw_] = part

            @pl.when(i > 0)
            def _(part=part, dh=dh, dw_=dw_):
                dw_ref[dh, dw_] += part
            gpatch = gp[:, 2 - dh:2 - dh + h, 2 - dw_:2 - dw_ + w, :] \
                .reshape(bn_ * h * w, co)
            # wt_ref is (3, 3, Co, Ci): standard-orientation dgrad matmul
            da += jnp.dot(gpatch, w_ref[dh, dw_],
                          preferred_element_type=jnp.float32)
    mask = (a32.reshape(bn_ * h * w, ci) > 0).astype(jnp.float32)
    dp = da * mask
    dp_ref[...] = dp.reshape(bn_, h, w, ci).astype(dp_ref.dtype)
    dbp = jnp.sum(dp, axis=0, keepdims=True)
    xhat = x.reshape(bn_ * h * w, ci) * xs_ref[...] + xh_ref[...]
    dgp = jnp.sum(dp * xhat, axis=0, keepdims=True)

    @pl.when(i == 0)
    def _():
        db_ref[...] = dbp
        dg_ref[...] = dgp

    @pl.when(i > 0)
    def _():
        db_ref[...] += dbp
        dg_ref[...] += dgp


# ---------------------------------------------------------------------------
# pallas_call wrappers
# ---------------------------------------------------------------------------

def _vec(v):
    return v.reshape(1, -1).astype(jnp.float32)


def _mm_fwd(x2d, w2d, sc, sh, with_stats, out_dtype):
    rows, ci = x2d.shape
    co = w2d.shape[1]
    br = _row_block(rows, ci, co)
    outs = [jax.ShapeDtypeStruct((rows, co), out_dtype),
            jax.ShapeDtypeStruct((1, co), jnp.float32),
            jax.ShapeDtypeStruct((1, co), jnp.float32)]
    kern = functools.partial(_k_matmul_fwd, with_stats=with_stats)
    y, s, ss = pl.pallas_call(
        kern,
        name="fu_mm_fwd",
        grid=(rows // br,),
        in_specs=[pl.BlockSpec((br, ci), lambda i: (i, 0)),
                  pl.BlockSpec((ci, co), lambda i: (0, 0)),
                  pl.BlockSpec((1, ci), lambda i: (0, 0)),
                  pl.BlockSpec((1, ci), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((br, co), lambda i: (i, 0)),
                   pl.BlockSpec((1, co), lambda i: (0, 0)),
                   pl.BlockSpec((1, co), lambda i: (0, 0))],
        out_shape=outs,
        interpret=_interpret())(x2d, w2d, _vec(sc), _vec(sh))
    return y, s[0], ss[0]


def _mm_skip_fwd(x2d, w2d, sc, sh, skip2d, out_dtype):
    rows, ci = x2d.shape
    co = w2d.shape[1]
    br = _row_block(rows, ci, co)
    y = pl.pallas_call(
        _k_matmul_skip_fwd,
        name="fu_mm_skip_fwd",
        grid=(rows // br,),
        in_specs=[pl.BlockSpec((br, ci), lambda i: (i, 0)),
                  pl.BlockSpec((ci, co), lambda i: (0, 0)),
                  pl.BlockSpec((1, ci), lambda i: (0, 0)),
                  pl.BlockSpec((1, ci), lambda i: (0, 0)),
                  pl.BlockSpec((br, co), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, co), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, co), out_dtype),
        interpret=_interpret())(x2d, w2d, _vec(sc), _vec(sh), skip2d)
    return y


def _c3_fwd(x4d, w4, sc, sh, out_dtype):
    n, h, w, ci = x4d.shape
    co = w4.shape[-1]
    # same calibrated liveness model as the backward kernel (measured
    # ~10.7M/item at h=w=56, ci=co=64)
    per = (6 * h * w * (ci + co) * 4
           + 2 * (h + 2) * (w + 2) * (ci + co) * 2)
    bn_ = _batch_tile(n, per, fixed_bytes=9 * ci * co * 2)
    outs = [jax.ShapeDtypeStruct((n, h, w, co), out_dtype),
            jax.ShapeDtypeStruct((1, co), jnp.float32),
            jax.ShapeDtypeStruct((1, co), jnp.float32)]
    y, s, ss = pl.pallas_call(
        _k_conv3_fwd,
        name="fu_c3_fwd",
        grid=(n // bn_,),
        in_specs=[pl.BlockSpec((bn_, h, w, ci), lambda i: (i, 0, 0, 0)),
                  pl.BlockSpec((3, 3, ci, co), lambda i: (0, 0, 0, 0)),
                  pl.BlockSpec((1, ci), lambda i: (0, 0)),
                  pl.BlockSpec((1, ci), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((bn_, h, w, co), lambda i: (i, 0, 0, 0)),
                   pl.BlockSpec((1, co), lambda i: (0, 0)),
                   pl.BlockSpec((1, co), lambda i: (0, 0))],
        out_shape=outs,
        interpret=_interpret())(x4d, w4, _vec(sc), _vec(sh))
    return y, s[0], ss[0]


def _img_row_block(n, h, w, ci, co, n_temps, fixed_bytes=0):
    """Row tile = whole images; batch-per-tile chosen by the calibrated
    f32-temp liveness model (plus resident weight/wgrad blocks) against
    the 16MB scoped-VMEM budget."""
    per_img = n_temps * h * w * (ci + co) * 4
    for bn in (16, 8, 4, 2, 1):
        if n % bn == 0 and bn * per_img + fixed_bytes <= 11 * 1024 * 1024:
            return bn
    return 1


def _c3_fwd2d(x2d, w4, sc, sh, n, h, w, out_dtype):
    rows, ci = x2d.shape
    co = w4.shape[-1]
    bn_ = _img_row_block(n, h, w, ci, co, 5,
                         fixed_bytes=9 * ci * co * 2)
    br = bn_ * h * w
    kern = functools.partial(_k_conv3_fwd_2d, h=h, w=w)
    outs = [jax.ShapeDtypeStruct((rows, co), out_dtype),
            jax.ShapeDtypeStruct((1, co), jnp.float32),
            jax.ShapeDtypeStruct((1, co), jnp.float32)]
    y, s, ss = pl.pallas_call(
        kern,
        name="fu_c3_fwd2d",
        grid=(n // bn_,),
        in_specs=[pl.BlockSpec((br, ci), lambda i: (i, 0)),
                  pl.BlockSpec((3, 3, ci, co), lambda i: (0, 0, 0, 0)),
                  pl.BlockSpec((1, ci), lambda i: (0, 0)),
                  pl.BlockSpec((1, ci), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((br, co), lambda i: (i, 0)),
                   pl.BlockSpec((1, co), lambda i: (0, 0)),
                   pl.BlockSpec((1, co), lambda i: (0, 0))],
        out_shape=outs,
        interpret=_interpret())(x2d, w4, _vec(sc), _vec(sh))
    return y, s[0], ss[0]


def _c3_bwd2d(dpn2d, y2_2d, fin, y1_2d, w4, sc, sh, xs, xh,
              n, h, w, dp_dtype):
    rows, ci = y1_2d.shape
    co = y2_2d.shape[-1]
    c1, u0, u1 = fin
    wt4 = jnp.transpose(w4, (0, 1, 3, 2))       # (3,3,Co,Ci) for dgrad
    bn_ = _img_row_block(n, h, w, ci, co, 8,
                         fixed_bytes=9 * ci * co * (2 + 4 + 2))
    br = bn_ * h * w
    kern = functools.partial(_k_conv3_bwd_2d, h=h, w=w)
    outs = [jax.ShapeDtypeStruct((rows, ci), dp_dtype),
            jax.ShapeDtypeStruct((3, 3, ci, co), jnp.float32),
            jax.ShapeDtypeStruct((1, ci), jnp.float32),
            jax.ShapeDtypeStruct((1, ci), jnp.float32)]
    dp, dw, db, dg = pl.pallas_call(
        kern,
        name="fu_c3_bwd2d",
        grid=(n // bn_,),
        in_specs=[pl.BlockSpec((br, co), lambda i: (i, 0)),
                  pl.BlockSpec((br, co), lambda i: (i, 0)),
                  pl.BlockSpec((1, co), lambda i: (0, 0)),
                  pl.BlockSpec((1, co), lambda i: (0, 0)),
                  pl.BlockSpec((1, co), lambda i: (0, 0)),
                  pl.BlockSpec((br, ci), lambda i: (i, 0)),
                  pl.BlockSpec((3, 3, co, ci), lambda i: (0, 0, 0, 0)),
                  pl.BlockSpec((1, ci), lambda i: (0, 0)),
                  pl.BlockSpec((1, ci), lambda i: (0, 0)),
                  pl.BlockSpec((1, ci), lambda i: (0, 0)),
                  pl.BlockSpec((1, ci), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((br, ci), lambda i: (i, 0)),
                   pl.BlockSpec((3, 3, ci, co), lambda i: (0, 0, 0, 0)),
                   pl.BlockSpec((1, ci), lambda i: (0, 0)),
                   pl.BlockSpec((1, ci), lambda i: (0, 0))],
        out_shape=outs,
        interpret=_interpret())(
            dpn2d, y2_2d, _vec(c1), _vec(u0), _vec(u1),
            y1_2d, wt4, _vec(sc), _vec(sh), _vec(xs), _vec(xh))
    return dp, dw, db[0], dg[0]


def _mm_bwd(g2d, yraw2d, fin, x2d, wt2d, sc, sh, xs, xh, dp_dtype):
    """Returns dp (R, Ci), dW (Ci, Co) f32, dbeta (Ci,), dgamma (Ci,).
    wt2d is the weight in its native (Co, Ci) layout."""
    rows, ci = x2d.shape
    co = wt2d.shape[0]
    br = _row_block(rows, ci, co, bwd=True)
    deferred = fin is not None
    if fin is None:
        c1 = jnp.ones((co,), jnp.float32)
        u0 = jnp.zeros((co,), jnp.float32)
        u1 = jnp.zeros((co,), jnp.float32)
        yraw2d = g2d                    # unused but must match block shape
    else:
        c1, u0, u1 = fin
    kern = functools.partial(_k_matmul_bwd, deferred=deferred)
    outs = [jax.ShapeDtypeStruct((rows, ci), dp_dtype),
            jax.ShapeDtypeStruct((ci, co), jnp.float32),
            jax.ShapeDtypeStruct((1, ci), jnp.float32),
            jax.ShapeDtypeStruct((1, ci), jnp.float32)]
    dp, dw, db, dg = pl.pallas_call(
        kern,
        name="fu_mm_bwd",
        grid=(rows // br,),
        in_specs=[pl.BlockSpec((br, co), lambda i: (i, 0)),
                  pl.BlockSpec((br, co), lambda i: (i, 0)),
                  pl.BlockSpec((1, co), lambda i: (0, 0)),
                  pl.BlockSpec((1, co), lambda i: (0, 0)),
                  pl.BlockSpec((1, co), lambda i: (0, 0)),
                  pl.BlockSpec((br, ci), lambda i: (i, 0)),
                  pl.BlockSpec((co, ci), lambda i: (0, 0)),
                  pl.BlockSpec((1, ci), lambda i: (0, 0)),
                  pl.BlockSpec((1, ci), lambda i: (0, 0)),
                  pl.BlockSpec((1, ci), lambda i: (0, 0)),
                  pl.BlockSpec((1, ci), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((br, ci), lambda i: (i, 0)),
                   pl.BlockSpec((ci, co), lambda i: (0, 0)),
                   pl.BlockSpec((1, ci), lambda i: (0, 0)),
                   pl.BlockSpec((1, ci), lambda i: (0, 0))],
        out_shape=outs,
        interpret=_interpret())(
            g2d, yraw2d, _vec(c1), _vec(u0), _vec(u1),
            x2d, wt2d, _vec(sc), _vec(sh), _vec(xs), _vec(xh))
    return dp, dw, db[0], dg[0]


def _c3_bwd(dpn4d, y2_4d, fin, y1_4d, w4, sc, sh, xs, xh, dp_dtype):
    n, h, w, ci = y1_4d.shape
    co = y2_4d.shape[-1]
    c1, u0, u1 = fin
    wt4 = jnp.transpose(w4, (0, 1, 3, 2))   # (3,3,Co,Ci) for the dgrad
    # Mosaic keeps ~6 f32 tile-sized temporaries live in this kernel
    # (x, a32, g-finalize, da, dp, xhat) plus two padded bf16 copies;
    # calibrated against a measured 18.4M scoped footprint at bn=16,
    # h=w=16, ci=co=64 (this formula gives 19.7M there)
    per = (6 * h * w * (ci + co) * 4
           + 2 * (h + 2) * (w + 2) * (ci + co) * 2)
    bn_ = _batch_tile(n, per, fixed_bytes=9 * ci * co * (2 + 8))
    outs = [jax.ShapeDtypeStruct((n, h, w, ci), dp_dtype),
            jax.ShapeDtypeStruct((3, 3, ci, co), jnp.float32),
            jax.ShapeDtypeStruct((1, ci), jnp.float32),
            jax.ShapeDtypeStruct((1, ci), jnp.float32)]
    dp, dw, db, dg = pl.pallas_call(
        _k_conv3_bwd,
        name="fu_c3_bwd",
        grid=(n // bn_,),
        in_specs=[pl.BlockSpec((bn_, h, w, co), lambda i: (i, 0, 0, 0)),
                  pl.BlockSpec((bn_, h, w, co), lambda i: (i, 0, 0, 0)),
                  pl.BlockSpec((1, co), lambda i: (0, 0)),
                  pl.BlockSpec((1, co), lambda i: (0, 0)),
                  pl.BlockSpec((1, co), lambda i: (0, 0)),
                  pl.BlockSpec((bn_, h, w, ci), lambda i: (i, 0, 0, 0)),
                  pl.BlockSpec((3, 3, co, ci), lambda i: (0, 0, 0, 0)),
                  pl.BlockSpec((1, ci), lambda i: (0, 0)),
                  pl.BlockSpec((1, ci), lambda i: (0, 0)),
                  pl.BlockSpec((1, ci), lambda i: (0, 0)),
                  pl.BlockSpec((1, ci), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((bn_, h, w, ci), lambda i: (i, 0, 0, 0)),
                   pl.BlockSpec((3, 3, ci, co), lambda i: (0, 0, 0, 0)),
                   pl.BlockSpec((1, ci), lambda i: (0, 0)),
                   pl.BlockSpec((1, ci), lambda i: (0, 0))],
        out_shape=outs,
        interpret=_interpret())(
            dpn4d, y2_4d, _vec(c1), _vec(u0), _vec(u1),
            y1_4d, wt4, _vec(sc), _vec(sh), _vec(xs), _vec(xh))
    return dp, dw, db[0], dg[0]


# ---------------------------------------------------------------------------
# The fused unit: forward/backward orchestration (custom_vjp)
# ---------------------------------------------------------------------------

# Width cutoff for the Pallas 3x3: above this the (3,3,Ci,Co) weight +
# f32 wgrad block alone exceed the scoped-VMEM budget (stage4's 512x512),
# so the middle conv falls back to the XLA segment — the 1x1 sandwich
# kernels still apply around it.
_C3_PALLAS_MAX_WIDTH = 256


def _c3_bwd_fits(h, w, cq):
    """The 3x3 BACKWARD holds ~10 tile-sized temporaries live; measured
    24.1M scoped at bn=1, h=w=56, cq=64 vs an 11.3M naive model — so the
    gate scales the model by the observed 2.2x and requires a bn=1 fit
    with headroom.  Large-spatial stages fall back to the XLA segment."""
    if cq > _C3_PALLAS_MAX_WIDTH:
        return False
    model = 6 * h * w * 2 * cq * 4 + 2 * (h + 2) * (w + 2) * 2 * cq * 2
    return 2.2 * model + 9 * cq * cq * 10 <= 12 * 1024 * 1024


def _c3_mode():
    from .. import config
    mode = config.get("MXNET_FUSED_UNIT_C3").lower()
    if mode not in ("auto", "2d", "4d", "xla"):
        raise MXNetError("MXNET_FUSED_UNIT_C3 must be one of "
                         "auto/2d/4d/xla, got %r" % mode)
    return mode


def _c3_fwd_fits(h, w, cq):
    """4D forward liveness model (same calibration as _c3_bwd_fits,
    fewer live temporaries): must fit at batch-tile 1."""
    model = 4 * h * w * 2 * cq * 4 + 2 * (h + 2) * (w + 2) * 2 * cq * 2
    return 1.5 * model + 9 * cq * cq * 4 <= 14 * 1024 * 1024


def _c3_2d_fits(h, w, cq, bwd):
    """2D-row-layout liveness: n_temps f32 tile copies per image plus the
    resident weights (and the f32 wgrad block in backward)."""
    n_temps = 8 if bwd else 5
    per_img = n_temps * h * w * 2 * cq * 4
    fixed = 9 * cq * cq * ((2 + 4 + 2) if bwd else 2)
    return per_img + fixed <= 11 * 1024 * 1024


def _c3_impl(h, w, cq, bwd):
    """-> '2d' | '4d' | 'xla' for the middle conv, per direction."""
    mode = _c3_mode()
    if mode == "xla":
        return "xla"
    if mode == "4d":
        if cq > _C3_PALLAS_MAX_WIDTH:
            return "xla"
        ok = _c3_bwd_fits(h, w, cq) if bwd else _c3_fwd_fits(h, w, cq)
        return "4d" if ok else "xla"
    # auto / 2d: prefer the row-layout kernels
    if cq <= _C3_PALLAS_MAX_WIDTH and _c3_2d_fits(h, w, cq, bwd):
        return "2d"
    return "xla"


def _c3_fwd_xla(x4d, w4, sc, sh, out_dtype):
    a = jnp.maximum(x4d.astype(jnp.float32) * sc + sh, 0).astype(out_dtype)
    w_ohwi = jnp.transpose(w4, (3, 0, 1, 2))
    y = lax.conv_general_dilated(
        a, w_ohwi, (1, 1), [(1, 1), (1, 1)],
        dimension_numbers=("NHWC", "OHWI", "NHWC"),
        preferred_element_type=out_dtype)
    yf = y.astype(jnp.float32)
    s = jnp.sum(yf, axis=(0, 1, 2))
    ss = jnp.sum(yf * yf, axis=(0, 1, 2))
    return y, s, ss


def _c3_bwd_xla(dpn4d, y2_4d, fin, y1_4d, w4, sc, sh, xs, xh, dp_dtype):
    c1, u0, u1 = fin
    g = (c1 * dpn4d.astype(jnp.float32) + u0
         + u1 * y2_4d.astype(jnp.float32)).astype(dp_dtype)
    a32 = jnp.maximum(y1_4d.astype(jnp.float32) * sc + sh, 0)
    a = a32.astype(dp_dtype)
    w_ohwi = jnp.transpose(w4, (3, 0, 1, 2))

    def conv(a_, w_):
        return lax.conv_general_dilated(
            a_, w_, (1, 1), [(1, 1), (1, 1)],
            dimension_numbers=("NHWC", "OHWI", "NHWC"),
            preferred_element_type=dp_dtype)
    _, vjp = jax.vjp(conv, a, w_ohwi)
    da, dw_ohwi = vjp(g)
    dp = da.astype(jnp.float32) * (a32 > 0)
    db = jnp.sum(dp, axis=(0, 1, 2))
    xhat = y1_4d.astype(jnp.float32) * xs + xh
    dg = jnp.sum(dp * xhat, axis=(0, 1, 2))
    dw = jnp.transpose(dw_ohwi.astype(jnp.float32), (1, 2, 3, 0))
    return dp.astype(dp_dtype), dw, db, dg


def _bn_vectors(mu, var, gamma, beta, eps):
    inv = lax.rsqrt(var + eps)
    sc = gamma * inv
    sh = beta - mu * sc
    xs = inv
    xh = -mu * inv
    return sc, sh, xs, xh, inv


def _finalize_vectors(gamma, inv, mu, dbeta, dgamma, m):
    c1 = gamma * inv
    u0 = -c1 * (dbeta + dgamma * (-mu * inv)) / m
    u1 = -c1 * dgamma * inv / m
    return c1, u0, u1


def _stats_from_sums(s, ss, m):
    mu = s / m
    var = jnp.maximum(ss / m - mu * mu, 0.0)
    return mu, var


def _w2d(w):
    """(Co, 1, 1, Ci) OHWI -> (Ci, Co)."""
    co = w.shape[0]
    return w.reshape(co, -1).T


def _w4(w):
    """(Co, 3, 3, Ci) OHWI -> (3, 3, Ci, Co)."""
    return jnp.transpose(w, (1, 2, 3, 0))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fused_unit_core(cfg, data, g1, b1, w1, g2, b2, w2, g3, b3, w3,
                     mu0, var0):
    """cfg = (eps, n, h, w); data may be 4D NHWC or 2D (n*h*w, c) — the
    chain runs 2D internally either way.  Returns (out, mu1, var1, mu2,
    var2): the interior batch stats are REAL outputs (consumed,
    stop-gradiented, by the moving-average updates) so the forward runs
    exactly once — no reliance on XLA CSE-ing duplicated pallas
    custom-calls."""
    out, _, _, st1, st2 = _fused_unit_fwd_impl(
        cfg, data, g1, b1, w1, g2, b2, w2, g3, b3, w3, mu0, var0)
    return (out,) + st1 + st2


def _fused_unit_fwd_impl(cfg, data, g1, b1, w1, g2, b2, w2, g3, b3, w3,
                         mu0, var0, fixed_stats=None):
    """The conv1 -> conv2 -> conv3+skip kernel chain.  Training mode
    (fixed_stats None) finalizes each interior BN's batch stats from the
    previous kernel's epilogue; eval passes the moving stats as
    fixed_stats=(mu1, var1, mu2, var2) and skips the epilogues — ONE
    chain serves both modes so they cannot drift.  Output shape follows
    the input (2D in -> 2D out: consecutive fused units chain without
    relayout copies at their boundaries)."""
    training = fixed_stats is None
    eps, n, h, w_ = cfg
    c = data.shape[-1]
    rows = n * h * w_
    x2d = data.reshape(rows, c)
    sc1, sh1, _, _, _ = _bn_vectors(mu0, var0, g1, b1, eps)
    y1_2d, s1, ss1 = _mm_fwd(x2d, _w2d(w1), sc1, sh1, training,
                             data.dtype)
    cq = w1.shape[0]
    mu1, var1 = _stats_from_sums(s1, ss1, rows) if training \
        else (fixed_stats[0], fixed_stats[1])
    sc2, sh2, _, _, _ = _bn_vectors(mu1, var1, g2, b2, eps)
    c3m = _c3_impl(h, w_, cq, bwd=False)
    if c3m == "2d":
        y2_2d, s2, ss2 = _c3_fwd2d(y1_2d, _w4(w2), sc2, sh2, n, h, w_,
                                   data.dtype)
    else:
        c3_fwd = _c3_fwd if c3m == "4d" else _c3_fwd_xla
        y2, s2, ss2 = c3_fwd(y1_2d.reshape(n, h, w_, cq), _w4(w2),
                             sc2, sh2, data.dtype)
        y2_2d = y2.reshape(rows, cq)
    mu2, var2 = _stats_from_sums(s2, ss2, rows) if training \
        else (fixed_stats[2], fixed_stats[3])
    sc3, sh3, _, _, _ = _bn_vectors(mu2, var2, g3, b3, eps)
    out2d = _mm_skip_fwd(y2_2d, _w2d(w3), sc3, sh3, x2d, data.dtype)
    return (out2d.reshape(data.shape), y1_2d, y2_2d,
            (mu1, var1), (mu2, var2))


def _fused_unit_fwd_vjp(cfg, data, g1, b1, w1, g2, b2, w2, g3, b3, w3,
                        mu0, var0):
    out, y1, y2, st1, st2 = _fused_unit_fwd_impl(
        cfg, data, g1, b1, w1, g2, b2, w2, g3, b3, w3, mu0, var0)
    res = (data, y1, y2, st1, st2, g1, b1, w1, g2, b2, w2, g3, b3, w3,
           mu0, var0)
    return (out,) + st1 + st2, res


def _fused_unit_bwd(cfg, res, cots):
    g_out = cots[0]   # stats outputs feed stop_gradient'd aux updates only
    (data, y1, y2, (mu1, var1), (mu2, var2),
     g1, b1, w1, g2, b2, w2, g3, b3, w3, mu0, var0) = res
    eps, n, h, w_ = cfg
    c = data.shape[-1]
    rows = n * h * w_
    cq = w1.shape[0]
    x2d = data.reshape(rows, c)
    g2d = g_out.reshape(rows, c)

    sc1, sh1, xs0, xh0, inv0 = _bn_vectors(mu0, var0, g1, b1, eps)
    sc2, sh2, xs1, xh1, inv1 = _bn_vectors(mu1, var1, g2, b2, eps)
    sc3, sh3, xs2, xh2, inv2 = _bn_vectors(mu2, var2, g3, b3, eps)

    # conv3 backward: cotangent at `out` is final (the +skip add passes
    # g_out through to d(data) unchanged, added at the end)
    dp3, dw3, db3, dg3 = _mm_bwd(
        g2d, None, None, y2,
        w3.reshape(w3.shape[0], -1), sc3, sh3, xs2, xh2, data.dtype)
    # conv2 backward: finalize bn3's backward in the prologue
    fin3 = _finalize_vectors(g3, inv2, mu2, db3, dg3, rows)
    c3m = _c3_impl(h, w_, cq, bwd=True)
    if c3m == "2d":
        dp2, dw2, db2, dg2 = _c3_bwd2d(
            dp3, y2, fin3, y1, _w4(w2), sc2, sh2, xs1, xh1,
            n, h, w_, data.dtype)
        dp2_2d = dp2
    else:
        c3_bwd = _c3_bwd if c3m == "4d" else _c3_bwd_xla
        dp2, dw2, db2, dg2 = c3_bwd(
            dp3.reshape(n, h, w_, cq), y2.reshape(n, h, w_, cq), fin3,
            y1.reshape(n, h, w_, cq), _w4(w2), sc2, sh2,
            xs1, xh1, data.dtype)
        dp2_2d = dp2.reshape(rows, cq)
    # conv1 backward: finalize bn2's backward in the prologue
    fin2 = _finalize_vectors(g2, inv1, mu1, db2, dg2, rows)
    dp1, dw1, db1, dg1 = _mm_bwd(
        dp2_2d, y1, fin2, x2d,
        w1.reshape(w1.shape[0], -1), sc1, sh1, xs0, xh0, data.dtype)
    # close: bn1's backward finalize + the skip path (one XLA fusion)
    c1v, u0v, u1v = _finalize_vectors(g1, inv0, mu0, db1, dg1, rows)
    g_data = (c1v * dp1.astype(jnp.float32) + u0v
              + u1v * x2d.astype(jnp.float32)
              + g2d.astype(jnp.float32)).astype(data.dtype)

    def wback(dw, wref):
        if wref.ndim == 4 and wref.shape[1] == 3:        # (Co,3,3,Ci)
            return jnp.transpose(dw, (3, 0, 1, 2)).astype(wref.dtype)
        return dw.T.reshape(wref.shape).astype(wref.dtype)

    zeros_like_stats = jnp.zeros_like(mu0)
    return (g_data.reshape(data.shape),
            dg1.astype(g1.dtype), db1.astype(b1.dtype), wback(dw1, w1),
            dg2.astype(g2.dtype), db2.astype(b2.dtype), wback(dw2, w2),
            dg3.astype(g3.dtype), db3.astype(b3.dtype), wback(dw3, w3),
            zeros_like_stats, zeros_like_stats)


_fused_unit_core.defvjp(_fused_unit_fwd_vjp, _fused_unit_bwd)


# ---------------------------------------------------------------------------
# Registry op
# ---------------------------------------------------------------------------

def _fbu_fill(attrs, in_shapes):
    out = list(in_shapes)
    dshape = out[0]
    if dshape is None:
        return out
    c = dshape[-1]
    cq = attrs["num_filter"] // 4
    want = [None, (c,), (c,), (cq, 1, 1, c),          # bn1 on data, conv1
            (cq,), (cq,), (cq, 3, 3, cq),             # bn2 on y1, conv2
            (cq,), (cq,), (c, 1, 1, cq),              # bn3 on y2, conv3
            (c,), (c,), (cq,), (cq,), (cq,), (cq,)]   # moving stats
    for i in range(1, len(out)):
        if out[i] is None and i < len(want):
            out[i] = want[i]
    return out


@register("_contrib_FusedBottleneckUnit",
          nin=16,
          input_names=["data", "gamma1", "beta1", "weight1",
                       "gamma2", "beta2", "weight2",
                       "gamma3", "beta3", "weight3",
                       "moving_mean1", "moving_var1",
                       "moving_mean2", "moving_var2",
                       "moving_mean3", "moving_var3"],
          aux_inputs=(10, 11, 12, 13, 14, 15), nout=1,
          mutate_aux={10: 1, 11: 2, 12: 3, 13: 4, 14: 5, 15: 6},
          mode_dependent=True, fill_shapes=_fbu_fill,
          params={"num_filter": P(int), "eps": P(float, EPS_DEFAULT),
                  "momentum": P(float, 0.9),
                  "height": P(int, 0), "width": P(int, 0),
                  "layout": P("str_or_none", None)})
def fused_bottleneck_unit(attrs, data, g1, b1, w1, g2, b2, w2, g3, b3, w3,
                          mm1, mv1, mm2, mv2, mm3, mv3):
    """A stride-1 dim-match pre-activation bottleneck unit
    (bn-relu-conv1x1, bn-relu-conv3x3, bn-relu-conv1x1, +skip) as the
    fused Pallas kernel chain.  Parameter set matches the unfused
    subgraph (models/resnet.py _residual_unit) so checkpoints load
    either way.  NHWC only."""
    if data.ndim == 4:
        n, h, w_, c = data.shape
    elif data.ndim == 2:
        # 2D chain form: consecutive fused units pass (n*h*w, c) rows so
        # no 4D<->2D relayout copy exists at their boundary; the builder
        # provides the spatial dims as attrs
        h, w_ = attrs["height"], attrs["width"]
        if not (h and w_):
            raise MXNetError("_contrib_FusedBottleneckUnit with 2D data "
                             "needs height/width attrs")
        c = data.shape[-1]
        if data.shape[0] % (h * w_):
            raise MXNetError(
                "_contrib_FusedBottleneckUnit 2D data: %d rows is not a "
                "multiple of height*width = %d*%d" % (data.shape[0], h, w_))
        n = data.shape[0] // (h * w_)
    else:
        raise MXNetError("_contrib_FusedBottleneckUnit expects NHWC 4D "
                         "or (rows, C) 2D data")
    eps = attrs["eps"]
    mom = attrs["momentum"]
    training = attrs.get("_training", False)
    cfg = (eps, n, h, w_)
    if training:
        xf = data.astype(jnp.float32).reshape(-1, c)
        mu0 = jnp.mean(xf, axis=0)
        var0 = jnp.var(xf, axis=0)
        out, mu1, var1, mu2, var2 = _fused_unit_core(
            cfg, data, g1, b1, w1, g2, b2, w2, g3, b3, w3,
            lax.stop_gradient(mu0), lax.stop_gradient(var0))
        sg = lax.stop_gradient
        upd = lambda old, new: mom * old + (1 - mom) * sg(new)  # noqa: E731
        return (out, upd(mm1, mu0), upd(mv1, var0),
                upd(mm2, mu1), upd(mv2, var1),
                upd(mm3, mu2), upd(mv3, var2))
    # eval: moving statistics through the SAME chain, forward only
    f32 = jnp.float32
    out, _, _, _, _ = _fused_unit_fwd_impl(
        cfg, data, g1, b1, w1, g2, b2, w2, g3, b3, w3,
        mm1.astype(f32), mv1.astype(f32),
        fixed_stats=(mm2.astype(f32), mv2.astype(f32),
                     mm3.astype(f32), mv3.astype(f32)))
    return (out, mm1, mv1, mm2, mv2, mm3, mv3)

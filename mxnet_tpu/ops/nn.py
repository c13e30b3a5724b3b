"""Neural-network layer ops.

Reference: src/operator/nn/ (fully_connected-inl.h:69, convolution-inl.h,
pooling-inl.h, batch_norm.cc:408, softmax, dropout), src/operator/
{softmax_output,regression_output,make_loss,l2_normalization,instance_norm,
lrn,crop,sequence_*}-inl.h, tensor/indexing_op.cc:145 (Embedding).

TPU-native notes:
- Convolutions lower to ``lax.conv_general_dilated`` → MXU.  The user-facing
  layout stays the reference's NCHW; XLA's layout assignment re-tiles for the
  hardware, so no manual NHWC plumbing is needed.
- BatchNorm / Dropout side effects (moving stats, masks) are functional:
  extra outputs wired back by the caller (``mutate_aux``), PRNG keys are
  explicit leading operands.
- Loss heads (SoftmaxOutput, *RegressionOutput, MakeLoss) use jax.custom_vjp
  to reproduce the reference semantics where ``backward()`` needs no head
  gradient (the op defines its own dL/dx, ignoring incoming cotangents —
  matching OperatorProperty backward that never sees out_grad).
"""
import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from jax.ad_checkpoint import checkpoint_name

from .registry import register, P
from ..base import MXNetError
from .. import config

# Activation-policy names: big layer outputs are tagged so a remat policy
# (jax.checkpoint with save_only_these_names; exercised by
# `perf/step_bench.py --remat names`) can store ONLY convolution outputs +
# BN statistics and recompute the BatchNorm-normalize/ReLU elementwise
# chains in backward.  On v5e ResNet-50 (earlier chip record, since
# deleted) the policy LOST (108.6 vs 94.7 ms/step) — the recompute chains
# do not fuse into single reads — so nothing in the library applies it by
# default; the tags stay because checkpoint_name is an identity outside
# jax.checkpoint contexts and they make the experiment reproducible.
CKPT_CONV = "conv_out"
CKPT_STATS = "bn_stats"
CKPT_POOL = "pool_out"
CKPT_FC = "fc_out"


# ---------------------------------------------------------------------------
# FullyConnected
# ---------------------------------------------------------------------------

def _fc_fill(attrs, in_shapes):
    data, w, b = (in_shapes + [None] * 3)[:3]
    out = list(in_shapes)
    if data is not None:
        nh = attrs["num_hidden"]
        in_dim = int(np.prod(data[1:])) if attrs.get("flatten", True) else data[-1]
        if len(out) > 1 and out[1] is None:
            out[1] = (nh, in_dim)
        if len(out) > 2 and out[2] is None:
            out[2] = (nh,)
    return out


@register("FullyConnected", aliases=["fully_connected"],
          nin=lambda attrs: 2 if (attrs or {}).get("no_bias") else 3,
          input_names=["data", "weight", "bias"],
          fill_shapes=_fc_fill,
          params={"num_hidden": P(int), "no_bias": P(bool, False),
                  "flatten": P(bool, True)})
def fully_connected(attrs, data, weight, bias=None):
    if attrs["flatten"]:
        x = data.reshape((data.shape[0], -1))
    else:
        x = data
    out = jnp.dot(x, weight.T, preferred_element_type=x.dtype)
    if bias is not None and not attrs["no_bias"]:
        out = out + bias
    return checkpoint_name(out, CKPT_FC)


# ---------------------------------------------------------------------------
# Convolution / Deconvolution
# ---------------------------------------------------------------------------

def _channels_last(attrs):
    lay = attrs.get("layout") or ""
    return lay.endswith("C")


def _conv_fill(attrs, in_shapes):
    out = list(in_shapes)
    data = out[0]
    if data is not None:
        k = attrs["kernel"]
        nf = attrs["num_filter"]
        ng = attrs.get("num_group", 1)
        if _channels_last(attrs):
            cin = data[-1]
            wshape = (nf,) + tuple(k) + (cin // ng,)
        else:
            cin = data[1]
            wshape = (nf, cin // ng) + tuple(k)
        if len(out) > 1 and out[1] is None:
            out[1] = wshape
        if len(out) > 2 and out[2] is None:
            out[2] = (nf,)
    return out


def _deconv_fill(attrs, in_shapes):
    out = list(in_shapes)
    data = out[0]
    if data is not None:
        k = attrs["kernel"]
        nf = attrs["num_filter"]
        ng = attrs.get("num_group", 1)
        if _channels_last(attrs):
            cin = data[-1]
            wshape = (cin,) + tuple(k) + (nf // ng,)
        else:
            cin = data[1]
            wshape = (cin, nf // ng) + tuple(k)
        if len(out) > 1 and out[1] is None:
            out[1] = wshape
        if len(out) > 2 and out[2] is None:
            out[2] = (nf,)
    return out


# --- 1x1 convolution as an explicit MXU matmul -----------------------------
#
# XLA's conv codegen runs ResNet's 1x1 convs (and especially their wgrad
# transposes at 7x7/14x14 spatial) far below MXU peak.
# A 1x1 stride-1 conv IS a matmul over the flattened batch*spatial dim, and
# the strided variants are a subsample (fwd/wgrad) or interior-dilate (dgrad)
# away, so route them through lax.dot_general with a custom VJP whose dgrad
# and wgrad are also plain dots.  Channels-last only (the TPU layout).

def _conv1x1_subsample(x, stride):
    if any(s > 1 for s in stride):
        idx = ((slice(None),)
               + tuple(slice(None, None, s) for s in stride)
               + (slice(None),))
        return x[idx]
    return x


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _conv1x1_cl(x, w, stride, in_spatial):
    xs = _conv1x1_subsample(x, stride)
    co, ci = w.shape[0], w.shape[-1]
    lead = xs.shape[:-1]
    y = lax.dot_general(xs.reshape((-1, ci)), w.reshape((co, ci)),
                        (((1,), (1,)), ((), ())),
                        preferred_element_type=xs.dtype)
    return y.reshape(lead + (co,))


def _conv1x1_cl_fwd(x, w, stride, in_spatial):
    xs = _conv1x1_subsample(x, stride)
    return _conv1x1_cl(x, w, stride, in_spatial), (xs, w)


def _conv1x1_cl_bwd(stride, in_spatial, res, dy):
    xs, w = res
    co, ci = w.shape[0], w.shape[-1]
    lead = dy.shape[:-1]
    dy2 = dy.reshape((-1, co))
    # wgrad: contract over every batch*spatial element — one MXU matmul
    dw = lax.dot_general(dy2, xs.reshape((-1, ci)),
                         (((0,), (0,)), ((), ())),
                         preferred_element_type=dy.dtype)
    dw = dw.reshape(w.shape)
    dxs = lax.dot_general(dy2, w.reshape((co, ci)),
                          (((1,), (0,)), ((), ())),
                          preferred_element_type=dy.dtype)
    dxs = dxs.reshape(lead + (ci,))
    if any(s > 1 for s in stride):
        # scatter back onto the strided input grid: interior + trailing pad
        cfg = [(0, 0, 0)]
        for s, isp, osp in zip(stride, in_spatial, dy.shape[1:-1]):
            cfg.append((0, isp - ((osp - 1) * s + 1), s - 1))
        cfg.append((0, 0, 0))
        dxs = lax.pad(dxs, jnp.zeros((), dxs.dtype), cfg)
    return dxs, dw


_conv1x1_cl.defvjp(_conv1x1_cl_fwd, _conv1x1_cl_bwd)


def _conv1x1_eligible(attrs, k, pad):
    # no dilate check: dilating a 1x1 kernel is an identity
    return (config.get("MXNET_CONV_DOT_1X1") and _channels_last(attrs)
            and all(ki == 1 for ki in k)
            and attrs["num_group"] == 1
            and all(p == (0, 0) for p in pad))


# --- Pallas fused 1x1-conv backward: dgrad + wgrad in ONE pass over dy ----
#
# XLA lowers a 1x1 conv's backward to two separate fusions — dgrad reads
# (dy, W) and wgrad reads (dy, x) — so dy crosses HBM twice.  On a
# bandwidth-bound step that second read is pure waste: a
# Pallas kernel tiles over the fused batch*spatial rows, computes the dx
# tile (dy @ W) AND accumulates the dW partial (dy^T @ x, f32) from the
# same resident dy tile.  Gated by MXNET_CONV1X1_FUSED_BWD.

_PALLAS_ROW_BLOCK = 256


def _fused1x1_bwd_pallas(x2d, dy2d, w2d):
    """x2d (R, Ci), dy2d (R, Co), w2d (Co, Ci) -> dx (R, Ci), dW f32."""
    import jax.experimental.pallas as pl
    R, ci = x2d.shape
    co = dy2d.shape[1]
    br = next(b for b in (2048, 1024, 512, 256) if R % b == 0)

    def kernel(x_ref, dy_ref, w_ref, dx_ref, dw_ref):
        i = pl.program_id(0)
        dy = dy_ref[...]
        dx_ref[...] = jnp.dot(dy, w_ref[...],
                              preferred_element_type=jnp.float32
                              ).astype(dx_ref.dtype)
        part = lax.dot_general(dy, x_ref[...], (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)

        @pl.when(i == 0)
        def _init():
            dw_ref[...] = part

        @pl.when(i > 0)
        def _acc():
            dw_ref[...] += part

    interpret = jax.devices()[0].platform != "tpu"
    dx, dw = pl.pallas_call(
        kernel,
        grid=(R // br,),
        in_specs=[pl.BlockSpec((br, ci), lambda i: (i, 0)),
                  pl.BlockSpec((br, co), lambda i: (i, 0)),
                  pl.BlockSpec((co, ci), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((br, ci), lambda i: (i, 0)),
                   pl.BlockSpec((co, ci), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((R, ci), x2d.dtype),
                   jax.ShapeDtypeStruct((co, ci), jnp.float32)],
        interpret=interpret)(x2d, dy2d, w2d)
    return dx, dw


@jax.custom_vjp
def _conv1x1_fused_bwd(x, w):
    # forward stays XLA's native conv (it was fine); only backward fuses
    return lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding=[(0, 0), (0, 0)],
        dimension_numbers=("NHWC", "OHWI", "NHWC"),
        preferred_element_type=x.dtype)


def _conv1x1_fused_fwd_rule(x, w):
    return _conv1x1_fused_bwd(x, w), (x, w)


def _conv1x1_fused_bwd_rule(res, dy):
    x, w = res
    n, h, wd, ci = x.shape
    co = w.shape[0]
    dx2d, dw = _fused1x1_bwd_pallas(x.reshape(-1, ci), dy.reshape(-1, co),
                                    w.reshape(co, ci))
    return dx2d.reshape(x.shape), dw.reshape(w.shape).astype(w.dtype)


_conv1x1_fused_bwd.defvjp(_conv1x1_fused_fwd_rule, _conv1x1_fused_bwd_rule)


def _conv1x1_fused_eligible(attrs, k, stride, pad, data):
    return (config.get("MXNET_CONV1X1_FUSED_BWD") and _channels_last(attrs)
            and data.ndim == 4
            and all(ki == 1 for ki in k)
            and all(s == 1 for s in stride)
            and attrs["num_group"] == 1
            and all(p == (0, 0) for p in pad)
            # small-spatial deep layers only: where XLA's per-fusion dy
            # re-read hurts most and the tile grid stays short
            and data.shape[1] * data.shape[2] <= 256
            and (data.shape[0] * data.shape[1] * data.shape[2])
            % _PALLAS_ROW_BLOCK == 0)


_CONV_PARAMS = {
    "kernel": P("shape"), "stride": P("shape", ()), "dilate": P("shape", ()),
    "pad": P("shape", ()), "num_filter": P(int), "num_group": P(int, 1),
    "workspace": P(int, 1024), "no_bias": P(bool, False),
    "cudnn_tune": P("str_or_none", None), "cudnn_off": P(bool, False),
    "layout": P("str_or_none", None),
}


def _conv_dims(attrs, ndim):
    nd = ndim - 2
    k = tuple(attrs["kernel"])
    stride = tuple(attrs["stride"]) or (1,) * nd
    dilate = tuple(attrs["dilate"]) or (1,) * nd
    pad = tuple(attrs["pad"]) or (0,) * nd
    return k, stride, dilate, [(p, p) for p in pad]


@register("Convolution", aliases=["convolution", "Convolution_v1",
                                  "convolution_v1"],
          nin=lambda attrs: 2 if (attrs or {}).get("no_bias") else 3,
          input_names=["data", "weight", "bias"], fill_shapes=_conv_fill,
          params=_CONV_PARAMS)
def convolution(attrs, data, weight, bias=None):
    k, stride, dilate, pad = _conv_dims(attrs, data.ndim)
    nd = data.ndim - 2
    sp = "DHW"[3 - nd:]
    if _conv1x1_fused_eligible(attrs, k, stride, pad, data):
        out = _conv1x1_fused_bwd(data, weight)
        if bias is not None and not attrs["no_bias"]:
            out = out + bias.reshape((1,) * (data.ndim - 1) + (-1,))
        return checkpoint_name(out, CKPT_CONV)
    if _conv1x1_eligible(attrs, k, pad):
        out = _conv1x1_cl(data, weight, stride, tuple(data.shape[1:-1]))
        if bias is not None and not attrs["no_bias"]:
            out = out + bias.reshape((1,) * (data.ndim - 1) + (-1,))
        return checkpoint_name(out, CKPT_CONV)
    if _channels_last(attrs):
        # channels-last (layout=NWC/NHWC/NDHWC): the TPU-preferred layout —
        # XLA tiles the trailing C dim straight onto the MXU lanes with no
        # relayout pass. Weights follow the reference's channels-last
        # convention (num_filter, *kernel, C/num_group).
        spec = "N" + sp + "C"
        wspec = "O" + sp + "I"
    else:
        # logical NCHW / NCDHW; lax dimension_numbers spell it explicitly
        spec = "NC" + sp
        wspec = "OI" + sp
    out = lax.conv_general_dilated(
        data, weight, window_strides=stride, padding=pad,
        rhs_dilation=dilate, feature_group_count=attrs["num_group"],
        dimension_numbers=(spec, wspec, spec),
        preferred_element_type=data.dtype)
    if bias is not None and not attrs["no_bias"]:
        bshape = (1,) * (data.ndim - 1) + (-1,) if _channels_last(attrs) \
            else (1, -1) + (1,) * nd
        out = out + bias.reshape(bshape)
    return checkpoint_name(out, CKPT_CONV)


@register("Deconvolution", aliases=["deconvolution"],
          nin=lambda attrs: 2 if (attrs or {}).get("no_bias", True) else 3,
          input_names=["data", "weight", "bias"], fill_shapes=_deconv_fill,
          params={**_CONV_PARAMS, "adj": P("shape", ()),
                  "target_shape": P("shape", ()), "no_bias": P(bool, True)})
def deconvolution(attrs, data, weight, bias=None):
    k, stride, dilate, pad = _conv_dims(attrs, data.ndim)
    nd = data.ndim - 2
    sp = "DHW"[3 - nd:]
    if _channels_last(attrs):
        # channels-last mirrors convolution's layout support: data N..C,
        # weight (C, *kernel, num_filter/num_group).
        spec = "N" + sp + "C"
        wspec = "I" + sp + "O"
    else:
        spec = "NC" + sp
        wspec = "IO" + sp
    # transposed conv = lhs-dilated conv (gradient of Convolution)
    pads = []
    for i in range(nd):
        eff_k = (k[i] - 1) * dilate[i] + 1
        p = pad[i][0]
        adj = attrs["adj"][i] if attrs["adj"] else 0
        pads.append((eff_k - 1 - p, eff_k - 1 - p + adj))
    out = lax.conv_general_dilated(
        data, weight, window_strides=(1,) * nd, padding=pads,
        lhs_dilation=stride, rhs_dilation=dilate,
        feature_group_count=attrs["num_group"],
        dimension_numbers=(spec, wspec, spec),
        preferred_element_type=data.dtype)
    if bias is not None and not attrs["no_bias"]:
        bshape = (1,) * (data.ndim - 1) + (-1,) if _channels_last(attrs) \
            else (1, -1) + (1,) * nd
        out = out + bias.reshape(bshape)
    return out


# ---------------------------------------------------------------------------
# Pooling
# ---------------------------------------------------------------------------

@register("Pooling", aliases=["pooling", "Pooling_v1", "pooling_v1"],
          params={"kernel": P("shape", ()), "stride": P("shape", ()),
                  "pad": P("shape", ()),
                  "pool_type": P(str, "max", choices=["max", "avg", "sum"]),
                  "global_pool": P(bool, False),
                  "pooling_convention": P(str, "valid", choices=["valid", "full"]),
                  "layout": P("str_or_none", None),
                  "cudnn_off": P(bool, False)})
def pooling(attrs, data):
    nd = data.ndim - 2
    cl = _channels_last(attrs)
    spatial = tuple(range(1, data.ndim - 1)) if cl \
        else tuple(range(2, data.ndim))
    if attrs["global_pool"]:
        if attrs["pool_type"] == "max":
            return checkpoint_name(
                jnp.max(data, axis=spatial, keepdims=True), CKPT_POOL)
        if attrs["pool_type"] == "sum":
            return checkpoint_name(
                jnp.sum(data, axis=spatial, keepdims=True), CKPT_POOL)
        return checkpoint_name(
            jnp.mean(data, axis=spatial, keepdims=True), CKPT_POOL)
    k = tuple(attrs["kernel"])
    stride = tuple(attrs["stride"]) or (1,) * nd
    pad = tuple(attrs["pad"]) or (0,) * nd
    spatial_pads = []
    for i in range(nd):
        lo = hi = pad[i]
        if attrs["pooling_convention"] == "full":
            # ceil mode: add extra high padding so the last partial window counts
            size = data.shape[spatial[i]] + 2 * pad[i]
            rem = (size - k[i]) % stride[i]
            if rem != 0:
                hi += stride[i] - rem
        spatial_pads.append((lo, hi))
    if cl:
        window = (1,) + k + (1,)
        strides = (1,) + stride + (1,)
        pads = [(0, 0)] + spatial_pads + [(0, 0)]
    else:
        window = (1, 1) + k
        strides = (1, 1) + stride
        pads = [(0, 0), (0, 0)] + spatial_pads
    pt = attrs["pool_type"]
    # init values must be CONCRETE scalars: a traced init breaks
    # reduce_window's autodiff on the TPU backend
    if pt == "max":
        init = -np.inf if jnp.issubdtype(data.dtype, jnp.floating) \
            else np.iinfo(np.dtype(data.dtype)).min
        return checkpoint_name(
            lax.reduce_window(data, np.array(init, data.dtype), lax.max,
                              window, strides, pads), CKPT_POOL)
    summed = lax.reduce_window(data, np.array(0, data.dtype), lax.add,
                               window, strides, pads)
    if pt == "sum":
        return checkpoint_name(summed, CKPT_POOL)
    # avg: divide by window size counting padding (MXNet counts full window)
    return checkpoint_name(summed / float(np.prod(k)), CKPT_POOL)


# ---------------------------------------------------------------------------
# BatchNorm — functional with moving-stat writeback
# ---------------------------------------------------------------------------

_BN_PARAMS = {"eps": P(float, 1e-3), "momentum": P(float, 0.9),
              "fix_gamma": P(bool, True), "use_global_stats": P(bool, False),
              "output_mean_var": P(bool, False), "axis": P(int, 1),
              "cudnn_off": P(bool, False)}


def _bn_fill(attrs, in_shapes):
    out = list(in_shapes)
    data = out[0]
    if data is not None:
        c = data[attrs.get("axis", 1) % len(data)]
        for i in range(1, 5):
            if len(out) > i and out[i] is None:
                out[i] = (c,)
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _bn_train_core(eps, red, bshape, x, gamma, beta):
    return _bn_train_fwd(eps, red, bshape, x, gamma, beta)[0][0]


def _bn_batch_stats(x, red):
    """f32 batch mean/variance — the one implementation every BN-family op
    shares.  Stats in f32 regardless of activation dtype: bf16 accumulation
    over batch*spatial elements is numerically unusable; the converts fuse
    into the reduction loop (no extra HBM pass).  E[x] and E[x^2] come from
    ONE fused multi-output reduction (one activation read).  The clamp:
    E[x^2]-E[x]^2 can go slightly negative from f32 cancellation on
    large-mean inputs, which would NaN the rsqrt."""
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=red)
    var = jnp.maximum(
        jnp.mean(jnp.square(xf), axis=red) - jnp.square(mean), 0.0)
    return mean, var


def _bn_train_fwd(eps, red, bshape, x, gamma, beta):
    xf = x.astype(jnp.float32)
    mean, var = _bn_batch_stats(x, red)
    mean = checkpoint_name(mean, CKPT_STATS)
    var = checkpoint_name(var, CKPT_STATS)
    inv = checkpoint_name(lax.rsqrt(var + eps), CKPT_STATS)
    scale = gamma * inv
    shift = beta - mean * scale
    out = (xf * scale.reshape(bshape) + shift.reshape(bshape)) \
        .astype(x.dtype)
    return (out, mean, var), (x, gamma, mean, inv)


def _bn_train_bwd(eps, red, bshape, res, cts):
    """Hand-written minimal-pass BN backward (batch_norm.cc backward math).

    Autodiff of the var = E[x^2]-E[x]^2 formulation issues ~2x the HBM
    passes this does; at ResNet-50 batch-256 scale BatchNorm reductions
    are ~40% of step time (profiled), so the backward is written directly:
    one fused pass for the two sums, one for dx.
    """
    dy = cts[0] if isinstance(cts, (tuple, list)) else cts
    x, gamma, mean, inv = res
    dyf = dy.astype(jnp.float32)
    xf = x.astype(jnp.float32)
    n = 1.0
    for i in red:
        n *= x.shape[i]
    sdy = jnp.sum(dyf, axis=red)
    sdyx = jnp.sum(dyf * xf, axis=red)
    dgamma = (sdyx - mean * sdy) * inv          # = sum(dy * xhat)
    dbeta = sdy
    c = (gamma * inv).reshape(bshape)
    xhat = (xf - mean.reshape(bshape)) * inv.reshape(bshape)
    dx = (c * (dyf - (sdy / n).reshape(bshape)
               - xhat * (dgamma / n).reshape(bshape))).astype(x.dtype)
    return dx, dgamma, dbeta


def _bn_train_fwd_vjp(eps, red, bshape, x, gamma, beta):
    (out, _, _), res = _bn_train_fwd(eps, red, bshape, x, gamma, beta)
    return out, res


_bn_train_core.defvjp(_bn_train_fwd_vjp, _bn_train_bwd)


def _batch_norm_impl(attrs, data, gamma, beta, mov_mean, mov_var):
    ax = attrs["axis"] % data.ndim
    red = tuple(i for i in range(data.ndim) if i != ax)
    bshape = tuple(data.shape[ax] if i == ax else 1 for i in range(data.ndim))
    training = attrs.get("_training", False) and not attrs["use_global_stats"]
    if attrs["fix_gamma"]:
        gamma = jnp.ones_like(gamma)
    gamma32 = gamma.astype(jnp.float32)
    beta32 = beta.astype(jnp.float32)
    if training:
        out = _bn_train_core(attrs["eps"], red, bshape, data, gamma32,
                             beta32)
        # stats for moving-average writeback and output_mean_var; XLA CSEs
        # this reduction with the one inside _bn_train_core (same operand)
        mean, var = _bn_batch_stats(data, red)
        mean = lax.stop_gradient(mean)
        var = lax.stop_gradient(var)
        m = attrs["momentum"]
        new_mean = m * mov_mean + (1 - m) * mean
        new_var = m * mov_var + (1 - m) * var
        return out, mean, var, new_mean, new_var
    mean = mov_mean.astype(jnp.float32)
    var = mov_var.astype(jnp.float32)
    inv = lax.rsqrt(var + attrs["eps"])
    scale = gamma32 * inv
    shift = beta32 - mean * scale
    out = (data.astype(jnp.float32) * scale.reshape(bshape)
           + shift.reshape(bshape)).astype(data.dtype)
    return out, mean, var, mov_mean, mov_var


# Output-tuple convention (see OpDef): impl returns nout graph outputs first,
# then one extra entry per mutate_aux target with index >= nout.  BatchNorm:
# (out, batch_mean, batch_var, new_moving_mean, new_moving_var) — nout=3
# graph outputs + 2 aux write-backs; imperative callers see `out` only,
# or all three with output_mean_var=true (batch_norm.cc:408 semantics).
register("BatchNorm", aliases=["batch_norm", "BatchNorm_v1", "batch_norm_v1",
                               "CuDNNBatchNorm"],
         nin=5, input_names=["data", "gamma", "beta", "moving_mean", "moving_var"],
         aux_inputs=(3, 4), nout=3,
         num_visible_outputs=lambda attrs: 3 if (attrs or {}).get("output_mean_var") else 1,
         mutate_aux={3: 3, 4: 4}, mode_dependent=True,
         fill_shapes=_bn_fill, params=_BN_PARAMS)(_batch_norm_impl)


# ---------------------------------------------------------------------------
# _contrib_BNStemConv — fused input-BatchNorm + stem convolution
# ---------------------------------------------------------------------------
#
# The reference ResNet applies BatchNorm(fix_gamma=True) to the raw input
# before the stem conv (symbols/resnet.py bn_data).  Under autodiff the only
# live cotangent into that BN is dbeta = sum(dgrad of the stem conv), so the
# graph pays a full stem dgrad (236 GFLOP at C=3 lane efficiency — 4.4 ms of
# a 94.7 ms ResNet-50 step in an earlier chip record, since deleted) to
# produce a 3-vector.  This op fuses BN+conv with a custom VJP that
# computes dbeta EXACTLY without the dgrad conv:
#
#     sum_m dx[m] = sum_{kh,kw} W[kh,kw] * (sum of g over the output
#                   positions whose window covers tap (kh,kw))
#
# i.e. per-tap rectangle sums of sum_n(g), computed from one prefix-sum
# table — one cheap pass over g instead of a transposed convolution.
# Contract: `data` is a graph INPUT (grad_req null, like the reference's
# data); the op returns zero for d(data).  fix_gamma must be true (gamma
# grads are zero; the reference's bn_data always fixes gamma).

def _bn_stem_fill(attrs, in_shapes):
    out = list(in_shapes)
    data = out[0]
    if data is not None:
        cl = _channels_last(attrs)
        cin = data[-1] if cl else data[1]
        k = attrs["kernel"]
        nf = attrs["num_filter"]
        for i in (1, 2, 4, 5):
            if len(out) > i and out[i] is None:
                out[i] = (cin,)
        if len(out) > 3 and out[3] is None:
            out[3] = (nf,) + tuple(k) + (cin,) if cl \
                else (nf, cin) + tuple(k)
    return out


def _stem_valid_range(k, pad, stride, in_size, out_size):
    """Output positions whose window covers tap k: oh*s + k - pad in
    [0, in_size)."""
    lo = max(0, -(-(pad - k) // stride))          # ceil((pad-k)/stride)
    hi = min(out_size - 1, (in_size - 1 + pad - k) // stride)
    return lo, hi


def _stem_valid_mask(k_dim, pad, stride, in_size, out_size):
    """(K, OUT) 0/1 mask: mask[k, o] = window of output o covers tap k."""
    o = np.arange(out_size)
    rows = []
    for k in range(k_dim):
        lo, hi = _stem_valid_range(k, pad, stride, in_size, out_size)
        rows.append((o >= lo) & (o <= hi))
    return jnp.asarray(np.stack(rows), jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _bn_stem_core(cfg, data, beta, weight):
    return _bn_stem_fwd_impl(cfg, data, beta, weight)[0]


def _bn_stem_norm(cfg, data, beta, mean, inv):
    eps, stride, pad, cl = cfg
    ax = data.ndim - 1 if cl else 1
    bshape = tuple(data.shape[ax] if i == ax else 1
                   for i in range(data.ndim))
    xf = data.astype(jnp.float32)
    return ((xf - mean.reshape(bshape)) * inv.reshape(bshape)
            + beta.astype(jnp.float32).reshape(bshape)).astype(data.dtype)


def _bn_stem_conv(cfg, bn, weight):
    eps, stride, pad, cl = cfg
    spec = ("NHWC", "OHWI", "NHWC") if cl else ("NCHW", "OIHW", "NCHW")
    return lax.conv_general_dilated(
        bn, weight, window_strides=stride, padding=[(p, p) for p in pad],
        dimension_numbers=spec, preferred_element_type=bn.dtype)


def _bn_stem_fwd_impl(cfg, data, beta, weight):
    eps, stride, pad, cl = cfg
    ax = data.ndim - 1 if cl else 1
    red = tuple(i for i in range(data.ndim) if i != ax)
    mean, var = _bn_batch_stats(data, red)
    mean = checkpoint_name(mean, CKPT_STATS)
    var = checkpoint_name(var, CKPT_STATS)
    inv = checkpoint_name(lax.rsqrt(var + eps), CKPT_STATS)
    bn = _bn_stem_norm(cfg, data, beta, mean, inv)
    out = checkpoint_name(_bn_stem_conv(cfg, bn, weight), CKPT_CONV)
    return out, mean, var, inv


def _bn_stem_fwd_vjp(cfg, data, beta, weight):
    out, mean, var, inv = _bn_stem_fwd_impl(cfg, data, beta, weight)
    return out, (data, beta, weight, mean, inv)


def _bn_stem_bwd(cfg, res, g):
    eps, stride, pad, cl = cfg
    data, beta, weight, mean, inv = res
    # wgrad through the conv (bn recomputed from saved stats: the read is
    # the same either way, the store is avoided)
    bn = _bn_stem_norm(cfg, data, beta, mean, inv)
    _, vjp_w = jax.vjp(lambda w: _bn_stem_conv(cfg, bn, w), weight)
    dw = vjp_w(g)[0]
    # dbeta without the dgrad conv: per-tap rectangle sums of sum_n g
    if cl:
        gh, gw = g.shape[1], g.shape[2]
        gsum = jnp.sum(g.astype(jnp.float32), axis=0)          # (OH, OW, O)
        kh_dim, kw_dim = weight.shape[1], weight.shape[2]
        in_h, in_w = data.shape[1], data.shape[2]
    else:
        gh, gw = g.shape[2], g.shape[3]
        gsum = jnp.sum(g.astype(jnp.float32), axis=0)          # (O, OH, OW)
        gsum = jnp.moveaxis(gsum, 0, -1)                       # (OH, OW, O)
        kh_dim, kw_dim = weight.shape[2], weight.shape[3]
        in_h, in_w = data.shape[2], data.shape[3]
    # Per-tap rectangle sums via separable masked contractions.  The r4
    # integral-image form subtracted nearly-equal prefix values (magnitude
    # ~ the whole-table sum), which carried cancellation error right at the
    # test tolerance at 40x40 and worse at 224^2; the
    # masked-matmul form sums each gsum element exactly once per tap, so its
    # error is that of a plain row/column reduction.
    vh = _stem_valid_mask(kh_dim, pad[0], stride[0], in_h, gh)  # (KH, OH)
    vw = _stem_valid_mask(kw_dim, pad[1], stride[1], in_w, gw)  # (KW, OW)
    t1 = jnp.einsum("ah,hwo->awo", vh, gsum,
                    preferred_element_type=jnp.float32)
    t = jnp.einsum("bw,awo->abo", vw, t1,
                   preferred_element_type=jnp.float32)          # (KH, KW, O)
    wf = weight.astype(jnp.float32)
    if cl:
        dbeta = jnp.einsum("hwo,ohwc->c", t, wf)
    else:
        dbeta = jnp.einsum("hwo,ochw->c", t, wf)
    # data is a graph input by contract (reference grad_req null): zero
    return jnp.zeros_like(data), dbeta.astype(beta.dtype), dw


_bn_stem_core.defvjp(_bn_stem_fwd_vjp, _bn_stem_bwd)


@register("_contrib_BNStemConv",
          nin=6,
          input_names=["data", "gamma", "beta", "weight",
                       "moving_mean", "moving_var"],
          aux_inputs=(4, 5), nout=1, mutate_aux={4: 1, 5: 2},
          mode_dependent=True, fill_shapes=_bn_stem_fill,
          params={"eps": P(float, 2e-5), "momentum": P(float, 0.9),
                  "fix_gamma": P(bool, True),
                  "num_filter": P(int), "kernel": P("shape"),
                  "stride": P("shape", ()), "pad": P("shape", ()),
                  "layout": P("str_or_none", None)})
def bn_stem_conv(attrs, data, gamma, beta, weight, mov_mean, mov_var):
    if not attrs["fix_gamma"]:
        raise MXNetError("_contrib_BNStemConv requires fix_gamma=true "
                         "(the reference bn_data contract); use separate "
                         "BatchNorm + Convolution otherwise")
    nd = data.ndim - 2
    if nd != 2:
        raise MXNetError("_contrib_BNStemConv supports 2D convs only")
    stride = tuple(attrs["stride"]) or (1, 1)
    pad = tuple(attrs["pad"]) or (0, 0)
    cfg = (attrs["eps"], stride, pad, _channels_last(attrs))
    training = attrs.get("_training", False)
    if training:
        out = _bn_stem_core(cfg, data, beta.astype(jnp.float32), weight)
        ax = data.ndim - 1 if cfg[3] else 1
        red = tuple(i for i in range(data.ndim) if i != ax)
        mean, var = _bn_batch_stats(data, red)
        mean = lax.stop_gradient(mean)
        var = lax.stop_gradient(var)
        m = attrs["momentum"]
        return out, m * mov_mean + (1 - m) * mean, m * mov_var + (1 - m) * var
    mean = mov_mean.astype(jnp.float32)
    inv = lax.rsqrt(mov_var.astype(jnp.float32) + attrs["eps"])
    bn = _bn_stem_norm(cfg, data, beta, mean, inv)
    return _bn_stem_conv(cfg, bn, weight), mov_mean, mov_var


# ---------------------------------------------------------------------------
# IdentityAttachKLSparseReg — identity with a KL sparsity penalty gradient
# ---------------------------------------------------------------------------
# Reference: src/operator/identity_attach_KL_sparse_reg-inl.h — forward is
# identity over (N, C) activations; backward adds the KL(rho || rho_hat)
# derivative penalty*(-rho/ma + (1-rho)/(1-ma)) where ma is a momentum
# moving average of the per-unit batch mean.  The reference updates ma
# during Backward and treats it as a CONSTANT in the gradient (a
# semi-gradient); here the functional equivalent computes the updated ma in
# forward (it depends only on data), writes it back as an aux, and the
# custom VJP uses it behind stop_gradient.

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _kl_sparse_identity(cfg, data, ma_new):
    return data


def _kl_sparse_fwd(cfg, data, ma_new):
    return data, ma_new


def _kl_sparse_bwd(cfg, ma_new, dy):
    rho, penalty = cfg
    term = penalty * (-rho / ma_new + (1.0 - rho) / (1.0 - ma_new))
    return (dy + term[None, :].astype(dy.dtype),
            jnp.zeros_like(ma_new))


_kl_sparse_identity.defvjp(_kl_sparse_fwd, _kl_sparse_bwd)


@register("IdentityAttachKLSparseReg",
          aliases=["identity_attach_kl_sparse_reg"],
          nin=2, input_names=["data", "moving_avg"],
          aux_inputs=(1,), nout=1, mutate_aux={1: 1}, mode_dependent=True,
          fill_shapes=lambda attrs, s: [
              s[0], (s[0][1],) if s[0] and len(s) > 1 and s[1] is None
              else (s[1] if len(s) > 1 else None)],
          params={"sparseness_target": P(float, 0.1),
                  "penalty": P(float, 0.001),
                  "momentum": P(float, 0.9)})
def identity_attach_kl_sparse_reg(attrs, data, moving_avg):
    if data.ndim != 2:
        raise MXNetError("IdentityAttachKLSparseReg expects 2D (batch, "
                         "hidden) data like the reference")
    training = attrs.get("_training", False)
    if not training:
        return data, moving_avg
    m = attrs["momentum"]
    avg = jnp.mean(data.astype(jnp.float32), axis=0)
    ma_new = lax.stop_gradient(m * moving_avg + (1 - m) * avg)
    out = _kl_sparse_identity(
        (attrs["sparseness_target"], attrs["penalty"]), data, ma_new)
    return out, ma_new


@register("InstanceNorm", aliases=["instance_norm"],
          nin=3, input_names=["data", "gamma", "beta"],
          fill_shapes=lambda attrs, s: [s[0],
                                        (s[0][1],) if s[0] and len(s) > 1 and s[1] is None else s[1],
                                        (s[0][1],) if s[0] and len(s) > 2 and s[2] is None else s[2]],
          params={"eps": P(float, 1e-3)})
def instance_norm(attrs, data, gamma, beta):
    red = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=red, keepdims=True)
    var = jnp.var(data, axis=red, keepdims=True)
    bshape = (1, -1) + (1,) * (data.ndim - 2)
    out = (data - mean) * lax.rsqrt(var + attrs["eps"])
    return out * gamma.reshape(bshape) + beta.reshape(bshape)


@register("LayerNorm", aliases=["layer_norm"],
          nin=3, input_names=["data", "gamma", "beta"],
          fill_shapes=lambda attrs, s: [s[0],
                                        (s[0][attrs.get("axis", -1)],) if s[0] and len(s) > 1 and s[1] is None else s[1],
                                        (s[0][attrs.get("axis", -1)],) if s[0] and len(s) > 2 and s[2] is None else s[2]],
          params={"axis": P(int, -1), "eps": P(float, 1e-5),
                  "output_mean_var": P(bool, False)})
def layer_norm(attrs, data, gamma, beta):
    ax = attrs["axis"]
    mean = jnp.mean(data, axis=ax, keepdims=True)
    var = jnp.var(data, axis=ax, keepdims=True)
    out = (data - mean) * lax.rsqrt(var + attrs["eps"])
    bshape = [1] * data.ndim
    bshape[ax] = data.shape[ax]
    return out * gamma.reshape(bshape) + beta.reshape(bshape)


@register("L2Normalization", aliases=["l2_normalization"],
          params={"eps": P(float, 1e-10),
                  "mode": P(str, "instance", choices=["instance", "channel", "spatial"])})
def l2_normalization(attrs, data):
    mode = attrs["mode"]
    if mode == "instance":
        red = tuple(range(1, data.ndim))
    elif mode == "channel":
        red = (1,)
    else:  # spatial
        red = tuple(range(2, data.ndim))
    n = jnp.sqrt(jnp.sum(jnp.square(data), axis=red, keepdims=True) + attrs["eps"])
    return data / n


@register("LRN", aliases=["lrn"],
          params={"alpha": P(float, 1e-4), "beta": P(float, 0.75),
                  "knorm": P(float, 2.0), "nsize": P(int)})
def lrn(attrs, data):
    n = attrs["nsize"]
    sq = jnp.square(data)
    # sum over channel window of size nsize centred at each channel (NCHW)
    pad = n // 2
    sq_pad = jnp.pad(sq, [(0, 0), (pad, pad)] + [(0, 0)] * (data.ndim - 2))
    windows = sum(sq_pad[:, i:i + data.shape[1]] for i in range(n))
    norm = jnp.power(attrs["knorm"] + attrs["alpha"] / n * windows, -attrs["beta"])
    return data * norm


# ---------------------------------------------------------------------------
# Activations / softmax family
# ---------------------------------------------------------------------------

@register("Activation", aliases=["activation"],
          params={"act_type": P(str, choices=["relu", "sigmoid", "tanh",
                                              "softrelu", "softsign"])})
def activation(attrs, x):
    t = attrs["act_type"]
    if t == "relu":
        return jnp.maximum(x, 0)
    if t == "sigmoid":
        return jax.nn.sigmoid(x)
    if t == "tanh":
        return jnp.tanh(x)
    if t == "softrelu":
        return jax.nn.softplus(x)
    return jax.nn.soft_sign(x)


@register("softmax", params={"axis": P(int, -1),
                             "temperature": P("float_or_none", None)})
def softmax_op(attrs, x):
    t = attrs["temperature"]
    if t:
        x = x / t
    return jax.nn.softmax(x, axis=attrs["axis"])


@register("log_softmax", params={"axis": P(int, -1),
                                 "temperature": P("float_or_none", None)})
def log_softmax_op(attrs, x):
    t = attrs["temperature"]
    if t:
        x = x / t
    return jax.nn.log_softmax(x, axis=attrs["axis"])


@register("SoftmaxActivation", aliases=["softmax_activation"],
          params={"mode": P(str, "instance", choices=["instance", "channel"])})
def softmax_activation(attrs, x):
    axis = 1 if attrs["mode"] == "channel" else -1
    if attrs["mode"] == "instance" and x.ndim > 2:
        shp = x.shape
        return jax.nn.softmax(x.reshape(shp[0], -1), axis=-1).reshape(shp)
    return jax.nn.softmax(x, axis=axis)


# -- SoftmaxOutput: loss head with implicit gradient ------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6, 7))
def _softmax_output_fn(data, label, grad_scale, ignore_label, multi_output,
                       use_ignore, normalization, smooth_alpha):
    return _softmax_output_fwd_only(data, multi_output)


def _softmax_output_fwd_only(data, multi_output):
    if multi_output:
        return jax.nn.softmax(data, axis=1)
    if data.ndim > 2:
        shp = data.shape
        return jax.nn.softmax(data.reshape(shp[0], -1), axis=-1).reshape(shp)
    return jax.nn.softmax(data, axis=-1)


def _softmax_output_fwd(data, label, grad_scale, ignore_label, multi_output,
                        use_ignore, normalization, smooth_alpha):
    out = _softmax_output_fwd_only(data, multi_output)
    return out, (out, label)


def _softmax_output_bwd(grad_scale, ignore_label, multi_output, use_ignore,
                        normalization, smooth_alpha, res, g):
    # reference semantics (softmax_output-inl.h): dL/ddata = p - onehot(label),
    # regardless of incoming cotangent g (backward() needs no head grad).
    out, label = res
    axis = 1 if multi_output else -1
    k = out.shape[axis]
    lab = label.astype(jnp.int32)
    onehot = jax.nn.one_hot(lab, k, axis=axis, dtype=out.dtype)
    if smooth_alpha:
        onehot = onehot * (1 - smooth_alpha) + smooth_alpha / (k - 1) * (1 - onehot)
    grad = out - onehot
    valid = None
    if use_ignore:
        mask = (lab != int(ignore_label)).astype(out.dtype)
        grad = grad * jnp.expand_dims(mask, axis)
        valid = jnp.maximum(mask.sum(), 1.0)
    if normalization == "batch":
        grad = grad / out.shape[0]
    elif normalization == "valid":
        n = valid if valid is not None else float(np.prod(label.shape))
        grad = grad / n
    return (grad * grad_scale, jnp.zeros_like(label))


_softmax_output_fn.defvjp(_softmax_output_fwd, _softmax_output_bwd)


@register("SoftmaxOutput", aliases=["softmax_output", "Softmax"],
          nin=2, input_names=["data", "label"],
          fill_shapes=lambda attrs, s: [s[0], (s[0][0],) if s[0] and len(s) > 1 and s[1] is None else s[1]],
          params={"grad_scale": P(float, 1.0), "ignore_label": P(float, -1.0),
                  "multi_output": P(bool, False), "use_ignore": P(bool, False),
                  "preserve_shape": P(bool, False),
                  "normalization": P(str, "null", choices=["null", "batch", "valid"]),
                  "out_grad": P(bool, False), "smooth_alpha": P(float, 0.0)})
def softmax_output(attrs, data, label):
    return _softmax_output_fn(data, label, attrs["grad_scale"],
                              attrs["ignore_label"], attrs["multi_output"],
                              attrs["use_ignore"], attrs["normalization"],
                              attrs["smooth_alpha"])


# -- Regression heads -------------------------------------------------------

def _make_regression_op(name, fwd, grad_fn):
    @functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
    def op(data, label, grad_scale):
        return fwd(data)

    def op_fwd(data, label, grad_scale):
        out = fwd(data)
        return out, (out, label)

    def op_bwd(grad_scale, res, g):
        out, label = res
        num = float(np.prod(out.shape)) / out.shape[0]
        grad = grad_fn(out, label.reshape(out.shape)) * grad_scale / num
        return (grad, jnp.zeros_like(label))

    op.defvjp(op_fwd, op_bwd)

    @register(name, aliases=[_snake(name)], nin=2, input_names=["data", "label"],
              fill_shapes=lambda attrs, s: [s[0], s[0] if s[0] and len(s) > 1 and s[1] is None else s[1]],
              params={"grad_scale": P(float, 1.0)})
    def impl(attrs, data, label, _op=op):
        return _op(data, label, attrs["grad_scale"])
    return impl


def _snake(name):
    out = []
    for i, c in enumerate(name):
        if c.isupper() and i > 0:
            out.append("_")
        out.append(c.lower())
    return "".join(out)


_make_regression_op("LinearRegressionOutput", lambda x: x,
                    lambda out, lab: out - lab)
_make_regression_op("LogisticRegressionOutput", jax.nn.sigmoid,
                    lambda out, lab: out - lab)
_make_regression_op("MAERegressionOutput", lambda x: x,
                    lambda out, lab: jnp.sign(out - lab))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _svm_output_fn(data, label, margin, reg_coef):
    return data


def _svm_fwd(data, label, margin, reg_coef):
    return data, (data, label)


def _svm_bwd(margin, reg_coef, res, g):
    data, label = res
    lab = label.astype(jnp.int32)
    k = data.shape[-1]
    onehot = jax.nn.one_hot(lab, k, dtype=data.dtype)
    correct = jnp.sum(data * onehot, axis=-1, keepdims=True)
    violate = ((data - correct + margin) > 0).astype(data.dtype) * (1 - onehot)
    grad = violate - onehot * violate.sum(axis=-1, keepdims=True)
    return (grad * reg_coef, jnp.zeros_like(label))


_svm_output_fn.defvjp(_svm_fwd, _svm_bwd)


@register("SVMOutput", aliases=["svm_output"], nin=2,
          input_names=["data", "label"],
          fill_shapes=lambda attrs, s: [s[0], (s[0][0],) if s[0] and len(s) > 1 and s[1] is None else s[1]],
          params={"margin": P(float, 1.0), "regularization_coefficient": P(float, 1.0),
                  "use_linear": P(bool, False)})
def svm_output(attrs, data, label):
    return _svm_output_fn(data, label, attrs["margin"],
                          attrs["regularization_coefficient"])


# -- MakeLoss (legacy layer op: forward data, backward grad_scale) ----------

@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _make_loss_fn(data, grad_scale, normalization):
    return data


def _make_loss_fwd(data, grad_scale, normalization):
    return data, data.shape


def _make_loss_bwd(grad_scale, normalization, shape, g):
    scale = grad_scale
    if normalization == "batch":
        scale = scale / shape[0]
    elif normalization == "valid":
        scale = scale / float(np.prod(shape))
    return (jnp.full(shape, scale),)


_make_loss_fn.defvjp(_make_loss_fwd, _make_loss_bwd)


@register("MakeLoss",
          params={"grad_scale": P(float, 1.0),
                  "valid_thresh": P(float, 0.0),
                  "normalization": P(str, "null", choices=["null", "batch", "valid"])})
def make_loss_layer(attrs, data):
    return _make_loss_fn(data, attrs["grad_scale"], attrs["normalization"])


# ---------------------------------------------------------------------------
# Dropout — explicit PRNG operand
# ---------------------------------------------------------------------------

@register("Dropout", aliases=["dropout"], stochastic=True, mode_dependent=True,
          params={"p": P(float, 0.5),
                  "mode": P(str, "training", choices=["training", "always"]),
                  "axes": P("shape", ())})
def dropout(attrs, rng, x):
    p = attrs["p"]
    active = attrs.get("_training", False) or attrs["mode"] == "always"
    if not active or p <= 0:
        return x
    shape = x.shape
    if attrs["axes"]:
        shape = tuple(1 if i in attrs["axes"] else s for i, s in enumerate(shape))
    keep = jax.random.bernoulli(rng, 1.0 - p, shape).astype(x.dtype)
    return x * keep / (1.0 - p)


# ---------------------------------------------------------------------------
# Embedding
# ---------------------------------------------------------------------------

def _emb_grad_stype(attrs, in_stypes):
    # sparse_grad=True: backward emits a row-sparse gradient with support =
    # the batch's (deduplicated) ids — O(nnz) through backward, update and
    # comm (indexing_op.cc:32-80 SparseEmbeddingOpBackwardRsp)
    return "row_sparse" if attrs.get("sparse_grad") else "default"


def _emb_sparse_bwd(attrs, in_vals, cot):
    from .sparse_vals import RSPValue
    from .sparse_ops import dedup_rows
    data = in_vals[0]
    idx = jnp.clip(data.astype(jnp.int32), 0,
                   attrs["input_dim"] - 1).reshape(-1)
    vals = cot.reshape((idx.shape[0], cot.shape[-1])).astype(jnp.float32)
    rows, summed = dedup_rows(idx, vals)
    return RSPValue(summed, rows,
                    (attrs["input_dim"], attrs["output_dim"]))


@register("Embedding", aliases=["embedding", "_contrib_SparseEmbedding"],
          nin=2, input_names=["data", "weight"], sparse_aware=True,
          sparse_grad={1: {"stype": _emb_grad_stype, "bwd": _emb_sparse_bwd}},
          fill_shapes=lambda attrs, s: [s[0],
                                        (attrs["input_dim"], attrs["output_dim"]) if len(s) > 1 and s[1] is None else s[1]],
          params={"input_dim": P(int), "output_dim": P(int),
                  "dtype": P(str, "float32"), "sparse_grad": P(bool, False)})
def embedding(attrs, data, weight):
    from .sparse_vals import RSPValue, densify
    idx = jnp.clip(densify(data).astype(jnp.int32), 0,
                   attrs["input_dim"] - 1)
    if isinstance(weight, RSPValue):
        # rsp-STORED table (only the pulled rows live on device): gather by
        # id lookup — the full (input_dim, output_dim) array never exists
        from .sparse_ops import rsp_lookup
        return rsp_lookup(weight, idx)
    return jnp.take(densify(weight), idx, axis=0)


# ---------------------------------------------------------------------------
# UpSampling / Crop
# ---------------------------------------------------------------------------

@register("UpSampling", aliases=["up_sampling"], variable_inputs=True,
          key_var_num_args="num_args",
          params={"scale": P(int), "num_filter": P(int, 0),
                  "sample_type": P(str, "nearest", choices=["nearest", "bilinear"]),
                  "multi_input_mode": P(str, "concat", choices=["concat", "sum"]),
                  "num_args": P(int, 1), "workspace": P(int, 512)})
def upsampling(attrs, *xs):
    s = attrs["scale"]
    outs = []
    for x in xs:
        if attrs["sample_type"] == "nearest":
            y = jnp.repeat(jnp.repeat(x, s, axis=2), s, axis=3)
        else:
            n, c, h, w = x.shape
            y = jax.image.resize(x, (n, c, h * s, w * s), method="bilinear")
        outs.append(y)
    if len(outs) == 1:
        return outs[0]
    if attrs["multi_input_mode"] == "sum":
        return sum(outs)
    return jnp.concatenate(outs, axis=1)


@register("Crop", nin=lambda attrs: int((attrs or {}).get("num_args", 1)),
          variable_inputs=True, key_var_num_args="num_args",
          params={"num_args": P(int, 1), "offset": P("shape", (0, 0)),
                  "h_w": P("shape", (0, 0)), "center_crop": P(bool, False)})
def crop_layer(attrs, *xs):
    x = xs[0]
    if len(xs) == 2:
        th, tw = xs[1].shape[2], xs[1].shape[3]
    else:
        th, tw = attrs["h_w"]
    if attrs["center_crop"]:
        oh = (x.shape[2] - th) // 2
        ow = (x.shape[3] - tw) // 2
    else:
        oh, ow = attrs["offset"]
    return x[:, :, oh:oh + th, ow:ow + tw]


# ---------------------------------------------------------------------------
# Sequence ops (src/operator/sequence_*.cc)
# ---------------------------------------------------------------------------

def _seq_len_or_full(use_len, seq_len, x):
    # data layout: (seq_len, batch, ...) per reference
    if use_len and seq_len is not None:
        return seq_len.astype(jnp.int32)
    return jnp.full((x.shape[1],), x.shape[0], dtype=jnp.int32)


@register("SequenceMask", aliases=["sequence_mask"],
          nin=lambda attrs: 2 if (attrs or {}).get("use_sequence_length") else 1,
          input_names=["data", "sequence_length"],
          params={"use_sequence_length": P(bool, False), "value": P(float, 0.0),
                  "axis": P(int, 0)})
def sequence_mask(attrs, data, seq_len=None):
    if not attrs["use_sequence_length"]:
        return data
    # time axis: 0 keeps the reference (T, B, ...) layout; any axis >= 1
    # assumes batch at axis 0 (the generalization analysis/rewrite.py
    # splices masks through — e.g. axis 2 of (B, T_q, T_k) attention
    # scores).  axis=1 reduces to the reference (B, T, ...) behaviour.
    ax = attrs["axis"]
    T = data.shape[ax]
    steps = jnp.arange(T)
    sl = seq_len.astype(jnp.int32)
    if ax == 0:
        mask = steps[:, None] < sl[None, :]
        mask = mask.reshape(mask.shape + (1,) * (data.ndim - 2))
    else:
        mask = (steps.reshape((1,) * ax + (T,) + (1,) * (data.ndim - ax - 1))
                < sl.reshape((sl.shape[0],) + (1,) * (data.ndim - 1)))
    return jnp.where(mask, data, jnp.asarray(attrs["value"], data.dtype))


@register("SequenceLast", aliases=["sequence_last"],
          nin=lambda attrs: 2 if (attrs or {}).get("use_sequence_length") else 1,
          input_names=["data", "sequence_length"],
          params={"use_sequence_length": P(bool, False), "axis": P(int, 0)})
def sequence_last(attrs, data, seq_len=None):
    ax = attrs["axis"]
    if not attrs["use_sequence_length"]:
        return jnp.take(data, data.shape[ax] - 1, axis=ax)
    idx = seq_len.astype(jnp.int32) - 1  # (batch,)
    if ax == 0:
        return jax.vmap(lambda d, i: d[i], in_axes=(1, 0))(data, idx)
    return jax.vmap(lambda d, i: d[i])(data, idx)


@register("SequenceReverse", aliases=["sequence_reverse"],
          nin=lambda attrs: 2 if (attrs or {}).get("use_sequence_length") else 1,
          input_names=["data", "sequence_length"],
          params={"use_sequence_length": P(bool, False), "axis": P(int, 0)})
def sequence_reverse(attrs, data, seq_len=None):
    if not attrs["use_sequence_length"]:
        return jnp.flip(data, axis=0)
    T = data.shape[0]
    sl = seq_len.astype(jnp.int32)  # (batch,)
    t = jnp.arange(T)[:, None]
    src = jnp.where(t < sl[None, :], sl[None, :] - 1 - t, t)  # (T, batch)
    return jnp.take_along_axis(
        data, src.reshape(src.shape + (1,) * (data.ndim - 2)), axis=0)


# ---------------------------------------------------------------------------
# Spatial transformer family
# ---------------------------------------------------------------------------

def _bilinear_sample(data, grid):
    """data (N,C,H,W); grid (N,2,Ho,Wo) with x,y in [-1,1]."""
    N, C, H, W = data.shape
    gx = (grid[:, 0] + 1) * (W - 1) / 2.0
    gy = (grid[:, 1] + 1) * (H - 1) / 2.0
    x0 = jnp.floor(gx)
    y0 = jnp.floor(gy)
    wx = gx - x0
    wy = gy - y0

    def gather(yy, xx):
        yy = jnp.clip(yy, 0, H - 1).astype(jnp.int32)
        xx = jnp.clip(xx, 0, W - 1).astype(jnp.int32)
        flat = data.reshape(N, C, H * W)
        idx = (yy * W + xx).reshape(N, 1, -1)
        out = jnp.take_along_axis(flat, jnp.broadcast_to(idx, (N, C, idx.shape[-1])), axis=2)
        return out.reshape((N, C) + gx.shape[1:])

    in_x = (gx >= 0) & (gx <= W - 1)
    in_y = (gy >= 0) & (gy <= H - 1)
    valid = (in_x & in_y).astype(data.dtype)[:, None]
    v00 = gather(y0, x0)
    v01 = gather(y0, x0 + 1)
    v10 = gather(y0 + 1, x0)
    v11 = gather(y0 + 1, x0 + 1)
    wx = wx[:, None]
    wy = wy[:, None]
    out = (v00 * (1 - wx) * (1 - wy) + v01 * wx * (1 - wy)
           + v10 * (1 - wx) * wy + v11 * wx * wy)
    return out * valid


@register("BilinearSampler", aliases=["bilinear_sampler"], nin=2,
          input_names=["data", "grid"])
def bilinear_sampler(attrs, data, grid):
    return _bilinear_sample(data, grid)


@register("GridGenerator", aliases=["grid_generator"],
          nin=1, input_names=["data"],
          params={"transform_type": P(str, "affine", choices=["affine", "warp"]),
                  "target_shape": P("shape", (0, 0))})
def grid_generator(attrs, data):
    if attrs["transform_type"] == "affine":
        h, w = attrs["target_shape"]
        theta = data.reshape(-1, 2, 3)
        ys = jnp.linspace(-1, 1, h)
        xs = jnp.linspace(-1, 1, w)
        gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
        ones = jnp.ones_like(gx)
        coords = jnp.stack([gx.ravel(), gy.ravel(), ones.ravel()])  # (3, h*w)
        out = jnp.einsum("nij,jk->nik", theta, coords)  # (n,2,h*w)
        return out.reshape(-1, 2, h, w)
    # warp: data is (n,2,h,w) flow field
    n, _, h, w = data.shape
    ys = jnp.linspace(-1, 1, h)
    xs = jnp.linspace(-1, 1, w)
    gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
    base = jnp.stack([gx, gy])[None]
    norm = jnp.array([2.0 / max(w - 1, 1), 2.0 / max(h - 1, 1)]).reshape(1, 2, 1, 1)
    return base + data * norm


@register("SpatialTransformer", aliases=["spatial_transformer"], nin=2,
          input_names=["data", "loc"],
          params={"target_shape": P("shape", (0, 0)),
                  "transform_type": P(str, "affine"),
                  "sampler_type": P(str, "bilinear"),
                  "cudnn_off": P(bool, False)})
def spatial_transformer(attrs, data, loc):
    grid = grid_generator({"transform_type": "affine",
                           "target_shape": attrs["target_shape"]}, loc)
    return _bilinear_sample(data, grid)

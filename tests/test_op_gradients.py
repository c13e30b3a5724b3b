"""Registry-wide numeric-gradient sweep.

The reference's universal op test is `check_numeric_gradient`
(python/mxnet/test_utils.py:789), applied per-op across
tests/python/unittest/test_operator.py.  Here the sweep is systematic:
every op in the registry must either have a gradient spec below or an
explicit skip entry with a reason — a meta-test enforces exhaustiveness,
so newly registered ops fail CI until they are covered.

Gradients are validated in float64 (central differences vs jax.grad) via
mxnet_tpu.test_utils.check_op_gradient.  A canary test breaks an op's VJP
on purpose and asserts the checker catches it.
"""
import numpy as np
import pytest

import mxnet_tpu  # noqa: F401 — populate the registry
from mxnet_tpu.ops.registry import _REGISTRY, get_op
from mxnet_tpu.test_utils import check_op_gradient, check_numeric_gradient

R = np.random.default_rng(42)


def _u(*shape, lo=-1.0, hi=1.0):
    return R.uniform(lo, hi, shape)


def _pos(*shape, lo=0.5, hi=2.0):
    return R.uniform(lo, hi, shape)


def _distinct(*shape):
    """Values with distinct magnitudes: keeps max/min/sort kink-free."""
    n = int(np.prod(shape))
    vals = np.linspace(-1.0, 1.0, n) + R.uniform(-0.3, 0.3, n) / n
    return R.permutation(vals).reshape(shape)


def _away_from_int(*shape):
    """Values bounded away from integers (safe for floor/ceil/round)."""
    return R.uniform(0.15, 0.35, shape) + R.integers(-2, 3, shape)


# --- spec table ------------------------------------------------------------
# op -> dict(attrs=..., inputs=callable->list, wrt=..., rtol=..., atol=...,
#            training=..., eps=...)
# default wrt: every float input.

def S(inputs, attrs=None, **kw):
    return dict(inputs=inputs, attrs=attrs or {}, **kw)


_ELEM_UNARY_SAFE = [
    "abs", "square", "exp", "expm1", "sin", "cos", "sinh", "cosh", "tanh",
    "arctan", "arcsinh", "softsign", "negative", "reciprocal", "sigmoid",
    "relu", "erf", "degrees", "radians", "_copy",
]
_ELEM_UNARY_POS = ["sqrt", "rsqrt", "cbrt", "rcbrt", "log", "log10", "log2",
                   "log1p", "gamma", "gammaln"]
_ZERO_GRAD_UNARY = ["ceil", "floor", "round", "rint", "trunc", "fix", "sign",
                    "logical_not", "ones_like", "zeros_like"]
_BIN_BROADCAST = ["_add", "_sub", "_mul", "_div", "_maximum", "_minimum",
                  "_hypot"]
_BIN_ZERO_GRAD = ["equal", "not_equal", "greater", "greater_equal", "lesser",
                  "lesser_equal", "logical_and", "logical_or", "logical_xor"]
_SCALAR_OPS = ["_plus_scalar", "_minus_scalar", "_rminus_scalar",
               "_mul_scalar", "_div_scalar", "_rdiv_scalar",
               "_maximum_scalar", "_minimum_scalar", "_hypot_scalar",
               "_scatter_plus_scalar", "_scatter_minus_scalar"]
_SCALAR_ZERO_GRAD = ["_equal_scalar", "_not_equal_scalar", "_greater_scalar",
                     "_greater_equal_scalar", "_lesser_scalar",
                     "_lesser_equal_scalar", "_logical_and_scalar",
                     "_logical_or_scalar", "_logical_xor_scalar"]
_REDUCE = ["sum", "mean", "nansum"]

SPECS = {}
for name in _ELEM_UNARY_SAFE:
    # offset from 0 so |x|, relu, sign kinks are not sampled
    SPECS[name] = S(lambda: [_u(2, 3, lo=0.2, hi=1.2)
                             * R.choice([-1, 1], (2, 3))])
SPECS["abs"] = S(lambda: [_pos(2, 3)])
SPECS["relu"] = S(lambda: [_u(2, 3, lo=0.2, hi=1.2)
                           * np.where(np.arange(6).reshape(2, 3) % 2, 1, -1)])
for name in _ELEM_UNARY_POS:
    SPECS[name] = S(lambda: [_pos(2, 3)])
for name in _ZERO_GRAD_UNARY:
    SPECS[name] = S(lambda: [_away_from_int(2, 3)])
SPECS["arcsin"] = S(lambda: [_u(2, 3, lo=-0.8, hi=0.8)])
SPECS["arccos"] = S(lambda: [_u(2, 3, lo=-0.8, hi=0.8)])
SPECS["arctanh"] = S(lambda: [_u(2, 3, lo=-0.8, hi=0.8)])
SPECS["arccosh"] = S(lambda: [_pos(2, 3, lo=1.5, hi=3.0)])
SPECS["erfinv"] = S(lambda: [_u(2, 3, lo=-0.7, hi=0.7)])
SPECS["tan"] = S(lambda: [_u(2, 3, lo=-1.0, hi=1.0)])
SPECS["smooth_l1"] = S(lambda: [_u(2, 3, lo=0.2, hi=0.7)],
                       {"scalar": 1.0})
SPECS["clip"] = S(lambda: [_u(2, 3, lo=-0.4, hi=0.4)],
                  {"a_min": -0.8, "a_max": 0.8})

for name in _BIN_BROADCAST:
    SPECS[name] = S(lambda: [_distinct(2, 3), _distinct(2, 3) + 0.05])
SPECS["_div"] = S(lambda: [_u(2, 3), _pos(2, 3)])
SPECS["_mod"] = S(lambda: [_pos(2, 3, lo=2.2, hi=2.8),
                           _pos(2, 3, lo=0.9, hi=1.1)])
SPECS["_power"] = S(lambda: [_pos(2, 3), _u(2, 3)])
for name in _BIN_ZERO_GRAD:
    SPECS[name] = S(lambda: [_distinct(2, 3), _distinct(2, 3) + 0.05])
for name in _SCALAR_OPS:
    SPECS[name] = S(lambda: [_pos(2, 3)], {"scalar": 1.7})
SPECS["_rmod_scalar"] = S(lambda: [_pos(2, 3, lo=0.9, hi=1.1)],
                          {"scalar": 2.5})
SPECS["_mod_scalar"] = S(lambda: [_pos(2, 3, lo=2.2, hi=2.8)],
                         {"scalar": 1.0})
SPECS["_power_scalar"] = S(lambda: [_pos(2, 3)], {"scalar": 1.7})
SPECS["_rpow_scalar"] = S(lambda: [_u(2, 3)], {"scalar": 1.7})
SPECS["_scatter_elemwise_div"] = S(lambda: [_u(2, 3), _pos(2, 3)])
for name in _SCALAR_ZERO_GRAD:
    SPECS[name] = S(lambda: [_pos(2, 3)], {"scalar": 1.0})

for name in _REDUCE:
    SPECS[name] = S(lambda: [_u(2, 3, 4)], {"axis": (1,)})
SPECS["prod"] = S(lambda: [_pos(2, 3)], {"axis": (1,)})
SPECS["nanprod"] = S(lambda: [_pos(2, 3)], {"axis": (1,)})
SPECS["max"] = S(lambda: [_distinct(2, 3)], {"axis": (1,)})
SPECS["min"] = S(lambda: [_distinct(2, 3)], {"axis": (1,)})
SPECS["norm"] = S(lambda: [_u(2, 3, lo=0.3, hi=1.0)])
SPECS["mean"] = S(lambda: [_u(2, 3, 4)], {"axis": (1,)})
SPECS["pick"] = S(lambda: [_u(3, 4), np.array([0., 2., 1.])], wrt=[0])
SPECS["argmax_channel"] = None  # int output — see SKIPS
SPECS["softmax_cross_entropy"] = None

# shape/layout ops
SPECS["Reshape"] = S(lambda: [_u(2, 6)], {"shape": (3, 4)})
SPECS["Flatten"] = S(lambda: [_u(2, 3, 4)])
SPECS["transpose"] = S(lambda: [_u(2, 3, 4)], {"axes": (2, 0, 1)})
SPECS["expand_dims"] = S(lambda: [_u(2, 3)], {"axis": 1})
SPECS["squeeze"] = S(lambda: [_u(2, 1, 3)], {"axis": (1,)})
SPECS["slice"] = S(lambda: [_u(4, 5)], {"begin": (1, 0), "end": (3, 4)})
SPECS["slice_axis"] = S(lambda: [_u(4, 5)],
                        {"axis": 1, "begin": 1, "end": 4})
SPECS["slice_like"] = S(lambda: [_u(4, 5), _u(2, 3)], wrt=[0])
SPECS["_slice_assign"] = S(lambda: [_u(4, 5), _u(2, 4)],
                           {"begin": (1, 0), "end": (3, 4)})
SPECS["_slice_assign_scalar"] = S(lambda: [_u(4, 5)],
                                  {"begin": (1, 0), "end": (3, 4),
                                   "scalar": 0.7})
SPECS["repeat"] = S(lambda: [_u(2, 3)], {"repeats": 2, "axis": 1})
SPECS["tile"] = S(lambda: [_u(2, 3)], {"reps": (2, 2)})
SPECS["reverse"] = S(lambda: [_u(2, 3)], {"axis": (1,)})
SPECS["stack"] = S(lambda: [_u(2, 3), _u(2, 3)],
                   {"num_args": 2, "axis": 1})
SPECS["Concat"] = S(lambda: [_u(2, 3), _u(2, 3)],
                    {"num_args": 2, "dim": 1})
SPECS["add_n"] = S(lambda: [_u(2, 3), _u(2, 3), _u(2, 3)], {"num_args": 3})
SPECS["SliceChannel"] = S(lambda: [_u(2, 4)], {"num_outputs": 2, "axis": 1})
SPECS["SwapAxis"] = S(lambda: [_u(2, 3, 4)], {"dim1": 0, "dim2": 2})
SPECS["Pad"] = S(lambda: [_u(1, 2, 3, 4)],
                 {"mode": "constant",
                  "pad_width": (0, 0, 0, 0, 1, 1, 2, 2)})
SPECS["reshape_like"] = S(lambda: [_u(2, 6), _u(3, 4)], wrt=[0])
SPECS["Cast"] = S(lambda: [_u(2, 3)], {"dtype": "float64"})
SPECS["broadcast_axis"] = S(lambda: [_u(2, 1, 3)], {"axis": (1,), "size": (4,)})
SPECS["broadcast_to"] = S(lambda: [_u(2, 1, 3)], {"shape": (2, 4, 3)})
SPECS["where"] = S(lambda: [np.array([[1., 0., 1.], [0., 1., 0.]]),
                            _u(2, 3), _u(2, 3)], wrt=[1, 2])
SPECS["Crop"] = S(lambda: [_u(1, 2, 6, 6)],
                  {"num_args": 1, "h_w": (3, 3), "center_crop": True})
SPECS["_identity_with_attr_like_rhs"] = S(lambda: [_u(2, 3), _u(2, 3)],
                                          wrt=[0])
SPECS["UpSampling"] = S(lambda: [_u(1, 2, 3, 3)],
                        {"num_args": 1, "scale": 2, "sample_type": "nearest"})
SPECS["one_hot"] = None  # int input only

# indexing
SPECS["take"] = S(lambda: [_u(5, 3), np.array([0, 2, 4])], wrt=[0])
SPECS["batch_take"] = S(lambda: [_u(3, 4), np.array([0, 2, 1])], wrt=[0])
SPECS["gather_nd"] = S(lambda: [_u(4, 5),
                                np.array([[0, 2], [1, 3]]).T], wrt=[0])
SPECS["scatter_nd"] = S(lambda: [_u(2), np.array([[0, 3]])],
                        {"shape": (6,)}, wrt=[0])
SPECS["_scatter_set_nd"] = S(lambda: [_u(2), np.array([[0, 3]])],
                             {"shape": (6,)}, wrt=[0])
SPECS["_cache_write_row"] = S(
    lambda: [_u(3, 5, 2), _u(3, 2), np.array([0., 4., 2.])], wrt=[0, 1])
SPECS["_cache_write_rows"] = S(
    lambda: [_u(3, 5, 2), _u(3, 2, 2), np.array([0., 3., 2.]),
             np.array([0., 2., 1.])], wrt=[0, 1])
# decoder-block ops (ops/transformer.py): plain jax, so jax.vjp is exact;
# positions are data, not differentiated
SPECS["RMSNorm"] = S(lambda: [_u(2, 3, 6), _pos(6)], {"eps": 1e-6})
SPECS["_dense"] = S(lambda: [_u(2, 3, 5), _u(4, 5)], {"num_hidden": 4})
SPECS["_rotary"] = S(lambda: [_u(2, 3, 8), np.array([[0., 5., 11.]])],
                     {"head_dim": 4, "theta": 100.0}, wrt=[0])
SPECS["_gqa_decode"] = S(
    lambda: [_u(3, 8), _u(3, 4, 4), _u(3, 4, 4), np.array([0., 3., 6.])],
    {"num_heads": 4, "num_kv_heads": 2, "window": 4}, wrt=[0, 1, 2])
SPECS["_gqa_prefill"] = S(
    lambda: [_u(2, 5, 8), _u(2, 5, 4), _u(2, 5, 4)],
    {"num_heads": 4, "num_kv_heads": 2, "window": 3, "block": 2})
# at a few rows every held expert multiplies every row (the sorted
# path's loop has a data-dependent trip count and no reverse rule);
# router logits spread apart so no choice sits on a tie
SPECS["_moe_experts"] = S(
    lambda: [_u(3, 4), _distinct(3, 5) * 3.0, _u(5, 3, 4), _u(5, 3, 4),
             _u(5, 3, 4)], {"top_k": 2})
SPECS["_gated_act"] = S(lambda: [_u(2, 3, 4), _u(2, 3, 4)])
# the gated short convolution: projections [B, C, x] of 2 channels, a
# state of two rows, three taps; a row's length is data
SPECS["_short_conv_step"] = S(
    lambda: [_u(3, 6), _u(3, 2, 2), _u(3, 2)])
SPECS["_short_conv_seq"] = S(
    lambda: [_u(2, 5, 6), np.array([5., 2.]), _u(3, 2)], wrt=[0, 2])
# the selective state space: 2 heads of 2 over a state of 2 in one
# group; the scan over 5 positions in chunks of 2, rows of lengths 5, 3
SPECS["_ssd_step"] = S(
    lambda: [_u(2, 4), _u(2, 2), _u(2, 2), _u(2, 2), _u(2, 2, 2, 2),
             _u(2), _u(2), _u(2)], {"num_groups": 1})
SPECS["_ssd_scan"] = S(
    lambda: [_u(2, 5, 4), _u(2, 5, 2), _u(2, 5, 2), _u(2, 5, 2),
             np.array([5., 3.]), _u(2), _u(2), _u(2)],
    {"num_groups": 1, "chunk": 2}, wrt=[0, 1, 2, 3, 5, 6, 7])
SPECS["Embedding"] = S(lambda: [np.array([0., 2., 1.]), _u(4, 3)],
                       {"input_dim": 4, "output_dim": 3}, wrt=[1])

# linalg
SPECS["dot"] = S(lambda: [_u(3, 4), _u(4, 2)])
SPECS["batch_dot"] = S(lambda: [_u(2, 3, 4), _u(2, 4, 2)])
SPECS["_linalg_gemm"] = S(lambda: [_u(3, 4), _u(4, 2), _u(3, 2)])
SPECS["_linalg_gemm2"] = S(lambda: [_u(3, 4), _u(4, 2)])


def _spd(n=3):
    b = R.uniform(0.5, 1.5, (n, n))
    return b @ b.T + n * np.eye(n)


SPECS["_linalg_potrf"] = S(lambda: [_spd()], rtol=5e-3, atol=1e-4)
SPECS["_linalg_potri"] = S(lambda: [np.linalg.cholesky(_spd())],
                           rtol=5e-3, atol=1e-4)
SPECS["_linalg_trmm"] = S(lambda: [np.tril(_pos(3, 3)) + np.eye(3),
                                   _u(3, 3)])
SPECS["_linalg_trsm"] = S(lambda: [np.tril(_pos(3, 3)) + 2 * np.eye(3),
                                   _u(3, 3)], rtol=5e-3, atol=1e-4)
SPECS["_linalg_sumlogdiag"] = S(lambda: [_spd()])
SPECS["_linalg_syrk"] = S(lambda: [_u(3, 4)])
SPECS["_linalg_extractdiag"] = S(lambda: [_u(3, 3)])
SPECS["_linalg_makediag"] = S(lambda: [_u(3)])
SPECS["_linalg_gelqf"] = S(lambda: [_u(2, 4) + np.eye(2, 4) * 3],
                           rtol=1e-2, atol=1e-3)
SPECS["_linalg_syevd"] = S(
    lambda: [_spd() + np.diag([0.0, 5.0, 11.0])],  # well-separated eigvals
    rtol=1e-2, atol=1e-3)
SPECS["khatri_rao"] = S(lambda: [_u(2, 3), _u(4, 3)], {"num_args": 2})

# ordering (value outputs only)
SPECS["sort"] = S(lambda: [_distinct(2, 5)], {"axis": 1})
SPECS["topk"] = S(lambda: [_distinct(2, 5)],
                  {"axis": 1, "k": 2, "ret_typ": "value"})

# NN layers
SPECS["FullyConnected"] = S(lambda: [_u(2, 5), _u(4, 5), _u(4)],
                            {"num_hidden": 4})
SPECS["Convolution"] = S(
    lambda: [_u(1, 2, 5, 5), _u(3, 2, 3, 3), _u(3)],
    {"kernel": (3, 3), "num_filter": 3}, rtol=5e-3, atol=1e-4)
SPECS["Deconvolution"] = S(
    lambda: [_u(1, 2, 4, 4), _u(2, 3, 3, 3)],
    {"kernel": (3, 3), "num_filter": 3}, rtol=5e-3, atol=1e-4)
SPECS["Pooling"] = S(lambda: [_distinct(1, 2, 4, 4)],
                     {"kernel": (2, 2), "stride": (2, 2),
                      "pool_type": "max"})
SPECS["Activation"] = S(lambda: [_u(2, 3)], {"act_type": "tanh"})
SPECS["LeakyReLU"] = S(
    lambda: [_u(2, 3, lo=0.2, hi=1.2)
             * np.where(np.arange(6).reshape(2, 3) % 2, 1, -1)],
    {"act_type": "leaky", "slope": 0.1})
SPECS["softmax"] = S(lambda: [_u(2, 4)])
SPECS["log_softmax"] = S(lambda: [_u(2, 4)])
SPECS["SoftmaxActivation"] = S(lambda: [_u(2, 4)])
# BatchNorm computes stats in f32 (by design, see ops/nn.py) — finite
# differences need a coarser step + tolerance than the f64 default
SPECS["BatchNorm"] = S(
    lambda: [_u(2, 3, 4, 4), _pos(3), _u(3), np.zeros(3), np.ones(3)],
    {"fix_gamma": False}, wrt=[0, 1, 2], training=True,
    eps=3e-3, rtol=3e-2, atol=3e-3)
# fused stem: d(data) is zero BY CONTRACT (graph input, reference grad_req
# null) — wrt covers beta+weight; the rectangle-sum dbeta is also checked
# against the unfused composition in tests/test_bn_stem.py
SPECS["_contrib_BNStemConv"] = S(
    lambda: [_u(2, 3, 6, 6), np.ones(3), _u(3), _u(4, 3, 3, 3),
             np.zeros(3), np.ones(3)],
    {"num_filter": 4, "kernel": (3, 3), "stride": (2, 2), "pad": (1, 1)},
    wrt=[2, 3], training=True, eps=3e-3, rtol=3e-2, atol=3e-3)
# fused bottleneck unit: whole-unit Pallas chain (interpret mode on CPU);
# differentiable wrt data + all 9 params, aux (moving stats) excluded;
# equivalence against the unfused composition is in tests/test_fused_unit.py
# betas biased +0.8 so no pre-ReLU activation sits within the
# finite-difference eps of its kink (the composite has 3 ReLUs; an
# unlucky draw otherwise puts ~1 element of the numeric grad across a
# kink).  wrt covers data + the three conv weights only: full-input
# central differences over the interpret-mode Pallas chain cost ~30 min,
# and per-input gradient equivalence vs the unfused composition is
# already exhaustive in tests/test_fused_unit.py.
def _fbu_inputs():
    # PRIVATE generator: the shared module rng R makes draws depend on
    # which tests ran before (the composite's ReLU kinks then flip the
    # finite differences on unlucky draws); this spec must see the same
    # verified kink-free draw in any execution order
    q = np.random.default_rng(20260731)
    u = lambda *s: q.uniform(-1.0, 1.0, s)          # noqa: E731
    pos = lambda *s: q.uniform(0.5, 1.5, s)         # noqa: E731
    return [u(2, 3, 3, 8), pos(8), u(8) + 0.8, u(2, 1, 1, 8),
            pos(2), u(2) + 0.8, u(2, 3, 3, 2),
            pos(2), u(2) + 0.8, u(8, 1, 1, 2),
            np.zeros(8), np.ones(8), np.zeros(2), np.ones(2),
            np.zeros(2), np.ones(2)]


SPECS["_contrib_FusedBottleneckUnit"] = S(
    _fbu_inputs, {"num_filter": 8, "layout": "NHWC"},
    wrt=[0, 3, 6, 9], training=True, eps=3e-3, rtol=3e-2, atol=3e-3)
SPECS["LayerNorm"] = S(lambda: [_u(2, 5), _pos(5), _u(5)])
SPECS["InstanceNorm"] = S(lambda: [_u(2, 3, 5), _pos(3), _u(3)],
                          rtol=5e-3, atol=1e-4)
SPECS["L2Normalization"] = S(lambda: [_u(2, 4, lo=0.3, hi=1.0)])
SPECS["LRN"] = S(lambda: [_u(1, 4, 3, 3)], {"nsize": 3})
SPECS["GridGenerator"] = S(lambda: [_u(1, 6)],
                           {"transform_type": "affine",
                            "target_shape": (4, 4)})
SPECS["BilinearSampler"] = S(
    lambda: [_u(1, 2, 5, 5), _u(1, 2, 4, 4, lo=-0.6, hi=0.6)],
    rtol=1e-2, atol=1e-3)
SPECS["SpatialTransformer"] = S(
    lambda: [_u(1, 2, 5, 5), _u(1, 6) * 0.1 + np.array(
        [[1, 0, 0, 0, 1, 0]], dtype=np.float64)],
    {"transform_type": "affine", "sampler_type": "bilinear",
     "target_shape": (4, 4)}, rtol=1e-2, atol=1e-3)
# CTC/fft compute in f32 internally — coarser steps/tolerances like BN
SPECS["_contrib_CTCLoss"] = S(
    lambda: [_u(4, 2, 3), np.array([[1., 2.], [2., 0.]])], wrt=[0],
    eps=3e-3, rtol=5e-2, atol=5e-3)
SPECS["_contrib_fft"] = S(lambda: [_u(2, 4)], eps=3e-3, rtol=3e-2,
                          atol=3e-3)
SPECS["_contrib_ifft"] = S(lambda: [_u(2, 8)], eps=3e-3, rtol=3e-2,
                           atol=3e-3)
SPECS["_contrib_count_sketch"] = S(
    lambda: [_u(2, 4), np.array([[0., 1., 0., 2.]]),
             np.array([[1., -1., 1., 1.]])],
    {"out_dim": 3}, wrt=[0], eps=3e-3, rtol=3e-2, atol=3e-3)
# bilinear sampling is piecewise-linear in the offsets (kinks at integer
# coordinates, like relu at 0): keep sampled positions mid-cell
SPECS["_contrib_DeformableConvolution"] = S(
    lambda: [_u(1, 2, 5, 5), _pos(1, 18, 3, 3, lo=0.25, hi=0.6),
             _u(2, 2, 3, 3)],
    {"kernel": (3, 3), "num_filter": 2, "no_bias": True},
    eps=3e-3, rtol=3e-2, atol=3e-3)
SPECS["_contrib_DeformablePSROIPooling"] = S(
    lambda: [_distinct(1, 4, 6, 6), np.array([[0, 1, 1, 4, 4]], np.float64),
             _u(1, 2, 2, 2) * 0.3],
    {"spatial_scale": 1.0, "output_dim": 1, "pooled_size": 2,
     "group_size": 2, "sample_per_part": 2, "trans_std": 0.1},
    wrt=[0, 2], eps=3e-3, rtol=3e-2, atol=3e-3)
SPECS["Correlation"] = S(
    lambda: [_u(1, 2, 5, 5), _u(1, 2, 5, 5)],
    {"kernel_size": 1, "max_displacement": 1, "pad_size": 1},
    eps=3e-3, rtol=3e-2, atol=3e-3)
SPECS["_contrib_PSROIPooling"] = S(
    lambda: [_distinct(1, 8, 4, 4),
             np.array([[0, 0, 0, 3, 3]], np.float64)],
    {"spatial_scale": 1.0, "output_dim": 2, "pooled_size": 2,
     "group_size": 2}, wrt=[0], eps=3e-3, rtol=3e-2, atol=3e-3)
SPECS["ROIPooling"] = S(
    lambda: [_distinct(1, 2, 5, 5),
             np.array([[0, 0, 0, 4, 4], [0, 1, 1, 3, 3]], np.float64)],
    {"pooled_size": (2, 2), "spatial_scale": 1.0}, wrt=[0])
SPECS["SequenceLast"] = S(lambda: [_u(4, 2, 3)], {"use_sequence_length": False})
SPECS["SequenceMask"] = S(lambda: [_u(4, 2, 3)], {"use_sequence_length": False})
SPECS["SequenceReverse"] = S(lambda: [_u(4, 2, 3)],
                             {"use_sequence_length": False})

SKIPS = {
    # intentionally non-standard gradient semantics (reference parity):
    "BlockGrad": "gradient intentionally blocked (BlockGrad contract)",
    "make_loss": "loss head: emits grad_scale regardless of cotangent",
    "MakeLoss": "loss head: emits grad_scale regardless of cotangent",
    "SoftmaxOutput": "custom head-free backward (p - onehot), tested in "
                     "test_op_gradients.py::test_loss_head_grads",
    "LinearRegressionOutput": "custom head-free backward, tested in "
                              "test_loss_head_grads",
    "LogisticRegressionOutput": "custom head-free backward, tested in "
                                "test_loss_head_grads",
    "MAERegressionOutput": "custom head-free backward (sign), kinked at 0",
    "SVMOutput": "custom head-free backward (margin hinge)",
    "softmax_cross_entropy": "loss op: VJP matches analytic p-onehot, "
                             "covered by test_loss_head_grads",
    # integer / index outputs (no gradient defined):
    "argmax": "integer output", "argmin": "integer output",
    "argsort": "integer output", "argmax_channel": "integer output",
    "one_hot": "integer input only", "shape_array": "integer output",
    "size_array": "integer output",
    # stochastic (gradient not deterministic / not defined):
    "Dropout": "stochastic mask (identity in eval mode)",
    "_shuffle": "stochastic permutation",
    "_sample_multinomial": "stochastic integer output",
    "_random_uniform": "sampler, no inputs",
    "_random_normal": "sampler, no inputs",
    "_random_gamma": "sampler, no inputs",
    "_random_exponential": "sampler, no inputs",
    "_random_poisson": "sampler, no inputs",
    "_random_negative_binomial": "sampler, no inputs",
    "_random_generalized_negative_binomial": "sampler, no inputs",
    "_random_randint": "sampler, no inputs",
    "_sample_uniform": "reparameterized sampler (dist-param grads are "
                       "distribution-dependent, not pointwise)",
    "_sample_normal": "reparameterized sampler",
    "_sample_gamma": "implicit-grad sampler",
    "_sample_exponential": "reparameterized sampler",
    "_sample_poisson": "discrete sampler",
    # no inputs:
    "_zeros": "nullary init op", "_ones": "nullary init op",
    "_full": "nullary init op", "_arange": "nullary init op",
    "_eye": "nullary init op",
    "_constant": "nullary init op (optimizer-baked literal)",
    # optimizer update rules (in-place state transitions, not differentiable
    # graph ops; validated against reference formulas in test_optimizer.py):
    "sgd_update": "optimizer state update",
    "sgd_mom_update": "optimizer state update",
    "mp_sgd_update": "optimizer state update",
    "mp_sgd_mom_update": "optimizer state update",
    "adam_update": "optimizer state update",
    "rmsprop_update": "optimizer state update",
    "rmspropalex_update": "optimizer state update",
    "ftrl_update": "optimizer state update",
    "signsgd_update": "optimizer state update",
    "signum_update": "optimizer state update",
    # recurrent: gradient flows tested end-to-end in test_gluon.py RNN
    # suites; the flat-param fused op's finite-difference sweep is O(P^2)
    "RNN": "fused RNN: covered by gluon rnn_layer equivalence tests",
    # detection ops: outputs are stop_gradient training targets /
    # post-processed detections (reference backward emits zeros)
    "_contrib_MultiBoxPrior": "anchor generation from static shapes",
    "_contrib_MultiBoxTarget": "stop-gradient target assignment",
    "_contrib_MultiBoxDetection": "stop-gradient NMS post-processing",
    # escape hatches
    "Custom": "user-defined host callback; gradient is the user's "
              "backward, canary-tested in test_custom_sparse.py",
    "IdentityAttachKLSparseReg":
        "semi-gradient by design: the reference backward treats the "
        "KL moving average as a constant "
        "(identity_attach_KL_sparse_reg-inl.h:109), so finite differences "
        "disagree on purpose; exact formula tested in "
        "test_contrib_misc.py::test_identity_attach_kl_sparse_reg",
    "_begin_state": "zero-state constructor (zero gradient by design)",
    # quantization: discrete outputs (straight-through estimators are a
    # user choice, not an op contract)
    "_contrib_Proposal": "stop-gradient RPN post-processing",
    "_contrib_MultiProposal": "stop-gradient RPN post-processing",
    "_contrib_quantize": "integer-quantized output",
    # sparse-storage format ops: gradients flow through the VALUES of the
    # sparse pytrees (covered end-to-end by
    # test_sparse_registry.py::test_sparse_symbol_graph_trains); the
    # f64 finite-difference harness feeds dense arrays only, and a dense
    # perturbation changes the sparsity PATTERN (non-differentiable
    # format boundary by construction)
    "cast_storage": "sparse-format op; dense perturbation changes the "
                    "nnz pattern — grads covered via sparse graph test",
    "_sparse_retain": "rsp-format op; covered by sparse graph test",
    "_square_sum": "rsp input op; dense-input path is sum(square()) "
                   "covered by the `sum`/`square` specs; rsp path covered "
                   "by test_sparse_registry.py",
    "_contrib_dequantize": "inverse of a discrete map (zero a.e. grad "
                           "wrt ranges; int data input)",
}


def _canonical_names():
    import mxnet_tpu
    builtin = mxnet_tpu.ops.BUILTIN_OPS
    return sorted(set(op.name for name, op in _REGISTRY.items()
                      if name in builtin))


def test_sweep_is_exhaustive():
    """Every registered op has a spec or an explicit skip (SURVEY §4)."""
    missing = [n for n in _canonical_names()
               if n not in SPECS and n not in SKIPS]
    assert not missing, "ops with no gradient spec/skip: %s" % missing
    stale = [n for n in list(SPECS) + list(SKIPS)
             if n not in _REGISTRY]
    assert not stale, "specs for unregistered ops: %s" % stale


@pytest.mark.parametrize("op_name",
                         [n for n in _canonical_names() if SPECS.get(n)])
def test_numeric_gradient(op_name):
    spec = SPECS[op_name]
    kw = {k: v for k, v in spec.items() if k not in ("inputs", "attrs")}
    check_op_gradient(op_name, spec["attrs"], spec["inputs"](), **kw)


@pytest.mark.parametrize("op_name",
                         [n for n in _canonical_names()
                          if SPECS.get(n) is None and n not in SKIPS])
def test_spec_placeholder(op_name):  # pragma: no cover
    pytest.fail("op %s has a None spec but no skip reason" % op_name)


def test_skips_are_documented():
    for name, reason in SKIPS.items():
        assert len(reason) > 8, name


def test_broken_vjp_is_caught(monkeypatch):
    """Canary: corrupt an op's gradient and assert the checker fails it."""
    import jax
    op = get_op("tanh")
    orig = op.impl

    def bad_impl(attrs, x):
        @jax.custom_vjp
        def f(x):
            return jax.numpy.tanh(x)

        def fwd(x):
            return f(x), x

        def bwd(res, g):
            return (g * 0.5,)  # wrong: should be g * (1 - tanh^2)
        f.defvjp(fwd, bwd)
        return f(x)

    monkeypatch.setattr(op, "impl", bad_impl)
    with pytest.raises(AssertionError):
        check_op_gradient("tanh", {}, [np.array([[0.3, -0.4]])])
    monkeypatch.setattr(op, "impl", orig)


def test_loss_head_grads():
    """Loss heads' custom backward vs the analytic reference formulas
    (src/operator/softmax_output-inl.h, regression_output-inl.h)."""
    import mxnet_tpu as mx
    from mxnet_tpu.test_utils import check_symbolic_backward

    x = R.uniform(-1, 1, (4, 3)).astype(np.float32)
    lab = np.array([0, 2, 1, 2], np.float32)
    e = np.exp(x - x.max(1, keepdims=True))
    p = (e / e.sum(1, keepdims=True)).astype(np.float32)
    onehot = np.eye(3, dtype=np.float32)[lab.astype(int)]

    data = mx.sym.Variable("data")
    label = mx.sym.Variable("softmax_label")
    out = mx.sym.SoftmaxOutput(data, label, name="softmax")
    check_symbolic_backward(out, {"data": x, "softmax_label": lab},
                            [np.ones((4, 3), np.float32)],
                            {"data": p - onehot}, rtol=1e-4, atol=1e-5)

    yhat = R.uniform(-1, 1, (4, 2)).astype(np.float32)
    y = R.uniform(-1, 1, (4, 2)).astype(np.float32)
    out = mx.sym.LinearRegressionOutput(
        mx.sym.Variable("data"), mx.sym.Variable("label"))
    # reference convention (regression_output-inl.h): grad_scale/num_output
    # where num_output = features per sample
    check_symbolic_backward(out, {"data": yhat, "label": y},
                            [np.ones((4, 2), np.float32)],
                            {"data": (yhat - y) / 2.0},
                            rtol=1e-4, atol=1e-5)


def test_symbol_level_numeric_gradient():
    """The executor-path checker on a small composite graph."""
    import mxnet_tpu as mx
    data = mx.sym.Variable("data")
    w = mx.sym.Variable("w")
    net = mx.sym.FullyConnected(data, weight=w, num_hidden=3, no_bias=True,
                                name="fc")
    net = mx.sym.Activation(net, act_type="tanh")
    check_numeric_gradient(
        net, {"data": R.uniform(-1, 1, (2, 4)).astype(np.float32),
              "w": R.uniform(-1, 1, (3, 4)).astype(np.float32)},
        numeric_eps=1e-3, rtol=5e-2, atol=1e-2)


def test_deconvolution_nhwc_matches_nchw():
    """layout='NHWC' deconvolution (ADVICE r2) == NCHW on the same weights."""
    import jax.numpy as jnp
    from mxnet_tpu.ops.registry import invoke_jax
    rng = np.random.default_rng(0)
    x = rng.standard_normal((2, 3, 5, 5)).astype(np.float32)
    w = rng.standard_normal((3, 4, 3, 3)).astype(np.float32)  # (C, F, k, k)
    ref = np.asarray(invoke_jax(
        "Deconvolution", {"kernel": (3, 3), "num_filter": 4},
        jnp.asarray(x), jnp.asarray(w))[0])
    x_cl = np.transpose(x, (0, 2, 3, 1))
    w_cl = np.transpose(w, (0, 2, 3, 1))  # (C, k, k, F)
    out = np.asarray(invoke_jax(
        "Deconvolution", {"kernel": (3, 3), "num_filter": 4,
                          "layout": "NHWC"},
        jnp.asarray(x_cl), jnp.asarray(w_cl))[0])
    np.testing.assert_allclose(np.transpose(out, (0, 3, 1, 2)), ref,
                               rtol=1e-4, atol=1e-5)

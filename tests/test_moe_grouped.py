"""The decode step's grouped expert kernel (``ops/transformer.py``
``moe_grouped``) in Pallas interpret mode against the plain products on
the CPU, and the rule that chooses it.

Shapes are the two expert cells' steps as they route: LFM2's 256 rows,
4 of 32 experts by sigmoid scores under a bias, SwiGLU; SmallThinker's
32 rows, 6 of 64 by a softmax over the chosen logits, ReGLU.  Hidden
size and expert width are cut to whole 128-lane tiles (128 and 256, two
width tiles of 128) so that the interpreter runs them in seconds.

Tolerances.  Kernel and plain path weight the gated activation in
float32 before it is rounded for the down product, and sum a row's
experts in float32: the plain path inside the down product's
accumulator, the kernel in an add after it.  So they differ by float32
rounding, and in bfloat16 at most by the one rounding of the result: a
bfloat16 ulp of the largest output (float32: ``TOL`` of it).
"""
import numpy as np
import pytest

import jax.numpy as jnp

from mxnet_tpu.ops import invoke_jax
from mxnet_tpu.ops import transformer as tf
from mxnet_tpu.ops.registry import get_op

TOL = 2e-5
HIDDEN, WIDTH = 128, 256
STEPS = {
    "lfm2": dict(rows=256, top_k=4, experts=32, routing="sigmoid",
                 activation="silu"),
    "smallthinker": dict(rows=32, top_k=6, experts=64, routing="softmax",
                         activation="relu"),
}


def _layer(step, dtype, routing="spread", first=0, held=0, seed=0):
    """Inputs and attributes of one expert layer at ``step``'s shape.
    ``skewed`` routing: every row's first choice is expert 5, the rest
    fall among the first ``top_k`` experts, and every other expert gets
    no row."""
    s = STEPS[step]
    rng = np.random.default_rng(seed)
    n_exp, k = s["experts"], s["top_k"]
    r = rng.standard_normal((s["rows"], n_exp)).astype(np.float32)
    if routing == "skewed":
        r[:, :k] += 20.0
        r[:, 5] += 50.0
    n_w = held or n_exp - first
    x = jnp.asarray(rng.standard_normal((s["rows"], HIDDEN)), dtype)
    w = [jnp.asarray(rng.standard_normal((n_w, WIDTH, HIDDEN))
                     / np.sqrt(HIDDEN), dtype) for _ in range(3)]
    attrs = {"top_k": k, "routing": s["routing"],
             "activation": s["activation"], "first_expert": first,
             "num_held": held}
    ins = [x, jnp.asarray(r)] + w
    if s["routing"] == "sigmoid":
        attrs["expert_bias"] = True
        ins.append(jnp.asarray(0.1 * rng.standard_normal(n_exp),
                               jnp.float32))
    return attrs, ins


def _kernel(attrs, ins):
    a = get_op("_moe_experts").normalize(attrs)
    bias = ins[5] if len(ins) > 5 else None
    top_i, w = tf._moe_route(a, ins[1].astype(jnp.float32), bias)
    y = tf.moe_grouped(ins[0], top_i, w, *ins[2:5],
                       first=a["first_expert"], activation=a["activation"],
                       width_tile=128, interpret=True)
    return y.astype(ins[0].dtype)


def _agree(got, want, dtype):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    top = np.abs(want).max()
    if dtype == "bfloat16":
        limit = 2.0 ** (np.floor(np.log2(top)) - 7)     # an ulp of top
    else:
        limit = TOL * top
    assert np.abs(got - want).max() <= limit


@pytest.mark.parametrize("routing", ["spread", "skewed"])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("step", sorted(STEPS))
def test_kernel_matches_the_plain_products(step, dtype, routing):
    attrs, ins = _layer(step, dtype, routing)
    want, _route = invoke_jax("_moe_experts", attrs, *ins)
    _agree(_kernel(attrs, ins), want, dtype)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("step", sorted(STEPS))
def test_kernel_over_a_held_share_of_the_experts(step, dtype):
    """A quarter of the experts held: the others' pairs are no part of
    the share, and in float32 the shares add up to the layer."""
    n_exp = STEPS[step]["experts"]
    q = n_exp // 4
    attrs, ins = _layer(step, dtype)
    whole, _ = invoke_jax("_moe_experts", attrs, *ins)
    total = 0.0
    for first in range(0, n_exp, q):
        part = dict(attrs, first_expert=first, num_held=q)
        share = ins[:2] + [a[first:first + q] for a in ins[2:5]] + ins[5:]
        want, _ = invoke_jax("_moe_experts", part, *share)
        got = _kernel(part, share)
        _agree(got, want, dtype)
        total = total + np.asarray(got, np.float32)
    if dtype == "float32":
        _agree(total, whole, dtype)


def _shapes(step, rows=None, hidden=2048, width=1792):
    s = STEPS[step]
    rows = rows or s["rows"]
    return [(rows, hidden), (rows, s["experts"]),
            (s["experts"], width, hidden), (s["experts"], width, hidden),
            (s["experts"], width, hidden)]


BF16 = [jnp.bfloat16, jnp.float32, jnp.bfloat16, jnp.bfloat16,
        jnp.bfloat16]


@pytest.mark.parametrize("step", sorted(STEPS))
def test_the_kernel_is_chosen_for_an_inference_step_on_a_tpu(step,
                                                             monkeypatch):
    """The rule, on what the op observes: a step's rows in an inference
    trace take the kernel where the program is lowered for a TPU;
    training, the CPU, a prefill's rows (the sorted path), mixed
    precisions and widths off the lane tile take the XLA paths."""
    attrs = get_op("_moe_experts").normalize(
        {k: v for k, v in STEPS[step].items()
         if k in ("top_k", "routing", "activation")})
    assert tf.moe_groupable(attrs, _shapes(step), BF16)
    assert not tf.moe_groupable(attrs, _shapes(step), BF16, training=True)
    assert not tf.moe_groupable(attrs, _shapes(step, rows=8192), BF16)
    assert not tf._moe_dense(attrs, 8192, STEPS[step]["experts"])
    assert not tf.moe_groupable(attrs, _shapes(step),
                                [jnp.bfloat16, jnp.float32, jnp.float32,
                                 jnp.bfloat16, jnp.bfloat16])
    assert not tf.moe_groupable(attrs, _shapes(step, hidden=2000), BF16)
    assert not tf.moe_takes_kernel(attrs, _shapes(step), BF16)    # the CPU
    monkeypatch.setattr(tf, "_lowers_for_tpu", lambda: True)
    assert tf.moe_takes_kernel(attrs, _shapes(step), BF16)


def test_the_op_on_the_cpu_is_the_plain_path_bit_for_bit():
    """At a shape the kernel takes on a TPU, the op lowered for the CPU
    is the plain products: the same bits as a training trace, which
    never takes the kernel."""
    attrs, ins = _layer("lfm2", "bfloat16")
    op = get_op("_moe_experts")
    a = op.normalize(attrs)
    shapes = [x.shape for x in ins[:5]]
    assert tf.moe_groupable(a, shapes, [x.dtype for x in ins[:5]])
    got = op.bound(a)(*ins)[0]
    want = op.bound(a, training=True)(*ins)[0]
    assert np.array_equal(np.asarray(got, np.float32),
                          np.asarray(want, np.float32))


def test_expert_products_count_the_kernels_padded_tiles(monkeypatch):
    """On the kernel each held expert's pairs are padded to whole tiles:
    at most ``pairs + held * (tile - 1)`` rows.  LFM2's step: 1,024
    pairs in tiles of 32 over 32 experts, 2,016 rows, 1.97 of the routed
    (the plain path's 8,192, 8 of them); SmallThinker's: 192 pairs in
    tiles of 16 over 64 experts, 1,152.  The declared FLOPs follow the
    same rows, and the step's temporaries are the rows in float32 and
    the pairs' indices, where the plain path declares its float32 gate
    and up."""
    lfm2 = get_op("_moe_experts").normalize(
        {"top_k": 4, "routing": "sigmoid", "activation": "silu"})
    small = get_op("_moe_experts").normalize({"top_k": 6})
    plain = tf.moe_products(lfm2, _shapes("lfm2"), BF16)
    plain_temp = tf._moe_temp(lfm2, _shapes("lfm2"), BF16)
    assert plain == 256 * 32
    monkeypatch.setattr(tf, "_lowers_for_tpu", lambda: True)
    assert tf.moe_products(lfm2, _shapes("lfm2"), BF16) == 2016 \
        == 1024 + 32 * 31
    assert tf.moe_products(
        small, _shapes("smallthinker", hidden=2560, width=768),
        BF16) == 1152 == 192 + 64 * 15
    assert tf._moe_flops(lfm2, _shapes("lfm2"), None) \
        == 6.0 * 2016 * 1792 * 2048
    temp = tf._moe_temp(lfm2, _shapes("lfm2"), BF16)
    assert temp == 256 * 2048 * 4 + 1024 * 16
    assert plain_temp == 256 * 32 * 1792 * 14 > 50 * temp
    # without dtypes the rule cannot tell the storage, and a prefill's
    # rows take the sorted path's count
    assert tf.moe_products(lfm2, _shapes("lfm2")) == 2016
    assert tf.moe_products(lfm2, _shapes("lfm2", rows=512), BF16) \
        == tf._moe_padded_rows(lfm2, 512, 32)

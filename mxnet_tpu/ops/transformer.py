"""Decoder-block ops: RMSNorm, rotary embedding, a float32-accumulating
linear map, grouped-query attention against a per-layer cache state
(one query row a slot) and over a whole prompt (causal, banded: one
fused Pallas kernel where a TPU can tile it, blockwise XLA elsewhere),
a short convolution, gated or plain, against a state of a few rows a
slot (one step, or a whole padded prompt), Mamba-2's selective state
space against a state of heads x head size x state size a slot (one
recurrence a step, or a chunked scan over a whole padded prompt), and a
sparse expert layer that is told which experts it holds and by which
rule its router's scores choose (in a decode step on a TPU, one grouped
Pallas kernel).

Every op declares what the analysis passes need of it where it is
written (``row_local``, ``flops``, ``temp_bytes``: ROADMAP D13); the
shape rule is the implementation under ``jax.eval_shape`` plus
``fill_shapes`` for the parameters.

Precision, whatever the storage dtype: a norm's statistics, the rotation,
attention scores and softmaxes, the router's scores, choice and weights,
the short convolution's multiply-adds, the state space's recurrence and
every product's accumulator are float32; results are rounded once to
the dtype of the activations (or the state) they join.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .registry import register, P

_MASKED = -1e30        # finite: a fully masked row softmaxes to uniform
_ACTIVATIONS = {"relu": jax.nn.relu, "silu": jax.nn.silu}


def _acc(*arrays):
    """The dtype statistics and accumulators are kept in: float32, or
    wider where the operands are (the gradient sweep runs in float64)."""
    return jnp.result_type(jnp.float32, *[a.dtype for a in arrays])


def _prod(shape):
    out = 1
    for d in shape:
        out *= int(d)
    return out


def _itemsize(dt):
    return jnp.dtype(dt).itemsize


# ---------------------------------------------------------------- RMSNorm
def _gamma_fill(attrs, in_shapes):
    out = list(in_shapes)
    if out[0] is not None and len(out) > 1 and out[1] is None:
        out[1] = (attrs["head_dim"] or out[0][-1],)
    return out


@register("RMSNorm", nin=2, input_names=["data", "gamma"],
          params={"eps": P(float, 1e-6), "head_dim": P(int, 0)},
          fill_shapes=_gamma_fill, row_local="leading",
          flops=lambda a, i, o: 4.0 * _prod(o))
def rms_norm(attrs, data, gamma):
    """``gamma * data / sqrt(mean(data**2, -1) + eps)``; the mean and
    the division in float32, rounded to ``data``'s dtype before the
    gain is applied.  With ``head_dim`` the last axis is heads (or
    groups) of that many values laid side by side, each normed over its
    own values, under the one gain of ``head_dim`` (a query/key norm)
    or, where ``gamma`` is as wide as ``data``, a gain a value (a
    grouped norm)."""
    d = attrs["head_dim"]
    x = data.astype(_acc(data))
    g = gamma.astype(data.dtype)
    if d:
        x = x.reshape(data.shape[:-1] + (-1, d))
        if g.shape[-1] != d:
            g = g.reshape(-1, d)
    inv = lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + attrs["eps"])
    out = (x * inv).astype(data.dtype) * g
    return out.reshape(data.shape)


# ----------------------------------------------------------------- linear
def _dense_fill(attrs, in_shapes):
    out = list(in_shapes)
    if out[0] is not None and len(out) > 1 and out[1] is None:
        out[1] = (attrs["num_hidden"], out[0][-1])
    return out


@register("_dense", nin=2, input_names=["data", "weight"],
          params={"num_hidden": P(int), "out_dtype": P(str, "float32")},
          fill_shapes=_dense_fill, row_local="leading",
          flops=lambda a, i, o: 2.0 * _prod(o) * i[0][-1])
def dense(attrs, data, weight):
    """``data @ weight.T`` over the last axis, accumulated and returned
    in ``out_dtype``: ``FullyConnected`` returns its input's dtype, and a
    router's logits and a head's logits are wanted in float32 from
    bfloat16 operands without a float32 copy of the weight."""
    return lax.dot_general(
        data, weight, (((data.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.promote_types(attrs["out_dtype"],
                                                 _acc(data)))


# ----------------------------------------------------------------- rotary
@register("_rotary", nin=2, input_names=["data", "pos"],
          params={"head_dim": P(int), "theta": P(float, 10000.0)},
          row_local="leading",
          flops=lambda a, i, o: 6.0 * _prod(o))
def rotary(attrs, data, pos):
    """Rotary position embedding by position, half-rotation layout:
    ``data`` is ``(..., heads * head_dim)``, ``pos`` broadcasts against
    its leading axes.  Within a head, element ``i`` of the first half
    pairs with element ``i`` of the second and the pair turns by
    ``pos * theta ** (-2 i / head_dim)``."""
    d = attrs["head_dim"]
    half = d // 2
    acc = _acc(data)
    inv = jnp.asarray(attrs["theta"], acc) ** (
        -jnp.arange(half, dtype=acc) * 2.0 / d)
    ang = pos.astype(acc)[..., None] * inv
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x = data.astype(acc).reshape(data.shape[:-1] + (-1, d))
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.reshape(data.shape).astype(data.dtype)


# ---------------------------------------------------- attention, one step
def _decode_attn_flops(attrs, ins, out):
    n, rows, c = ins[1]
    return 4.0 * n * attrs["num_heads"] * rows * c


def _decode_attn_temp(attrs, ins, dts):
    n, rows, _c = ins[1]
    return 2 * 4 * n * attrs["num_heads"] * rows


@register("_gqa_decode", nin=4,
          input_names=["query", "k_cache", "v_cache", "pos"],
          params={"num_heads": P(int), "num_kv_heads": P(int),
                  "window": P(int, 0)},
          row_local="axis0", flops=_decode_attn_flops,
          temp_bytes=_decode_attn_temp)
def gqa_decode(attrs, q, k_cache, v_cache, pos):
    """One query row a slot against that slot's cache state.

    ``q`` is ``(slots, heads * d)``; the caches are ``(slots, rows,
    kv_heads * d)`` and already hold the row of position ``pos``.  Row
    ``j`` holds the latest position ``p <= pos`` with ``p mod rows ==
    j``: a cache of ``max_len`` rows holds position ``j`` there, a ring
    of ``window`` rows written at ``pos mod window`` holds the last
    ``window`` positions.  A row is read when that position exists
    (``p >= 0``) and, under a window, ``pos - p < window``.

    The cache keeps its ``(rows, kv_heads * d)`` layout: the queries of
    a group are laid block-diagonally over the ``kv_heads * d`` columns,
    so scores and output are two plain batched products over the whole
    row with no relayout of the cache (four times the score FLOPs of a
    per-group product; the step is bound by the cache read)."""
    h, kv, w = attrs["num_heads"], attrs["num_kv_heads"], attrs["window"]
    n, rows, c = k_cache.shape
    d, g = c // kv, h // kv
    acc = _acc(q)
    eye = jnp.eye(kv, dtype=q.dtype)
    qbd = (q.reshape(n, kv, g, 1, d) * eye[None, :, None, :, None]) \
        .reshape(n, h, c)
    s = jnp.einsum("nhc,nrc->nhr", qbd, k_cache,
                   preferred_element_type=acc) * (d ** -0.5)
    p = pos.astype(jnp.int32)[:, None]
    held = p - jnp.mod(p - jnp.arange(rows, dtype=jnp.int32)[None, :], rows)
    ok = held >= 0
    if w > 0:
        ok = jnp.logical_and(ok, p - held < w)
    a = jax.nn.softmax(jnp.where(ok[:, None, :], s, _MASKED), axis=-1)
    o = jnp.einsum("nhr,nrc->nhc", a.astype(v_cache.dtype), v_cache,
                   preferred_element_type=acc)
    o = (o.reshape(n, kv, g, kv, d)
         * eye.astype(acc)[None, :, None, :, None]).sum(3)
    return o.reshape(n, h * d).astype(q.dtype)


# ------------------------------------------------ attention, whole prompt
def _prefill_blocks(t, block, window):
    """(query start, query end, first key) of each query block: keys
    run to the block's end (causal) and, under a window, start at the
    first position its first query still sees."""
    out = []
    for qs in range(0, t, block):
        ks = 0 if window <= 0 else max(0, qs - window + 1)
        out.append((qs, min(qs + block, t), ks))
    return out


def _prefill_attn_flops(attrs, ins, out):
    b, t, hd = ins[0]
    blocks = _prefill_blocks(t, min(attrs["block"], t), attrs["window"])
    return sum(4.0 * b * hd * (qe - qs) * (qe - ks)
               for qs, qe, ks in blocks)


def _blockwise_temp(attrs, ins):
    b, t, _hd = ins[0]
    blocks = _prefill_blocks(t, min(attrs["block"], t), attrs["window"])
    return max(2 * 4 * b * attrs["num_heads"] * (qe - qs) * (qe - ks)
               for qs, qe, ks in blocks)


def _prefill_attn_temp(attrs, ins, dts):
    """Of the path this process's programs take: the fused kernel keeps
    scores, maxima and sums in VMEM and has no temporary in HBM; the
    blockwise path holds a block's float32 scores and their
    exponentials."""
    if prefill_takes_kernel(attrs, ins, dts):
        return 0
    return _blockwise_temp(attrs, ins)


# rows of keys a tile, the largest that divides T; of queries, the same
# from 512 down.  On a v5e at T 8,192 (a batch of two, ms global /
# window; the blockwise path 35.3 / 27.6): 512 x 1,024 8.49 / 7.13,
# 1,024 x 1,024 8.15 / 6.81 at twice the compile time, 512 x 512
# 13.7 / 10.9, 256 x 1,024 8.94 / 7.36, 512 x 2,048 8.60 / 7.66
_FUSED_BLOCKS = (1024, 512, 256, 128)
_FUSED_MIN_T = 1024


def _lowers_for_tpu():
    """Whether this process's programs are lowered for a TPU: what the
    declared rules and the engine's counter go by (the op itself lets
    ``lax.platform_dependent`` decide at lowering, so a program compiled
    for a described chip from a CPU host takes the kernel too)."""
    return jax.default_backend() == "tpu"


def prefill_fusable(attrs, shapes, dtypes, training=False):
    """Whether ``_gqa_prefill`` over inputs of these shapes and dtypes
    (query, key, value) is the fused kernel's where the program is
    lowered for a TPU: an inference trace (``pallas_call`` has no
    differentiation rule), all three bfloat16 or all float32, a head
    dimension of whole 128-lane tiles, and ``T`` a whole number of
    kernel blocks and at least ``_FUSED_MIN_T`` (below that the
    blockwise scores are a few tens of megabytes and nothing is bound
    by them)."""
    h, kv = attrs["num_heads"], attrs["num_kv_heads"]
    q_shape, k_shape = shapes[0], shapes[1]
    if training or len(q_shape) != 3 or kv <= 0 or h % kv \
            or k_shape[-1] % kv:
        return False
    d, t = k_shape[-1] // kv, q_shape[1]
    kinds = {jnp.dtype(x) for x in dtypes}
    return (d % 128 == 0 and q_shape[-1] == h * d
            and t >= _FUSED_MIN_T and t % _FUSED_BLOCKS[-1] == 0
            and len(kinds) == 1
            and kinds <= {jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)})


def prefill_takes_kernel(attrs, shapes, dtypes):
    """Whether an inference program this process builds runs
    ``_gqa_prefill`` over these inputs as the fused kernel: what the
    declared ``temp_bytes`` and the engine's ``fused_attention`` counter
    go by."""
    return _lowers_for_tpu() and prefill_fusable(attrs, shapes, dtypes)


@functools.partial(jax.jit, static_argnames=(
    "num_heads", "num_kv_heads", "window", "block_q", "block_k",
    "interpret"))
def gqa_prefill_fused(q, k, v, num_heads, num_kv_heads, window=0,
                      block_q=None, block_k=None, interpret=False):
    """``_gqa_prefill`` as one Pallas TPU kernel: flash attention over
    the ``(batch, T, heads * d)`` layout as it stands, no relayout.

    Grid ``(batch, kv head, query block, key block)``, the key blocks
    innermost.  A grid step holds one ``(block_q, group * d)`` tile of
    the queries of a key/value head's whole group and one ``(block_k,
    d)`` tile each of its keys and values, so a key tile is read from
    HBM once a group, not once a query head.  For each of the group's
    heads: scores ``q k^T`` accumulated in float32 on the MXU, scaled,
    masked to the causal band, folded into the running row maximum and
    row sum (float32, VMEM scratch), the exponentials rounded to the
    values' dtype for the second product, whose float32 result joins
    the rescaled accumulator (VMEM scratch).  After a query block's
    last key block the accumulator is divided by the row sums and
    rounded once.  Only the key blocks a query block can see are
    visited: the key axis of the grid counts from the block's first
    visible key block (``first``), steps past its last repeat that
    block's index, so nothing is fetched, and compute nothing.

    The same mathematics as the blockwise path at the same precision;
    the online softmax reorders the sums and nothing else."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    h, kv, w = num_heads, num_kv_heads, int(window)
    b, t, _ = q.shape
    d, g = k.shape[-1] // kv, h // kv
    bq = block_q or next(x for x in _FUSED_BLOCKS[1:] if t % x == 0)
    bk = block_k or next(x for x in _FUSED_BLOCKS if t % x == 0)
    if t % bq or t % bk or d % 128:
        raise ValueError("the fused prefill attention tiles T=%d by "
                         "(%d, %d) and heads of whole 128 lanes, got "
                         "d=%d" % (t, bq, bk, d))
    acc = jnp.float32
    scale = d ** -0.5
    lanes = 128

    # the first and the last key block that query block i sees (of a
    # traced i in the kernel and the index maps, of a number here)
    def first(i, most=jnp.maximum):
        return most(i * bq - (w - 1), 0) // bk if w > 0 else 0

    def last(i):
        return (i * bq + bq - 1) // bk
    nk = max(last(i) - first(i, max) + 1 for i in range(t // bq))

    def kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref):
        i, jj = pl.program_id(2), pl.program_id(3)
        j = first(i) + jj

        @pl.when(jj == 0)
        def _init():
            m_ref[...] = jnp.full(m_ref.shape, _MASKED, acc)
            l_ref[...] = jnp.zeros(l_ref.shape, acc)
            acc_ref[...] = jnp.zeros(acc_ref.shape, acc)

        @pl.when(j <= last(i))
        def _block():
            kb, vb = k_ref[...], v_ref[...]
            # query position less key position: one mask for the group
            # (masking only the blocks the band's edges cross read 2 %
            # faster on the chip and compiled twice as long)
            ahead = (i * bq - j * bk
                     + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
                     - lax.broadcasted_iota(jnp.int32, (bq, bk), 1))
            ok = ahead >= 0
            if w > 0:
                ok = jnp.logical_and(ok, ahead < w)
            for n in range(g):
                cols = slice(n * d, (n + 1) * d)
                s = lax.dot_general(
                    q_ref[:, cols], kb, (((1,), (1,)), ((), ())),
                    preferred_element_type=acc) * scale
                s = jnp.where(ok, s, _MASKED)
                m_prev = m_ref[n]
                m_next = jnp.maximum(
                    m_prev, jnp.max(s, axis=1, keepdims=True))
                # a row whose keys here are all masked weighs them 1
                # against a maximum of _MASKED; its first visible key
                # rescales that by exp(_MASKED - score) = 0
                p = jnp.exp(s - m_next[:, :1])
                alpha = jnp.exp(m_prev - m_next)
                l_ref[n] = alpha * l_ref[n] \
                    + jnp.sum(p, axis=1, keepdims=True)
                m_ref[n] = m_next
                pv = lax.dot_general(
                    p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
                    preferred_element_type=acc)
                acc_ref[:, cols] = acc_ref[:, cols] * alpha[:, :1] + pv

        @pl.when(jj == nk - 1)
        def _done():
            for n in range(g):
                cols = slice(n * d, (n + 1) * d)
                o_ref[:, cols] = (acc_ref[:, cols]
                                  / l_ref[n][:, :1]).astype(o_ref.dtype)

    def kv_block(bi, ki, i, jj):
        return (bi, jnp.minimum(first(i) + jj, last(i)), ki)
    flops = int(_prefill_attn_flops({"block": bq, "window": w},
                                    [q.shape], None))
    return pl.pallas_call(
        kernel,
        grid=(b, kv, t // bq, nk),
        in_specs=[
            pl.BlockSpec((None, bq, g * d),
                         lambda bi, ki, i, jj: (bi, i, ki)),
            pl.BlockSpec((None, bk, d), kv_block),
            pl.BlockSpec((None, bk, d), kv_block)],
        out_specs=pl.BlockSpec((None, bq, g * d),
                               lambda bi, ki, i, jj: (bi, i, ki)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((g, bq, lanes), acc),
                        pltpu.VMEM((g, bq, lanes), acc),
                        pltpu.VMEM((bq, g * d), acc)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        cost_estimate=pl.CostEstimate(
            flops=flops, transcendentals=flops // (4 * d),
            bytes_accessed=2 * (q.nbytes + k.nbytes)),
        name="gqa_prefill_fused",
        interpret=bool(interpret),
    )(q, k, v)


@functools.partial(jax.jit, static_argnames=(
    "num_heads", "num_kv_heads", "window", "block"))
def gqa_prefill_blockwise(q, k, v, num_heads, num_kv_heads, window=0,
                          block=512):
    """A block of queries at a time against the keys that block can
    see, in XLA: the largest score tensor is ``block x T`` a head and
    no ``T x T`` one exists; a block wholly outside the band is never
    multiplied.  Differentiable, any shape, any backend.  (Under
    ``jit`` like the kernel, so that a graph of like layers traces
    either once a shape and not once a layer: 16 blocks of 8,192
    positions take a third of a second to trace.)"""
    h, kv, w = num_heads, num_kv_heads, window
    b, t, _ = q.shape
    d, g = k.shape[-1] // kv, h // kv
    acc = _acc(q)
    outs = []
    for qs, qe, ks in _prefill_blocks(t, min(block, t), w):
        qb = q[:, qs:qe].reshape(b, qe - qs, kv, g, d)
        kb = k[:, ks:qe].reshape(b, qe - ks, kv, d)
        vb = v[:, ks:qe].reshape(b, qe - ks, kv, d)
        s = jnp.einsum("bqkgd,blkd->bkgql", qb, kb,
                       preferred_element_type=acc) * (d ** -0.5)
        qi = jnp.arange(qs, qe, dtype=jnp.int32)[:, None]
        kj = jnp.arange(ks, qe, dtype=jnp.int32)[None, :]
        ok = kj <= qi
        if w > 0:
            ok = jnp.logical_and(ok, qi - kj < w)
        # the weights leave here unnormalised and the row sums divide
        # the product: one pass over the block's scores fewer than a
        # softmax that is normalised before it is multiplied
        s = jnp.where(ok, s, _MASKED)
        a = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        o = jnp.einsum("bkgql,blkd->bqkgd", a.astype(v.dtype), vb,
                       preferred_element_type=acc)
        o = o / jnp.moveaxis(jnp.sum(a, axis=-1), 3, 1)[..., None]
        outs.append(o.reshape(b, qe - qs, h * d).astype(q.dtype))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)


@register("_gqa_prefill", nin=3, input_names=["query", "key", "value"],
          params={"num_heads": P(int), "num_kv_heads": P(int),
                  "window": P(int, 0), "block": P(int, 512)},
          mode_dependent=True, row_local="axis0",
          flops=_prefill_attn_flops, temp_bytes=_prefill_attn_temp)
def gqa_prefill(attrs, q, k, v):
    """Causal grouped-query attention over ``(batch, T, heads * d)``
    queries and ``(batch, T, kv_heads * d)`` keys and values, position
    ``i`` seeing ``j <= i`` and, under a window, ``i - j < window``.

    One contract, two formulations, chosen by what the op observes
    (``prefill_fusable`` and the platform the program is lowered for).
    An inference trace lowered for a TPU with heads of whole 128-lane
    tiles and ``T`` at least 1,024 in whole kernel blocks takes
    ``gqa_prefill_fused``: scores and softmax statistics never leave
    VMEM.  Everything else (training traces, the CPU, small heads,
    short or ragged ``T``) takes ``gqa_prefill_blockwise``: ``block``
    queries at a time in XLA, whose float32 scores cross HBM three
    times.  On a v5e, 8,192 positions of 28 heads of 128 over 4
    key/value heads in bfloat16, a batch row: fused 4.2 ms with no
    window (61 % of the MXU's pace for the band's FLOPs) and 3.5 ms
    under a window of 4,096 (59 %), blockwise 17.0 and 13.2 ms (15 %);
    the two agree to a bfloat16 ulp and lie equally far from float32
    operands at the highest precision."""
    heads = {n: attrs[n] for n in ("num_heads", "num_kv_heads", "window")}
    blockwise = functools.partial(gqa_prefill_blockwise,
                                  block=attrs["block"], **heads)
    if not prefill_fusable(attrs, [x.shape for x in (q, k, v)],
                           [x.dtype for x in (q, k, v)],
                           attrs.get("_training", False)):
        return blockwise(q, k, v)
    return lax.platform_dependent(
        q, k, v, tpu=functools.partial(gqa_prefill_fused, **heads),
        default=blockwise)


# ------------------------------------------------------- gated activation
@register("_gated_act", nin=2, input_names=["gate", "up"],
          params={"activation": P(str, "silu", choices=list(_ACTIVATIONS))},
          row_local="leading",
          flops=lambda a, i, o: 6.0 * _prod(o))
def gated_act(attrs, gate, up):
    """``act(gate) * up`` (a gated linear unit's middle: ``silu`` makes
    it SwiGLU, ``relu`` ReGLU), computed in float32 and rounded once."""
    acc = _acc(gate)
    return (_ACTIVATIONS[attrs["activation"]](gate.astype(acc))
            * up.astype(acc)).astype(gate.dtype)


# ------------------------------------------------------ short convolution
_CONV_PARAMS = {"taps": P(int, 3), "gated": P(bool, True),
                "activation": P(str, "none",
                                choices=["none"] + list(_ACTIVATIONS)),
                "has_bias": P(bool, False)}


def _conv_nin(attrs):
    return 4 if (attrs or {}).get("has_bias") else 3


def _conv_width(attrs, proj_width):
    """Channels of the convolution: a third of a gated projection
    ``[B, C, x]``, the whole of a plain one."""
    return proj_width // 3 if attrs["gated"] else proj_width


def _conv_in(attrs, proj, dtype):
    """``(u, C)``: the convolution's input rounded to ``dtype`` and the
    gate applied after it (``u = B * x`` and ``C`` of a gated
    projection; a plain one is ``u`` itself and has no gate)."""
    if not attrs["gated"]:
        return proj.astype(dtype), None
    d = proj.shape[-1] // 3
    b, c, x = proj[..., :d], proj[..., d:2 * d], proj[..., 2 * d:]
    acc = _acc(proj)
    return (b.astype(acc) * x.astype(acc)).astype(dtype), c


def _conv_out(attrs, y, c, bias, dtype):
    """The float32 sum over the taps, plus the bias, through the
    activation, times the gate, rounded once to ``dtype``."""
    if bias is not None:
        y = y + bias.astype(y.dtype)
    if attrs["activation"] != "none":
        y = _ACTIVATIONS[attrs["activation"]](y)
    if c is not None:
        y = c.astype(y.dtype) * y
    return y.astype(dtype)


def _conv_flops(attrs, ins, out):
    # a multiply-add a tap; B * x and C * y where gated; the bias; silu
    per = 2.0 * ins[2][0] + 2.0 * attrs["gated"] + attrs["has_bias"] \
        + 5.0 * (attrs["activation"] != "none")
    return per * _prod(ins[0]) / (3.0 if attrs["gated"] else 1.0)


def _conv_fill(attrs, in_shapes):
    out = list(in_shapes)
    if out[0] is not None:
        d = _conv_width(attrs, out[0][-1])
        if out[2] is None:
            out[2] = (attrs["taps"], d)
        if len(out) > 3 and out[3] is None:
            out[3] = (d,)
    return out


@register("_short_conv_step", nin=_conv_nin, nout=2,
          input_names=["data", "state", "weight", "bias"],
          params=_CONV_PARAMS, fill_shapes=_conv_fill,
          row_local="axis0", flops=_conv_flops)
def short_conv_step(attrs, proj, state, weight, bias=None):
    """One position a slot of a short causal depthwise convolution.

    ``proj`` ``(slots, 3 * d)`` is a gated input projection's ``[B, C,
    x]`` (``gated``, the default) or ``(slots, d)`` the convolution's
    own input; ``state`` ``(slots, taps - 1, d)`` holds its input ``u``
    (``B * x``, or ``proj`` itself) of the ``taps - 1`` positions before
    this one, oldest first (zeros before the sequence's start);
    ``weight`` ``(taps, d)`` is a tap a row, the last on the current
    position, and ``bias`` (with ``has_bias``) ``(d,)``.  Returns
    ``act(y + bias)``, times ``C`` where gated, with ``y = sum_k
    weight[k] * u_{t - (taps - 1) + k}`` per channel, and the next
    state: the old one shifted by a row with ``u_t`` behind it.

    ``u`` is computed in float32 and rounded once to the state's dtype,
    the value every later position reads of it; the multiply-adds, the
    bias, the activation and the gate are float32, the result rounded
    once."""
    u, c = _conv_in(attrs, proj, state.dtype)
    acc = _acc(proj)
    held = jnp.concatenate([state, u[:, None]], axis=1)
    w = weight.astype(acc)
    y = sum(held[:, k].astype(acc) * w[k] for k in range(w.shape[0]))
    return _conv_out(attrs, y, c, bias, proj.dtype), held[:, 1:]


def _conv_seq_temp(attrs, ins, dts):
    # u padded at the front, and the float32 sum over the taps
    b, t, width = ins[0]
    return b * (t + attrs["taps"]) * _conv_width(attrs, width) \
        * (_itemsize(dts[0]) + 4)


@register("_short_conv_seq", nin=_conv_nin, nout=2,
          input_names=["data", "length", "weight", "bias"],
          params=_CONV_PARAMS, fill_shapes=_conv_fill,
          row_local="axis0", flops=_conv_flops, temp_bytes=_conv_seq_temp)
def short_conv_seq(attrs, proj, length, weight, bias=None):
    """A whole padded prompt of a short convolution, in XLA: one
    shifted multiply-add a tap (``taps`` passes over ``u``; nothing here
    is bound by them).

    ``proj`` ``(batch, T, width)``, ``length`` ``(batch,)`` live
    positions a row, ``weight``, ``bias`` and the attributes as
    ``_short_conv_step``.  Returns the output at every position (causal,
    so padding behind a row's length touches no live position) and the
    state *at each row's own length*: ``u`` of positions ``length -
    (taps - 1) .. length - 1``, zeros where that is before the start,
    whatever the padding holds.  The same roundings as the step."""
    taps = weight.shape[0]
    u, c = _conv_in(attrs, proj, proj.dtype)
    acc = _acc(proj)
    n, t, d = u.shape
    front = jnp.concatenate(
        [jnp.zeros((n, taps - 1, d), u.dtype), u], axis=1)
    w = weight.astype(acc)
    y = sum(front[:, k:k + t].astype(acc) * w[k] for k in range(taps))
    # row j of the state is padded position length + j
    at = length.astype(jnp.int32)[:, None] \
        + jnp.arange(taps - 1, dtype=jnp.int32)[None, :]
    state = jnp.take_along_axis(
        front, jnp.clip(at, 0, t + taps - 2)[:, :, None], axis=1)
    return _conv_out(attrs, y, c, bias, proj.dtype), state


# ------------------------------------------------- selective state space
def _ssd_dims(attrs, x_shape, dt_shape, b_shape):
    """``(heads, head size, groups, state size)``: head ``h`` reads
    group ``h // (heads // groups)`` of ``B`` and ``C``."""
    h, g = dt_shape[-1], attrs["num_groups"]
    return h, x_shape[-1] // h, g, b_shape[-1] // g


def _ssd_fill(attrs, in_shapes):
    out = list(in_shapes)
    if out[1] is not None:
        for i in (5, 6, 7):
            if out[i] is None:
                out[i] = (out[1][-1],)
    return out


def _ssd_rates(a_log, dt, dt_bias, live=None):
    """``(dt, dt * A)`` in float32: ``dt = softplus(dt + dt_bias)``,
    nought where ``live`` is false; ``A = -exp(A_log)``."""
    acc = _acc(dt)
    rate = jax.nn.softplus(dt.astype(acc) + dt_bias.astype(acc))
    if live is not None:
        rate = jnp.where(live, rate, 0.0)
    return rate, rate * -jnp.exp(a_log.astype(acc))


def _ssd_step_flops(attrs, ins, out):
    # decay, dt x B and the sum a state value; y = S C a multiply-add
    # a state value; D x
    h, p, _g, n = _ssd_dims(attrs, ins[0], ins[1], ins[2])
    return ins[0][0] * (5.0 * h * p * n + 2.0 * h * p)


@register("_ssd_step", nin=8, nout=2,
          input_names=["data", "dt", "B", "C", "state", "A_log", "dt_bias",
                       "D"],
          params={"num_groups": P(int, 1)}, fill_shapes=_ssd_fill,
          row_local="axis0", flops=_ssd_step_flops)
def ssd_step(attrs, x, dt, b, c, state, a_log, dt_bias, d):
    """One position a slot of Mamba-2's selective state space.

    ``x`` ``(slots, heads * P)``, ``dt`` ``(slots, heads)`` before its
    bias, ``B`` and ``C`` ``(slots, groups * N)``, ``state`` ``(slots,
    heads, P, N)``; ``A_log``, ``dt_bias`` and ``D`` a value a head.
    With ``dt = softplus(dt + dt_bias)`` and ``A = -exp(A_log)``, head
    ``h`` of group ``g``::

        S_h = exp(dt_h A_h) S_h + dt_h x_h (outer) B_g
        y_h = S_h C_g + D_h x_h

    Returns ``y`` in ``x``'s dtype and the next state in the state's.
    The arithmetic is float32 and the state is rounded once; ``y`` is
    read off the float32 state, before that rounding."""
    h, p, g, n = _ssd_dims(attrs, x.shape, dt.shape, b.shape)
    slots, k = x.shape[0], h // g
    acc = _acc(x)
    rate, decay = _ssd_rates(a_log, dt, dt_bias)
    xs = x.astype(acc).reshape(slots, g, k, p)
    s = state.astype(acc).reshape(slots, g, k, p, n)
    s = jnp.exp(decay).reshape(slots, g, k, 1, 1) * s \
        + (rate.reshape(slots, g, k, 1) * xs)[..., None] \
        * b.astype(acc).reshape(slots, g, 1, 1, n)
    y = jnp.einsum("bgkpn,bgn->bgkp", s, c.astype(acc).reshape(slots, g, n)) \
        + d.astype(acc).reshape(g, k, 1) * xs
    return (y.reshape(x.shape).astype(x.dtype),
            s.reshape(state.shape).astype(state.dtype))


def _ssd_chunks(attrs, t):
    q = min(attrs["chunk"], t)
    return q, -(-t // q)


def _ssd_scan_flops(attrs, ins, out):
    """By whole chunks, as the scan computes them: ``C B^T`` a group and
    its weights times ``x`` a head (``q x q`` a chunk), each chunk's
    contribution to the state and the state's to each position (``q x
    P x N`` a head), the state carried (``P x N`` a head), ``D x``."""
    b, t, _hp = ins[0]
    h, p, g, n = _ssd_dims(attrs, ins[0], ins[1], ins[2])
    q, nc = _ssd_chunks(attrs, t)
    per_chunk = 2.0 * q * q * (g * n + h * p) + 3.0 * h * q * q \
        + 4.0 * q * h * p * n + 2.0 * h * p * n + 2.0 * q * h * p
    return b * nc * per_chunk


def _ssd_scan_temp(attrs, ins, dts):
    """In float32 a chunk's weights ``(batch, heads, q, q)`` twice, the
    state carried, the chunk's contribution and the state its positions
    read ``(batch, heads, P, N)``, the chunk's ``x`` and ``y``; ``y`` of
    every chunk in ``x``'s dtype."""
    b, t, hp = ins[0]
    h, p, _g, n = _ssd_dims(attrs, ins[0], ins[1], ins[2])
    q, nc = _ssd_chunks(attrs, t)
    return b * (4 * (2 * h * q * q + 3 * h * p * n + 2 * q * hp)
                + nc * q * hp * _itemsize(dts[0]))


@register("_ssd_scan", nin=8, nout=2,
          input_names=["data", "dt", "B", "C", "length", "A_log",
                       "dt_bias", "D"],
          params={"num_groups": P(int, 1), "chunk": P(int, 128)},
          fill_shapes=_ssd_fill, row_local="axis0", flops=_ssd_scan_flops,
          temp_bytes=_ssd_scan_temp)
def ssd_scan(attrs, x, dt, b, c, length, a_log, dt_bias, d):
    """A whole padded prompt of ``_ssd_step``'s state space from a zero
    state, in chunks of ``chunk`` positions (Mamba-2's state space
    duality), in XLA.

    ``x`` ``(batch, T, heads * P)``, ``dt``, ``B``, ``C`` likewise a
    position, ``length`` ``(batch,)`` live positions a row.  Within a
    chunk the outputs are two matrix products: ``(C B^T)`` masked to the
    causal band and weighted by ``dt_j exp(sum of dt A over j < .. <=
    i)``, times ``x``.  Across chunks one state a head is carried, chunk
    after chunk (unrolled: a prompt's bucket fixes how many; with the
    chunks in one ``lax.scan`` a v5e never finished Falcon-H1's prefill
    program over 8 prompts of 512, which it runs unrolled): each
    position reads the state the chunk was entered with, decayed to it,
    and the chunk leaves its own contribution behind.  No state a
    position exists.  Positions at or past a row's
    length take ``dt = 0``: they neither decay the state nor feed it,
    so the state returned is the one *at the row's own length*, whatever
    the padding holds.  Returns ``y`` at every position in ``x``'s dtype
    and that state ``(batch, heads, P, N)``, rounded once to ``x``'s
    dtype; the arithmetic is float32."""
    h, p, g, n = _ssd_dims(attrs, x.shape, dt.shape, b.shape)
    bsz, t = x.shape[:2]
    k = h // g
    q, nc = _ssd_chunks(attrs, t)
    acc = _acc(x)
    live = jnp.arange(t)[None, :] < length.astype(jnp.int32)[:, None]
    rate, decay = _ssd_rates(a_log, dt, dt_bias, live[..., None])

    def chunked(v, *tail):
        """``(batch, T, ...)`` -> ``(batch, chunks * q, *tail)``, the
        padding past ``T`` nought."""
        v = jnp.pad(v, [(0, 0), (0, nc * q - t)] + [(0, 0)] * (v.ndim - 2))
        return v.reshape((bsz, nc * q) + tail)
    ins = (chunked(x, g, k, p), chunked(rate, g, k), chunked(decay, g, k),
           chunked(b, g, n), chunked(c, g, n))
    causal = jnp.tril(jnp.ones((q, q), bool))
    skip = d.astype(acc).reshape(g, k, 1)

    def one_chunk(s, lo):
        xc, rc, dc, bc, cc = (v[:, lo:lo + q].astype(acc) for v in ins)
        cum = jnp.cumsum(dc, axis=1)                        # (b, q, g, k)
        # weight of position j's input at position i of the chunk
        gap = jnp.moveaxis(cum, 1, -1)[..., :, None] \
            - jnp.moveaxis(cum, 1, -1)[..., None, :]        # (b, g, k, i, j)
        w = jnp.exp(jnp.where(causal, gap, -jnp.inf)) \
            * jnp.einsum("bign,bjgn->bgij", cc, bc)[:, :, None] \
            * jnp.moveaxis(rc, 1, -1)[..., None, :]
        y = jnp.einsum("bgkij,bjgkp->bigkp", w, xc) \
            + jnp.einsum("bign,bgkpn->bigkp", cc, s) * jnp.exp(cum)[..., None]
        # the chunk's inputs decayed to its end, and the state carried
        left = jnp.exp(cum[:, -1:] - cum) * rc              # (b, q, g, k)
        s = jnp.exp(cum[:, -1])[..., None, None] * s \
            + jnp.einsum("bjgn,bjgk,bjgkp->bgkpn", bc, left, xc)
        return s, (y + skip * xc).astype(x.dtype)

    s, ys = jnp.zeros((bsz, g, k, p, n), acc), []
    for lo in range(0, nc * q, q):
        s, y = one_chunk(s, lo)
        ys.append(y)
    y = jnp.concatenate(ys, axis=1).reshape(bsz, nc * q, h * p)[:, :t]
    return y, s.reshape(bsz, h, p, n).astype(x.dtype)


# ------------------------------------------------------------ expert layer
def _held(attrs, n_experts):
    first = attrs["first_expert"]
    held = attrs["num_held"] if attrs["num_held"] > 0 else n_experts - first
    return first, held


def _moe_rows(ins):
    return _prod(ins[0][:-1])


def _moe_padded_rows(attrs, rows, held):
    """Rows the sorted path multiplies at most: the pairs in whole
    blocks, and a block of padding an expert."""
    blk = attrs["block"]
    return (-(-rows * attrs["top_k"] // blk) + held) * blk


def _moe_dense(attrs, rows, held):
    """Every held expert over every row, where that is no more rows
    multiplied than the sorted path may pad to."""
    return rows * held <= _moe_padded_rows(attrs, rows, held)


def _moe_tile(pairs, held):
    """Rows of the grouped kernel's tile: a whole bfloat16 sublane tile
    (16), or twice that where the pairs per held expert fill it twice
    over (LFM2's step: 1,024 pairs over 32 experts)."""
    return 32 if pairs >= 32 * held else 16


def _moe_grouped_rows(attrs, rows, held):
    """Rows the grouped kernel may multiply: each held expert's pairs
    padded to whole tiles, at most ``pairs + held * (tile - 1)``."""
    pairs = rows * attrs["top_k"]
    tile = _moe_tile(pairs, held)
    return (pairs + held * (tile - 1)) // tile * tile


# widest tile of an expert's width the kernel holds, in bytes of one of
# its three matrices (double-buffered: six such blocks in VMEM)
_GROUPED_BLOCK_BYTES = 4 * 1024 * 1024
_GROUPED_VMEM_BYTES = 64 * 1024 * 1024


def _moe_width_tile(f, d, item):
    """Rows of an expert's matrices a grid step holds: the widest whole
    number of 128-row tiles that divides ``f`` within the block bytes."""
    return max(t for t in range(128, f + 1, 128)
               if f % t == 0 and (t == 128 or t * d * item
                                  <= _GROUPED_BLOCK_BYTES))


def _moe_grouped_vmem(rows, f, d, item):
    """VMEM the grouped kernel asks: the rows and the float32 output
    resident (double-buffered), an expert's gathered rows, their weights
    and float32 results, and three double-buffered tiles of an expert's
    matrices."""
    cap = -(-rows // 32) * 32           # rows an expert holds, whole tiles
    tf = _moe_width_tile(f, d, item)
    return 4 * rows * d * 4 + cap * (2 * d * 4 + 128 * 4) + 6 * tf * d * item


def moe_groupable(attrs, shapes, dtypes=None, training=False):
    """Whether ``_moe_experts`` over inputs of these shapes (and dtypes,
    where known) is the grouped kernel's where the program is lowered
    for a TPU: an inference trace (``pallas_call`` has no
    differentiation rule) at a step's size (where the plain path is
    chosen over the sorted one), the data and the three weights all
    bfloat16 or all float32, hidden size and expert width in whole
    128-lane tiles, and what the kernel holds within its VMEM."""
    if training or len(shapes) < 5:
        return False
    rows, (held, f, d) = _moe_rows(shapes), tuple(shapes[2])
    if not _moe_dense(attrs, rows, held) or d % 128 or f % 128 \
            or shapes[0][-1] != d:
        return False
    item = 2
    if dtypes is not None:
        kinds = {jnp.dtype(x) for x in (dtypes[0],) + tuple(dtypes[2:5])}
        if len(kinds) != 1 or not kinds <= {jnp.dtype(jnp.bfloat16),
                                            jnp.dtype(jnp.float32)}:
            return False
        item = kinds.pop().itemsize
    return _moe_grouped_vmem(rows, f, d, item) <= _GROUPED_VMEM_BYTES


def moe_takes_kernel(attrs, shapes, dtypes=None):
    """Whether an inference program this process builds runs
    ``_moe_experts`` over these inputs as the grouped kernel: what the
    declared ``flops`` and ``temp_bytes`` and the engine's
    ``expert_products`` counter go by."""
    return _lowers_for_tpu() and moe_groupable(attrs, shapes, dtypes)


def moe_products(attrs, shapes, dtypes=None):
    """(row, expert) products a ``_moe_experts`` node over inputs of
    these shapes multiplies: on the grouped kernel each held expert's
    pairs padded to whole tiles; every held expert over every row on the
    plain path; the rows the sorted path may pad its pairs to past it.
    The rows that were routed are ``rows * top_k``."""
    rows, held = _moe_rows(shapes), shapes[2][0]
    if moe_takes_kernel(attrs, shapes, dtypes):
        return _moe_grouped_rows(attrs, rows, held)
    if _moe_dense(attrs, rows, held):
        return rows * held
    return _moe_padded_rows(attrs, rows, held)


def _moe_flops(attrs, ins, out):
    rows, held, f, d = (_moe_rows(ins),) + tuple(ins[2])
    if moe_takes_kernel(attrs, ins):
        return 6.0 * _moe_grouped_rows(attrs, rows, held) * f * d
    if _moe_dense(attrs, rows, held):
        return 6.0 * rows * held * f * d
    return 6.0 * rows * attrs["top_k"] * f * d


def _moe_temp(attrs, ins, dts):
    rows, held, f, d = (_moe_rows(ins),) + tuple(ins[2])
    item = _itemsize(dts[0])
    if moe_takes_kernel(attrs, ins, dts):
        # the rows in float32 and the pairs' order, rows and weights;
        # gathered rows, act(gate) * up and the results stay in VMEM
        return rows * d * 4 + rows * attrs["top_k"] * 4 * 4
    if _moe_dense(attrs, rows, held):
        return rows * held * f * (3 * 4 + item)
    padded = _moe_padded_rows(attrs, rows, held)
    return padded * d * 2 * item + rows * attrs["top_k"] * d * 4


def _moe_fill(attrs, in_shapes):
    out = list(in_shapes)
    if out[0] is not None and out[1] is not None:
        _first, held = _held(attrs, out[1][-1])
        for i in (2, 3, 4):
            if len(out) > i and out[i] is None:
                out[i] = (held, attrs["expert_width"], out[0][-1])
    return out


def _moe_route(attrs, r, bias):
    """``(chosen experts, their weights)`` of each row of float32
    router logits ``r``, by the layer's rule.  ``softmax``: the
    ``top_k`` largest logits, weighted by the softmax over them.
    ``sigmoid``: scores ``sigmoid(r)``; the ``top_k`` largest of score
    plus ``bias`` (where the layer has one: it steers the choice and
    nothing else) are chosen, and weighted by their own unbiased scores,
    over their sum plus 1e-6 under ``norm_topk``, times
    ``route_scale``."""
    k = attrs["top_k"]
    if attrs["routing"] == "softmax":
        top_v, top_i = lax.top_k(r, k)
        return top_i, jax.nn.softmax(top_v, axis=-1)
    s = jax.nn.sigmoid(r)
    _, top_i = lax.top_k(s if bias is None else s + bias.astype(r.dtype), k)
    w = jnp.take_along_axis(s, top_i, axis=-1)
    if attrs["norm_topk"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    return top_i, w * attrs["route_scale"]


@register("_moe_experts", nout=2,
          nin=lambda attrs: 6 if (attrs or {}).get("expert_bias") else 5,
          input_names=["data", "router", "gate_weight", "up_weight",
                       "down_weight", "bias"],
          params={"top_k": P(int), "first_expert": P(int, 0),
                  "num_held": P(int, 0), "expert_width": P(int, 0),
                  "block": P(int, 256),
                  "routing": P(str, "softmax",
                               choices=["softmax", "sigmoid"]),
                  "activation": P(str, "relu", choices=list(_ACTIVATIONS)),
                  "expert_bias": P(bool, False),
                  "norm_topk": P(bool, True),
                  "route_scale": P(float, 1.0)},
          fill_shapes=_moe_fill, row_local="leading", flops=_moe_flops,
          temp_bytes=_moe_temp, mode_dependent=True)
def moe_experts(attrs, data, router, wg, wu, wd, bias=None):
    """Sparse gated-linear experts, dropless, over the experts held here.

    ``router`` holds a row's logits over *all* the experts (the
    published router width); ``routing`` says how they choose and weigh
    (``_moe_route``: by default each row takes its ``top_k`` largest and
    weights them by the softmax over those ``top_k`` logits; ``sigmoid``
    scores each expert alone and, with ``expert_bias``, chooses under
    the sixth input, a bias an expert).  The weights ``(num_held, width,
    hidden)`` are those of experts ``first_expert .. first_expert +
    num_held - 1`` (``num_held`` 0: all from ``first_expert`` on);
    expert ``e`` computes ``down_e.T @ (act(gate_e @ x) * (up_e @ x))``
    with ``activation`` ``relu`` or ``silu``.  Returns the held experts' part
    of the weighted sum (shares over a partition of the experts add up
    to the whole layer) and the ``(..., experts)`` routing weights, zero
    where an expert was not chosen.

    Three formulations of the one sum, and in each an expert's gated
    activation takes its routing weight in float32 before it is rounded
    for the down projection.  Where every held expert over every row is
    no more rows multiplied than the sorted path may pad to (up to 284
    rows for 64 experts, 6 a row, blocks of 256: a decode step), an
    inference trace lowered for a TPU takes ``moe_grouped`` (chosen by
    ``moe_groupable`` and the platform at lowering): one Pallas kernel
    that reads each hit expert's weights once and multiplies only its
    routed rows, padded to tiles of 16 or 32.  Everything else at that
    size (training traces, the CPU, other dtypes or widths) takes three
    plain products against the parameters as stored, every held expert
    over every row.  Past that size (a prefill), the (row, expert) pairs
    are sorted by expert, each expert's run padded to a multiple of
    ``block``, and a loop multiplies one block by one expert's weights:
    no pair is dropped, and the padding is at most ``block`` rows an
    expert.  On a v5e, a layer at the published widths in bfloat16 (ms;
    PERF.md section 5): LFM2's step, 256 rows, 4 of 32: plain 1.18,
    kernel 0.98 with every expert hit (0.94 of it the kernel, 92 % of
    the peak HBM rate), 0.87 with 27 hit; SmallThinker's step, 32 rows,
    6 of 64: plain 1.05, kernel 1.02 with 62 hit, 0.61 with 32;
    ``ragged_dot`` 2.46 and 1.34; sorted at 32 rows 3.14, at 512 rows
    3.44 against 2.48 plain, so the rule leaves the plain products
    early; 8,192 rows 16 ms sorted, where the plain products would be
    64/6 of the work (40 ms at the peak) and 4.8 GB of activations."""
    blk = attrs["block"]
    lead, d = data.shape[:-1], data.shape[-1]
    n_exp = router.shape[-1]
    first, held = _held(attrs, n_exp)
    f = wg.shape[1]
    x = data.reshape(-1, d)
    acc = _acc(data)
    r = router.reshape(-1, n_exp).astype(acc)
    m = x.shape[0]
    top_i, w = _moe_route(attrs, r, bias)
    act_fn = _ACTIVATIONS[attrs["activation"]]
    route = jnp.sum(jax.nn.one_hot(top_i, n_exp, dtype=acc)
                    * w[..., None], axis=1)
    nt = (((1,), (1,)), ((), ()))

    def plain(x, top_i, w, route, wg, wu, wd):
        local = route[:, first:first + held]
        gate = lax.dot_general(x, wg.reshape(held * f, d), nt,
                               preferred_element_type=acc)
        up = lax.dot_general(x, wu.reshape(held * f, d), nt,
                             preferred_element_type=acc)
        act = (act_fn(gate) * up).reshape(m, held, f) \
            * local[:, :, None]
        return jnp.dot(act.reshape(m, held * f).astype(x.dtype),
                       wd.reshape(held * f, d), preferred_element_type=acc)

    def grouped(x, top_i, w, route, wg, wu, wd):
        return moe_grouped(x, top_i, w, wg, wu, wd, first=first,
                           activation=attrs["activation"])

    ins = (x, top_i, w, route, wg, wu, wd)
    if moe_groupable(attrs, [a.shape for a in (data, router, wg, wu, wd)],
                     [a.dtype for a in (data, router, wg, wu, wd)],
                     attrs.get("_training", False)):
        y = lax.platform_dependent(*ins, tpu=grouped, default=plain)
    elif _moe_dense(attrs, m, held):
        y = plain(*ins)
    else:
        y = _moe_sorted(x, top_i, w, wg, wu, wd, first, held, blk, act_fn)
    return (y.reshape(lead + (d,)).astype(data.dtype),
            route.reshape(lead + (n_exp,)))


@functools.partial(jax.jit, static_argnames=(
    "first", "activation", "tile", "width_tile", "interpret"))
def moe_grouped(x, top_i, w, wg, wu, wd, first=0, activation="relu",
                tile=None, width_tile=None, interpret=False):
    """The step's expert layer as one grouped product, a Pallas TPU
    kernel: each held expert's weights read from HBM once and multiplied
    by that expert's routed rows alone.  The weights are those of
    experts ``first .. first + len(wg) - 1``; routing weights ``w`` are
    float32, ``top_i`` the chosen experts, ``(rows, top_k)`` each.

    The ``rows x top_k`` (row, expert) pairs are sorted by expert; each
    pair's row index and routing weight are scalar-prefetched.  Grid
    ``(expert, width tile)``: a step holds one ``(width_tile, hidden)``
    tile of the expert's gate, up and down matrices.  An expert's first
    step gathers its pairs' rows from the resident ``x`` into VMEM;
    every step multiplies them in ``tile``-row tiles (the last one
    padded), ``act(x gate^T) * (x up^T)`` in float32, weighted by the
    pair's routing weight, rounded once for the down product, whose
    float32 product adds into the expert's rows; its last step adds each
    pair's float32 result into its row of the resident float32 output,
    so a row's ``top_k`` results are summed in float32.  An expert no
    pair chose maps its steps to the blocks of the step before it, so
    its weights are never fetched.

    The same mathematics as the plain products at the same precision:
    the sum over a row's experts runs in a float32 add where the plain
    path runs it in the down product's float32 accumulator."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, d = x.shape
    k = top_i.shape[1]
    held, f = wg.shape[:2]
    pairs = m * k
    acc = jnp.float32
    item = jnp.dtype(wg.dtype).itemsize
    t = tile or _moe_tile(pairs, held)
    tf = width_tile or _moe_width_tile(f, d, item)
    nj = f // tf
    m_cap = -(-m // t) * t          # an expert holds each row at most once
    act_fn = _ACTIVATIONS[activation]

    # pairs choice-major (all rows' first choice, then their second, ...);
    # the others' pairs sort last
    e = top_i.T.reshape(-1) - first
    mine = jnp.logical_and(e >= 0, e < held)
    e = jnp.where(mine, e, held).astype(jnp.int32)
    order = jnp.argsort(e, stable=True).astype(jnp.int32)
    sizes = jnp.zeros((held + 1,), jnp.int32).at[e].add(1)[:held]
    starts = jnp.cumsum(sizes) - sizes
    rows_of = order % m
    weight_of = w.T.reshape(-1).astype(acc)[order]
    # the blocks an expert with no pairs maps to: the last tile of the
    # held expert before it, or the first tile of the first held one
    idx = jnp.arange(held, dtype=jnp.int32)
    some = sizes > 0
    before = lax.cummax(jnp.where(some, idx, -1))
    blk_e = jnp.where(before >= 0, before,
                      jnp.argmax(some).astype(jnp.int32))
    blk_j = jnp.where(some, -1, jnp.where(before >= 0, nj - 1, 0))

    def weights(ei, j, sizes_r, starts_r, blk_e_r, blk_j_r, *_):
        jj = blk_j_r[ei]
        return (blk_e_r[ei], jnp.where(jj < 0, j, jj), 0)

    def whole(ei, j, *_):
        return (0, 0)

    def kernel(sizes_r, starts_r, blk_e_r, blk_j_r, rows_r, weight_r,
               x_ref, wg_ref, wu_ref, wd_ref, out_ref, xg_ref, wt_ref,
               y_ref):
        ei, j = pl.program_id(0), pl.program_id(1)
        n, at = sizes_r[ei], starts_r[ei]

        @pl.when(jnp.logical_and(ei == 0, j == 0))
        def _zero():
            out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

        @pl.when(j == 0)
        def _gather():
            def one(i, carry):
                xg_ref[pl.ds(i, 1), :] = x_ref[pl.ds(rows_r[at + i], 1), :]
                wt_ref[pl.ds(i, 1), :] = jnp.full((1, 128), weight_r[at + i],
                                                  acc)
                return carry
            lax.fori_loop(0, n, one, 0)

        def one_tile(c, carry):
            r = pl.ds(pl.multiple_of(c * t, t), t)
            xb = xg_ref[r, :].astype(wg_ref.dtype)
            gate = lax.dot_general(xb, wg_ref[...], (((1,), (1,)), ((), ())),
                                   preferred_element_type=acc)
            up = lax.dot_general(xb, wu_ref[...], (((1,), (1,)), ((), ())),
                                 preferred_element_type=acc)
            a = act_fn(gate) * up * wt_ref[r, :][:, :1]
            y = jnp.dot(a.astype(xb.dtype), wd_ref[...],
                        preferred_element_type=acc)

            @pl.when(j == 0)
            def _first():
                y_ref[r, :] = y

            @pl.when(j > 0)
            def _more():
                y_ref[r, :] += y
            return carry

        lax.fori_loop(0, (n + t - 1) // t, one_tile, 0)

        @pl.when(j == nj - 1)
        def _scatter():
            def one(i, carry):
                row = pl.ds(rows_r[at + i], 1)
                out_ref[row, :] += y_ref[pl.ds(i, 1), :]
                return carry
            lax.fori_loop(0, n, one, 0)

    w_spec = pl.BlockSpec((None, tf, d), weights)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(held, nj),
            in_specs=[pl.BlockSpec((m, d), whole), w_spec, w_spec, w_spec],
            out_specs=pl.BlockSpec((m, d), whole),
            scratch_shapes=[pltpu.VMEM((m_cap, d), acc),
                            pltpu.VMEM((m_cap, 128), acc),
                            pltpu.VMEM((m_cap, d), acc)]),
        out_shape=jax.ShapeDtypeStruct((m, d), acc),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_GROUPED_VMEM_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=6 * _moe_grouped_rows({"top_k": k}, m, held) * f * d,
            transcendentals=pairs * f,
            bytes_accessed=3 * held * f * d * item + 2 * m * d * 4),
        name="moe_grouped",
        interpret=interpret,
    )(sizes, starts.astype(jnp.int32), blk_e, blk_j.astype(jnp.int32),
      rows_of, weight_of, x.astype(acc), wg, wu, wd)


def _moe_sorted(x, top_i, w, wg, wu, wd, first, held, blk, act_fn):
    """The grouped product: pairs sorted by expert into runs padded to
    whole blocks, one block against one expert's weights a loop turn
    (the pair's routing weight on the activation), then each row's
    ``top_k`` results summed in float32.
    Pairs are numbered choice-major (all rows' first choice, then their
    second, ...), so that the results come back as ``(top_k, rows,
    hidden)`` and the sum runs over the leading axis."""
    m, d = x.shape
    k = top_i.shape[1]
    pairs = m * k
    acc = _acc(x)
    nt = (((1,), (1,)), ((), ()))
    e = top_i.T.reshape(-1) - first
    mine = jnp.logical_and(e >= 0, e < held)
    e = jnp.where(mine, e, held)                 # the others sort last
    order = jnp.argsort(e, stable=True)
    rank = jnp.argsort(order)                    # pair -> place when sorted
    sizes = jnp.zeros((held + 1,), jnp.int32).at[e].add(1)[:held]
    start = jnp.cumsum(sizes) - sizes
    padded = -(-sizes // blk) * blk
    pend = jnp.cumsum(padded)
    pstart = pend - padded
    n_rows = (-(-pairs // blk) + held) * blk
    p = jnp.arange(n_rows, dtype=jnp.int32)
    grp = jnp.minimum(jnp.searchsorted(pend, p, side="right"), held - 1)
    off = p - pstart[grp]
    real = jnp.logical_and(off < sizes[grp], p < pend[-1])
    # a padding row reads the zero row appended to ``x``
    src = jnp.where(real,
                    order[jnp.clip(start[grp] + off, 0, pairs - 1)] % m, m)
    xs = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)])[src]
    # a pair's weight, in sorted order; padding and the others' pairs 0
    wk = w.T.reshape(-1) * mine.astype(acc)
    ws = jnp.where(real, wk[order[jnp.clip(start[grp] + off, 0, pairs - 1)]],
                   0.0)
    block_e = grp[::blk]

    def one_block(b, ys):
        at = b * blk
        xb = lax.dynamic_slice_in_dim(xs, at, blk, axis=0)
        ex = block_e[b]
        gate = lax.dot_general(xb, wg[ex], nt, preferred_element_type=acc)
        up = lax.dot_general(xb, wu[ex], nt, preferred_element_type=acc)
        wb = lax.dynamic_slice_in_dim(ws, at, blk, axis=0)
        act = (act_fn(gate) * up * wb[:, None]).astype(x.dtype)
        yb = jnp.dot(act, wd[ex], preferred_element_type=acc)
        return lax.dynamic_update_slice_in_dim(ys, yb.astype(x.dtype), at,
                                               axis=0)

    ys = lax.fori_loop(0, pend[-1] // blk, one_block,
                       jnp.zeros((n_rows, d), x.dtype))
    ec = jnp.minimum(e, held - 1)
    dest = jnp.clip(pstart[ec] + rank - start[ec], 0, n_rows - 1)
    return jnp.sum(jnp.where(mine.reshape(k, m, 1),
                             ys[dest].reshape(k, m, d).astype(acc), 0.0),
                   axis=0)

"""Plain reference for ``lfm2-8b-a1b-14l-bf16``: the decoder's full
forward pass in ``jax.numpy``, one sequence at a time, with no cache, no
conv state, no batching, no kernel and nothing of the program.

The layer, from the published config (Liquid AI, LFM2-8B-A1B,
``config.json``, ``model_type`` ``lfm2_moe``); ``h`` is the layer's
input and every norm is ``g * x / sqrt(mean(x^2) + 1e-5)``::

    a   = norm_op(h)
    conv layer:       [B, C, x] = W_in a          three blocks of 2,048
                      u   = B * x
                      y_t = w_0 u_{t-2} + w_1 u_{t-1} + w_2 u_t   per channel
                      h'  = h + W_out (C * y)
    attention layer:  q, k, v = W_q a, W_k a, W_v a   32 / 8 / 8 heads of 64
                      q, k = rotate(headnorm(q), pos), rotate(headnorm(k), pos)
                      h'  = h + W_o attention(q, k, v)        causal, 1/8
    u'  = norm_ffn(h')
    layers 0-1:       h'' = h' + W_2 (silu(W_1 u') * W_3 u')     width 7,168
    after them:       s   = sigmoid(W_r u')                      32 experts
                      chosen = top4(s + b)
                      h'' = h' + sum_{e chosen} s_e / (sum_chosen s + 1e-6)
                                   * W_2,e (silu(W_1,e u') * W_3,e u')

then a final norm and the head, which is the embedding matrix.
``layer_types`` says which layers are which.  What the catalog's config
leaves open is listed under ``assumed`` in the configuration's file.

Precision.  ``"default"`` is what the configuration states, in the
dtype the weights come in: weights and activations in that dtype
(bfloat16 on the chip), every product accumulated in float32; router
scores, bias, choice and normalisation, attention's scores and softmax,
the conv's multiply-adds and the norms' statistics in float32; ``u`` is
rounded once to the activations' dtype (what a state row would hold),
an expert's output before the float32 weighted sum.  With float32
weights (the CPU tests) every product runs at ``highest``.  The controls
keep the default's structure and plant one fault each: ``"fp8"`` rounds
both operands of every product to float8 (e4m3), the step below;
``"top3"`` drops each row's fourth expert; ``"no_bias"`` chooses by the
scores alone; ``"conv_cold"`` lets every position after the prompt read
zeros for the prompt's ``u`` (decoding from a zero conv state: the
prefill's rows not carried); ``"no_qk_norm"`` leaves the head norms out.
A control does not decode: at every served position it reads the gap, in
the reference's logits, of the token the faulty computation puts first.
"""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

CONTROLS = ("fp8", "top3", "no_bias", "conv_cold", "no_qk_norm")
_F32 = jnp.float32


def _highest(dtype):
    if jnp.dtype(dtype) == jnp.dtype(_F32):
        return jax.default_matmul_precision("highest")
    return contextlib.nullcontext()


def _operand(x, variant):
    if variant == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(x.dtype)
    return x


def _dot(x, w, variant, spec="...i,oi->...o"):
    """A product in the stated precision: operands as stored (or
    rounded to fp8 by the control), accumulated in float32."""
    return jnp.einsum(spec, _operand(x, variant), _operand(w, variant),
                      preferred_element_type=_F32)


def rms_norm(x, gamma, eps):
    x32 = x.astype(_F32)
    inv = lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * inv).astype(x.dtype) * gamma.astype(x.dtype)


def head_norm(x, gamma, eps):
    """Each head of ``(T, heads * d)`` normed over its own ``d`` values
    under the one gain ``gamma`` ``(d,)``."""
    d = gamma.shape[0]
    return rms_norm(x.reshape(x.shape[0], -1, d), gamma, eps) \
        .reshape(x.shape)


def rotate(x, pos, head_dim, theta):
    """Half-rotation rotary embedding of ``(T, heads * head_dim)`` rows
    at integer positions ``pos`` ``(T,)``."""
    half = head_dim // 2
    inv = jnp.asarray(theta, _F32) ** (
        -jnp.arange(half, dtype=_F32) * 2.0 / head_dim)
    ang = pos.astype(_F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    xh = x.astype(_F32).reshape(x.shape[0], -1, head_dim)
    x1, x2 = xh[..., :half], xh[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.reshape(x.shape).astype(x.dtype)


def attention(q, k, v, heads, kv_heads, variant="default", q_block=512):
    """Causal attention of ``(T, heads * d)`` queries over ``(T,
    kv_heads * d)`` keys and values; query head ``h`` reads key head
    ``h // (heads // kv_heads)``.  A block of queries at a time against
    all keys, masked."""
    t = q.shape[0]
    d = k.shape[1] // kv_heads
    g = heads // kv_heads
    kh = k.reshape(t, kv_heads, d)
    vh = v.reshape(t, kv_heads, d)
    pad = -t % q_block
    qh = jnp.pad(q, ((0, pad), (0, 0))).reshape(-1, q_block, kv_heads, g, d)
    starts = jnp.arange(qh.shape[0], dtype=jnp.int32) * q_block
    kj = jnp.arange(t, dtype=jnp.int32)[None, :]

    def block(args):
        qb, s0 = args
        qi = s0 + jnp.arange(q_block, dtype=jnp.int32)[:, None]
        s = _dot(qb, kh, variant, "qkgd,lkd->kgql") * (d ** -0.5)
        a = jax.nn.softmax(jnp.where(kj <= qi, s, -1e30), axis=-1)
        o = _dot(a.astype(v.dtype), vh, variant, "kgql,lkd->qkgd")
        return o.reshape(q_block, heads * d).astype(q.dtype)

    out = lax.map(block, (qh, starts))
    return out.reshape(-1, heads * d)[:t]


def short_conv(proj, taps, cold_from=0):
    """The gated short convolution of ``(T, 3 d)`` projections ``[B, C,
    x]`` under ``taps`` ``(L, d)``, the last tap on the current
    position: ``C * y``, ``(T, d)``.  A position at or past
    ``cold_from`` reads zeros for ``u`` of every position before it
    (the ``conv_cold`` control; 0 is the plain convolution)."""
    t, d = proj.shape[0], proj.shape[1] // 3
    b, c, x = proj[:, :d], proj[:, d:2 * d], proj[:, 2 * d:]
    u = (b.astype(_F32) * x.astype(_F32)).astype(proj.dtype).astype(_F32)
    at = jnp.arange(t, dtype=jnp.int32)[:, None]
    n_taps = taps.shape[0]
    y = jnp.zeros((t, d), _F32)
    for k in range(n_taps):
        back = n_taps - 1 - k
        src = jnp.pad(u, ((back, 0), (0, 0)))[:t]       # u_{t - back}
        seen = jnp.logical_or(at < cold_from, at - back >= cold_from)
        y = y + jnp.where(seen, src, 0.0) * taps[k].astype(_F32)
    return (c.astype(_F32) * y).astype(proj.dtype)


def route(r, bias, top_k, variant="default", norm=True, scale=1.0):
    """``(T, experts)`` routing weights from float32 router logits:
    scores ``sigmoid(r)``, the ``top_k`` largest of score plus bias
    chosen, weighted by their unbiased scores over their sum (plus
    1e-6), zero elsewhere."""
    s = jax.nn.sigmoid(r.astype(_F32))
    by = s if bias is None or variant == "no_bias" \
        else s + bias.astype(_F32)
    _, top_i = lax.top_k(by, top_k)
    w = jnp.take_along_axis(s, top_i, axis=-1)
    if norm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    w = w * scale
    if variant == "top3":
        w = w.at[:, -1].set(0.0)
    onehot = jax.nn.one_hot(top_i, r.shape[-1], dtype=_F32)
    return jnp.sum(onehot * w[..., None], axis=1)


def experts(u, weights, wg, wu, wd, variant="default"):
    """``sum_e weights[:, e] * down_e.T (silu(gate_e u) * up_e u)`` over
    the experts given (``wg, wu, wd``: ``(n, width, hidden)``), every
    expert over every row, one expert at a time."""
    def one(acc, ew):
        g, up, dn, w_e = ew
        act = (jax.nn.silu(_dot(u, g, variant)) * _dot(u, up, variant)) \
            .astype(u.dtype)
        y = _dot(act, dn, variant, "...f,fd->...d").astype(u.dtype)
        return acc + w_e[:, None] * y.astype(_F32), None

    acc, _ = lax.scan(one, jnp.zeros(u.shape, _F32),
                      (wg, wu, wd, weights.T))
    return acc.astype(u.dtype)


def swiglu(u, wg, wu, wd, variant="default"):
    """The dense layers' ``W_2 (silu(W_1 u) * W_3 u)``; gate and up are
    rounded to the activations' dtype, their gated product once more."""
    g = _dot(u, wg, variant).astype(u.dtype).astype(_F32)
    up = _dot(u, wu, variant).astype(u.dtype).astype(_F32)
    act = (jax.nn.silu(g) * up).astype(u.dtype)
    return _dot(act, wd, variant).astype(u.dtype)


def hidden(params, cfg, tokens, variant="default", plen=0):
    """``(T,)`` token ids -> the final norm's output ``(T, hidden)``.
    ``plen`` is the prompt's length, which only ``conv_cold`` reads."""
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, eps = cfg["hidden_size"] // heads, cfg["norm_eps"]
    pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    cold = plen if variant == "conv_cold" else 0
    h = params["emb_weight"][tokens]
    for i in range(cfg["num_hidden_layers"]):
        pre = "l%d_" % i
        a = rms_norm(h, params[pre + "op_norm_gamma"], eps)
        if cfg["layer_types"][i] == "conv":
            proj = _dot(a, params[pre + "in_weight"], variant) \
                .astype(h.dtype)
            gated = short_conv(proj, params[pre + "conv_weight"], cold)
            h = h + _dot(gated, params[pre + "out_weight"], variant) \
                .astype(h.dtype)
        else:
            q = _dot(a, params[pre + "q_weight"], variant).astype(h.dtype)
            k = _dot(a, params[pre + "k_weight"], variant).astype(h.dtype)
            v = _dot(a, params[pre + "v_weight"], variant).astype(h.dtype)
            if variant != "no_qk_norm":
                q = head_norm(q, params[pre + "q_norm_gamma"], eps)
                k = head_norm(k, params[pre + "k_norm_gamma"], eps)
            q = rotate(q, pos, hd, cfg["rope_theta"])
            k = rotate(k, pos, hd, cfg["rope_theta"])
            att = attention(q, k, v, heads, kv, variant)
            h = h + _dot(att, params[pre + "o_weight"], variant) \
                .astype(h.dtype)
        u = rms_norm(h, params[pre + "ffn_norm_gamma"], eps)
        if i < cfg["num_dense_layers"]:
            h = h + swiglu(u, params[pre + "gate_weight"],
                           params[pre + "up_weight"],
                           params[pre + "down_weight"], variant)
        else:
            w = route(_dot(u, params[pre + "router_weight"], variant),
                      params.get(pre + "expert_bias"),
                      cfg["num_experts_per_tok"], variant,
                      bool(cfg["norm_topk_prob"]),
                      float(cfg["routed_scaling_factor"]))
            h = h + experts(u, w, params[pre + "gate_weight"],
                            params[pre + "up_weight"],
                            params[pre + "down_weight"], variant)
    return rms_norm(h, params["final_norm_gamma"], eps)


def logits(params, rows, variant="default"):
    return _dot(rows, params["emb_weight"], variant)


def forward(params, cfg, tokens, variant="default", plen=0):
    """Logits ``(T, vocab)`` of one sequence: the whole forward pass
    (for the tests, at small sizes)."""
    with _highest(params["emb_weight"].dtype):
        return logits(params, hidden(params, cfg, jnp.asarray(
            tokens, jnp.int32), variant, plen), variant)


def served_gaps(params, cfg, requests, precision="default", block=128,
                width=None):
    """``requests``: list of (prompt ids, served ids).  Each is
    teacher-forced (its prompt, then the served tokens) through the
    whole forward pass, alone; returns ``{"gaps", "tokens"}``: at every
    served position, how far the served token's logit lies below the
    reference's best, in that position's logit standard deviations;
    under a control, the same gap for the token the control puts first.
    ``width`` pads every sequence to one length, so one compiled program
    serves every request and every run."""
    if precision != "default" and precision not in CONTROLS:
        raise ValueError("unknown precision %r" % (precision,))
    seqs = [list(p) + list(s[:-1]) for p, s in requests]
    width = max([width or 0] + [len(s) for s in seqs])
    hid = jax.jit(lambda p, t, n, variant: hidden(p, cfg, t, variant, n),
                  static_argnums=3)

    @jax.jit
    def gaps_of(p, rows, chosen):
        lg = logits(p, rows)
        pick = jnp.take_along_axis(lg, chosen[:, None], axis=1)[:, 0]
        return (jnp.max(lg, axis=1) - pick) / jnp.std(lg, axis=1)

    first_of = jax.jit(lambda p, rows: jnp.argmax(
        logits(p, rows, precision), axis=1).astype(jnp.int32))

    out = []
    with _highest(params["emb_weight"].dtype):
        for (prompt, served), seq in zip(requests, seqs):
            tokens = np.zeros((width,), np.int32)
            tokens[:len(seq)] = seq
            plen = np.int32(len(prompt))
            at = len(prompt) - 1 + np.arange(len(served))
            fill = -len(at) % block
            at = np.concatenate([at, np.repeat(at[-1:], fill)])
            chosen = np.concatenate(
                [np.asarray(served, np.int32),
                 np.repeat(np.int32(served[-1]), fill)])
            rows = hid(params, tokens, plen, "default")[at]
            low = hid(params, tokens, plen, precision)[at] \
                if precision != "default" else None
            got = []
            for lo in range(0, len(at), block):
                sl = slice(lo, lo + block)
                pick = jnp.asarray(chosen[sl])
                if low is not None:
                    pick = first_of(params, low[sl])
                got.append(np.asarray(gaps_of(params, rows[sl], pick)))
            out.append(np.concatenate(got)[:len(served)])
    gaps = np.concatenate(out)
    return {"gaps": gaps, "tokens": int(gaps.size)}

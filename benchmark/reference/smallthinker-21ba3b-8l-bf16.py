"""Plain reference for ``smallthinker-21ba3b-8l-bf16``: the decoder's
full forward pass in ``jax.numpy``, one sequence at a time, with no
cache, no batching, no kernel and nothing of the program.

The layer, from the published config (PowerInfer, SmallThinker-21BA3B-
Instruct, ``config.json``) and the catalog's description of it; ``h`` is
the layer's input::

    r   = W_r h                                   router, on h itself, float32
    a   = RMSNorm_in(h)
    q, k, v = W_q a, W_k a, W_v a                 28 / 4 / 4 heads of 128
    q, k = rotate(q, pos), rotate(k, pos)         window layers only
    h'  = h + W_o attention(q, k, v)              causal; window layers see
                                                  i - j < 4096, global all
    u   = RMSNorm_post(h')
    h'' = h' + sum_{e in top6(r)} softmax(r[top6])_e
                   * W_down,e (relu(W_gate,e u) * W_up,e u)

then a final RMSNorm and the untied head.  ``rope_layout`` and
``sliding_window_layout`` say which layers rotate and which are windowed
(both ``[0, 1, 1, 1]`` repeated: every fourth layer is global and has no
positional encoding at all).

Departures from the published description, all listed under ``assumed``
in the configuration's file: the router reads the un-normalised layer
input; no biases and no query/key norm; rotary in the half-rotation
layout; the window counts the current position; the "secondary experts"
the description mentions have no key in the config and are absent.

Precision.  ``"default"`` is what the configuration states, in the
dtype the weights come in: weights and activations in that dtype
(bfloat16 on the chip), every product accumulated in float32, the router's
logits and softmax, attention's scores and softmax and the norms'
statistics in float32; an expert's output is rounded to the activations'
dtype before the weighted sum, which is float32.  With float32 weights
(the CPU tests) every product runs at ``highest``.  The controls keep the
default's structure and plant one fault each: ``"fp8"`` rounds both
operands of every product to float8 (e4m3), the step below; ``"top5"``
drops each row's sixth expert; ``"no_window"`` lets window layers see
the whole context; ``"rope_all"`` rotates the global layers too.  A
control does not decode: at every served position it reads the gap, in
the reference's logits, of the token the faulty computation puts first.
"""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

CONTROLS = ("fp8", "top5", "no_window", "rope_all")
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


def attention(q, k, v, heads, kv_heads, window, variant="default",
              q_block=512):
    """Causal attention of ``(T, heads * d)`` queries over ``(T,
    kv_heads * d)`` keys and values; query head ``h`` reads key head
    ``h // (heads // kv_heads)``; ``window`` 0 sees everything before.
    A block of queries at a time against all keys, masked."""
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
        ok = kj <= qi
        if window > 0:
            ok = jnp.logical_and(ok, qi - kj < window)
        s = _dot(qb, kh, variant, "qkgd,lkd->kgql") * (d ** -0.5)
        a = jax.nn.softmax(jnp.where(ok, s, -1e30), axis=-1)
        o = _dot(a.astype(v.dtype), vh, variant, "kgql,lkd->qkgd")
        return o.reshape(q_block, heads * d).astype(q.dtype)

    out = lax.map(block, (qh, starts))
    return out.reshape(-1, heads * d)[:t]


def route(r, top_k, variant="default"):
    """``(T, experts)`` routing weights from float32 router logits: the
    softmax over each row's ``top_k`` largest, zero elsewhere."""
    top_v, top_i = lax.top_k(r.astype(_F32), top_k)
    w = jax.nn.softmax(top_v, axis=-1)
    if variant == "top5":
        w = w.at[:, -1].set(0.0)
    onehot = jax.nn.one_hot(top_i, r.shape[-1], dtype=_F32)
    return jnp.sum(onehot * w[..., None], axis=1)


def experts(u, weights, wg, wu, wd, variant="default"):
    """``sum_e weights[:, e] * down_e.T (relu(gate_e u) * up_e u)`` over
    the experts given (``wg, wu, wd``: ``(n, width, hidden)``), every
    expert over every row, one expert at a time."""
    def one(acc, ew):
        g, up, dn, w_e = ew
        act = (jax.nn.relu(_dot(u, g, variant)) * _dot(u, up, variant)) \
            .astype(u.dtype)
        y = _dot(act, dn, variant, "...f,fd->...d").astype(u.dtype)
        return acc + w_e[:, None] * y.astype(_F32), None

    acc, _ = lax.scan(one, jnp.zeros(u.shape, _F32),
                      (wg, wu, wd, weights.T))
    return acc.astype(u.dtype)


def hidden(params, cfg, tokens, variant="default"):
    """``(T,)`` token ids -> the final norm's output ``(T, hidden)``."""
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    h = params["emb_weight"][tokens]
    for i in range(cfg["num_hidden_layers"]):
        pre = "l%d_" % i
        rope = bool(cfg["rope_layout"][i]) or variant == "rope_all"
        window = cfg["sliding_window_size"] \
            if cfg["sliding_window_layout"][i] and variant != "no_window" \
            else 0
        r = _dot(h, params[pre + "router_weight"], variant)
        a = rms_norm(h, params[pre + "in_norm_gamma"], eps)
        q = _dot(a, params[pre + "q_weight"], variant).astype(h.dtype)
        k = _dot(a, params[pre + "k_weight"], variant).astype(h.dtype)
        v = _dot(a, params[pre + "v_weight"], variant).astype(h.dtype)
        if rope:
            q = rotate(q, pos, hd, cfg["rope_theta"])
            k = rotate(k, pos, hd, cfg["rope_theta"])
        att = attention(q, k, v, heads, kv, window, variant)
        h = h + _dot(att, params[pre + "o_weight"], variant).astype(h.dtype)
        u = rms_norm(h, params[pre + "post_norm_gamma"], eps)
        w = route(r, cfg["moe_num_active_primary_experts"], variant)
        h = h + experts(u, w, params[pre + "gate_weight"],
                        params[pre + "up_weight"],
                        params[pre + "down_weight"], variant)
    return rms_norm(h, params["final_norm_gamma"], eps)


def logits(params, rows, variant="default"):
    return _dot(rows, params["head_weight"], variant)


def forward(params, cfg, tokens, variant="default"):
    """Logits ``(T, vocab)`` of one sequence: the whole forward pass
    (for the tests, at small sizes)."""
    with _highest(params["emb_weight"].dtype):
        return logits(params, hidden(params, cfg, jnp.asarray(
            tokens, jnp.int32), variant), variant)


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
    hid = jax.jit(lambda p, t, variant: hidden(p, cfg, t, variant),
                  static_argnums=2)

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
            at = len(prompt) - 1 + np.arange(len(served))
            fill = -len(at) % block
            at = np.concatenate([at, np.repeat(at[-1:], fill)])
            chosen = np.concatenate(
                [np.asarray(served, np.int32),
                 np.repeat(np.int32(served[-1]), fill)])
            rows = hid(params, tokens, "default")[at]
            low = hid(params, tokens, precision)[at] \
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

"""Plain reference for ``falcon-h1-34b-4l-bf16``: the decoder's full
forward pass in ``jax.numpy``, one sequence at a time, with no cache, no
conv state, no chunking, no batching, no kernel and nothing of the
program.

The layer, from the published config (TII, Falcon-H1-34B-Instruct,
``config.json``, ``model_type`` ``falcon_h1``); ``h`` is the residual
stream, every norm is ``g * x / sqrt(mean(x^2) + 1e-5)`` and every
multiplier the config's::

    h0   = E[token] * embedding_multiplier
    a    = norm_in(h)
    h'   = h + (ssm_out_multiplier * Mamba2(a * ssm_in_multiplier)
                + attention_out_multiplier * Attn(a * attention_in_multiplier))
    h''  = h' + mlp_multipliers[1] * W_down(silu(mlp_multipliers[0] * W_gate u)
                                           * W_up u),   u = norm_ff(h')
    Attn:   q = W_q a (20 heads of 128), k = W_k a * key_multiplier,
            v = W_v a (4 heads); q, k rotated (half rotation, theta 1e11);
            causal attention, scale 1/sqrt(128); W_o
    Mamba2: [z, x, B, C, dt] = W_in a, times ssm_multipliers block by block
            xBC = silu(conv(concat(x, B, C)) + bias)   4 causal taps a channel
            dt_h = softplus(dt_h + dt_bias_h), A_h = -exp(A_log_h)
            S_t = exp(dt A) S_{t-1} + dt x_t (outer) B_t    head h reads group
            y_t = S_t C_t + D x_t                            h // 16 of B, C
            out = W_out norm_groups(y * silu(z))             2 groups of 2,048

then a final norm and the untied head, times ``lm_head_multiplier``.
What the catalog's config leaves open is listed under ``assumed`` in the
configuration's file.

Precision.  ``"default"`` is what the configuration states, in the
dtype the weights come in: weights and activations in that dtype
(bfloat16 on the chip), every product accumulated in float32 and
rounded once; the norms' statistics, the rotation, attention's scores
and softmax, the conv's sum, bias and silu, and the state space in
float32.  The state space is the per-position recurrence itself, in
float32, its state rounded to the activations' dtype where the program
stores it: at the prompt's last position and after every later one (so
at the prompt's end the program's chunked scan and this recurrence
differ by float32 sums in another order, nothing else).  A multiplier
meets a rounded activation in that activation's dtype, as the program's
scalar multiply does.  One departure from upstream's arithmetic, in the
program too: ``y * silu(z)`` is rounded to the activations' dtype before
the grouped norm, where upstream norms it in float32.  With float32
weights (the CPU tests) every product runs at ``highest``.  The controls
keep the default's structure and plant one fault each: ``"fp8"``
rounds both operands of every product to float8 (e4m3), the step below;
``"ssm_cold"`` decodes from a zero state space state after the prompt;
``"pad_advance"`` hands the decoding positions the state carried on
through the prompt's padding to its bucket's end (the next power of
two, zeros as the engine pads);
``"no_gate"`` norms ``y`` without ``silu(z)``; ``"no_ssm_mup"`` leaves
the five ``ssm_multipliers`` at 1.  A control does not decode: at every
served position it reads the gap, in the reference's logits, of the
token the faulty computation puts first.
"""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

CONTROLS = ("fp8", "ssm_cold", "pad_advance", "no_gate", "no_ssm_mup")
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


def _linear(x, w, variant):
    """``W x`` rounded once to the activations' dtype."""
    return _dot(x, w, variant).astype(x.dtype)


def rms_norm(x, gamma, eps, group=None):
    """Over the last axis or, with ``group``, over each run of that many
    values, under a gain a value."""
    x32 = x.astype(_F32)
    if group:
        x32 = x32.reshape(x.shape[:-1] + (-1, group))
    inv = lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * inv).reshape(x.shape).astype(x.dtype) \
        * gamma.astype(x.dtype)


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


def causal_conv(u, taps, bias):
    """``silu(sum_k taps[k] u_{t - (L-1) + k} + bias)`` of ``(T, d)``
    inputs, a channel at a time, zeros before the start; float32,
    rounded once."""
    t, n_taps = u.shape[0], taps.shape[0]
    y = jnp.zeros(u.shape, _F32)
    for k in range(n_taps):
        back = n_taps - 1 - k
        src = jnp.pad(u.astype(_F32), ((back, 0), (0, 0)))[:t]
        y = y + src * taps[k].astype(_F32)
    return jax.nn.silu(y + bias.astype(_F32)).astype(u.dtype)


def state_space(x, dt, b, c, a_log, dt_bias, d, groups, round_from,
                stop=None, inject=None, inject_at=-1):
    """The selective state space as its recurrence, one position after
    another, in float32, from a zero state.  ``x`` ``(T, heads * P)``,
    ``dt`` ``(T, heads)``, ``B`` and ``C`` ``(T, groups * N)``.  After
    the update at a position ``>= round_from`` the state is rounded to
    ``x``'s dtype.  Positions at or past ``stop`` leave the state as it
    is; entering position ``inject_at`` the state is ``inject`` (the
    controls).  Returns ``y`` ``(T, heads * P)`` in ``x``'s dtype and the
    last state ``(heads, P, N)``."""
    t_len, hp = x.shape
    h = dt.shape[1]
    p, n = hp // h, b.shape[1] // groups
    rate = jax.nn.softplus(dt.astype(_F32) + dt_bias.astype(_F32))
    a = -jnp.exp(a_log.astype(_F32))
    per_head = lambda m: jnp.repeat(                      # noqa: E731
        m.astype(_F32).reshape(t_len, groups, n), h // groups, axis=1)
    stop = t_len if stop is None else stop

    def one(s, ins):
        t, x_t, r_t, b_t, c_t = ins
        if inject is not None:
            s = jnp.where(t == inject_at, inject, s)
        s_next = jnp.exp(r_t * a)[:, None, None] * s \
            + (r_t[:, None] * x_t)[..., None] * b_t[:, None, :]
        y = jnp.einsum("hpn,hn->hp", s_next, c_t) \
            + d.astype(_F32)[:, None] * x_t
        s_next = jnp.where(t >= round_from,
                           s_next.astype(x.dtype).astype(_F32), s_next)
        return jnp.where(t < stop, s_next, s), y

    s, y = lax.scan(one, jnp.zeros((h, p, n), _F32),
                    (jnp.arange(t_len), x.astype(_F32).reshape(t_len, h, p),
                     rate, per_head(b), per_head(c)))
    return y.reshape(t_len, hp).astype(x.dtype), s


def mamba(params, cfg, pre, a, variant, round_from, stop, inject, at):
    """The Mamba-2 mixer on ``a`` (already times ``ssm_in_multiplier``):
    its output before ``ssm_out_multiplier``, and its last state."""
    e, h = cfg["mamba_d_ssm"], cfg["mamba_n_heads"]
    g, n = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    mults = [1.0] * 5 if variant == "no_ssm_mup" else cfg["ssm_multipliers"]
    proj = _linear(a, params[pre + "ssm_in_weight"], variant)
    bounds = np.cumsum([0, e, e, g * n, g * n, h])
    z, xs, b, c, dt = (proj[:, lo:hi] * m for lo, hi, m
                       in zip(bounds, bounds[1:], mults))
    xbc = causal_conv(jnp.concatenate([xs, b, c], axis=1),
                      params[pre + "conv_weight"], params[pre + "conv_bias"])
    y, s = state_space(xbc[:, :e], dt, xbc[:, e:e + g * n],
                       xbc[:, e + g * n:], params[pre + "A_log"],
                       params[pre + "dt_bias"], params[pre + "D"], g,
                       round_from, stop, inject, at)
    if variant != "no_gate":
        y = (y.astype(_F32) * jax.nn.silu(z.astype(_F32))).astype(y.dtype)
    normed = rms_norm(y, params[pre + "ssm_norm_gamma"], cfg["rms_norm_eps"],
                      group=e // g)
    return _linear(normed, params[pre + "ssm_out_weight"], variant), s


def attend(params, cfg, pre, a, pos, variant):
    """The attention branch on ``a`` (already times
    ``attention_in_multiplier``), before ``attention_out_multiplier``."""
    hd = cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    q = _linear(a, params[pre + "q_weight"], variant)
    k = _linear(a, params[pre + "k_weight"], variant) * cfg["key_multiplier"]
    v = _linear(a, params[pre + "v_weight"], variant)
    q, k = (rotate(x, pos, hd, cfg["rope_theta"]) for x in (q, k))
    return _linear(attention(q, k, v, heads, kv, variant),
                   params[pre + "o_weight"], variant)


def mlp(params, cfg, pre, u, variant):
    gate_m, down_m = cfg["mlp_multipliers"]
    g = _linear(u, params[pre + "gate_weight"], variant) * gate_m
    up = _linear(u, params[pre + "up_weight"], variant)
    act = (jax.nn.silu(g.astype(_F32)) * up.astype(_F32)).astype(u.dtype)
    return _linear(act, params[pre + "down_weight"], variant) * down_m


def hidden(params, cfg, tokens, variant="default", plen=1, stop=None,
           inject=None):
    """``(T,)`` token ids -> the final norm's output ``(T, hidden)`` and
    each layer's last state space state.  ``plen`` is the prompt's
    length: the state is rounded from its last position on, and the
    controls' ``inject`` (a state a layer) takes the state's place
    entering position ``plen``.  ``stop`` as ``state_space``."""
    eps = cfg["rms_norm_eps"]
    pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    h = params["emb_weight"][tokens] * cfg["embedding_multiplier"]
    states = []
    for i in range(cfg["num_hidden_layers"]):
        pre = "l%d_" % i
        a = rms_norm(h, params[pre + "in_norm_gamma"], eps)
        m, s = mamba(params, cfg, pre, a * cfg["ssm_in_multiplier"], variant,
                     plen - 1, stop, None if inject is None else inject[i],
                     plen)
        att = attend(params, cfg, pre, a * cfg["attention_in_multiplier"],
                     pos, variant)
        states.append(s)
        h = h + (m * cfg["ssm_out_multiplier"]
                 + att * cfg["attention_out_multiplier"])
        h = h + mlp(params, cfg, pre,
                    rms_norm(h, params[pre + "ff_norm_gamma"], eps), variant)
    return rms_norm(h, params["final_norm_gamma"], eps), states


def logits(params, cfg, rows, variant="default"):
    return _dot(rows, params["head_weight"], variant) \
        * cfg["lm_head_multiplier"]


def bucket_of(plen):
    """The padded length a prompt's prefill dispatch runs at: the next
    power of two (of a traced length too)."""
    if isinstance(plen, int):
        return 1 << max(0, plen - 1).bit_length()
    pows = 2 ** jnp.arange(31, dtype=jnp.int32)
    return jnp.min(jnp.where(pows >= plen, pows, pows[-1]))


def control_hidden(params, cfg, tokens, variant, plen):
    """``hidden`` under ``variant``, the two controls of the state handed
    from the prompt to the decoding positions included."""
    if variant == "ssm_cold":
        zero = jnp.zeros((cfg["mamba_n_heads"], cfg["mamba_d_head"],
                          cfg["mamba_d_state"]), _F32)
        return hidden(params, cfg, tokens, "default", plen,
                      inject=[zero] * cfg["num_hidden_layers"])[0]
    if variant == "pad_advance":
        padded = jnp.where(jnp.arange(tokens.shape[0]) < plen, tokens, 0)
        _h, carried = hidden(params, cfg, padded, "default",
                             bucket_of(plen), stop=bucket_of(plen))
        return hidden(params, cfg, tokens, "default", plen,
                      inject=carried)[0]
    return hidden(params, cfg, tokens, variant, plen)[0]


def forward(params, cfg, tokens, variant="default", plen=None):
    """Logits ``(T, vocab)`` of one sequence, the first ``plen`` of which
    are the prompt (all of them by default): the whole forward pass (for
    the tests, at small sizes)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    plen = tokens.shape[0] if plen is None else plen
    with _highest(params["emb_weight"].dtype):
        return logits(params, cfg, control_hidden(params, cfg, tokens,
                                                  variant, plen), variant)


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
    # wide enough for pad_advance's run of each prompt padded to its
    # bucket
    width = max([width or 0] + [len(s) for s in seqs]
                + [bucket_of(len(p)) for p, _s in requests])
    hid = jax.jit(lambda p, t, n, variant: control_hidden(p, cfg, t, variant,
                                                          n),
                  static_argnums=3)

    @jax.jit
    def gaps_of(p, rows, chosen):
        lg = logits(p, cfg, rows)
        pick = jnp.take_along_axis(lg, chosen[:, None], axis=1)[:, 0]
        return (jnp.max(lg, axis=1) - pick) / jnp.std(lg, axis=1)

    first_of = jax.jit(lambda p, rows: jnp.argmax(
        logits(p, cfg, rows, precision), axis=1).astype(jnp.int32))

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

"""Plain reference for ``biglstm-lm1b-f32`` decoding: the two LSTMP
layers and the full softmax head in ``jax.numpy``, with nothing of the
program.  Gate order i, f, c, o; the recurrent input of a layer is its
projected state r.

``served_gaps`` teacher-forces each sampled request (its prompt, then
the tokens the engine served) from a zero state and reads, at every
served position, how far the served token's logit lies below the best
logit, in units of that position's logit standard deviation.  Greedy
serving puts the best first, so a sound run reads rounding only.

``precision``: ``"default"`` is the reference proper, the precision the
configuration states: float32 weights, state and elementwise maths,
every matrix product at the chip's default precision (operands rounded
to bfloat16 once, accumulated in float32).  ``"bfloat16"`` is the
control, the next precision below: weights, state, activations and every
product in bfloat16.  A control does not decode; at each position of the
same prompts and tokens it reads the gap, in the reference's logits, of
the token the lower precision puts first.
"""
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def _cast(params, dtype):
    return {k: v.astype(dtype) for k, v in params.items()}


def _dot(x, w, prec):
    return jnp.dot(x, w.T, precision=prec)


def _hidden(params, cfg, tokens, prec):
    """tokens (n, T) int32 -> r of the last layer at every position
    (n, T, proj)."""
    n_layers, h = cfg["num_layers"], cfg["lstm_cells"]
    dt = params["emb_weight"].dtype
    n = tokens.shape[0]

    def cell(carry, tok):
        x = params["emb_weight"][tok]
        new = []
        for i in range(n_layers):
            pre = "lstm%d_" % i
            r, c = carry[i]
            gates = (_dot(x, params[pre + "i2h_weight"], prec)
                     + params[pre + "i2h_bias"]
                     + _dot(r, params[pre + "h2h_weight"], prec)
                     + params[pre + "h2h_bias"])
            gi, gf, gc, go = jnp.split(gates, 4, axis=1)
            c2 = jax.nn.sigmoid(gf) * c + jax.nn.sigmoid(gi) * jnp.tanh(gc)
            hid = jax.nn.sigmoid(go) * jnp.tanh(c2)
            x = _dot(hid, params[pre + "proj_weight"], prec)
            new.append((x.astype(dt), c2.astype(dt)))
        return tuple(new), x.astype(dt)

    init = tuple((jnp.zeros((n, cfg["proj_dim"]), dt), jnp.zeros((n, h), dt))
                 for _ in range(n_layers))
    _, rs = lax.scan(cell, init, tokens.T)
    return jnp.swapaxes(rs, 0, 1)


def _head(params, rows, prec):
    return (_dot(rows, params["out_fc_weight"], prec)
            + params["out_fc_bias"]).astype(jnp.float32)


def served_gaps(params, cfg, requests, precision="default", block=128,
                width=None):
    """``requests``: list of (prompt ids, served ids).  Returns
    ``{"gaps": per-token gaps, "tokens": n}``: under ``"default"`` the
    gaps of the served tokens; under ``"bfloat16"`` the gaps, in the
    reference's logits, of the control's own first choice.
    ``width`` pads every sequence to one length and the rows go through
    the head in whole blocks, so one compiled program serves every run."""
    if precision not in ("default", "bfloat16"):
        raise ValueError("unknown precision %r" % (precision,))
    ref = lax.Precision.DEFAULT
    n = len(requests)
    seqs = [list(p) + list(s[:-1]) for p, s in requests]
    width = max([width or 0] + [len(s) for s in seqs])
    tokens = np.zeros((n, width), np.int32)
    for i, s in enumerate(seqs):
        tokens[i, :len(s)] = s
    # the flat (request, position) rows whose output is a served token
    at = [(i, len(p) - 1 + k) for i, (p, s) in enumerate(requests)
          for k in range(len(s))]
    served = np.array([t for _p, s in requests for t in s], np.int32)
    idx = np.array([i * width + j for i, j in at], np.int32)
    n_rows = len(idx)
    fill = -n_rows % block
    idx = np.concatenate([idx, np.repeat(idx[-1:], fill)])
    served = np.concatenate([served, np.repeat(served[-1:], fill)])

    hidden = jax.jit(lambda p, t: _hidden(p, cfg, t, ref))
    r_ref = hidden(params, tokens).reshape(n * width, -1)[idx]
    low = precision == "bfloat16"
    if low:
        p_low = jax.jit(lambda p: _cast(p, jnp.bfloat16))(params)
        r_low = hidden(p_low, tokens).reshape(n * width, -1)[idx]

    @jax.jit
    def gaps_of(p, rows, chosen):
        lg = _head(p, rows, ref)
        pick = jnp.take_along_axis(lg, chosen[:, None], axis=1)[:, 0]
        return (jnp.max(lg, axis=1) - pick) / jnp.std(lg, axis=1)

    @jax.jit
    def first_of(p, rows):
        return jnp.argmax(_head(p, rows, ref), axis=1).astype(jnp.int32)

    out = []
    for lo in range(0, len(idx), block):
        sl = slice(lo, lo + block)
        chosen = jnp.asarray(served[sl])
        if low:
            chosen = first_of(p_low, r_low[sl])
        out.append(np.asarray(gaps_of(params, r_ref[sl], chosen)))
    return {"gaps": np.concatenate(out)[:n_rows], "tokens": int(n_rows)}

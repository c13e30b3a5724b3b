"""Falcon-H1 hybrid decoder (TII, ``model_type`` ``falcon_h1``): every
layer runs a Mamba-2 mixer and grouped-query attention side by side on
the same normed input and adds both to the residual stream, then a
dense SwiGLU.  Every branch is scaled by the config's own multipliers
(µP): the embedding, the key, each branch's input and output, the five
blocks ``[z, x, B, C, dt]`` of the Mamba-2 input projection, the MLP's
gate and down projection, the head.  The head is its own matrix.

The Mamba-2 mixer of layer ``i`` on ``a`` (its input times
``ssm_in_multiplier``)::

    [z, x, B, C, dt] = W_in a, each block times its multiplier
    xBC  = silu(conv1d(concat(x, B, C)) + bias)     causal, depthwise
    y, S = selective state space(x, dt, B, C; A_log, dt_bias, D)
    out  = W_out RMSNorm_groups(y * silu(z))

Built from the keys of the model's ``config.json``: the decode step for
``serving.DecodeEngine`` (``[logits] + next states``) and the prefill
graph for one padded prompt length.  A slot holds, a layer, two
``cache`` states written at ``pos`` by ``_cache_write_row`` and two
plain rows, the convolution's last ``mamba_d_conv - 1`` inputs and the
state space's ``(heads, head size, state size)`` state: the step
replaces them whole, a join zeroes them and a prefill hands them over as
they stand at the row's own prompt length.
"""
from .. import symbol as sym


def _widths(cfg):
    """``(ssm width, heads, head size, groups, state size, conv
    channels, projection width)``."""
    e, h = cfg["mamba_d_ssm"], cfg["mamba_n_heads"]
    g, n = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    conv = e + 2 * g * n
    return e, h, cfg["mamba_d_head"], g, n, conv, e + conv + h


def param_shapes(cfg):
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    hd = cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    e, h, _p, _g, _n, conv, proj = _widths(cfg)
    f = cfg["intermediate_size"]
    shapes = {"emb_weight": (v, d), "head_weight": (v, d),
              "final_norm_gamma": (d,)}
    for i in range(cfg["num_hidden_layers"]):
        pre = "l%d_" % i
        shapes.update({
            pre + "in_norm_gamma": (d,), pre + "ff_norm_gamma": (d,),
            pre + "q_weight": (q, d), pre + "k_weight": (kv, d),
            pre + "v_weight": (kv, d), pre + "o_weight": (d, q),
            pre + "ssm_in_weight": (proj, d),
            pre + "conv_weight": (cfg["mamba_d_conv"], conv),
            pre + "conv_bias": (conv,),
            pre + "A_log": (h,), pre + "dt_bias": (h,), pre + "D": (h,),
            pre + "ssm_norm_gamma": (e,), pre + "ssm_out_weight": (d, e),
            pre + "gate_weight": (f, d), pre + "up_weight": (f, d),
            pre + "down_weight": (d, f)})
    return shapes


def state_info(cfg, max_len):
    """In layer order: the keys' and the values' cache of ``max_len``
    rows, the convolution's row ``(mamba_d_conv - 1, conv channels)``,
    the state space's row ``(heads, head size, state size)``."""
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    _e, h, p, _g, n, conv, _proj = _widths(cfg)
    out = []
    for i in range(cfg["num_hidden_layers"]):
        out.extend({"name": "l%d_%s_cache" % (i, which),
                    "shape": (max_len, kv), "cache": True}
                   for which in "kv")
        out.append({"name": "l%d_conv" % i,
                    "shape": (cfg["mamba_d_conv"] - 1, conv)})
        out.append({"name": "l%d_ssm" % i, "shape": (h, p, n)})
    return out


def _var(name, shapes):
    return sym.Variable(name, shape=shapes[name])


def _fc(x, width, name):
    return sym.FullyConnected(x, num_hidden=width, no_bias=True,
                              flatten=False, name=name)


def _cut(x, begin, end):
    return sym.slice_axis(x, axis=-1, begin=begin, end=end)


def _mamba(cfg, shapes, i, a, conv, ssd):
    """The Mamba-2 mixer of layer ``i`` on ``a``, ``ssm_out_multiplier``
    included.  ``conv(i, xbc, weight, bias)`` and ``ssd(i, x, dt, B, C,
    A_log, dt_bias, D)`` are the step's or the prompt's form of the two
    operators."""
    pre = "l%d_" % i
    e, _h, _p, g, n, _conv, proj_w = _widths(cfg)
    m = cfg["ssm_multipliers"]
    proj = _fc(a * cfg["ssm_in_multiplier"], proj_w, pre + "ssm_in")
    bounds = [0, e, 2 * e, 2 * e + g * n, 2 * e + 2 * g * n, proj_w]
    z, xs, b, c, dt = (_cut(proj, lo, hi) * mult for lo, hi, mult
                       in zip(bounds, bounds[1:], m))
    xbc = conv(i, sym.Concat(xs, b, c, dim=-1),
               _var(pre + "conv_weight", shapes),
               _var(pre + "conv_bias", shapes))
    y = ssd(i, _cut(xbc, 0, e), dt, _cut(xbc, e, e + g * n),
            _cut(xbc, e + g * n, e + 2 * g * n),
            *[_var(pre + k, shapes) for k in ("A_log", "dt_bias", "D")])
    gated = sym._gated_act(z, y, activation="silu", name=pre + "ssm_gate")
    normed = sym.RMSNorm(gated, _var(pre + "ssm_norm_gamma", shapes),
                         eps=cfg["rms_norm_eps"], head_dim=e // g,
                         name=pre + "ssm_norm")
    return _fc(normed, cfg["hidden_size"], pre + "ssm_out") \
        * cfg["ssm_out_multiplier"]


def _attention(cfg, shapes, i, a, pos, attend):
    """The attention branch of layer ``i`` on ``a``, its output
    multiplier included; ``attend(i, q, k, v)`` is the step's or the
    prompt's attention."""
    pre = "l%d_" % i
    hd = cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    a = a * cfg["attention_in_multiplier"]
    q = _fc(a, nq * hd, pre + "q")
    k = _fc(a, nkv * hd, pre + "k") * cfg["key_multiplier"]
    v = _fc(a, nkv * hd, pre + "v")
    q, k = (sym._rotary(x, pos, head_dim=hd, theta=float(cfg["rope_theta"]),
                        name=pre + w + "_rot")
            for w, x in (("q", q), ("k", k)))
    return _fc(attend(i, q, k, v), cfg["hidden_size"], pre + "o") \
        * cfg["attention_out_multiplier"]


def _block(cfg, shapes, i, h, pos, attend, conv, ssd):
    """One layer on ``h``: both mixers on the one normed input, their sum
    added to the stream, then the MLP."""
    pre = "l%d_" % i
    eps, f = cfg["rms_norm_eps"], cfg["intermediate_size"]
    a = sym.RMSNorm(h, _var(pre + "in_norm_gamma", shapes), eps=eps,
                    name=pre + "in_norm")
    # attention first: its caches come before the mixer's rows in
    # state order
    att = _attention(cfg, shapes, i, a, pos, attend)
    h = h + (_mamba(cfg, shapes, i, a, conv, ssd) + att)
    u = sym.RMSNorm(h, _var(pre + "ff_norm_gamma", shapes), eps=eps,
                    name=pre + "ff_norm")
    gate_m, down_m = cfg["mlp_multipliers"]
    act = sym._gated_act(_fc(u, f, pre + "gate") * gate_m,
                         _fc(u, f, pre + "up"), activation="silu",
                         name=pre + "act")
    return h + _fc(act, cfg["hidden_size"], pre + "down") * down_m


def _embed(cfg, shapes, ids):
    return sym.Embedding(ids, _var("emb_weight", shapes),
                         input_dim=cfg["vocab_size"],
                         output_dim=cfg["hidden_size"], name="emb") \
        * cfg["embedding_multiplier"]


def _head(cfg, shapes, h):
    h = sym.RMSNorm(h, _var("final_norm_gamma", shapes),
                    eps=cfg["rms_norm_eps"], name="final_norm")
    return sym._dense(h, _var("head_weight", shapes),
                      num_hidden=cfg["vocab_size"], name="head") \
        * cfg["lm_head_multiplier"]


def _mixers(cfg, conv_op, ssd_op, states_out, rows_in, **ssd_attrs):
    """The two Mamba-2 operators in the form ``conv_op`` and ``ssd_op``
    give them; each appends its state to ``states_out`` in state order.
    ``rows_in(i, kind)`` is the input each takes after its data: the
    step's state, or the prompt's lengths."""
    def conv(i, xbc, weight, bias):
        out = conv_op(xbc, rows_in(i, "conv"), weight, bias,
                      taps=cfg["mamba_d_conv"], gated=False,
                      activation="silu", has_bias=True,
                      name="l%d_xbc_conv" % i)
        states_out.append(out[1])
        return out[0]

    def ssd(i, x, dt, b, c, *head_params):
        out = ssd_op(x, dt, b, c, rows_in(i, "ssm"), *head_params,
                     num_groups=cfg["mamba_n_groups"], name="l%d_ssd" % i,
                     **ssd_attrs)
        states_out.append(out[1])
        return out[0]
    return conv, ssd


def decode_step(cfg, max_len):
    """``(step symbol, state_info)``: outputs ``[logits] + next
    states``."""
    shapes = param_shapes(cfg)
    info = state_info(cfg, max_len)
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    pos = sym.Variable("pos")
    states_out = []

    def attend(i, q, k, v):
        caches = [sym._cache_write_row(
            sym.Variable("l%d_%s_cache" % (i, which)), row, pos,
            name="l%d_%s_write" % (i, which))
            for which, row in (("k", k), ("v", v))]
        states_out.extend(caches)
        return sym._gqa_decode(q, caches[0], caches[1], pos, num_heads=nq,
                               num_kv_heads=nkv, name="l%d_attn" % i)

    conv, ssd = _mixers(cfg, sym._short_conv_step, sym._ssd_step,
                        states_out,
                        lambda i, kind: sym.Variable("l%d_%s" % (i, kind)))
    h = _embed(cfg, shapes, sym.Variable("token"))
    for i in range(cfg["num_hidden_layers"]):
        h = _block(cfg, shapes, i, h, pos, attend, conv, ssd)
    return sym.Group([_head(cfg, shapes, h)] + states_out), info


def prefill(cfg, attn_block=512):
    """``T -> Symbol`` over ``prompt`` ``(batch, T)`` and ``plen``
    ``(batch,)``: outputs the logits at each row's last live position
    and, in state order, a layer's keys and values ``(batch, T, kv_heads
    * head_dim)``, its convolution row and its state space's state as
    they stand after ``plen`` positions.  ``attn_block`` is the
    blockwise attention's tile (the tests pass a small one)."""
    shapes = param_shapes(cfg)
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]

    def build(t):
        pos = sym.Reshape(sym._arange(start=0, stop=t), shape=(1, t))
        plen = sym.Variable("plen")
        rows = []

        def attend(i, q, k, v):
            rows.extend([k, v])
            return sym._gqa_prefill(q, k, v, num_heads=nq, num_kv_heads=nkv,
                                    block=attn_block, name="l%d_attn" % i)

        conv, ssd = _mixers(cfg, sym._short_conv_seq, sym._ssd_scan, rows,
                            lambda i, kind: plen,
                            chunk=cfg["mamba_chunk_size"])
        h = _embed(cfg, shapes, sym.Variable("prompt"))
        for i in range(cfg["num_hidden_layers"]):
            h = _block(cfg, shapes, i, h, pos, attend, conv, ssd)
        last = sym.SequenceLast(h, plen, use_sequence_length=True, axis=1)
        return sym.Group([_head(cfg, shapes, last)] + rows)
    return build

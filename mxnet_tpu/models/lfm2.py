"""LFM2 sparse decoder (Liquid AI, ``model_type`` ``lfm2_moe``): gated
short-convolution layers, whose state a slot is the last
``conv_L_cache - 1`` rows of the gated input, mixed with grouped-query
attention layers (query and key heads normed, rotary, a cache row a
position); a dense SwiGLU in the first ``num_dense_layers`` layers and
after them SwiGLU experts scored by a sigmoid and chosen under a bias an
expert.  The head is the embedding matrix.

Built from the keys of the model's ``config.json``: the decode step for
``serving.DecodeEngine`` (``[logits] + next states + [expert_load]``)
and the prefill graph for one padded prompt length.  A slot holds two
kinds of state side by side: two ``cache`` states an attention layer,
written at ``pos`` by ``_cache_write_row``, and one plain row state a
conv layer, which the step replaces whole, a join zeroes and a prefill
hands over as it stands at the row's own prompt length.
"""
from .. import symbol as sym


def _kinds(cfg):
    """``layer_types`` of the layers kept."""
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def _head_dim(cfg):
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def _is_dense(cfg, i):
    return i < cfg["num_dense_layers"]


def param_shapes(cfg, first_expert=0, num_held=0):
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    hd = _head_dim(cfg)
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    n_exp = cfg["num_experts"]
    held = num_held or n_exp - first_expert
    shapes = {"emb_weight": (v, d), "final_norm_gamma": (d,)}
    for i, kind in enumerate(_kinds(cfg)):
        pre = "l%d_" % i
        shapes.update({pre + "op_norm_gamma": (d,),
                       pre + "ffn_norm_gamma": (d,)})
        if kind == "conv":
            shapes.update({pre + "in_weight": (3 * d, d),
                           pre + "conv_weight": (cfg["conv_L_cache"], d),
                           pre + "out_weight": (d, d)})
        else:
            shapes.update({pre + "q_weight": (q, d),
                           pre + "k_weight": (kv, d),
                           pre + "v_weight": (kv, d),
                           pre + "o_weight": (d, q),
                           pre + "q_norm_gamma": (hd,),
                           pre + "k_norm_gamma": (hd,)})
        if _is_dense(cfg, i):
            f = cfg["intermediate_size"]
            shapes.update({pre + "gate_weight": (f, d),
                           pre + "up_weight": (f, d),
                           pre + "down_weight": (d, f)})
        else:
            f = cfg["moe_intermediate_size"]
            shapes.update({pre + "router_weight": (n_exp, d),
                           pre + "gate_weight": (held, f, d),
                           pre + "up_weight": (held, f, d),
                           pre + "down_weight": (held, f, d)})
            if cfg["use_expert_bias"]:
                shapes[pre + "expert_bias"] = (n_exp,)
    return shapes


def state_info(cfg, max_len):
    """In layer order: two cache states of ``max_len`` rows an attention
    layer, one plain row state ``(conv_L_cache - 1, hidden)`` a conv
    layer."""
    kv = cfg["num_key_value_heads"] * _head_dim(cfg)
    out = []
    for i, kind in enumerate(_kinds(cfg)):
        if kind == "conv":
            out.append({"name": "l%d_conv" % i,
                        "shape": (cfg["conv_L_cache"] - 1,
                                  cfg["hidden_size"])})
        else:
            out.extend({"name": "l%d_%s_cache" % (i, which),
                        "shape": (max_len, kv), "cache": True}
                       for which in "kv")
    return out


def _var(name, shapes):
    return sym.Variable(name, shape=shapes[name])


def _fc(x, width, name):
    return sym.FullyConnected(x, num_hidden=width, no_bias=True,
                              flatten=False, name=name)


def _ffn(cfg, shapes, i, u, moe_attrs):
    """The layer's second half on the normed ``u``: ``(output, routing
    weights or None)``."""
    pre = "l%d_" % i
    if _is_dense(cfg, i):
        f = cfg["intermediate_size"]
        act = sym._gated_act(_fc(u, f, pre + "gate"), _fc(u, f, pre + "up"),
                             activation="silu", name=pre + "act")
        return _fc(act, cfg["hidden_size"], pre + "down"), None
    r = sym._dense(u, _var(pre + "router_weight", shapes),
                   num_hidden=cfg["num_experts"], name=pre + "router")
    ins = [u, r] + [_var(pre + n, shapes)
                    for n in ("gate_weight", "up_weight", "down_weight")]
    if cfg["use_expert_bias"]:
        ins.append(_var(pre + "expert_bias", shapes))
    out = sym._moe_experts(
        *ins, top_k=cfg["num_experts_per_tok"],
        expert_width=cfg["moe_intermediate_size"], routing="sigmoid",
        activation="silu", expert_bias=bool(cfg["use_expert_bias"]),
        norm_topk=bool(cfg["norm_topk_prob"]),
        route_scale=float(cfg["routed_scaling_factor"]),
        name=pre + "experts", **moe_attrs)
    return out[0], out[1]


def _block(cfg, shapes, i, h, pos, attend, conv, moe_attrs):
    """One layer on ``h``.  ``attend(i, q, k, v)`` and ``conv(i, proj,
    weight)`` are the step's or the prompt's form of the two operators.
    Returns the layer's output and its routing weights (None on a dense
    layer)."""
    pre = "l%d_" % i
    eps, d = cfg["norm_eps"], cfg["hidden_size"]
    a = sym.RMSNorm(h, _var(pre + "op_norm_gamma", shapes), eps=eps,
                    name=pre + "op_norm")
    if _kinds(cfg)[i] == "conv":
        gated = conv(i, _fc(a, 3 * d, pre + "in"),
                     _var(pre + "conv_weight", shapes))
        h = h + _fc(gated, d, pre + "out")
    else:
        hd = _head_dim(cfg)
        nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        proj = {which: _fc(a, n * hd, pre + which)
                for which, n in (("q", nq), ("k", nkv), ("v", nkv))}
        for which in "qk":
            normed = sym.RMSNorm(
                proj[which], _var(pre + which + "_norm_gamma", shapes),
                eps=eps, head_dim=hd, name=pre + which + "_norm")
            proj[which] = sym._rotary(
                normed, pos, head_dim=hd, theta=float(cfg["rope_theta"]),
                name=pre + which + "_rot")
        h = h + _fc(attend(i, proj["q"], proj["k"], proj["v"]), d,
                    pre + "o")
    u = sym.RMSNorm(h, _var(pre + "ffn_norm_gamma", shapes), eps=eps,
                    name=pre + "ffn_norm")
    out, route = _ffn(cfg, shapes, i, u, moe_attrs)
    return h + out, route


def _embed(cfg, table, ids):
    return sym.Embedding(ids, table, input_dim=cfg["vocab_size"],
                         output_dim=cfg["hidden_size"], name="emb")


def _head(cfg, shapes, table, h):
    """The final norm, then the head: the embedding matrix ``table``,
    the one variable node the embedding reads."""
    h = sym.RMSNorm(h, _var("final_norm_gamma", shapes),
                    eps=cfg["norm_eps"], name="final_norm")
    return sym._dense(h, table, num_hidden=cfg["vocab_size"], name="head")


def decode_step(cfg, max_len, first_expert=0, num_held=0):
    """``(step symbol, state_info)``: outputs ``[logits] + next states +
    [expert_load]``, the last a ``(expert layers, experts)`` count of
    the live rows each expert got this step."""
    shapes = param_shapes(cfg, first_expert, num_held)
    info = state_info(cfg, max_len)
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    pos, valid = sym.Variable("pos"), sym.Variable("valid")
    states_out = []

    def attend(i, q, k, v):
        caches = [sym._cache_write_row(
            sym.Variable("l%d_%s_cache" % (i, which)), row, pos,
            name="l%d_%s_write" % (i, which))
            for which, row in (("k", k), ("v", v))]
        states_out.extend(caches)
        return sym._gqa_decode(q, caches[0], caches[1], pos, num_heads=nq,
                               num_kv_heads=nkv, name="l%d_attn" % i)

    def conv(i, proj, weight):
        out = sym._short_conv_step(
            proj, sym.Variable("l%d_conv" % i), weight,
            taps=cfg["conv_L_cache"], name="l%d_conv_step" % i)
        states_out.append(out[1])
        return out[0]

    table = _var("emb_weight", shapes)
    h = _embed(cfg, table, sym.Variable("token"))
    loads = []
    moe_attrs = {"first_expert": first_expert, "num_held": num_held}
    for i in range(cfg["num_hidden_layers"]):
        h, route = _block(cfg, shapes, i, h, pos, attend, conv, moe_attrs)
        if route is not None:
            chosen = sym.broadcast_mul(route > 0.0,
                                       sym.expand_dims(valid, axis=1))
            loads.append(sym.sum(chosen, axis=0))
    load = sym.stack(*loads, axis=0, name="expert_load")
    return sym.Group([_head(cfg, shapes, table, h)] + states_out
                     + [load]), info


def prefill(cfg, first_expert=0, num_held=0, moe_block=256, attn_block=512):
    """``T -> Symbol`` over ``prompt`` ``(batch, T)`` and ``plen``
    ``(batch,)``: outputs the logits at each row's last live position
    and, in state order, an attention layer's keys and values ``(batch,
    T, kv_heads * head_dim)`` and a conv layer's row ``(batch,
    conv_L_cache - 1, hidden)`` as it stands after ``plen`` positions.
    ``moe_block`` and ``attn_block`` are the tiles of the two blockwise
    ops (the tests pass small ones)."""
    shapes = param_shapes(cfg, first_expert, num_held)
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    moe_attrs = {"first_expert": first_expert, "num_held": num_held,
                 "block": moe_block}

    def build(t):
        pos = sym.Reshape(sym._arange(start=0, stop=t), shape=(1, t))
        plen = sym.Variable("plen")
        rows = []

        def attend(i, q, k, v):
            rows.extend([k, v])
            return sym._gqa_prefill(q, k, v, num_heads=nq, num_kv_heads=nkv,
                                    block=attn_block, name="l%d_attn" % i)

        def conv(i, proj, weight):
            out = sym._short_conv_seq(proj, plen, weight,
                                      taps=cfg["conv_L_cache"],
                                      name="l%d_conv_seq" % i)
            rows.append(out[1])
            return out[0]

        table = _var("emb_weight", shapes)
        h = _embed(cfg, table, sym.Variable("prompt"))
        for i in range(cfg["num_hidden_layers"]):
            h, _route = _block(cfg, shapes, i, h, pos, attend, conv,
                               moe_attrs)
        last = sym.SequenceLast(h, plen, use_sequence_length=True, axis=1)
        return sym.Group([_head(cfg, shapes, table, last)] + rows)
    return build

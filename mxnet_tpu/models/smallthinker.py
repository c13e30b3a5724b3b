"""SmallThinker decoder (PowerInfer, 2025): sparse ReGLU experts behind
a router that reads the layer's input before attention, and window
layers (rotary, a ring of ``sliding_window_size`` cache rows) mixed with
global layers (no positional encoding, a cache row a position).

Built from the keys of the model's ``config.json``: the decode step for
``serving.DecodeEngine`` (``[logits, expert_load] + next states``) and
the prefill graph for one padded prompt length.  The step writes each
layer's key and value straight into its cache state with
``_cache_write_row``, at ``pos mod window`` on window layers.
"""
from .. import symbol as sym


def _layer_kinds(cfg):
    """(rotates, window) of each layer kept."""
    n = cfg["num_hidden_layers"]
    return [(bool(cfg["rope_layout"][i]),
             cfg["sliding_window_size"]
             if cfg["sliding_window_layout"][i] else 0) for i in range(n)]


def param_shapes(cfg, first_expert=0, num_held=0):
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    hd = cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    held = num_held or cfg["moe_num_primary_experts"] - first_expert
    width = cfg["moe_ffn_hidden_size"]
    shapes = {"emb_weight": (v, d), "final_norm_gamma": (d,),
              "head_weight": (v, d)}
    for i in range(cfg["num_hidden_layers"]):
        pre = "l%d_" % i
        shapes.update({
            pre + "router_weight": (cfg["moe_num_primary_experts"], d),
            pre + "in_norm_gamma": (d,), pre + "post_norm_gamma": (d,),
            pre + "q_weight": (q, d), pre + "k_weight": (kv, d),
            pre + "v_weight": (kv, d), pre + "o_weight": (d, q),
            pre + "gate_weight": (held, width, d),
            pre + "up_weight": (held, width, d),
            pre + "down_weight": (held, width, d)})
    return shapes


def state_info(cfg, max_len):
    """Two cache states a layer, each with the rows its kind needs:
    ``sliding_window_size`` on a window layer, ``max_len`` on a global
    one."""
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    out = []
    for i, (_rope, window) in enumerate(_layer_kinds(cfg)):
        rows = min(window, max_len) if window else max_len
        for which in "kv":
            info = {"name": "l%d_%s" % (i, which), "shape": (rows, kv),
                    "cache": True}
            if window:
                info["window"] = rows
            out.append(info)
    return out


def _var(name, shapes):
    return sym.Variable(name, shape=shapes[name])


def _block(cfg, shapes, i, h, pos, attend, moe_attrs):
    """One layer on ``h``; ``attend(q, k, v, window)`` is the kind of
    attention (a step against the cache, or a whole prompt).  Returns
    the layer's output and its routing weights."""
    pre = "l%d_" % i
    rope, window = _layer_kinds(cfg)[i]
    hd = cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    r = sym._dense(h, _var(pre + "router_weight", shapes),
                   num_hidden=cfg["moe_num_primary_experts"],
                   name=pre + "router")
    a = sym.RMSNorm(h, _var(pre + "in_norm_gamma", shapes),
                    eps=cfg["rms_norm_eps"], name=pre + "in_norm")
    proj = {}
    for which, width in (("q", nq * hd), ("k", nkv * hd), ("v", nkv * hd)):
        proj[which] = sym.FullyConnected(
            a, num_hidden=width, no_bias=True, flatten=False,
            name=pre + which)
    if rope:
        for which in "qk":
            proj[which] = sym._rotary(
                proj[which], pos, head_dim=hd, theta=cfg["rope_theta"],
                name=pre + which + "_rot")
    att = attend(i, proj["q"], proj["k"], proj["v"], window)
    h = h + sym.FullyConnected(att, num_hidden=cfg["hidden_size"],
                               no_bias=True, flatten=False, name=pre + "o")
    u = sym.RMSNorm(h, _var(pre + "post_norm_gamma", shapes),
                    eps=cfg["rms_norm_eps"], name=pre + "post_norm")
    out = sym._moe_experts(
        u, r, _var(pre + "gate_weight", shapes),
        _var(pre + "up_weight", shapes), _var(pre + "down_weight", shapes),
        top_k=cfg["moe_num_active_primary_experts"],
        expert_width=cfg["moe_ffn_hidden_size"], name=pre + "experts",
        **moe_attrs)
    return h + out[0], out[1]


def _head(cfg, shapes, h):
    h = sym.RMSNorm(h, _var("final_norm_gamma", shapes),
                    eps=cfg["rms_norm_eps"], name="final_norm")
    return sym._dense(h, _var("head_weight", shapes),
                      num_hidden=cfg["vocab_size"], name="head")


def decode_step(cfg, max_len, first_expert=0, num_held=0):
    """``(step symbol, state_info)``: outputs ``[logits] + next states +
    [expert_load]``, the last a ``(layers, experts)`` count of the live
    rows each expert got this step."""
    shapes = param_shapes(cfg, first_expert, num_held)
    info = state_info(cfg, max_len)
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    pos, valid = sym.Variable("pos"), sym.Variable("valid")
    states_out = []

    def attend(i, q, k, v, window):
        at = pos
        if window:
            at = sym._mod_scalar(pos, scalar=float(info[2 * i]["shape"][0]))
        caches = []
        for which, row in (("k", k), ("v", v)):
            caches.append(sym._cache_write_row(
                sym.Variable("l%d_%s" % (i, which)), row, at,
                name="l%d_%s_write" % (i, which)))
        states_out.extend(caches)
        return sym._gqa_decode(q, caches[0], caches[1], pos, num_heads=nq,
                               num_kv_heads=nkv, window=window,
                               name="l%d_attn" % i)

    h = sym.Embedding(sym.Variable("token"), input_dim=cfg["vocab_size"],
                      output_dim=cfg["hidden_size"], name="emb")
    loads = []
    moe_attrs = {"first_expert": first_expert, "num_held": num_held}
    for i in range(cfg["num_hidden_layers"]):
        h, route = _block(cfg, shapes, i, h, pos, attend, moe_attrs)
        chosen = sym.broadcast_mul(route > 0.0,
                                   sym.expand_dims(valid, axis=1))
        loads.append(sym.sum(chosen, axis=0))
    load = sym.stack(*loads, axis=0, name="expert_load")
    return sym.Group([_head(cfg, shapes, h)] + states_out + [load]), info


def prefill(cfg, first_expert=0, num_held=0, moe_block=256, attn_block=512):
    """``T -> Symbol`` over ``prompt`` ``(batch, T)`` and ``plen``
    ``(batch,)``: outputs the logits at each row's last live position
    and every layer's keys and values ``(batch, T, kv_heads * head_dim)``
    in state order, for the engine to lay into the cache states.
    ``moe_block`` and ``attn_block`` are the tiles of the two blockwise
    ops (the tests pass small ones, so that a prompt of a few dozen
    positions runs through several)."""
    shapes = param_shapes(cfg, first_expert, num_held)
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    moe_attrs = {"first_expert": first_expert, "num_held": num_held,
                 "block": moe_block}

    def build(t):
        pos = sym.Reshape(sym._arange(start=0, stop=t), shape=(1, t))
        rows = []

        def attend(i, q, k, v, window):
            rows.extend([k, v])
            return sym._gqa_prefill(q, k, v, num_heads=nq, num_kv_heads=nkv,
                                    window=window, block=attn_block,
                                    name="l%d_attn" % i)

        h = sym.Embedding(sym.Variable("prompt"),
                          input_dim=cfg["vocab_size"],
                          output_dim=cfg["hidden_size"], name="emb")
        for i in range(cfg["num_hidden_layers"]):
            h, _route = _block(cfg, shapes, i, h, pos, attend, moe_attrs)
        last = sym.SequenceLast(h, sym.Variable("plen"),
                                use_sequence_length=True, axis=1)
        return sym.Group([_head(cfg, shapes, last)] + rows)
    return build

"""LFM2-8B-A1B cut to fourteen layers: the step and prefill graphs for
``serving.DecodeEngine`` (``mxnet_tpu/models/lfm2.py`` builds them from
the config's own keys), the weights from a seed, and what a decode step
and a prefill dispatch require of the chip.

Only ``build_step`` and ``build_prefill`` touch the program; the rest is
shapes and ``jax``.  The short convolution's two forms are XLA
formulations (three shifted multiply-adds), not kernels, so there is no
roofline count for them here.
"""
import math

ITEM = 2                   # bytes a bfloat16 weight, state or activation


def _kinds(cfg):
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def _widths(cfg):
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    return cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd


def param_shapes(cfg):
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    q, kv = _widths(cfg)
    hd = q // cfg["num_attention_heads"]
    e, f = cfg["num_experts"], cfg["moe_intermediate_size"]
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
            shapes.update({pre + "q_weight": (q, d), pre + "k_weight": (kv, d),
                           pre + "v_weight": (kv, d), pre + "o_weight": (d, q),
                           pre + "q_norm_gamma": (hd,),
                           pre + "k_norm_gamma": (hd,)})
        if i < cfg["num_dense_layers"]:
            w = cfg["intermediate_size"]
            shapes.update({pre + "gate_weight": (w, d),
                           pre + "up_weight": (w, d),
                           pre + "down_weight": (d, w)})
        else:
            shapes.update({pre + "router_weight": (e, d),
                           pre + "expert_bias": (e,),
                           pre + "gate_weight": (e, f, d),
                           pre + "up_weight": (e, f, d),
                           pre + "down_weight": (e, f, d)})
    return shapes


def param_count(cfg):
    return sum(math.prod(s) for s in param_shapes(cfg).values())


def build_step(cfg, max_len=1280):
    """token, pos, valid + two cache states an attention layer and one
    row state a conv layer -> [logits] + next states + [expert_load]."""
    from mxnet_tpu.models import lfm2
    return lfm2.decode_step(cfg, max_len)


def build_prefill(cfg):
    """``T -> Symbol``: a padded prompt in one dispatch."""
    from mxnet_tpu.models import lfm2
    return lfm2.prefill(cfg)


def init_params(cfg, seed):
    """Every weight on the default device in the configuration's dtype
    (the expert bias in float32), one jitted call a distinct shape."""
    import jax
    import jax.numpy as jnp
    dtype = jnp.dtype(cfg["dtype"])
    key = jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)),
                             seed // (2 ** 31))

    def make(k, shape, scale, dt):
        return (scale * jax.random.normal(k, shape, jnp.float32)).astype(dt)
    make = jax.jit(make, static_argnums=(1, 2, 3))
    out = {}
    for n, (name, shape) in enumerate(sorted(param_shapes(cfg).items())):
        k = jax.random.fold_in(key, n)
        if name.endswith(("q_norm_gamma", "k_norm_gamma")):
            # at gains of 1 over unit-variance projections a head norm
            # is all but the identity, and leaving it out reads as a
            # sound run does (PERF.md section 2)
            out[name] = (1.0 + make(k, shape, 0.5, jnp.dtype(jnp.float32))) \
                .astype(dtype)
        elif name.endswith("_gamma"):
            out[name] = jnp.ones(shape, dtype)
        elif name.endswith("expert_bias"):
            out[name] = make(k, shape, 0.1, jnp.dtype(jnp.float32))
        elif name.endswith("conv_weight"):
            out[name] = make(k, shape, 1.0 / math.sqrt(shape[0]), dtype)
        else:
            # (out, in) matrices, the tied embedding among them (as the
            # head its fan-in is the hidden size: at unit variance its
            # own row would stand 8 standard deviations over every other
            # logit and the model would echo its input); an expert's
            # down projection is stored (experts, width, hidden) and
            # sums over its width
            fan = shape[1] if len(shape) == 3 and "down" in name \
                else shape[-1]
            out[name] = make(k, shape, 1.0 / math.sqrt(fan), dtype)
    return out


def _per_layer(cfg):
    """Parameters a row is multiplied by: an attention operator, a conv
    operator, the dense SwiGLU, a router, one expert."""
    d = cfg["hidden_size"]
    q, kv = _widths(cfg)
    return {"attn": d * (2 * q + 2 * kv), "conv": d * 3 * d + d * d,
            "dense": 3 * d * cfg["intermediate_size"],
            "router": cfg["num_experts"] * d,
            "expert": 3 * d * cfg["moe_intermediate_size"]}


def _counts(cfg):
    kinds = _kinds(cfg)
    n_attn = sum(1 for k in kinds if k != "conv")
    n_dense = min(cfg["num_dense_layers"], len(kinds))
    return n_attn, len(kinds) - n_attn, n_dense, len(kinds) - n_dense


def _row_params(cfg):
    """Matrix parameters every row is multiplied by, the experts and
    the head apart: operators, dense layers, routers."""
    n_attn, n_conv, n_dense, n_moe = _counts(cfg)
    per = _per_layer(cfg)
    return n_attn * per["attn"] + n_conv * per["conv"] \
        + n_dense * per["dense"] + n_moe * per["router"]


def _small(cfg):
    """Parameters outside the matrices: two norm gains a layer, the two
    head norms of an attention layer, a conv layer's taps, an expert
    layer's bias (float32: two items), the final norm."""
    d = cfg["hidden_size"]
    n_attn, n_conv, _n_dense, n_moe = _counts(cfg)
    hd = d // cfg["num_attention_heads"]
    return (n_attn + n_conv) * 2 * d + n_attn * 2 * hd \
        + n_conv * cfg["conv_L_cache"] * d \
        + n_moe * 2 * cfg["num_experts"] + d


def rows_read(cfg, context):
    """Cache rows (keys and values counted apart) a slot whose context
    holds ``context`` positions must read in a step: all of them on each
    attention layer; a conv layer has no cache."""
    return 2 * context * _counts(cfg)[0]


def step_required(cfg, slots, contexts):
    """FLOPs and HBM bytes one decode step needs when the live slots
    hold ``contexts`` positions each (the one being written included):
    every operator's, the dense layers', the routers' and the tied
    head's weights once, the weights of the experts that can be hit
    (``top_k`` a live row, at most all), the live rows' embedding rows,
    the cache rows a live slot must read on the attention layers
    (``rows_read``) and the one it writes, and a live slot's conv rows
    read and written.  FLOPs count live rows only, ``top_k`` experts a
    row.  ``cache_rows`` is the rows read."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    q, kv = _widths(cfg)
    k, n_exp = cfg["num_experts_per_tok"], cfg["num_experts"]
    n_attn, n_conv, _n_dense, n_moe = _counts(cfg)
    expert, row = _per_layer(cfg)["expert"], _row_params(cfg)
    live = len(contexts)
    hit = min(n_exp, k * live)
    weights = row + n_moe * hit * expert + _small(cfg) + v * d
    rows = sum(rows_read(cfg, int(c)) for c in contexts)
    conv_rows = 2 * n_conv * live * (cfg["conv_L_cache"] - 1) * d
    nbytes = ITEM * (weights + live * d + rows * kv
                     + 2 * n_attn * live * kv + conv_rows)
    flops = live * 2.0 * (row + n_moe * k * expert + v * d) \
        + 2.0 * q * rows            # scores and weighted sum: 4 a pair
    return {"flops": flops, "bytes": float(nbytes), "cache_rows": rows}


def pairs_seen(cfg, plen):
    """(query, key) pairs a prompt of ``plen`` positions attends over,
    summed over the attention layers: causal, no window."""
    return _counts(cfg)[0] * plen * (plen + 1) // 2


def prefill_required(cfg, prompt_lens):
    """FLOPs and HBM bytes one prefill dispatch needs for prompts of
    ``prompt_lens`` live positions: every live position through the
    operators, the dense layers, the routers and ``top_k`` experts;
    attention over the pairs it may see; the head once a prompt.
    Padding is not required work.  Bytes: every weight once (the tied
    matrix once), the live positions' embedding rows, the keys and
    values and the conv rows written."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    q, kv = _widths(cfg)
    k, n_exp = cfg["num_experts_per_tok"], cfg["num_experts"]
    n_attn, n_conv, _n_dense, n_moe = _counts(cfg)
    expert, row = _per_layer(cfg)["expert"], _row_params(cfg)
    tokens = sum(prompt_lens)
    flops = tokens * 2.0 * (row + n_moe * k * expert) \
        + sum(4.0 * q * pairs_seen(cfg, int(p)) for p in prompt_lens) \
        + len(prompt_lens) * 2.0 * v * d
    weights = row + n_moe * n_exp * expert + _small(cfg) + v * d
    conv_rows = n_conv * len(prompt_lens) * (cfg["conv_L_cache"] - 1) * d
    nbytes = ITEM * (weights + tokens * d + 2 * n_attn * tokens * kv
                     + conv_rows)
    return {"flops": flops, "bytes": float(nbytes)}

"""SmallThinker-21BA3B-Instruct cut to eight layers: the step and prefill
graphs for ``serving.DecodeEngine`` (``mxnet_tpu/models/smallthinker.py``
builds them from the config's own keys), the weights from a seed, and
what a decode step and a prefill dispatch require of the chip.

Only ``build_step`` and ``build_prefill`` touch the program; the rest is
shapes and ``jax``.
"""
import math

ITEM = 2                   # bytes a bfloat16 weight, cache or activation


def _layers(cfg):
    """The window of each layer kept (0: global)."""
    return [cfg["sliding_window_size"] if cfg["sliding_window_layout"][i]
            else 0 for i in range(cfg["num_hidden_layers"])]


def param_shapes(cfg):
    d, v, hd = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    e, f = cfg["moe_num_primary_experts"], cfg["moe_ffn_hidden_size"]
    shapes = {"emb_weight": (v, d), "final_norm_gamma": (d,),
              "head_weight": (v, d)}
    for i in range(cfg["num_hidden_layers"]):
        pre = "l%d_" % i
        shapes.update({
            pre + "router_weight": (e, d), pre + "in_norm_gamma": (d,),
            pre + "post_norm_gamma": (d,), pre + "q_weight": (q, d),
            pre + "k_weight": (kv, d), pre + "v_weight": (kv, d),
            pre + "o_weight": (d, q), pre + "gate_weight": (e, f, d),
            pre + "up_weight": (e, f, d), pre + "down_weight": (e, f, d)})
    return shapes


def param_count(cfg):
    return sum(math.prod(s) for s in param_shapes(cfg).values())


def build_step(cfg, max_len=12288):
    """token, pos, valid + two cache states a layer -> [logits] + next
    states + [expert_load]; window layers hold ``sliding_window_size``
    rows, global ones ``max_len``."""
    from mxnet_tpu.models import smallthinker
    return smallthinker.decode_step(cfg, max_len)


def build_prefill(cfg):
    """``T -> Symbol``: a padded prompt in one dispatch."""
    from mxnet_tpu.models import smallthinker
    return smallthinker.prefill(cfg)


def init_params(cfg, seed):
    """Every weight on the default device in the configuration's dtype,
    one jitted call a distinct shape."""
    import jax
    import jax.numpy as jnp
    dtype = jnp.dtype(cfg["dtype"])
    key = jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)),
                             seed // (2 ** 31))

    def make(k, shape, scale):
        return (scale * jax.random.normal(k, shape, jnp.float32)) \
            .astype(dtype)
    make = jax.jit(make, static_argnums=(1, 2))
    out = {}
    for n, (name, shape) in enumerate(sorted(param_shapes(cfg).items())):
        if name.endswith("_gamma"):
            out[name] = jnp.ones(shape, dtype)
        else:
            scale = 1.0 if name == "emb_weight" \
                else 1.0 / math.sqrt(shape[-1] if "down" not in name
                                     else shape[1])
            out[name] = make(jax.random.fold_in(key, n), shape, scale)
    return out


def _per_layer(cfg):
    """Parameters a layer multiplies a row by: attention's four
    projections and the router; one expert's three matrices."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    attn = d * (2 * q + 2 * kv) + cfg["moe_num_primary_experts"] * d
    return attn, 3 * d * cfg["moe_ffn_hidden_size"]


def rows_read(cfg, context):
    """Cache rows (keys and values counted apart) a slot whose context
    holds ``context`` positions must read in a step: the last ``window``
    on a window layer, all of them on a global one."""
    return sum(2 * (min(context, w) if w else context)
               for w in _layers(cfg))


def step_required(cfg, slots, contexts):
    """FLOPs and HBM bytes one decode step needs when the live slots
    hold ``contexts`` positions each (the one being written included):
    attention's, the router's and the head's weights once, the weights
    of the experts that can be hit (``top_k`` a live row, at most all),
    the live rows' embedding rows, the cache rows a live slot *must*
    read (``rows_read``: not the whole layout) and the one it writes.
    FLOPs count live rows only.  ``cache_rows`` is the rows read."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    kvw = cfg["num_key_value_heads"] * cfg["head_dim"]
    qw = cfg["num_attention_heads"] * cfg["head_dim"]
    k, n_exp = cfg["moe_num_active_primary_experts"], \
        cfg["moe_num_primary_experts"]
    n_layers, live = cfg["num_hidden_layers"], len(contexts)
    attn, expert = _per_layer(cfg)
    hit = min(n_exp, k * live)
    weights = n_layers * (attn + 2 * d + hit * expert) + d + v * d
    rows = sum(rows_read(cfg, int(c)) for c in contexts)
    nbytes = ITEM * (weights + live * d + rows * kvw
                     + 2 * n_layers * live * kvw)
    flops = live * 2.0 * (n_layers * (attn + k * expert) + v * d) \
        + 2.0 * qw * rows           # scores and weighted sum: 4 a pair
    return {"flops": flops, "bytes": float(nbytes), "cache_rows": rows}


def pairs_seen(cfg, plen):
    """(query, key) pairs a prompt of ``plen`` positions attends over,
    summed over the layers: causal, and banded on window layers."""
    total = 0
    for w in _layers(cfg):
        full = plen * (plen + 1) // 2
        if w and plen > w:
            full -= (plen - w) * (plen - w + 1) // 2
        total += full
    return total


def prefill_required(cfg, prompt_lens):
    """FLOPs and HBM bytes one prefill dispatch needs for prompts of
    ``prompt_lens`` live positions: every live position through the
    projections, the router and its ``top_k`` experts; attention over the
    pairs it may see (``pairs_seen``); the head once a prompt.  Padding
    is not required work.  Bytes: every weight once, the keys and values
    written."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    kvw = cfg["num_key_value_heads"] * cfg["head_dim"]
    qw = cfg["num_attention_heads"] * cfg["head_dim"]
    k, n_exp = cfg["moe_num_active_primary_experts"], \
        cfg["moe_num_primary_experts"]
    n_layers = cfg["num_hidden_layers"]
    attn, expert = _per_layer(cfg)
    tokens = sum(prompt_lens)
    flops = tokens * 2.0 * n_layers * (attn + k * expert) \
        + sum(4.0 * qw * pairs_seen(cfg, int(p)) for p in prompt_lens) \
        + len(prompt_lens) * 2.0 * v * d
    weights = n_layers * (attn + 2 * d + n_exp * expert) + d + v * d
    nbytes = ITEM * (weights + tokens * d + 2 * n_layers * tokens * kvw)
    return {"flops": flops, "bytes": float(nbytes)}

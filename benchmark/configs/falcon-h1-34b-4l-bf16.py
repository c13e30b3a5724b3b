"""Falcon-H1-34B cut to four layers: the step and prefill graphs for
``serving.DecodeEngine`` (``mxnet_tpu/models/falcon_h1.py`` builds them
from the config's own keys), the weights from a seed, and what a decode
step and a prefill dispatch require of the chip.

Only ``build_step`` and ``build_prefill`` touch the program; the rest is
shapes and ``jax``.  The short convolution and the state space's two
forms are XLA formulations, not kernels, so there is no roofline count
for them here; ``state_bytes`` and ``scan_flops`` say what of a step and
of a dispatch is theirs.
"""
import math

ITEM = 2                   # bytes a bfloat16 weight, state or activation


def _widths(cfg):
    """``(query width, key/value width, ssm width, heads, head size,
    groups, state size, conv channels, projection width)``."""
    hd = cfg["head_dim"]
    e, h = cfg["mamba_d_ssm"], cfg["mamba_n_heads"]
    g, n = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    conv = e + 2 * g * n
    return (cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd,
            e, h, cfg["mamba_d_head"], g, n, conv, e + conv + h)


def param_shapes(cfg):
    d, v, f = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    q, kv, e, h, _p, _g, _n, conv, proj = _widths(cfg)
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


def param_count(cfg):
    return sum(math.prod(s) for s in param_shapes(cfg).values())


def build_step(cfg, max_len=1280):
    """token, pos, valid + a layer's two caches, conv row and state space
    row -> [logits] + next states."""
    from mxnet_tpu.models import falcon_h1
    return falcon_h1.decode_step(cfg, max_len)


def build_prefill(cfg):
    """``T -> Symbol``: a padded prompt in one dispatch."""
    from mxnet_tpu.models import falcon_h1
    return falcon_h1.prefill(cfg)


def _scales(cfg):
    """The standard deviation of each matrix's entries: 1/sqrt(fan_in)
    over the multiplier that follows it, so that every branch's
    activations are of unit scale under the published multipliers."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    q, _kv, e, *_ = _widths(cfg)
    a_in, gate_m, down_m = (cfg["attention_in_multiplier"],
                            *cfg["mlp_multipliers"])
    return {"q_weight": 1 / (a_in * math.sqrt(d)),
            "k_weight": 1 / (a_in * cfg["key_multiplier"] * math.sqrt(d)),
            "v_weight": 1 / (a_in * math.sqrt(d)),
            "o_weight": 1 / (cfg["attention_out_multiplier"] * math.sqrt(q)),
            "ssm_out_weight": 1 / (cfg["ssm_out_multiplier"] * math.sqrt(e)),
            "gate_weight": 1 / (gate_m * math.sqrt(d)),
            "up_weight": 1 / math.sqrt(d),
            "down_weight": 1 / (down_m * math.sqrt(f)),
            "head_weight": 1 / math.sqrt(d),
            "emb_weight": 1 / cfg["embedding_multiplier"]}


def _in_scales(cfg):
    """A row scale of the Mamba-2 input projection: its block's
    ``ssm_multipliers`` entry times ``ssm_in_multiplier``, inverted."""
    import numpy as np
    _q, _kv, e, h, _p, g, n, _conv, _proj = _widths(cfg)
    sizes = (e, e, g * n, g * n, h)
    return np.concatenate([
        np.full(s, 1 / (cfg["ssm_in_multiplier"] * m
                        * math.sqrt(cfg["hidden_size"])), np.float32)
        for s, m in zip(sizes, cfg["ssm_multipliers"])])


def init_params(cfg, seed):
    """Every weight on the default device in the configuration's dtype,
    one jitted call a distinct shape and kind: matrices normal at
    ``_scales`` (the Mamba-2 input projection a block at a time), the
    conv's taps normal at 1/sqrt(taps) and its bias 0, Mamba-2's own
    initialisation of ``A_log``, ``dt_bias`` and ``D``, every gain 1."""
    import jax
    import jax.numpy as jnp
    dtype = jnp.dtype(cfg["dtype"])
    key = jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)),
                             seed // (2 ** 31))

    def make(k, shape, scale, dt):
        return (scale * jax.random.normal(k, shape, jnp.float32)).astype(dt)
    make = jax.jit(make, static_argnums=(1, 3))

    def log_uniform(k, shape, lo, hi):
        return jnp.exp(jax.random.uniform(k, shape, jnp.float32,
                                          math.log(lo), math.log(hi)))
    scales = _scales(cfg)
    out = {}
    for n, (name, shape) in enumerate(sorted(param_shapes(cfg).items())):
        k = jax.random.fold_in(key, n)
        kind = name.split("_", 1)[1] if name[0] == "l" else name
        if name.endswith("_gamma") or kind == "D":
            out[name] = jnp.ones(shape, dtype)
        elif kind == "conv_bias":
            out[name] = jnp.zeros(shape, dtype)
        elif kind == "conv_weight":
            out[name] = make(k, shape, 1.0 / math.sqrt(shape[0]), dtype)
        elif kind == "A_log":
            out[name] = jnp.log(jax.random.uniform(
                k, shape, jnp.float32, 1.0, 16.0)).astype(dtype)
        elif kind == "dt_bias":
            dt = log_uniform(k, shape, 1e-3, 1e-1)
            out[name] = (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
        elif kind == "ssm_in_weight":
            rows = jnp.asarray(_in_scales(cfg))[:, None]
            out[name] = (make(k, shape, 1.0, jnp.dtype(jnp.float32))
                         * rows).astype(dtype)
        else:
            out[name] = make(k, shape, scales[kind], dtype)
    return out


def _per_layer(cfg):
    """Matrix parameters a row is multiplied by in a layer: attention,
    the Mamba-2 projections, the MLP."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    q, kv, e, _h, _p, _g, _n, _conv, proj = _widths(cfg)
    return d * (2 * q + 2 * kv) + d * (proj + e) + 3 * d * f


def _small(cfg):
    """Parameters outside the matrices: two norm gains, the conv's taps
    and bias, ``A_log``, ``dt_bias``, ``D`` and the grouped norm's gain
    a layer; the final norm."""
    d = cfg["hidden_size"]
    _q, _kv, e, h, _p, _g, _n, conv, _proj = _widths(cfg)
    return cfg["num_hidden_layers"] * (
        2 * d + (cfg["mamba_d_conv"] + 1) * conv + 3 * h + e) + d


def _row_bytes(cfg):
    """A slot's plain rows a layer, in items: the conv's last inputs and
    the state space's state."""
    _q, _kv, _e, h, p, _g, n, conv, _proj = _widths(cfg)
    return (cfg["mamba_d_conv"] - 1) * conv + h * p * n


def rows_read(cfg, context):
    """Cache rows (keys and values counted apart) a slot whose context
    holds ``context`` positions must read in a step: all of them, on
    every layer."""
    return 2 * context * cfg["num_hidden_layers"]


def _ssd_step_flops(cfg):
    """A live row's state space a layer: decay, ``dt x B`` and the sum a
    state value, ``S C`` a multiply-add a state value, ``D x``."""
    _q, _kv, _e, h, p, _g, n, _conv, _proj = _widths(cfg)
    return 5.0 * h * p * n + 2.0 * h * p


def step_required(cfg, slots, contexts):
    """FLOPs and HBM bytes one decode step needs when the live slots
    hold ``contexts`` positions each (the one being written included):
    every weight once (the head's too), the live rows' embedding rows,
    the cache rows a live slot must read (``rows_read``) and the one it
    writes a layer, and a live slot's conv and state space rows read and
    written, which are also returned apart as ``state_bytes``.  FLOPs
    count live rows only: the products, the head, attention over the
    context and the state space's recurrence.  ``cache_rows`` is the
    rows read."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    q, kv, *_ = _widths(cfg)
    layers = cfg["num_hidden_layers"]
    live = len(contexts)
    rows = sum(rows_read(cfg, int(c)) for c in contexts)
    state = 2 * layers * live * _row_bytes(cfg) * ITEM
    weights = layers * _per_layer(cfg) + _small(cfg) + v * d
    nbytes = ITEM * (weights + live * d + rows * kv + 2 * layers * live * kv) \
        + state
    flops = live * (2.0 * (layers * _per_layer(cfg) + v * d)
                    + layers * _ssd_step_flops(cfg)) \
        + 2.0 * q * rows            # scores and weighted sum: 4 a pair
    return {"flops": flops, "bytes": float(nbytes), "cache_rows": rows,
            "state_bytes": float(state)}


def pairs_seen(cfg, plen):
    """(query, key) pairs a prompt of ``plen`` positions attends over,
    summed over the layers: causal, no window."""
    return cfg["num_hidden_layers"] * plen * (plen + 1) // 2


def scan_flops(cfg, plen):
    """The chunked scan's FLOPs over a prompt's ``plen`` live positions,
    a layer summed over the layers, chunk by chunk as the scan computes
    them (``_ssd_scan``'s count with each chunk cut to the positions it
    holds of the prompt; the padding is not counted)."""
    _q, _kv, _e, h, p, g, n, _conv, _proj = _widths(cfg)
    chunk = cfg["mamba_chunk_size"]
    total = 0.0
    for lo in range(0, plen, chunk):
        c = min(chunk, plen - lo)
        total += 2.0 * c * c * (g * n + h * p) + 3.0 * h * c * c \
            + 4.0 * c * h * p * n + 2.0 * h * p * n + 2.0 * c * h * p
    return cfg["num_hidden_layers"] * total


def prefill_required(cfg, prompt_lens):
    """FLOPs and HBM bytes one prefill dispatch needs for prompts of
    ``prompt_lens`` live positions: every live position through the
    products, attention over the pairs it may see, the chunked scan
    (also apart, as ``scan_flops``), the head once a prompt.  Padding is
    not required work.  Bytes: every weight once, the live positions'
    embedding rows, the keys and values and each prompt's conv and state
    space rows written."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    q, kv, *_ = _widths(cfg)
    layers = cfg["num_hidden_layers"]
    tokens = sum(prompt_lens)
    scan = sum(scan_flops(cfg, int(p)) for p in prompt_lens)
    flops = tokens * 2.0 * layers * _per_layer(cfg) \
        + sum(4.0 * q * pairs_seen(cfg, int(p)) for p in prompt_lens) \
        + scan + len(prompt_lens) * 2.0 * v * d
    weights = layers * _per_layer(cfg) + _small(cfg) + v * d
    nbytes = ITEM * (weights + tokens * d + 2 * layers * tokens * kv
                     + layers * len(prompt_lens) * _row_bytes(cfg))
    return {"flops": flops, "bytes": float(nbytes), "scan_flops": scan}

"""BIG LSTM of Jozefowicz et al. (arXiv:1602.02410, Table 1): the step
graph for ``serving.DecodeEngine``, the weights from a seed, and what one
decode step requires of the chip.

Only ``build_step`` touches the program; the rest is shapes and ``jax``.
"""
import math


def param_shapes(cfg):
    v, e = cfg["vocab_size"], cfg["embed_dim"]
    h, p = cfg["lstm_cells"], cfg["proj_dim"]
    shapes = {"emb_weight": (v, e)}
    width = e
    for i in range(cfg["num_layers"]):
        pre = "lstm%d_" % i
        shapes[pre + "i2h_weight"] = (4 * h, width)
        shapes[pre + "i2h_bias"] = (4 * h,)
        shapes[pre + "h2h_weight"] = (4 * h, p)
        shapes[pre + "h2h_bias"] = (4 * h,)
        shapes[pre + "proj_weight"] = (p, h)
        width = p
    shapes["out_fc_weight"] = (v, p)
    shapes["out_fc_bias"] = (v,)
    return shapes


def param_count(cfg):
    return sum(math.prod(s) for s in param_shapes(cfg).values())


def matmul_param_count(cfg):
    """Weights a decode step multiplies by (the embedding is gathered,
    not multiplied; biases are added)."""
    return sum(math.prod(s) for n, s in param_shapes(cfg).items()
               if n.endswith("_weight") and n != "emb_weight")


def build_step(cfg):
    """token + per-layer (r, c) -> [logits] + per-layer (r', c'): per
    layer an ``LSTMCell`` whose recurrent input is the projected state
    (Sak et al. LSTMP), then the projection."""
    import mxnet_tpu as mx
    from mxnet_tpu.rnn.rnn_cell import LSTMCell
    out = mx.sym.Embedding(mx.sym.Variable("token"),
                           input_dim=cfg["vocab_size"],
                           output_dim=cfg["embed_dim"], name="emb")
    states_out, state_info = [], []
    for i in range(cfg["num_layers"]):
        pre = "lstm%d_" % i
        cell = LSTMCell(cfg["lstm_cells"], prefix=pre)
        h, (_h, c2) = cell(out, [mx.sym.Variable(pre + "r"),
                                 mx.sym.Variable(pre + "c")])
        out = mx.sym.FullyConnected(h, num_hidden=cfg["proj_dim"],
                                    no_bias=True, name=pre + "proj")
        states_out += [out, c2]
        state_info += [{"name": pre + "r", "shape": (cfg["proj_dim"],)},
                       {"name": pre + "c", "shape": (cfg["lstm_cells"],)}]
    logits = mx.sym.FullyConnected(out, num_hidden=cfg["vocab_size"],
                                   name="out_fc")
    return mx.sym.Group([logits] + states_out), state_info


def init_params(cfg, seed):
    """Every weight on the default device, float32, in one jitted call."""
    import jax
    import jax.numpy as jnp
    shapes = param_shapes(cfg)
    names = sorted(shapes)
    h = cfg["lstm_cells"]

    def make(key):
        out = {}
        for k, name in zip(jax.random.split(key, len(names)), names):
            shape = shapes[name]
            if name.endswith("i2h_bias"):     # gate order i, f, c, o
                out[name] = jnp.zeros(shape, jnp.float32).at[h:2 * h].set(1.0)
            elif len(shape) == 1:
                out[name] = jnp.zeros(shape, jnp.float32)
            else:
                scale = 1.0 if name == "emb_weight" \
                    else 1.0 / math.sqrt(shape[1])
                out[name] = scale * jax.random.normal(k, shape, jnp.float32)
        return out

    key = jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)),
                             seed // (2 ** 31))
    return jax.jit(make)(key)


def step_required(cfg, slots, live):
    """FLOPs and HBM bytes one decode step over ``slots`` rows needs, of
    which ``live`` hold a request: every matmul weight and bias read
    once (the step multiplies all rows, live or not), the live rows'
    embedding rows, every slot's state read and written.  The logits
    are not counted: the sampler is in the graph, so an argmax fused
    into the head needs none of them in HBM.  FLOPs count live rows
    only: a dead slot's row is not required work."""
    item = 4
    mm = matmul_param_count(cfg)
    state = cfg["num_layers"] * (cfg["proj_dim"] + cfg["lstm_cells"])
    bias = sum(math.prod(s) for n, s in param_shapes(cfg).items()
               if n.endswith("_bias"))
    nbytes = item * (mm + bias + live * cfg["embed_dim"]
                     + 2 * slots * state)
    return {"flops": 2.0 * mm * live, "bytes": float(nbytes)}

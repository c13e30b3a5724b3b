"""Plain reference for the ``resnet50-imagenet-bf16`` training step:
forward, mean cross-entropy, gradients by ``jax.grad`` and SGD with
momentum, in ``jax.numpy``/``lax`` with nothing of the program.

The network is written out from the architecture (bottleneck units
3-4-6-3, pre-activation arrangement, batch statistics in training mode,
biased variance, eps 2e-5, bn_data's gamma fixed at 1), with parameters
by the names the configuration gives them.

``precision``: ``"default"`` is the reference proper: float32 weights,
activations, batch statistics, loss and update, every convolution and
matrix product at the chip's default precision (operands rounded to
bfloat16 once, accumulated in float32), which is how the program runs.
The cell is held to bfloat16-grade arithmetic and no closer (PERF.md
section 2), so the control is the step below that: ``"fp8"``, both
operands of every product rounded to float8 e4m3 (one scale a tensor),
accumulated in float32; the backward products take the rounded operands
the forward kept.  ``keep`` < 1 plants a fault: only the first ``keep``
of the rows are used and the mean is taken over them (half the batch
left out; one chip's rows with the exchange left out).
"""
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

UNITS = (3, 4, 6, 3)
FILTERS = (64, 256, 512, 1024, 2048)
EPS = 2e-5


def _fp8(x):
    """x rounded to float8 e4m3 under one scale for the tensor; the
    gradient passes straight through."""
    scale = lax.stop_gradient(jnp.max(jnp.abs(x))) / 448.0
    y = (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale
    return x + lax.stop_gradient(y - x)


def _conv(x, w, stride, pad, fp8):
    if fp8:
        x, w = _fp8(x), _fp8(w)
    if w.shape[1:3] == (1, 1):
        # a 1x1 convolution is a matrix product over the rows that the
        # stride keeps; written so, its program is a fraction of the size
        x = x[:, ::stride, ::stride, :]
        y = jnp.dot(x.reshape(-1, x.shape[-1]), w.reshape(w.shape[0], -1).T)
        return y.reshape(x.shape[:3] + (w.shape[0],))
    return lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "OHWI", "NHWC"))


def _bn(x, gamma, beta):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) * lax.rsqrt(var + EPS) * gamma + beta


UNIT_LEAVES = ("bn1_gamma", "bn1_beta", "conv1_weight", "bn2_gamma",
               "bn2_beta", "conv2_weight", "bn3_gamma", "bn3_beta",
               "conv3_weight")


def _unit(u, x, stride, fp8):
    """One bottleneck unit from its own leaves; ``sc_weight`` is there
    when the unit changes the shape."""
    a1 = jax.nn.relu(_bn(x, u["bn1_gamma"], u["bn1_beta"]))
    y = _conv(a1, u["conv1_weight"], 1, 0, fp8)
    y = jax.nn.relu(_bn(y, u["bn2_gamma"], u["bn2_beta"]))
    y = _conv(y, u["conv2_weight"], stride, 1, fp8)
    y = jax.nn.relu(_bn(y, u["bn3_gamma"], u["bn3_beta"]))
    y = _conv(y, u["conv3_weight"], 1, 0, fp8)
    sc = _conv(a1, u["sc_weight"], stride, 0, fp8) if "sc_weight" in u \
        else x
    return y + sc


def _leaves(p, name, keys):
    return {k: p["%s_%s" % (name, k)] for k in keys}


def logits(p, x, fp8):
    x = _bn(x, jnp.ones_like(p["bn_data_gamma"]), p["bn_data_beta"])
    x = _conv(x, p["conv0_weight"], 2, 3, fp8)
    x = jax.nn.relu(_bn(x, p["bn0_gamma"], p["bn0_beta"]))
    x = lax.reduce_window(x, np.array(-np.inf, x.dtype), lax.max,
                          (1, 3, 3, 1), (1, 2, 2, 1),
                          [(0, 0), (1, 1), (1, 1), (0, 0)])
    for s, n_units in enumerate(UNITS):
        stage = "stage%d_unit" % (s + 1)
        x = _unit(_leaves(p, stage + "1", UNIT_LEAVES + ("sc_weight",)), x,
                  2 if s > 0 else 1, fp8)
        for u in range(2, n_units + 1):
            x = _unit(_leaves(p, stage + str(u), UNIT_LEAVES), x, 1, fp8)
    x = jax.nn.relu(_bn(x, p["bn1_gamma"], p["bn1_beta"]))
    x = jnp.mean(x, axis=(1, 2))
    w = p["fc1_weight"]
    if fp8:
        x, w = _fp8(x), _fp8(w)
    return jnp.dot(x, w.T) + p["fc1_bias"]


def loss_fn(p, x, label, fp8):
    lp = jax.nn.log_softmax(logits(p, x, fp8), axis=-1)
    return -jnp.mean(jnp.take_along_axis(
        lp, label.astype(jnp.int32)[:, None], axis=1))


def leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v))) for k, v in tree.items()}


def first_steps(params, data, label, lr, momentum, steps=3,
                precision="default", keep=1.0):
    """Run ``steps`` SGD-momentum steps on the one batch.  Returns the
    loss before each step, the norm of every leaf's first gradient and
    the norm of every leaf's change after the last step, as floats."""
    if precision not in ("default", "fp8"):
        raise ValueError("unknown precision %r" % (precision,))
    fp8 = precision == "fp8"
    if keep < 1.0:
        rows = max(1, int(round(data.shape[0] * keep)))
        data, label = data[:rows], label[:rows]

    @jax.jit
    def step(p, mom, data, label):
        loss, g = jax.value_and_grad(loss_fn)(p, data, label, fp8)
        mom = {k: momentum * mom[k] - lr * g[k] for k in p}
        return loss, leaf_norms(g), {k: p[k] + mom[k] for k in p}, mom

    @jax.jit
    def change(p, p0):
        return leaf_norms({k: p[k] - p0[k] for k in p})

    p = params
    mom = {k: jnp.zeros_like(v) for k, v in params.items()}
    losses, grad1 = [], None
    for i in range(steps):
        loss, gn, p, mom = step(p, mom, data, label)
        losses.append(float(loss))
        if i == 0:
            grad1 = {k: float(v) for k, v in gn.items()}
    delta = {k: float(v) for k, v in change(p, params).items()}
    return {"losses": losses, "grad1": grad1, "delta": delta}

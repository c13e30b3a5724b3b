"""Fused optimizer-update ops.

Reference: src/operator/optimizer_op.cc (sgd_update:39, sgd_mom_update:66,
mp_sgd_update:111, mp_sgd_mom_update:128, adam_update:146, rmsprop_update:195,
rmspropalex_update:245, ftrl_update:286).

The reference fuses optimizer math into single kernels to avoid temporaries;
here each registered update is one jitted XLA computation a parameter, keyed
by its attributes (a new `lr` is a new program).  State (momentum etc.) is
an input returned updated via ``mutate_aux``.  ``multi_sgd_update`` applies
`sgd_update` / `sgd_mom_update` to a whole list of parameters in one
program with the hyper-parameters as operands: what `SGD.update_multi`, and
through it `Module.update`, dispatches once a step.  Every other caller
(`gluon.Trainer`, `KVStore`, the other optimizers) runs one op a parameter.

All updates implement: weight' = f(weight, grad * rescale_grad clipped, state)
with weight-decay folded in exactly as the reference does.
"""
import jax.numpy as jnp

from .registry import register, P

_COMMON = {"lr": P(float), "wd": P(float, 0.0), "rescale_grad": P(float, 1.0),
           "clip_gradient": P(float, -1.0)}


def _prep_grad(attrs, grad, weight):
    """SGD-family semantics (optimizer_op-inl.h:74-78): clip(rescale*grad),
    weight decay applied separately."""
    g = grad * attrs["rescale_grad"]
    if attrs["clip_gradient"] > 0:
        c = attrs["clip_gradient"]
        g = jnp.clip(g, -c, c)
    return g


def _prep_grad_wd(attrs, grad, weight):
    """Adam/RMSProp-family semantics (optimizer_op-inl.h AdamUpdate): fold
    wd*weight into the gradient FIRST, then clip."""
    g = grad * attrs["rescale_grad"] + attrs["wd"] * weight
    if attrs["clip_gradient"] > 0:
        c = attrs["clip_gradient"]
        g = jnp.clip(g, -c, c)
    return g


@register("sgd_update", nin=2, input_names=["weight", "grad"],
          nout=1, mutate_aux={0: 0}, num_visible_outputs=1,
          params={**_COMMON, "lazy_update": P(bool, True)})
def sgd_update(attrs, weight, grad):
    g = _prep_grad(attrs, grad, weight)
    new_w = weight - attrs["lr"] * (g + attrs["wd"] * weight)
    return (new_w,)


@register("sgd_mom_update", nin=3, input_names=["weight", "grad", "mom"],
          nout=2, mutate_aux={0: 0, 2: 1}, num_visible_outputs=1,
          params={**_COMMON, "momentum": P(float, 0.0), "lazy_update": P(bool, True)})
def sgd_mom_update(attrs, weight, grad, mom):
    g = _prep_grad(attrs, grad, weight)
    new_mom = attrs["momentum"] * mom - attrs["lr"] * (g + attrs["wd"] * weight)
    new_w = weight + new_mom
    return new_w, new_mom


def multi_sgd_update(weights, grads, moms, lrs, wds, rescale_grad, momentum,
                     clip_gradient=-1.0):
    """`sgd_update` (where a parameter's ``moms`` entry is None) or
    `sgd_mom_update` over equally long tuples, the reference's
    multi_sgd(_mom)_update.  ``lrs`` and ``wds`` are float32 vectors with
    one entry a parameter, ``rescale_grad`` and ``momentum`` float32
    scalars: operands, each cast to the dtype a Python float would take
    beside that array, so the arithmetic per element is the registered
    op's (to the bit for float32 arrays, the only ones `SGD.update_multi`
    sends; a narrower one gets its rate through float32).  What picks the expression is not an operand: ``clip_gradient``
    is a Python float, and ``rescale_grad`` None stands for the 1.0 that
    XLA drops from the registered op (a product kept there would pair up
    differently into fused multiply-adds).  Returns (new weights, new
    momenta)."""
    new_ws, new_moms = [], []
    for i, (w, g, m) in enumerate(zip(weights, grads, moms)):
        attrs = {"lr": lrs[i].astype(w.dtype), "wd": wds[i].astype(w.dtype),
                 "rescale_grad": 1.0 if rescale_grad is None
                 else rescale_grad.astype(g.dtype),
                 "clip_gradient": clip_gradient}
        if m is None:
            (new_w,), new_m = sgd_update(attrs, w, g), None
        else:
            attrs["momentum"] = momentum.astype(m.dtype)
            new_w, new_m = sgd_mom_update(attrs, w, g, m)
        new_ws.append(new_w)
        new_moms.append(new_m)
    return tuple(new_ws), tuple(new_moms)


@register("mp_sgd_update", nin=3, input_names=["weight", "grad", "weight32"],
          nout=2, mutate_aux={0: 0, 2: 1}, num_visible_outputs=1,
          params={**_COMMON, "lazy_update": P(bool, True)})
def mp_sgd_update(attrs, weight, grad, weight32):
    g = _prep_grad(attrs, grad.astype(jnp.float32), weight32)
    new_w32 = weight32 - attrs["lr"] * (g + attrs["wd"] * weight32)
    return new_w32.astype(weight.dtype), new_w32


@register("mp_sgd_mom_update", nin=4,
          input_names=["weight", "grad", "mom", "weight32"],
          nout=3, mutate_aux={0: 0, 2: 1, 3: 2}, num_visible_outputs=1,
          params={**_COMMON, "momentum": P(float, 0.0), "lazy_update": P(bool, True)})
def mp_sgd_mom_update(attrs, weight, grad, mom, weight32):
    g = _prep_grad(attrs, grad.astype(jnp.float32), weight32)
    new_mom = attrs["momentum"] * mom - attrs["lr"] * (g + attrs["wd"] * weight32)
    new_w32 = weight32 + new_mom
    return new_w32.astype(weight.dtype), new_mom, new_w32


@register("adam_update", nin=4, input_names=["weight", "grad", "mean", "var"],
          nout=3, mutate_aux={0: 0, 2: 1, 3: 2}, num_visible_outputs=1,
          params={**_COMMON, "beta1": P(float, 0.9), "beta2": P(float, 0.999),
                  "epsilon": P(float, 1e-8), "lazy_update": P(bool, True)})
def adam_update(attrs, weight, grad, mean, var):
    g = _prep_grad_wd(attrs, grad, weight)
    new_mean = attrs["beta1"] * mean + (1 - attrs["beta1"]) * g
    new_var = attrs["beta2"] * var + (1 - attrs["beta2"]) * jnp.square(g)
    new_w = weight - attrs["lr"] * new_mean / (jnp.sqrt(new_var) + attrs["epsilon"])
    return new_w, new_mean, new_var


@register("rmsprop_update", nin=3, input_names=["weight", "grad", "n"],
          nout=2, mutate_aux={0: 0, 2: 1}, num_visible_outputs=1,
          params={**_COMMON, "gamma1": P(float, 0.95), "epsilon": P(float, 1e-8),
                  "clip_weights": P(float, -1.0)})
def rmsprop_update(attrs, weight, grad, n):
    g = _prep_grad_wd(attrs, grad, weight)
    new_n = (1 - attrs["gamma1"]) * jnp.square(g) + attrs["gamma1"] * n
    new_w = weight - attrs["lr"] * g / jnp.sqrt(new_n + attrs["epsilon"])
    if attrs["clip_weights"] > 0:
        c = attrs["clip_weights"]
        new_w = jnp.clip(new_w, -c, c)
    return new_w, new_n


@register("rmspropalex_update", nin=5,
          input_names=["weight", "grad", "n", "g", "delta"],
          nout=4, mutate_aux={0: 0, 2: 1, 3: 2, 4: 3}, num_visible_outputs=1,
          params={**_COMMON, "gamma1": P(float, 0.95), "gamma2": P(float, 0.9),
                  "epsilon": P(float, 1e-8), "clip_weights": P(float, -1.0)})
def rmspropalex_update(attrs, weight, grad, n, gbar, delta):
    g = _prep_grad_wd(attrs, grad, weight)
    new_n = (1 - attrs["gamma1"]) * jnp.square(g) + attrs["gamma1"] * n
    new_g = (1 - attrs["gamma1"]) * g + attrs["gamma1"] * gbar
    new_delta = attrs["gamma2"] * delta - attrs["lr"] * g / jnp.sqrt(
        new_n - jnp.square(new_g) + attrs["epsilon"])
    new_w = weight + new_delta
    if attrs["clip_weights"] > 0:
        c = attrs["clip_weights"]
        new_w = jnp.clip(new_w, -c, c)
    return new_w, new_n, new_g, new_delta


@register("ftrl_update", nin=4, input_names=["weight", "grad", "z", "n"],
          nout=3, mutate_aux={0: 0, 2: 1, 3: 2}, num_visible_outputs=1,
          params={**_COMMON, "lamda1": P(float, 0.01), "beta": P(float, 1.0)})
def ftrl_update(attrs, weight, grad, z, n):
    g = _prep_grad(attrs, grad, weight)
    lr, l1, beta, wd = attrs["lr"], attrs["lamda1"], attrs["beta"], attrs["wd"]
    new_n = n + jnp.square(g)
    sigma = (jnp.sqrt(new_n) - jnp.sqrt(n)) / lr
    new_z = z + g - sigma * weight
    new_w = jnp.where(
        jnp.abs(new_z) <= l1,
        jnp.zeros_like(weight),
        -(new_z - jnp.sign(new_z) * l1) / ((beta + jnp.sqrt(new_n)) / lr + wd))
    return new_w, new_z, new_n


@register("signsgd_update", nin=2, input_names=["weight", "grad"],
          nout=1, mutate_aux={0: 0}, num_visible_outputs=1, params=dict(_COMMON))
def signsgd_update(attrs, weight, grad):
    g = _prep_grad(attrs, grad, weight)
    return (weight - attrs["lr"] * (jnp.sign(g) + attrs["wd"] * weight),)


@register("signum_update", nin=3, input_names=["weight", "grad", "mom"],
          nout=2, mutate_aux={0: 0, 2: 1}, num_visible_outputs=1,
          params={**_COMMON, "momentum": P(float, 0.0),
                  "wd_lh": P(float, 0.0)})
def signum_update(attrs, weight, grad, mom):
    g = _prep_grad(attrs, grad, weight)
    new_mom = attrs["momentum"] * mom - (1 - attrs["momentum"]) * g
    new_w = (1 - attrs["lr"] * attrs["wd_lh"]) * weight \
        + attrs["lr"] * jnp.sign(new_mom)
    return new_w, new_mom

"""Optimizer registry + 12 optimizers + Updater.

Reference: python/mxnet/optimizer.py — base `Optimizer:35` with registry,
SGD:433, DCASGD:534, NAG:590, SGLD:626, Adam:661, AdaGrad:738, RMSProp:806,
AdaDelta:882, Ftrl:932, Adamax:1008, Nadam:1057, and `Updater:1142` (the
client-side per-key state store, serializable so distributed servers can run
the same update — kvstore.py:460).

TPU-native redesign: the hot optimizers (SGD/Adam/RMSProp/Ftrl/SignSGD) call
the fused update *ops* (mxnet_tpu/ops/optimizer_ops.py), so every update is a
single XLA computation on-device, and the Module/Trainer fast path can inline
these same impls into the jitted train step (the `update_on_kvstore` collapse).
The long-tail optimizers are jnp math through the same invoke path.  An
update op's hyper-params (lr, wd) are attributes, so jit caches one program
per value; `update_multi` (SGD) applies a whole list of parameters in one
program whose hyper-params are operands.
"""
from __future__ import annotations

import contextlib
import functools
import logging
import math
import pickle

import numpy

from .base import MXNetError, named_program
from .ndarray import NDArray, zeros, ones, full, invoke
from .ndarray import sgd_update, sgd_mom_update, mp_sgd_update, \
    mp_sgd_mom_update, adam_update, rmsprop_update, rmspropalex_update, \
    ftrl_update, signsgd_update, signum_update

__all__ = ["Optimizer", "SGD", "DCASGD", "NAG", "SGLD", "ccSGD", "Adam",
           "AdaGrad", "RMSProp", "AdaDelta", "Ftrl", "Adamax", "Nadam",
           "Signum", "SignSGD", "Test", "Updater", "get_updater", "create",
           "register"]


def _no_mark(index):
    return contextlib.nullcontext()


class Optimizer(object):
    """Base optimizer; mirrors python/mxnet/optimizer.py:35 API."""

    opt_registry = {}

    @staticmethod
    def register(klass):
        assert isinstance(klass, type)
        name = klass.__name__.lower()
        if name in Optimizer.opt_registry:
            logging.warning("WARNING: New optimizer %s.%s is overriding "
                            "existing optimizer %s.%s", klass.__module__,
                            klass.__name__,
                            Optimizer.opt_registry[name].__module__,
                            Optimizer.opt_registry[name].__name__)
        Optimizer.opt_registry[name] = klass
        return klass

    @staticmethod
    def create_optimizer(name, **kwargs):
        if name.lower() in Optimizer.opt_registry:
            return Optimizer.opt_registry[name.lower()](**kwargs)
        raise ValueError("Cannot find optimizer %s" % name)

    def __init__(self, rescale_grad=1.0, param_idx2name=None, wd=0.0,
                 clip_gradient=None, learning_rate=0.01, lr_scheduler=None,
                 sym=None, begin_num_update=0, param_dict=None):
        self.rescale_grad = rescale_grad
        self.lr = learning_rate
        self.lr_scheduler = lr_scheduler
        if lr_scheduler is not None:
            self.lr_scheduler.base_lr = learning_rate
        self.wd = wd
        self.lr_mult = {}
        self.wd_mult = {}
        self.begin_num_update = begin_num_update
        self.num_update = begin_num_update
        self._index_update_count = {}
        self.clip_gradient = clip_gradient
        if param_idx2name is None:
            param_idx2name = {}
        assert isinstance(param_idx2name, dict), \
            "param_idx2name should be a dict of param indexes to names."
        self.idx2name = param_idx2name.copy()
        self.sym = sym
        self.param_dict = param_dict if param_dict else {}
        self.set_lr_mult({})
        self.set_wd_mult({})

    def create_state(self, index, weight):
        """Create per-weight auxiliary state (momentum etc.)."""
        return None

    def update(self, index, weight, grad, state):
        raise NotImplementedError()

    def update_multi(self, indices, weights, grads, states, mark=_no_mark):
        """Update a list of parameters; returns how many update programs
        were dispatched.  The default is the loop over `update`; an
        optimizer with a multi-tensor rule (SGD) overrides it.
        ``mark(index)`` gives a context manager held around that
        parameter's update, ``mark(None)`` one held around a program
        that updates them all: the caller's trace marks."""
        for index, weight, grad, state in zip(indices, weights, grads,
                                              states):
            with mark(index):
                self.update(index, weight, grad, state)
        return len(indices)

    def set_lr_mult(self, args_lr_mult):
        """Per-param lr multipliers, seeded from symbol __lr_mult__ attrs."""
        self.lr_mult = {}
        if self.sym is not None:
            attr = self.sym.attr_dict()
            for name in self.sym.list_arguments():
                if name in attr and "__lr_mult__" in attr[name]:
                    self.lr_mult[name] = float(attr[name]["__lr_mult__"])
        self.lr_mult.update(args_lr_mult)

    def set_wd_mult(self, args_wd_mult):
        """Per-param wd multipliers; bias/gamma/beta default to wd 0 like the
        reference (optimizer.py set_wd_mult)."""
        self.wd_mult = {}
        for n in self.idx2name.values():
            is_weight = n.endswith("_weight")
            if not (is_weight or n.endswith("_gamma")):
                self.wd_mult[n] = 0.0
        if self.sym is not None:
            attr = self.sym.attr_dict()
            for name in self.sym.list_arguments():
                if name in attr and "__wd_mult__" in attr[name]:
                    self.wd_mult[name] = float(attr[name]["__wd_mult__"])
        self.wd_mult.update(args_wd_mult)

    def _update_count(self, index):
        if index not in self._index_update_count:
            self._index_update_count[index] = self.begin_num_update
        self._index_update_count[index] += 1
        self.num_update = max(self._index_update_count[index], self.num_update)

    def _multiplier(self, index, table, param_attr):
        """Per-parameter multiplier resolution order: explicit Parameter
        attr > index-keyed entry > name-keyed entry > 1."""
        if index in self.param_dict:
            return getattr(self.param_dict[index], param_attr)
        if index in table:
            return table[index]
        name = self.idx2name.get(index)
        return table.get(name, 1.0) if name is not None else 1.0

    def _get_lr(self, index):
        base = self.lr_scheduler(self.num_update) \
            if self.lr_scheduler is not None else self.lr
        return base * self._multiplier(index, self.lr_mult, "lr_mult")

    def _get_wd(self, index):
        return self.wd * self._multiplier(index, self.wd_mult, "wd_mult")

    def _common_attrs(self, index):
        a = {"lr": self._get_lr(index), "wd": self._get_wd(index),
             "rescale_grad": self.rescale_grad}
        if self.clip_gradient:
            a["clip_gradient"] = self.clip_gradient
        return a

    def __getstate__(self):
        d = self.__dict__.copy()
        d.pop("sym", None)
        return d

    def __setstate__(self, state):
        self.__dict__.update(state)
        self.sym = None


register = Optimizer.register  # pylint: disable=invalid-name


def _rsp_grad_rows(grad, rescale, clip):
    """Host-side (index, rows) view of a row_sparse gradient with
    rescale/clip applied — the preamble every rsp kernel shares."""
    import numpy as _np
    idx = _np.asarray(grad._aux["indices"]._data).astype(_np.int64)
    rows = _np.asarray(grad._aux["data"]._data).astype(_np.float32) * rescale
    if clip:
        rows = _np.clip(rows, -clip, clip)
    return idx, rows


def _gather_weight_rows(weight, idx):
    """Rows `idx` of a dense OR row_sparse-stored array (absent rsp rows
    read as zero), as f32 numpy."""
    import numpy as _np
    from .ndarray.sparse import RowSparseNDArray, gather_rsp_rows
    if isinstance(weight, RowSparseNDArray):
        w_idx = _np.asarray(weight.indices._data).astype(_np.int64)
        w_rows = _np.asarray(weight.data._data)
        return gather_rsp_rows(w_idx, w_rows, idx).astype(_np.float32)
    return _np.asarray(weight._data[idx]).astype(_np.float32)


def _scatter_weight_rows(weight, idx, w_new):
    """Write updated rows back, keeping a row_sparse store COMPRESSED.
    Steady state (all touched rows already present, indices sorted) is an
    in-place O(grad_nnz) row write; only genuinely NEW rows pay the
    union-rebuild."""
    import numpy as _np
    import jax.numpy as jnp
    from .ndarray.sparse import RowSparseNDArray, row_sparse_array
    if isinstance(weight, RowSparseNDArray):
        store_dtype = weight.data._data.dtype
        w_idx = _np.asarray(weight.indices._data).astype(_np.int64)
        if len(w_idx) and _np.all(w_idx[:-1] <= w_idx[1:]):
            pos = _np.clip(_np.searchsorted(w_idx, idx), 0, len(w_idx) - 1)
            if _np.array_equal(w_idx[pos], idx):
                weight.data._data = weight.data._data.at[
                    jnp.asarray(pos)].set(jnp.asarray(w_new, store_dtype))
                return
        w_rows = _np.asarray(weight.data._data)
        union = _np.union1d(w_idx, idx)
        merged = _np.zeros((len(union),) + w_new.shape[1:], store_dtype)
        if len(w_idx):
            merged[_np.searchsorted(union, w_idx)] = w_rows
        merged[_np.searchsorted(union, idx)] = w_new.astype(store_dtype)
        fresh = row_sparse_array((merged, union), shape=weight.shape,
                                 dtype=store_dtype)
        weight._aux = fresh._aux
        return
    weight._data = weight._data.at[jnp.asarray(idx)].set(
        jnp.asarray(w_new, weight._data.dtype))


def _rsp_sgd_update(weight, grad, mom, momentum, lr, wd, rescale, clip):
    """Row-sparse sgd(_mom)_update with the reference's lazy_update
    semantics: ONLY rows present in the gradient touch the weight and the
    momentum (src/operator/optimizer_op.cc sgd rsp kernels) — O(nnz).
    Works against dense- or rsp-stored weights (the kvstore keeps master
    weights compressed)."""
    idx, rows = _rsp_grad_rows(grad, rescale, clip)
    w_rows = _gather_weight_rows(weight, idx)
    g = rows + wd * w_rows
    if mom is not None:
        m_rows = momentum * _gather_weight_rows(mom, idx) - lr * g
        _scatter_weight_rows(mom, idx, m_rows)
        w_new = w_rows + m_rows
    else:
        w_new = w_rows - lr * g
    _scatter_weight_rows(weight, idx, w_new)


def _state_like(weight):
    """Optimizer-state array matching the weight's STORAGE: rsp-stored
    weights get an (initially empty) rsp state so a compressed embedding
    server never allocates O(rows) dense state (reference lazy_update
    keeps server state sparse too)."""
    import numpy as _np
    if getattr(weight, "stype", "default") == "row_sparse":
        from .ndarray.sparse import row_sparse_array
        return row_sparse_array(
            (_np.zeros((0,) + weight.shape[1:], _np.float32),
             _np.zeros((0,), _np.int64)), shape=weight.shape)
    return zeros(weight.shape, weight.context, dtype=weight.dtype)


@register
class SGD(Optimizer):
    """SGD with momentum and optional fp16 multi-precision master weights.

    Reference: optimizer.py:433 + fused ops src/operator/optimizer_op.cc:39-128.
    """

    def __init__(self, momentum=0.0, multi_precision=False, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.multi_precision = multi_precision

    def create_state(self, index, weight):
        momentum = None
        weight_master_copy = None
        if self.multi_precision and weight.dtype == numpy.float16:
            weight_master_copy = weight.astype(numpy.float32)
            if self.momentum != 0.0:
                momentum = zeros(weight.shape, weight.context,
                                 dtype=numpy.float32)
            return (momentum, weight_master_copy)
        if weight.dtype == numpy.float16 and not self.multi_precision:
            logging.warning("Accumulating with float16 in optimizer can lead "
                            "to poor accuracy or slow convergence. Consider "
                            "using multi_precision=True option of the SGD "
                            "optimizer")
        if self.momentum != 0.0:
            momentum = _state_like(weight)
        return momentum

    def update(self, index, weight, grad, state):
        self._update_count(index)
        kwargs = self._common_attrs(index)
        if self.momentum > 0:
            kwargs["momentum"] = self.momentum
        if getattr(grad, "stype", "default") == "row_sparse" \
                and not isinstance(state, (list, tuple)):
            _rsp_sgd_update(weight, grad, state, self.momentum,
                            kwargs["lr"], kwargs["wd"], self.rescale_grad,
                            self.clip_gradient)
            return
        use_mp = isinstance(state, (list, tuple))
        if not use_mp:
            if state is not None:
                sgd_mom_update(weight, grad, state, out=weight, **kwargs)
            else:
                sgd_update(weight, grad, out=weight, **kwargs)
        else:
            if state[0] is not None:
                mp_sgd_mom_update(weight, grad, state[0], state[1],
                                  out=weight, **kwargs)
            else:
                mp_sgd_update(weight, grad, state[1], out=weight, **kwargs)

    def update_multi(self, indices, weights, grads, states, mark=_no_mark):
        """One `multi_sgd_update` program for the whole list where every
        weight, gradient and momentum is dense and float32 (the rates are
        float32 operands: a narrower weight would see its rate rounded
        twice, the loop rounds it once), no state is a (momentum, float32
        master) tuple and no subclass has its own `update`; else the
        loop.  Host side it is the loop's bookkeeping in the loop's
        order; lr, wd, rescale_grad and momentum are operands, so a
        scheduler's new rate compiles nothing.  Results land in the same
        NDArray objects."""
        def fusable(weight, grad, state):
            return (weight.stype == "default" and grad.stype == "default"
                    and weight.dtype == numpy.float32
                    and grad.dtype == weight.dtype
                    and (state is None or (
                        isinstance(state, NDArray)
                        and state.stype == "default"
                        and state.dtype == weight.dtype)))
        if type(self).update is not SGD.update \
                or not all(map(fusable, weights, grads, states)):
            return super().update_multi(indices, weights, grads, states,
                                        mark)
        lrs, wds = [], []
        for index in indices:
            self._update_count(index)
            lrs.append(self._get_lr(index))
            wds.append(self._get_wd(index))
        # arrays fresh from an initializer or `zeros` are uncommitted and
        # come back committed: left so, the first call would compile one
        # program and every later call another.  A momentum goes where
        # its weight lives (under a ShardingPlan, on every chip)
        import jax
        for weight, state in zip(weights, states):
            sharding = weight._data.sharding
            for array in (weight, state):
                if array is not None and not array._data.committed:
                    array._data = jax.device_put(array._data, sharding)
        with mark(None):
            new_weights, new_moms = _multi_sgd_jit()(
                tuple(w._data for w in weights),
                tuple(g._data for g in grads),
                tuple(None if s is None else s._data for s in states),
                numpy.asarray(lrs, numpy.float32),
                numpy.asarray(wds, numpy.float32),
                None if self.rescale_grad == 1.0
                else numpy.float32(self.rescale_grad),
                numpy.float32(self.momentum if self.momentum > 0 else 0.0),
                clip_gradient=float(self.clip_gradient or -1.0))
        for weight, state, new_w, new_m in zip(weights, states, new_weights,
                                               new_moms):
            weight._data = new_w
            if state is not None:
                state._data = new_m
        return 1


@functools.cache
def _multi_sgd_jit():
    """`ops.optimizer_ops.multi_sgd_update` under one `jax.jit`, built at
    the first multi-tensor update (nothing at import).  Nothing is
    donated: `copyto` and `detach` share buffers, so an array a caller
    holds may alias a weight or a momentum, and must outlive the step."""
    import jax
    from .ops.optimizer_ops import multi_sgd_update
    return jax.jit(named_program(multi_sgd_update, "mx_update_multi_sgd"),
                   static_argnames=("clip_gradient",))


@register
class SignSGD(Optimizer):
    """Takes the sign of the gradient (optimizer_op.cc signsgd_update)."""

    def __init__(self, **kwargs):
        super().__init__(**kwargs)

    def create_state(self, index, weight):
        return None

    def update(self, index, weight, grad, state):
        self._update_count(index)
        signsgd_update(weight, grad, out=weight, **self._common_attrs(index))


@register
class Signum(Optimizer):
    """Signum: sign of momentum (optimizer_op.cc signum_update)."""

    def __init__(self, momentum=0.9, wd_lh=0.0, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.wd_lh = wd_lh

    def create_state(self, index, weight):
        if self.momentum != 0.0:
            return zeros(weight.shape, weight.context, dtype=weight.dtype)
        return None

    def update(self, index, weight, grad, state):
        self._update_count(index)
        kwargs = self._common_attrs(index)
        if state is not None:
            if self.wd_lh:
                kwargs["wd_lh"] = self.wd_lh
            kwargs["momentum"] = self.momentum
            signum_update(weight, grad, state, out=weight, **kwargs)
        else:
            signsgd_update(weight, grad, out=weight, **kwargs)


@register
class DCASGD(Optimizer):
    """Delay-compensated async SGD (optimizer.py:534)."""

    def __init__(self, momentum=0.0, lamda=0.04, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.weight_previous = {}
        self.lamda = lamda

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return (None, weight.copy())
        return (zeros(weight.shape, weight.context, dtype=weight.dtype),
                weight.copy())

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        grad = grad * self.rescale_grad
        if self.clip_gradient is not None:
            grad = invoke("clip", [grad], {"a_min": -self.clip_gradient,
                                           "a_max": self.clip_gradient})
        mom, previous_weight = state
        delta = -lr * (grad + wd * weight
                       + self.lamda * grad * grad * (weight - previous_weight))
        if mom is not None:
            mom *= self.momentum
            mom += delta
            d = mom
        else:
            d = delta
        previous_weight._data = weight._data
        weight += d


@register
class NAG(Optimizer):
    """Nesterov accelerated SGD (optimizer.py:590)."""

    def __init__(self, momentum=0.0, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return zeros(weight.shape, weight.context, dtype=weight.dtype)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        grad = grad * self.rescale_grad
        if self.clip_gradient is not None:
            grad = invoke("clip", [grad], {"a_min": -self.clip_gradient,
                                           "a_max": self.clip_gradient})
        if state is not None:
            mom = state
            mom *= self.momentum
            grad = grad + wd * weight
            mom += grad
            grad = grad + self.momentum * mom
            weight += -lr * grad
        else:
            weight += -lr * (grad + wd * weight)


@register
class SGLD(Optimizer):
    """Stochastic Gradient Langevin Dynamics (optimizer.py:626)."""

    def __init__(self, **kwargs):
        super().__init__(**kwargs)

    def create_state(self, index, weight):
        return None

    def update(self, index, weight, grad, state):
        from . import ndarray as nd
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        grad = grad * self.rescale_grad
        if self.clip_gradient is not None:
            grad = invoke("clip", [grad], {"a_min": -self.clip_gradient,
                                           "a_max": self.clip_gradient})
        noise = nd.random.normal(0, math.sqrt(lr), shape=weight.shape,
                                 ctx=weight.context, dtype=weight.dtype)
        weight += -lr / 2 * (grad + wd * weight) + noise


@register  # noqa: F811
class ccSGD(SGD):
    """Back-compat alias of SGD (optimizer.py ccSGD)."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)


@register
class Adam(Optimizer):
    """Adam (optimizer.py:661, fused op optimizer_op.cc:146)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon

    def create_state(self, index, weight):
        return (_state_like(weight),   # mean
                _state_like(weight))   # var

    def update(self, index, weight, grad, state):
        self._update_count(index)
        t = self._index_update_count[index]
        kwargs = self._common_attrs(index)
        kwargs.update({"beta1": self.beta1, "beta2": self.beta2,
                       "epsilon": self.epsilon})
        # bias correction folded into lr, as the reference does
        coef1 = 1. - self.beta1 ** t
        coef2 = 1. - self.beta2 ** t
        kwargs["lr"] *= math.sqrt(coef2) / coef1
        mean, var = state
        if getattr(grad, "stype", "default") == "row_sparse":
            # rsp lazy_update (optimizer_op.cc adam rsp kernel): only the
            # gradient's rows touch mean/var/weight — O(nnz)
            import numpy as _np
            import jax.numpy as jnp
            idx, rows = _rsp_grad_rows(grad, self.rescale_grad,
                                       self.clip_gradient)
            w_rows = _gather_weight_rows(weight, idx)
            g = rows + kwargs["wd"] * w_rows
            m_rows = (self.beta1 * _gather_weight_rows(mean, idx)
                      + (1 - self.beta1) * g)
            v_rows = (self.beta2 * _gather_weight_rows(var, idx)
                      + (1 - self.beta2) * g * g)
            w_new = w_rows - kwargs["lr"] * m_rows / (
                _np.sqrt(v_rows) + self.epsilon)
            _scatter_weight_rows(mean, idx, m_rows)
            _scatter_weight_rows(var, idx, v_rows)
            _scatter_weight_rows(weight, idx, w_new)
            return
        adam_update(weight, grad, mean, var, out=weight, **kwargs)


@register
class AdaGrad(Optimizer):
    """AdaGrad (optimizer.py:738)."""

    def __init__(self, eps=1e-7, **kwargs):
        super().__init__(**kwargs)
        self.float_stable_eps = eps

    def create_state(self, index, weight):
        return zeros(weight.shape, weight.context)  # history

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        grad = grad * self.rescale_grad
        if self.clip_gradient is not None:
            grad = invoke("clip", [grad], {"a_min": -self.clip_gradient,
                                           "a_max": self.clip_gradient})
        history = state
        history += grad * grad
        div = grad / invoke("sqrt", [history + self.float_stable_eps], {})
        weight += (div + weight * wd) * -lr


@register
class RMSProp(Optimizer):
    """RMSProp, centered (Graves) or plain (Tieleman); optimizer.py:806,
    fused ops optimizer_op.cc:195/245."""

    def __init__(self, learning_rate=0.001, gamma1=0.9, gamma2=0.9,
                 epsilon=1e-8, centered=False, clip_weights=None, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.gamma1 = gamma1
        self.gamma2 = gamma2
        self.centered = centered
        self.epsilon = epsilon
        self.clip_weights = clip_weights

    def create_state(self, index, weight):
        if self.centered:
            return (zeros(weight.shape, weight.context),  # n
                    zeros(weight.shape, weight.context),  # g
                    zeros(weight.shape, weight.context))  # delta
        return zeros(weight.shape, weight.context)  # n

    def update(self, index, weight, grad, state):
        self._update_count(index)
        kwargs = self._common_attrs(index)
        kwargs.update({"gamma1": self.gamma1, "epsilon": self.epsilon})
        if self.centered:
            kwargs["gamma2"] = self.gamma2
        if self.clip_weights:
            kwargs["clip_weights"] = self.clip_weights
        if not self.centered:
            n = state
            rmsprop_update(weight, grad, n, out=weight, **kwargs)
        else:
            n, g, delta = state
            rmspropalex_update(weight, grad, n, g, delta, out=weight, **kwargs)


@register
class AdaDelta(Optimizer):
    """AdaDelta (optimizer.py:882)."""

    def __init__(self, rho=0.90, epsilon=1e-5, **kwargs):
        super().__init__(**kwargs)
        self.rho = rho
        self.epsilon = epsilon

    def create_state(self, index, weight):
        return (zeros(weight.shape, weight.context),  # accumulated g
                zeros(weight.shape, weight.context))  # accumulated delta

    def update(self, index, weight, grad, state):
        from . import ndarray as nd
        self._update_count(index)
        wd = self._get_wd(index)
        grad = grad * self.rescale_grad
        if self.clip_gradient is not None:
            grad = invoke("clip", [grad], {"a_min": -self.clip_gradient,
                                           "a_max": self.clip_gradient})
        acc_g, acc_delta = state
        acc_g._data = (self.rho * acc_g + (1. - self.rho) * grad * grad)._data
        current_delta = (nd.sqrt(acc_delta + self.epsilon)
                         / nd.sqrt(acc_g + self.epsilon)) * grad
        acc_delta._data = (self.rho * acc_delta
                           + (1. - self.rho) * current_delta * current_delta)._data
        weight -= current_delta + wd * weight


@register
class Ftrl(Optimizer):
    """FTRL-proximal (optimizer.py:932, fused op optimizer_op.cc:286)."""

    def __init__(self, lamda1=0.01, learning_rate=0.1, beta=1, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.lamda1 = lamda1
        self.beta = beta

    def create_state(self, index, weight):
        return (zeros(weight.shape, weight.context),  # z
                zeros(weight.shape, weight.context))  # n

    def update(self, index, weight, grad, state):
        self._update_count(index)
        kwargs = self._common_attrs(index)
        kwargs.update({"lamda1": self.lamda1, "beta": self.beta})
        z, n = state
        ftrl_update(weight, grad, z, n, out=weight, **kwargs)


@register
class Adamax(Optimizer):
    """AdaMax, the infinity-norm Adam variant (optimizer.py:1008)."""

    def __init__(self, learning_rate=0.002, beta1=0.9, beta2=0.999, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2

    def create_state(self, index, weight):
        return (zeros(weight.shape, weight.context, dtype=weight.dtype),  # mean
                zeros(weight.shape, weight.context, dtype=weight.dtype))  # variance

    def update(self, index, weight, grad, state):
        from . import ndarray as nd
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        t = self._index_update_count[index]
        lr /= (1. - self.beta1 ** t)
        grad = grad * self.rescale_grad + wd * weight
        if self.clip_gradient is not None:
            grad = invoke("clip", [grad], {"a_min": -self.clip_gradient,
                                           "a_max": self.clip_gradient})
        m_t, u_t = state
        m_t._data = (self.beta1 * m_t + (1. - self.beta1) * grad)._data
        u_t._data = nd.maximum(self.beta2 * u_t, nd.abs(grad))._data
        weight -= lr * m_t / (u_t + 1e-8)


@register
class Nadam(Optimizer):
    """Nesterov Adam (optimizer.py:1057)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, schedule_decay=0.004, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon
        self.schedule_decay = schedule_decay
        self.m_schedule = 1.

    def create_state(self, index, weight):
        return (zeros(weight.shape, weight.context, dtype=weight.dtype),  # mean
                zeros(weight.shape, weight.context, dtype=weight.dtype))  # variance

    def update(self, index, weight, grad, state):
        from . import ndarray as nd
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        t = self._index_update_count[index]
        grad = grad * self.rescale_grad + wd * weight
        if self.clip_gradient is not None:
            grad = invoke("clip", [grad], {"a_min": -self.clip_gradient,
                                           "a_max": self.clip_gradient})
        momentum_t = self.beta1 * (1. - 0.5 * (pow(0.96, t * self.schedule_decay)))
        momentum_t_1 = self.beta1 * (1. - 0.5 * (pow(0.96, (t + 1) * self.schedule_decay)))
        self.m_schedule = self.m_schedule * momentum_t
        m_schedule_next = self.m_schedule * momentum_t_1
        m_t, v_t = state
        m_t._data = (self.beta1 * m_t + (1. - self.beta1) * grad)._data
        v_t._data = (self.beta2 * v_t + (1. - self.beta2) * grad * grad)._data
        grad_prime = grad / (1. - self.m_schedule)
        m_t_prime = m_t / (1. - m_schedule_next)
        v_t_prime = v_t / (1. - pow(self.beta2, t))
        m_t_bar = (1. - momentum_t) * grad_prime + momentum_t_1 * m_t_prime
        weight -= lr * m_t_bar / (nd.sqrt(v_t_prime) + self.epsilon)


@register
class Test(Optimizer):
    """Trivial optimizer used by the reference's tests (optimizer.py Test)."""

    def __init__(self, **kwargs):
        super().__init__(**kwargs)

    def create_state(self, index, weight):
        return zeros(weight.shape, weight.context)

    def update(self, index, weight, grad, state):
        weight += grad * self.rescale_grad
        state._data = weight._data


create = Optimizer.create_optimizer  # pylint: disable=invalid-name


class Updater(object):
    """Per-key state store applying an optimizer; serializable for dist
    servers (reference optimizer.py:1142 + kvstore.py:460)."""

    def __init__(self, optimizer):
        self.optimizer = optimizer
        self.states = {}
        self.states_synced = {}

    def __call__(self, index, grad, weight, mark=_no_mark):
        """Update one key, or lists of keys, gradients and weights in one
        call (``mark``: see `Optimizer.update_multi`); returns how many
        update programs the optimizer dispatched."""
        if not isinstance(index, (list, tuple)):
            self.optimizer.update(index, weight, grad,
                                  self._state(index, weight))
            return 1
        states = [self._state(i, w) for i, w in zip(index, weight)]
        return self.optimizer.update_multi(index, weight, grad, states, mark)

    def _state(self, index, weight):
        if index not in self.states:
            self.states[index] = self.optimizer.create_state(index, weight)
            self.states_synced[index] = True
        elif not self.states_synced[index]:
            self.states[index] = self.sync_state_context(self.states[index],
                                                         weight.context)
            self.states_synced[index] = True
        return self.states[index]

    def sync_state_context(self, state, context):
        if isinstance(state, NDArray):
            return state.as_in_context(context)
        if isinstance(state, (tuple, list)):
            synced_state = (self.sync_state_context(i, context) for i in state)
            if isinstance(state, tuple):
                return tuple(synced_state)
            return list(synced_state)
        return state

    def set_states(self, states):
        """Load serialized states (numpy-backed pickle)."""
        states = pickle.loads(states)
        if isinstance(states, tuple) and len(states) == 2:
            states, self.optimizer = states

        def to_nd(v):
            if isinstance(v, numpy.ndarray):
                return NDArray(v)
            if isinstance(v, (tuple, list)):
                return type(v)(to_nd(x) for x in v)
            return v
        self.states = {k: to_nd(v) for k, v in states.items()}
        self.states_synced = dict.fromkeys(self.states.keys(), False)

    def get_states(self, dump_optimizer=False):
        """Serialize states (+optionally the optimizer itself)."""
        def to_np(v):
            if isinstance(v, NDArray):
                return v.asnumpy()
            if isinstance(v, (tuple, list)):
                return type(v)(to_np(x) for x in v)
            return v
        states = {k: to_np(v) for k, v in self.states.items()}
        return pickle.dumps((states, self.optimizer) if dump_optimizer
                            else states)


def get_updater(optimizer):
    """Returns a closure-style updater (reference optimizer.py get_updater)."""
    return Updater(optimizer)

"""Module: the concrete symbolic training module over ONE compiled executor.

Reference: python/mxnet/module/module.py (Module:39, bind:388, update:629) +
executor_group.py (DataParallelExecutorGroup:128).

TPU-native collapse: the reference splits each batch over N per-device
executors (decide_slices, executor_group.py:266) and reduces grads through
kvstore comm ops.  Here there is always ONE executor whose whole
fwd+bwd(+update) is a single XLA program; multi-device data parallelism is a
sharding annotation on the batch dimension over a jax Mesh
(mxnet_tpu.parallel.DataParallel), with gradient reduction compiled in as
psum — so Module code is identical for 1 chip or a pod slice.
"""
from __future__ import annotations

import logging

from ..base import MXNetError
from .. import context as ctx_mod
from .. import ndarray as nd
from .. import optimizer as opt
from ..context import cpu
from ..executor import Executor
from ..initializer import Uniform, InitDesc
from ..io import DataDesc
from ..model import save_checkpoint, load_checkpoint, BatchEndParam  # noqa: F401
from ..ndarray.ndarray import _wrap
from .base_module import BaseModule, _check_input_names


class Module(BaseModule):
    """Module over a Symbol (module.py:39)."""

    def __init__(self, symbol, data_names=("data",),
                 label_names=("softmax_label",), logger=logging,
                 context=None, work_load_list=None, fixed_param_names=None,
                 state_names=None, group2ctxs=None, compression_params=None):
        super().__init__(logger=logger)
        if context is None:
            context = ctx_mod.current_context()
        if isinstance(context, ctx_mod.Context):
            context = [context]
        self._context = context
        self._work_load_list = work_load_list

        self._symbol = symbol
        data_names = list(data_names) if data_names is not None else []
        label_names = list(label_names) if label_names is not None else []
        state_names = list(state_names) if state_names is not None else []
        fixed_param_names = list(fixed_param_names) \
            if fixed_param_names is not None else []

        _check_input_names(symbol, data_names, "data", True)
        _check_input_names(symbol, label_names, "label", False)
        _check_input_names(symbol, state_names, "state", True)
        _check_input_names(symbol, fixed_param_names, "fixed_param", True)

        arg_names = symbol.list_arguments()
        input_names = data_names + label_names + state_names
        self._param_names = [x for x in arg_names if x not in input_names]
        self._fixed_param_names = fixed_param_names
        self._aux_names = symbol.list_auxiliary_states()
        self._data_names = data_names
        self._label_names = label_names
        self._state_names = state_names
        self._output_names = symbol.list_outputs()

        self._arg_params = None
        self._aux_params = None
        self._params_dirty = False

        self._compression_params = compression_params
        self._optimizer = None
        self._kvstore = None
        self._update_on_kvstore = None
        self._updater = None
        self._preload_opt_states = None

        self._exec = None
        self._data_shapes = None
        self._label_shapes = None
        self._plan = None  # parallel.ShardingPlan (set_sharding_plan)
        self._dist_fused = False  # grads reduced inside the jitted step

    def set_sharding_plan(self, plan):
        """Attach a parallel.ShardingPlan; bind() will place data batch-
        sharded and params per plan.param_rules over the plan's mesh.  The
        replacement for DataParallelExecutorGroup/group2ctx: same Module
        code drives 1 chip or a pod slice."""
        assert not self.binded, "set_sharding_plan must precede bind"
        self._plan = plan

    def _maybe_auto_dist_plan(self):
        """Inside a launched multi-process job (jax.distributed env set),
        install a data-parallel ShardingPlan over the GLOBAL device mesh so
        gradients are reduced by compiled collectives inside the one fused
        step — the default dist path.  Per-key kvstore push/pull remains
        the compat veneer for direct KVStore use."""
        if self._plan is not None:
            return
        from .. import kvstore_dist
        if not kvstore_dist.init_distributed():
            return
        import jax
        if jax.process_count() <= 1:
            return
        import numpy as np
        from jax.sharding import Mesh
        from ..parallel.mesh import ShardingPlan
        mesh = Mesh(np.array(jax.devices()), ("dp",))
        self._plan = ShardingPlan(mesh, batch_axis="dp")
        self._dist_fused = True

    def _global_shapes(self, descs):
        """Scale local batch descriptors to global (dim0 x num_processes)
        in fused-dist mode."""
        if not self._dist_fused:
            return descs
        import jax
        n = jax.process_count()
        return [DataDesc(d.name, (d.shape[0] * n,) + tuple(d.shape[1:]),
                         d.dtype, d.layout) for d in descs]

    def _build_sharding_map(self):
        if self._plan is None:
            return None
        plan = self._plan
        shardings = {}
        for d in self._global_shapes(self._data_shapes):
            shardings[d.name] = plan.data_sharding(d.shape)
        for l in self._global_shapes(self._label_shapes or []):
            shardings[l.name] = plan.data_sharding(l.shape)
        arg_shapes, _, aux_shapes = self._symbol.infer_shape(
            **{d.name: d.shape for d in self._global_shapes(self._data_shapes)},
            **({l.name: l.shape
                for l in self._global_shapes(self._label_shapes)}
               if self._label_shapes else {}))
        for name, s in zip(self._symbol.list_arguments(), arg_shapes):
            if name not in shardings:
                shardings[name] = plan.param_sharding(name, tuple(s))
        for name, s in zip(self._aux_names, aux_shapes):
            shardings[name] = plan.param_sharding(name, tuple(s))
        return shardings

    # ------------------------------------------------------------------
    @staticmethod
    def load(prefix, epoch, load_optimizer_states=False, **kwargs):
        """Create a Module from a checkpoint (module.py load)."""
        sym, args, auxs = load_checkpoint(prefix, epoch)
        mod = Module(symbol=sym, **kwargs)
        mod._arg_params = args
        mod._aux_params = auxs
        mod.params_initialized = True
        if load_optimizer_states:
            mod._preload_opt_states = "%s-%04d.states" % (prefix, epoch)
        return mod

    def save_checkpoint(self, prefix, epoch, save_optimizer_states=False):
        """Save symbol + params (+optimizer states) (module.py:255)."""
        self._sync_params_from_devices()
        save_checkpoint(prefix, epoch, self.symbol, self._arg_params,
                        self._aux_params)
        if save_optimizer_states:
            state_name = "%s-%04d.states" % (prefix, epoch)
            self.save_optimizer_states(state_name)

    # ------------------------------------------------------------------
    @property
    def data_names(self):
        return self._data_names

    @property
    def label_names(self):
        return self._label_names

    @property
    def output_names(self):
        return self._output_names

    @property
    def data_shapes(self):
        assert self.binded
        return self._data_shapes

    @property
    def label_shapes(self):
        assert self.binded
        return self._label_shapes

    @property
    def output_shapes(self):
        assert self.binded
        shapes = {d.name: d.shape for d in self._data_shapes}
        if self._label_shapes:
            shapes.update({l.name: l.shape for l in self._label_shapes})
        _, out_shapes, _ = self._symbol.infer_shape(**shapes)
        return list(zip(self._output_names, [tuple(s) for s in out_shapes]))

    # ------------------------------------------------------------------
    def get_params(self):
        assert self.binded and self.params_initialized
        if self._params_dirty:
            self._sync_params_from_devices()
        return (self._arg_params, self._aux_params)

    def init_params(self, initializer=Uniform(0.01), arg_params=None,
                    aux_params=None, allow_missing=False, force_init=False,
                    allow_extra=False):
        if self.params_initialized and not force_init:
            logging.warning("Parameters already initialized and force_init=False. "
                            "init_params call ignored.")
            return
        assert self.binded, "call bind before initializing the parameters"

        attrs = self._symbol.attr_dict()

        def _impl(name, arr, cache):
            if cache is not None:
                if name in cache:
                    cache_arr = cache[name]
                    if cache_arr is not arr:
                        cache_arr.copyto(arr)
                else:
                    if not allow_missing:
                        raise RuntimeError("%s is not presented" % name)
                    if initializer is not None:
                        initializer(InitDesc(name, attrs.get(name, {})), arr)
            else:
                if initializer is not None:
                    initializer(InitDesc(name, attrs.get(name, {})), arr)

        for name in self._param_names:
            _impl(name, self._exec.arg_dict[name], arg_params)
        for name in self._aux_names:
            _impl(name, self._exec.aux_dict[name], aux_params)

        self.params_initialized = True
        self._params_dirty = True
        self._sync_params_from_devices()

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True, allow_extra=False):
        if not allow_missing:
            self.init_params(initializer=None, arg_params=arg_params,
                             aux_params=aux_params, allow_missing=allow_missing,
                             force_init=force_init, allow_extra=allow_extra)
            return
        if self.params_initialized and not force_init:
            logging.warning("Parameters already initialized and force_init=False. "
                            "set_params call ignored.")
            return
        for name, arr in (arg_params or {}).items():
            if name in self._exec.arg_dict:
                arr.copyto(self._exec.arg_dict[name])
            elif not allow_extra:
                raise MXNetError("unknown arg %r" % name)
        for name, arr in (aux_params or {}).items():
            if name in self._exec.aux_dict:
                arr.copyto(self._exec.aux_dict[name])
            elif not allow_extra:
                raise MXNetError("unknown aux %r" % name)
        self.params_initialized = True
        self._params_dirty = True
        self._sync_params_from_devices()

    # ------------------------------------------------------------------
    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        """Create the compiled executor (module.py:388 → one XLA program)."""
        if force_rebind:
            self._reset_bind()
        if self.binded:
            self.logger.warning("Already bound, ignoring bind()")
            return

        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        assert not (not for_training and inputs_need_grad)

        self._data_shapes = [x if isinstance(x, DataDesc) else DataDesc(*x)
                             for x in data_shapes]
        if label_shapes is not None:
            self._label_shapes = [x if isinstance(x, DataDesc) else DataDesc(*x)
                                  for x in label_shapes]
        else:
            self._label_shapes = None

        # in a launched dist job, default to the fused sharded step:
        # user-facing shapes stay LOCAL, the compiled program is GLOBAL
        self._maybe_auto_dist_plan()
        if len(self._context) > 1 and self._plan is None:
            # one executor binds on one device: a context list without a
            # plan would train on self._context[0] alone, in silence
            raise MXNetError(
                "Module(context=%s): %d contexts but no sharding plan. "
                "Call set_sharding_plan(ShardingPlan(make_mesh({'dp': "
                "%d}), batch_axis='dp')) before bind() to train "
                "data-parallel across them, or pass one context."
                % (self._context, len(self._context),
                   len(self._context)))
        gdata = self._global_shapes(self._data_shapes)
        glabel = self._global_shapes(self._label_shapes or []) or None

        shapes = {d.name: d.shape for d in gdata}
        if glabel:
            shapes.update({l.name: l.shape for l in glabel})
        types = {d.name: d.dtype for d in gdata}
        if glabel:
            types.update({l.name: l.dtype for l in glabel})

        arg_shapes, _, aux_shapes = self._symbol.infer_shape(**shapes)
        arg_types, _, aux_types = self._symbol.infer_type(**types)
        arg_names = self._symbol.list_arguments()

        import jax.numpy as jnp
        ctx = self._context[0]
        req = {}
        for name in arg_names:
            if name in self._data_names:
                req[name] = "write" if inputs_need_grad else "null"
            elif name in self._label_names or name in self._state_names:
                req[name] = "null"
            elif name in self._fixed_param_names:
                req[name] = "null"
            else:
                req[name] = grad_req if for_training else "null"

        args = {}
        with ctx:
            for name, s, t in zip(arg_names, arg_shapes, arg_types):
                args[name] = _wrap(jnp.zeros(tuple(s), t), ctx)
            aux = {}
            for name, s, t in zip(self._aux_names, aux_shapes, aux_types):
                aux[name] = _wrap(jnp.zeros(tuple(s), t), ctx)

        self._exec = Executor(self._symbol, ctx, args, None, req, aux,
                              sharding=self._build_sharding_map())
        self.binded = True

        if shared_module is not None and shared_module.params_initialized:
            self.set_params(*shared_module.get_params())
            if shared_module.optimizer_initialized:
                self.borrow_optimizer(shared_module)
        elif self._arg_params is not None:
            # params preloaded (e.g. Module.load)
            self.params_initialized = True
            for name in self._param_names:
                if name in self._arg_params:
                    self._arg_params[name].copyto(self._exec.arg_dict[name])
            for name in self._aux_names:
                if name in self._aux_params:
                    self._aux_params[name].copyto(self._exec.aux_dict[name])

    def _reset_bind(self):
        self.binded = False
        self._exec = None

    def reshape(self, data_shapes, label_shapes=None):
        """Re-bind for new shapes; XLA re-traces per shape automatically."""
        assert self.binded
        if self.params_initialized and self._params_dirty:
            self._sync_params_from_devices()
        arg_params, aux_params = (self._arg_params, self._aux_params) \
            if self.params_initialized else (None, None)
        self._reset_bind()
        self.bind(data_shapes, label_shapes, self.for_training,
                  self.inputs_need_grad, force_rebind=False)
        if arg_params is not None:
            self._arg_params, self._aux_params = arg_params, aux_params
            self.params_initialized = True
            for name in self._param_names:
                if name in arg_params:
                    arg_params[name].copyto(self._exec.arg_dict[name])
            for name in self._aux_names:
                if name in aux_params:
                    aux_params[name].copyto(self._exec.aux_dict[name])

    # ------------------------------------------------------------------
    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        assert self.binded and self.params_initialized
        if self.optimizer_initialized and not force_init:
            self.logger.warning("optimizer already initialized, ignoring...")
            return
        if self._params_dirty:
            self._sync_params_from_devices()

        from ..model import _create_kvstore
        (kvstore, update_on_kvstore) = _create_kvstore(
            kvstore, len(self._context), self._exec.arg_dict)
        if self._dist_fused:
            # gradients are reduced by compiled collectives inside the
            # jitted step; the store would double-count them.  Keep the
            # store only for rank/num_workers/barrier bookkeeping.
            update_on_kvstore = False

        batch_size = self._data_shapes[0].shape[0]
        if kvstore and "dist" in kvstore.type and "_async" not in kvstore.type:
            batch_size *= kvstore.num_workers
        rescale_grad = 1.0 / batch_size

        idx2name = {i: n for i, n in enumerate(self._param_names)}
        if isinstance(optimizer, str):
            optimizer_params = dict(optimizer_params)
            if "rescale_grad" not in optimizer_params:
                optimizer_params["rescale_grad"] = rescale_grad
            optimizer = opt.create(optimizer, sym=self.symbol,
                                   param_idx2name=idx2name,
                                   **optimizer_params)
        else:
            assert isinstance(optimizer, opt.Optimizer)
            if optimizer.rescale_grad != rescale_grad:
                self.logger.warning(
                    "Optimizer created manually outside Module but rescale_grad "
                    "is not normalized to 1.0/batch_size/num_workers (%s vs. %s). "
                    "Is this intended?", optimizer.rescale_grad, rescale_grad)
            if not optimizer.idx2name:
                optimizer.idx2name = idx2name.copy()

        self._optimizer = optimizer
        self._kvstore = kvstore
        self._update_on_kvstore = update_on_kvstore
        self._updater = None

        if kvstore:
            if self._compression_params:
                kvstore.set_gradient_compression(self._compression_params)
            if update_on_kvstore:
                kvstore.set_optimizer(self._optimizer)
            # init keys with current weights
            for idx, name in enumerate(self._param_names):
                kvstore.init(name, self._exec.arg_dict[name])
        if not update_on_kvstore:
            self._updater = opt.get_updater(optimizer)

        self.optimizer_initialized = True

        if self._preload_opt_states is not None:
            self.load_optimizer_states(self._preload_opt_states)
            self._preload_opt_states = None

    def borrow_optimizer(self, shared_module):
        assert shared_module.optimizer_initialized
        self._optimizer = shared_module._optimizer
        self._kvstore = shared_module._kvstore
        self._update_on_kvstore = shared_module._update_on_kvstore
        self._updater = shared_module._updater
        self.optimizer_initialized = True

    # ------------------------------------------------------------------
    def forward(self, data_batch, is_train=None):
        assert self.binded and self.params_initialized
        if is_train is None:
            is_train = self.for_training

        # allow shape changes (bucketing / final partial batch): re-binding is
        # cheap — jit caches one program per shape signature
        curr_shapes = [d.shape for d in self._data_shapes]
        new_shapes = [d.shape for d in data_batch.data]
        if curr_shapes != new_shapes:
            new_dshape = [DataDesc(i.name, shape, i.dtype, i.layout)
                          for i, shape in zip(self._data_shapes, new_shapes)]
            if data_batch.label is not None and self._label_shapes:
                new_lshape = [DataDesc(i.name, j.shape, i.dtype, i.layout)
                              for i, j in zip(self._label_shapes,
                                              data_batch.label)]
            else:
                new_lshape = None
            self.reshape(new_dshape, new_lshape)

        feed = {}
        for name, arr in zip(self._data_names, data_batch.data):
            feed[name] = arr
        if self._label_shapes and data_batch.label is not None:
            for name, arr in zip(self._label_names, data_batch.label):
                feed[name] = arr
        self._exec.forward(is_train=is_train, **feed)
        self._params_dirty = True

    def backward(self, out_grads=None):
        assert self.binded and self.params_initialized
        self._exec.backward(out_grads=out_grads)

    def update(self):
        """Apply optimizer to gradients (module.py:629 → model.py:126)."""
        assert self.binded and self.params_initialized \
            and self.optimizer_initialized
        from ..telemetry import step as step_mod
        self._params_dirty = True
        # step attribution: self-time is the optimizer math — nested
        # kv_push/kv_pull phases (kvstore.py) subtract themselves.  One
        # span feeds the phase histogram, the timeline ring, the
        # profiler's trace and mx.profiler's "update" region
        with step_mod.active_phase("optimizer",
                                   chrome=("update", "update")) as sp:
            self._update_impl(sp)

    def _update_impl(self, sp):
        """One updater call with every parameter that has a gradient
        (on the kvstore, one push and pull each).  ``sp`` is what
        ``active_phase`` yielded, the open ``fit.optimizer`` span: the
        number of update programs dispatched rides it as ``updates``
        (1 where the optimizer has a multi-tensor rule and takes it),
        and with the timeline plane on each is marked in the profiler's
        trace, ``mx:update/multi_tensor`` or ``mx:update/<parameter>``."""
        mark = sp.child
        updates = 0
        if self._update_on_kvstore:
            for name in self._param_names:
                if self._exec.grad_dict.get(name) is None:
                    continue
                with mark("update/" + name):
                    self._kvstore.push(name, self._exec.grad_dict[name])
                    self._kvstore.pull(name, out=self._exec.arg_dict[name])
                updates += 1
        else:
            if self._kvstore and not self._dist_fused:
                for name in self._param_names:
                    g = self._exec.grad_dict.get(name)
                    if g is None:
                        continue
                    self._kvstore.push(name, g)
                    self._kvstore.pull(name, out=g)
            names = self._param_names
            idxs = [idx for idx, name in enumerate(names)
                    if self._exec.grad_dict.get(name) is not None]
            if idxs:
                updates = self._updater(
                    idxs, [self._exec.grad_dict[names[i]] for i in idxs],
                    [self._exec.arg_dict[names[i]] for i in idxs],
                    mark=lambda idx: mark(
                        "update/" + ("multi_tensor" if idx is None
                                     else names[idx])))
        sp.args = {"updates": updates}

    def get_outputs(self, merge_multi_context=True):
        assert self.binded and self.params_initialized
        outs = self._exec.outputs
        if outs is None:
            return []
        return outs  # may be lazy (_LazyOutputs); touching it materializes

    def get_input_grads(self, merge_multi_context=True):
        assert self.binded and self.params_initialized and self.inputs_need_grad
        return [self._exec.grad_dict[n] for n in self._data_names]

    def get_states(self, merge_multi_context=True):
        assert self.binded and self.params_initialized
        return [self._exec.arg_dict[n] for n in self._state_names]

    def set_states(self, states=None, value=None):
        assert self.binded and self.params_initialized
        if states is not None:
            for name, s in zip(self._state_names, states):
                arr = s[0] if isinstance(s, (list, tuple)) else s
                self._exec.arg_dict[name]._data = \
                    arr.as_in_context(self._exec.arg_dict[name].context)._data
        else:
            for name in self._state_names:
                self._exec.arg_dict[name]._data = \
                    nd.full(self._exec.arg_dict[name].shape, value,
                            ctx=self._exec.arg_dict[name].context)._data

    def update_metric(self, eval_metric, labels):
        preds = {name: out for name, out in zip(self._output_names,
                                                self.get_outputs())}
        label_dict = {name: l for name, l in zip(self._label_names,
                                                 labels or [])}
        eval_metric.update_dict(label_dict, preds)

    def _sync_params_from_devices(self):
        if self._exec is None:
            return
        self._arg_params = {n: self._exec.arg_dict[n].copy()
                            for n in self._param_names}
        self._aux_params = {n: self._exec.aux_dict[n].copy()
                            for n in self._aux_names}
        self._params_dirty = False

    def save_optimizer_states(self, fname):
        assert self.optimizer_initialized
        if self._update_on_kvstore:
            self._kvstore.save_optimizer_states(fname)
        else:
            with open(fname, "wb") as fout:
                fout.write(self._updater.get_states())

    def load_optimizer_states(self, fname):
        assert self.optimizer_initialized
        if self._update_on_kvstore:
            self._kvstore.load_optimizer_states(fname)
        else:
            with open(fname, "rb") as fin:
                self._updater.set_states(fin.read())

    def install_monitor(self, mon):
        assert self.binded
        mon.install(self._exec)

    @property
    def _executor(self):
        return self._exec

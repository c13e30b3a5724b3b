"""Executor: compiled symbolic runtime.

Reference: src/executor/graph_executor.cc + include/mxnet/executor.h:53-129.
The reference compiles a Symbol by appending a gradient subgraph
(nnvm::pass::Gradient), planning memory, then pushing one engine op per node
per Forward/Backward call.

TPU-native collapse (SURVEY §7, BASELINE north star): the whole graph —
forward, backward (via jax.vjp), gradient accumulation (grad_req add/write),
and aux-state updates — is ONE jit-compiled XLA computation.  There is no
per-op dispatch, no memory planner (XLA buffer assignment + donated gradient
buffers replace PlanMemory/inplace detection), and backward-with-recompute
never happens: forward(is_train=True) is lazy and the fused fwd+bwd program
runs once per step at backward() time, producing outputs AND gradients.

Multi-device data parallelism does not use N executors like the reference's
DataParallelExecutorGroup (executor_group.py:128); instead Module binds ONE
executor whose arrays are sharded over a mesh (see mxnet_tpu.parallel) —
batch-split + gradient allreduce become sharding annotations + psum compiled
into this same XLA program.
"""
from __future__ import annotations

import numpy as _np

from .base import MXNetError, named_program
from .context import Context, current_context
from . import random as _random
from .ndarray.ndarray import NDArray, _wrap
from .symbol.symbol import Symbol, _topo
from .telemetry import timeline as _timeline

__all__ = ["Executor", "build_graph_fn"]


_TM_CACHE = {}          # memoized instrument children (see telemetry.bound)


_XLA_TRACES_EVER = 0


def xla_traces_ever():
    """Process-lifetime XLA trace count across every jitted graph
    program, counted regardless of telemetry state.  Zero means no
    program has compiled yet — the 'serving entrypoint owns process
    bring-up' signal MXNET_AOT_XLA_CACHE='auto' keys on."""
    return _XLA_TRACES_EVER


def _count_xla_trace():
    """Trace-time side effect shared by the executor's jitted programs
    (same contract as CachedOp's counter: fires once per XLA compile,
    never on cached dispatches)."""
    global _XLA_TRACES_EVER
    _XLA_TRACES_EVER += 1
    from . import telemetry
    if telemetry.enabled():
        telemetry.bound(
            _TM_CACHE, "xla_traces",
            lambda: telemetry.counter(
                "mxnet_xla_traces_total",
                "XLA program traces (compiles) across the process's "
                "jitted graph programs (CachedOp + Executor); cached "
                "dispatches never move this")).inc()


def _count_dispatch(kind):
    """One executor graph dispatch (forward / forward_backward);
    memoized child, no registry lock on the warm path."""
    from . import telemetry
    if telemetry.enabled():
        telemetry.bound(
            _TM_CACHE, ("dispatch", kind),
            lambda: telemetry.counter(
                "mxnet_executor_dispatch_total",
                "Executor graph dispatches by kind",
                labelnames=("kind",)).labels(kind=kind)).inc()


def build_graph_fn(symbol, arg_names, aux_names):
    """Compile a Symbol DAG into a pure function
    ``fn(arg_vals, aux_vals, key, training) -> (outputs, new_aux)``.

    This is the attach_op_execs_pass.cc analog: one interpreter over registry
    impls, meant to run under jax.jit so the whole graph becomes one XLA
    computation.  Aux-state mutation (mutate_aux) is threaded functionally:
    the updated value replaces the aux entry for downstream readers and is
    returned for write-back by the caller.

    Sparse-gradient support (see Executor._get_fwd_bwd): ``probes`` maps a
    node's id to an array ADDED to that node's first output — differentiating
    the probe yields the cotangent arriving at that output without making the
    node's own inputs wrt leaves.  ``capture`` lists node ids whose (input
    values, first output) to return so op-declared sparse backwards can run
    on the same traced values; when non-empty the return becomes
    ``(outputs, new_aux, captures)``."""
    topo = _topo(symbol._outputs)
    var_kind = {}   # node id -> ('arg', name) | ('aux', name)
    aux_set = set(aux_names)
    for n in topo:
        if n.op is None:
            var_kind[id(n)] = ("aux" if n.name in aux_set else "arg", n.name)
    sto_index = {}
    for n in topo:
        if n.op is not None and n.op.stochastic:
            sto_index[id(n)] = len(sto_index)
    heads = symbol._outputs

    def graph_fn(arg_vals, aux_vals, key, training, probes=None, capture=()):
        import jax
        env = {}
        captured = {}
        aux_env = dict(zip(aux_names, aux_vals))
        argd = dict(zip(arg_names, arg_vals))
        for n in topo:
            if n.op is None:
                kind, name = var_kind[id(n)]
                env[(id(n), 0)] = argd[name] if kind == "arg" else aux_env[name]
                continue
            ins = [env[(id(i), ix)] for (i, ix) in n.inputs]
            attrs = {k: v for k, v in n.attrs.items() if not k.startswith("__")}
            attrs = n.op.normalize(attrs)
            f = n.op.bound(attrs, training)
            # the node's name rides every HLO op it lowers to (op_name
            # metadata), so a device trace can say which layer a fusion
            # belongs to; metadata only, the program is unchanged (and
            # so is the persistent compile cache's key: a cached
            # executable keeps the names it was first compiled with)
            with jax.named_scope(n.name):
                if n.op.stochastic:
                    k = jax.random.fold_in(key, sto_index[id(n)])
                    outs = f(k, *ins)
                else:
                    outs = f(*ins)
            if probes is not None and id(n) in probes:
                outs = (outs[0] + probes[id(n)],) + tuple(outs[1:])
            if id(n) in capture:
                captured[id(n)] = (tuple(ins), outs[0])
            for i, o in enumerate(outs):
                env[(id(n), i)] = o
            for in_idx, out_idx in n.op.mutate_aux.items():
                src, _ = n.inputs[in_idx]
                if src.op is None and var_kind[id(src)][0] == "aux":
                    aux_env[var_kind[id(src)][1]] = outs[out_idx]
        out_vals = tuple(env[(id(n), ix)] for (n, ix) in heads)
        new_aux = tuple(aux_env[a] for a in aux_names)
        if capture:
            return out_vals, new_aux, tuple(captured[c] for c in capture)
        return out_vals, new_aux

    # deterministic graphs never consume the key: callers use this to
    # skip the per-dispatch eager fold_in (~0.35 ms on CPU — measured
    # at ~45% of a small batched-inference dispatch, perf/serve_bench)
    graph_fn.stochastic = bool(sto_index)
    return graph_fn


class Executor:
    def __init__(self, symbol, ctx, args, args_grad=None, grad_req="write",
                 aux_states=None, group2ctx=None, shared_exec=None,
                 sharding=None):
        import jax
        self._symbol = symbol
        self._ctx = ctx if isinstance(ctx, Context) else (ctx or current_context())
        # optional {arg_or_aux_name: jax.sharding.Sharding} placement map
        # (built by Module from a parallel.ShardingPlan).  Computation follows
        # data under jit: batch-sharded data + replicated params = data
        # parallelism with the gradient psum compiled in; param_rules give
        # tensor parallelism.  Gradients are pinned to their param's sharding
        # via with_sharding_constraint (forcing the cross-replica reduce).
        self._sharding = dict(sharding) if sharding else None
        self.arg_names = symbol.list_arguments()
        self.aux_names = symbol.list_auxiliary_states()
        self.output_names = symbol.list_outputs()

        self.arg_dict = self._as_dict(args, self.arg_names, "args")
        self.aux_dict = self._as_dict(aux_states or {}, self.aux_names, "aux")

        if isinstance(grad_req, str):
            self._grad_req = {n: grad_req for n in self.arg_names}
        elif isinstance(grad_req, (list, tuple)):
            self._grad_req = dict(zip(self.arg_names, grad_req))
        else:
            self._grad_req = {n: grad_req.get(n, "null") for n in self.arg_names}

        if args_grad is None:
            args_grad = {}
        self.grad_dict = self._as_dict(args_grad, self.arg_names, "grads",
                                       allow_missing=True)

        self.outputs = []
        self._monitor = None
        self._fwd_jit = {}
        self._fwd_bwd_jit = {}
        self._base_key = None
        self._step = 0
        self._pending_train_fwd = False
        # span seam: each graph dispatch is one timeline.span (ring,
        # profiler trace, mx.profiler's Chrome ring, the request trace
        # current on the thread); None = plane off
        self._tl = _timeline.get() if _timeline.enabled() else None
        self._build()
        self._resolve_grad_storage()
        for n in self.arg_names:
            if self._grad_req.get(n, "null") != "null" and n not in self.grad_dict:
                if self._grad_storage.get(n, "dense") != "dense":
                    # row-sparse gradient: pre-allocating a dense
                    # zeros_like would materialize the (vocab, dim) array
                    # this path exists to avoid; start empty, backward()
                    # writes the real (indices, values) pair
                    from .ndarray import sparse as _sp
                    self.grad_dict[n] = _sp.zeros(
                        "row_sparse", self.arg_dict[n].shape, self._ctx,
                        self.arg_dict[n].dtype)
                else:
                    import jax.numpy as jnp
                    self.grad_dict[n] = _wrap(
                        jnp.zeros_like(self.arg_dict[n]._data), self._ctx)
        if self._sharding:
            self._apply_sharding()

    # ------------------------------------------------------------------
    def _as_dict(self, values, names, what, allow_missing=False):
        if isinstance(values, dict):
            out = {}
            for n in names:
                if n in values:
                    out[n] = values[n]
                elif not allow_missing:
                    raise MXNetError("%s: missing %r" % (what, n))
            return out
        values = list(values or [])
        if not allow_missing and len(values) != len(names):
            raise MXNetError("%s: expected %d entries, got %d"
                             % (what, len(names), len(values)))
        return {n: v for n, v in zip(names, values) if v is not None}

    @staticmethod
    def _spans_processes(sh):
        """True when a sharding's mesh includes non-addressable devices
        (multi-host jax.distributed job)."""
        try:
            return len(sh.mesh.devices.flat) > len(sh.addressable_devices)
        except AttributeError:
            return False

    def _place_global(self, value, sh):
        """Place a host value with GLOBAL shape under a sharding (used for
        bind-time arg/aux/grad buffers)."""
        import jax
        if sh is None:
            return jax.device_put(value, self._ctx.jax_device())
        if self._spans_processes(sh):
            host = _np.asarray(value)
            return jax.make_array_from_callback(
                host.shape, sh, lambda idx: host[idx])
        return jax.device_put(value, sh)

    def _place_local(self, value, sh):
        """Place this process's LOCAL portion (its batch slice for
        dp-sharded inputs, the full value for replicated entries) — the
        TPU-native equivalent of the reference's per-worker data partition
        (kvstore_dist.h rank/size record sharding)."""
        import jax
        if sh is None:
            return jax.device_put(value, self._ctx.jax_device())
        if self._spans_processes(sh):
            return jax.make_array_from_process_local_data(
                sh, _np.asarray(value))
        return jax.device_put(value, sh)

    @staticmethod
    def _localize(arr):
        """Host-readable view of a possibly multi-process array: the full
        value when replicated, this process's dim0 rows when dp-sharded
        (metrics in dist training are per-worker, like the reference)."""
        if getattr(arr, "is_fully_addressable", True):
            return arr
        if getattr(arr, "is_fully_replicated", False):
            return arr.addressable_shards[0].data
        import jax
        shards = sorted(arr.addressable_shards,
                        key=lambda s: (s.index[0].start or 0)
                        if s.index else 0)
        local = _np.concatenate([_np.asarray(s.data) for s in shards],
                                axis=0)
        return jax.device_put(local, shards[0].data.devices().pop())

    def _apply_sharding(self):
        from .ndarray.sparse import BaseSparseNDArray
        for name, sh in self._sharding.items():
            for d in (self.arg_dict, self.aux_dict, self.grad_dict):
                if name in d and not isinstance(d[name], BaseSparseNDArray):
                    d[name]._data = self._place_global(d[name]._data, sh)

    # ------------------------------------------------------------------
    def _build(self):
        self._topo = _topo(self._symbol._outputs)
        self._graph_fn = build_graph_fn(self._symbol, self.arg_names,
                                        self.aux_names)

    def _resolve_grad_storage(self):
        """Gradient storage-type inference — the FInferStorageType analog
        (include/mxnet/op_attr_types.h, dispatched per-op in the reference).

        Per grad-requesting arg:
          * 'rsp_stored'  — the arg itself is bound row-sparse; jax.vjp over
            its RSPValue pytree yields an O(nnz) cotangent on the .data leaf
            directly (no special machinery).
          * ('rsp_probe', node, pos, attrs, spec) — the arg is dense-stored
            but its single consumer declares an O(nnz) row-sparse backward
            for it (Embedding sparse_grad=True, dot(csr, w)); the dense vjp
            for this arg is skipped and replaced by the op's sparse bwd fed
            with the consumer's output cotangent (probe mechanism).
          * 'dense' — everything else.
        """
        from .ndarray.sparse import RowSparseNDArray
        self._grad_storage = {}
        var_nodes = {n.name: n for n in self._topo if n.op is None}
        for name in self.arg_names:
            if self._grad_req.get(name, "null") == "null":
                continue
            arr = self.arg_dict[name]
            if isinstance(arr, RowSparseNDArray):
                if self._grad_req[name] == "add":
                    raise MXNetError(
                        "grad_req='add' is not supported for row-sparse "
                        "gradients (%r): successive batches touch "
                        "different rows" % name)
                self._grad_storage[name] = "rsp_stored"
                continue
            storage = "dense"
            vnode = var_nodes.get(name)
            consumers = []
            if vnode is not None:
                for node in self._topo:
                    if node.op is None:
                        continue
                    for pos, (src, _ix) in enumerate(node.inputs):
                        if src is vnode:
                            consumers.append((node, pos))
            user_buf = self.grad_dict.get(name)   # pre-supplied args_grad
            if user_buf is not None \
                    and not isinstance(user_buf, RowSparseNDArray):
                # the caller bound a DENSE gradient buffer (the bind
                # args_grad contract): keep the dense vjp writing into it
                # rather than silently orphaning the buffer
                self._grad_storage[name] = "dense"
                continue
            if len(consumers) == 1 and arr.ndim >= 2:
                node, pos = consumers[0]
                spec = node.op.sparse_grad.get(pos)
                if spec is not None:
                    attrs = node.op.normalize(
                        {k: v for k, v in node.attrs.items()
                         if not k.startswith("__")})
                    in_stypes = []
                    for (src, _ix) in node.inputs:
                        st = "default"
                        if src.op is None:
                            a = self.arg_dict.get(src.name)
                            if a is None:
                                a = self.aux_dict.get(src.name)
                            st = getattr(a, "stype", "default")
                        in_stypes.append(st)
                    if spec["stype"](attrs, in_stypes) == "row_sparse":
                        if self._grad_req[name] == "add":
                            raise MXNetError(
                                "grad_req='add' is not supported for "
                                "row-sparse gradients (%r)" % name)
                        storage = ("rsp_probe", node, pos, attrs, spec)
            self._grad_storage[name] = storage

    def _key(self):
        import jax
        if self._base_key is None:
            self._base_key = _random.next_key()
        if not self._graph_fn.stochastic:
            # no stochastic ops: the key is a dead jit input, so reuse
            # one constant instead of paying an eager fold_in per step
            return self._base_key
        self._step += 1
        return jax.random.fold_in(self._base_key, self._step)

    def _get_fwd(self, training):
        import jax
        fn = self._fwd_jit.get(training)
        if fn is None:
            g = self._graph_fn

            def fwd(a, x, k):
                _count_xla_trace()  # side effect: once per compile
                return g(a, x, k, training)

            fn = jax.jit(named_program(fwd, "mx_forward"))
            self._fwd_jit[training] = fn
        return fn

    def _get_fwd_bwd(self, with_head_grads):
        import jax
        import jax.numpy as jnp
        fn = self._fwd_bwd_jit.get(with_head_grads)
        if fn is None:
            from .ops.sparse_vals import RSPValue
            g = self._graph_fn
            grad_names = [n for n in self.arg_names
                          if self._grad_req.get(n, "null") != "null"]
            storage = self._grad_storage
            # wrt leaves: dense args AND rsp-stored args (whose RSPValue
            # pytree yields an O(nnz) .data cotangent); probe-class args are
            # NOT differentiated — their grad comes from the op's sparse bwd
            wrt_names = [n for n in grad_names
                         if not isinstance(storage[n], tuple)]
            probe_specs = [(n,) + tuple(storage[n][1:]) for n in grad_names
                           if isinstance(storage[n], tuple)]
            probe_order = [n for (n, *_r) in probe_specs]
            wrt_idx = [self.arg_names.index(n) for n in wrt_names]
            dense_names = [n for n in grad_names if storage[n] == "dense"]
            req_add = {n: self._grad_req[n] == "add" for n in dense_names}
            self._grad_names = grad_names
            self._dense_grad_names = dense_names
            grad_shards = {n: self._sharding.get(n) for n in dense_names} \
                if self._sharding else {}
            cap_ids = tuple(id(node) for (_n, node, _p, _a, _s)
                            in probe_specs)

            from . import config
            mirror = config.get("MXNET_BACKWARD_DO_MIRROR")

            def fwd_bwd(arg_vals, aux_vals, key, head_grads, old_grads):
                _count_xla_trace()  # side effect: once per compile
                if cap_ids:
                    # trace-time shape probe: the consumer outputs' avals
                    # give each probe's shape/dtype
                    cap_avals = jax.eval_shape(
                        lambda av: g(av, aux_vals, key, True, None,
                                     cap_ids), arg_vals)[2]
                    probe_zeros = tuple(jnp.zeros(c[1].shape, c[1].dtype)
                                        for c in cap_avals)
                else:
                    probe_zeros = ()

                def f(*wrt):
                    av = list(arg_vals)
                    for i, w in zip(wrt_idx, wrt):
                        av[i] = w
                    if cap_ids:
                        probes = dict(zip(cap_ids, wrt[len(wrt_idx):]))
                        outs, new_aux, caps = g(tuple(av), aux_vals, key,
                                                True, probes, cap_ids)
                    else:
                        outs, new_aux = g(tuple(av), aux_vals, key, True)
                        caps = ()
                    return outs, (new_aux, caps)
                if mirror:
                    # MXNET_BACKWARD_DO_MIRROR ≡ rematerialization: recompute
                    # forward activations in backward instead of storing
                    # them (graph_executor.cc:282 mirror pass → jax.checkpoint)
                    f = jax.checkpoint(f)
                wrt_vals = tuple(arg_vals[i] for i in wrt_idx) + probe_zeros
                outs, vjp, (new_aux, caps) = jax.vjp(f, *wrt_vals,
                                                     has_aux=True)
                if head_grads is None:
                    # backward() with no out_grads: seed ones (loss heads'
                    # custom vjps ignore the cotangent, reference semantics)
                    head_grads = tuple(jnp.ones_like(o) for o in outs)
                cots = vjp(tuple(head_grads))
                by_name = dict(zip(wrt_names, cots[:len(wrt_idx)]))
                probe_cots = cots[len(wrt_idx):]
                dense_old = dict(zip(dense_names, old_grads))
                new_grads = []
                for n in grad_names:
                    st = storage[n]
                    if st == "dense":
                        gv = by_name[n]
                        if req_add[n]:
                            gv = dense_old[n] + gv
                        sh = grad_shards.get(n)
                        if sh is not None:
                            # pin grads to their param's sharding: for
                            # replicated params under a dp mesh this
                            # compiles the allreduce in
                            gv = jax.lax.with_sharding_constraint(gv, sh)
                        new_grads.append(gv)
                    elif st == "rsp_stored":
                        cot = by_name[n]     # RSPValue-structured cotangent
                        orig = arg_vals[self.arg_names.index(n)]
                        new_grads.append(
                            RSPValue(cot.data, orig.indices, orig.shape))
                    else:                    # rsp_probe
                        k = probe_order.index(n)
                        (_nm, _node, _pos, attrs, spec) = probe_specs[k]
                        in_vals, _out0 = caps[k]
                        new_grads.append(
                            spec["bwd"](attrs, in_vals, probe_cots[k]))
                return outs, new_aux, tuple(new_grads)

            if with_head_grads:
                fn = jax.jit(named_program(fwd_bwd, "mx_forward_backward"),
                             donate_argnums=(4,))
            else:
                fn = jax.jit(
                    named_program(
                        lambda a, x, k, og: fwd_bwd(a, x, k, None, og),
                        "mx_forward_backward"),
                    donate_argnums=(3,))
            self._fwd_bwd_jit[with_head_grads] = fn
        return fn

    # ------------------------------------------------------------------
    def _arg_vals(self):
        return tuple(self._as_graph_value(self.arg_dict[n], n)
                     for n in self.arg_names)

    def _as_graph_value(self, arr, name):
        """Dense args flow as jax arrays; sparse NDArrays flow as their
        compressed pytree (FComputeEx dispatch — sparse-aware ops consume
        them, others densify at the op boundary).  Grads are allowed for
        rsp args (storage 'rsp_stored': the vjp cotangent of the pytree's
        .data leaf is the O(nnz) gradient) but not for csr args."""
        from .ndarray.sparse import CSRNDArray, to_value
        if isinstance(arr, CSRNDArray) \
                and self._grad_req.get(name, "null") != "null":
            raise MXNetError(
                "grad_req must be null for csr argument %r" % name)
        return to_value(arr)

    def _aux_vals(self):
        return tuple(self.aux_dict[n]._data for n in self.aux_names)

    def forward(self, is_train=False, **kwargs):
        import jax
        from .telemetry import step as _step
        with _step.active_phase("h2d"):
            # batch upload: attributed as the training step's h2d phase
            # when a StepTimer is ambient (no-op otherwise)
            for k, v in kwargs.items():
                if k not in self.arg_dict:
                    raise MXNetError("forward: unknown argument %r" % k)
                sh = self._sharding.get(k) if self._sharding else None
                if isinstance(v, NDArray):
                    v = v._data
                if sh is None:
                    dev = self._ctx.jax_device()
                    if hasattr(v, "sharding"):
                        # host-pipeline batches arrive on the CPU backend;
                        # move them onto the executor's device when they
                        # differ
                        if v.sharding.device_set != {dev}:
                            v = jax.device_put(v, dev)
                        self.arg_dict[k]._data = v
                    else:
                        self.arg_dict[k]._data = jax.device_put(
                            _np.asarray(v), dev)
                else:
                    # batch feed: local slice on multi-process meshes
                    self.arg_dict[k]._data = self._place_local(v, sh)
        if is_train:
            # lazy: the fused fwd+bwd program at backward() computes outputs
            # too, so running forward now would execute the graph twice.
            self._pending_train_fwd = True
            self._pending_key = self._key()
            self._materialized = False
            self.outputs = _LazyOutputs(self)
            return self.outputs
        _count_dispatch("forward")
        with _timeline.span("executor.forward", "executor", "executor",
                            chrome=("forward", "forward"), tl=self._tl):
            outs, new_aux = self._get_fwd(False)(self._arg_vals(),
                                                 self._aux_vals(),
                                                 self._key())
        self._set_outputs(outs)
        self._pending_train_fwd = False
        return self.outputs

    def backward(self, out_grads=None):
        if not self._pending_train_fwd and not self.outputs:
            raise MXNetError("backward called without forward(is_train=True)")
        key = getattr(self, "_pending_key", None)
        if key is None:
            key = self._key()
        _count_dispatch("forward_backward")
        fn = self._get_fwd_bwd(out_grads is not None)
        grad_names = self._grad_names
        with _timeline.span("executor.forward_backward", "executor",
                            "executor",
                            chrome=("forward_backward", "backward"),
                            tl=self._tl):
            with _timeline.part("executor.args", "executor", "executor",
                                self._tl):
                old = tuple(self.grad_dict[n]._data
                            for n in self._dense_grad_names)
                args = (self._arg_vals(), self._aux_vals(), key)
                if out_grads is not None:
                    if isinstance(out_grads, NDArray):
                        out_grads = [out_grads]
                    args += (tuple(o._data for o in out_grads),)
            with _timeline.part("executor.call", "executor", "executor",
                                self._tl):
                outs, new_aux, new_grads = fn(*args, old)
            with _timeline.part("executor.outputs", "executor", "executor",
                                self._tl):
                self._write_back(outs, new_aux, grad_names, new_grads)
        self._pending_train_fwd = False
        self._pending_key = None

    def _write_back(self, outs, new_aux, grad_names, new_grads):
        """A forward-and-backward dispatch's results into the outputs,
        the auxiliary states and the gradient arrays."""
        self._set_outputs(outs)
        for n, a in zip(self.aux_names, new_aux):
            self.aux_dict[n]._data = a
        from .ops.sparse_vals import RSPValue
        for n, gv in zip(grad_names, new_grads):
            if isinstance(gv, RSPValue):
                from .ndarray.sparse import RowSparseNDArray
                cur = self.grad_dict.get(n)
                if isinstance(cur, RowSparseNDArray) \
                        and cur._aux["data"]._data.shape == gv.data.shape:
                    # in-place: keeps references handed out at bind alive
                    cur._aux["data"]._data = gv.data
                    cur._aux["indices"]._data = gv.indices
                else:
                    self.grad_dict[n] = RowSparseNDArray._from_aux(
                        {"data": _wrap(gv.data, self._ctx),
                         "indices": _wrap(gv.indices, self._ctx)}, gv.shape)
            else:
                self.grad_dict[n]._data = gv

    def _materialize_pending(self):
        if self._pending_train_fwd and not getattr(self, "_materialized", True):
            self._materialized = True
            _count_dispatch("forward")  # lazy path is a real dispatch
            outs, new_aux = self._get_fwd(True)(self._arg_vals(),
                                                self._aux_vals(),
                                                self._pending_key)
            self._set_outputs(outs)
            for n, a in zip(self.aux_names, new_aux):
                self.aux_dict[n]._data = a

    def _set_outputs(self, outs):
        from .ndarray.sparse import from_value
        from .ops.sparse_vals import is_sparse

        def _localized(o):
            if is_sparse(o):
                # localize each LEAF: the pytree container itself reports
                # no addressability, its jax arrays do
                import jax
                leaves, treedef = jax.tree_util.tree_flatten(o)
                return jax.tree_util.tree_unflatten(
                    treedef, [self._localize(x) for x in leaves])
            return self._localize(o)
        self.outputs = [from_value(_localized(o), self._ctx) for o in outs]
        if self._monitor is not None:
            for name, o in zip(self.output_names, self.outputs):
                self._monitor(name, o)

    # ------------------------------------------------------------------
    @property
    def arg_arrays(self):
        return [self.arg_dict[n] for n in self.arg_names]

    @property
    def grad_arrays(self):
        return [self.grad_dict.get(n) for n in self.arg_names]

    @property
    def aux_arrays(self):
        return [self.aux_dict[n] for n in self.aux_names]

    def set_monitor_callback(self, callback, monitor_all=False):
        self._monitor = callback

    def copy_params_from(self, arg_params, aux_params=None,
                         allow_extra_params=False):
        for k, v in (arg_params or {}).items():
            if k in self.arg_dict:
                v.astype(self.arg_dict[k].dtype).copyto(self.arg_dict[k])
            elif not allow_extra_params:
                raise MXNetError("unknown arg %r" % k)
        for k, v in (aux_params or {}).items():
            if k in self.aux_dict:
                v.astype(self.aux_dict[k].dtype).copyto(self.aux_dict[k])
            elif not allow_extra_params:
                raise MXNetError("unknown aux %r" % k)

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        """Return a new executor for new input shapes.  XLA jit re-traces per
        shape signature automatically (the CachedOp/bucketing trick), so this
        only re-allocates arg arrays."""
        shapes = {n: self.arg_dict[n].shape for n in self.arg_names}
        shapes.update({k: tuple(v) for k, v in kwargs.items()})
        arg_shapes, _, aux_shapes = self._symbol.infer_shape(**shapes)
        import jax.numpy as jnp
        new_args = {}
        for n, s in zip(self.arg_names, arg_shapes):
            old = self.arg_dict[n]
            if old.shape == tuple(s):
                new_args[n] = old
            else:
                new_args[n] = _wrap(jnp.zeros(s, old.dtype), self._ctx)
        new_aux = {}
        for n, s in zip(self.aux_names, aux_shapes):
            old = self.aux_dict[n]
            new_aux[n] = old if old.shape == tuple(s) else \
                _wrap(jnp.zeros(s, old.dtype), self._ctx)
        grad_req = dict(self._grad_req)
        return Executor(self._symbol, self._ctx, new_args, None, grad_req,
                        new_aux, sharding=self._sharding)

    def lowered_fwd_bwd_text(self):
        """StableHLO text of the fused fwd+bwd program.

        Diagnostic surface for the sparse no-densify contract: tests grep
        this for vocab-extent tensor shapes to prove a row-sparse path
        never materializes the dense (vocab, dim) array on device."""
        import jax
        fn = self._get_fwd_bwd(False)
        old = tuple(self.grad_dict[n]._data for n in self._dense_grad_names)
        return str(fn.lower(self._arg_vals(), self._aux_vals(),
                            jax.random.PRNGKey(0), old).as_text())

    def debug_str(self):
        lines = ["Symbol outputs: %s" % ", ".join(self.output_names)]
        for n in self._topo:
            if n.op is not None:
                lines.append("  %s(%s)" % (n.op.name, n.name))
        lines.append("Total args: %d, aux: %d" % (len(self.arg_names),
                                                  len(self.aux_names)))
        return "\n".join(lines)

    # ------------------------------------------------------------------
    @staticmethod
    def _simple_bind(symbol, ctx, grad_req, type_dict, shapes,
                     shared_exec=None):
        import jax.numpy as jnp
        try:
            arg_shapes, out_shapes, aux_shapes = symbol.infer_shape(**shapes)
        except MXNetError as e:
            # the message already names the failing/blocked node
            # (symbol._infer_shape_impl); point at the analysis CLI for
            # the full dataflow trace instead of burying it here
            raise MXNetError(
                "simple_bind: %s  (tools/graph_lint.py --shapes ... "
                "prints per-node provenance for this graph)" % e) from None
        type_kwargs = {k: v for k, v in (type_dict or {}).items()}
        arg_types, _, aux_types = symbol.infer_type(**type_kwargs)
        ctx = ctx or current_context()
        args = {}
        with ctx:
            for n, s, t in zip(symbol.list_arguments(), arg_shapes, arg_types):
                args[n] = _wrap(jnp.zeros(s, t), ctx)
            aux = {}
            for n, s, t in zip(symbol.list_auxiliary_states(), aux_shapes,
                               aux_types):
                aux[n] = _wrap(jnp.zeros(s, t), ctx)
        return Executor(symbol, ctx, args, None, grad_req, aux)


class _LazyOutputs(list):
    """forward(is_train=True) returns this; touching it materializes."""

    def __init__(self, executor):
        super().__init__()
        self._ex = executor

    def _force(self):
        self._ex._materialize_pending()
        if not list.__len__(self) and self._ex.outputs is not self \
                and self._ex.outputs:
            self.extend(self._ex.outputs)

    def __getitem__(self, i):
        self._force()
        return list.__getitem__(self, i)

    def __iter__(self):
        self._force()
        return list.__iter__(self)

    def __len__(self):
        self._force()
        return list.__len__(self)

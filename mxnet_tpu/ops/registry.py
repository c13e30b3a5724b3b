"""Central operator registry — the NNVM op registry re-designed for XLA.

Reference: the dual registration system in `src/operator/` +
`include/mxnet/op_attr_types.h:109-248` (NNVM_REGISTER_OP with FCompute /
FComputeEx / FCreateOpState, shape/type inference attrs), shared by the
symbolic executor and the imperative runtime (SURVEY §1 "Symbolic and
imperative share the op registry").

TPU-native redesign: one registration per op holds
  - a typed parameter schema (the dmlc::Parameter equivalent, auto-generating
    python signatures and validating string attrs round-tripped via symbol
    JSON),
  - one pure-JAX implementation ``impl(attrs, *inputs) -> output(s)`` that is
    simultaneously the eager kernel (wrapped in a per-(op, attrs) jax.jit so
    each eager call is one fused XLA computation, replacing the reference's
    per-op mshadow/CUDA kernels), the symbolic lowering (the executor traces
    impls into one whole-graph XLA program), the gradient definition (via
    jax.vjp), and the shape/type inference (via jax.eval_shape) — one source
    of truth instead of the reference's five separate attr registrations.

Mutation of auxiliary state (e.g. BatchNorm moving averages,
src/operator/nn/batch_norm.cc) is expressed functionally: ``mutate_aux`` maps
an input index to an extra impl output that the frontend/executor writes back.
Stochastic ops (dropout, samplers) take an explicit leading PRNG-key operand,
threaded by the caller, keeping impls pure and jit-cacheable.
"""
from __future__ import annotations

import functools

from ..base import Param, normalize_attrs, MXNetError

__all__ = ["OpDef", "register", "get_op", "list_ops", "alias_map", "invoke_jax"]

_REGISTRY = {}
_ALIASES = {}


class OpDef:
    def __init__(self, name, impl, params=None, nin=1, nout=1,
                 input_names=None, variable_inputs=False, stochastic=False,
                 mode_dependent=False, mutate_aux=None, fill_shapes=None,
                 num_visible_outputs=None, key_var_num_args=None,
                 aux_inputs=(), sparse_aware=False, sparse_grad=None,
                 host_sync=False, row_local=None, flops=None,
                 temp_bytes=None, doc=""):
        self.name = name
        self.impl = impl
        self.params = params or {}
        self.nin = nin
        self.nout = nout
        self.input_names_spec = input_names or (["data"] if nin == 1 else None)
        self.variable_inputs = variable_inputs
        self.stochastic = stochastic
        self.mode_dependent = mode_dependent
        self.mutate_aux = mutate_aux or {}
        self.fill_shapes = fill_shapes
        self.num_visible_outputs = (num_visible_outputs if num_visible_outputs
                                    is not None else nout)
        self.key_var_num_args = key_var_num_args
        # indices of inputs that are auxiliary state (not arguments/learnable;
        # cf. NNVM FMutateInputs + symbol list_auxiliary_states)
        self.aux_inputs = tuple(aux_inputs)
        # FComputeEx analog: sparse-aware impls receive CSRValue/RSPValue
        # pytrees; all other ops see densified inputs (the reference's
        # storage-fallback executor, attach_op_execs_pass.cc:49)
        self.sparse_aware = sparse_aware
        # FInferStorageType analog for GRADIENTS (op_attr_types.h FInferStorageType
        # + e.g. indexing_op.cc SparseEmbeddingOpBackwardRsp): declares, per
        # input index, that this op can emit an O(nnz) row-sparse gradient.
        #   {in_index: {"stype": fn(attrs, in_stypes) -> "row_sparse"|"default",
        #               "bwd":   fn(attrs, in_vals, cotangent) -> RSPValue}}
        # The executor consults "stype" at bind time (with the stypes of the
        # op's VARIABLE inputs; intermediates count as "default") and, when it
        # answers row_sparse, skips the dense vjp for that input entirely —
        # it differentiates a zero probe added to the op's output instead and
        # hands the probe cotangent to "bwd" (see Executor._get_fwd_bwd).
        self.sparse_grad = sparse_grad or {}
        # declares that the impl round-trips to host Python per dispatch
        # (a pure_callback bridge like the Custom op): the analysis
        # host-sync detector (analysis/retrace.py) trusts this flag and
        # only falls back to impl-source scanning when it is unset
        self.host_sync = host_sync
        # what the analysis passes need of an op, declared where the op
        # is written instead of once in each pass (ROADMAP D13):
        #   row_local  "leading": every axis but the last is a batch of
        #              independent positions; "axis0": only axis 0 is.
        #              analysis/padding.py then needs no rule of its own
        #   flops      fn(attrs, in_shapes, out_shape) -> forward FLOPs
        #              (analysis/flops.py, where _RULES has no entry)
        #   temp_bytes fn(attrs, in_shapes, in_dtypes) -> bytes of the
        #              impl's own temporaries, live while the node runs
        #              (analysis/memory.py prices outputs only)
        self.row_local = row_local
        self.flops = flops
        self.temp_bytes = temp_bytes
        self.doc = doc or (impl.__doc__ or "")
        self._jit_cache = {}

    # ------------------------------------------------------------------
    def normalize(self, attrs):
        return normalize_attrs(self.params, attrs, self.name)

    def input_names(self, attrs=None, num_inputs=None):
        if self.variable_inputs:
            n = num_inputs
            if n is None and attrs:
                n = attrs.get(self.key_var_num_args or "num_args")
            n = int(n or 0)
            return ["arg%d" % i for i in range(n)]
        if self.input_names_spec is not None:
            if callable(self.input_names_spec):
                return list(self.input_names_spec(attrs))
            names = list(self.input_names_spec)
            n = self.nin(attrs) if callable(self.nin) else self.nin
            if isinstance(n, int) and 0 < n <= len(names):
                names = names[:n]
            return names
        return ["arg%d" % i for i in range(self.nin)]

    def num_outputs(self, attrs=None):
        return self.nout(attrs) if callable(self.nout) else self.nout

    # ------------------------------------------------------------------
    def bound(self, attrs, training=False):
        """Return impl closed over attrs: f(*jax_inputs) -> tuple of outputs.

        Output tuple layout: visible outputs first, then mutate_aux updates.
        """
        opdef = self

        def f(*jax_inputs):
            a = dict(attrs)
            if opdef.mode_dependent:
                a["_training"] = training
            if not opdef.sparse_aware:
                from .sparse_vals import densify
                jax_inputs = tuple(densify(x) for x in jax_inputs)
            out = opdef.impl(a, *jax_inputs)
            if not isinstance(out, tuple):
                out = (out,)
            return out
        return f

    def _freeze(self, attrs, training):
        def fz(v):
            if isinstance(v, (list, tuple)):
                return tuple(fz(x) for x in v)
            if isinstance(v, dict):
                return tuple(sorted((k, fz(x)) for k, x in v.items()))
            return v
        return (tuple(sorted((k, fz(v)) for k, v in attrs.items()
                             if not k.startswith("__"))), training)

    def jitted(self, attrs, training=False):
        """Eager-mode kernel: impl under jax.jit, cached per (attrs, mode).
        This is the FCompute path — one fused XLA executable per config."""
        import jax
        key = self._freeze(attrs, training)
        fn = self._jit_cache.get(key)
        if fn is None:
            fn = jax.jit(self.bound(attrs, training))
            self._jit_cache[key] = fn
        return fn

    # -- inference ------------------------------------------------------
    def infer(self, attrs, in_shapes, in_dtypes):
        """Forward shape/dtype inference (infer_graph_attr_pass.cc:64 analog).

        Returns (in_shapes, out_shapes, out_dtypes, aux_update_shapes).
        ``fill_shapes`` lets layer ops complete unknown *parameter* shapes
        from the data shape (the reason Module.simple_bind works without the
        user spelling out weight shapes).
        """
        import jax
        import jax.numpy as jnp
        in_shapes = list(in_shapes)
        if self.fill_shapes is not None:
            in_shapes = list(self.fill_shapes(attrs, in_shapes))
        if any(s is None for s in in_shapes):
            unknown = [i for i, s in enumerate(in_shapes) if s is None]
            raise MXNetError(
                "%s: cannot infer shapes; inputs %s unknown" % (self.name, unknown))
        dt = [d if d is not None else jnp.float32 for d in in_dtypes]
        structs = [jax.ShapeDtypeStruct(tuple(s), d)
                   for s, d in zip(in_shapes, dt)]
        out = jax.eval_shape(self.bound(attrs, training=True), *structs)
        out_shapes = [tuple(o.shape) for o in out]
        out_dtypes = [o.dtype for o in out]
        return in_shapes, out_shapes, out_dtypes


def register(name, aliases=(), **kwargs):
    """Decorator: register a pure-JAX impl as an operator."""
    def deco(impl):
        opdef = OpDef(name, impl, **kwargs)
        _REGISTRY[name] = opdef
        _ALIASES[name] = name
        for a in aliases:
            _ALIASES[a] = name
        return impl
    return deco


def register_opdef(opdef, aliases=()):
    _REGISTRY[opdef.name] = opdef
    _ALIASES[opdef.name] = opdef.name
    for a in aliases:
        _ALIASES[a] = opdef.name
    return opdef


def get_op(name):
    real = _ALIASES.get(name)
    if real is None:
        raise MXNetError("operator %r is not registered (%d ops known)"
                         % (name, len(_REGISTRY)))
    return _REGISTRY[real]


def list_ops():
    return sorted(_ALIASES)


def alias_map():
    return dict(_ALIASES)


def invoke_jax(op_name, attrs, *jax_inputs, training=False):
    """Run an op on raw jax arrays (used by executor/tests)."""
    op = get_op(op_name)
    a = op.normalize(attrs)
    return op.bound(a, training)(*jax_inputs)


# convenience re-export for op modules
P = Param

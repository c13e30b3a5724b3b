"""CachedOp: JIT-compiled subgraph for the imperative frontend.

Reference: src/imperative/cached_op.cc (GetForwardGraph:179 caches an
optimized graph per input-shape signature, Forward:332, Backward:424) —
the machinery behind Gluon hybridize.

TPU-native collapse (SURVEY §7 stage 3): CachedOp ≡ jax.jit.  The symbol's
graph function is jitted once per (shapes, dtypes, training) signature —
jax.jit's own cache plays the role of GetForwardGraph's shape-keyed cache.
Under autograd recording the whole subgraph becomes ONE tape node whose vjp
is the jitted backward — exactly how the reference backprops through a
CachedOp as a single opaque op.
"""
from __future__ import annotations

import contextlib

from .base import MXNetError, named_program
from . import autograd
from . import random as _random
from .ndarray.ndarray import NDArray, _wrap
from .executor import build_graph_fn

__all__ = ["CachedOp"]


class CachedOp:
    """``program`` names the compiled programs (``jit_<program>`` in
    the device's trace): the owner's kind of program, the same for
    every shape it compiles."""

    def __init__(self, sym, flags=None, program="mx_cached_op"):
        self._sym = sym
        self._flags = dict(flags or {})
        self._program = program
        self.arg_names = sym.list_arguments()
        self.aux_names = sym.list_auxiliary_states()
        self.input_names = sym.list_inputs()  # args + aux, topo order
        self._aux_pos = {n: i for i, n in enumerate(self.input_names)
                         if n in set(self.aux_names)}
        self._graph_fn = build_graph_fn(sym, self.arg_names, self.aux_names)
        self._jit = {}
        self._base_key = None
        self._step = 0
        self._trace_count = 0
        # serving dispatches one CachedOp from several threads (worker +
        # warmup); the step counter must not hand two batches the same
        # rng fold or "random" draws repeat bitwise across requests
        import threading
        self._key_lock = threading.Lock()

    @property
    def trace_count(self):
        """Number of XLA traces so far — jax.jit retraces once per new
        input-shape/dtype signature (the GetForwardGraph shape-keyed
        cache, cached_op.cc:179), so this is the compile counter the
        serving program cache exposes: warm traffic must not move it."""
        return self._trace_count

    def lint(self, data_shapes=None, **kwargs):
        """Run the static-analysis suite (mxnet_tpu.analysis) over this
        op's symbol graph — the pre-compile view of what __call__ will
        jit.  Returns the :class:`~mxnet_tpu.analysis.Report`."""
        from .analysis import analyze
        report, _ = analyze(self._sym, data_shapes=data_shapes, **kwargs)
        return report

    def _key(self):
        import jax
        with self._key_lock:
            if self._base_key is None:
                self._base_key = _random.next_key()
            if not self._graph_fn.stochastic:
                # deterministic subgraph: the key is a dead jit input —
                # reuse one constant, skip the eager fold_in per call
                return self._base_key
            self._step += 1
            step = self._step
        return jax.random.fold_in(self._base_key, step)

    def _get_jit(self, training):
        import jax
        fn = self._jit.get(training)
        if fn is None:
            g = self._graph_fn
            na = len(self.arg_names)

            def call(key, *flat_inputs):
                # Python side effect runs once per trace == once per
                # compiled program (never on cached dispatches)
                self._trace_count += 1
                from .executor import _count_xla_trace
                _count_xla_trace()
                args = flat_inputs[:na]
                aux = flat_inputs[na:]
                outs, new_aux = g(args, aux, key, training)
                return tuple(outs) + tuple(new_aux)

            fn = jax.jit(named_program(call, self._program))
            self._jit[training] = fn
        return fn

    def __call__(self, *inputs, **kwargs):
        if len(inputs) != len(self.input_names):
            raise MXNetError("CachedOp expects %d inputs (%s), got %d"
                             % (len(self.input_names), self.input_names,
                                len(inputs)))
        # reorder: inputs arrive in list_inputs order; split args vs aux
        by_name = dict(zip(self.input_names, inputs))
        arg_nds = [by_name[n] for n in self.arg_names]
        aux_nds = [by_name[n] for n in self.aux_names]
        ordered = arg_nds + aux_nds
        jax_ins = [x._data for x in ordered]
        training = autograd.is_training()
        kernel = self._get_jit(training)
        key = self._key()
        primal = lambda *ins: kernel(key, *ins)  # noqa: E731
        n_out = len(self._sym._outputs)

        recording = autograd.is_recording() and autograd.any_traced(ordered)
        from . import telemetry
        # one contextvar probe on the common no-trace path: the span
        # name formatting and contextmanager only exist under an
        # active trace (near-zero-cost-when-disabled discipline)
        tc = telemetry.current_trace()
        span = (tc.span("CachedOp(%s)" % (self._sym.name or "graph"),
                        "op")
                if tc is not None and not tc.finished
                else contextlib.nullcontext())
        with span:
            if recording:
                import jax
                flat, raw_vjp = jax.vjp(primal, *jax_ins)
                vjp_fn = lambda cots, _v=raw_vjp: _v(tuple(cots))  # noqa: E731,E501
            else:
                flat = primal(*jax_ins)
                vjp_fn = None

        ctx = ordered[0].context if ordered else None
        out_nds = [_wrap(o, ctx) for o in flat[:n_out]]
        # write back updated aux state
        for i, n in enumerate(self.aux_names):
            by_name[n]._data = flat[n_out + i]

        if recording:
            aux_nds_out = [_wrap(o, ctx) for o in flat[n_out:]]
            autograd.record_op("CachedOp(%s)" % (self._sym.name or "graph"),
                               vjp_fn, primal, list(ordered),
                               out_nds + aux_nds_out, jax_ins)
        if len(out_nds) == 1:
            return out_nds[0]
        return out_nds

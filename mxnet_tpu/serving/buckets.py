"""Shape buckets + compile-once program cache for the serving engine.

The compiler-first serving argument (PAPERS.md: "Compiler-First State
Space Duality …" §portable caching; "Operator Fusion in XLA" §fusion
amortization): XLA specializes one program per input-shape signature, so
an engine that dispatched every request at its natural shape would
retrace constantly.  Instead all traffic is quantized onto a small grid:

- **batch buckets**: powers of two up to ``max_batch`` — a batch of n
  requests pads up to the next power of two, so at most
  log2(max_batch)+1 programs exist per input signature;
- **seq buckets** (optional): a designated per-example axis is padded up
  to the next configured bucket, for token/length-polymorphic models
  whose outputs are row-independent along that axis.

:class:`ProgramCache` reuses the :class:`~mxnet_tpu.cached_op.CachedOp`
machinery — the same jit-per-signature compile path Gluon hybridize
uses — rather than ``Predictor``'s bind path: params/aux live on device
once, each bucket shape becomes one cached XLA program, and
``CachedOp.trace_count`` is the **compile counter**: warm traffic must
leave it unchanged, which tests and perf/serve_bench.py assert.

The symbol handed in is the graph the engine decided to SERVE: by
default (``MXNET_SERVE_OPTIMIZE``) the verdict-gated optimizer
(``analysis/optimize.py``) has already run CSE / constant folding /
DCE / algebraic simplification over it, so every bucket program traces
the smaller graph — fewer nodes per trace, identical outputs (the
acceptance protocol rejected any candidate whose re-analysis verdicts
got worse).
"""
from __future__ import annotations

import threading

import numpy as np

from ..base import MXNetError
from .locks import named_lock
from ..cached_op import CachedOp
from ..predict import _infer_label_shapes, _label_like

__all__ = ["BucketPolicy", "ProgramCache", "pad_valid_lengths"]


def _next_pow2(n):
    p = 1
    while p < n:
        p <<= 1
    return p


def pad_valid_lengths(lengths, bucket):
    """Batch-pad a per-request live-length vector onto the bucket grid.

    The repaired-graph dispatch contract (analysis/rewrite.py): slot i
    carries request i's live extent along the repaired axis; the pad
    rows carry 0, so every spliced SequenceMask masks them entirely —
    a pad row can never leak into live rows no matter what garbage the
    zero-padded data slots hold.  Lengths are ALWAYS float32 — no
    dtype knob on purpose: the spliced variable declares float32, and
    a half-precision dtype would round large lengths onto the wrong
    mask boundary (float16 cannot represent 2049).
    """
    out = np.zeros((bucket,), dtype=np.float32)
    out[:len(lengths)] = lengths
    return out


class BucketPolicy(object):
    """Quantizes request-batch sizes (and optionally one per-example
    axis) onto the bucket grid the program cache compiles for."""

    def __init__(self, max_batch=8, seq_axis=None, seq_buckets=()):
        if max_batch < 1:
            raise MXNetError("max_batch must be >= 1, got %d" % max_batch)
        self.max_batch = _next_pow2(int(max_batch))
        self.seq_axis = seq_axis
        self.seq_buckets = tuple(sorted(int(b) for b in seq_buckets))
        if self.seq_buckets and seq_axis is None:
            raise MXNetError("seq_buckets given without seq_axis")

    @classmethod
    def from_config(cls):
        """Build from the MXNET_SERVE_* env tier (config.py)."""
        from .. import config
        raw = config.get("MXNET_SERVE_SEQ_BUCKETS").strip()
        seq_buckets = tuple(int(t) for t in raw.split(",") if t.strip())
        return cls(max_batch=config.get("MXNET_SERVE_MAX_BATCH"),
                   seq_axis=0 if seq_buckets else None,
                   seq_buckets=seq_buckets)

    def batch_buckets(self):
        out, b = [], 1
        while b <= self.max_batch:
            out.append(b)
            b <<= 1
        return out

    def batch_bucket(self, n):
        if n < 1:
            raise MXNetError("empty batch")
        if n > self.max_batch:
            raise MXNetError("batch %d exceeds max_batch %d"
                             % (n, self.max_batch))
        return _next_pow2(n)

    def seq_bucket(self, length):
        """Smallest configured seq bucket >= length (identity when seq
        bucketing is off)."""
        if not self.seq_buckets:
            return length
        for b in self.seq_buckets:
            if length <= b:
                return b
        raise MXNetError(
            "sequence length %d exceeds largest seq bucket %d"
            % (length, self.seq_buckets[-1]))

    def example_shape(self, shape):
        """Pad a per-example shape onto the bucket grid."""
        if self.seq_axis is None:
            return tuple(shape)
        if self.seq_axis >= len(shape):
            raise MXNetError("seq_axis %d out of range for shape %s"
                             % (self.seq_axis, tuple(shape)))
        s = list(shape)
        s[self.seq_axis] = self.seq_bucket(s[self.seq_axis])
        return tuple(s)


class ProgramCache(object):
    """Device-resident params + one compiled forward per bucket shape.

    Not a second compile cache on top of jax.jit's: the jit trace cache
    (inside the wrapped :class:`CachedOp`) IS the program store, keyed by
    input shapes exactly as GetForwardGraph keys on shape signatures in
    the reference (cached_op.cc:179).  This class contributes the fixed
    input plumbing around it (param/aux placement, dummy label buffers
    per bucket) plus observability: ``compile_count`` (the CachedOp
    trace counter) and the set of bucket signatures seen.
    """

    def __init__(self, symbol, arg_params, aux_params, data_names,
                 ctx=None, dtype=np.float32, aot=None, aot_kind="serve",
                 plan=None, program="mx_cached_op"):
        from ..context import cpu
        self._ctx = ctx or cpu()
        # model-parallel serving (parallel/mesh.py ShardingPlan): with a
        # plan, params upload as ONE sharded device_put each (jax splits
        # the transfer per shard — the full weight is never staged once
        # per device), dispatch inputs commit to the plan's data
        # sharding, and every program compiles under the resulting
        # pjit-style placement — computation follows data, XLA inserts
        # the collectives.  plan=None is the single-device fast path,
        # byte-for-byte the pre-sharding cache.
        self._plan = plan
        # persistent AOT program cache (serving/aot_cache.py): when the
        # engine hands one in, every bucket program resolves through it
        # — a warm entry loads with ZERO traces, a cold one compiles
        # through jax.export and is persisted for the next process (or
        # the next replica).  The graph digest is computed once here;
        # per-signature keys fold in the flat argument signature.
        self._aot = aot if (aot is not None and aot.enabled) else None
        self._aot_kind = aot_kind
        self._graph_digest = None
        if self._aot is not None:
            from .aot_cache import graph_digest
            self._graph_digest = graph_digest(symbol)
        self._sym = symbol
        self._dtype = np.dtype(dtype)
        self.data_names = list(data_names)
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()
        missing = [n for n in arg_names
                   if n not in (arg_params or {})
                   and n not in self.data_names]
        # loss-head label inputs get per-bucket dummy zeros (the
        # c_predict_api placeholder-label convention, predict._label_like)
        self._label_names = _label_like(missing)
        missing = [n for n in missing if n not in self._label_names]
        if missing:
            raise MXNetError("ProgramCache: params missing for %s" % missing)
        def _upload(src, n):
            # device placement per parameter: single-device replicas
            # ride the NDArray context path unchanged; a ShardingPlan
            # commits each weight straight to its NamedSharding
            if self._plan is not None:
                return self._plan.put_param(n, src[n]._data)
            return src[n].as_in_context(self._ctx)._data
        self._params = {n: _upload(arg_params, n)
                        for n in arg_names
                        if n not in self.data_names
                        and n not in self._label_names}
        self._aux = {n: _upload(aux_params or {}, n)
                     for n in aux_names}
        # ``program``: the name of every bucket's compiled program
        self._op = CachedOp(symbol, program=program)
        # flat-input template in the kernel's order (args then aux):
        # params/aux slots hold their device-resident jax array once,
        # data and label slots are filled per shape key / per dispatch —
        # driving the CachedOp's jit kernel directly skips the
        # per-dispatch NDArray wrapping of the imperative front end
        # (measured ~0.3 ms/batch on CPU, perf/serve_bench.py)
        order = self._op.arg_names + self._op.aux_names
        self._data_pos = {n: i for i, n in enumerate(order)
                          if n in self.data_names}
        self._label_pos = {n: i for i, n in enumerate(order)
                           if n in self._label_names}
        self._template = [None] * len(order)
        for i, n in enumerate(order):
            if n in self._params:
                self._template[i] = self._params[n]
            elif n in self._aux:
                self._template[i] = self._aux[n]
        self._n_out = len(symbol._outputs)
        self._plans = {}         # full data-shape key -> prefilled flat
        self._keys = set()       # bucket signatures dispatched so far
        self._lock = named_lock("serve.programs")
        self._build_lock = named_lock("serve.programs.build")
        # plan-cache traffic counters: plain ints (only the single
        # worker + pre-start warmup touch them), mirrored into the
        # telemetry registry by the engine's collect callback and
        # reported by ServingEngine.stats()
        self.plan_hits = 0
        self.plan_misses = 0
        # serving efficiency plane (telemetry/goodput.py): advisory
        # integer FLOPs price per bucket signature, computed ONCE in
        # _plan_for alongside the program build (None = the FLOPs pass
        # could not price it; dispatches then count as unpriced)
        self.flops_by_key = {}

    # ------------------------------------------------------------------
    @property
    def compile_count(self):
        """Number of XLA traces so far — one per (bucket shapes) program.
        Warm traffic over already-seen buckets must not move this."""
        return self._op.trace_count

    @property
    def bucket_keys(self):
        with self._lock:
            return sorted(self._keys)

    def flops_for(self, shape_key):
        """Advisory FLOPs price of one bucket program (the run()-side
        shape key: sorted (name, padded shape) tuples).  None =
        unpriced, or priced before the efficiency plane was on."""
        return self.flops_by_key.get(shape_key)

    def node_inputs(self, op_name, data_shapes):
        """``(attrs, input shapes, input dtypes)`` of every ``op_name``
        node of the program at these data shapes, in graph order: what
        such a node can observe of its inputs when the program is
        built (the shapes pass over this cache's graph and its
        parameters' dtypes; a node whose inputs stayed unresolved is
        left out)."""
        from ..analysis.shapes import node_inputs
        return node_inputs(self._sym, op_name, data_shapes,
                           {n: np.dtype(a.dtype)
                            for n, a in self._params.items()})

    def _plan_for(self, shape_key, data_specs):
        """Prefilled flat-input list + kernel + rng key for one bucket
        signature: everything per-dispatch work can reuse verbatim.
        Built once per signature under the lock; dispatches only copy
        the list and fill the data slots.  ``data_specs`` maps data
        name -> (shape, dtype) — the dtype half keys the AOT cache."""
        # builds serialize on their own lock so the (possibly
        # multi-second, on cold AOT misses: jax.export trace + fsync'd
        # store) kernel resolution never holds self._lock — a stats()
        # scrape or flight-recorder dump reading bucket_keys must not
        # block behind a compile
        with self._build_lock:
            plan = self._plans.get(shape_key)
            if plan is None:
                flat = list(self._template)
                if self._label_names:
                    import jax.numpy as jnp
                    shapes = _infer_label_shapes(
                        self._sym,
                        {k: s for k, (s, _d) in data_specs.items()},
                        self._label_names)
                    for n, pos in self._label_pos.items():
                        z = jnp.zeros(shapes[n], jnp.float32)
                        if self._plan is not None:
                            # every committed input must live on the
                            # plan's mesh — a default-device dummy
                            # label would make the dispatch a cross-
                            # device computation jit refuses
                            z = self._plan.put_data(z)
                        flat[pos] = z
                # deterministic graphs can freeze the (dead) rng key
                # into the plan; stochastic ones must fold a fresh
                # key per dispatch or every batch on this bucket
                # replays identical draws
                key = (None if self._op._graph_fn.stochastic
                       else self._op._key())
                kernel = self._resolve_kernel(data_specs, flat)
                from ..telemetry import goodput as _goodput
                if _goodput.enabled():
                    # price the program once per signature, on the
                    # cold path only — warm dispatches read the dict
                    self.flops_by_key[shape_key] = _goodput.price_graph(
                        self._sym,
                        {k: s for k, (s, _d) in data_specs.items()},
                        dtypes={k: d for k, (_s, d) in
                                data_specs.items()},
                        label_names=self._label_names)
                plan = (flat, kernel, key,
                        sorted(self._data_pos.items()))
                with self._lock:
                    self._plans[shape_key] = plan
                    self._keys.add(shape_key)
        return plan

    def _resolve_kernel(self, data_specs, flat):
        """The dispatch kernel for one bucket signature: the CachedOp's
        jit program, resolved through the persistent AOT cache when the
        engine configured one — a warm entry deserializes with zero
        traces (``compile_count`` is pinned across a restart), a cold
        one compiles through jax.export and persists for the next
        process or replica."""
        jit_fn = self._op._get_jit(False)
        if self._aot is None:
            return jit_fn
        import jax
        from .aot_cache import resolve_kernel
        args = [jax.random.PRNGKey(0)] + list(flat)
        for n, pos in self._data_pos.items():
            shape, dt = data_specs[n]
            if self._plan is not None:
                # sharded avals: the exported program records the
                # plan's placement, so a warm load serves the same
                # partitioned StableHLO the cold compile did
                args[1 + pos] = jax.ShapeDtypeStruct(
                    shape, np.dtype(dt),
                    sharding=self._plan.data_sharding(shape))
            else:
                args[1 + pos] = jax.ShapeDtypeStruct(shape, np.dtype(dt))
        kernel, _src = resolve_kernel(
            self._aot, jit_fn, self._aot_kind, self._graph_digest, args)
        return kernel

    def run(self, feeds, _record=True, _fixed_key=None):
        """Dispatch one padded batch: ``feeds`` maps data name -> host
        ndarray WITH batch dim, already padded to bucket shapes.
        Returns the outputs as host ndarrays (still batch-padded).

        Hot path: drives the CachedOp's jit kernel directly — the graph
        is frozen, so aux write-back and autograd bookkeeping are
        skipped, the non-data input slots come from the prebuilt
        device-resident template, and the whole non-data plumbing is a
        cached per-signature plan (no lock, no rebuild on warm keys).

        ``_record=False`` skips the hit/miss counters — the pad probe's
        second dispatch of the SAME logical batch must not make the
        accounting read two dispatches.  ``_fixed_key`` overrides the
        rng key (replica probation: two caches' probe dispatches must
        draw identically even for stochastic graphs, whose per-cache
        key streams would otherwise never agree bitwise)."""
        return [np.asarray(o)
                for o in self.dispatch(feeds, _record, _fixed_key)]

    def dispatch(self, feeds, _record=True, _fixed_key=None):
        """:meth:`run` without the read: the outputs stay device
        arrays (a decode prefill's state rows go from here into the
        slot pool and never visit the host)."""
        shape_key = tuple(sorted((k, v.shape) for k, v in feeds.items()))
        plan = self._plans.get(shape_key)
        if plan is None:
            if _record:
                self.plan_misses += 1
            plan = self._plan_for(
                shape_key, {k: (tuple(v.shape), v.dtype)
                            for k, v in feeds.items()})
        elif _record:
            self.plan_hits += 1
        template, kernel, key, data_pos = plan
        if _fixed_key is not None:
            key = _fixed_key
        elif key is None:
            key = self._op._key()       # stochastic graph: fresh draws
        flat = list(template)
        if self._plan is not None:
            # commit each input to the plan's data sharding so the
            # dispatch lands on the replica's device group (replicated
            # by default; batch/seq axes shard when the plan says so)
            for n, pos in data_pos:
                flat[pos] = self._plan.put_data(feeds[n])
        else:
            for n, pos in data_pos:
                flat[pos] = feeds[n]    # jit commits host arrays itself
        return list(kernel(key, *flat)[:self._n_out])

    def run_pad_probe(self, feeds, live_masks, sentinel=7.5):
        """Runtime padding-soundness assert (MXNET_SERVE_PAD_CHECK) —
        the dynamic complement of analysis/padding.py: dispatch the
        batch twice, once as given (zero pads) and once with every pad
        slot set to ``sentinel``.  A graph that is truly row-local
        along the padded axes computes live outputs from live inputs
        only, so the two runs must agree bitwise on live rows (same
        compiled program, same live operands — no float slop); any
        divergence is contamination.  Returns (base_outs, probed_outs);
        the engine compares per-request live regions and raises.

        ``live_masks`` maps input name -> bool ndarray (batch-padded
        shape), True on live slots.  Both dispatches share one bucket
        signature, so the probe never compiles extra programs.
        """
        base = self.run(feeds)
        probed_feeds = {}
        for name, arr in feeds.items():
            mask = live_masks.get(name)
            if mask is None:
                probed_feeds[name] = arr
            else:
                probed_feeds[name] = np.where(
                    mask, arr, np.asarray(sentinel, arr.dtype))
        probed = self.run(probed_feeds, _record=False)
        return base, probed

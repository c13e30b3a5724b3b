"""Continuous batching for autoregressive decode — iteration-level
scheduling over a persistent slot pool.

The one-shot engine (engine.py) coalesces requests into a batch,
dispatches ONCE, and scatters results.  Sequence models cannot be
served that way without catastrophic waste: a static batch holds every
finished sequence hostage until the slowest member completes, and new
requests wait for the whole batch to drain.  This module schedules at
the *iteration* level instead (ROADMAP item 1 — THE millions-of-users
workload):

- **one persistent step program** compiled ONCE over a fixed-capacity
  slot pool (``MXNET_DECODE_SLOTS`` slots x ``MXNET_DECODE_MAX_LEN``
  positions).  Requests join and leave the running batch BETWEEN steps
  with zero retraces — shapes never change, so the jit cache is never
  busted (the compile counter is pinned across churn by tests);
- **device-resident per-slot state**: recurrent state (h/c per
  :meth:`~mxnet_tpu.rnn.rnn_cell.BaseRNNCell.begin_state_arrays`) or a
  fixed-layout KV cache in the O(1)-per-token mold of PAPERS.md
  "Compiler-First State Space Duality and Portable O(1) Autoregressive
  Caching" (arxiv 2603.09555): a ``(slots, max_len, d)`` buffer
  written at one position per step, never grown, never re-laid-out.
  State stays in HBM across steps (buffers are donated to the step
  dispatch off-CPU); the host ships four small vectors a step (tokens,
  positions, the slot-occupancy/valid vector, the join-time reset) and
  receives only the sampled token ids back;
- **one step in flight**: the plain loop dispatches step N+1 BEFORE it
  reads step N's ids (``DecodeEngine._step_body``).  A slot that
  generates is fed the id step N sampled for it on the device, from
  that step's output buffer (``StepFeed`` / ``FROM_PREVIOUS``: a
  select inside the one step program), a slot fed its prompt takes the
  host's token, and positions advance at the dispatch.  The device
  runs N+1 while the host reads N, walks its slots, admits and builds
  N+2.  Delivery goes by who sat where when the step was dispatched; a
  finish by length is known before the read and its slot is not stepped
  again, so only an eos id, a deadline or a raising callback costs one
  slot-step whose id is thrown away (``stats()["decode"]``:
  ``steps_ahead``, ``slot_steps_discarded``).  Whatever leaves the
  steady state (an empty pool, a close, a failure) first reads the step
  in flight.  A speculative step commits a count of positions only its
  read tells, so it is read where it is dispatched;
- **masked dead slots**: free slots ride along in every dispatch
  holding whatever a finished request left behind.  That is sound
  exactly when the step graph is row-local along the slot axis —
  :func:`mxnet_tpu.analysis.check_decode_step` proves it at
  construction with the same padding classifier serving already
  trusts, seeding state inputs pad-DIRTY so stale garbage gets no
  zero-absorption credit (``tools/graph_lint.py --decode-step`` runs
  the same lint offline);
- **bucketed prefill**: a prompt is consumed either token-by-token
  through the running step batch (teacher forcing — no extra
  programs), or, with a ``prefill_sym``, in ONE dispatch through the
  existing :class:`~mxnet_tpu.serving.buckets.ProgramCache` at pow2
  seq buckets, its output state scattered into the free slot —
  and joiners COALESCE (``MXNET_DECODE_COALESCE_PREFILL``, default
  on): requests seated in the same scheduler iteration whose prompts
  pad to the same seq bucket share one dispatch at the next pow2 batch
  extent instead of prefilling at batch 1 each
  (``perf/decode_bench.py --prefill``);
- **joins that wait for a dispatch worth its cost**: a prefill dispatch
  stops every decoding slot while it runs, so a free slot is not filled
  the moment a request waits for it.  The scheduler seats as many of
  the waiting requests as a dispatch is worth now, from what the engine
  has observed and nothing else (``join_policy.py``): what a dispatch
  of each warm batch costs the device, the step's time, the slots
  decoding, and how many requests become seatable a step.  Where a
  dispatch has a fixed cost of several steps they are seated several
  at a time, after a hold of bounded length in the admission queue
  (deadlines swept, cancels honoured, back-pressure counted); where it
  costs by its rows, where nothing decodes, and where a join rides the
  step, at once.  There is no option for it (``stats()["decode"]``:
  ``prefill_cost_ms``, ``slot_steps_held``);
- **fused-op selection**: before any program compiles, the optimizer's
  kernel-selection pipeline (``analysis.SELECT_OPT_PASSES``, behind
  ``MXNET_SERVE_OPTIMIZE`` + ``MXNET_OPT_SELECT_KERNELS``) rewrites
  the step graph under the same slot-axis/pad-dirty spec the preflight
  lint uses — today swapping the one-hot-blend KV-cache row write
  (O(max_len*d) per token; all XLA's fuser reliably handles, per
  arxiv 2301.13062) for the O(d) ``_cache_write_row`` scatter
  (ops/cache.py: Pallas kernel on TPU, ``dynamic_update_slice``
  elsewhere).  Adoption is verdict-gated exactly like every optimizer
  rewrite: re-analysis no worse, slot row-locality preserved under
  pad-dirty seeding, rejected plans serve the unmodified step.  The
  adopted selection rides the AOT cache's validity fingerprint, so
  toggling it between restarts REJECTS stale entries;
- **per-token streaming**: ``submit(..., on_token=cb)`` fires the
  callback with each generated token id in order (the exact
  ``greedy_decode`` prefix) from the slot loop; a raising callback
  evicts only its own request (SSE per-request streams remain a
  follow-up — this is their engine seam);
- **admission + per-step deadlines**: the same
  :class:`~mxnet_tpu.serving.admission.AdmissionController` front door
  (bounded queue, reject/shed overload policies); deadlines are
  re-checked every iteration, and an expired request — queued or
  mid-generation — completes with its PARTIAL output and the
  ``expired`` flag instead of failing (``Request.on_expire``).

Quick start::

    eng = serving.DecodeEngine(step_sym, params, {}, state_info=[
        {"name": "h", "shape": (H,)}, {"name": "c", "shape": (H,)}])
    eng.warmup()
    fut = eng.submit([bos_id], max_new_tokens=32)
    res = fut.result()          # DecodeResult: tokens, finish_reason
    eng.close()

Step-graph contract: ``step_sym`` outputs ``[logits] + next_states``
(exactly like ``BaseRNNCell.__call__``), over arguments ``token``
(slot vector of last token ids), the state names from ``state_info``
(each ``(slots,) + per_slot_shape``), and optionally ``pos`` (per-slot
write position) and ``valid`` (1/0 occupancy).  The engine appends a
greedy ``argmax`` head so only token ids cross the host boundary, and
they cross it one step late.
"""
from __future__ import annotations

import collections
import threading
import time
import warnings
import weakref
from concurrent.futures import Future

import numpy as np

from ..base import MXNetError, named_program
from .. import telemetry as _telemetry
from ..telemetry import goodput as _goodput
from . import faults as _faults
from .locks import named_lock, named_condition
from .admission import (AdmissionController, Request, EngineClosedError,
                        _fail_future)
from .buckets import ProgramCache, _next_pow2
from .engine import (_ENGINE_SEQ, _percentile, aot_metric_families,
                     _supervisor_state, memory_metric_families,
                     _memory_stats_block, refresh_memory_gauges)
from .replica import DecodeReplica, resolve_replica_placements
from . import join_policy as _join_policy
from .slot_state import SlotLayout

__all__ = ["DecodeEngine", "DecodeResult", "StepProgram", "greedy_decode",
           "Sampler", "GreedySampler", "TemperatureSampler"]

class Sampler(object):
    """Pluggable token-selection head for the decode step (ROADMAP 1a).

    The step program's contract is ``[logits] + next_states``; a
    Sampler decides how the per-slot logits row becomes the sampled
    token id.  ``greedy=True`` samplers keep the original in-graph
    ``argmax`` head — bitwise-pinned against ``greedy_decode`` and the
    batch-1 reference, zero behavior change.  Stochastic samplers run
    inside the SAME compiled step kernel using the rng key the step
    already carried dead: the kernel folds a per-step tick into the
    engine's base key, so join/leave churn never retraces and a fixed
    ``seed`` replays bitwise.

    Note the reproducibility boundary: greedy output is independent of
    slot-pool company (the row-local contract); a stochastic sampler's
    draws additionally depend on WHICH step ticks and slot a request
    rode through, so they replay only against the same engine history.
    """
    greedy = False

    def sample(self, key, logits):
        """jax-land: (slots, vocab) logits + folded PRNG key -> (slots,)
        sampled ids (cast back to the logits dtype — the token vector
        rides the same float pipeline the argmax head fed)."""
        raise NotImplementedError

    def spec_logits(self, logits):
        """jax-land: raw logits -> the sampler's log-space
        distribution (temperature scaling, top-k masking) — what
        speculative rejection sampling verifies draft proposals
        against.  Must be the same transform :meth:`sample` draws
        from, applied identically to target and draft logits, or the
        emitted distribution drifts from the single-token engine's.
        Greedy samplers never call this (acceptance is exact argmax
        prefix match)."""
        raise MXNetError(
            "%s does not support speculative decode: implement "
            "spec_logits() (the distribution rejection sampling "
            "verifies against)" % type(self).__name__)

    def describe(self):
        return {"kind": type(self).__name__}


class GreedySampler(Sampler):
    """The default argmax head — spliced into the step GRAPH itself
    (exactly the pre-sampler engine), so greedy decode stays bitwise-
    identical to ``greedy_decode`` and compiles the identical program."""
    greedy = True

    def describe(self):
        return {"kind": "greedy"}


class TemperatureSampler(Sampler):
    """Temperature (optionally top-k-truncated) categorical sampling.

    ``logits / temperature`` -> optional top-k mask (everything below
    the k-th logit pinned to -inf) -> one Gumbel-max categorical draw
    per slot (``jax.random.categorical``).  ``top_k=1`` degenerates to
    argmax whatever the key — the cheap sanity anchor tests pin.
    ``seed`` fixes the engine's base key for reproducible replays;
    None draws it from the process rng stream.
    """

    def __init__(self, temperature=1.0, top_k=None, seed=None):
        if temperature <= 0:
            raise MXNetError("TemperatureSampler: temperature must be "
                             "> 0, got %r (top_k=1 IS argmax)"
                             % (temperature,))
        if top_k is not None and int(top_k) < 1:
            raise MXNetError("TemperatureSampler: top_k must be >= 1")
        self.temperature = float(temperature)
        self.top_k = None if top_k is None else int(top_k)
        self.seed = seed

    def sample(self, key, logits):
        import jax
        return jax.random.categorical(key, self.spec_logits(logits),
                                      axis=-1).astype(logits.dtype)

    def spec_logits(self, logits):
        import jax
        import jax.numpy as jnp
        z = logits / self.temperature
        if self.top_k is not None and self.top_k < z.shape[-1]:
            kth = jax.lax.top_k(z, self.top_k)[0][..., -1:]
            z = jnp.where(z < kth, -jnp.inf, z)
        return z

    def describe(self):
        return {"kind": "temperature", "temperature": self.temperature,
                "top_k": self.top_k, "seed": self.seed}


class DecodeResult(object):
    """What a decode future resolves to: the generated token ids plus
    how generation ended.

    ``finish_reason`` is one of ``"eos"`` (the eos id was sampled),
    ``"length"`` (max_new_tokens or the slot's max_len capacity),
    ``"deadline"`` (the request's deadline passed mid-flight — tokens
    holds the PARTIAL generation), ``"closed"`` (engine shut down
    without drain), or ``"error"`` (the request's device replica
    failed mid-generation and was retired — tokens holds the PARTIAL
    generation; co-resident replicas keep serving).  ``expired``
    mirrors the deadline case.
    """
    __slots__ = ("tokens", "finish_reason", "n_steps", "prompt_len")

    def __init__(self, tokens, finish_reason, n_steps=0, prompt_len=0):
        self.tokens = np.asarray(tokens, dtype=np.int64)
        self.finish_reason = finish_reason
        self.n_steps = n_steps
        self.prompt_len = prompt_len

    @property
    def expired(self):
        return self.finish_reason == "deadline"

    def __len__(self):
        return len(self.tokens)

    def __repr__(self):
        return ("<DecodeResult %d tokens, %s>"
                % (len(self.tokens), self.finish_reason))


class DecodeRequest(Request):
    """One decode request: a prompt plus generation bookkeeping the
    scheduler mutates as the request moves queue -> slot -> done."""
    __slots__ = ("prompt", "max_new", "tokens", "prompt_i", "slot",
                 "t_join", "n_steps", "t_first_tok", "t_last_tok",
                 "on_token", "sse_id", "uflops", "n_ahead")

    def __init__(self, prompt, max_new, future, deadline=None,
                 trace=None, on_token=None, sse_id=None):
        super().__init__({}, ("__decode__",), future, deadline=deadline,
                         trace=trace)
        self.prompt = list(prompt)
        self.max_new = int(max_new)
        # per-request SSE stream key (ROADMAP item 4 residual): with a
        # client-supplied request id, every generated token is ALSO
        # published to the /events EventHub as a `decode.token` event
        # keyed by it — the hub's bounded replay ring gives
        # Last-Event-ID resume for free.  None = no HTTP surface, the
        # pre-SSE engine byte-for-byte.
        self.sse_id = sse_id
        # per-token streaming hook (ROADMAP 4a): called from the slot
        # loop with each generated token id, in generation order — the
        # exact greedy_decode prefix.  A raising callback evicts ONLY
        # its own request (the future fails with the exception; co-
        # residents keep generating).  SSE per-request streaming stays
        # a follow-up; this is its engine-side seam.
        self.on_token = on_token
        self.tokens = []            # generated ids (host mirror)
        self.prompt_i = 0           # next prompt token to teacher-force
        # generated tokens dispatched for and not delivered yet (the
        # plain loop reads a step after it dispatched the next): with
        # ``tokens`` it tells, before the read, which step is the last
        self.n_ahead = 0
        self.slot = None
        self.t_join = None
        self.n_steps = 0
        # decode latency anatomy: first/last generated-token stamps
        # feed the TTFT and inter-token (TPOT) histograms
        self.t_first_tok = None
        self.t_last_tok = None
        # useful-FLOPs accumulator for tenant accounting (goodput.py):
        # each dispatch this request rides adds its share; flushed to
        # the tenant series when the slot finishes
        self.uflops = 0


def _pin_state_dtypes(step_sym, states):
    """The step graph with every next-state output cast to the dtype
    its pool buffer has (``states``: the model's, of a
    :class:`SlotLayout`).  A graph that mixes a low-precision state with
    float32 host vectors (a one-hot blend of ``pos`` into a cache)
    promotes the state, and the pool would come back float32 from the
    first step: twice the bytes, no donation, one more compile.  A
    float32 pool over a float32 graph is left as it is, and so is an
    output that already ends in the cast (``StepProgram`` pins whatever
    graph it is given; the engine pins first, for its analyses)."""
    from .. import symbol as sym
    want = [s.dtype for s in states]
    if all(dt == np.dtype(np.float32) for dt in want):
        return step_sym
    outs = [step_sym[i] for i in range(len(step_sym))]
    for i, dt in enumerate(want):
        node = outs[1 + i]._outputs[0][0]
        if node.op is not None and node.op.name == "Cast" \
                and np.dtype(node.attrs.get("dtype")) == dt:
            continue
        outs[1 + i] = sym.Cast(outs[1 + i], dtype=dt.name)
    return sym.Group(outs)


#: In a :class:`StepFeed`'s token vector: the slot is fed the id that
#: the step before sampled for it.  Token ids are never negative.
FROM_PREVIOUS = -1.0


class StepFeed(object):
    """What a caller that keeps one step in flight hands
    :meth:`StepProgram.step` in place of the token vector: the host's
    ``tokens``, ``FROM_PREVIOUS`` where a slot takes the id that
    ``after`` sampled for it, and ``after``, the :class:`PendingStep`
    dispatched before this one (None where no slot asks).  The ids go
    from one step's output buffer into the next step's input on the
    device; the host need not have read them."""
    __slots__ = ("tokens", "after")

    def __init__(self, tokens, after=None):
        self.tokens = tokens
        self.after = after


class PendingStep(object):
    """A dispatched step whose ids the host has not read: what
    :meth:`StepProgram.step` returns for a :class:`StepFeed`.  The copy
    to the host starts at the dispatch; :meth:`read` waits for it.
    ``ids`` stays a device array, for the :class:`StepFeed` of the step
    after.  ``dispatch_s`` and, once read, ``read_s`` and ``extras``
    (the step's counters past its states) are what ``last_split`` and
    ``last_extras`` hold for a step that is read at once."""
    __slots__ = ("ids", "extras", "dispatch_s", "read_s", "_extra_outs",
                 "_names", "_tl")

    def __init__(self, ids, extra_outs, names, tl):
        self.ids = ids
        self._extra_outs = list(extra_outs)
        self._names = names
        self._tl = tl
        self.extras = {}
        self.dispatch_s = self.read_s = 0.0
        for o in [ids] + self._extra_outs:
            o.copy_to_host_async()

    def read(self):
        """The sampled ids as a host vector; blocks until the device
        has finished the step."""
        t0 = time.perf_counter()
        with (self._tl.annotate("decode.step.read")
              if self._tl is not None else _telemetry.timeline.NO_SPAN):
            ids = np.asarray(self.ids)
            self.extras = dict(zip(
                self._names, [np.asarray(o) for o in self._extra_outs]))
        self._extra_outs = ()
        self.read_s = time.perf_counter() - t0
        return ids

    def __array__(self, dtype=None, copy=None):
        # ``np.array(step)``: what a wrapper around ``StepProgram.step``
        # that looks at the ids gets (``StepProgram.pending``)
        return np.array(self.read(), dtype=dtype)


class StepProgram(object):
    """The persistent compiled decode step over a fixed slot pool.

    Wraps ``step_sym`` (outputs ``[logits] + next_states``) with a
    greedy ``argmax`` head and compiles it ONCE at batch extent
    ``num_slots`` — iteration-level scheduling never changes a shape,
    so ``trace_count`` is the whole compile story: the step kernel,
    plus one tiny row-write kernel per distinct state shape (slot
    join/leave scatter), all exercised by ``DecodeEngine.warmup``.

    Per-slot state lives as jax device buffers between calls; on
    non-CPU backends the state arguments are DONATED to the dispatch,
    so the pool is updated in place in HBM (the O(1) cache layout of
    arxiv 2603.09555 — no growth, no re-layout, no host round-trip).
    """

    def __init__(self, step_sym, arg_params, aux_params, state_info,
                 num_slots, token_name="token", pos_name="pos",
                 valid_name="valid", ctx=None, dtype=np.float32,
                 sampler=None, aot=None, plan=None, spec=None):
        import jax
        import jax.numpy as jnp
        from ..context import cpu
        from ..executor import build_graph_fn, _count_xla_trace
        from .. import symbol as sym
        from . import spec as _spec_mod
        self._ctx = ctx or cpu()
        # speculative draft-k-verify (serving/spec.py, ISSUE 15): with
        # a SpecConfig the ONE compiled program per replica widens —
        # k+1 draft steps and k+1 target steps unroll in-graph, the
        # accept logic picks the committed prefix, and the commit
        # graph (blend chain or the selected _cache_write_rows
        # scatter) writes only accepted rows into the ORIGINAL cache.
        # None = the single-token program byte-for-byte.
        self._spec = spec
        # model-parallel decode (parallel/mesh.py ShardingPlan): params
        # upload as one sharded device_put each, per-slot state buffers
        # lay out under the plan's state_rules (a KV cache's feature
        # axis shards over tp), and the persistent step compiles under
        # the resulting placement — continuous batching runs tensor-
        # parallel across the replica's device group.  None = the
        # single-device program byte-for-byte.
        self._plan = plan
        self._aot = aot if (aot is not None and aot.enabled) else None
        self.num_slots = int(num_slots)
        self._dtype = np.dtype(dtype)
        self.sampler = sampler if sampler is not None else GreedySampler()
        # what a slot holds, for both models of a speculative program
        # (slot_state.py reads ``state_info``; nothing here does)
        self.layout = SlotLayout(
            state_info, self.num_slots, self._dtype,
            None if spec is None else spec.draft_state_info)
        self.state_names = [s.name for s in self.layout.target]
        self.token_name = token_name
        n_states = len(self.state_names)
        if len(step_sym) < 1 + n_states \
                or (self._spec is not None
                    and len(step_sym) != 1 + n_states):
            raise MXNetError(
                "decode step graph has %d outputs; expected 1 (logits) "
                "+ %d next-state outputs (state_info order)%s"
                % (len(step_sym), n_states,
                   "" if self._spec is None else
                   "; a speculative step takes no further outputs"))
        # outputs past the states ride along as counters: small arrays
        # the host reads with the sampled ids each step (an expert
        # layer's load) and the scheduler hangs on its ``decode.step``
        # event as ``<name>_max`` / ``<name>_mean``
        self.extra_names = [
            n[:-len("_output")] if n.endswith("_output") else n
            for n in step_sym.list_outputs()[1 + n_states:]]
        self.last_extras = {}
        step_sym = _pin_state_dtypes(step_sym, self.layout.target)
        if self._spec is not None:
            # the spec program needs per-position RAW logits (the
            # greedy head becomes a jnp.argmax with identical
            # semantics inside the accept logic — same impl, same
            # tie-breaking, same dtype cast as the argmax op)
            head = step_sym[0]
        elif self.sampler.greedy:
            # greedy keeps the in-graph argmax head: bitwise-pinned
            # against greedy_decode, identical compiled program
            head = sym.argmax(step_sym[0], axis=1,
                              name="__decode_sample__")
        else:
            # stochastic samplers take the raw logits into the kernel
            # and sample there with the (formerly dead) rng key
            head = step_sym[0]
        self._serve_sym = sym.Group(
            [head] + [step_sym[i]
                      for i in range(1, len(step_sym))])
        arg_names = self._serve_sym.list_arguments()
        aux_names = self._serve_sym.list_auxiliary_states()
        if token_name not in arg_names:
            raise MXNetError("decode step graph has no %r input "
                             "(token_name); arguments: %s"
                             % (token_name, arg_names))
        missing = [n for n in self.state_names if n not in arg_names]
        if missing:
            raise MXNetError("decode step graph is missing state "
                             "input(s) %s" % missing)
        self.pos_name = pos_name if pos_name in arg_names else None
        self.valid_name = valid_name if valid_name in arg_names else None
        feeds = set([token_name] + self.state_names)
        feeds.update(n for n in (self.pos_name, self.valid_name) if n)
        lacking = [n for n in arg_names
                   if n not in feeds and n not in (arg_params or {})]
        if lacking:
            raise MXNetError("StepProgram: params missing for %s"
                             % lacking)
        order = list(arg_names) + list(aux_names)
        self._template = [None] * len(order)
        for i, n in enumerate(order):
            if n in feeds:
                continue
            src = arg_params if n in (arg_params or {}) else aux_params
            if self._plan is not None:
                self._template[i] = self._plan.put_param(n, src[n]._data)
            else:
                self._template[i] = src[n].as_in_context(self._ctx)._data
        self._feed_pos = {n: order.index(n) for n in feeds}
        gf = build_graph_fn(self._serve_sym, arg_names, aux_names)
        if gf.stochastic:
            raise MXNetError(
                "decode step graph contains stochastic ops (Dropout, "
                "samplers): the persistent step must be deterministic "
                "— greedy decode parity and per-slot bitwise "
                "reproducibility both depend on it")
        self._trace_count = 0
        na = len(arg_names)
        n_t = len(order)
        state_pos = tuple(order.index(n) for n in self.state_names)
        reset_pos = tuple(order.index(n)
                          for n in self.layout.reset_names())
        _sampler = self.sampler
        # -------------------------------------------------- draft half
        # the draft model is a full second graph riding the same flat
        # argument vector: its params append to the template (uploaded
        # to this replica's device / sharded under its plan exactly
        # like the target's), its per-slot state buffers live in the
        # same states dict under prefixed keys, and its token/pos/
        # valid inputs are fed the SAME host vectors as the target's.
        self.draft_state_keys = []
        self._spec_cache_t = []         # (name, T) target cache states
        self._spec_cache_d = []         # (key, T) draft cache states
        if self._spec is not None:
            dspec = self._spec
            # idempotent: the engine builds the shared commit graph
            # once before any replica constructs; a directly-built
            # StepProgram(spec=...) gets the same build here instead
            # of a KeyError inside its first traced dispatch
            dspec.build(self.layout)
            dsym = sym.Group(list(dspec.draft_sym))
            d_args = dsym.list_arguments()
            d_auxs = dsym.list_auxiliary_states()
            if dspec.token_name not in d_args:
                raise MXNetError("draft graph has no %r input; "
                                 "arguments: %s"
                                 % (dspec.token_name, d_args))
            d_states = [s.name for s in self.layout.draft]
            missing = [n for n in d_states if n not in d_args]
            if missing:
                raise MXNetError("draft graph is missing state "
                                 "input(s) %s" % missing)
            if len(dsym) != 1 + len(d_states):
                raise MXNetError(
                    "draft graph has %d outputs; expected 1 (logits) "
                    "+ %d next-state outputs" % (len(dsym),
                                                 len(d_states)))
            self._d_tok = dspec.token_name
            self._d_pos = (dspec.pos_name
                           if dspec.pos_name in d_args else None)
            self._d_valid = (dspec.valid_name
                             if dspec.valid_name in d_args else None)
            d_feeds = set([self._d_tok] + d_states)
            d_feeds.update(n for n in (self._d_pos, self._d_valid) if n)
            d_order = list(d_args) + list(d_auxs)
            lacking = [n for n in d_order
                       if n not in d_feeds
                       and n not in dspec.draft_arg_params
                       and n not in dspec.draft_aux_params]
            if lacking:
                raise MXNetError("SpecConfig: draft params missing "
                                 "for %s" % lacking)
            self._template += [None] * len(d_order)
            for i, n in enumerate(d_order):
                if n in d_feeds:
                    continue
                src = (dspec.draft_arg_params
                       if n in dspec.draft_arg_params
                       else dspec.draft_aux_params)
                if self._plan is not None:
                    self._template[n_t + i] = self._plan.put_param(
                        n, src[n]._data)
                else:
                    self._template[n_t + i] = \
                        src[n].as_in_context(self._ctx)._data
            # absolute feed positions in the merged flat vector,
            # keyed by the engine-side draft state keys
            self._d_feed_pos = {n: n_t + d_order.index(n)
                                for n in d_feeds if n not in d_states}
            self._d_feed_pos.update((s.key, n_t + d_order.index(s.name))
                                    for s in self.layout.draft)
            self.draft_state_keys = [s.key for s in self.layout.draft]
            gf_d = build_graph_fn(dsym, d_args, d_auxs)
            if gf_d.stochastic:
                raise MXNetError("draft graph contains stochastic "
                                 "ops: the speculative step must be "
                                 "deterministic given its rng key")
            nda = len(d_args)
            d_state_pos = tuple(n_t + d_order.index(n)
                                for n in d_states)
            # commit structure: cache-declared states commit accepted
            # rows through the (possibly _cache_write_rows-selected)
            # commit graph; everything else selects the chain state
            # at the accepted count
            self._spec_cache_t = self.layout.cache_rows(
                "target", pos_name, self.pos_name is not None)
            self._spec_cache_d = self.layout.cache_rows(
                "draft", dspec.pos_name, self._d_pos is not None)
            gf_commit = commit_args = None
            if dspec.commit_sym is not None:
                commit_args = dspec.commit_sym.list_arguments()
                gf_commit = build_graph_fn(dspec.commit_sym,
                                           commit_args, [])
            K = dspec.K
            cache_keys = set(k for k, _t in
                             self._spec_cache_t + self._spec_cache_d)

            def call_spec(key, tick, reset, spec_m, *flat):
                self._trace_count += 1
                _count_xla_trace()
                flat = list(flat)
                # join-time zeroing covers BOTH models' state rows
                for i in state_pos + d_state_pos:
                    s = flat[i]
                    r = reset.reshape((-1,) + (1,) * (s.ndim - 1))
                    flat[i] = jnp.where(r > 0, jnp.zeros((), s.dtype),
                                        s)
                token0 = flat[self._feed_pos[self.token_name]]
                pos0 = (flat[self._feed_pos[self.pos_name]]
                        if self.pos_name is not None else None)
                kstep = jax.random.fold_in(key, tick)
                # ---- draft chain: k proposals + one state-advancing
                # extra step (its proposal is discarded; it exists so
                # an all-accept window leaves the draft having
                # consumed every committed token)
                xs = [token0]
                d_chain = []
                cur = {kk: flat[self._d_feed_pos[kk]]
                       for kk in self.draft_state_keys}
                dlogits = []
                for j in range(K):
                    df = list(flat[n_t:])
                    df[self._d_feed_pos[self._d_tok] - n_t] = xs[j]
                    if self._d_pos is not None:
                        df[self._d_feed_pos[self._d_pos] - n_t] = \
                            flat[self._d_feed_pos[self._d_pos]] \
                            + jnp.float32(j)
                    for ix, kk in enumerate(self.draft_state_keys):
                        df[d_state_pos[ix] - n_t] = cur[kk]
                    outs_d, _ = gf_d(df[:nda], df[nda:], key, False)
                    dlogits.append(outs_d[0])
                    cur = {kk: outs_d[1 + ix] for ix, kk in
                           enumerate(self.draft_state_keys)}
                    d_chain.append(cur)
                    if j < K - 1:
                        if _sampler.greedy:
                            prop = jnp.argmax(outs_d[0], axis=1) \
                                .astype(outs_d[0].dtype)
                        else:
                            zq = _sampler.spec_logits(outs_d[0])
                            prop = jax.random.categorical(
                                jax.random.fold_in(kstep, 2 * j),
                                zq, axis=-1).astype(outs_d[0].dtype)
                        xs.append(prop)
                # ---- target chain: score all K positions
                t_chain = []
                tlogits = []
                cur_t = {n2: flat[self._feed_pos[n2]]
                         for n2 in self.state_names}
                for j in range(K):
                    tf = list(flat[:n_t])
                    tf[self._feed_pos[self.token_name]] = xs[j]
                    if self.pos_name is not None:
                        tf[self._feed_pos[self.pos_name]] = \
                            pos0 + jnp.float32(j)
                    for n2 in self.state_names:
                        tf[self._feed_pos[n2]] = cur_t[n2]
                    outs_t, _ = gf(tf[:na], tf[na:], key, False)
                    tlogits.append(outs_t[0])
                    cur_t = {n2: outs_t[1 + ix] for ix, n2 in
                             enumerate(self.state_names)}
                    t_chain.append(cur_t)
                # ---- accept
                if _sampler.greedy:
                    toks, a = _spec_mod.greedy_accept(xs, tlogits)
                else:
                    toks, a = _spec_mod.rejection_accept(
                        kstep, xs, tlogits, dlogits,
                        _sampler.spec_logits)
                count = jnp.where(spec_m > 0, a + 1.0, 1.0)
                idx = (count - 1.0).astype(jnp.int32)
                # ---- commit: caches write accepted rows into the
                # ORIGINAL buffers (post-reset), everything else
                # selects the chain candidate at the accepted count
                committed = {}
                for n2 in self.state_names:
                    if n2 not in cache_keys:
                        committed[n2] = _spec_mod.commit_select(
                            [st[n2] for st in t_chain], idx)
                for kk in self.draft_state_keys:
                    if kk not in cache_keys:
                        committed[kk] = _spec_mod.commit_select(
                            [st[kk] for st in d_chain], idx)
                if gf_commit is not None:
                    # both models' caches share one window start: the
                    # engine feeds the same host pos vector to both
                    # graphs' pos inputs
                    base_pos = pos0 if pos0 is not None \
                        else flat[self._d_feed_pos[self._d_pos]]
                    cvals = {"__spec_pos__": base_pos,
                             "__spec_count__": count}
                    for n2, T in self._spec_cache_t:
                        cvals["__spec_cache__%s" % n2] = \
                            flat[self._feed_pos[n2]]
                        cvals["__spec_rows__%s" % n2] = \
                            _spec_mod.gather_rows(
                                [st[n2] for st in t_chain],
                                base_pos, T)
                    for kk, T in self._spec_cache_d:
                        cvals["__spec_cache__%s" % kk] = \
                            flat[self._d_feed_pos[kk]]
                        cvals["__spec_rows__%s" % kk] = \
                            _spec_mod.gather_rows(
                                [st[kk] for st in d_chain],
                                base_pos, T)
                    outs_c, _ = gf_commit(
                        [cvals[a2] for a2 in commit_args], [], key,
                        False)
                    ci = 0
                    for n2, _T in self._spec_cache_t:
                        committed[n2] = outs_c[ci]
                        ci += 1
                    for kk, _T in self._spec_cache_d:
                        committed[kk] = outs_c[ci]
                        ci += 1
                return ([toks, count]
                        + [committed[n2] for n2 in self.state_names]
                        + [committed[kk]
                           for kk in self.draft_state_keys])

        token_pos = order.index(token_name)

        def call(key, tick, reset, prev_ids, *flat):
            self._trace_count += 1      # runs once per XLA trace
            _count_xla_trace()
            # a joining slot's state is zeroed HERE, fused into the
            # step program (``reset`` is a per-slot 1/0 host vector):
            # a join costs no device dispatch of its own, unlike a
            # write_row scatter (~ms each on CPU jax) per join.
            # jnp.where, not multiply: stale rows may hold non-finite
            # values and 0*inf would leak NaN into the fresh state.
            flat = list(flat)
            for i in reset_pos:
                s = flat[i]
                r = reset.reshape((-1,) + (1,) * (s.ndim - 1))
                flat[i] = jnp.where(r > 0, jnp.zeros((), s.dtype), s)
            # a slot whose token is ``FROM_PREVIOUS`` takes the id the
            # step before sampled for it, here, from that step's output
            # buffer: a caller that keeps a step in flight dispatches
            # this one before it has read that one (``StepFeed``).  The
            # select is part of the one program, whoever calls it: a
            # host-fed step hands in ids that no slot asks for
            tok = flat[token_pos]
            flat[token_pos] = jnp.where(tok < 0, prev_ids, tok)
            outs, _ = gf(flat[:na], flat[na:], key, False)
            outs = list(outs)
            if not _sampler.greedy:
                # fold the per-step tick into the (formerly dead) key
                # INSIDE the jit: tick is a traced scalar, so churning
                # values never retrace, and the sampler's draws are a
                # pure function of (base key, tick, logits)
                k = jax.random.fold_in(key, tick)
                outs[0] = _sampler.sample(k, outs[0])
            # the ids leave in the dtype the token feed has, so that
            # they can be the next step's ``prev_ids`` under the one
            # signature (the head's own dtype is the logits': the same
            # values in a bfloat16 graph, and no cast in a float32 one)
            outs[0] = outs[0].astype(tok.dtype)
            return outs

        if self._spec is not None:
            call = call_spec
        donate = ()
        if jax.default_backend() != "cpu":
            # in-place HBM update of the slot pool: the old state
            # buffers are donated to the dispatch (CPU jax cannot
            # honor donation and would warn per compile).  Offsets
            # skip the (key, tick, reset, spec or prev_ids) leading
            # args; the previous ids are read, never donated.
            lead = 4
            donate = tuple(lead + order.index(n)
                           for n in self.state_names)
            if self._spec is not None:
                donate += tuple(lead + self._d_feed_pos[kk]
                                for kk in self.draft_state_keys)
        # the persistent step kernel resolves lazily at the first step
        # when an AOT cache is configured (serving/aot_cache.py): a
        # warm entry deserializes with zero traces — the compiled
        # decode step of arxiv 2603.09555 is never compiled twice for
        # the same (graph, pool geometry, sampler policy, backend) —
        # while a cold one compiles through jax.export (the one trace
        # that would have happened anyway) and persists.  Donation
        # does NOT survive the round trip on its own, so the donate
        # spec is re-applied on the jit wrapper around the exported
        # program (resolve_kernel donate_argnums) — the in-place HBM
        # slot-pool update must hold whether the program was traced
        # fresh or loaded from disk.
        # the program's name is what the device's trace calls its runs
        self._jit_kernel = jax.jit(
            named_program(call, "mx_decode_step" if self._spec is None
                          else "mx_decode_spec_step"),
            donate_argnums=donate)
        self._donate = donate
        self._kernel = None if self._aot is not None else self._jit_kernel
        # the lazy resolution can be reached from two threads at once
        # (the replica scheduler's first step racing a rehab probe on
        # this program): serialize it so exactly one trace happens
        self._kernel_lock = named_lock("decode.kernel")
        self._graph_digest = None
        if self._aot is not None:
            from .aot_cache import graph_digest
            self._graph_digest = graph_digest(self._serve_sym)
            if self._spec is not None:
                # the compiled program is the whole widened step:
                # target graph x draft graph x commit graph x window
                # width — all four are program identity (toggling k or
                # swapping the draft must never hit a stale entry)
                self._graph_digest = "spec.k%d.%s.%s.%s" % (
                    self._spec.k, self._graph_digest,
                    self._spec.draft_digest,
                    self._spec.commit_digest or "none")
        self._tick = 0          # per-step sample counter (stochastic
        #                         samplers fold it into the key; dead
        #                         and DCE'd under the greedy head)
        # span seam (telemetry/timeline.py): the engine that owns this
        # program hands it its own ring (one gate: ``_new_replica``).
        # With a ring, each step's host round trip is split where it
        # happens — building and enqueuing the dispatch against the
        # blocking read of the ids — and left in ``last_split`` for the
        # scheduler's ``decode.step`` event; None = untimed,
        # byte-for-byte
        self._tl = None
        self.last_split = None  # (dispatch seconds, read seconds)
        # what a step is handed as its previous ids where no slot asks
        # for one: the newest ids this program sampled, zeros before
        # the first.  Only its shape, dtype and placement matter (the
        # select takes none of its values), and they are those of every
        # fed-back step, so the host-fed and the fed-back form are one
        # compiled program
        self._ids_like = jax.device_put(
            np.zeros((self.num_slots,), np.float32),
            None if self._plan is not None else self._ctx.jax_device())
        seed = getattr(self.sampler, "seed", None)
        if seed is not None:
            self._key = jax.random.PRNGKey(int(seed))
        else:
            from .. import random as _random
            self._key = _random.next_key()  # greedy: dead input

        def set_row(buf, idx, row):
            self._trace_count += 1
            _count_xla_trace()
            return buf.at[idx].set(row)

        # one trace per distinct state shape; the slot index is a
        # traced scalar so churn across slots never retraces.  With an
        # AOT cache the per-shape kernels resolve through it too —
        # warmup()'s row-write traces must also pin to zero on a warm
        # restart, or the "0 compiles for previously-served buckets"
        # contract would leak through the scatter path.
        # off-CPU the buffer is donated, like the pool to the step: a
        # row write patches the pool in place and never holds a second
        # copy of a state (a caller rebinds the dict ``write_row``
        # returns; the one it passed holds consumed buffers)
        self._set_row_jit = jax.jit(
            named_program(set_row, "mx_decode_set_row"),
            donate_argnums=(0,) if donate else ())
        self._row_kernels = {}
        self._jnp = jnp

        n_s = len(self.state_names)

        def commit(slots, lens, *flat):
            self._trace_count += 1
            _count_xla_trace()
            return self.layout.lay_prefill(flat[:n_s], flat[n_s:], slots,
                                           lens)

        # a prefill's state rows laid into the pool on the device, one
        # program a (batch, prompt bucket) shape (``commit_prefill``),
        # resolved through the AOT cache like the row kernels
        self._commit_donate = tuple(range(2, 2 + n_s)) if donate else ()
        self._commit_jit = jax.jit(named_program(commit, "mx_decode_commit"),
                                   donate_argnums=self._commit_donate)
        self._commit_kernels = {}

    @property
    def trace_count(self):
        return self._trace_count

    def init_states(self):
        """Fresh all-zero slot-pool state buffers, committed to this
        program's device — with replica routing the pool must live on
        ITS replica's device from the first step (an uncommitted buffer
        would land on the default device and make the step a cross-
        device computation)."""
        import jax
        dev = None if self._plan is not None else self._ctx.jax_device()
        if self._plan is not None:
            # sharded slot-pool layout: the plan's state_rules decide
            # which per-slot axes partition over the group.  Built from
            # HOST zeros — a pool sized to fit only when sharded must
            # never be staged whole on one device (device_put ships
            # each shard's slice)
            return {s.key: self._plan.put_state(s.name, z)
                    for s, z in self.layout.zeros(pool=True)}
        return {s.key: jax.device_put(z, dev)
                for s, z in self.layout.zeros(pool=True, xp=self._jnp)}

    def _row_kernel(self, buf, idx, row):
        """The row-scatter kernel for one (buffer, row) signature,
        resolved through the AOT cache when one is configured.  The
        graph component is a fixed tag — ``buf.at[idx].set(row)`` is
        the same program whatever engine asks — so entries are shared
        across engines and model architectures."""
        if self._aot is None:
            return self._set_row_jit
        # the sharded layout is part of the program identity: two state
        # buffers of one shape whose state_rules place them differently
        # must neither share a memoized kernel nor hit each other's
        # universal entries (the flat signature carries shapes/dtypes
        # only, so the placement rides the graph tag)
        shard = ("" if self._plan is None
                 else "|%s" % (getattr(getattr(buf, "sharding", None),
                                       "spec", None),))
        sig = (tuple(buf.shape), str(np.dtype(buf.dtype)),
               tuple(np.shape(row)),
               str(np.dtype(getattr(row, "dtype", None)
                            or np.asarray(row).dtype)), shard)
        kernel = self._row_kernels.get(sig)
        if kernel is None:
            from .aot_cache import resolve_kernel
            kernel, _src = resolve_kernel(
                self._aot, self._set_row_jit, "decode_set_row",
                "jnp_at_set_v1" + shard, [buf, idx, row], universal=True)
            self._row_kernels[sig] = kernel
        return kernel

    def _ensure_kernel(self, reset, fourth, flat):
        """Resolve the persistent step kernel at the first dispatch
        (the argument avals are only concrete here; ``fourth`` is the
        speculative mask, or the plain step's previous ids): AOT-cache
        hit loads the serialized program with zero traces; a miss
        compiles once through jax.export and persists it.
        Double-checked under a lock: the scheduler's first step and a
        rehab probe may race here, and exactly one resolution must
        win."""
        if self._kernel is None:
            with self._kernel_lock:
                if self._kernel is None:
                    from .aot_cache import resolve_kernel
                    kernel, _src = resolve_kernel(
                        self._aot, self._jit_kernel, "decode_step",
                        self._graph_digest,
                        [self._key, np.int32(0), reset, fourth]
                        + list(flat),
                        donate_argnums=self._donate)
                    self._kernel = kernel
        return self._kernel

    def write_row(self, states, slot, rows):
        """Scatter per-slot state rows (host or device arrays) into
        ``slot`` of every buffer named in ``rows``; returns the updated
        state dict.  The index is passed as a traced scalar — one
        compile per state shape, ever."""
        idx = self._jnp.asarray(slot, self._jnp.int32)
        out = dict(states)
        for name, row in rows.items():
            out[name] = self._row_kernel(out[name], idx, row)(
                out[name], idx, row)
        return out

    def commit_prefill(self, states, rows, slots, lens):
        """Lay one prefill dispatch's state rows (device arrays,
        ``(batch,) + row shape`` in ``state_info`` order) into slots
        ``slots`` of the pool, in one dispatch, and return the state
        dict.  A row of a state's own shape replaces the slot's row.  A
        ``cache`` state also takes the keys or values of every prompt
        position, ``(batch, T) + tail``: position ``p`` goes to row
        ``p``, and where the state is a ring of ``window`` rows shorter
        than ``T``, row ``j`` takes the last position ``p < lens[i]``
        with ``p mod window == j``, which is where the step, writing at
        ``pos mod window``, will look for it.  Batch rows are written
        last to first, so a dead row of a padded batch is given the
        slot and length of row 0 and is overwritten by it."""
        # the slots and lengths go in as host int32 vectors: converted on
        # the device they would be two small programs of their own
        args = [np.asarray(slots, np.int32), np.asarray(lens, np.int32)] \
            + [states[n] for n in self.state_names] + list(rows)
        kernel = self._commit_jit
        if self._aot is not None:
            sig = tuple((tuple(a.shape), str(a.dtype)) for a in args)
            kernel = self._commit_kernels.get(sig)
            if kernel is None:
                from .aot_cache import resolve_kernel
                kernel, _src = resolve_kernel(
                    self._aot, self._commit_jit, "decode_commit_prefill",
                    self.layout.commit_tag(), args,
                    donate_argnums=self._commit_donate, universal=True)
                self._commit_kernels[sig] = kernel
        out = dict(states)
        out.update(zip(self.state_names, kernel(*args)))
        return out

    def zero_row(self, states, slot, which="all"):
        """Zero one slot's rows in every state buffer (a joining
        request must never inherit the previous occupant's state).
        ``which="draft"`` zeroes only the draft model's rows — the
        prefill commit path writes REAL target rows but the draft
        (which never saw the prompt) must start the generation cold,
        not from a dead request's leftovers."""
        return self.write_row(
            states, slot, {s.key: z for s, z in self.layout.zeros(which)})

    def step(self, tokens, pos, valid, states, reset=None):
        """One decode iteration over the whole pool.  ``tokens``/
        ``pos``/``valid`` are host float32 vectors of length
        ``num_slots``; ``states`` the device buffers from
        :meth:`init_states`/previous steps.  ``reset`` optionally
        marks slots (1/0) whose state rows must read as fresh zeros
        this step — how a join clears the previous occupant's rows
        without a single extra device dispatch.  Returns (sampled ids
        as a host float vector, new state dict) — the only
        device->host traffic is the id vector.  Handed a
        :class:`StepFeed` as ``tokens``, it reads nothing and returns
        (:class:`PendingStep`, new state dict): the caller reads the
        ids when it has dispatched the step after."""
        if self._spec is not None:
            raise MXNetError("this StepProgram compiled a speculative "
                             "draft-k-verify step: dispatch through "
                             "step_spec()")
        if isinstance(tokens, StepFeed):
            return self._step_ahead(tokens, pos, valid, states, reset)
        host, outs = self._run(tokens, pos, valid, states, reset, None, 1)
        new_states = {name: outs[1 + i]
                      for i, name in enumerate(self.state_names)}
        if self.extra_names:
            self.last_extras = dict(zip(self.extra_names, host[1:]))
        return host[0], new_states

    def _step_ahead(self, feed, pos, valid, states, reset):
        """:meth:`step` for a :class:`StepFeed`: the same one dispatch,
        returned as ``(PendingStep, new state dict)`` with nothing
        read.  The caller goes on writing its vectors while the step
        runs, so the dispatch takes copies of them."""
        tl = self._tl
        after = feed.after
        t0 = time.perf_counter()
        with (tl.annotate("decode.step.dispatch") if tl is not None
              else _telemetry.timeline.NO_SPAN):
            outs = self._dispatch(
                feed.tokens.copy(), pos.copy(), valid.copy(), states,
                None if reset is None else reset.copy(), None,
                prev=None if after is None else after.ids)
            pending = PendingStep(
                outs[0], outs[len(outs) - len(self.extra_names):],
                self.extra_names, tl)
        pending.dispatch_s = time.perf_counter() - t0
        return pending, {name: outs[1 + i]
                         for i, name in enumerate(self.state_names)}

    def pending(self, ids):
        """``ids`` as the :class:`PendingStep` of the step that sampled
        them: what a caller that keeps a step in flight makes of a host
        vector it is handed where it expected the step unread, because
        a wrapper around :meth:`step` read the ids itself, and may have
        changed them.  They are the step's ids from here on, for the
        requests and for the step after."""
        import jax
        return PendingStep(
            jax.device_put(np.asarray(ids, np.float32),
                           self._ids_like.sharding), (), (), self._tl)

    def _dispatch(self, tokens, pos, valid, states, reset, spec,
                  prev=None):
        """Build the flat argument vector and enqueue the step kernel;
        returns its device outputs without waiting for them.  ``prev``
        is the plain step's previous ids (``call``), the newest this
        program sampled where the caller names none."""
        if reset is None:
            reset = np.zeros((self.num_slots,), np.float32)
        flat = self._build_flat(tokens, pos, valid, states)
        if spec is None:
            fourth = self._ids_like if prev is None else prev
        else:
            fourth = spec
        kernel = self._ensure_kernel(reset, fourth, flat)
        self._tick = (self._tick + 1) & 0x7fffffff
        outs = kernel(self._key, np.int32(self._tick), reset, fourth,
                      *flat)
        if spec is None:
            self._ids_like = outs[0]
        return outs

    def _run(self, tokens, pos, valid, states, reset, spec, n_read):
        """One dispatch and the blocking read of its first ``n_read``
        outputs and of the counters past the states (the only
        device->host traffic of a step); returns
        ``(host arrays, device outputs)``.  ``decode.step.read`` holds
        the device's own step time while the host waits for it."""
        tl = self._tl
        n_extra = len(self.extra_names)

        def read(outs):
            return [np.asarray(o) for o in list(outs[:n_read])
                    + list(outs[len(outs) - n_extra:])]
        if tl is None:
            outs = self._dispatch(tokens, pos, valid, states, reset, spec)
            return read(outs), outs
        t0 = time.perf_counter()
        with tl.annotate("decode.step.dispatch"):
            outs = self._dispatch(tokens, pos, valid, states, reset, spec)
        t1 = time.perf_counter()
        with tl.annotate("decode.step.read"):
            host = read(outs)
        self.last_split = (t1 - t0, time.perf_counter() - t1)
        return host, outs

    def _build_flat(self, tokens, pos, valid, states):
        """Assemble the full flat argument vector: params from the
        template, the shared token/pos/valid host vectors into BOTH
        models' feed slots, every state buffer at its position."""
        flat = list(self._template)
        flat[self._feed_pos[self.token_name]] = tokens
        if self.pos_name is not None:
            flat[self._feed_pos[self.pos_name]] = pos
        if self.valid_name is not None:
            flat[self._feed_pos[self.valid_name]] = valid
        for name in self.state_names:
            flat[self._feed_pos[name]] = states[name]
        if self._spec is not None:
            flat[self._d_feed_pos[self._d_tok]] = tokens
            if self._d_pos is not None:
                flat[self._d_feed_pos[self._d_pos]] = pos
            if self._d_valid is not None:
                flat[self._d_feed_pos[self._d_valid]] = valid
            for key in self.draft_state_keys:
                flat[self._d_feed_pos[key]] = states[key]
        return flat

    def step_spec(self, tokens, pos, valid, spec, states, reset=None):
        """One speculative iteration over the whole pool: up to
        ``k + 1`` tokens commit per slot per dispatch.  ``spec`` marks
        the slots eligible for speculation (generating, past their
        prompt) — ineligible slots commit exactly ONE position, the
        plain step's semantics, so teacher forcing and dead slots ride
        the wider program unchanged.  Returns ``(tokens, counts,
        new_states)``: a ``(slots, k+1)`` token matrix, the per-slot
        committed counts, and the committed state dict."""
        if self._spec is None:
            raise MXNetError("step_spec() needs a StepProgram built "
                             "with a SpecConfig")
        (toks, counts), outs = self._run(tokens, pos, valid, states,
                                         reset, spec, 2)
        keys = list(self.state_names) + list(self.draft_state_keys)
        new_states = {key: outs[2 + i] for i, key in enumerate(keys)}
        return toks, counts, new_states

    def probe_step(self):
        """One fixed-key, fixed-tick dispatch over an all-zero scratch
        pool — the bitwise probe replica probation rides on: two
        programs built from the same graph (traced fresh OR loaded
        from the AOT cache) must return exactly equal outputs here
        before a rehabilitated replica may take traffic.  Uses a
        constant PRNGKey and tick so stochastic samplers compare
        deterministically, touches neither ``self._tick`` nor any live
        slot state, and compiles nothing a warmed program has not
        already compiled."""
        import jax
        z = np.zeros((self.num_slots,), np.float32)
        states = self.init_states()
        flat = self._build_flat(z, z, z, states)
        # the speculative mask, or previous ids that no slot asks for
        fourth = z if self._spec is not None else self._ids_like
        kernel = self._ensure_kernel(z, fourth, flat)
        outs = kernel(jax.random.PRNGKey(0), np.int32(0), z, fourth,
                      *flat)
        return [np.asarray(o) for o in outs]

    def sample_tokens(self, logits):
        """Host-side sampling of a ``(rows, vocab)`` logits array with
        this program's sampler — the bucketed-prefill path's first
        token (the prefill program returns raw logits for non-greedy
        samplers; each call burns one tick so prefill draws never
        collide with step draws)."""
        logits = np.asarray(logits)
        if self.sampler.greedy:
            return np.argmax(logits, axis=-1).astype(np.float32)
        import jax
        self._tick = (self._tick + 1) & 0x7fffffff
        k = jax.random.fold_in(self._key, np.int32(self._tick))
        return np.asarray(self.sampler.sample(k, self._jnp.asarray(
            logits, dtype=self._jnp.float32)))


def greedy_decode(program, prompt, max_new_tokens, eos_id=None,
                  max_len=None):
    """Reference single-request greedy decode: teacher-force the prompt
    through ``program`` one token per step, then feed each argmax
    sample back, alone in slot 0.  This is the bitwise ground truth
    the continuous-batching engine is held to (tests/test_decode.py):
    whatever company a request keeps in the slot pool, its tokens must
    equal this loop's output exactly."""
    states = program.init_states()
    n = program.num_slots
    tokens = np.zeros((n,), np.float32)
    pos = np.zeros((n,), np.float32)
    valid = np.zeros((n,), np.float32)
    valid[0] = 1.0
    prompt = list(prompt)
    if not prompt:
        raise MXNetError("greedy_decode needs a non-empty prompt")
    tokens[0] = prompt[0]
    out, p, i = [], 0, 1
    while len(out) < max_new_tokens:
        if max_len is not None and p >= max_len:
            break
        pos[0] = p
        sampled, states = program.step(tokens, pos, valid, states)
        p += 1
        if i < len(prompt):             # still consuming the prompt
            tokens[0] = prompt[i]
            i += 1
            continue
        tok = int(sampled[0])
        out.append(tok)
        tokens[0] = sampled[0]
        if eos_id is not None and tok == eos_id:
            break
    return np.asarray(out, dtype=np.int64)


class _DecodeTelemetry(object):
    """Decode engine's instrument bundle (mxnet_serve_decode_*), built
    only when telemetry is enabled.  Shares the admission families
    with the one-shot engine (AdmissionController reads ``admitted``/
    ``rejected``/``shed``/``expired``/``queue_depth`` off this object)
    so both engine kinds aggregate into one serving picture; decode-
    specific series follow the PR 3-7 idiom — shared counters, per-
    engine gauges reclaimed at close()."""

    def __init__(self, engine):
        reg = _telemetry.registry()
        self.engine_label = str(next(_ENGINE_SEQ))
        self.closed = False
        self.requests = reg.counter(
            "mxnet_serve_requests_total", "serving requests submitted")
        self.admitted = reg.counter(
            "mxnet_serve_admitted_total", "requests admitted")
        self.rejected = reg.counter(
            "mxnet_serve_rejected_total",
            "requests rejected with QueueFullError backpressure")
        self.shed = reg.counter(
            "mxnet_serve_shed_total",
            "requests shed under the shed-oldest overload policy")
        self.regulator_shed = reg.counter(
            "mxnet_serve_regulator_shed_total",
            "requests shed cost-aware by the overload regulator's "
            "tightened queue limit — deliberately NOT part of the "
            "queue-saturation burn numerator (the regulator's own "
            "sheds must not re-fire the rule it is resolving)")
        self.expired = reg.counter(
            "mxnet_serve_expired_total",
            "requests expired past their deadline while queued")
        queue_depth_fam = reg.gauge(
            "mxnet_serve_queue_depth",
            "pending admission-queue depth per engine",
            labelnames=("engine",))
        self.queue_depth = queue_depth_fam.labels(
            engine=self.engine_label)
        self.tokens = reg.counter(
            "mxnet_serve_decode_tokens_total",
            "tokens generated by continuous-batching decode engines")
        self.steps = reg.counter(
            "mxnet_serve_decode_steps_total",
            "decode step-program dispatches (each steps every live "
            "slot once)")
        # slot-occupancy decomposition of every step dispatch (ISSUE
        # 18 satellite): the persistent step always computes num_slots
        # rows, so each dispatch splits exactly into live rows (a
        # seated request advanced) and dead rows (masked slots riding
        # along).  Scraped counters, not occupancy-gauge inference —
        # the goodput plane's dead-slot FLOPs class divides out of
        # these same integers.
        self.slot_steps_live = reg.counter(
            "mxnet_serve_decode_live_slot_steps_total",
            "slot-steps computed for LIVE slots (a seated request's "
            "row advanced one position) across decode step dispatches")
        self.slot_steps_dead = reg.counter(
            "mxnet_serve_decode_dead_slot_steps_total",
            "slot-steps computed for DEAD slots (valid=0 rows riding "
            "the fixed-extent persistent step) across decode step "
            "dispatches")
        # coalesced-prefill element split, per prompt bucket: live =
        # real prompt positions, padded = the pow2 batch extent times
        # the bucket length (what the program actually computed) minus
        # live.  Bounded cardinality: one series per configured bucket.
        self.prefill_live_elems = reg.counter(
            "mxnet_serve_decode_prefill_live_elements_total",
            "prompt positions carrying real tokens in coalesced "
            "prefill dispatches, per prompt bucket",
            labelnames=("bucket",))
        self.prefill_padded_elems = reg.counter(
            "mxnet_serve_decode_prefill_padded_elements_total",
            "padding positions (batch-row and sequence overhang) in "
            "coalesced prefill dispatches, per prompt bucket",
            labelnames=("bucket",))
        self._prefill_elem_handles = {}
        self.joins = reg.counter(
            "mxnet_serve_decode_joins_total",
            "requests that joined the running decode batch (slot "
            "assigned between steps — never a retrace)")
        self.steals = reg.counter(
            "mxnet_serve_decode_steals_total",
            "routed-but-unseated requests STOLEN by a sibling replica "
            "with free slots (cross-replica work stealing: a request "
            "queued behind a full pool re-offers instead of waiting "
            "out its pinned replica's generations)")
        self.leaves = reg.counter(
            "mxnet_serve_decode_leaves_total",
            "requests that left the decode batch, by how generation "
            "ended (eos / length / deadline / closed / cancelled)",
            labelnames=("reason",))
        # label handles resolved ONCE: .labels() does registry work
        # per call, and leaves are hot-path (one per finished request)
        self._leave = {r: self.leaves.labels(reason=r)
                       for r in ("eos", "length", "deadline", "closed",
                                 "cancelled")}
        self.evictions = reg.counter(
            "mxnet_serve_decode_evictions_total",
            "slot-resident requests evicted mid-generation by their "
            "deadline: the future resolves with the PARTIAL tokens "
            "and expired=True, and the slot frees for queued work")
        self.step_ms = reg.histogram(
            "mxnet_serve_decode_step_ms",
            "wall time of one decode iteration (deadline sweep + "
            "dispatch of the next step + read and delivery of the step "
            "in flight), per engine and device replica",
            labelnames=("engine", "replica"),
            buckets=_telemetry.LATENCY_MS_BUCKETS)
        # per-request tail latency the tokens/s counter cannot see
        # (the 2603.09555 O(1)-per-token framing is throughput-only):
        # TTFT = submit -> first generated token (queue wait + prefill
        # + first step), TPOT = mean inter-token gap over a finished
        # request's generation.  Engine-labeled so co-resident engines
        # keep distinct tails AND the series reclaim at close().
        ttft_fam = reg.histogram(
            "mxnet_serve_decode_ttft_seconds",
            "time to first token: submit -> first generated token id "
            "(queue wait + prefill + first step), per decode engine",
            labelnames=("engine",),
            buckets=_telemetry.LATENCY_S_BUCKETS)
        self.ttft = ttft_fam.labels(engine=self.engine_label)
        tpot_fam = reg.histogram(
            "mxnet_serve_decode_tpot_seconds",
            "inter-token latency: mean gap between consecutive "
            "generated tokens per finished request (>= 2 tokens), per "
            "decode engine",
            labelnames=("engine",),
            buckets=_telemetry.LATENCY_S_BUCKETS)
        self.tpot = tpot_fam.labels(engine=self.engine_label)
        # speculative decode plane (ISSUE 15): counters + per-engine
        # accept-rate histogram + tokens-per-step gauge, registered
        # ONLY for spec engines (a k=0 engine's scrape is byte-
        # identical to the pre-spec engine's) and reclaimed at close
        self.spec_drafted = None
        self._spec_fams = ()
        if getattr(engine, "_spec_k", 0):
            self.spec_drafted = reg.counter(
                "mxnet_serve_decode_spec_drafted_total",
                "draft tokens proposed by speculative decode steps "
                "(k per spec-eligible slot per dispatch)")
            self.spec_accepted = reg.counter(
                "mxnet_serve_decode_spec_accepted_total",
                "draft tokens ACCEPTED by target verification — the "
                "tokens that cost one target dispatch for k+1 "
                "positions instead of one dispatch each")
            self.spec_rejected = reg.counter(
                "mxnet_serve_decode_spec_rejected_total",
                "draft tokens rejected by target verification "
                "(speculative work thrown away)")
            spec_accept_fam = reg.histogram(
                "mxnet_serve_decode_spec_accept_rate",
                "per-dispatch draft acceptance fraction "
                "(accepted / drafted over the step's spec-eligible "
                "slots), per decode engine",
                labelnames=("engine",),
                buckets=_telemetry.RATIO_BUCKETS)
            self.spec_accept = spec_accept_fam.labels(
                engine=self.engine_label)
            spec_tps_fam = reg.gauge(
                "mxnet_serve_decode_spec_tokens_per_step",
                "mean committed tokens PER SLOT per speculative step "
                "over the engine lifetime (1.0 = no speculative win; "
                "the ceiling is k+1 — occupancy does not move this "
                "number), per decode engine",
                labelnames=("engine",))
            self.spec_tps = spec_tps_fam.labels(
                engine=self.engine_label)
            self._spec_fams = (spec_accept_fam, spec_tps_fam)
        self.slots_fam = reg.gauge(
            "mxnet_serve_decode_slots",
            "slot-pool capacity per decode engine and device replica",
            labelnames=("engine", "replica"))
        self.occupied_fam = reg.gauge(
            "mxnet_serve_decode_slots_occupied",
            "slots currently generating per decode engine and device "
            "replica — occupied/capacity is decode's batch-occupancy "
            "analog, and the router's most-free-slots signal",
            labelnames=("engine", "replica"))
        compile_fam = reg.gauge(
            "mxnet_serve_compile_count",
            "CachedOp trace counter — programs compiled so far, per "
            "engine", labelnames=("engine",))
        self.compile_count = compile_fam.labels(
            engine=self.engine_label)
        # replica plane: families defined ONCE in replica.py, shared
        # with the one-shot engine (engine ordinals are process-unique)
        # so /healthz renders one per-replica block over both kinds
        from .replica import replica_metric_families
        (replicas_fam, self.replica_healthy, self.replica_inflight,
         self.replica_failures,
         self.replica_shards) = replica_metric_families(reg)
        self.replicas_g = replicas_fam.labels(engine=self.engine_label)
        self.replicas_g.set(len(engine._replicas))
        for r in engine._replicas:
            r.tm_step_ms = self.step_ms.labels(
                engine=self.engine_label, replica=r.label)
            r.tm_failures = self.replica_failures.labels(
                engine=self.engine_label, replica=r.label)
            # per-shard identity under the replica label (static)
            self.replica_shards.labels(
                engine=self.engine_label, replica=r.label).set(
                len(r.plan.devices()) if r.plan is not None else 1)
        # persistent-AOT-cache traffic: same families the one-shot
        # bundle registers (engine ordinals are process-unique, so the
        # shared families aggregate into one fleet view)
        self.aot_fams = aot_metric_families(reg)
        # static memory planner pair (families shared with the
        # one-shot bundle): predicted set eagerly, measured created
        # lazily on the first successful allocator probe so CPU hosts
        # never publish a dead series
        mem_pred_fam, mem_meas_fam = memory_metric_families(reg)
        self.mem_predicted = mem_pred_fam.labels(
            engine=self.engine_label)
        self._mem_meas_fam = mem_meas_fam
        self._mem_measured = None
        self._mem_probe_ok = True
        self._engine_gauge_fams = (queue_depth_fam, compile_fam,
                                   ttft_fam, tpot_fam, replicas_fam,
                                   mem_pred_fam, mem_meas_fam) \
            + self._spec_fams
        self._replica_fams = (self.slots_fam, self.occupied_fam,
                              self.step_ms, self.replica_healthy,
                              self.replica_inflight,
                              self.replica_failures,
                              self.replica_shards) + self.aot_fams
        self._engine = weakref.ref(engine)
        reg.register_callback(self._refresh)

    def leave(self, reason):
        handle = self._leave.get(reason)
        (handle if handle is not None
         else self.leaves.labels(reason=reason)).inc()

    def prefill_elems(self, bucket, live, padded):
        """Count one coalesced prefill dispatch's element split under
        its prompt-bucket label (handles memoized: the bucket set is
        fixed at construction)."""
        h = self._prefill_elem_handles.get(bucket)
        if h is None:
            b = str(bucket)
            h = (self.prefill_live_elems.labels(bucket=b),
                 self.prefill_padded_elems.labels(bucket=b))
            self._prefill_elem_handles[bucket] = h
        if live:
            h[0].inc(live)
        if padded:
            h[1].inc(padded)

    def close(self):
        self.closed = True
        _telemetry.registry().unregister_callback(self._refresh)
        self._remove_engine_series()

    def _remove_engine_series(self):
        for fam in self._engine_gauge_fams:
            fam.remove(engine=self.engine_label)
        for fam in self._replica_fams:
            for values, _inst in fam.series():
                if values[0] == self.engine_label:
                    fam.remove(*values)

    def _refresh(self, reg):
        eng = self._engine()
        if eng is None:
            reg.unregister_callback(self._refresh)
            self._remove_engine_series()
            return
        self.compile_count.set(eng.compile_count)
        refresh_memory_gauges(self, eng)
        eff = getattr(eng, "_eff", None)
        if eff is not None:
            eff.refresh()
        if self.spec_drafted is not None:
            # GIL-atomic int reads: a collect-time callback must not
            # take scheduler locks
            steps, toks = eng._spec_slot_steps, eng._spec_accepted
            if steps:
                # committed tokens per slot per spec step = accepted
                # drafts + the one target token every step yields
                self.spec_tps.set((toks + steps) / float(steps))
        el = self.engine_label
        for r in eng._replicas:
            self.slots_fam.labels(engine=el,
                                  replica=r.label).set(eng.num_slots)
            self.occupied_fam.labels(
                engine=el, replica=r.label).set(r.occupied_count())
            self.replica_healthy.labels(
                engine=el, replica=r.label).set(1.0 if r.healthy
                                                else 0.0)
            self.replica_inflight.labels(
                engine=el, replica=r.label).set(r.inflight())


class DecodeEngine(object):
    """Continuous-batching autoregressive decode over one frozen step
    graph (module docstring has the architecture).

    Parameters
    ----------
    step_sym : Symbol with outputs ``[logits] + next_states``.
    arg_params, aux_params : trained weights (checkpoint artifacts).
    state_info : list of ``{"name", "shape"[, "dtype"][, "cache"]
        [, "window"]}`` — per-slot state buffers, in the order the step
        graph returns their next values (``BaseRNNCell.state_info``
        shapes with the batch dim dropped; see ``begin_state_arrays``
        for the cell-side analog).  ``serving/slot_state.py`` defines
        the format and is its one reader: what ``cache`` and ``window``
        mean, the pool's shapes, dtypes and bytes.
        Outputs of the step graph past the states are counters read
        with the sampled ids (``StepProgram.extra_names``).
    num_slots, max_len : slot-pool geometry (defaults from
        ``MXNET_DECODE_SLOTS`` / ``MXNET_DECODE_MAX_LEN``).
    eos_id : sampling this id ends a request with reason "eos".
    prefill_sym : optional prompt-consumption graph with outputs
        ``[logits_at_last_valid_position] + state_rows`` over arguments
        ``prefill_data_name`` ((1, T) prompt ids, T padded onto pow2
        buckets) and ``prefill_len_name`` ((1,) live prompt length the
        graph's masking keys on).  Either a length-polymorphic Symbol
        or a callable ``T -> Symbol`` (the BucketingModule idiom — an
        unrolled graph bakes its length in).  Compiled through the
        one-shot bucket path (ProgramCache, one program per pow2
        bucket); its state rows are scattered into the free slot.
        Without it, prompts are teacher-forced token-by-token through
        the running step batch (no extra programs).
    prefill_buckets : the padded prompt lengths to compile (default:
        every power of two up to ``max_len``'s).  A deployment that
        knows its prompts names the few it needs; a prompt longer than
        the largest is fed through the step.
    sampler : :class:`Sampler` hook for the token-selection head
        (default :class:`GreedySampler` — bitwise-pinned argmax).
        :class:`TemperatureSampler` runs temperature/top-k categorical
        draws inside the same compiled step using the rng key the
        step already carried dead.
    replicas : data-parallel device replicas (default
        ``MXNET_SERVE_REPLICAS``), each a full slot pool; requests land
        on the freest replica and pin there.  ``ctx`` may be a LIST of
        contexts naming the replica set verbatim.
    sharding : model-parallel plan spec (``parallel/mesh.py``; default
        ``MXNET_SERVE_SHARDING``).  Each replica's step program,
        prefill buckets, and per-slot state then span a
        ``prod(axes)``-device group — state_rules lay the KV cache out
        sharded, so continuous batching runs tensor-parallel.  A plan
        partitioning the SLOT axis is verdict-gated on the step
        graph's row-locality (``analysis.check_sharding_plan``);
        rejected plans refuse construction with a reason.
    """

    def __init__(self, step_sym, arg_params, aux_params, state_info,
                 token_name="token", pos_name="pos", valid_name="valid",
                 num_slots=None, max_len=None, eos_id=None,
                 prefill_sym=None, prefill_data_name="prompt",
                 prefill_len_name="plen", prefill_buckets=None,
                 max_queue=None, default_deadline_ms=None,
                 overload_policy=None, ctx=None, dtype=np.float32,
                 start=True, sampler=None, replicas=None, sharding=None,
                 draft_sym=None, draft_arg_params=None,
                 draft_aux_params=None, draft_state_info=None,
                 spec_k=None):
        from .. import config
        # chaos plan (serving/faults.py): see ServingEngine
        _faults.ensure_env_plan()
        if num_slots is None:
            num_slots = config.get("MXNET_DECODE_SLOTS")
        if max_len is None:
            max_len = config.get("MXNET_DECODE_MAX_LEN")
        # speculative draft-k-verify (ISSUE 15): k > 0 plus a draft
        # model widens every replica's step program to commit up to
        # k+1 tokens per slot per dispatch.  0 (the default) is the
        # single-token engine BYTE-IDENTICAL to the pre-spec code —
        # same programs, same AOT keys, same scrape — whatever draft
        # arguments were passed.
        if spec_k is None:
            spec_k = config.get("MXNET_DECODE_SPEC_K")
        spec_k = int(spec_k)
        if spec_k < 0:
            raise MXNetError("spec_k must be >= 0, got %d" % spec_k)
        if spec_k > 0 and draft_sym is None:
            raise MXNetError(
                "spec_k=%d needs a draft model: pass draft_sym= (and "
                "its params/state_info) — speculation verifies a "
                "cheap draft against the target, there is no draft "
                "to verify" % spec_k)
        self._spec_k = spec_k if draft_sym is not None else 0
        if self._spec_k and sampler is not None and not sampler.greedy \
                and type(sampler).spec_logits is Sampler.spec_logits:
            # refuse at construction, like every other spec contract
            # violation — raising inside the first traced dispatch
            # would ride the replica-failure path and retire healthy
            # replicas over a config error
            raise MXNetError(
                "speculative decode needs the sampler's verification "
                "distribution: %s must implement spec_logits() (see "
                "TemperatureSampler), or use spec_k=0"
                % type(sampler).__name__)
        if max_queue is None:
            max_queue = config.get("MXNET_SERVE_MAX_QUEUE")
        if default_deadline_ms is None:
            default_deadline_ms = config.get(
                "MXNET_SERVE_DEFAULT_DEADLINE_MS")
        if overload_policy is None:
            overload_policy = config.get("MXNET_SERVE_OVERLOAD_POLICY")
        if num_slots < 1:
            raise MXNetError("num_slots must be >= 1, got %d" % num_slots)
        self.num_slots = int(num_slots)
        self.max_len = int(max_len)
        self.eos_id = eos_id
        self._dtype = np.dtype(dtype)
        self._default_deadline_s = float(default_deadline_ms) / 1e3
        self._sampler = sampler if sampler is not None else GreedySampler()
        # the pool's layout, for the analyses that run before a program
        # exists (each replica's StepProgram builds its own)
        self._layout = SlotLayout(
            state_info, self.num_slots, self._dtype,
            draft_state_info if self._spec_k else None)
        self.analysis_report = None
        self.step_verdict = None
        self.draft_verdict = None
        if config.get("MXNET_ANALYSIS_ON"):
            self.step_verdict, self.analysis_report = self._preflight(
                step_sym, "target", token_name, pos_name,
                valid_name, config.get("MXNET_ANALYSIS_STRICT"),
                what="step")
            if self._spec_k:
                # the draft's states ride the SAME slot pool: a cross-
                # position draft would leak one request's (or a dead
                # slot's stale) values into a co-resident's proposals
                # — and through acceptance, into its LATENCY; greedy
                # content stays exact, but the soundness bar is the
                # same as the target's
                self.draft_verdict, _ = self._preflight(
                    draft_sym, "draft", token_name,
                    pos_name, valid_name,
                    config.get("MXNET_ANALYSIS_STRICT"), what="draft")
        if self._spec_k:
            # head compatibility is NOT an analysis-suite opinion —
            # it only needs infer_shape, and a mismatched pair emits
            # garbage tokens silently (take_along_axis clamps under
            # jit) — so it refuses construction even with
            # MXNET_ANALYSIS_ON=0
            self._check_draft_heads(step_sym, draft_sym, token_name,
                                    pos_name, valid_name)
        # fused-op selection (ISSUE 13): run the optimizer's kernel-
        # selection pipeline over the step graph BEFORE any program is
        # built, so StepProgram serves the optimized graph — the
        # one-hot-blend KV write becomes the O(d) _cache_write_row
        # scatter (ops/cache.py) when the verdict-gated plan accepts.
        # A rejected/crashed plan serves the step exactly as handed in.
        # With speculation the DRAFT graph rides the same pipeline —
        # its per-step KV write is as selectable as the target's.
        self.opt_plan = None
        self.selection = None
        self.draft_opt_plan = None
        if config.get("MXNET_SERVE_OPTIMIZE") \
                and config.get("MXNET_ANALYSIS_ON") \
                and config.get("MXNET_OPT_SELECT_KERNELS"):
            step_sym, self.opt_plan, self.selection = \
                self._optimize_step(step_sym, "target", token_name,
                                    pos_name, valid_name, what="step")
            if self._spec_k:
                draft_sym, self.draft_opt_plan, _dsel = \
                    self._optimize_step(draft_sym, "draft", token_name,
                                        pos_name, valid_name, what="draft")
        # what every replica's StepProgram will run (it pins what it is
        # given; a graph already pinned comes back as it is), so that
        # the analyses below price the pool in the dtype it has
        step_sym = _pin_state_dtypes(step_sym, self._layout.target)
        if self._spec_k:
            draft_sym = _pin_state_dtypes(draft_sym, self._layout.draft)
        # the spec bundle every replica's StepProgram shares: draft
        # graph/params plus the ONE verdict-gated commit graph (built
        # here, not per replica — the selection decision is engine
        # policy, and it rides the AOT validity fingerprint)
        self._spec_cfg = None
        if self._spec_k:
            from .spec import SpecConfig
            self._spec_cfg = SpecConfig(
                self._spec_k, draft_sym,
                draft_arg_params=draft_arg_params,
                draft_aux_params=draft_aux_params,
                draft_state_info=draft_state_info,
                token_name=token_name, pos_name=pos_name,
                valid_name=valid_name)
            self._spec_cfg.build(self._layout)
        # model-parallel decode (ROADMAP item 1): the plan spec is
        # verdict-gated on the step graph's slot-axis row-locality —
        # a plan partitioning the slot axis of a cross-position (or
        # unanalyzed) step is rejected with a reason at construction,
        # exactly like every rewrite.  Param/state tensor-parallel
        # rules are placement-only and never gated.
        from ..analysis.sharding import gate_plan_spec
        # sharded plans gate the WIDER step like any program: with
        # speculation the compiled step contains both models, so a
        # slot-partitioning plan needs BOTH slot verdicts row-local
        # (either unproven/cross-position verdict fails the gate)
        gate_verdict = self.step_verdict
        if self._spec_k and gate_verdict == "row-local" \
                and self.draft_verdict != "row-local":
            gate_verdict = self.draft_verdict
        self.sharding_check, self._sharding_spec = gate_plan_spec(
            sharding, {"slot": gate_verdict}, "decode",
            "DecodeEngine")
        self._prefill_data_name = prefill_data_name
        self._prefill_len_name = prefill_len_name
        # coalesced bucketed prefill (ROADMAP 4b): joiners landing in
        # the same scheduler iteration share ONE prefill dispatch per
        # pow2 (batch, prompt) bucket instead of batch-1 each — the
        # direct TTFT lever at concurrency (decode_bench --prefill)
        self._coalesce = bool(config.get("MXNET_DECODE_COALESCE_PREFILL"))
        self._prefill_dispatches = 0
        # what a prefill dispatch costs the device, in seconds, as
        # {bucket: {batch: s}}: the median of the last readings of the
        # live dispatches (``_prefill_observed``), which ``_seats_now``
        # decides on
        self._prefill_cost = {}
        self._prefill_readings = {}
        # (attention nodes that took the fused kernel, attention
        # nodes) of each (bucket, batch) prefill program, and their
        # sums over the prefill dispatches
        self._prefill_fused = {}
        self._prefill_fused_attention = 0
        self._prefill_attention_nodes = 0
        # (row, expert) products the step's expert nodes multiply at
        # pool extent, and the experts a live row is routed to, summed
        # over those nodes (None: the step has no expert layer)
        self._expert_work = self._step_expert_work(
            step_sym, arg_params, token_name, pos_name, valid_name)
        # device replicas (serving/replica.py, ROADMAP 2a): each owns a
        # FULL slot pool — persistent step program + device-resident
        # state + prefill bucket caches, params uploaded once per
        # replica.  New requests land on the replica with the most free
        # slots and pin there for their whole generation (migrating a
        # request would ship its KV cache across devices); replicas == 1
        # is the pre-replica fast path, no router, no extra threads.
        #
        # Per-replica prefill goes through the one-shot bucket path:
        # one compiled program per pow2 prompt bucket, batch 1 (state
        # rows scatter into exactly one free slot).  ``prefill_sym`` is
        # either a length-polymorphic Symbol (one graph, ProgramCache's
        # shape keys are the buckets) or — the BucketingModule idiom,
        # since an unrolled graph bakes its length in — a callable
        # ``T -> Symbol`` invoked once per bucket.
        if prefill_sym is None:
            prefill_buckets = ()
        elif prefill_buckets is None:
            buckets, b = [], 1
            top = _next_pow2(self.max_len)
            while b <= top:
                buckets.append(b)
                b <<= 1
            prefill_buckets = tuple(buckets)
        else:
            prefill_buckets = tuple(sorted(int(b) for b in prefill_buckets))
        # coalesced prefill dispatches at pow2 BATCH buckets too (a
        # group of joiners pads up to the next one); serial mode only
        # ever dispatches batch 1 — warmup warms exactly this grid, so
        # the zero-warm-retrace contract covers every coalesced shape
        batches, bb = [], 1
        top_b = _next_pow2(self.num_slots)
        while bb <= top_b:
            batches.append(bb)
            bb <<= 1
        self._prefill_batches = tuple(batches) if self._coalesce else (1,)
        # static memory planner (analysis/memory.py): liveness-price
        # the whole warm set — step program at slot-pool shapes with
        # the pool's state-for-state donation spec gated for
        # soundness, draft step additively under spec, largest
        # prefill bucket plus the resident pool — against the device
        # budget BEFORE any compile.  Purely diagnostic: the engine
        # serves bitwise-identically with the planner off.
        self.memory_plan = None
        # the positions one prefill dispatch may hold, which the memory
        # preflight derives from what the device has free beside the
        # weights and the pool (None: no budget known, every batch of
        # every bucket).  The warm set is the (batch, bucket) shapes
        # within it, and coalescing forms no group past it
        self._prefill_token_budget = None
        if config.get("MXNET_MEMORY_PLAN") \
                and config.get("MXNET_ANALYSIS_ON"):
            self._memory_preflight(
                step_sym, arg_params, aux_params,
                token_name, pos_name, valid_name, prefill_sym,
                prefill_buckets, draft_sym,
                draft_arg_params, draft_aux_params,
                config.get("MXNET_ANALYSIS_STRICT"))
        budget = self._prefill_token_budget
        self._prefill_grid = {
            b: tuple(bb for bb in self._prefill_batches
                     if budget is None or bb * b <= budget)
            for b in prefill_buckets}
        prefill_buckets = tuple(b for b in prefill_buckets
                                if self._prefill_grid[b])
        # persistent AOT program cache (serving/aot_cache.py,
        # MXNET_AOT_CACHE_DIR): one per engine, shared by every
        # replica's step program, prefill buckets, and row-scatter
        # kernels — a restarted engine (or a rehabilitated replica)
        # loads warm instead of retracing.  The step verdict rides the
        # validity fingerprint (re-validated on load: drift rejects the
        # entry); the sampler policy — which shapes the compiled head —
        # rides the key, minus the runtime-only seed.
        from .aot_cache import AOTCache
        sampler_fp = {k: v for k, v in self._sampler.describe().items()
                      if k != "seed"}
        # spec policy rides the KEY (cross-k and cross-draft hits are
        # impossible by address) AND the validity fingerprint (below):
        # graph-invariant entries — prefill buckets, universal
        # row-scatter kernels — share one key across spec regimes, so
        # only the fingerprint protects them, and it must: toggling k
        # or swapping drafts REJECTS those entries (alertable "cold
        # start that should have been warm"), never serves a program
        # compiled under different spec conclusions.  Both components
        # are OMITTED when spec is off, so a pre-spec cache volume
        # stays warm across this upgrade.
        artifact = {"kind": "decode",
                    "step_verdict": self.step_verdict,
                    "selection": self.selection,
                    "optimizer": {
                        "accepted": (bool(self.opt_plan.accepted)
                                     if self.opt_plan is not None
                                     else None),
                        "nodes_before": (self.opt_plan.nodes_before
                                         if self.opt_plan is not None
                                         else None),
                        "nodes_after": (self.opt_plan.nodes_after
                                        if self.opt_plan is not None
                                        else None)},
                    # the memory plan's digest rides the validity
                    # fingerprint: a planner upgrade that moves the
                    # prediction re-prices warm entries instead of
                    # serving under stale capacity conclusions
                    "memory": (self.memory_plan.get("digest")
                               if self.memory_plan else None)}
        key_extra = {"engine_kind": "decode", "sampler": sampler_fp}
        if self._spec_cfg is not None:
            artifact["spec"] = dict(self._spec_cfg.describe(),
                                    draft_verdict=self.draft_verdict)
            key_extra["spec"] = {"k": self._spec_cfg.k,
                                 "draft": self._spec_cfg.draft_digest}
        # the fused-op selection outcome rides the validity FINGERPRINT
        # (not the key): flipping MXNET_OPT_SELECT_KERNELS between
        # restarts moves the fingerprint, so every entry the previous
        # selection regime wrote is REJECTED on load (alertable "cold
        # start that should have been warm") rather than any program
        # compiled under different analysis conclusions being served —
        # the step graph's own key also moves (its canonical form
        # changed), but graph-invariant entries (prefill buckets,
        # universal row-scatter kernels) are only protected by the
        # fingerprint (tests/test_decode_fastpath.py pins the reject)
        self._aot = AOTCache.from_config(
            artifact=artifact,
            key_extra=key_extra,
            # plan spec = the key's sharding component (residual b2):
            # sharded and unsharded step programs (or two plans) can
            # never hit each other's entries; same-plan replicas share
            sharding=self._sharding_spec or "none")
        # everything _new_replica needs, kept for probation re-warm
        # (rehabilitate): the param handles are the same NDArrays the
        # program caches already hold device copies of — no extra
        # host memory of consequence
        self._ctor = {"step_sym": step_sym, "arg_params": arg_params,
                      "aux_params": aux_params,
                      "state_info": state_info,
                      "token_name": token_name, "pos_name": pos_name,
                      "valid_name": valid_name, "dtype": dtype,
                      "prefill_sym": prefill_sym,
                      "prefill_buckets": prefill_buckets}
        # unified fleet timeline (telemetry/timeline.py): cached ring
        # reference, None when the plane is off — the disabled path
        # appends nothing and decodes bitwise-identically.  Read once,
        # before the replicas: their step programs time with this ring
        self._tl = (_telemetry.timeline.get()
                    if _telemetry.timeline.enabled() else None)
        self._replicas = []
        placements = resolve_replica_placements(replicas, ctx,
                                                self._sharding_spec)
        for i, (rctx, rplan) in enumerate(placements):
            self._replicas.append(self._new_replica(i, rctx, rplan))
        self._multi = len(self._replicas) > 1
        self._dr_lock = named_lock("decode.replica")
        self._dr_cond = named_condition("decode.replica", self._dr_lock)
        self._dr_stop = False
        self._slot_free = threading.Event()
        self._tm = (_DecodeTelemetry(self)
                    if _telemetry.enabled() else None)
        # serving efficiency plane (ISSUE 18): per-dispatch FLOPs
        # ledger + MFU/goodput gauges + per-tenant accounting.  Step
        # programs are priced ONCE here (memoized on the program);
        # prefill buckets price lazily in ProgramCache._plan_for.
        self._eff = None
        if self._tm is not None and _goodput.enabled():
            self._eff = _goodput.EngineEfficiency(
                "decode", self._tm.engine_label)
            for r in self._replicas:
                self._eff.add_replica(r.label, ctx=r.ctx)
                _goodput.price_step_program(r.program)
        if self._tm is not None and self._aot is not None:
            self._aot.bind_telemetry(*(
                fam.labels(engine=self._tm.engine_label)
                for fam in self._tm.aot_fams))
        self._trace_chain = (_telemetry.chain_from_config()
                             if self._tm is not None else None)
        self._owns_http_server = (_telemetry.server.engine_acquire()
                                  if self._tm is not None else False)
        self._adm = AdmissionController(
            max_queue=max_queue, overload_policy=overload_policy,
            wake_hint=self.num_slots * len(self._replicas),
            telemetry=self._tm)
        self._lock = named_lock("decode.engine")
        self._step_ms = collections.deque(maxlen=4096)
        self._lat_ms = collections.deque(maxlen=4096)
        self._steps = 0
        self._steps_ahead = 0       # dispatched before the step before
        #                             them was read
        self._discarded = 0         # slot-steps whose result was thrown
        #                             away: their request had left
        self._slot_steps_held = 0   # seatable requests x steps the join
        #                             policy left waiting for a batch
        self._joins = 0
        self._steals = 0
        self._leaves = 0
        self._evictions = 0
        self._tokens_out = 0
        self._requests_served = 0
        self._spec_steps = 0        # dispatches with >=1 spec slot
        self._spec_slot_steps = 0   # per-slot spec steps (the
        #                             tokens-per-step denominator)
        self._spec_drafted = 0
        self._spec_accepted = 0
        self._abort = False
        # history/alerting plane (engine.py has the full story): the
        # scheduler loop stamps a heartbeat, the engine registers for
        # flight-recorder stats() capture, default SLO rules cover the
        # decode plane (shared burn rates + per-engine zero-progress
        # watchdog), and the recorder sampler is refcounted.
        # Registered LAST — after the failure-prone slot-pool state
        # allocation — so a constructor that raises never holds a
        # rule, heartbeat, or recorder reference close() cannot drop.
        self._hb_t = time.monotonic()
        self._hb_busy = False
        self._owns_recorder = False
        self._alert_owner = None
        self._obs_name = None
        if self._tm is not None:
            self._obs_name = "decode.%s" % self._tm.engine_label
            _telemetry.recorder.register_heartbeat(self._obs_name,
                                                   self._heartbeat)
            _telemetry.recorder.register_engine(self._obs_name, self)
            self._owns_recorder = _telemetry.recorder.recorder_acquire()
            if config.get("MXNET_TELEMETRY_ALERTS"):
                self._alert_owner = \
                    _telemetry.register_engine_default_rules(
                        "decode", self._tm.engine_label,
                        aot=self._aot is not None)
        # self-healing control plane (ISSUE 12): see ServingEngine
        self._regulator = None
        if self._tm is not None and config.get("MXNET_REGULATOR"):
            from .regulator import Regulator
            self._regulator = Regulator(
                self._adm, engine_label=self._tm.engine_label,
                name=self._obs_name or "decode")
        self._sup_owner = False
        if config.get("MXNET_SUPERVISOR"):
            from . import supervisor as _supervisor
            _supervisor.engine_acquire(self,
                                       name=self._obs_name or "decode")
            self._sup_owner = True
        self._worker = None
        if start:
            self.start()

    # single-replica aliases: replica 0 IS the engine on the fast path,
    # and tests stage prefill failures by swapping these directly
    @property
    def _program(self):
        return self._replicas[0].program

    @property
    def _prefill_caches(self):
        return self._replicas[0].prefill_caches

    @_prefill_caches.setter
    def _prefill_caches(self, value):
        self._replicas[0].prefill_caches = value

    @property
    def _prefill_buckets(self):
        return self._replicas[0].prefill_buckets

    @_prefill_buckets.setter
    def _prefill_buckets(self, value):
        self._replicas[0].prefill_buckets = tuple(value)

    def _new_replica(self, index, rctx, plan=None):
        """Build one fully-formed DecodeReplica (step program + prefill
        caches, params uploaded to its device — or sharded across its
        plan's device group) from the construction state — used at
        engine construction AND by ``rehabilitate()``, which must
        rebuild a retired replica's programs from scratch (its donated
        state buffers may be consumed) but draws every compile from
        the AOT cache when one is configured."""
        from ..symbol import Symbol as _Symbol
        c = self._ctor
        prog = StepProgram(c["step_sym"], c["arg_params"],
                           c["aux_params"], c["state_info"],
                           self.num_slots,
                           token_name=c["token_name"],
                           pos_name=c["pos_name"],
                           valid_name=c["valid_name"],
                           ctx=rctx, dtype=c["dtype"],
                           sampler=self._sampler, aot=self._aot,
                           plan=plan, spec=self._spec_cfg)
        # the engine's gate is the program's: a replica rebuilt later
        # (rehabilitate) splits its steps iff ``_step_once`` reads them
        prog._tl = self._tl
        rep = DecodeReplica(index, rctx, prog, plan=plan)
        prefill_sym = c["prefill_sym"]
        if prefill_sym is not None:
            rep.prefill_buckets = c["prefill_buckets"]
            # Symbol is itself callable (compose), so "callable" alone
            # cannot distinguish the T -> Symbol builder idiom
            if not isinstance(prefill_sym, _Symbol) \
                    and callable(prefill_sym):
                for b in rep.prefill_buckets:
                    rep.prefill_caches[b] = self._build_prefill(
                        prefill_sym(b), c["arg_params"],
                        c["aux_params"], rctx, c["dtype"], prog, plan)
            else:
                shared = self._build_prefill(
                    prefill_sym, c["arg_params"], c["aux_params"],
                    rctx, c["dtype"], prog, plan)
                for b in rep.prefill_buckets:
                    rep.prefill_caches[b] = shared
        return rep

    def _build_prefill(self, psym, arg_params, aux_params, ctx, dtype,
                       program, plan=None):
        """Wrap one prefill graph with the sampling head and compile-
        once plumbing: outputs become [first sampled token id] + state
        rows under the greedy head, or [last-position logits] + state
        rows for stochastic samplers (the host then draws through
        ``StepProgram.sample_tokens`` so prefill uses the same sampler
        and key stream as the step)."""
        from .. import symbol as sym
        if len(psym) != 1 + len(program.state_names):
            raise MXNetError(
                "prefill graph has %d outputs; expected 1 (logits at "
                "the last valid position) + %d state rows"
                % (len(psym), len(program.state_names)))
        head = (sym.argmax(psym[0], axis=1,
                           name="__decode_prefill_sample__")
                if self._sampler.greedy else psym[0])
        wrapped = sym.Group(
            [head] + [psym[i] for i in range(1, len(psym))])
        return ProgramCache(
            wrapped, arg_params, aux_params,
            data_names=[self._prefill_data_name, self._prefill_len_name],
            ctx=ctx, dtype=dtype, aot=self._aot, aot_kind="prefill",
            plan=plan, program="mx_decode_prefill")

    # ---------------------------------------------------------- preflight
    def _preflight(self, step_sym, which, token_name, pos_name,
                   valid_name, strict, what="step"):
        """Construction-time soundness lint: the masked step must be
        row-local along the SLOT axis with state seeded pad-dirty
        (analysis.check_decode_step) — a cross-position step would let
        one request's (or a dead slot's stale) values bleed into a
        co-resident request's tokens.  Runs over the target step AND
        (speculative engines) the draft graph — both ride the same
        slot pool.  Returns (verdict, report)."""
        from ..analysis import check_decode_step, AnalysisError
        grid = self._layout.grid(step_sym, token_name, pos_name,
                                 valid_name, which)
        verdict, report = check_decode_step(
            step_sym, grid.shapes, state_names=grid.state_names,
            valid_name=valid_name if valid_name in grid.shapes else None)
        if report.errors:
            if strict:
                report.raise_if_errors()
            warnings.warn("DecodeEngine: %s-graph verification "
                          "failed:\n%s" % (what, report.format()))
            return verdict, report
        if verdict == "cross-position":
            detail = "\n".join("  " + str(d) for d in report.warnings) \
                or "  (see report)"
            msg = ("[padding] DecodeEngine: %s graph is cross-"
                   "position along the SLOT axis — co-resident "
                   "requests (and stale state in freed slots) would "
                   "contaminate each other's tokens:\n%s"
                   % (what, detail))
            if strict:
                raise AnalysisError(msg)
            warnings.warn(msg + "\ncontinuing because "
                          "MXNET_ANALYSIS_STRICT=0; decoded output "
                          "WILL differ from single-request decode")
        return verdict, report

    def _memory_preflight(self, step_sym, arg_params, aux_params,
                          token_name, pos_name, valid_name, prefill_sym,
                          prefill_buckets, draft_sym, draft_arg_params,
                          draft_aux_params, strict):
        """OOM preflight + donation gate (analysis/memory.py).

        The step program is priced at slot-pool shapes with the pool's
        state-for-state donation spec — state ``i`` aliases output
        ``1+i``, exactly what StepProgram donates — and an UNSOUND
        donation (a state read by a node not ordered before its
        aliasing next-state write) is refused here with the node
        pinned, because the in-place update would clobber the buffer
        before its last read.  Speculative engines price the draft
        step additively: both models and both state pools are resident
        during a dispatch.  Prefill is priced at its largest
        (batch, prompt) bucket PLUS the resident slot pool (prefill
        runs while the pool lives; the pool is not among its inputs).
        Bytes divide along plan-partitioned axes.  Over budget warns
        naming the offending program and bytes — plus a max-slots-
        that-fit advisory — and ``MXNET_ANALYSIS_STRICT=1`` raises;
        either way the verdict lands before any compile."""
        from ..analysis import AnalysisError
        from ..analysis.memory import (plan_memory, plan_digest,
                                       device_memory_budget, format_bytes)
        from ..symbol import Symbol as _Symbol
        try:
            spec = self._sharding_spec

            def price_step(sym_, which, a_params, x_params):
                grid = self._layout.grid(sym_, token_name, pos_name,
                                         valid_name, which)
                dtypes = dict(grid.dtypes)
                for src in (a_params or {}), (x_params or {}):
                    for k, v in src.items():
                        dt = getattr(v, "dtype", None)
                        if dt is not None:
                            dtypes.setdefault(k, np.dtype(dt))
                plan, _rep = plan_memory(sym_, grid.shapes, dtypes=dtypes,
                                         sharding=spec, donate=grid.donate,
                                         state_names=grid.state_names)
                return plan

            plan = price_step(step_sym, "target", arg_params, aux_params)
            if not plan:
                return
            dplan = None
            if self._spec_k and draft_sym is not None:
                dplan = price_step(draft_sym, "draft", draft_arg_params,
                                   draft_aux_params)
            # the target's pool, which the step's inputs already
            # include, stays resident under prefill too
            pool = self._layout.pool_bytes(spec)
            per_slot = self._layout.slot_bytes(spec)

            def row(label, p):
                return {"program": label,
                        "peak_bytes": p["peak_bytes"],
                        "param_bytes": p["param_bytes"],
                        "transient_peak_bytes":
                            p["transient_peak_bytes"],
                        "inplace_savings_bytes":
                            p["inplace_savings_bytes"]}

            programs = [row("step", plan)]
            need = plan["peak_bytes"]
            offender = "step"
            donation = {"step": plan["donation"]}
            if dplan:
                programs.append(row("draft", dplan))
                need += dplan["peak_bytes"]
                offender = "step+draft"
                donation["draft"] = dplan["donation"]
            budget = device_memory_budget()
            if prefill_sym is not None and prefill_buckets:
                # one row of the largest bucket prices a position; what
                # the device has free beside the step's peak (weights,
                # pool, the step's own temporaries) then says how many
                # positions a dispatch may hold, and the largest warm
                # shape within that is the row that is reported
                b_top = max(prefill_buckets)
                bb = 1
                psym = prefill_sym
                if not isinstance(psym, _Symbol) and callable(psym):
                    psym = psym(b_top)
                parg = set(psym.list_arguments())
                pshapes = {}
                if self._prefill_data_name in parg:
                    pshapes[self._prefill_data_name] = (bb, b_top)
                if self._prefill_len_name in parg:
                    pshapes[self._prefill_len_name] = (bb,)
                pdtypes = {}
                for src in (arg_params or {}), (aux_params or {}):
                    for k, v in src.items():
                        dt = getattr(v, "dtype", None)
                        if dt is not None:
                            pdtypes.setdefault(k, np.dtype(dt))
                pplan, _rep = plan_memory(psym, pshapes,
                                          dtypes=pdtypes,
                                          sharding=spec)
                if pplan:
                    per_token = max(
                        1, pplan["transient_peak_bytes"] // b_top)
                    if budget is not None:
                        self._prefill_token_budget = max(
                            0, int((budget - need) // per_token))
                    cap = self._prefill_token_budget
                    warm = [bt * b for b in prefill_buckets
                            for bt in self._prefill_batches
                            if cap is None or bt * b <= cap]
                    tokens = max(warm) if warm else 0
                    label = "prefill[%d positions]" % tokens
                    r = row(label, pplan)
                    r["transient_peak_bytes"] = per_token * tokens
                    r["peak_bytes"] = (pplan["param_bytes"] + pool
                                       + per_token * tokens)
                    programs.append(r)
                    if r["peak_bytes"] > need:
                        need = r["peak_bytes"]
                        offender = label
            mem = {
                "enabled": True,
                "programs": programs,
                "predicted_peak_bytes": need,
                "param_bytes": plan["param_bytes"],
                "pool_bytes": pool,
                "per_slot_bytes": per_slot,
                "offender": offender,
                "sharded": bool(spec),
                "donation": donation,
            }
            # budget is a property of THIS host, not of the plan:
            # digest only the deterministic prediction, or the same
            # program would fingerprint-drift across machines
            mem["digest"] = plan_digest(
                {k: mem[k] for k in ("programs", "predicted_peak_bytes",
                                     "sharded", "donation")})
            mem["prefill_token_budget"] = self._prefill_token_budget
            mem["budget_bytes"] = budget
            mem["budget_ok"] = (None if budget is None
                                else need <= budget)
            mem["max_slots_fit"] = (
                max(0, int((budget - (need - pool)) // per_slot))
                if budget is not None and per_slot > 0 else None)
            self.memory_plan = mem
            bad = [(label, d) for label, d in sorted(donation.items())
                   if d is not None and not d["accepted"]]
            if bad:
                detail = "\n".join(
                    "  [%s] %s" % (label, reason)
                    for label, d in bad for reason in d["reasons"])
                msg = ("[memory] DecodeEngine slot-pool donation is "
                       "UNSOUND — an in-place next-state write would "
                       "clobber a state buffer before its last read:"
                       "\n%s" % detail)
                if strict:
                    raise AnalysisError(msg)
                warnings.warn(msg + "\ncontinuing because "
                              "MXNET_ANALYSIS_STRICT=0; the engine "
                              "does NOT donate these buffers safely")
            if mem["budget_ok"] is False:
                fit = mem["max_slots_fit"]
                msg = ("DecodeEngine memory preflight: program %r "
                       "predicts peak %s (slot pool %s for %d slots "
                       "+ params %s) but the device budget is %s — "
                       "the warm set cannot fit%s; shrink num_slots/"
                       "max_len, shard the plan, or raise "
                       "MXNET_MEMORY_BUDGET_BYTES (priced before any "
                       "compile)"
                       % (offender, format_bytes(need),
                          format_bytes(pool), self.num_slots,
                          format_bytes(plan["param_bytes"]),
                          format_bytes(budget),
                          (" (at most %d slots fit)" % fit
                           if fit is not None else "")))
                if strict:
                    raise AnalysisError("[memory] " + msg)
                warnings.warn(msg)
        except AnalysisError:
            raise
        except Exception as e:      # planner crash must never block
            #                         construction: advisory pass
            warnings.warn("DecodeEngine: memory preflight crashed "
                          "(%r); continuing without a memory plan"
                          % (e,))

    def _check_draft_heads(self, step_sym, draft_sym, token_name,
                           pos_name, valid_name):
        """Draft-compatibility contract: the two heads must score the
        SAME vocabulary — acceptance compares the draft's proposal
        against the target's distribution index-for-index, so a vocab
        (or logits-rank) mismatch produces garbage comparisons, not an
        error, and must be refused at construction."""
        def logits_shape(sym_, which):
            _a, out, _x = sym_.infer_shape(**self._layout.grid(
                sym_, token_name, pos_name, valid_name, which).shapes)
            return tuple(out[0])
        try:
            t_shape = logits_shape(step_sym, "target")
            d_shape = logits_shape(draft_sym, "draft")
        except Exception as e:
            warnings.warn("DecodeEngine: cannot infer draft/target "
                          "head shapes (%r); the head-compatibility "
                          "check is skipped" % (e,))
            return
        if t_shape != d_shape:
            raise MXNetError(
                "speculative decode: target head scores %s but the "
                "draft head scores %s — draft and target must share "
                "one vocabulary (and logits layout) for acceptance "
                "to compare them" % (t_shape, d_shape))

    def _optimize_step(self, step_sym, which, token_name, pos_name,
                       valid_name, what="step"):
        """Run the kernel-selection optimizer pipeline
        (``analysis.SELECT_OPT_PASSES``) over the step graph under the
        SAME spec the preflight lint uses — slot-pool shapes, slot
        padded axis, state inputs seeded pad-DIRTY — so a selection is
        adopted only via an accepted verdict-gated OptPlan: re-analysis
        no worse, slot-axis row-locality preserved.  Returns
        ``(graph, plan, selection)`` where the graph is what
        StepProgram should compile (the input graph verbatim on
        rejection or crash)."""
        from ..analysis import optimize_graph, SELECT_OPT_PASSES
        try:
            grid = self._layout.grid(step_sym, token_name, pos_name,
                                     valid_name, which)
            plan = optimize_graph(
                step_sym, data_shapes=grid.shapes, dtypes=grid.dtypes,
                pad_axes={"slot": {name: 0 for name in grid.shapes}},
                valid_lengths=({"slot": valid_name}
                               if valid_name in grid.shapes else None),
                pad_dirty=tuple(grid.state_names),
                passes=SELECT_OPT_PASSES)
        except Exception as e:    # optimizer crash must never block
            warnings.warn("DecodeEngine: %s-graph optimization "
                          "crashed (%r); serving the unmodified graph"
                          % (what, e))
            return step_sym, None, None
        if plan.accepted and plan.symbol is not None and plan.rewrites:
            # the fingerprint-visible selection summary: which fused
            # kernels the accepted plan swapped in, and where
            selection = [{"op": "_cache_write_row",
                          "site": a.node}
                         for a in plan.actions
                         if a.kind == "select"]
            return plan.symbol, plan, selection
        if not plan.accepted:
            warnings.warn("DecodeEngine: %s-graph optimization "
                          "rejected (%s); serving the unmodified graph"
                          % (what, plan.reason))
        return step_sym, plan, None

    # ---------------------------------------------------------- lifecycle
    def start(self):
        if self._adm.closed:
            raise EngineClosedError(
                "engine is closed; build a new DecodeEngine")
        if self._worker is None:
            self._worker = threading.Thread(target=self._run,
                                            name="mxnet-decode-worker",
                                            daemon=True)
            self._worker.start()
        self._ensure_replica_threads()
        return self

    def _ensure_replica_threads(self):
        """Spawn the per-replica scheduler threads (multi-replica only:
        the single-replica worker steps its pool inline)."""
        if not self._multi:
            return
        for rep in self._replicas:
            if rep.thread is None:
                rep.thread = threading.Thread(
                    target=self._decode_replica_run, args=(rep,),
                    name="mxnet-decode-replica-%d" % rep.index,
                    daemon=True)
                rep.thread.start()

    def close(self, drain=True):
        """Stop admitting.  With ``drain``, queued AND slot-resident
        requests run to completion first; otherwise queued futures
        fail with EngineClosedError and in-flight requests resolve
        with their PARTIAL tokens (finish_reason "closed")."""
        # regulator first: a drain must not race a still-ticking
        # regulator shedding the queued work it is trying to finish
        if self._regulator is not None:
            self._regulator.close()
            self._regulator = None
        if self._sup_owner:
            from . import supervisor as _supervisor
            self._sup_owner = False
            _supervisor.engine_release(self)
        if not drain:
            self._abort = True
        self._adm.close(drain=drain)
        if self._worker is not None:
            self._worker.join(timeout=None if drain else 60)
            if not self._worker.is_alive():
                self._worker = None
        elif drain:
            # never started: route the backlog on the caller's thread
            # (replica threads must exist to drain the routed half)
            self._ensure_replica_threads()
            self._run()
        if self._multi:
            # router is done; replica threads finish seated generations
            # (drain) or abort with partial output, then exit
            with self._dr_lock:
                self._dr_stop = True
                self._dr_cond.notify_all()
            for rep in self._replicas:
                if rep.thread is not None:
                    rep.thread.join(timeout=None if drain else 60)
                    if not rep.thread.is_alive():
                        rep.thread = None
        if self._eff is not None:
            self._eff.close()
            self._eff = None
        # the timeline ring is process-wide (no per-engine state to
        # reclaim); drop the reference so a closed engine cannot feed
        self._tl = None
        if self._tm is not None:
            self._tm.close()
        if self._obs_name is not None:
            _telemetry.recorder.unregister_heartbeat(self._obs_name)
            _telemetry.recorder.unregister_engine(self._obs_name)
            self._obs_name = None
        if self._alert_owner is not None:
            _telemetry.default_manager().remove_owner(self._alert_owner)
            self._alert_owner = None
        if self._owns_recorder:
            token, self._owns_recorder = self._owns_recorder, False
            _telemetry.recorder.recorder_release(token)
        if self._owns_http_server:
            self._owns_http_server = False
            _telemetry.server.engine_release()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # ------------------------------------------------------------- client
    def submit(self, prompt, max_new_tokens=None, deadline_ms=None,
               on_token=None, request_id=None, tenant=None):
        """Enqueue one generation request; returns a Future resolving
        to a :class:`DecodeResult`.

        ``prompt`` is a non-empty sequence of token ids; generation
        continues until ``eos_id`` is sampled, ``max_new_tokens`` are
        out, the slot's ``max_len`` positions fill, or the deadline
        passes (partial result, ``expired=True``).

        ``on_token`` optionally streams the generation: it is called
        with each generated token id (int) in order — the exact prefix
        the final ``DecodeResult.tokens`` will hold — from the engine's
        scheduler thread, so it must be cheap and thread-safe.  A
        raising callback evicts only its own request: the future fails
        with the callback's exception and co-resident requests keep
        generating.

        ``request_id`` additionally publishes the stream over HTTP:
        each generated token becomes a ``decode.token`` event on the
        ``GET /events`` SSE endpoint (``{"request_id", "index",
        "token"}``, with a final ``{"request_id", "done": true,
        "finish_reason"}`` frame), so any SSE client can follow one
        request's generation by filtering on its id — and resume after
        a disconnect via the standard ``Last-Event-ID`` replay the
        EventHub already implements.  Requires telemetry; None (the
        default) publishes nothing.

        ``tenant`` optionally attributes this request to an accounting
        tenant: the serving-efficiency plane (telemetry/goodput.py)
        then tracks its useful FLOPs, generated tokens, end-to-end
        latency, and outcome under a bounded-cardinality ``tenant``
        label (``MXNET_TELEMETRY_TENANTS_MAX`` distinct labels; later
        tenants aggregate into ``"other"``).  Pure observability —
        scheduling is tenant-blind."""
        if self._adm.closed:
            raise EngineClosedError("decode engine is closed")
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise MXNetError("decode needs a non-empty prompt (feed at "
                             "least a BOS token)")
        if len(prompt) >= self.max_len:
            raise MXNetError(
                "prompt length %d leaves no room to generate within "
                "max_len=%d positions" % (len(prompt), self.max_len))
        cap = self.max_len - len(prompt)
        if max_new_tokens is None:
            max_new_tokens = cap
        max_new_tokens = int(max_new_tokens)
        if max_new_tokens < 1:
            raise MXNetError("max_new_tokens must be >= 1")
        max_new_tokens = min(max_new_tokens, cap)
        if deadline_ms is None and self._default_deadline_s > 0:
            deadline_ms = self._default_deadline_s * 1e3
        deadline = None if not deadline_ms else \
            time.monotonic() + float(deadline_ms) / 1e3
        fut = Future()
        trace = None
        if self._tm is not None:
            self._tm.requests.inc()
            if self._trace_chain is not None:
                trace = _telemetry.LazyTrace(self._trace_chain,
                                             name="decode.request")
        req = DecodeRequest(prompt, max_new_tokens, fut,
                            deadline=deadline, trace=trace,
                            on_token=on_token,
                            sse_id=(str(request_id)
                                    if request_id is not None
                                    and self._tm is not None else None))
        if req.sse_id is not None:
            # terminal stream frame on ANY outcome — the future is the
            # one place every finish/failure/cancel path converges
            fut.add_done_callback(
                lambda f, _req=req: self._emit_done(_req, f))
        if tenant is not None and self._eff is not None:
            # tenant accounting (goodput.py): resolve the label ONCE
            # under the cardinality guard; outcome/latency/tokens ride
            # the same every-outcome convergence point as the SSE frame
            req.tenant = self._eff.tenant_enter(tenant)
            if req.tenant is not None:
                fut.add_done_callback(
                    lambda f, _eff=self._eff, _t=req.tenant,
                    _t0=req.t_enqueue: _eff.tenant_done(_t, f, _t0))
        # padded-element cost for the regulator's cost-aware shed: a
        # decode request prices as its bucketed prompt plus the
        # positions its generation budget can occupy.  Under
        # speculative decode every generated token costs up to k+1
        # TARGET positions (the verify window scores the whole draft
        # whatever gets accepted), so the width multiplies the
        # generation half — the regulator's cost ordering and the
        # admission-time padded-element accounting stay honest.
        req.cost = int(_next_pow2(len(prompt))
                       + max_new_tokens * (self._spec_k + 1))
        # a deadline hit — queued or mid-generation — COMPLETES the
        # request with whatever was generated (admission._deliver
        # routes DeadlineExceededError through this instead of failing)
        req.on_expire = lambda exc, r=req: DecodeResult(
            r.tokens, "deadline", n_steps=r.n_steps,
            prompt_len=len(r.prompt))
        try:
            self._adm.admit(req)
        except Exception as e:
            if trace is not None:
                trace.abort(type(e).__name__)
            raise
        return fut

    def generate(self, prompt, max_new_tokens=None, deadline_ms=None,
                 timeout=None):
        """Synchronous convenience wrapper around :meth:`submit`."""
        return self.submit(prompt, max_new_tokens=max_new_tokens,
                           deadline_ms=deadline_ms).result(timeout=timeout)

    # ------------------------------------------------------------- worker
    def _occupied_count(self):
        return sum(r.occupied_count() for r in self._replicas)

    def _heartbeat(self):
        """Watchdog probe: progress age of the scheduler loop, busy
        when any slot is generating or work is queued.  A step program
        wedged in dispatch (donated-buffer failure modes, a hung
        backend) shows up as busy + growing age — named by this
        heartbeat, not inferred from throughput silence.  Multi-replica
        engines report the STALEST busy replica (one wedged pool must
        trip the watchdog even while its siblings keep generating)
        plus a per-replica breakdown the flight bundle captures."""
        now = time.monotonic()
        queued = len(self._adm)
        occupied = self._occupied_count()
        out = {"age_s": now - self._hb_t,
               "busy": bool(self._hb_busy or queued or occupied),
               "in_step": bool(self._hb_busy),
               "queued": queued, "slots_occupied": occupied,
               "kind": "decode",
               "engine": (self._tm.engine_label
                          if self._tm is not None else None)}
        if self._multi:
            ages = [now - self._hb_t] if (self._hb_busy or queued) else []
            reps = []
            for r in self._replicas:
                age = now - r.hb_t
                if r.healthy and (r.occupied_count() or r.pending):
                    ages.append(age)
                reps.append({"replica": r.label, "healthy": r.healthy,
                             "slots_occupied": r.occupied_count(),
                             "pending": len(r.pending),
                             "age_s": round(age, 3)})
            out["replicas"] = reps
            out["busy"] = bool(ages)
            out["age_s"] = max(ages) if ages else now - self._hb_t
            out["in_step"] = any(r.in_step for r in self._replicas)
        return out

    def _run(self):
        if self._multi:
            self._router_run()
        else:
            self._single_run(self._replicas[0])

    def _single_run(self, rep):
        """The single-replica fast path: one thread admits, seats, and
        steps the one slot pool — exactly the pre-replica engine."""
        while True:
            self._hb_t = rep.hb_t = time.monotonic()
            self._hb_busy = False
            try:
                if self._abort:
                    self._settle(rep)
                    for i in rep.occupied():
                        self._finish_slot(rep, i, "closed")
                    return
                occ = rep.occupied()
                free = self.num_slots - len(occ)
                if not occ and rep.flight is None:
                    # nothing decodes: nothing is held
                    rep.joins.idle()
                    batch = self._adm.take(free, 0.0)
                    if batch is None:
                        return          # closed and drained
                    self._join_many(rep, batch)
                    continue
                # busy: never block a step.  Of the requests a free
                # slot waits for, seat as many as a prefill dispatch is
                # worth stopping the decoding slots for now
                # (``_seats_now``); the others stay in the admission
                # queue, whose deadlines are kept honest either way —
                # expiry must not wait for a drain, or for a batch
                waiting, head = self._adm.head() if free else (0, None)
                n = self._seats_now(rep, min(free, waiting), head)
                if n:
                    polled = self._adm.poll(n)
                    if polled:
                        self._join_many(rep, polled)
                elif waiting or not free:
                    self._adm.sweep()
                self._hb_busy = True    # a wedged step must read busy
                self._step_once(rep)
            except Exception as e:      # fail the batch, keep serving
                for i in rep.occupied():
                    req = rep.slots[i]
                    rep.slots[i] = None
                    rep.valid_np[i] = 0.0
                    if not req.future.done():
                        _fail_future(req.future, e)
                    if req.trace is not None:
                        req.trace.abort(type(e).__name__)
                # a failed step dispatch may have consumed the DONATED
                # state buffers (non-CPU backends): rep.states would
                # point at deleted arrays and wedge every later step —
                # the pool is empty now, so fresh zeros lose nothing.
                # The step in flight goes unread: its requests failed
                rep.flight = None
                rep.joins.idle()
                rep.states = rep.program.init_states()
                rep.tokens_np.fill(0.0)
                rep.pos_np.fill(0.0)
                rep.reset_np.fill(0.0)
                rep.spec_np.fill(0.0)

    # ------------------------------------------------------------- router
    def _router_run(self):
        """Multi-replica scheduler front end: takes admitted requests
        and routes each to the healthy replica with the most free
        slots, where it PINS (per-slot state is device-resident).  The
        router never promises more than the fleet's free capacity, so
        backlog waits in admission where deadlines sweep and
        backpressure applies."""
        while True:
            self._hb_t = time.monotonic()
            self._hb_busy = False
            try:
                if self._abort:
                    with self._dr_cond:
                        self._dr_cond.notify_all()
                    return
                with self._dr_lock:
                    live = [r for r in self._replicas if r.healthy]
                    free_total = sum(max(0, r.assignable())
                                     for r in live)
                if not live:
                    # dead fleet: fail incoming work fast instead of
                    # wedging the queue (the flight recorder already
                    # dumped on each replica's retirement)
                    batch = self._adm.take(self.num_slots, 0.0)
                    if batch is None:
                        return
                    err = MXNetError(
                        "all %d decode replicas are unhealthy (step "
                        "failures drained them); build a new engine"
                        % len(self._replicas))
                    for req in batch:
                        _fail_future(req.future, err)
                        if req.trace is not None:
                            req.trace.abort("MXNetError")
                    continue
                if free_total <= 0:
                    # pool full: keep queued deadlines honest while
                    # waiting for a leave to free capacity
                    self._adm.sweep()
                    if self._adm.closed and not len(self._adm):
                        return
                    self._slot_free.wait(0.05)
                    self._slot_free.clear()
                    continue
                batch = self._adm.take(free_total, 0.0)
                if batch is None:
                    return              # closed and drained
                self._hb_busy = True
                for req in batch:
                    # per-request isolation: a failing assign (or its
                    # telemetry) must fail THAT request's future, not
                    # silently drop the rest of the popped batch
                    try:
                        self._assign(req)
                    except Exception as e:
                        if not req.future.done():
                            _fail_future(req.future, e)
                            if req.trace is not None:
                                req.trace.abort(type(e).__name__)
            except Exception:           # defense: never lose the router
                continue

    def _assign(self, req):
        """Route one admitted request to the freest healthy replica.
        The append happens under the same lock the replica threads'
        exit checks hold, and only onto an ``accepting`` replica — a
        request must never land on a queue no thread will drain."""
        with self._dr_lock:
            live = [r for r in self._replicas
                    if r.healthy and r.accepting]
            if live:
                r = max(live, key=lambda x: (x.assignable(), -x.index))
                r.pending.append(req)
                self._dr_cond.notify_all()
                return
            unhealthy = any(not r.healthy for r in self._replicas)
        err = (MXNetError("all %d decode replicas are unhealthy"
                          % len(self._replicas)) if unhealthy
               else EngineClosedError("engine closed before seating"))
        _fail_future(req.future, err)
        if req.trace is not None:
            req.trace.abort(type(err).__name__)

    def _decode_replica_run(self, rep):
        """One replica's scheduler loop: seat routed requests, step the
        pool, deliver leaves.  A step dispatch that raises retires the
        replica — seated requests are evicted with their PARTIAL output
        (finish_reason "error"), routed-but-unseated ones re-route, and
        co-resident replicas keep generating untouched."""
        while True:
            rep.hb_t = time.monotonic()
            if self._abort:
                with self._dr_lock:
                    rep.accepting = False
                    pend = list(rep.pending)
                    rep.pending.clear()
                e = EngineClosedError("engine closed before seating")
                for req in pend:
                    if not req.future.done():
                        _fail_future(req.future, e)
                        if req.trace is not None:
                            req.trace.abort(type(e).__name__)
                self._settle(rep)
                for i in rep.occupied():
                    self._finish_slot(rep, i, "closed")
                return
            self._sweep_pending(rep, time.monotonic())
            seats = []
            stolen = 0
            with self._dr_lock:
                n_free = rep.free_slots()
                # the same decision as the single pool's, over the
                # requests routed here: one it leaves stays in
                # ``rep.pending``, swept above and counted against the
                # router's promise (``assignable``)
                w = min(n_free, len(rep.pending))
                n = self._seats_now(rep, w,
                                    rep.pending[0] if w else None)
                while len(seats) < n:
                    seats.append(rep.pending.popleft())
                if n == w and len(seats) < n_free and rep.healthy:
                    # cross-replica work stealing (ROADMAP a3): a
                    # request routed to a sibling whose pool is FULL
                    # would otherwise wait a whole generation for its
                    # pinned replica — re-offer it here instead (it
                    # has not seated, so no device state moves).  The
                    # window exists after a failure re-route overflows
                    # a sibling, or when a pool saturates between the
                    # router's capacity check and the seat.
                    for sib in self._replicas:
                        if sib is rep or len(seats) >= n_free:
                            continue
                        while sib.pending and sib.free_slots() == 0 \
                                and len(seats) < n_free:
                            seats.append(sib.pending.popleft())
                            stolen += 1
            if stolen:
                with self._lock:
                    self._steals += stolen
                if self._tm is not None:
                    self._tm.steals.inc(stolen)
                if self._tl is not None:
                    self._tl.instant("decode.steal", "decode",
                                     "decode:%s" % rep.label,
                                     args={"stolen": stolen})
            live = []
            for req in seats:
                # honor deadlines that expired in the routed-but-
                # unseated window exactly like the admission sweep
                # (AdmissionController.expire_request): the request
                # completes with its (empty) partial output
                if req.expired():
                    self._adm.expire_request(req,
                                             "expired before seating")
                else:
                    live.append(req)
            if live:
                self._join_many(rep, live)
            if not rep.occupied_count() and rep.flight is None:
                with self._dr_cond:
                    if rep.pending:
                        continue
                    if self._dr_stop or not rep.healthy:
                        # refuse further routing ATOMICALLY with the
                        # exit decision — the router must never hand
                        # a request to a dead scheduler thread
                        rep.accepting = False
                        return
                    self._dr_cond.wait(0.05)
                continue
            rep.in_step = True
            try:
                self._step_once(rep)
            except Exception as e:
                rep.in_step = False
                self._decode_replica_failed(rep, e)
                return
            rep.in_step = False
            rep.hb_t = time.monotonic()
            if rep.free_slots():
                self._slot_free.set()

    def _sweep_pending(self, rep, now):
        """Per-iteration deadline sweep over this replica's routed-but-
        unseated queue — the one waiting room the admission sweep can
        no longer see.  Matters after a sibling replica's failure
        re-routes more requests than this replica has free slots: the
        overflow must not wait a whole generation to expire."""
        if not rep.pending:
            return
        expired = []
        with self._dr_lock:
            if any(r.deadline is not None and now >= r.deadline
                   for r in rep.pending):
                keep = collections.deque()
                for r in rep.pending:
                    if r.deadline is not None and now >= r.deadline:
                        expired.append(r)
                    else:
                        keep.append(r)
                rep.pending = keep
        for r in expired:
            self._adm.expire_request(r, "expired before seating")

    def _decode_replica_failed(self, rep, exc):
        """Retire one replica after a failed step dispatch: seated
        requests are evicted with their PARTIAL tokens (finish_reason
        "error" — the donated state buffers may be consumed, so the
        pool cannot step again), routed requests re-route, and the
        flight recorder dumps while the evidence is fresh."""
        with self._dr_lock:
            rep.healthy = False
            rep.accepting = False
            orphans = list(rep.pending)
            rep.pending.clear()
            stopping = self._dr_stop
            self._dr_cond.notify_all()
        warnings.warn(
            "decode replica %d (%s) retired after a step failure (%r): "
            "%d seated request(s) evicted with partial output, traffic "
            "re-routed to %d sibling(s)"
            % (rep.index, rep.ctx if rep.ctx is not None else "cpu(0)",
               exc, rep.occupied_count(),
               sum(1 for x in self._replicas if x.healthy)))
        self._settle(rep)
        for i in rep.occupied():
            self._finish_slot(rep, i, "error")
        if rep.tm_failures is not None:
            rep.tm_failures.inc()
        if self._tl is not None:
            self._tl.instant("decode.replica_failed", "decode",
                             "decode:%s" % rep.label,
                             args={"error": repr(exc)})
        fr = _telemetry.recorder.flight_recorder()
        if fr is not None:
            fr.dump("replica_failed:%s:%s"
                    % (self._obs_name or "decode", rep.label),
                    detail={"replica": rep.describe(),
                            "error": repr(exc)})
        for req in orphans:
            if stopping:
                # sibling scheduler threads may already have drained
                # and exited — a re-assigned request would never seat
                # and its future would hang forever; fail it instead
                if not req.future.done():
                    _fail_future(req.future, exc)
                    if req.trace is not None:
                        req.trace.abort(type(exc).__name__)
            else:
                self._assign(req)
        self._slot_free.set()

    def rehabilitate(self, replicas=None):
        """Replica probation/re-warm (ROADMAP follow-up a2): rebuild
        every retired replica's programs from scratch (its donated
        state buffers may be consumed), re-warm them — drawn from the
        persistent AOT cache when one is configured, so re-entry
        compiles nothing — and admit the replica back only after ONE
        probe step matches a healthy sibling's output bitwise
        (``StepProgram.probe_step``: fixed key, fixed tick, zero
        scratch state — deterministic for stochastic samplers too).
        A replica that fails any stage stays retired.

        ``replicas`` restricts probation to those replica indices
        (the supervisor's one-due-replica-at-a-time calls; None =
        every unhealthy replica).

        Returns one outcome dict per attempted replica:
        ``{"replica", "ok", "reason"}``.
        """
        if self._adm.closed:
            raise EngineClosedError("decode engine is closed")
        want = None if replicas is None else {int(i) for i in replicas}
        return [self._rehabilitate_one(r) for r in self._replicas
                if not r.healthy and (want is None or r.index in want)]

    def _rehabilitate_one(self, rep):
        out = {"replica": rep.label, "ok": False, "reason": None}
        with self._dr_lock:
            sib = next((x for x in self._replicas
                        if x.healthy and x is not rep), None)
        if sib is None:
            out["reason"] = ("no healthy sibling to probe against; "
                             "build a new engine")
            return out
        try:
            fresh = self._new_replica(rep.index, rep.ctx, rep.plan)
            # probation warmup: exactly engine.warmup's per-replica
            # sequence (step twice for committed-sharding parity,
            # row-write kernels, prefill buckets) — with an AOT cache
            # every one of these loads instead of tracing
            self._warm_replica(fresh)
            # the probation gate: one probe step, bitwise against the
            # live sibling's program, before any traffic
            want = sib.program.probe_step()
            got = fresh.program.probe_step()
            if not (len(want) == len(got)
                    and all(np.array_equal(a, b, equal_nan=True)
                            for a, b in zip(want, got))):
                out["reason"] = ("probe step diverged bitwise from "
                                 "healthy replica %s" % sib.label)
                return out
        except Exception as e:
            out["reason"] = repr(e)
            return out
        with self._dr_lock:
            rep.program = fresh.program
            rep.prefill_caches = fresh.prefill_caches
            rep.prefill_buckets = fresh.prefill_buckets
            rep.slots = list(fresh.slots)
            rep.tokens_np = fresh.tokens_np
            rep.pos_np = fresh.pos_np
            rep.valid_np = fresh.valid_np
            rep.reset_np = fresh.reset_np
            rep.spec_np = fresh.spec_np
            rep.states = fresh.states
            rep.flight = None
            rep.joins = fresh.joins
            rep.pending.clear()
            rep.in_step = False
            rep.healthy = True
            rep.accepting = True
            rep.thread = None
            rep.probations += 1
            rep.hb_t = time.monotonic()
            self._dr_cond.notify_all()
        self._ensure_replica_threads()
        self._slot_free.set()
        warnings.warn(
            "decode replica %d (%s) rehabilitated after probation: "
            "probe step bitwise-equal to replica %s"
            % (rep.index, rep.ctx if rep.ctx is not None else "cpu(0)",
               sib.label))
        out["ok"] = True
        return out

    def _join(self, rep, req):
        """Seat one admitted request BETWEEN steps (single-request
        compatibility wrapper over :meth:`_join_many`)."""
        self._join_many(rep, [req])

    def _join_many(self, rep, reqs):
        """Seat a batch of admitted requests in free slots BETWEEN
        steps: zero (or prefill-fill) each slot's state rows, stage
        first tokens, mark slots valid.  No shape changes anywhere —
        the next step dispatch reuses the same compiled program.

        With a prefill graph and ``MXNET_DECODE_COALESCE_PREFILL``
        (default on), the joiners it is handed COALESCE: one dispatch
        per pow2 (batch, prompt) bucket instead of batch 1 per joiner
        (ROADMAP 4b; the ``decode_bench --prefill`` sweep measures the
        win).  How many it is handed at a time is the scheduler's
        decision (``_seats_now``): everything seatable while nothing
        decodes, and otherwise as many as a dispatch is worth stopping
        the decoding slots for.  Serial mode (knob off) dispatches per
        request, byte-for-byte the pre-coalescing engine."""
        # the slots a prefill dispatch of this join stops
        rep.joins.decoding = int(rep.valid_np.sum())
        seated = [req for req in reqs if self._seat_slot(rep, req)]
        if not seated:
            return
        stepped = seated
        if rep.prefill_caches:
            # serial mode is the degenerate grouping — one singleton
            # group per joiner dispatches the identical (1, bucket)
            # program the pre-coalescing engine did, through the SAME
            # code path (no serial/coalesced divergence to maintain).
            # A group never outgrows its bucket's largest warm batch
            # (the token budget a dispatch); a prompt past the largest
            # bucket is fed through the step like any token
            groups, stepped = [], []    # [(bucket, [reqs])], seat order
            for req in seated:
                b = next((bk for bk in rep.prefill_buckets
                          if bk >= len(req.prompt)), None)
                if b is None:
                    stepped.append(req)
                    continue
                g = next((g for g in groups if g[0] == b
                          and len(g[1]) < self._prefill_grid[b][-1]),
                         None) if self._coalesce else None
                if g is None:
                    groups.append((b, [req]))
                else:
                    g[1].append(req)
            for b, grp in groups:
                self._prefill_group(rep, b, grp)
        for req in stepped:
            # the previous occupant's state rows are cleared IN
            # the next step dispatch (StepProgram reset mask) — a
            # join costs zero device traffic of its own
            slot = req.slot
            rep.reset_np[slot] = 1.0
            rep.tokens_np[slot] = req.prompt[0]
            rep.pos_np[slot] = 0.0
            req.prompt_i = 1
            # spec eligibility starts with the FIRST sampling step
            # — the one that consumes the last prompt token
            rep.spec_np[slot] = (1.0 if req.prompt_i
                                 >= len(req.prompt) else 0.0)
        for req in seated:
            if req.slot is not None and rep.slots[req.slot] is req:
                for slot, reason in self._ended(rep, req.slot):
                    self._finish_slot(rep, slot, reason)

    def _seat_slot(self, rep, req):
        """Claim a free slot for one admitted request; False when the
        request was cancelled before seating (counted as a leave so the
        scraped series and stats() carry the same numbers)."""
        if not req.future.set_running_or_notify_cancel():
            if req.trace is not None:
                req.trace.abort("cancelled")
            with self._lock:
                self._leaves += 1
            if self._tm is not None:
                self._tm.leave("cancelled")
            return False
        slot = rep.slots.index(None)
        req.slot = slot
        req.t_join = time.perf_counter()
        rep.slots[slot] = req
        rep.valid_np[slot] = 1.0
        rep.spec_np[slot] = 0.0
        with self._lock:
            self._joins += 1
        if self._tm is not None:
            self._tm.joins.inc()
        if self._tl is not None:
            self._tl.instant("decode.join", "decode",
                             "decode:%s" % rep.label,
                             args={"slot": slot,
                                   "request": req.sse_id,
                                   "prompt_len": len(req.prompt)})
        return True

    def _fail_seated(self, rep, req, exc):
        """Fail ONE seated request and free its slot — the per-request
        isolation every prefill/callback failure path rides: co-
        resident mid-generation requests share no state with it and
        keep their partial generations."""
        slot = req.slot
        if slot is not None and rep.slots[slot] is req:
            rep.slots[slot] = None
            rep.valid_np[slot] = 0.0
            rep.spec_np[slot] = 0.0
        with self._lock:
            self._leaves += 1
        if self._tm is not None:
            self._tm.leave("error")
        if req.tenant is not None and req.uflops \
                and self._eff is not None:
            self._eff.tenant_useful(req.tenant, req.uflops)
            req.uflops = 0
        _fail_future(req.future, exc)
        if req.trace is not None:
            req.trace.abort(type(exc).__name__)

    def _prefill_group(self, rep, bucket, group):
        """The coalesced path: every joiner whose prompt pads into
        ``bucket`` rides ONE dispatch at the next pow2 batch extent
        (dead rows padded with zero prompts and length 0 — exactly the
        all-pad rows warmup feeds), output state rows scattered into
        each request's slot.  A failed dispatch fails the GROUP's
        requests (they share that one program invocation) and nothing
        else; the chaos seam still trips per request so a fault plan
        targeting one joiner fails exactly one."""
        live = []
        for req in group:
            if _faults.ACTIVE:
                try:
                    _faults.trip("decode.prefill", replica=rep.label)
                except Exception as e:
                    self._fail_seated(rep, req, e)
                    continue
            live.append(req)
        if not live:
            return
        bb = next(b for b in self._prefill_grid.get(
            bucket, self._prefill_batches) if b >= len(live))
        lane = "decode:%s" % rep.label
        t_pf0 = time.perf_counter()
        ann = (self._tl.annotate("decode.prefill") if self._tl is not None
               else _telemetry.timeline.NO_SPAN)

        def part(name):
            return _telemetry.timeline.part(name, "decode", lane, self._tl)
        try:
            with ann:
                with part("decode.prefill.pad"):
                    arr = np.zeros((bb, bucket), np.float32)
                    lens = np.zeros((bb,), np.float32)
                    for r_i, req in enumerate(live):
                        plen = len(req.prompt)
                        arr[r_i, :plen] = req.prompt
                        lens[r_i] = plen
                    fused, attn_nodes = self._fused_attention(rep, bucket,
                                                              bb)
                    # the states this dispatch's commit lays, of either
                    # kind
                    n_caches = len(rep.program.layout.caches("target"))
                    n_rows = len(rep.program.layout.target) - n_caches
                t_disp = time.perf_counter()
                with part("decode.prefill.dispatch"):
                    outs = rep.prefill_caches[bucket].dispatch({
                        self._prefill_data_name: arr,
                        self._prefill_len_name: lens})
                with self._lock:
                    self._prefill_dispatches += 1
                    self._prefill_fused_attention += fused
                    self._prefill_attention_nodes += attn_nodes
                on_device = self._rows_stay_on_device(rep)
                if on_device:
                    # dead rows of the padded batch take row 0's slot
                    # and length, and are overwritten by it
                    slots = [live[0].slot] * bb
                    plens = [len(live[0].prompt)] * bb
                    for r_i, req in enumerate(live):
                        slots[r_i], plens[r_i] = req.slot, len(req.prompt)
                    with part("decode.prefill.commit"):
                        rep.states = rep.program.commit_prefill(
                            rep.states, outs[1:], slots, plens)
                with part("decode.prefill.read"):
                    if self._sampler.greedy:
                        first = np.asarray(outs[0])
                    else:
                        first = rep.program.sample_tokens(outs[0])
                    rows_all = None if on_device \
                        else [np.asarray(o) for o in outs[1:]]
                t_pf1 = time.perf_counter()
        except Exception as e:
            for req in live:
                self._fail_seated(rep, req, e)
            return
        # the first ids are read, so the device has run the dispatch and
        # its commit: less what the step in flight still had to run
        # when it went out, that is what it cost the decoding slots
        self._prefill_observed(
            bucket, bb, t_pf1 - t_disp - rep.joins.in_flight_left(t_disp))
        rep.joins.stalled = True
        # element split + FLOPs ledger for this one dispatch: the
        # program computed bb*bucket positions; Σ prompt lengths of
        # them carried real tokens, the rest were batch-row padding
        # and sequence overhang
        live_elems = int(sum(len(r.prompt) for r in live))
        padded_elems = bb * bucket
        if self._tm is not None:
            self._tm.prefill_elems(bucket, live_elems,
                                   padded_elems - live_elems)
        if self._tl is not None:
            self._tl.complete("decode.prefill", "decode", lane, t_pf0,
                              time.perf_counter(),
                              args={"bucket": bucket, "group": len(live),
                                    "live": rep.joins.decoding,
                                    "tokens": live_elems,
                                    "padded": padded_elems,
                                    "fused_attention": fused,
                                    "attention_nodes": attn_nodes,
                                    "row_states": n_rows,
                                    "cache_states": n_caches})
        if self._eff is not None:
            shape_key = tuple(sorted(
                (k, v.shape)
                for k, v in ((self._prefill_data_name, arr),
                             (self._prefill_len_name, lens))))
            useful = self._eff.record_batch(
                rep.label, rep.prefill_caches[bucket].flops_for(
                    shape_key), live_elems, padded_elems)
            if useful:
                for req in live:
                    if req.tenant is not None:
                        req.uflops += (useful * len(req.prompt)
                                       // live_elems)
        for r_i, req in enumerate(live):
            rows = None if rows_all is None else {
                name: rows_all[i][r_i]
                for i, name in enumerate(rep.program.state_names)}
            self._commit_prefill(rep, req, rows, first[r_i])

    def _prefill_observed(self, bucket, batch, seconds):
        """Fold what one warm ``(batch, bucket)`` prefill dispatch cost
        the device into the table ``_seats_now`` decides on: the median
        of its last five readings.  (A mean keeps a share of a reading
        that held a pause of the host for as long as the policy then
        avoids that batch, and so never corrects it.)"""
        with self._lock:
            last = self._prefill_readings.setdefault(
                (bucket, batch), collections.deque(maxlen=5))
            last.append(max(seconds, 0.0))
            # a new dict a reading: a scheduler thread reads its
            # bucket's without the lock
            costs = dict(self._prefill_cost.get(bucket, ()))
            costs[batch] = sorted(last)[len(last) // 2]
            self._prefill_cost[bucket] = costs

    def _seats_now(self, rep, w, head):
        """How many of the ``w`` requests a free slot of ``rep`` waits
        for are seated in this iteration (``join_policy.seats_now``),
        from what the engine has observed: the slots decoding now, the
        step's time, the rate at which requests become seatable, and
        what a prefill dispatch of the oldest one's bucket costs by its
        batch.  ``head`` is that oldest request.  A join that rides the
        step (no prefill program for its bucket) costs no dispatch and
        is never held."""
        st = rep.joins
        st.seatable(w)
        n = 0
        if w:
            bucket = next((b for b in rep.prefill_buckets
                           if b >= len(head.prompt)), None) \
                if rep.prefill_caches else None
            costs = None if bucket is None else _join_policy.cost_table(
                self._prefill_cost.get(bucket),
                self._prefill_grid.get(bucket, self._prefill_batches))
            n = _join_policy.seats_now(
                w, int(rep.valid_np.sum()), st.step_s, st.rate, costs,
                st.held_steps)
        st.seated(n, w)
        return n

    def _step_expert_work(self, step_sym, arg_params, token_name,
                          pos_name, valid_name):
        """``(products, top_k)`` of the step graph's ``_moe_experts``
        nodes: the (row, expert) products they multiply a step by the
        op's own rule for the shapes built (``ops/transformer.py``
        ``moe_products``: each held expert's pairs padded to whole
        tiles on the grouped kernel, every held expert over every slot
        on the plain path), and the experts a row is routed to, both
        summed over the nodes.  None where the step has none."""
        from ..analysis.shapes import node_inputs
        from ..ops.transformer import moe_products
        try:
            grid = self._layout.grid(step_sym, token_name, pos_name,
                                     valid_name, "target")
            dtypes = dict(grid.dtypes)
            dtypes.update((n, np.dtype(a.dtype))
                          for n, a in (arg_params or {}).items())
            nodes = node_inputs(step_sym, "_moe_experts", grid.shapes,
                                dtypes)
        except Exception:           # a count must never fail an engine
            nodes = []
        if not nodes:
            return None
        return (sum(moe_products(a, s, d) for a, s, d in nodes),
                sum(a["top_k"] for a, _s, _d in nodes))

    def _fused_attention(self, rep, bucket, bb):
        """How many ``_gqa_prefill`` nodes of the ``(bb, bucket)``
        prefill program take the fused kernel, and how many it has:
        the op's own predicate (``ops/transformer.py``
        ``prefill_takes_kernel``) over what each node observes of its
        inputs, asked once a shape (at ``warmup()`` for the warm grid,
        so a dispatch only looks it up)."""
        n = self._prefill_fused.get((bucket, bb))
        if n is None:
            from ..ops.transformer import prefill_takes_kernel
            try:
                nodes = rep.prefill_caches[bucket].node_inputs(
                    "_gqa_prefill",
                    {self._prefill_data_name: (bb, bucket),
                     self._prefill_len_name: (bb,)})
            except Exception:       # a count must never fail a dispatch
                nodes = []
            n = (sum(1 for node in nodes if prefill_takes_kernel(*node)),
                 len(nodes))
            self._prefill_fused[(bucket, bb)] = n
        return n

    def _rows_stay_on_device(self, rep):
        """Whether a prefill's state rows are laid into the pool by
        ``StepProgram.commit_prefill``: on the device, one dispatch a
        group, nothing read back.  Two pools keep the row write a
        request and a state, each for a reason: a sharded one, because
        the commit program's outputs would take the shardings the
        compiler infers and the next step would meet a pool laid out
        otherwise than it was warmed on (the row kernels are resolved a
        sharding); a speculative one, because its join also writes the
        draft's rows, which the prefill graph does not produce."""
        return rep.plan is None and not self._spec_k

    def _commit_prefill(self, rep, req, rows, first):
        """Deliver one request's first generated token, after its
        prefill rows went into its slot: here, by a row write a state
        (one traced-index kernel per state shape — never a new
        compile), unless the group's rows were laid in on the device
        already (``rows`` None)."""
        slot = req.slot
        if rows is not None:
            rep.states = rep.program.write_row(rep.states, slot, rows)
        if self._spec_k:
            # the prefill graph produced TARGET rows only; the draft
            # never saw this prompt, and the previous occupant's draft
            # rows must not leak into its proposals — start it cold.
            # (Draft quality only moves the accept RATE; acceptance
            # keeps the emitted stream exact regardless.)
            rep.states = rep.program.zero_row(rep.states, slot,
                                              which="draft")
            rep.spec_np[slot] = 1.0
        rep.reset_np[slot] = 0.0        # prefill rows are live data
        req.prompt_i = len(req.prompt)
        req.tokens.append(int(first))
        req.t_last_tok = self._first_token(req, time.perf_counter())
        rep.tokens_np[slot] = first
        rep.pos_np[slot] = float(len(req.prompt))
        with self._lock:
            self._tokens_out += 1
        if self._tm is not None:
            self._tm.tokens.inc()
        self._emit_token(req, first)
        if req.on_token is not None:
            self._fire_on_token(rep, req, int(first))

    def _first_token(self, req, t_tok):
        """A request's first generated token, once: the TTFT sample,
        and the timeline's split of it into the wait for a slot
        (submit to seat) and the prompt's feeding (seat to first
        token), on the one ``perf_counter`` clock.  The instant is
        back-dated through its ``enqueued`` argument, so it goes to
        the ring only.  Returns ``t_tok``."""
        req.t_first_tok = t_tok
        if self._tm is not None:
            self._tm.ttft.observe(t_tok - req.t_submit)
        if self._tl is not None:
            self._tl.instant(
                "decode.first_token", "decode", "decode.tokens",
                args={"enqueued": req.t_submit,
                      "queue_wait_ms": (req.t_join - req.t_submit) * 1e3,
                      "prompt_feed_ms": (t_tok - req.t_join) * 1e3,
                      "prompt_len": len(req.prompt),
                      "request": req.sse_id})
        return t_tok

    def _emit_token(self, req, tok):
        """Publish one generated token onto the /events EventHub as a
        ``decode.token`` event keyed by the request's client-supplied
        id — the SSE half of per-token streaming (ROADMAP 4a residual).
        Requests without a ``request_id`` pay a single attribute check."""
        if req.sse_id is None:
            return
        if self._tl is not None:
            # streaming requests already pay an SSE publish per token;
            # the ring append is cheaper and gives request_autopsy the
            # exact per-token gaps instead of step-derived estimates
            self._tl.instant("decode.token", "decode", "decode.tokens",
                             args={"request": req.sse_id,
                                   "index": len(req.tokens) - 1})
        try:
            _telemetry.server.publish_event(
                "decode.token",
                {"request_id": req.sse_id,
                 "engine": (self._tm.engine_label
                            if self._tm is not None else None),
                 "index": len(req.tokens) - 1, "token": int(tok)})
        except Exception:
            pass    # the stream is observability: never fail a request

    def _emit_done(self, req, fut):
        """Terminal SSE frame, fired from the request future's done
        callback — hooking the future (not the individual finish
        paths) means EVERY terminal outcome publishes exactly one
        ``{"done": true}`` frame: normal finishes, deadline partials,
        replica failures, a raising on_token callback, engine close,
        and client-side cancellation alike.  An SSE consumer can
        therefore treat stream silence as in-flight, never as an
        ambiguous death."""
        if fut.cancelled():
            reason = "cancelled"
        elif fut.exception() is not None:
            reason = "error"
        else:
            reason = getattr(fut.result(), "finish_reason", "eos")
        try:
            _telemetry.server.publish_event(
                "decode.token",
                {"request_id": req.sse_id,
                 "engine": (self._tm.engine_label
                            if self._tm is not None else None),
                 "done": True, "finish_reason": reason,
                 "tokens": len(req.tokens)})
        except Exception:
            pass

    def _fire_on_token(self, rep, req, tok):
        """Streaming hook: a raising callback evicts ONLY its own
        request (future fails with the exception, slot frees, co-
        residents untouched).  Returns False when the request was
        evicted."""
        try:
            req.on_token(int(tok))
            return True
        except Exception as e:
            self._fail_seated(rep, req, e)
            return False

    def _step_once(self, rep):
        tl = self._tl
        if tl is None:
            # plane off: no span object, no annotation, no append
            self._step_body(rep, _telemetry.timeline.NO_SPAN,
                            time.perf_counter())
            return
        with _telemetry.timeline.span(
                "decode.step", "decode", "decode:%s" % rep.label,
                tl=tl) as sp:
            sp.args = self._step_body(rep, sp, sp.t0)
            if sp.args is None:
                sp.drop()       # no step read: its event comes with it

    def _booked(self, rep, live, new_tokens, t0, ahead=0, discarded=0,
                leaving=()):
        """Book one scheduler iteration begun at ``t0`` (``stats()``
        and the scraped series): the step it dispatched, over ``live``
        slots (none: it only read the step in flight), and what the
        step it read delivered.  Only then do the requests that ended
        with that step leave (``leaving``: ``(slot, reason)``): a
        caller woken by its last token may read ``stats()`` straight
        away, and finds the token counted."""
        dt_ms = (time.perf_counter() - t0) * 1e3
        with self._lock:
            self._tokens_out += new_tokens
            self._discarded += discarded
            if live:
                self._steps += 1
                self._steps_ahead += ahead
                self._slot_steps_held += rep.joins.held
                self._step_ms.append(dt_ms)
        if self._tm is not None:
            if new_tokens:
                self._tm.tokens.inc(new_tokens)
            if live:
                self._tm.steps.inc()
                rep.tm_step_ms.observe(dt_ms)
                # slot-occupancy split of this dispatch (ISSUE 18
                # satellite): the persistent step computed num_slots
                # rows whatever the occupancy — scraped, not inferred
                self._tm.slot_steps_live.inc(live)
                dead = self.num_slots - live
                if dead:
                    self._tm.slot_steps_dead.inc(dead)
        for slot, reason in leaving:
            self._finish_slot(rep, slot, reason)

    def _step_args(self, held, live, tokens, dispatch_s, read_s, ahead=0,
                   discarded=0, extras=()):
        """The arguments of one step's ``decode.step`` event (nothing
        with the plane off): the step's counters go in as
        ``<name>_max`` / ``<name>_mean``.  ``held`` alone is the writing
        iteration's and not the read step's: the requests a free slot
        waited for that its decision left in their queue."""
        if self._tl is None:
            return None
        args = {"live": live, "tokens": tokens,
                "dispatch_ms": dispatch_s * 1e3, "read_ms": read_s * 1e3,
                "ahead": ahead, "discarded": discarded, "held": held}
        for name, arr in dict(extras).items():
            args[name + "_max"] = float(arr.max())
            args[name + "_mean"] = float(arr.mean())
        if self._expert_work is not None:
            # what the expert layers multiplied (every slot rides) and
            # what the live rows were routed to
            args["expert_products"] = self._expert_work[0]
            args["expert_routed"] = self._expert_work[1] * live
        return args

    def _step_body(self, rep, sp, t0):
        """One scheduler iteration, begun at ``t0``: deadline scan, the
        dispatch of the next step, the read and delivery of the step in
        flight, the booking.  Returns the arguments of the
        ``decode.step`` event of the step it read, all of them that
        step's own (one event a step, written by the iteration that
        reads it, whose interval also holds the dispatch of the step
        after), or None when it read none: an empty pool, or a step
        dispatched onto an idle one.  ``sp`` is the open ``decode.step``
        span (its inert stand-in with the plane off): the scan and the
        delivery are marked inside it in the profiler's trace
        (``mx:decode.step.scan`` / ``.deliver``); the step program
        marks its own dispatch and read.

        The plain loop keeps ONE step in flight.  Step N+1 goes out
        before step N's ids are read: a slot that generates takes its
        token from step N's output buffer on the device
        (``FROM_PREVIOUS``), a slot fed its prompt takes the host's,
        and every stepped slot's position is advanced here, at the
        dispatch.  The device runs N+1 while the host reads N, walks
        its slots, admits, and builds N+2.  A speculative step commits
        a count of positions that only its read tells, so it is read
        where it is dispatched."""
        now = time.monotonic()
        # per-iteration deadline check folded into ONE slot scan: an
        # expired slot-resident request completes with its partial
        # tokens and frees the slot for queued work — mid-generation
        # eviction, not failure.  A seated slot that is not valid has
        # its last token in the step in flight: it is not stepped
        # again, and not evicted either (its answer is whole, and is
        # delivered further down this iteration)
        with sp.child("decode.step.scan"):
            occ = []
            for i, req in enumerate(rep.slots):
                if req is None or not rep.valid_np[i]:
                    continue
                if req.deadline is not None and now >= req.deadline:
                    self._finish_slot(rep, i, "deadline")
                else:
                    occ.append(i)
        read = rep.flight
        if not occ and read is None:
            return None
        if occ and _faults.ACTIVE:
            # chaos seam: a raise retires this replica through the
            # real step-failure path (partial-output eviction +
            # re-route); a hang wedges the pool for the watchdog
            _faults.trip("decode.step", replica=rep.label)
        if self._spec_k:
            t_spec = time.perf_counter()
            toks_mat, counts, rep.states = rep.program.step_spec(
                rep.tokens_np, rep.pos_np, rep.valid_np, rep.spec_np,
                rep.states, reset=rep.reset_np)
            rep.joins.step_time(time.perf_counter() - t_spec)
            rep.reset_np.fill(0.0)
            if self._eff is not None:
                # FLOPs ledger, BEFORE the slot advance (a slot that
                # finishes this very step must still absorb its tenant
                # share): committed positions = Σ counts over occupied
                # slots (spec mask 0 rows commit exactly 1), the rest
                # of the K-position verify window was rejected drafts
                cl = counts.tolist()
                committed = int(sum(cl[i] for i in occ))
                self._ledger_step(
                    rep, occ,
                    self._eff.record_spec_step(
                        rep.label,
                        _goodput.price_step_program(rep.program),
                        len(occ), self.num_slots, committed,
                        self._spec_k + 1))
            with sp.child("decode.step.deliver"):
                new_tokens, leaving = self._advance_spec(
                    rep, occ, toks_mat, counts)
                self._booked(rep, len(occ), new_tokens, t0,
                             leaving=leaving)
            return self._step_args(rep.joins.held, len(occ), new_tokens,
                                   *(rep.program.last_split or (0, 0)))
        if occ:
            # a slot that holds ``FROM_PREVIOUS`` was stepped by the
            # step in flight: there is one whenever there is such a slot
            step, rep.states = rep.program.step(
                StepFeed(rep.tokens_np, read and read[0]), rep.pos_np,
                rep.valid_np, rep.states, reset=rep.reset_np)
            rep.reset_np.fill(0.0)      # consumed: rows are zeroed now
            if not isinstance(step, PendingStep):
                step = rep.program.pending(step)
            rep.flight = (step, self._seats_ahead(rep, occ),
                          int(read is not None))
            if self._eff is not None:
                self._ledger_step(
                    rep, occ,
                    self._eff.record_step(
                        rep.label,
                        _goodput.price_step_program(rep.program),
                        len(occ), self.num_slots))
        else:
            rep.flight = None
        if read is None:
            # dispatched onto an idle device: the step begins now
            rep.joins.t_flight = time.perf_counter()
            rep.joins.stalled = False
            self._booked(rep, len(occ), 0, t0)
            return None
        before, seats, ahead = read
        ids = before.read()
        rep.joins.step_read(time.perf_counter(), bool(occ))
        with sp.child("decode.step.deliver"):
            new_tokens, discarded, leaving = self._deliver(rep, seats, ids)
            self._booked(rep, len(occ), new_tokens, t0, int(bool(occ)),
                         discarded, leaving)
        return self._step_args(rep.joins.held, len(seats), new_tokens,
                               before.dispatch_s, before.read_s, ahead,
                               discarded, before.extras)

    def _seats_ahead(self, rep, occ):
        """The host's half of a dispatch, made before the step's ids
        exist: every stepped slot moves on one position and is given
        its next token, the host's while it is fed its prompt and the
        step's own (``FROM_PREVIOUS``) once it generates.  A request
        whose count of tokens or of positions is full with the token
        now in flight is not stepped again: its slot goes dead here and
        is freed when that token is delivered, so that a finish by
        length costs no slot-step.  Only a finish the host cannot
        foresee (the eos id, a deadline, a callback that raises) leaves
        one slot-step in flight, whose id is thrown away.  Returns who
        sat where: ``(slot, request, kind)`` a stepped slot, kind 0 fed
        its prompt, 1 generating, 2 generating its last token."""
        seats = []
        for i in occ:
            req = rep.slots[i]
            rep.pos_np[i] += 1.0
            if req.prompt_i < len(req.prompt):
                # teacher forcing: the sample is discarded, the next
                # prompt token rides the next step
                rep.tokens_np[i] = req.prompt[req.prompt_i]
                req.prompt_i += 1
                seats.append((i, req, 0))
                continue
            req.n_ahead += 1
            if len(req.tokens) + req.n_ahead >= req.max_new \
                    or rep.pos_np[i] >= self.max_len:
                # a dead slot rides along at position 0, like any other
                rep.valid_np[i] = 0.0
                rep.tokens_np[i] = 0.0
                rep.pos_np[i] = 0.0
                seats.append((i, req, 2))
            else:
                rep.tokens_np[i] = FROM_PREVIOUS
                seats.append((i, req, 1))
        return seats

    def _deliver(self, rep, seats, ids):
        """Hand a read step's ids to the requests that sat in its slots
        when it was dispatched (``seats``), not to whoever sits there
        now: a request that has left since (deadline, eos one step
        back, a raising callback, a failure), its slot free or seated
        anew, gets nothing, and the slot-step counts as discarded.
        Returns ``(new tokens, discarded slot-steps, leaving)``: who
        ended with this step leaves once it is booked (``_booked``)."""
        # one C-level conversion instead of num_slots ndarray-scalar
        # __getitem__ calls: the slot loop below is the scheduler's
        # per-step GIL cost, and with replica routing two of these
        # loops interleave on the host
        ids_l = ids.tolist()
        eos = self.eos_id
        new_tokens = discarded = 0
        leaving = []
        t_tok = time.perf_counter()     # one stamp serves every slot
        for i, req, kind in seats:
            if rep.slots[i] is not req:
                discarded += 1
                continue
            req.n_steps += 1
            if not kind:
                continue
            req.n_ahead -= 1
            tok = int(ids_l[i])
            req.tokens.append(tok)
            new_tokens += 1
            if req.t_first_tok is None:
                self._first_token(req, t_tok)
            req.t_last_tok = t_tok
            self._emit_token(req, tok)
            if req.on_token is not None \
                    and not self._fire_on_token(rep, req, tok):
                continue        # evicted by its own callback
            if eos is not None and tok == eos:
                leaving.append((i, "eos"))
            elif kind == 2:
                leaving.append((i, "length"))
        return new_tokens, discarded, leaving

    def _settle(self, rep):
        """Read and deliver the step in flight, if there is one: what
        touches the pool next (a close, a failure's clean-up) finds
        every sampled id delivered and nothing pending.  A failure may
        be what brought the loop here: a step that cannot be read is
        dropped, and its requests keep the tokens they have.  Such a
        step leaves no ``decode.step`` event; its tokens are booked."""
        read, rep.flight = rep.flight, None
        if read is None:
            return
        try:
            ids = read[0].read()
        except Exception:
            return
        new_tokens, discarded, leaving = self._deliver(rep, read[1], ids)
        self._booked(rep, 0, new_tokens, time.perf_counter(),
                     discarded=discarded, leaving=leaving)

    def _ledger_step(self, rep, occ, useful):
        """Spread one step dispatch's useful FLOPs over the live slots
        for tenant accounting (integer shares; the remainder stays in
        the engine-level ledger, which is exact by construction)."""
        if not useful:
            return
        share = useful // len(occ)
        if not share:
            return
        for i in occ:
            req = rep.slots[i]
            if req.tenant is not None:
                req.uflops += share

    def _advance_spec(self, rep, occ, toks_mat, counts):
        """The variable-width slot advance (ISSUE 15): slot ``i``
        committed ``counts[i]`` positions this dispatch and
        ``toks_mat[i, :counts[i]]`` holds its accepted tokens in
        generation order — the exact ``greedy_decode`` prefix under
        the greedy sampler.  Emission truncates at eos / max_new /
        max_len (a truncated slot always FINISHES, so positions the
        program committed past the truncation point free with the
        slot); ``on_token`` and the SSE stream fire once per accepted
        token, in order, exactly like the single-token loop.  Returns
        ``(new tokens, leaving)``, as :meth:`_deliver` does."""
        toks_l = toks_mat.tolist()
        counts_l = counts.tolist()
        new_tokens = 0
        leaving = []
        drafted = accepted = spec_slots = 0
        t_tok = time.perf_counter()
        for i in occ:
            req = rep.slots[i]
            req.n_steps += 1
            if req.prompt_i < len(req.prompt):
                # teacher forcing: the program committed ONE position
                # (spec mask 0) — both models consumed the staged
                # prompt token; stage the next one
                rep.pos_np[i] += 1.0
                rep.tokens_np[i] = req.prompt[req.prompt_i]
                req.prompt_i += 1
                if req.prompt_i >= len(req.prompt):
                    rep.spec_np[i] = 1.0
                leaving += self._ended(rep, i)
                continue
            c = int(counts_l[i])
            spec_slots += 1
            drafted += self._spec_k
            accepted += c - 1
            cap = min(c, req.max_new - len(req.tokens),
                      self.max_len - int(rep.pos_np[i]))
            rep.pos_np[i] += float(c)
            evicted = False
            last = None
            for jj in range(cap):
                tok = int(toks_l[i][jj])
                req.tokens.append(tok)
                new_tokens += 1
                if req.t_first_tok is None:
                    self._first_token(req, t_tok)
                req.t_last_tok = t_tok
                self._emit_token(req, tok)
                if req.on_token is not None \
                        and not self._fire_on_token(rep, req, tok):
                    evicted = True
                    break
                last = tok
                if self.eos_id is not None and tok == self.eos_id:
                    break
            if evicted:
                continue
            if last is not None:
                rep.tokens_np[i] = float(last)
            leaving += self._ended(rep, i)
        if spec_slots:
            with self._lock:
                self._spec_steps += 1
                self._spec_slot_steps += spec_slots
                self._spec_drafted += drafted
                self._spec_accepted += accepted
            if self._tm is not None and self._tm.spec_drafted \
                    is not None:
                self._tm.spec_drafted.inc(drafted)
                self._tm.spec_accepted.inc(accepted)
                self._tm.spec_rejected.inc(drafted - accepted)
                if drafted:
                    self._tm.spec_accept.observe(accepted
                                                 / float(drafted))
        return new_tokens, leaving

    def _ended(self, rep, slot):
        """``[(slot, reason)]`` where the slot's request has ended with
        the tokens it holds, ``[]`` where it goes on."""
        req = rep.slots[slot]
        if req is None or not req.tokens:
            return []
        if self.eos_id is not None and req.tokens[-1] == self.eos_id:
            return [(slot, "eos")]
        # at ``max_len`` no position is left to consume the staged
        # token at: the fixed O(1) cache layout is full
        if len(req.tokens) >= req.max_new \
                or rep.pos_np[slot] >= self.max_len:
            return [(slot, "length")]
        return []

    def _finish_slot(self, rep, slot, reason):
        """Leave the batch between steps: deliver the result, mark the
        slot dead (valid=0) — its state rows stay as stale garbage,
        which the row-local step verdict proves harmless, and the next
        join rewrites them."""
        req = rep.slots[slot]
        rep.slots[slot] = None
        rep.valid_np[slot] = 0.0
        rep.tokens_np[slot] = 0.0
        rep.pos_np[slot] = 0.0
        rep.spec_np[slot] = 0.0
        now = time.monotonic()
        t1 = time.perf_counter()
        res = DecodeResult(req.tokens, reason, n_steps=req.n_steps,
                           prompt_len=len(req.prompt))
        if req.tenant is not None and req.uflops \
                and self._eff is not None:
            # flush the request's accumulated useful-FLOPs share to
            # its tenant series (tokens/outcome/latency ride the
            # future's done callback)
            self._eff.tenant_useful(req.tenant, req.uflops)
            req.uflops = 0
        if not req.future.cancelled():
            try:
                req.future.set_result(res)
            except Exception:
                pass
        with self._lock:
            self._leaves += 1
            self._requests_served += 1
            if reason == "deadline":
                self._evictions += 1
            self._lat_ms.append((now - req.t_enqueue) * 1e3)
        if self._tl is not None:
            self._tl.instant(
                "decode.evict" if reason == "deadline"
                else "decode.leave", "decode",
                "decode:%s" % rep.label,
                args={"slot": slot, "reason": reason,
                      "request": req.sse_id,
                      "tokens": len(req.tokens)})
        if self._tm is not None:
            self._tm.leave(reason)
            if reason == "deadline":
                self._tm.evictions.inc()
            if len(req.tokens) >= 2 and req.t_first_tok is not None \
                    and req.t_last_tok is not None:
                # mean inter-token gap over this request's generation:
                # one observation per request keeps the hot loop at
                # O(1) instrument calls while the histogram still
                # carries the per-request tail the counter cannot
                self._tm.tpot.observe(
                    (req.t_last_tok - req.t_first_tok)
                    / (len(req.tokens) - 1))
        if req.trace is not None:
            t_join = req.t_join if req.t_join is not None else t1

            def build(tc, _req=req, _t_join=t_join, _t1=t1,
                      _reason=reason):
                tc.add("queue-wait", tc.root.t0, _t_join, "serve")
                meta = {"steps": _req.n_steps,
                        "tokens": len(_req.tokens),
                        "prompt_len": len(_req.prompt),
                        "finish_reason": _reason}
                if _req.sse_id is not None:
                    # the request id joins the retained trace to its
                    # SSE stream and timeline token instants — the
                    # request_autopsy lookup key
                    meta["request"] = _req.sse_id
                tc.add("decode", _t_join, _t1, "serve", meta=meta)
            req.trace.finish(t1, build=build)

    # ------------------------------------------------------------ observe
    def warmup(self):
        """Compile everything live traffic will ever dispatch: the
        persistent step program, the per-state row-write kernels, and
        (with a prefill graph) one program per pow2 prompt bucket.
        After this, joins/leaves/steps never trace — tests pin
        ``compile_count`` across churn.  Returns the compile count.
        Call it before traffic: an empty pool is warmed in place (a
        second pool beside it need not fit the device).

        The step runs TWICE on purpose: jax's executable cache keys on
        argument sharding, and the kernel's own state outputs (every
        live iteration's inputs) carry committed shardings that fresh
        ``init_states`` buffers don't — one warm step would leave the
        first live iteration paying a silent ~100ms recompile that the
        trace counter cannot even see.  The second step is also the
        first to be handed a step's own ids as its previous ids, as
        every fed-back step of live traffic is.  The row-write kernel likewise
        warms against both a fresh buffer and a stepped one (the two
        shardings a prefill scatter can meet)."""
        for rep in self._replicas:
            self._warm_replica(rep)
        return self.compile_count

    def _warm_replica(self, rep):
        """One replica's warm sequence — the docstring above is the
        contract; shared with ``rehabilitate()`` so a rehabilitated
        replica warms (and commits state shardings) exactly like a
        fresh one."""
        z = np.zeros((self.num_slots,), np.float32)
        prog = rep.program
        # an empty pool is warmed in place and keeps the stepped,
        # committed buffers (dead slots hold whatever a warm step left,
        # as they hold a finished request's rows: a join resets or
        # overwrites them); a second pool beside the first need not fit.
        # With a request seated, a scratch pool takes the warm steps
        idle = rep.occupied_count() == 0

        def keep(states):
            # step by step: the pool's buffers are donated to each warm
            # program, and a warm-up that raises midway must leave the
            # replica holding live ones
            if idle:
                rep.states = states
            return states

        states = rep.states if idle else prog.init_states()
        states = keep(prog.zero_row(states, 0))
        for _ in range(2):
            if self._spec_k:
                _t, _c, states = prog.step_spec(z, z, z, z, states)
            else:
                _, states = prog.step(z, z, z, states)
            keep(states)
        # ALL states — the prefill path also scatters draft rows
        # (zero_row which="draft") into STEPPED buffers, and their
        # per-sharding row kernels must be warm too
        states = keep(prog.zero_row(states, 0))
        for b in rep.prefill_buckets:
            # the full (batch, prompt) bucket grid: coalesced prefill
            # dispatches at pow2 BATCH extents too, and every shape
            # live traffic can meet must be warm or the zero-warm-
            # retrace contract would leak through the coalesced path
            # within the token budget a dispatch (``_prefill_grid``)
            for bb in self._prefill_grid.get(b, self._prefill_batches):
                self._fused_attention(rep, b, bb)
                outs = rep.prefill_caches[b].dispatch({
                    self._prefill_data_name: np.zeros((bb, b),
                                                      np.float32),
                    self._prefill_len_name: np.zeros((bb,), np.float32)})
                if self._rows_stay_on_device(rep):
                    # against stepped buffers, as live traffic meets it
                    states = keep(prog.commit_prefill(
                        states, outs[1:], [0] * bb, [0] * bb))
                np.asarray(outs[0])

    @property
    def compile_count(self):
        c = 0
        seen = set()
        for rep in self._replicas:
            c += rep.program.trace_count
            for cache in rep.prefill_caches.values():
                if id(cache) not in seen:   # shared length-poly cache
                    seen.add(id(cache))
                    c += cache.compile_count
        return c

    def _spec_stats(self):
        """The ``stats()["decode"]["spec"]`` block — caller holds
        ``self._lock``.  ``accept_rate`` is lifetime accepted/drafted;
        ``tokens_per_step`` counts committed tokens per SLOT per
        speculative step (accepted drafts + the one target token
        every per-slot step yields; 1.0 floor, k+1 ceiling) — the
        same numbers the spec telemetry series carry."""
        if not self._spec_k:
            return {"enabled": False, "k": 0}
        drafted = self._spec_drafted
        steps = self._spec_steps
        return {
            "enabled": True,
            "k": self._spec_k,
            "draft_verdict": self.draft_verdict,
            "steps": steps,
            "drafted": drafted,
            "accepted": self._spec_accepted,
            "rejected": drafted - self._spec_accepted,
            "accept_rate": (self._spec_accepted / float(drafted)
                            if drafted else None),
            "tokens_per_step": ((self._spec_accepted
                                 + self._spec_slot_steps)
                                / float(self._spec_slot_steps)
                                if self._spec_slot_steps else None),
            "commit_selection": self._spec_cfg.selection,
            "commit_accepted": (bool(self._spec_cfg.commit_plan
                                     .accepted)
                                if self._spec_cfg.commit_plan
                                is not None else None),
            "draft_digest": self._spec_cfg.draft_digest,
        }

    def stats(self):
        """Admission counters plus the ``decode`` block: slot-pool
        geometry and occupancy, step/token/join/leave/eviction
        counts, per-step and end-to-end latency percentiles — the
        same numbers the ``mxnet_serve_decode_*`` series carry."""
        snap = self._adm.stats()
        # allocator peek outside the lock: device_memory_peak() can
        # stall on the backend, and a scrape must not block stepping
        mem = _memory_stats_block(self.memory_plan)
        with self._lock:
            step = sorted(self._step_ms)
            lat = sorted(self._lat_ms)
            snap["decode"] = {
                "slots": self.num_slots * len(self._replicas),
                "slots_per_replica": self.num_slots,
                "slots_occupied": self._occupied_count(),
                "max_len": self.max_len,
                "steps": self._steps,
                # of those, the ones dispatched before the step before
                # them was read; and the slot-steps whose result was
                # thrown away because their request had left
                "steps_ahead": self._steps_ahead,
                "slot_steps_discarded": self._discarded,
                # seatable requests x steps the join policy left in
                # their queue for a fuller prefill dispatch, and the
                # table it decides on: what a dispatch has cost the
                # device, by bucket and batch (``_seats_now``)
                "slot_steps_held": self._slot_steps_held,
                "prefill_cost_ms": {
                    b: {bb: c * 1e3 for bb, c in sorted(costs.items())}
                    for b, costs in sorted(self._prefill_cost.items())},
                "tokens_generated": self._tokens_out,
                "joins": self._joins,
                "steals": self._steals,
                "leaves": self._leaves,
                "evictions": self._evictions,
                "requests_served": self._requests_served,
                "compile_count": self.compile_count,
                "sampler": self._sampler.describe(),
                "sharding": self._sharding_spec,
                "aot": (self._aot.stats() if self._aot is not None
                        else {"enabled": False}),
                "memory": mem,
                "efficiency": (self._eff.stats_block()
                               if self._eff is not None
                               else {"enabled": False}),
                "replicas": [r.describe() for r in self._replicas],
                "prefill": ("bucket" if self._prefill_caches
                            else "step"),
                "prefill_buckets": list(self._prefill_buckets),
                "prefill_coalesced": bool(self._coalesce),
                "prefill_batch_buckets": list(self._prefill_batches),
                "prefill_token_budget": self._prefill_token_budget,
                "prefill_programs": sum(
                    len(self._prefill_grid[b])
                    for b in self._prefill_buckets),
                "state_rows": dict(self._program.layout.cache_rows()),
                # a slot's plain rows (no cache: zeroed at a join,
                # replaced by a prefill), in bytes
                "row_state_bytes": self._program.layout.row_state_bytes(),
                "prefill_dispatches": self._prefill_dispatches,
                # attention nodes of those dispatches' programs, and
                # those that took the fused kernel (ops/transformer.py)
                "prefill_attention_nodes": self._prefill_attention_nodes,
                "prefill_fused_attention": self._prefill_fused_attention,
                "optimizer": {
                    "accepted": (bool(self.opt_plan.accepted)
                                 if self.opt_plan is not None else None),
                    "rewrites": (len(self.opt_plan.rewrites)
                                 if self.opt_plan is not None
                                 and self.opt_plan.accepted else 0),
                    "reason": (self.opt_plan.reason
                               if self.opt_plan is not None else None),
                    "selection": self.selection,
                },
                "spec": self._spec_stats(),
                "step_ms": {
                    "count": len(step),
                    "mean": float(np.mean(step)) if step else 0.0,
                    "p50": _percentile(step, 0.50),
                    "p99": _percentile(step, 0.99),
                },
                "latency_ms": {
                    "count": len(lat),
                    "mean": float(np.mean(lat)) if lat else 0.0,
                    "p50": _percentile(lat, 0.50),
                    "p99": _percentile(lat, 0.99),
                },
            }
        snap["supervisor"] = _supervisor_state(self)
        snap["regulator"] = (self._regulator.stats()
                             if self._regulator is not None
                             else {"enabled": False})
        snap["faults"] = _faults.stats()
        return snap

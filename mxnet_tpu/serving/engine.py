"""ServingEngine — dynamic-batching inference runtime.

The ROADMAP north star serves heavy multi-user traffic; the unit of
efficiency on an XLA device is the *compiled program dispatch*, not the
request (PAPERS.md fusion-amortization argument).  This engine turns
many concurrent single-example requests into few large dispatches:

    client threads --submit()--> AdmissionController (bounded queue,
        deadlines, shedding)  --take()--> worker thread: coalesce the
        oldest request's shape group, pad to the bucket grid
        (BucketPolicy), ONE CachedOp dispatch per batch (ProgramCache),
        scatter unpadded rows back to per-request futures.

Contrast with :class:`~mxnet_tpu.predict.Predictor`: the Predictor is a
blocking single-client executor that rebinds on shape change; the engine
is thread-safe, batches across clients, and never compiles off the
bucket grid — after ``warmup()`` the compile counter stays flat.

Observability: every enqueue/coalesce/dispatch emits a Chrome-trace span
through :mod:`mxnet_tpu.profiler` ('serve' lane) plus queue-depth and
batch-occupancy counters; ``stats()`` returns a point-in-time snapshot
including p50/p99 request latency.  With :mod:`mxnet_tpu.telemetry`
enabled (``MXNET_TELEMETRY_ON``, default on) the engine additionally
feeds the process-wide metrics registry (``mxnet_serve_*`` series:
queue depth, shed/reject/expiry, occupancy, padding waste per bucket,
program-cache hit/miss, retraces keyed by the retrace-linter's hazard
fingerprints, shape-signature entropy), traces every request and
retains span trees tail-biased (top-K slowest + moving-p99 + error
keep, with ``MXNET_TELEMETRY_TRACE_SAMPLE`` as the periodic floor;
``telemetry/sampling.py``) — queue-wait -> coalesce -> pad -> dispatch
-> unpad, retrievable by trace id via ``tools/telemetry_dump.py`` or
the live HTTP endpoint (``MXNET_TELEMETRY_PORT``: /metrics, /traces,
/healthz; released by ``close()``).

Multi-device: with ``replicas=N`` (or ``MXNET_SERVE_REPLICAS``) the
engine owns N data-parallel device replicas (serving/replica.py) —
each with its own program cache and device-resident params — and the
coalescer routes every formed batch to the least-loaded one; a replica
whose dispatch raises is drained, marked unhealthy, and its traffic
re-routed while siblings keep serving.

Persistence: with ``MXNET_AOT_CACHE_DIR`` set every bucket program is
serialized (jax.export) to a content-addressed on-disk cache at first
compile, and a restarted engine — or replica N+1 joining under load,
or a replica re-entering service through ``rehabilitate()`` — loads
warm with ZERO traces, serving bitwise-identically
(serving/aot_cache.py).

Env knobs (config.py): ``MXNET_SERVE_MAX_BATCH``,
``MXNET_SERVE_MAX_QUEUE``, ``MXNET_SERVE_BATCH_TIMEOUT_MS``,
``MXNET_SERVE_DEFAULT_DEADLINE_MS``, ``MXNET_SERVE_OVERLOAD_POLICY``,
``MXNET_SERVE_SEQ_BUCKETS``, ``MXNET_SERVE_REPAIR``,
``MXNET_SERVE_OPTIMIZE``, ``MXNET_SERVE_REPLICAS``,
``MXNET_AOT_CACHE_DIR`` / ``MXNET_AOT_CACHE``.
"""
from __future__ import annotations

import collections
import itertools
import math
import threading
import time
import warnings
import weakref
from concurrent.futures import Future

import numpy as np

from ..base import MXNetError
from .. import profiler
from .. import telemetry as _telemetry
from ..telemetry import goodput as _goodput
from . import faults as _faults
from .locks import named_lock, named_condition
from .admission import (AdmissionController, Request, EngineClosedError,
                        _fail_future)
from .buckets import BucketPolicy, ProgramCache, pad_valid_lengths
from .replica import ServeReplica, resolve_replica_placements

__all__ = ["ServingEngine"]


def _percentile(sorted_vals, q):
    if not sorted_vals:
        return 0.0
    k = min(len(sorted_vals) - 1, int(round(q * (len(sorted_vals) - 1))))
    return sorted_vals[k]


# distinct shape signatures tracked as individual label values before
# spilling into the catch-all "other" series (label cardinality bound)
_MAX_SIG_LABELS = 64

# hazard fingerprints carried verbatim in the retraces `hazards` label
# before the overflow marker takes over (label length bound); overflow
# is EXPLICIT ("...,+3") — tools/hazard_rank.py must be able to tell a
# truncated label from "these are all the hazards"
_MAX_HAZARD_LABEL_FPS = 16

# per-process engine ordinal: the `engine` label on point-in-time
# gauges, so co-resident engines get distinct series
_ENGINE_SEQ = itertools.count()

# unregistered sink for the submit-vs-close race: a counter nothing
# scrapes, so a racing submit cannot resurrect removed series
_NULL_COUNTER = _telemetry.Counter()


def aot_metric_families(reg):
    """Register (idempotently) the persistent-AOT-cache traffic
    families both engine kinds share — ``mxnet_serve_aot_{hits,misses,
    writes,rejects,prunes}_total``, per engine.  Hits are programs
    loaded from disk with zero traces; misses compiled fresh and
    persisted; writes are entries committed; rejects are
    present-but-unusable entries (corruption / fingerprint drift) —
    the "cold start that should have been warm" signal the default
    alert rule fires on; prunes are entries evicted oldest-first by
    the ``MXNET_AOT_CACHE_MAX_MB`` write-path size budget."""
    return tuple(reg.counter(
        "mxnet_serve_aot_%s_total" % what, doc, labelnames=("engine",))
        for what, doc in (
            ("hits", "AOT-cache entries loaded warm (a compiled "
                     "program this process never traced)"),
            ("misses", "AOT-cache misses: programs compiled fresh "
                       "(and persisted) because no entry existed"),
            ("writes", "AOT-cache entries committed to disk "
                       "(atomic tmp+rename)"),
            ("rejects", "AOT-cache entries present but unusable — "
                        "corrupt payload or fingerprint drift — "
                        "forcing a cold compile that should have "
                        "been warm (alertable; the engine's stats() "
                        "names the offending key)"),
            ("prunes", "AOT-cache entries evicted oldest-first by "
                       "the MXNET_AOT_CACHE_MAX_MB size budget on "
                       "the store() write path")))


def memory_metric_families(reg):
    """Register (idempotently) the static-memory-planner gauge pair
    both engine kinds share, per engine: the planner's construction-time
    liveness prediction and the backend allocator's measured high-water
    mark (where ``memory_stats`` exists — CPU hosts publish only the
    prediction).  Returns ``(predicted_fam, measured_fam)``."""
    return (reg.gauge(
        "mxnet_serve_memory_predicted_peak_bytes",
        "predicted peak HBM bytes for this engine's warm program set "
        "(params resident + activation high-water over the worst "
        "bucket program, divided along plan-partitioned axes) — the "
        "static memory planner's construction-time liveness watermark, "
        "computed before any compile",
        labelnames=("engine",)),
        reg.gauge(
            "mxnet_serve_memory_measured_peak_bytes",
            "backend allocator peak_bytes_in_use measured at scrape "
            "time (telemetry/devicemem.py probe) — the honest runtime "
            "side of the planner's predicted-vs-measured pair; absent "
            "on backends without memory_stats (CPU)",
            labelnames=("engine",)))


def refresh_memory_gauges(bundle, eng):
    """Scrape-time update of the predicted-vs-measured memory pair
    (shared by both engine bundles): the planner's watermark from the
    engine's construction-time plan, and the allocator's measured peak
    via the shared devicemem probe — probe-once, so a backend without
    ``memory_stats`` never grows a dead series."""
    mem = getattr(eng, "memory_plan", None)
    if mem:
        bundle.mem_predicted.set(float(mem.get(
            "predicted_peak_bytes", 0) or 0))
    if bundle._mem_probe_ok:
        from ..telemetry.devicemem import device_memory_peak
        peak = device_memory_peak()
        if peak is None:
            bundle._mem_probe_ok = False
        else:
            if bundle._mem_measured is None:
                bundle._mem_measured = bundle._mem_meas_fam.labels(
                    engine=bundle.engine_label)
            bundle._mem_measured.set(float(peak))


def _memory_stats_block(memory_plan):
    """One engine's ``stats()["memory"]`` block (shared by both engine
    kinds): the construction-time plan — predicted peak, per-program
    rows, budget verdict, donation outcome — plus the allocator's
    measured peak where the backend supports it (the same
    predicted-vs-measured pair the gauges carry)."""
    if not memory_plan:
        return {"enabled": False}
    from ..telemetry.devicemem import device_memory_peak
    return dict(memory_plan,
                measured_peak_bytes=device_memory_peak())


def _supervisor_state(engine):
    """One engine's ``stats()["supervisor"]`` block: the live process
    supervisor's per-engine slice, ``{"enabled": False}`` otherwise.
    Shared by both engine kinds (decode imports it)."""
    try:
        from . import supervisor as _supervisor
        return _supervisor.engine_state(engine)
    except Exception:
        return {"enabled": False}


class _EngineTelemetry(object):
    """The engine's instrument bundle against the default telemetry
    registry.  Built once per engine ONLY when telemetry is enabled —
    with ``MXNET_TELEMETRY_ON=0`` the engine holds ``None`` and its hot
    path performs zero instrument calls (tests assert this).

    Families are shared process-wide (a second engine reuses them), so
    counters aggregate across engines; point-in-time gauges (queue
    depth, program-cache hits/misses, compile count, shape entropy)
    carry an ``engine`` label so two live engines in one process
    cannot clobber each other's series.
    """

    def __init__(self, engine):
        reg = _telemetry.registry()
        self.engine_label = str(next(_ENGINE_SEQ))
        self.closed = False
        self.requests = reg.counter(
            "mxnet_serve_requests_total", "serving requests submitted")
        self.queue_wait = reg.histogram(
            "mxnet_serve_queue_wait_ms",
            "enqueue -> worker-pop wait per request",
            buckets=_telemetry.LATENCY_MS_BUCKETS)
        self.latency = reg.histogram(
            "mxnet_serve_request_latency_ms",
            "enqueue -> result end-to-end request latency",
            buckets=_telemetry.LATENCY_MS_BUCKETS)
        self.batches = reg.counter(
            "mxnet_serve_batches_total", "batches dispatched")
        self.occupancy = reg.histogram(
            "mxnet_serve_batch_occupancy",
            "live requests / bucket size per dispatched batch, per "
            "engine and device replica",
            labelnames=("engine", "replica"),
            buckets=_telemetry.RATIO_BUCKETS)
        self.dispatch_ms = reg.histogram(
            "mxnet_serve_dispatch_ms",
            "compiled-program dispatch wall time per batch, per engine "
            "and device replica — a replica whose dispatch tail "
            "diverges from its siblings is the straggling device",
            labelnames=("engine", "replica"),
            buckets=_telemetry.LATENCY_MS_BUCKETS)
        self.pad_waste = reg.histogram(
            "mxnet_serve_padding_waste_ratio",
            "padded-but-dead input elements / total padded elements "
            "per batch, by batch bucket",
            labelnames=("bucket",), buckets=_telemetry.RATIO_BUCKETS)
        self.padded_elems = reg.counter(
            "mxnet_serve_padded_elements_total",
            "total input elements dispatched (live + pad slots)",
            labelnames=("bucket",))
        self.live_elems = reg.counter(
            "mxnet_serve_live_elements_total",
            "live (request-backed) input elements dispatched",
            labelnames=("bucket",))
        self.compiles = reg.counter(
            "mxnet_serve_compiles_total",
            "XLA programs traced by this process's serving dispatches "
            "(warmup + cold buckets + retraces)")
        self.retraces = reg.counter(
            "mxnet_serve_retraces_total",
            "post-warmup XLA traces on serving dispatches — the "
            "compile-once contract demands this stays 0 per device "
            "replica (each replica owns its own program cache); the "
            "hazards label carries the retrace-linter fingerprints of "
            "the graph's statically known hazards, per engine, so "
            "tools/hazard_rank.py can credit each fingerprint with "
            "its own engine's traffic exposure",
            labelnames=("engine", "replica", "hazards"))
        self.shape_seen = reg.counter(
            "mxnet_serve_shape_signature_total",
            "requests per observed (bucket-padded) input-shape "
            "signature, per engine; drives the shape-entropy gauge",
            labelnames=("engine", "sig"))
        entropy_fam = reg.gauge(
            "mxnet_serve_shape_entropy_bits",
            "Shannon entropy (bits) of one engine's observed shape-"
            "signature distribution — high entropy + retrace hazards "
            "= the traffic most likely to trigger a retrace storm",
            labelnames=("engine",))
        self.entropy = entropy_fam.labels(engine=self.engine_label)
        queue_depth_fam = reg.gauge(
            "mxnet_serve_queue_depth",
            "pending admission-queue depth per engine",
            labelnames=("engine",))
        self.queue_depth = queue_depth_fam.labels(
            engine=self.engine_label)
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
        cache_hits_fam = reg.gauge(
            "mxnet_serve_program_cache_hits",
            "dispatch-plan cache hits (warm bucket signatures) per "
            "engine", labelnames=("engine",))
        self.cache_hits = cache_hits_fam.labels(engine=self.engine_label)
        cache_misses_fam = reg.gauge(
            "mxnet_serve_program_cache_misses",
            "dispatch-plan cache misses (first sight of a signature) "
            "per engine", labelnames=("engine",))
        self.cache_misses = cache_misses_fam.labels(
            engine=self.engine_label)
        compile_count_fam = reg.gauge(
            "mxnet_serve_compile_count",
            "CachedOp trace counter — programs compiled so far, per "
            "engine", labelnames=("engine",))
        self.compile_count = compile_count_fam.labels(
            engine=self.engine_label)
        self.repairs_applied = reg.counter(
            "mxnet_serve_repairs_applied_total",
            "construction-time masking rewrites adopted (verdict "
            "flipped row-local) per padded axis and frontier op — "
            "each count is one SequenceMask splice / mean renorm the "
            "engine now serves through instead of degrading",
            labelnames=("engine", "axis", "op"))
        self.repairs_rejected = reg.counter(
            "mxnet_serve_repairs_rejected_total",
            "construction-time repair attempts whose rewritten graph "
            "did not re-verify row-local: the engine fell back to the "
            "degrade path (exact-length programs / max_batch=1)",
            labelnames=("engine",))
        self.opt_removed = reg.counter(
            "mxnet_serve_opt_nodes_removed_total",
            "graph nodes the construction-time optimizer pipeline "
            "(analysis/optimize.py, MXNET_SERVE_OPTIMIZE) removed from "
            "the served graph, per pass that disconnected them — the "
            "candidate was adopted only after re-analysis verdicts "
            "came back no worse than the input graph's",
            labelnames=("engine", "pass"))
        self.opt_rejected = reg.counter(
            "mxnet_serve_opt_rejected_total",
            "optimizer rewrites planned but thrown away because the "
            "candidate graph's re-analysis verdicts came back worse "
            "(the engine serves the unoptimized graph), per pass that "
            "planned them",
            labelnames=("engine", "pass"))
        # replica plane (serving/replica.py): configured replica count,
        # per-replica health/load gauges the router's decisions read
        # back out of, and the failure counter the failover contract
        # is monitored by — families defined ONCE in replica.py and
        # shared with DecodeEngine (engine labels are process-unique
        # ordinals, so both kinds aggregate into one fleet view)
        from .replica import replica_metric_families
        (replicas_fam, self.replica_healthy, self.replica_inflight,
         self.replica_failures,
         self.replica_shards) = replica_metric_families(reg)
        self.replicas_g = replicas_fam.labels(engine=self.engine_label)
        self.replica_batches = reg.counter(
            "mxnet_serve_replica_batches_total",
            "batches dispatched per device replica — uniform counts "
            "mean the least-loaded router is actually balancing",
            labelnames=("engine", "replica"))
        # persistent-AOT-cache traffic (serving/aot_cache.py): families
        # defined ONCE here and shared with the decode bundle via
        # aot_metric_families — per-engine children bound by the engine
        # right after the bundle exists, reclaimed at close
        self.aot_fams = aot_metric_families(reg)
        # static memory planner (analysis/memory.py): predicted peak
        # set from the engine's plan at every scrape; measured peak
        # probed via the shared devicemem helper with the probe-once
        # discipline (CPU backends never publish the series)
        mem_pred_fam, mem_meas_fam = memory_metric_families(reg)
        self.mem_predicted = mem_pred_fam.labels(engine=self.engine_label)
        self._mem_meas_fam = mem_meas_fam
        self._mem_measured = None
        self._mem_probe_ok = True
        self._engine_gauge_fams = (queue_depth_fam, cache_hits_fam,
                                   cache_misses_fam, compile_count_fam,
                                   entropy_fam, replicas_fam,
                                   mem_pred_fam, mem_meas_fam)
        self._replica_fams = (self.replica_healthy, self.replica_inflight,
                              self.replica_failures, self.replica_batches,
                              self.replica_shards,
                              self.dispatch_ms, self.occupancy,
                              self.retraces)
        self.replicas_g.set(len(engine._replicas))
        # per-shard identity under the existing replica label: shard
        # count is construction-static, so set once here (1 for a
        # single-device replica; the devices themselves are on
        # describe()/healthz)
        for r in engine._replicas:
            self.replica_shards.labels(
                engine=self.engine_label, replica=r.label).set(
                len(r.plan.devices()) if r.plan is not None else 1)
        # bind per-replica children once — the dispatch hot path never
        # pays a labels() registry probe — and pre-touch the retrace
        # series under this graph's hazard label so a healthy replica
        # scrapes an explicit 0 (absence of the series would be
        # indistinguishable from "not instrumented" — and the
        # zero-count series is how the offline ranker knows a lint
        # fingerprint is DEPLOYED)
        for r in engine._replicas:
            r.tm_dispatch = self.dispatch_ms.labels(
                engine=self.engine_label, replica=r.label)
            r.tm_occupancy = self.occupancy.labels(
                engine=self.engine_label, replica=r.label)
            r.tm_retraces = self.retraces.labels(
                engine=self.engine_label, replica=r.label,
                hazards=engine._hazard_label)
            r.tm_batches = self.replica_batches.labels(
                engine=self.engine_label, replica=r.label)
            r.tm_failures = self.replica_failures.labels(
                engine=self.engine_label, replica=r.label)
        self._engine = weakref.ref(engine)
        reg.register_callback(self._refresh)

    def close(self):
        """Detach from the registry: an engine's bundle must not
        outlive it (constructing engines in a loop would otherwise
        leak one dead callback — and its per-engine series — per
        engine into every future scrape)."""
        self.closed = True      # before removal: see _sig_counter
        _telemetry.registry().unregister_callback(self._refresh)
        self._remove_engine_series()

    def _remove_engine_series(self):
        for fam in self._engine_gauge_fams:
            fam.remove(engine=self.engine_label)
        for fam in (self.shape_seen,
                    self.repairs_applied, self.repairs_rejected,
                    self.opt_removed, self.opt_rejected) \
                + self.aot_fams + self._replica_fams:
            for values, _inst in fam.series():
                if values[0] == self.engine_label:
                    fam.remove(*values)

    def _refresh(self, reg):
        """Collect-time callback: mirror engine-owned state into gauges
        so every scrape is fresh without a sampler thread."""
        eng = self._engine()
        if eng is None:
            # engine was GC'd without close(): self-evict, series too
            reg.unregister_callback(self._refresh)
            self._remove_engine_series()
            return
        self.cache_hits.set(sum(r.cache.plan_hits
                                for r in eng._replicas))
        self.cache_misses.set(sum(r.cache.plan_misses
                                  for r in eng._replicas))
        self.compile_count.set(eng.compile_count)
        refresh_memory_gauges(self, eng)
        eff = getattr(eng, "_eff", None)
        if eff is not None:
            eff.refresh()       # window MFU + goodput gauges per scrape
        for r in eng._replicas:
            self.replica_healthy.labels(
                engine=self.engine_label,
                replica=r.label).set(1.0 if r.healthy else 0.0)
            self.replica_inflight.labels(
                engine=self.engine_label,
                replica=r.label).set(r.inflight())
        # entropy over THIS engine's series only (sig children carry
        # the engine label) — a co-resident engine's traffic must not
        # contaminate the estimate
        vals = [inst.value for values, inst in self.shape_seen.series()
                if values[0] == self.engine_label]
        total = sum(vals)
        if total > 0:
            ent = -sum((v / total) * math.log2(v / total)
                       for v in vals if v > 0)
            self.entropy.set(ent if ent else 0.0)   # never -0.0


class ServingEngine(object):
    """Thread-safe batched-inference front end over one frozen graph.

    Parameters
    ----------
    symbol, arg_params, aux_params : the frozen graph + trained weights
        (same checkpoint artifacts ``Predictor`` consumes).
    data_shapes : dict name -> per-EXAMPLE shape (no batch dim); the
        reference signature requests are validated against.  With seq
        bucketing, the axis named by the policy may vary per request.
    policy : BucketPolicy, default built from the MXNET_SERVE_* env tier.
    start : spawn the worker thread immediately (tests pass False to
        stage requests against a stopped engine).
    replicas : data-parallel device replicas (default
        ``MXNET_SERVE_REPLICAS``).  ``ctx`` may also be a LIST of
        contexts, which is then the replica set verbatim (two replicas
        on one device is legal and how tests exercise routing without
        forcing a host device count).
    sharding : model-parallel plan spec (``parallel/mesh.py``
        ShardingPlan spec dict / JSON; default
        ``MXNET_SERVE_SHARDING``).  Each replica then owns a
        ``prod(axes)``-device GROUP in dp order and compiles every
        bucket program under the plan — pjit-style partitioning with
        params uploaded as sharded ``device_put``.  Data-parallel x
        model-parallel composition: ``replicas=N`` with a G-device
        plan serves N sharded replicas through the same
        router/failover machinery.  A plan that partitions a padded
        data axis is VERDICT-GATED like every rewrite
        (``analysis.check_sharding_plan``): cross-position or unproven
        axes reject at construction with a reason.
    """

    def __init__(self, symbol, arg_params, aux_params, data_shapes,
                 ctx=None, policy=None, max_queue=None,
                 batch_timeout_ms=None, default_deadline_ms=None,
                 overload_policy=None, dtype=np.float32, start=True,
                 replicas=None, sharding=None):
        from .. import config
        # chaos plan (serving/faults.py): installs MXNET_FAULT_PLAN if
        # one is named; with none the injection sites stay a single
        # predicate check and the engine is byte-for-byte uninjected
        _faults.ensure_env_plan()
        self._policy = policy or BucketPolicy.from_config()
        if max_queue is None:
            max_queue = config.get("MXNET_SERVE_MAX_QUEUE")
        if batch_timeout_ms is None:
            batch_timeout_ms = config.get("MXNET_SERVE_BATCH_TIMEOUT_MS")
        if default_deadline_ms is None:
            default_deadline_ms = config.get("MXNET_SERVE_DEFAULT_DEADLINE_MS")
        if overload_policy is None:
            overload_policy = config.get("MXNET_SERVE_OVERLOAD_POLICY")
        self._window_s = float(batch_timeout_ms) / 1e3
        self._default_deadline_s = float(default_deadline_ms) / 1e3
        self._sym = symbol
        self._data_shapes = {k: tuple(v) for k, v in dict(data_shapes).items()}
        self._dtype = np.dtype(dtype)
        # static pre-flight: IR verifier + padding-soundness over the
        # axes this engine will zero-pad.  A cross-position graph first
        # gets a masking REPAIR attempt (analysis/rewrite.py splices
        # SequenceMask nodes driven by a per-request valid-length
        # input; adopted only if re-analysis verdicts the rewritten
        # graph row-local) and only then has its unsound bucketing
        # REFUSED (strict) or de-fanged (warn + fall back to
        # exact-shape dispatch) instead of silently returning
        # contaminated values (ROADMAP padded-axis + auto-masking items).
        self.analysis_report = None
        self.repair_plan = None          # accepted RepairPlan, if any
        self._repair_rejected = None     # rejection reason, if attempted
        self._serve_sym = symbol         # what the ProgramCache compiles
        self._valid_name = None          # repaired graphs' extra input
        self._length_sources = {}        # input name -> per-example axis
        self._hazard_label = "none"
        self.hazard_fingerprints = {}
        self._verdicts = None            # padded-axis verdicts, if analyzed
        self._pad_check = config.get("MXNET_SERVE_PAD_CHECK")
        self._preflight_pre = None       # (report, ctx) over the original
        self._policy0 = self._policy     # policy before any degrade
        if config.get("MXNET_ANALYSIS_ON"):
            self._preflight(symbol, config.get("MXNET_ANALYSIS_STRICT"))
        # optimizing pass pipeline (analysis/optimize.py): rewrite the
        # graph the ProgramCache compiles — CSE, constant folding, DCE,
        # algebraic identities — adopted ONLY when re-analysis verdicts
        # are no worse than the input graph's.  Needs the analysis tier
        # (the acceptance protocol IS analysis), so both knobs gate it.
        self.opt_plan = None
        if config.get("MXNET_SERVE_OPTIMIZE") \
                and config.get("MXNET_ANALYSIS_ON"):
            self._optimize_preflight(arg_params, aux_params)
        # the preflight (report, ctx) pair is construction-time-only:
        # drop it so the full per-node shape/dtype environment is not
        # held resident for the engine's serving lifetime
        self._preflight_pre = None
        # device replicas (serving/replica.py, ROADMAP 2a): each owns
        # its own compile-once ProgramCache with params uploaded to its
        # device once.  replicas == 1 is the pre-replica fast path —
        # the worker dispatches inline, no router, no extra threads.
        data_names = list(self._data_shapes)
        if self._valid_name is not None:
            data_names.append(self._valid_name)
        # model-parallel serving (ROADMAP item 1): resolve the sharding
        # plan spec and gate it on the preflight's padded-axis verdicts
        # exactly like every rewrite — a plan that partitions a padded
        # axis the analysis cannot prove row-local is REJECTED with a
        # reason at construction (there is no degrade path for a wrong
        # placement).  With analysis off the gate fails closed for
        # data-axis partitions; placement-only plans (param rules) are
        # never gated.
        from ..analysis.sharding import gate_plan_spec
        self.sharding_check, self._sharding_spec = gate_plan_spec(
            sharding, self._verdicts, "serve", "ServingEngine")
        # static memory planner (analysis/memory.py): liveness-price
        # the full warm bucket grid — params resident + activation
        # high-water, divided along plan-partitioned axes — and
        # preflight it against the device budget BEFORE any compile.
        # Diagnosis only: the planner never mutates graph or policy,
        # so served outputs are bitwise-identical with it on or off.
        self.memory_plan = None
        if config.get("MXNET_MEMORY_PLAN") \
                and config.get("MXNET_ANALYSIS_ON"):
            self._memory_preflight(arg_params, aux_params,
                                   config.get("MXNET_ANALYSIS_STRICT"))
        # persistent AOT program cache (serving/aot_cache.py,
        # MXNET_AOT_CACHE_DIR): shared by every replica's ProgramCache
        # — a restarted engine loads every previously-served bucket
        # program warm (zero traces), and replica N+1 joining under
        # load draws replica 0's compiles from disk.  The analysis
        # verdicts + repair/optimizer outcome ride every entry's
        # validity fingerprint and are re-validated on load (drift =
        # reject + fresh compile, never a stale program); the bucket
        # policy rides the key.
        from .aot_cache import AOTCache
        self._aot = AOTCache.from_config(
            artifact={
                "kind": "serve",
                "verdicts": self._verdicts,
                "repair": {
                    "applied": (len(self.repair_plan.actions)
                                if self.repair_plan is not None else 0),
                    "valid_length_input": self._valid_name,
                    "rejected": bool(self._repair_rejected)},
                "optimizer": {
                    "accepted": (bool(self.opt_plan.accepted)
                                 if self.opt_plan is not None else None),
                    "nodes_before": (self.opt_plan.nodes_before
                                     if self.opt_plan is not None
                                     else None),
                    "nodes_after": (self.opt_plan.nodes_after
                                    if self.opt_plan is not None
                                    else None)},
                # the memory plan digest rides the validity
                # fingerprint like the padding/optimizer artifacts: a
                # persisted program priced under a different plan (or
                # with the planner toggled) re-validates before load
                "memory": (self.memory_plan.get("digest")
                           if self.memory_plan else None)},
            key_extra={"engine_kind": "serve",
                       "max_batch": self._policy.max_batch,
                       "seq_axis": self._policy.seq_axis,
                       "seq_buckets": list(self._policy.seq_buckets)},
            # the plan spec IS the key's sharding component (ROADMAP
            # residual b2): a sharded program and its unsharded twin —
            # or two different plans — can never hit each other's
            # entries, while N same-plan replicas share one entry
            # (device identities are not in the spec)
            sharding=self._sharding_spec or "none")
        # construction state rehabilitate() rebuilds retired replicas
        # from (the param handles are the same NDArrays the program
        # caches already hold device copies of)
        self._ctor = {"arg_params": arg_params, "aux_params": aux_params,
                      "data_names": data_names}
        self._replicas = []
        placements = resolve_replica_placements(replicas, ctx,
                                                self._sharding_spec)
        for i, (rctx, rplan) in enumerate(placements):
            cache = ProgramCache(self._serve_sym, arg_params, aux_params,
                                 data_names, ctx=rctx, dtype=dtype,
                                 aot=self._aot, plan=rplan,
                                 program="mx_serve_batch")
            self._replicas.append(ServeReplica(i, rctx, cache,
                                               plan=rplan))
        self._cache = self._replicas[0].cache   # single-replica alias
        self._multi = len(self._replicas) > 1
        self._route_lock = named_lock("serve.route")
        self._route_cond = named_condition("serve.route",
                                           self._route_lock)
        self._replicas_stop = False
        # telemetry bundle: None when disabled — every instrumented
        # branch below gates on that, keeping the disabled hot path at
        # zero registry calls per request
        self._tm = _EngineTelemetry(self) if _telemetry.enabled() else None
        # unified fleet timeline (telemetry/timeline.py): cached ring
        # reference, None when the plane is off — the disabled path
        # appends nothing and serves bitwise-identically
        self._tl = (_telemetry.timeline.get()
                    if _telemetry.timeline.enabled() else None)
        # serving efficiency plane (telemetry/goodput.py): the FLOPs
        # ledger + MFU/goodput gauges + tenant accounting.  None unless
        # telemetry AND MXNET_SERVE_EFFICIENCY are on — the disabled
        # dispatch path prices nothing and makes zero instrument calls
        self._eff = None
        if self._tm is not None and _goodput.enabled():
            self._eff = _goodput.EngineEfficiency(
                "serve", self._tm.engine_label)
            for r in self._replicas:
                self._eff.add_replica(r.label, ctx=r.ctx)
        if self._tm is not None:
            self._record_repair_telemetry()
            self._record_opt_telemetry()
            if self._aot is not None:
                self._aot.bind_telemetry(*(
                    fam.labels(engine=self._tm.engine_label)
                    for fam in self._tm.aot_fams))
        # trace-retention chain (telemetry/sampling.py): every request
        # is traced cheaply and kept/dropped at finish() — tail-biased
        # (top-K slowest + moving p99) with error keep and the
        # every-Nth periodic floor.  None = tracing off entirely
        # (MXNET_TELEMETRY_TRACE_SAMPLE=0 or telemetry disabled).
        self._trace_chain = (_telemetry.chain_from_config()
                             if self._tm is not None else None)
        # live HTTP endpoint: the first engine to find
        # MXNET_TELEMETRY_PORT set with no server running starts one;
        # close() releases it (refcounted across co-resident engines)
        self._owns_http_server = (_telemetry.server.engine_acquire()
                                  if self._tm is not None else False)
        self._sig_labels = {}        # group key -> shape-sig counter child
        self._sig_other = None       # shared catch-all child past the cap
        self._sig_lock = named_lock("serve.sig")  # creation + the cap
        self._retraces = 0
        self._adm = AdmissionController(max_queue=max_queue,
                                        overload_policy=overload_policy,
                                        wake_hint=self._policy.max_batch,
                                        telemetry=self._tm)
        self._lock = named_lock("serve.engine")
        self._group_cache = {}   # exact input shapes -> validated group
        self._lat_ms = collections.deque(maxlen=4096)
        self._batches = 0
        self._requests_served = 0
        self._occupancy_sum = 0.0
        self._warmup_batches = 0
        # time-series history + SLO alerting (telemetry/recorder.py,
        # alerts.py): the worker loop stamps a heartbeat the watchdog
        # rule polls, the engine registers under the per-engine label
        # so a flight-recorder bundle captures its stats(), and the
        # first engine starts the sampler thread (refcounted; the last
        # close() stops it).  All of it reclaimed at close().
        # Registered LAST: a constructor that raises above never holds
        # a rule, heartbeat, or recorder reference close() cannot drop.
        self._hb_t = time.monotonic()
        self._hb_busy = False
        self._owns_recorder = False
        self._alert_owner = None
        self._obs_name = None
        if self._tm is not None:
            self._obs_name = "serve.%s" % self._tm.engine_label
            _telemetry.recorder.register_heartbeat(self._obs_name,
                                                   self._heartbeat)
            _telemetry.recorder.register_engine(self._obs_name, self)
            self._owns_recorder = _telemetry.recorder.recorder_acquire()
            if config.get("MXNET_TELEMETRY_ALERTS"):
                self._alert_owner = \
                    _telemetry.register_engine_default_rules(
                        "serve", self._tm.engine_label,
                        aot=self._aot is not None)
        # self-healing control plane (ISSUE 12), both OFF by default:
        # the SLO-driven overload regulator (reads the burn-rate rule
        # states, adapts admission pressure) and the automatic
        # probation supervisor (drives rehabilitate() on a backoff
        # clock when a replica retires)
        self._regulator = None
        if self._tm is not None and config.get("MXNET_REGULATOR"):
            from .regulator import Regulator
            self._regulator = Regulator(
                self._adm, engine_label=self._tm.engine_label,
                name=self._obs_name or "serve")
        self._sup_owner = False
        if config.get("MXNET_SUPERVISOR"):
            from . import supervisor as _supervisor
            _supervisor.engine_acquire(self,
                                       name=self._obs_name or "serve")
            self._sup_owner = True
        self._worker = None
        if start:
            self.start()

    def _preflight(self, symbol, strict):
        """Construction-time static analysis (mxnet_tpu.analysis).

        Verifier errors raise under ``MXNET_ANALYSIS_STRICT``; a
        cross-position verdict along the bucketed **seq** axis first
        gets an automatic masking repair attempt (MXNET_SERVE_REPAIR,
        on by default): analysis/rewrite.py splices SequenceMask nodes
        driven by a new per-request valid-length input, and the
        rewritten graph is adopted ONLY when re-running
        verify+shapes+padding flips the verdict to row-local.  When the
        repair is rejected (or disabled) the engine degrades the
        affected bucketing to stay sound, exactly as before:

        - cross-position along **seq**: seq buckets are dropped — each
          exact length compiles its own program (correct, more traces);
        - cross-position along **batch**: requests stop coalescing at
          all (``max_batch=1``) — with positions mixing across the
          batch axis, even unpadded batching would blend requests.
        """
        from .. import config
        from ..analysis import (check_serving_graph, repair_serving_graph,
                                AnalysisError)
        verdicts, report, ctx = check_serving_graph(
            symbol, self._data_shapes, self._policy, with_ctx=True)
        self.analysis_report = report
        self._verdicts = dict(verdicts)
        self._preflight_pre = (report, ctx)
        # fingerprint the retrace-linter's hazard findings: runtime
        # retrace events are counted under these labels, tying an
        # observed compile storm back to the static warning that
        # predicted it (ROADMAP: rank hazards by observed traffic)
        self._harvest_hazards(report)
        if report.errors:
            if strict:
                report.raise_if_errors()    # names the failing passes
            warnings.warn("ServingEngine: graph verification failed:\n%s"
                          % report.format())
        cross = [lb for lb, v in verdicts.items() if v == "cross-position"]
        if not cross:
            return
        if "seq" in cross and config.get("MXNET_SERVE_REPAIR") \
                and not report.errors:
            plan = repair_serving_graph(symbol, self._data_shapes,
                                        self._policy,
                                        precomputed=(report, ctx))
            if plan.accepted:
                # serve the rewritten graph from the full bucket grid;
                # dispatch feeds the per-request live lengths that
                # drive the spliced masks (see _dispatch)
                self.repair_plan = plan
                self._serve_sym = plan.symbol
                self._valid_name = plan.valid_length_name
                self._length_sources = dict(plan.length_sources)
                cross.remove("seq")
                if not cross:
                    return
            else:
                self._repair_rejected = plan.reason
        detail = "\n".join(
            "  " + str(d) for d in report.warnings) or "  (see report)"
        if strict:
            raise AnalysisError(
                "[padding] ServingEngine: graph is cross-position along "
                "padded axis(es) %s — zero-pad slots would bleed into "
                "live outputs%s:\n%s"
                % (cross,
                   " (repair rejected: %s)" % self._repair_rejected
                   if self._repair_rejected else "", detail))
        if "seq" in cross:
            warnings.warn(
                "ServingEngine: graph is cross-position along the "
                "bucketed seq axis%s; disabling seq buckets (lengths "
                "still vary per request, but each exact length now "
                "compiles its own program):\n%s"
                % (" and the masking repair was rejected (%s)"
                   % self._repair_rejected if self._repair_rejected
                   else "", detail))
            self._policy = BucketPolicy(
                max_batch=self._policy.max_batch,
                seq_axis=self._policy.seq_axis, seq_buckets=())
            self._collect_seq_hazards()
        if "batch" in cross:
            warnings.warn(
                "ServingEngine: graph mixes positions across the BATCH "
                "axis; disabling request coalescing (max_batch=1) so "
                "requests cannot contaminate each other:\n%s" % detail)
            self._policy = BucketPolicy(
                max_batch=1, seq_axis=self._policy.seq_axis,
                seq_buckets=self._policy.seq_buckets)

    def _harvest_hazards(self, report):
        """Fold the report's retrace-linter warnings into this engine's
        hazard fingerprints (the ``hazards`` label on runtime retrace
        counts, and the offline ranker's join key)."""
        from ..analysis import hazard_fingerprint
        for d in report.warnings:
            if d.pass_name != "retrace":
                continue
            fp = hazard_fingerprint(d.node, d.op, d.message)
            self.hazard_fingerprints.setdefault(fp, str(d))
        if self.hazard_fingerprints:
            fps = sorted(self.hazard_fingerprints)
            label = fps[:_MAX_HAZARD_LABEL_FPS]
            if len(fps) > _MAX_HAZARD_LABEL_FPS:
                # no silent caps: the overflow count rides the label so
                # the offline ranker knows attribution is incomplete
                label.append("+%d" % (len(fps) - _MAX_HAZARD_LABEL_FPS))
            self._hazard_label = ",".join(label)

    def _collect_seq_hazards(self):
        """Exact-length degrade mode IS the retrace linter's
        unbucketed-dynamic-dim hazard (one compiled program per
        observed length, unbounded under real traffic) — invisible to
        the construction-time lint, which saw concrete bucket shapes.
        Re-run the linter at the degraded policy with the seq axis
        declared dynamic so the engine's runtime retrace counter
        carries the SAME fingerprints a ``graph_lint --json`` report
        yields — tools/hazard_rank.py joins the two."""
        from ..analysis import analyze
        shapes = {}
        for name, ex in self._data_shapes.items():
            s = [0 if ax == self._policy.seq_axis else d
                 for ax, d in enumerate(ex)]
            shapes[name] = (self._policy.max_batch,) + tuple(s)
        try:
            report, _ = analyze(self._sym, data_shapes=shapes,
                                policy=self._policy,
                                passes=("verify", "shapes", "retrace"))
        except Exception:
            return                      # advisory only: never block
        self._harvest_hazards(report)

    def _optimize_preflight(self, arg_params, aux_params):
        """Optimize the graph the ProgramCache compiles (the repaired
        symbol when a repair was adopted).  The candidate is served
        only when the plan's re-analysis verdicts are no worse than
        the input graph's — padded-axis verdicts, output shapes, and
        output dtypes all intact — so the compile-once contract and
        bitwise parity with the batch-1 Predictor survive every
        accepted rewrite.  A rejected (or crashed) optimization leaves
        the engine serving the unoptimized graph."""
        from ..analysis import optimize_graph
        from ..analysis.rewrite import serving_pad_spec
        try:
            full, pad_axes = serving_pad_spec(self._data_shapes,
                                              self._policy)
            valid_lengths = None
            if self._valid_name is not None:
                full[self._valid_name] = (self._policy.max_batch,)
                pad_axes["batch"][self._valid_name] = 0
                valid_lengths = {self.repair_plan.label: self._valid_name}
            dtypes = {n: self._dtype for n in self._data_shapes}
            if self._valid_name is not None:
                dtypes[self._valid_name] = np.dtype(np.float32)
            for src in (arg_params or {}), (aux_params or {}):
                for k, v in src.items():
                    dt = getattr(v, "dtype", None)
                    if dt is not None:
                        dtypes.setdefault(k, np.dtype(dt))
            # the preflight analysis covered exactly this symbol/spec
            # unless a repair swapped the graph or a degrade changed
            # the policy — reuse it then, re-analyze otherwise.  It
            # also assumed float32 throughout (no dtype seeding), so
            # any non-f32 tensor — engine data dtype OR a single
            # mixed-precision param — forces a re-analysis with honest
            # dtypes, or the cast-elimination guards would trust the
            # wrong beliefs (e.g. delete a real f16->f32 upcast).
            f32 = np.dtype(np.float32)
            pre = self._preflight_pre \
                if (self._serve_sym is self._sym
                    and self._policy is self._policy0
                    and all(np.dtype(d) == f32
                            for d in dtypes.values())) else None
            plan = optimize_graph(self._serve_sym, data_shapes=full,
                                  dtypes=dtypes, policy=self._policy,
                                  pad_axes=pad_axes, training=False,
                                  valid_lengths=valid_lengths,
                                  precomputed=pre)
        except Exception as e:      # optimizer crash must never block
            #                         construction: serve unoptimized
            warnings.warn("ServingEngine: graph optimization crashed "
                          "(%r); serving the unoptimized graph" % (e,))
            return
        self.opt_plan = plan
        if plan.accepted and plan.symbol is not None and plan.rewrites:
            self._serve_sym = plan.symbol
        elif not plan.accepted:
            warnings.warn("ServingEngine: graph optimization rejected "
                          "(%s); serving the unoptimized graph"
                          % plan.reason)

    def _memory_preflight(self, arg_params, aux_params, strict):
        """OOM preflight (analysis/memory.py): liveness-price the warm
        program set — one program per seq bucket at the largest batch
        bucket (byte cost is monotone in every padded extent, so the
        grid maximum IS the warm set's watermark) — with bytes divided
        along plan-partitioned axes, then compare against the device
        budget BEFORE any compile.  Over budget warns naming the
        offending program and bytes (``MXNET_ANALYSIS_STRICT=1``
        raises).  Every replica prices identically (same graph, same
        plan), so the watermark is per replica device group."""
        from ..analysis import AnalysisError
        from ..analysis.memory import (plan_memory, plan_digest,
                                       device_memory_budget,
                                       format_bytes)
        try:
            dtypes = {n: self._dtype for n in self._data_shapes}
            for src in (arg_params or {}), (aux_params or {}):
                for k, v in src.items():
                    dt = getattr(v, "dtype", None)
                    if dt is not None:
                        dtypes.setdefault(k, np.dtype(dt))
            seq_shapes = [(None, self._data_shapes)]
            if self._policy.seq_axis is not None \
                    and self._policy.seq_buckets:
                seq_shapes = []
                for sb in self._policy.seq_buckets:
                    shapes = {}
                    for name, ex in self._data_shapes.items():
                        s = list(ex)
                        s[self._policy.seq_axis] = sb
                        shapes[name] = tuple(s)
                    seq_shapes.append((sb, shapes))
            bb = max(self._policy.batch_buckets())
            programs = []
            for sb, shapes in seq_shapes:
                full = {name: (bb,) + tuple(ex)
                        for name, ex in shapes.items()}
                if self._valid_name is not None:
                    full[self._valid_name] = (bb,)
                plan, _rep = plan_memory(self._serve_sym, full,
                                         dtypes=dtypes,
                                         sharding=self._sharding_spec)
                if not plan:
                    continue
                programs.append({
                    "program": ("b%d" % bb) + ("s%d" % sb
                                               if sb is not None else ""),
                    "peak_bytes": plan["peak_bytes"],
                    "param_bytes": plan["param_bytes"],
                    "transient_peak_bytes": plan["transient_peak_bytes"],
                    "inplace_savings_bytes":
                        plan["inplace_savings_bytes"]})
            if not programs:
                return
            worst = max(programs, key=lambda p: p["peak_bytes"])
            mem = {
                "enabled": True,
                "programs": programs,
                "predicted_peak_bytes": worst["peak_bytes"],
                "param_bytes": worst["param_bytes"],
                "offender": worst["program"],
                "sharded": bool(self._sharding_spec),
                "donation": None,
            }
            # budget is a property of THIS host, not of the plan:
            # digest only the deterministic prediction, or the same
            # program would fingerprint-drift across machines
            mem["digest"] = plan_digest(
                {k: mem[k] for k in ("programs", "predicted_peak_bytes",
                                     "sharded", "donation")})
            budget = device_memory_budget()
            mem["budget_bytes"] = budget
            mem["budget_ok"] = (None if budget is None
                                else worst["peak_bytes"] <= budget)
            self.memory_plan = mem
            if mem["budget_ok"] is False:
                msg = ("ServingEngine memory preflight: program %r "
                       "predicts peak %s (params %s + transient %s) "
                       "but the device budget is %s — the warm set "
                       "cannot fit; shrink max_batch/seq buckets, "
                       "shard the plan, or raise "
                       "MXNET_MEMORY_BUDGET_BYTES (priced before any "
                       "compile)"
                       % (worst["program"],
                          format_bytes(worst["peak_bytes"]),
                          format_bytes(worst["param_bytes"]),
                          format_bytes(worst["transient_peak_bytes"]),
                          format_bytes(budget)))
                if strict:
                    raise AnalysisError("[memory] " + msg)
                warnings.warn(msg)
        except AnalysisError:
            raise
        except Exception as e:      # planner crash must never block
            #                         construction: advisory pass
            warnings.warn("ServingEngine: memory preflight crashed "
                          "(%r); continuing without a memory plan"
                          % (e,))

    def _record_opt_telemetry(self):
        """Mirror the construction-time optimizer outcome into the
        registry (mxnet_serve_opt_*_total), per pass."""
        tm = self._tm
        plan = self.opt_plan
        if plan is None:
            return
        if plan.accepted:
            for p, st in plan.per_pass.items():
                if st.get("nodes_removed"):
                    tm.opt_removed.labels(tm.engine_label, p).inc(
                        st["nodes_removed"])
        else:
            # only graph-changing actions count as rejected rewrites —
            # fusion hints and DCE orphan sweeps were never candidate
            # rewrites (keeps the counter consistent with
            # stats()["optimizer"]["rejected"])
            rej = collections.Counter(
                a.pass_name for a in plan.rewrites)
            for p, c in rej.items():
                tm.opt_rejected.labels(tm.engine_label, p).inc(c)

    def _record_repair_telemetry(self):
        """Mirror the construction-time repair outcome into the
        registry (mxnet_serve_repairs_*_total): runs once, right after
        the telemetry bundle exists — _preflight decided the outcome
        before the bundle was built."""
        tm = self._tm
        if self.repair_plan is not None:
            for a in self.repair_plan.actions:
                tm.repairs_applied.labels(
                    engine=tm.engine_label,
                    axis=self.repair_plan.label, op=a.op).inc()
        if self._repair_rejected is not None:
            tm.repairs_rejected.labels(engine=tm.engine_label).inc()

    @classmethod
    def from_checkpoint(cls, prefix, epoch, data_shapes, **kwargs):
        """Build from Module checkpoint artifacts
        (``prefix-symbol.json`` + ``prefix-%04d.params``)."""
        from ..model import load_checkpoint
        symbol, arg_params, aux_params = load_checkpoint(prefix, epoch)
        return cls(symbol, arg_params, aux_params, data_shapes, **kwargs)

    # ------------------------------------------------------------ lifecycle
    def start(self):
        if self._adm.closed:
            raise EngineClosedError(
                "engine is closed; build a new ServingEngine")
        if self._worker is None:
            self._worker = threading.Thread(target=self._run,
                                            name="mxnet-serve-worker",
                                            daemon=True)
            self._worker.start()
        self._ensure_replica_threads()
        return self

    def _ensure_replica_threads(self):
        """Spawn the per-replica dispatch threads (multi-replica only:
        the single-replica worker dispatches inline)."""
        if not self._multi:
            return
        for r in self._replicas:
            if r.thread is None:
                r.thread = threading.Thread(
                    target=self._replica_run, args=(r,),
                    name="mxnet-serve-replica-%d" % r.index, daemon=True)
                r.thread.start()

    def close(self, drain=True):
        """Stop admitting; with ``drain`` finish queued work first.
        Closing is PERMANENT (``start()`` afterwards raises — build a
        new engine).  Draining waits for the worker as long as the
        queue needs; the no-drain path fails pending futures and bounds
        the wait.  The worker handle is only cleared once the thread is
        actually dead."""
        # stop the overload regulator FIRST: a drain must complete the
        # queued work, not have a still-ticking regulator shed it
        if self._regulator is not None:
            self._regulator.close()
            self._regulator = None
        if self._sup_owner:
            from . import supervisor as _supervisor
            self._sup_owner = False
            _supervisor.engine_release(self)
        self._adm.close(drain=drain)
        if self._worker is not None:
            self._worker.join(timeout=None if drain else 60)
            if not self._worker.is_alive():
                self._worker = None
        elif drain:
            # never started: route/dispatch the backlog on the caller's
            # thread (replica threads must exist for the routed half)
            self._ensure_replica_threads()
            self._run()
        if self._multi:
            # coalescer is done routing; replica threads drain their
            # queues (or fail them, no-drain) and exit
            with self._route_lock:
                self._replicas_stop = True
                if not drain:
                    orphans = []
                    for r in self._replicas:
                        orphans.extend(r.pending)
                        r.pending.clear()
                self._route_cond.notify_all()
            if not drain:
                for reqs, _t in orphans:
                    e = EngineClosedError("engine closed before dispatch")
                    for req in reqs:
                        if not req.future.done():
                            _fail_future(req.future, e)
                            if req.trace is not None:
                                req.trace.abort(type(e).__name__)
            for r in self._replicas:
                if r.thread is not None:
                    r.thread.join(timeout=None if drain else 60)
                    if not r.thread.is_alive():
                        r.thread = None
        if self._eff is not None:
            # ledger series (engine+replica+tenant children), healthz
            # section refcount — reclaimed with the bundle
            self._eff.close()
            self._eff = None
        # the timeline ring is process-wide (no per-engine state to
        # reclaim); drop the reference so a closed engine cannot feed
        self._tl = None
        if self._tm is not None:
            self._tm.close()
        if self._obs_name is not None:
            # observability plane detach: heartbeat, flight-recorder
            # stats registration, and this engine's alert rules (shared
            # burn-rate rules drop only at the last owner) all go —
            # reload loops must not grow the watchdog poll or the rule
            # table
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
            # last engine out stops the HTTP endpoint: port + acceptor
            # thread are released, so reload loops cannot leak either
            self._owns_http_server = False
            _telemetry.server.engine_release()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # -------------------------------------------------------------- client
    def _group_for(self, feeds):
        """Validate one request's inputs and compute its coalescing key
        (bucket-padded per-example shapes, name-sorted).  Memoized on
        the exact input shapes — warm traffic repeats a handful of
        shapes, so the hot submit path is one dict probe."""
        try:
            sig = tuple(sorted((k, v.shape) for k, v in feeds.items()))
            hit = self._group_cache.get(sig)
            if hit is not None:
                return hit
        except TypeError:
            sig = None
        if set(feeds) != set(self._data_shapes):
            raise MXNetError("inputs %s do not match engine data inputs %s"
                             % (sorted(feeds), sorted(self._data_shapes)))
        group = []
        for name in sorted(feeds):
            x = feeds[name]
            ref = self._data_shapes[name]
            if x.ndim != len(ref):
                raise MXNetError(
                    "input %r: rank %d does not match reference %s "
                    "(per-example shapes, no batch dim)"
                    % (name, x.ndim, ref))
            for ax, (got, want) in enumerate(zip(x.shape, ref)):
                if ax == self._policy.seq_axis:
                    continue
                if got != want:
                    raise MXNetError(
                        "input %r: axis %d is %d, engine serves %d "
                        "(only the seq axis may vary per request)"
                        % (name, ax, got, want))
            padded = self._policy.example_shape(x.shape)
            group.append((name, padded))
        if self._length_sources:
            # repaired graph: every input the repaired axis pads must
            # agree on ONE live length per request — reject the
            # offending request HERE, at submit, so it cannot fail the
            # whole coalesced batch at dispatch (_live_length is the
            # backstop)
            lens = {feeds[n].shape[ax]
                    for n, ax in self._length_sources.items()}
            if len(lens) > 1:
                raise MXNetError(
                    "repaired-graph request needs ONE live length, but "
                    "its inputs disagree along the repaired axis: %s"
                    % {n: feeds[n].shape[ax]
                       for n, ax in sorted(self._length_sources.items())})
        # With seq bucketing, outputs must be sliced back to exactly what
        # the graph would produce at the UNPADDED input — inferred from
        # the symbol, never guessed from axis sizes (an output axis that
        # merely coincides with the pad length must not be cut).
        out_rows = None
        if self._policy.seq_axis is not None:
            _, out_shapes, _ = self._sym.infer_shape(
                **{k: (1,) + v.shape for k, v in feeds.items()})
            out_rows = tuple(tuple(s[1:]) for s in out_shapes)
        # padded-element cost: what this request occupies in a
        # dispatched batch (the per-bucket padded/live element
        # accounting prices batches with exactly these numbers) —
        # the overload regulator's cost-aware shed ranks by it
        cost = int(sum(int(np.prod(shape)) if shape else 1
                       for _name, shape in group))
        out = tuple(group), out_rows, cost
        if sig is not None:
            self._group_cache[sig] = out
        return out

    def submit(self, value=None, deadline_ms=None, tenant=None, **feeds):
        """Enqueue one request; returns a ``concurrent.futures.Future``
        resolving to the per-request output array (list of arrays for
        multi-output graphs).

        ``tenant`` optionally names the submitting tenant for the
        efficiency plane's per-tenant accounting (useful FLOPs,
        outcome, e2e latency under a bounded-cardinality label;
        telemetry/goodput.py).  Ignored — zero instrument calls —
        when the plane is off.

        Raises :class:`QueueFullError` immediately under backpressure;
        the future fails with :class:`DeadlineExceededError` /
        :class:`ServerOverloadError` for expiry / shedding.
        """
        if value is not None:
            if len(self._data_shapes) != 1:
                raise MXNetError("positional submit needs a single-input "
                                 "graph; pass inputs by name")
            if feeds:
                raise MXNetError("pass the input either positionally or "
                                 "by name, not both")
            feeds = {next(iter(self._data_shapes)): value}
        # fail fast pre-instrumentation: a submit against a closed
        # engine must not touch the registry — close() already removed
        # this engine's per-engine series, and re-creating one here
        # (new shape signature) would orphan it in every future scrape
        if self._adm.closed:
            raise EngineClosedError("serving engine is closed")
        feeds = {k: np.asarray(v, dtype=self._dtype)
                 for k, v in feeds.items()}
        group, out_rows, cost = self._group_for(feeds)
        if deadline_ms is None and self._default_deadline_s > 0:
            deadline_ms = self._default_deadline_s * 1e3
        deadline = None if not deadline_ms else \
            time.monotonic() + float(deadline_ms) / 1e3
        fut = Future()
        trace = None
        if self._tm is not None:
            self._tm.requests.inc()
            self._sig_counter(group).inc()
            if self._trace_chain is not None:
                # trace EVERY request, cheaply: a LazyTrace is one
                # timestamp; the chain decides retention at finish(),
                # when the e2e latency is known — that is what makes
                # tail-biased keeps retroactive — and only the kept
                # minority materializes a real span tree
                trace = _telemetry.LazyTrace(self._trace_chain)
        req = Request(feeds, group, fut, deadline=deadline,
                      out_rows=out_rows, trace=trace, cost=cost)
        if tenant is not None and self._eff is not None:
            # resolve the tenant onto the bounded label set ONCE here;
            # the done-callback covers every terminal path (result,
            # error, cancel) for outcome/latency accounting, and
            # _dispatch attributes the useful-FLOPs share by label
            req.tenant = self._eff.tenant_enter(tenant)
            if req.tenant is not None:
                fut.add_done_callback(
                    lambda f, _eff=self._eff, _t=req.tenant,
                    _t0=req.t_enqueue: _eff.tenant_done(_t, f, _t0))
        try:
            if profiler.is_running():
                with profiler.record_span("serve.enqueue", "serve"):
                    self._adm.admit(req)
                profiler.counter("serve.queue_depth", len(self._adm))
            else:
                self._adm.admit(req)
        except Exception as e:
            if trace is not None:     # rejected at the door: still record
                trace.abort(type(e).__name__)
            raise
        return fut

    def _sig_counter(self, group):
        """Shape-signature counter child for one coalescing key,
        memoized; past _MAX_SIG_LABELS distinct signatures traffic
        lands on the catch-all 'other' series (bounded cardinality:
        the point is an entropy estimate, not an exact census)."""
        child = self._sig_labels.get(group)
        if child is not None:
            return child                # warm path: lock-free dict probe
        with self._sig_lock:            # cold path: create under a lock
            child = self._sig_labels.get(group)
            if child is not None:
                return child
            if self._tm.closed:
                # racing a concurrent close(): do not re-create series
                # the close just removed — count into an unregistered
                # sink instead (the submit is about to be rejected)
                return _NULL_COUNTER
            if len(self._sig_labels) >= _MAX_SIG_LABELS:
                # at the cap, do NOT memoize new keys either — the memo
                # dict must stay as bounded as the label set (the lock
                # makes the cap exact under concurrent submits)
                if self._sig_other is None:
                    self._sig_other = self._tm.shape_seen.labels(
                        engine=self._tm.engine_label, sig="other")
                return self._sig_other
            sig = "|".join("%s:%s" % (name, "x".join(map(str, shape)))
                           for name, shape in group)
            child = self._tm.shape_seen.labels(
                engine=self._tm.engine_label, sig=sig)
            self._sig_labels[group] = child
            return child

    def predict(self, value=None, timeout=None, deadline_ms=None, **feeds):
        """Synchronous convenience wrapper around :meth:`submit`."""
        return self.submit(value, deadline_ms=deadline_ms,
                           **feeds).result(timeout=timeout)

    def _heartbeat(self):
        """Watchdog probe (telemetry/recorder.py): age since the worker
        loop last made progress, and whether it HAS work — ``busy`` is
        the false-positive guard: an idle engine blocked on an empty
        queue is healthy however stale its stamp, while a worker that
        is mid-dispatch (or has work queued) and stale is wedged.
        Multi-replica engines report the STALEST busy component (a
        replica wedged in dispatch must trip the watchdog even while
        the coalescer keeps routing around it), plus a per-replica
        breakdown the flight bundle captures."""
        now = time.monotonic()
        queued = len(self._adm)
        out = {"age_s": now - self._hb_t,
               "busy": bool(self._hb_busy or queued),
               "in_dispatch": bool(self._hb_busy),
               "queued": queued, "kind": "serve",
               "engine": (self._tm.engine_label
                          if self._tm is not None else None)}
        if self._multi:
            ages = [now - self._hb_t] if out["busy"] else []
            reps = []
            for r in self._replicas:
                infl = r.inflight()
                age = now - r.hb_t
                if infl and r.healthy:
                    ages.append(age)
                reps.append({"replica": r.label, "healthy": r.healthy,
                             "inflight": infl,
                             "age_s": round(age, 3)})
            out["replicas"] = reps
            out["busy"] = bool(ages)
            out["age_s"] = max(ages) if ages else now - self._hb_t
            out["in_dispatch"] = any(r.in_dispatch
                                     for r in self._replicas)
        return out

    # -------------------------------------------------------------- worker
    def _run(self):
        while True:
            # heartbeat: progress stamp at every loop turn; busy only
            # once work is actually in hand (the blocking take below
            # may idle for hours on a quiet engine)
            self._hb_t = time.monotonic()
            self._hb_busy = False
            try:
                reqs = self._adm.take(self._policy.max_batch,
                                      self._window_s)
            except Exception:              # defense: never lose the worker
                continue
            if reqs is None:
                return                     # closed and drained
            if not reqs:
                continue
            self._hb_t = time.monotonic()
            self._hb_busy = True
            t_pop = time.perf_counter()
            if self._tm is not None:
                now_mono = time.monotonic()
                for r in reqs:
                    self._tm.queue_wait.observe(
                        (now_mono - r.t_enqueue) * 1e3)
            if profiler.is_running():
                # true coalescing latency (oldest enqueue -> dispatch),
                # NOT a span around the blocking take(), which would be
                # dominated by idle queue-wait on a quiet engine
                profiler.counter("serve.coalesce_ms",
                                 (time.monotonic()
                                  - reqs[0].t_enqueue) * 1e3)
            try:
                if self._multi:
                    self._route(reqs, t_pop)
                else:
                    self._dispatch(reqs, t_pop)
            except Exception as e:         # fail the batch, keep serving
                self._fail_batch(reqs, e)

    @staticmethod
    def _fail_batch(reqs, e):
        for r in reqs:
            if not r.future.done():
                _fail_future(r.future, e)
                if r.trace is not None:
                    r.trace.abort(type(e).__name__)
            elif r.trace is not None:
                # delivered before the batch blew up mid-
                # scatter: close the trace as-is, NOT 'failed'
                r.trace.finish()

    # ------------------------------------------------------------- replicas

    # batches a replica may hold past admission (1 dispatching + 1
    # staged): the router BLOCKS beyond this, so under overload the
    # backlog stays in the admission queue where max_queue
    # backpressure, shed-oldest, and the deadline sweep all still
    # apply — an unbounded pending queue would silently disable all
    # three (the single-replica worker holds exactly one popped batch,
    # and this keeps the multi-replica pop-to-dispatch window the same
    # order of magnitude)
    _MAX_REPLICA_INFLIGHT = 2

    def _route(self, reqs, t_pop):
        """Hand one formed batch to the least-loaded healthy replica
        (emptiest in-flight queue; index breaks ties so an idle fleet
        fills deterministically), blocking while every healthy replica
        is at its in-flight cap.  Raises when every replica is
        unhealthy (the caller fails the batch and the coalescer keeps
        serving — a dead fleet fails fast instead of wedging the
        queue) or when the engine is stopping (replica threads may
        already have drained and exited; an appended batch would
        strand its futures)."""
        with self._route_lock:
            while True:
                live = [r for r in self._replicas
                        if r.healthy and r.accepting]
                if not live:
                    if any(not r.healthy for r in self._replicas):
                        raise MXNetError(
                            "all %d serving replicas are unhealthy "
                            "(dispatch failures drained them); build "
                            "a new engine" % len(self._replicas))
                    raise EngineClosedError(
                        "engine closed before dispatch")
                r = min(live, key=lambda r: (r.inflight(), r.index))
                if r.inflight() < self._MAX_REPLICA_INFLIGHT:
                    break
                self._route_cond.wait(0.05)
            # appended under the same lock the replica thread's exit
            # check holds: an accepting replica is guaranteed to drain
            # this batch before it exits
            r.pending.append((reqs, t_pop))
            self._route_cond.notify_all()

    def _replica_run(self, r):
        """One replica's dispatch loop: drain routed batches against
        this replica's device-resident program cache.  A dispatch that
        raises fails ITS batch and retires the replica (unhealthy +
        drained, queued batches re-routed) — co-resident replicas keep
        serving."""
        while True:
            with self._route_lock:
                while not r.pending and not self._replicas_stop \
                        and r.healthy:
                    self._route_cond.wait(0.05)
                if r.pending:
                    reqs, t_pop = r.pending.popleft()
                    r.in_dispatch = True
                else:
                    # stopped or retired, drained: refuse further
                    # routing ATOMICALLY with the exit decision — the
                    # router must never hand work to a dead thread
                    r.accepting = False
                    return
            r.hb_t = time.monotonic()
            try:
                self._dispatch(reqs, t_pop, r)
            except Exception as e:
                self._fail_batch(reqs, e)
                self._replica_failed(r, e)
            finally:
                with self._route_lock:
                    r.in_dispatch = False
                    # a capped router may be waiting for this slot
                    self._route_cond.notify_all()
                r.hb_t = time.monotonic()

    def _replica_failed(self, r, exc):
        """Retire one replica after a failed dispatch: mark unhealthy,
        drain its queue back through the router, dump a flight bundle
        while the evidence is fresh.  The failed batch itself was
        already failed by the caller — one-shot requests have no
        partial output to salvage."""
        with self._route_lock:
            first = r.healthy
            r.healthy = False
            r.failures += 1
            orphans = list(r.pending)
            r.pending.clear()
            stopping = self._replicas_stop
            self._route_cond.notify_all()
        if first:
            warnings.warn(
                "serving replica %d (%s) retired after a dispatch "
                "failure (%r); traffic re-routed to %d sibling(s)"
                % (r.index, r.ctx if r.ctx is not None else "cpu(0)",
                   exc, sum(1 for x in self._replicas if x.healthy)))
            if r.tm_failures is not None:
                r.tm_failures.inc()
            if self._tl is not None:
                self._tl.instant("serve.replica_failed", "serve",
                                 "replica:%d" % r.index,
                                 args={"error": repr(exc)})
            fr = _telemetry.recorder.flight_recorder()
            if fr is not None:
                fr.dump("replica_failed:%s:%s"
                        % (self._obs_name or "serve", r.label),
                        detail={"replica": r.describe(),
                                "error": repr(exc)})
        for reqs, t_pop in orphans:
            if stopping:
                # sibling dispatch threads may already have drained and
                # exited — a re-routed batch would strand its futures
                # forever; fail it with the original error instead
                self._fail_batch(reqs, exc)
                continue
            try:
                self._route(reqs, t_pop)
            except Exception as e2:
                self._fail_batch(reqs, e2)

    def rehabilitate(self, replicas=None):
        """Replica probation/re-warm (ROADMAP follow-up a2): give every
        retired replica a path back into service instead of permanent
        retirement.  Each unhealthy replica gets a FRESH program cache
        (its old one may hold poisoned state), a probation warmup over
        every bucket signature the fleet has served — drawn from the
        persistent AOT cache when one is configured, so re-entry costs
        zero traces — and ONE probe batch that must match a healthy
        sibling's output bitwise before the replica takes traffic
        again.  A replica that fails any stage stays retired.

        ``replicas`` restricts probation to those replica indices (the
        supervisor rehabs one due replica at a time; None = every
        unhealthy replica, the operator verb).

        Returns one outcome dict per attempted replica:
        ``{"replica", "ok", "reason", "warmed"}``.
        """
        if self._adm.closed:
            raise EngineClosedError("serving engine is closed")
        want = None if replicas is None else {int(i) for i in replicas}
        return [self._rehabilitate_one(r) for r in self._replicas
                if not r.healthy and (want is None or r.index in want)]

    def _rehabilitate_one(self, r):
        out = {"replica": r.label, "ok": False, "reason": None,
               "warmed": 0}
        with self._route_lock:
            sib = next((x for x in self._replicas
                        if x.healthy and x is not r), None)
            keys = set()
            for x in self._replicas:
                keys |= x.dispatched_keys
            sib_keys = set(sib.dispatched_keys) if sib is not None \
                else set()
        if sib is None:
            out["reason"] = ("no healthy sibling to probe against; "
                             "build a new engine")
            return out
        c = self._ctor
        try:
            cache = ProgramCache(self._serve_sym, c["arg_params"],
                                 c["aux_params"], c["data_names"],
                                 ctx=r.ctx, dtype=self._dtype,
                                 aot=self._aot, plan=r.plan,
                                 program="mx_serve_batch")
            probe_key = None
            for key in sorted(keys):
                feeds = {name: np.zeros(shape,
                                        np.float32 if name ==
                                        self._valid_name
                                        else self._dtype)
                         for name, shape in key}
                cache.run(feeds)
                out["warmed"] += 1
                # probe on a key the SIBLING has already dispatched:
                # the reference dispatch below must never inject a
                # synchronous compile into a live serving replica
                if probe_key is None and (key in sib_keys
                                          or not sib_keys):
                    probe_key = key
            if probe_key is None:
                # fleet never dispatched: probe the smallest bucket
                # (the one-off compile lands on an idle engine)
                probe_key = tuple(sorted(
                    (name, (1,) + ex)
                    for name, ex in self._data_shapes.items()))
                if self._valid_name is not None:
                    probe_key += ((self._valid_name, (1,)),)
            # the probation gate: same compiled-program contract the
            # replica fleet already serves under — one probe batch,
            # bitwise against a live sibling, or no traffic.  The rng
            # key is pinned so stochastic graphs probe
            # deterministically (two caches' own key streams never
            # agree; see StepProgram.probe_step for the decode analog)
            import jax
            pk = jax.random.PRNGKey(0)
            probe_feeds = self._probe_feeds(probe_key)
            want = sib.cache.run(probe_feeds, _record=False,
                                 _fixed_key=pk)
            got = cache.run(probe_feeds, _record=False, _fixed_key=pk)
            if not (len(want) == len(got)
                    and all(np.array_equal(a, b, equal_nan=True)
                            for a, b in zip(want, got))):
                out["reason"] = ("probe batch diverged bitwise from "
                                 "healthy replica %s" % sib.label)
                return out
        except Exception as e:
            out["reason"] = repr(e)
            return out
        with self._route_lock:
            r.cache = cache
            if r is self._replicas[0]:
                # keep the single-replica alias honest: stats()'s
                # bucket_keys reads through it, and holding the old
                # poisoned cache alive would also pin its device
                # buffers
                self._cache = cache
            r.dispatched_keys = set(keys)
            r.pending.clear()
            r.in_dispatch = False
            r.healthy = True
            r.accepting = True
            r.thread = None
            r.probations += 1
            self._route_cond.notify_all()
        self._ensure_replica_threads()
        warnings.warn(
            "serving replica %d (%s) rehabilitated after probation: "
            "%d bucket program(s) re-warmed, probe batch bitwise-equal "
            "to replica %s" % (r.index,
                               r.ctx if r.ctx is not None else "cpu(0)",
                               out["warmed"], sib.label))
        out["ok"] = True
        return out

    def _probe_feeds(self, key):
        """Deterministic NON-degenerate probe batch for one bucket
        signature.  All-zero feeds would be useless as a probe: a
        zero-bias model maps zeros to the same output whatever its
        weights, so a rehab candidate rebuilt from wrong params would
        pass.  Small integer values (0,1,2 cycling) excite the weights
        while staying legal for id-valued inputs (Embedding rows); a
        repaired graph's valid-length vector is set to each input's
        full live extent so the spliced masks keep every probe row
        live."""
        feeds = {}
        for name, shape in key:
            if name == self._valid_name:
                continue
            n = int(np.prod(shape)) if len(shape) else 1
            feeds[name] = (np.arange(n) % 3).astype(
                self._dtype).reshape(shape)
        if self._valid_name is not None:
            shapes = dict(key)
            b = shapes[self._valid_name][0]
            name, ax = next(iter(sorted(self._length_sources.items())))
            ext = shapes[name][1 + ax]
            feeds[self._valid_name] = pad_valid_lengths([ext] * b, b)
        return feeds

    def _dispatch(self, reqs, t_pop=None, replica=None):
        tm = self._tm
        rep = replica if replica is not None else self._replicas[0]
        t_pop = time.perf_counter() if t_pop is None else t_pop
        # claim every future up front: a claimed (RUNNING) future can no
        # longer be cancel()ed out from under the scatter, and requests
        # the client already cancelled drop out of the batch here
        live = []
        for r in reqs:
            if r.future.set_running_or_notify_cancel():
                live.append(r)
            elif r.trace is not None:
                r.trace.abort("cancelled")
        reqs = live
        if not reqs:
            return
        n = len(reqs)
        b = self._policy.batch_bucket(n)
        group = dict(reqs[0].group)
        t_pad0 = time.perf_counter()
        feeds = {}
        live_elems = 0
        for name, ex_shape in group.items():
            arr = np.zeros((b,) + ex_shape, dtype=self._dtype)
            for i, r in enumerate(reqs):
                x = r.inputs[name]
                arr[(i,) + tuple(slice(0, d) for d in x.shape)] = x
                live_elems += x.size
            feeds[name] = arr
        padded_elems = sum(arr.size for arr in feeds.values())
        if self._valid_name is not None:
            # repaired graph: feed each request's live length so the
            # spliced masks neutralize exactly the pad slots (pad rows
            # carry 0 -> fully masked).  Always float32 — the model
            # dtype must not round lengths (float16 cannot represent
            # 2049), and the spliced variable declares float32
            feeds[self._valid_name] = pad_valid_lengths(
                [self._live_length(r) for r in reqs], b)
        if _faults.ACTIVE:
            # chaos seam: a raise here rides the REAL failure path —
            # multi-replica dispatch threads retire the replica and
            # re-route its queue; the single-replica worker fails the
            # batch and keeps serving
            _faults.trip("serve.dispatch", replica=rep.label)
        c0 = rep.cache.compile_count
        # one span: the ring's serve.dispatch event, mx:serve.dispatch
        # in a profiler trace, mx.profiler's serve.dispatch[...] region
        with _telemetry.timeline.span(
                "serve.dispatch", "serve", "replica:%d" % rep.index,
                chrome=("serve.dispatch[b=%d,n=%d,r=%d]"
                        % (b, n, rep.index) if self._multi else
                        "serve.dispatch[b=%d,n=%d]" % (b, n), "serve"),
                tl=self._tl) as sp:
            if self._pad_check:
                outs = self._pad_probe(feeds, reqs, rep)
            else:
                outs = rep.cache.run(feeds)
            sp.args = {"bucket": b, "live": n,
                       "compiled": rep.cache.compile_count - c0}
        t_disp0, t_disp1 = sp.t0, sp.t1
        compiled = self._count_compiles(c0, feeds, rep)
        now = time.monotonic()
        # scatter first: unblock the waiting clients before doing any
        # stats bookkeeping (closed-loop clients resubmit ~0.1 ms
        # sooner) — trace assembly included, so a traced request at
        # slot 0 cannot delay slots 1..n-1's set_result
        traced = []
        for i, r in enumerate(reqs):
            t_u0 = time.perf_counter() if r.trace is not None else 0.0
            res = [self._unpad(o[i], r, j) for j, o in enumerate(outs)]
            r.future.set_result(res if len(res) > 1 else res[0])
            if r.trace is not None:
                traced.append((r, t_u0, time.perf_counter()))
        for r, t_u0, t_u1 in traced:
            self._finish_trace(r, t_pop, t_pad0, t_disp0, t_disp1,
                               t_u0, t_u1, b, n, compiled)
        with self._lock:
            self._batches += 1
            self._requests_served += n
            self._occupancy_sum += n / float(b)
            for r in reqs:
                self._lat_ms.append((now - r.t_enqueue) * 1e3)
        rep.batches += 1
        if tm is not None:
            tm.batches.inc()
            rep.tm_batches.inc()
            rep.tm_occupancy.observe(n / float(b))
            rep.tm_dispatch.observe((t_disp1 - t_disp0) * 1e3)
            for r in reqs:
                tm.latency.observe((now - r.t_enqueue) * 1e3)
            bucket = str(b)
            tm.padded_elems.labels(bucket=bucket).inc(padded_elems)
            tm.live_elems.labels(bucket=bucket).inc(live_elems)
            if padded_elems:
                tm.pad_waste.labels(bucket=bucket).observe(
                    1.0 - live_elems / float(padded_elems))
        tl = self._tl
        if tl is not None:
            lane = "replica:%d" % rep.index
            tl.counter("serve.batch_occupancy", "serve", lane,
                       n / float(b))
            tl.counter("serve.queue_depth", "serve", "serve",
                       len(self._adm))
        eff = self._eff
        if eff is not None:
            # FLOPs ledger: the program was priced once at plan build
            # (ProgramCache._plan_for); this dispatch splits its price
            # into useful (live elements' floor-share) + padding, then
            # attributes each tenant-labeled request its live-element
            # share of the useful half
            shape_key = tuple(sorted((k, v.shape)
                              for k, v in feeds.items()))
            useful = eff.record_batch(rep.label,
                                      rep.cache.flops_for(shape_key),
                                      live_elems, padded_elems)
            if useful:
                for r in reqs:
                    if r.tenant is not None and live_elems:
                        r_elems = sum(x.size for x in r.inputs.values())
                        eff.tenant_useful(
                            r.tenant, useful * r_elems // live_elems)
        if profiler.is_running():
            profiler.counter("serve.batch_occupancy", n / float(b))

    def _count_compiles(self, c0, feeds, rep):
        """Attribute XLA traces observed during one dispatch: every
        trace counts as a compile; a trace on a bucket signature THIS
        REPLICA already dispatched (or any trace once warmup ran) is a
        RETRACE — the compile-once contract broken at runtime — and is
        counted under the engine's static hazard fingerprints, per
        replica (each replica owns its own program cache, so a
        signature warm on replica 0 is a legitimate cold compile on
        replica 1).  The engine-side bookkeeping
        (``stats()['retraces']``) always runs — a compile storm must
        be visible even with the registry disabled; only the
        instrument writes gate on the bundle."""
        tm = self._tm
        compiled = rep.cache.compile_count - c0
        key = tuple(sorted((k, v.shape) for k, v in feeds.items()))
        if compiled:
            if tm is not None:
                tm.compiles.inc(compiled)
            # retrace = a compile on a signature ALREADY dispatched
            # (warmup seeds the set).  A first-sight signature is a
            # legitimate cold compile even post-warmup: exact-length
            # seq mode (cross-position graphs degrade to one program
            # per length) compiles new lengths by design.
            if key in rep.dispatched_keys:
                self._retraces += compiled
                if tm is not None:
                    rep.tm_retraces.inc(compiled)
        rep.dispatched_keys.add(key)
        return compiled

    def _finish_trace(self, r, t_pop, t_pad0, t_disp0, t_disp1, t_u0,
                      t_u1, b, n, compiled):
        """Finish one request's trace: batch-stage intervals were
        measured once per batch and are attributed to every member
        request.  Span assembly is DEFERRED behind the retention
        verdict — with every request traced, the dropped majority must
        pay only for the keep/drop decision, never for building a span
        tree nobody will read.  Runs AFTER the scatter loop — store
        inserts and the profiler-ring bridge must not sit between two
        clients' set_result calls."""
        def build(tc):
            tc.add("queue-wait", tc.root.t0, t_pop, "serve")
            tc.add("coalesce", t_pop, t_pad0, "serve",
                   meta={"batch": n})
            tc.add("pad", t_pad0, t_disp0, "serve", meta={"bucket": b})
            dsp = tc.add("dispatch", t_disp0, t_disp1, "serve",
                         meta={"bucket": b, "live": n,
                               "compiled": bool(compiled)})
            if compiled:
                sp = _telemetry.Span("compile", "serve", t0=t_disp0)
                sp.t1 = t_disp1
                sp.meta = {"programs": compiled}
                dsp.children.append(sp)
            tc.add("unpad", t_u0, t_u1, "serve")

        r.trace.finish(t_u1, build=build)

    def _live_length(self, req):
        """One request's live extent along the repaired axis, read off
        its unpadded inputs.  Every input the repaired label pads must
        agree — they share the one padded source axis the masks
        neutralize; disagreement would silently mask the wrong slots,
        so it fails the batch instead."""
        lengths = {req.inputs[n].shape[ax]
                   for n, ax in self._length_sources.items()}
        if len(lengths) != 1:
            raise MXNetError(
                "repaired-graph dispatch needs ONE live length per "
                "request, but the padded inputs disagree along the "
                "repaired axis: %s"
                % {n: req.inputs[n].shape[ax]
                   for n, ax in sorted(self._length_sources.items())})
        return lengths.pop()

    def _pad_probe(self, feeds, reqs, rep=None):
        """MXNET_SERVE_PAD_CHECK: dispatch twice via the ProgramCache
        probe hook and require bitwise-equal live regions (see
        buckets.ProgramCache.run_pad_probe).  Debug knob — doubles
        dispatch cost, compiles nothing extra."""
        cache = (rep.cache if rep is not None
                 else self._replicas[0].cache)
        live_masks = {}
        for name, arr in feeds.items():
            mask = np.zeros(arr.shape, dtype=bool)
            if name == self._valid_name:
                # the lengths vector's live slots are the first n rows;
                # perturbing its PAD entries scrambles only pad-row
                # masks, which a sound repair keeps out of live rows
                mask[:len(reqs)] = True
            else:
                for i, r in enumerate(reqs):
                    x = r.inputs[name]
                    mask[(i,) + tuple(slice(0, d) for d in x.shape)] = True
            live_masks[name] = mask
        base, probed = cache.run_pad_probe(feeds, live_masks)
        for j, (o0, o1) in enumerate(zip(base, probed)):
            for i, r in enumerate(reqs):
                a = self._unpad(o0[i], r, j)
                bb = self._unpad(o1[i], r, j)
                if not np.array_equal(a, bb, equal_nan=True):
                    raise MXNetError(
                        "padding contamination detected at runtime: "
                        "output %d of request %d changed when pad "
                        "slots were perturbed — the graph is "
                        "cross-position along a padded axis.  Run "
                        "`tools/graph_lint.py --passes padding` for "
                        "the offending node" % (j, i))
        return base

    def _unpad(self, row, req, j):
        """Slice output ``j``'s row back to the shape the graph infers
        at the request's UNPADDED input (row-independent models).  An
        output whose inferred shape is pad-invariant — even one whose
        axis size coincides with the pad length — passes through."""
        if req.out_rows is None:
            return row
        want = req.out_rows[j]
        if row.shape == want:
            return row
        return row[tuple(slice(0, d) for d in want)]

    # ------------------------------------------------------------- observe
    def warmup(self):
        """Compile every configured bucket program up front (one dummy
        dispatch per batch-bucket × seq-bucket combination) so live
        traffic never pays a trace.  Returns the compile count."""
        seq_shapes = [self._data_shapes]
        if self._policy.seq_axis is not None and self._policy.seq_buckets:
            seq_shapes = []
            for sb in self._policy.seq_buckets:
                shapes = {}
                for name, ex in self._data_shapes.items():
                    s = list(ex)
                    s[self._policy.seq_axis] = sb
                    shapes[name] = tuple(s)
                seq_shapes.append(shapes)
        c0 = self.compile_count
        for shapes in seq_shapes:
            for bb in self._policy.batch_buckets():
                feeds = {name: np.zeros((bb,) + ex, dtype=self._dtype)
                         for name, ex in shapes.items()}
                if self._valid_name is not None:
                    # all-pad lengths: the compiled program is the
                    # same; the outputs are discarded
                    feeds[self._valid_name] = pad_valid_lengths([], bb)
                key = tuple(sorted(
                    (k, v.shape) for k, v in feeds.items()))
                # every replica compiles its own program per bucket —
                # live traffic must never pay a trace whichever
                # replica the router picks
                for rep in self._replicas:
                    with _telemetry.timeline.span(
                            "serve.warmup", "serve",
                            "replica:%d" % rep.index,
                            args={"bucket": bb},
                            chrome=("serve.warmup[b=%d]" % bb, "serve"),
                            tl=self._tl):
                        rep.cache.run(feeds)
                    rep.dispatched_keys.add(key)
                    with self._lock:
                        self._warmup_batches += 1
        if self._tm is not None:
            self._tm.compiles.inc(self.compile_count - c0)
        return self.compile_count

    @property
    def compile_count(self):
        """XLA traces across every replica's program cache."""
        return sum(r.cache.compile_count for r in self._replicas)

    def stats(self):
        """Point-in-time snapshot of engine health: admission counters
        (queue depth + cumulative rejected/shed/expired — the same
        numbers the mxnet_serve_* telemetry gauges/counters carry),
        dispatch/occupancy aggregates, program-cache traffic, retrace
        count, the construction-time repair outcome (``repairs``:
        actions applied / rejection reason / the valid-length input a
        repaired graph is fed), the optimizer outcome (``optimizer``:
        rewrites adopted or thrown away, node counts before/after —
        the same numbers the ``mxnet_serve_opt_*`` counters carry),
        and request latency percentiles (ms)
        over the last ≤4096 completions.  An empty latency window
        reports zeros for every latency field, never NaN or an
        exception."""
        snap = self._adm.stats()
        # allocator peek outside the lock: device_memory_peak() can
        # stall on the backend, and a scrape must not block dispatch
        mem = _memory_stats_block(self.memory_plan)
        with self._lock:
            lat = sorted(self._lat_ms)
            snap.update({
                "batches": self._batches,
                "warmup_batches": self._warmup_batches,
                "requests_served": self._requests_served,
                "batch_occupancy": (self._occupancy_sum / self._batches
                                    if self._batches else 0.0),
                "compile_count": self.compile_count,
                "retraces": self._retraces,
                "program_cache": {
                    "hits": sum(r.cache.plan_hits
                                for r in self._replicas),
                    "misses": sum(r.cache.plan_misses
                                  for r in self._replicas)},
                "bucket_keys": len(self._cache.bucket_keys),
                "max_batch": self._policy.max_batch,
                "sharding": self._sharding_spec,
                "replicas": [r.describe() for r in self._replicas],
                "aot": (self._aot.stats() if self._aot is not None
                        else {"enabled": False}),
                "supervisor": _supervisor_state(self),
                "regulator": (self._regulator.stats()
                              if self._regulator is not None
                              else {"enabled": False}),
                "faults": _faults.stats(),
                "repairs": {
                    "applied": (len(self.repair_plan.actions)
                                if self.repair_plan is not None else 0),
                    "rejected": 1 if self._repair_rejected else 0,
                    "valid_length_input": self._valid_name,
                    "reason": self._repair_rejected,
                },
                "optimizer": {
                    "applied": (len(self.opt_plan.rewrites)
                                if self.opt_plan is not None
                                and self.opt_plan.accepted else 0),
                    "rejected": (len(self.opt_plan.rewrites)
                                 if self.opt_plan is not None
                                 and not self.opt_plan.accepted else 0),
                    "nodes_before": (self.opt_plan.nodes_before
                                     if self.opt_plan is not None
                                     else None),
                    "nodes_after": (self.opt_plan.nodes_after
                                    if self.opt_plan is not None
                                    else None),
                    "reason": (self.opt_plan.reason
                               if self.opt_plan is not None else None),
                },
                "memory": mem,
                "efficiency": (self._eff.stats_block()
                               if self._eff is not None
                               else {"enabled": False}),
                "latency_ms": {
                    "count": len(lat),
                    "mean": float(np.mean(lat)) if lat else 0.0,
                    "p50": _percentile(lat, 0.50),
                    "p99": _percentile(lat, 0.99),
                    # validates the tail-biased sampler: the traces it
                    # retains must cover the latencies up here
                    "p999": _percentile(lat, 0.999),
                },
            })
        return snap

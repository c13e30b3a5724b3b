"""Data-parallel replica routing for the serving tier (ROADMAP 2a).

The serving stack through PR 9 is production-shaped but single-device:
every coalesced batch and every decode step dispatches to ONE device
while ``parallel/mesh.py`` and N-1 devices of the mesh sit idle at
inference time.  This module is the bridge from "one fast device" to
fleet-scale serving — the pjit/NamedSharding *data-parallel* move
(SNIPPETS.md, PAPERS.md 2004.13336: shard over the dp axis) applied to
served traffic, with one twist: served batches are already small and
latency-bound, so instead of sharding one batch across devices, each
replica owns a whole dp-axis device (``parallel.mesh
.data_parallel_devices`` fixes the device order) and whole batches
route to the least-loaded replica:

- **one-shot** (:class:`~mxnet_tpu.serving.engine.ServingEngine`): the
  coalescer keeps forming batches exactly as before; each formed batch
  is handed to the replica with the emptiest in-flight queue, whose
  dispatch thread pads, runs its own device-resident
  :class:`~mxnet_tpu.serving.buckets.ProgramCache`, and scatters
  results — padding, device compute, and unpadding all overlap across
  replicas;
- **decode** (:class:`~mxnet_tpu.serving.decode.DecodeEngine`): each
  replica owns a full slot pool + persistent step program.  A new
  request lands on the replica with the most free slots and then PINS
  to it for its whole generation (per-slot state is device-resident —
  migrating a request would mean shipping its KV cache across
  devices); co-resident replicas step independently.

Every replica has its own compiled-program cache and its own
device-resident copy of the params (uploaded once per replica at
construction, shared across that replica's bucket programs — the
``Predictor.reshape`` no-re-upload discipline per device), so warm
traffic never moves weights and the compile-once contract holds per
replica.

**Failure handling**: a replica whose dispatch raises is marked
unhealthy and drained — its queued one-shot batches re-route to healthy
replicas, its seated decode requests are evicted with their PARTIAL
output (finish_reason ``"error"``), and the flight recorder
(``MXNET_FLIGHT_RECORDER_DIR``) dumps a post-mortem bundle on the
transition.  Traffic keeps flowing on the survivors; only when every
replica is unhealthy do new requests fail.

Observability: dispatch/occupancy/retrace series gain a ``replica``
label, ``mxnet_serve_replica_{healthy,inflight}`` gauges and
``mxnet_serve_replica_failures_total`` tell the router's story per
scrape, ``GET /healthz`` carries a per-replica block, and
``tools/telemetry_dump.py healthz`` renders it.

Config: ``MXNET_SERVE_REPLICAS`` (default 1 — the single-device fast
path, byte-for-byte the pre-replica engines).
"""
from __future__ import annotations

import collections
import time

from ..base import MXNetError
from .join_policy import JoinState

__all__ = ["replica_contexts", "resolve_replica_placements",
           "ServeReplica", "DecodeReplica", "replica_metric_families"]


def replica_metric_families(reg):
    """Register (idempotently) the replica-plane metric families BOTH
    engine kinds share — one definition, so the help text and label
    sets cannot drift between the serving and decode bundles.  Returns
    ``(replicas, healthy, inflight, failures, shards)`` families;
    engine ordinals are process-unique, so the shared families
    aggregate into one fleet view per scrape."""
    replicas = reg.gauge(
        "mxnet_serve_replicas",
        "configured device replicas per engine",
        labelnames=("engine",))
    healthy = reg.gauge(
        "mxnet_serve_replica_healthy",
        "1 while a device replica serves traffic, 0 once a failed "
        "dispatch drained it (traffic re-routed to its siblings)",
        labelnames=("engine", "replica"))
    inflight = reg.gauge(
        "mxnet_serve_replica_inflight",
        "in-flight work per device replica (one-shot: routed "
        "batches queued or dispatching; decode: occupied slots + "
        "routed requests) — the least-loaded routing signal",
        labelnames=("engine", "replica"))
    failures = reg.counter(
        "mxnet_serve_replica_failures_total",
        "dispatch failures that drained a device replica and "
        "marked it unhealthy (the flight recorder dumps on each)",
        labelnames=("engine", "replica"))
    shards = reg.gauge(
        "mxnet_serve_replica_shards",
        "mesh devices one replica's programs span (1 = single-device; "
        ">1 = a pjit ShardingPlan partitions the replica's params/"
        "state across its device group) — the per-shard identity "
        "rides the existing replica label, so a straggling shard "
        "shows up as its replica's dispatch tail",
        labelnames=("engine", "replica"))
    return replicas, healthy, inflight, failures, shards


def _context_for_device(dev):
    """Map one jax device back onto the Context vocabulary the
    ProgramCache/StepProgram ``ctx`` argument speaks."""
    import jax
    from ..context import Context
    plat = getattr(dev, "platform", "cpu")
    kind = {"cpu": "cpu", "tpu": "tpu"}.get(plat, "gpu")
    try:
        idx = jax.local_devices(backend=plat).index(dev)
    except (RuntimeError, ValueError):
        idx = getattr(dev, "id", 0)
    return Context(kind, idx)


def replica_contexts(replicas=None, ctx=None):
    """Resolve an engine's ``(replicas, ctx)`` arguments into the
    per-replica Context list.

    - ``ctx`` a list/tuple of Contexts: that IS the replica set
      (``replicas``, if also given, must agree) — how tests run two
      replicas on one device without forcing a host device count;
    - ``replicas`` explicit int > available devices: raises — a bench
      must not silently measure fewer replicas than it claims;
    - ``replicas`` unset: ``MXNET_SERVE_REPLICAS`` decides, clamped to
      the addressable device count with a warning (a fleet-wide env
      default must not break the one-device dev box);
    - the default single-replica case returns ``[ctx]`` untouched
      (possibly ``[None]``) so the engine's fast path stays
      byte-for-byte the pre-replica one, with zero jax device
      enumeration at construction.

    Multi-replica device order comes from
    :func:`mxnet_tpu.parallel.mesh.data_parallel_devices` — replica i
    is dp rank i.
    """
    from .. import config
    from ..context import Context
    if isinstance(ctx, (list, tuple)):
        if not ctx:
            raise MXNetError("replica ctx list is empty")
        ctxs = [Context(c) for c in ctx]
        if replicas is not None and int(replicas) != len(ctxs):
            raise MXNetError(
                "replicas=%d disagrees with the %d-entry ctx list"
                % (int(replicas), len(ctxs)))
        return ctxs
    explicit = replicas is not None
    if replicas is None:
        replicas = config.get("MXNET_SERVE_REPLICAS")
    replicas = int(replicas)
    if replicas < 1:
        raise MXNetError("replicas must be >= 1, got %d" % replicas)
    if replicas == 1:
        return [ctx]
    from ..parallel.mesh import data_parallel_devices
    try:
        devs = data_parallel_devices(replicas)
    except MXNetError:
        if explicit:
            raise
        import warnings
        devs = data_parallel_devices()
        warnings.warn(
            "MXNET_SERVE_REPLICAS=%d but only %d addressable device(s) "
            "exist; clamping to %d replica(s) "
            "(XLA_FLAGS=--xla_force_host_platform_device_count=N "
            "forces a CPU host to expose N)"
            % (replicas, len(devs), len(devs)))
    if ctx is not None:
        # a single explicit ctx pins replica 0's device; the rest
        # follow the dp order (skipping the pinned device's duplicate)
        base = Context(ctx)
        rest = [d for d in devs if _context_for_device(d) != base]
        return ([base] + [_context_for_device(d) for d in rest])[:len(devs)]
    return [_context_for_device(d) for d in devs]


def resolve_replica_placements(replicas, ctx, sharding):
    """Resolve an engine's ``(replicas, ctx, sharding)`` arguments into
    per-replica ``(Context, ShardingPlan-or-None)`` placements.

    With ``sharding=None`` this is exactly :func:`replica_contexts` —
    single-device replicas, the pre-sharding engines byte-for-byte.
    With a plan spec (dict / JSON / :class:`ShardingPlan`), each
    replica owns a contiguous GROUP of ``prod(axes)`` devices in the
    dp order (``parallel.mesh.replica_device_groups``), and its plan
    is the spec instantiated over that group: N replicas x G-device
    plans composes data-parallel with model-parallel on the same
    router/failover machinery.  Sharded placement is always explicit:
    too few devices raises (never a silent clamp), and a ``ctx``
    argument is refused — the plan owns device placement."""
    if sharding is None:
        return [(c, None) for c in replica_contexts(replicas, ctx)]
    from ..parallel.mesh import (ShardingPlan, normalize_plan_spec,
                                 plan_group_size, replica_device_groups)
    if ctx is not None:
        raise MXNetError(
            "ctx and a sharding plan are mutually exclusive: the plan "
            "owns device placement (pass replicas=N; replica i takes "
            "the i-th device group in dp order)")
    from .. import config
    if replicas is None:
        replicas = config.get("MXNET_SERVE_REPLICAS")
    replicas = int(replicas)
    if replicas < 1:
        raise MXNetError("replicas must be >= 1, got %d" % replicas)
    spec = normalize_plan_spec(sharding)
    groups = replica_device_groups(replicas, plan_group_size(spec))
    return [(_context_for_device(grp[0]),
             ShardingPlan.from_spec(spec, devices=grp))
            for grp in groups]


class ServeReplica(object):
    """One one-shot-engine device replica: its own
    :class:`~mxnet_tpu.serving.buckets.ProgramCache` (params
    device-resident on ``ctx``), an in-flight batch queue its dispatch
    thread drains, and health/throughput bookkeeping.

    Mutation discipline: ``pending``/``in_dispatch``/``healthy`` are
    guarded by the engine's router lock; ``dispatched_keys``/
    ``batches``/``hb_t`` are touched only by the thread currently
    dispatching on this replica (the engine worker itself on the
    single-replica fast path).
    """
    __slots__ = ("index", "label", "ctx", "plan", "cache", "healthy",
                 "accepting", "pending",
                 "in_dispatch", "dispatched_keys", "batches", "failures",
                 "probations", "hb_t", "thread", "tm_dispatch",
                 "tm_occupancy", "tm_retraces", "tm_batches",
                 "tm_failures")

    def __init__(self, index, ctx, cache, plan=None):
        self.index = index
        self.label = str(index)
        self.ctx = ctx
        # ShardingPlan when this replica's programs span a device
        # GROUP (model-parallel serving); None = single-device replica
        self.plan = plan
        self.cache = cache
        self.healthy = True
        # times this replica re-entered service through the probation
        # warmup + bitwise probe gate (engine.rehabilitate) after a
        # dispatch failure retired it
        self.probations = 0
        # flipped False UNDER the engine's router lock the moment this
        # replica's thread decides to exit — the router must never
        # append work a dead thread will not drain (is_alive() has a
        # decided-to-exit-but-still-alive window; this flag does not)
        self.accepting = True
        self.pending = collections.deque()      # (reqs, t_pop) batches
        self.in_dispatch = False
        self.dispatched_keys = set()            # per-replica: retrace
        #                                         accounting is per cache
        self.batches = 0
        self.failures = 0
        self.hb_t = time.monotonic()
        self.thread = None
        # bound telemetry children (None with telemetry off) — resolved
        # once at engine construction so the dispatch hot path never
        # pays a labels() registry probe
        self.tm_dispatch = None
        self.tm_occupancy = None
        self.tm_retraces = None
        self.tm_batches = None
        self.tm_failures = None

    def inflight(self):
        """Routed-but-unfinished batches — the router's load signal."""
        return len(self.pending) + (1 if self.in_dispatch else 0)

    def describe(self):
        out = {"replica": self.label,
               "ctx": str(self.ctx) if self.ctx is not None else "cpu(0)",
               "healthy": self.healthy,
               "inflight": self.inflight(),
               "batches": self.batches,
               "failures": self.failures,
               "probations": self.probations,
               "compile_count": self.cache.compile_count}
        out.update(_shard_identity(self.plan))
        return out


def _shard_identity(plan):
    """The per-shard identity block a sharded replica's describe()/
    healthz rows carry under the existing replica label."""
    if plan is None:
        return {"shards": 1}
    return {"shards": len(plan.devices()),
            "shard_devices": [str(d) for d in plan.devices()],
            "sharding": plan.digest()}


class DecodeReplica(object):
    """One decode-engine device replica: a full slot pool (persistent
    step program + device-resident per-slot state + host mirror
    vectors), the pending queue of requests routed-but-not-yet-seated,
    and health bookkeeping.  Slot state is touched only by the
    replica's scheduler thread (the engine worker itself on the
    single-replica fast path); ``pending``/``healthy`` are guarded by
    the engine's router lock.
    """
    __slots__ = ("index", "label", "ctx", "plan", "program",
                 "prefill_caches",
                 "prefill_buckets", "slots", "tokens_np", "pos_np",
                 "valid_np", "reset_np", "spec_np", "states", "flight",
                 "joins", "pending", "healthy",
                 "accepting", "in_step", "probations", "hb_t", "thread",
                 "tm_step_ms", "tm_failures")

    def __init__(self, index, ctx, program, plan=None):
        import numpy as np
        self.index = index
        self.label = str(index)
        self.ctx = ctx
        self.plan = plan
        self.program = program
        # probation re-entries (DecodeEngine.rehabilitate)
        self.probations = 0
        # see ServeReplica.accepting: flipped False under the engine's
        # router lock when this replica's scheduler thread exits
        self.accepting = True
        self.prefill_caches = {}
        self.prefill_buckets = ()
        n = program.num_slots
        self.slots = [None] * n
        self.tokens_np = np.zeros((n,), np.float32)
        self.pos_np = np.zeros((n,), np.float32)
        self.valid_np = np.zeros((n,), np.float32)
        self.reset_np = np.zeros((n,), np.float32)
        # speculative eligibility mask (ISSUE 15): 1 while a slot is
        # generating past its prompt — ineligible slots commit exactly
        # one position per spec step.  Allocated unconditionally (one
        # float per slot); non-spec programs never read it.
        self.spec_np = np.zeros((n,), np.float32)
        self.states = program.init_states()
        # the plain loop keeps one step in flight (decode.py
        # ``_step_body``): the dispatched step whose ids are unread, as
        # ``(PendingStep, who sat where at its dispatch, whether it went
        # out ahead of a read)``, or None.  Its output buffer feeds the
        # slots that generate
        self.flight = None
        # what this pool's scheduler has observed for the decision when
        # a join waits (join_policy.py)
        self.joins = JoinState()
        self.pending = collections.deque()      # routed DecodeRequests
        self.healthy = True
        self.in_step = False
        self.hb_t = time.monotonic()
        self.thread = None
        self.tm_step_ms = None
        self.tm_failures = None

    def occupied(self):
        return [i for i, s in enumerate(self.slots) if s is not None]

    def occupied_count(self):
        return sum(1 for s in self.slots if s is not None)

    def free_slots(self):
        return self.program.num_slots - self.occupied_count()

    def assignable(self):
        """Free capacity the router may still promise: free slots minus
        requests already routed here but not yet seated."""
        return self.free_slots() - len(self.pending)

    def inflight(self):
        return self.occupied_count() + len(self.pending)

    def describe(self):
        out = {"replica": self.label,
               "ctx": str(self.ctx) if self.ctx is not None else "cpu(0)",
               "healthy": self.healthy,
               "slots": self.program.num_slots,
               "slots_occupied": self.occupied_count(),
               "pending": len(self.pending),
               "probations": self.probations,
               "compile_count": self.program.trace_count}
        out.update(_shard_identity(self.plan))
        return out

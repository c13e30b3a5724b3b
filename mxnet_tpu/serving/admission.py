"""Admission control for the serving engine.

Reference analog: the reference's engine has per-device bounded task
queues (threaded_engine_pooled.cc) but no request-level admission — a
serving runtime needs one.  This layer owns the *pending request* queue
that sits in front of the compiled-program dispatcher:

- **bounded queue / backpressure**: at most ``max_queue`` requests wait;
  beyond that ``admit`` either raises :class:`QueueFullError` (policy
  ``reject`` — push backpressure to the client) or evicts the oldest
  pending request (policy ``shed-oldest`` — graceful degradation under
  overload: old work is the least likely to still meet its deadline).
- **deadlines**: each request may carry an absolute expiry; a sweep runs
  on every queue interaction and inside the blocking ``take`` wait, so
  an expired request fails fast with :class:`DeadlineExceededError`
  instead of occupying a batch slot.
- **coalescing pop**: ``take`` blocks until work is available, honors a
  batching window measured from the oldest request's enqueue time, and
  returns the oldest request plus every queued request in the same
  shape *group* (set by the engine), oldest-first, up to ``max_batch``.

All state is guarded by one condition variable; producers are client
threads calling ``admit``, the single consumer is the engine worker.
"""
from __future__ import annotations

import collections
import threading
import time

from ..base import MXNetError
from . import faults as _faults
from .locks import named_condition

__all__ = ["AdmissionController", "Request", "QueueFullError",
           "DeadlineExceededError", "ServerOverloadError",
           "EngineClosedError"]


def _fail_future(fut, exc):
    """Deliver ``exc`` to a pending future, tolerating client-side
    ``cancel()``: a cancelled future has already delivered its outcome,
    and ``set_exception`` on it raises InvalidStateError — which must
    never propagate into the admission paths (it would kill the single
    worker thread or surface to an innocent submitter)."""
    if not fut.cancelled():
        try:
            fut.set_exception(exc)
        except Exception:       # lost a cancel() race — outcome delivered
            pass


class QueueFullError(MXNetError):
    """Raised to the submitting client when the bounded queue is full
    and the overload policy is ``reject`` (backpressure)."""


class DeadlineExceededError(MXNetError):
    """Set on a request's future when its deadline passed while the
    request was still queued."""


class ServerOverloadError(MXNetError):
    """Set on the future of a request shed under the ``shed-oldest``
    overload policy."""


class EngineClosedError(MXNetError):
    """Raised/set when submitting to (or draining of) a closed engine."""


class Request(object):
    """One pending inference request.

    ``inputs`` maps data-input name to a host ndarray (per-example, no
    batch dim).  ``group`` is the engine-computed coalescing key (padded
    per-example shapes after seq bucketing): only requests with equal
    groups share a dispatched batch.  ``out_rows`` holds the per-example
    output shapes the graph infers at the UNPADDED input, which the
    engine slices dispatched rows back to (None when seq bucketing is
    off).  ``trace`` optionally carries a
    :class:`~mxnet_tpu.telemetry.LazyTrace` (or an explicit
    ``TraceContext``) across the thread hop to the worker; retention —
    which requests yield a stored span tree — is decided at finish by
    the tail-biased sampler chain.

    ``cost`` is the request's padded-element price (the engine computes
    it from the bucket-padded group shapes; decode uses prompt +
    generation budget) — what the overload regulator's cost-aware
    shedding ranks by: under pressure the HIGHEST-cost queued request
    sheds first, buying the most queue drain per lost request.  None
    ranks as zero (raw Requests staged by tests keep working).

    ``on_expire`` generalizes deadline accounting beyond the original
    one-dispatch-per-request model: a MULTI-STEP request (continuous-
    batching decode, serving/decode.py — its deadline is re-checked on
    every scheduler iteration, queued or slot-resident) does not FAIL
    at its deadline, it *completes with whatever it has*.  When set,
    the expiry sweep calls ``on_expire(exc)`` and delivers the returned
    value as the future's RESULT (a partial output carrying an
    ``expired`` flag) instead of setting ``DeadlineExceededError``;
    returning ``None`` falls back to the exception.  One-shot requests
    leave it unset and keep the original fail-fast contract.

    ``tenant`` carries the RESOLVED per-tenant accounting label
    (telemetry/goodput.py: submit resolves the caller's tenant id onto
    the bounded label set once, so every downstream inc reuses the
    resolution).  None = unattributed (no tenant given, or the
    efficiency plane is off).
    """
    __slots__ = ("inputs", "group", "future", "t_enqueue", "t_submit",
                 "deadline", "out_rows", "trace", "on_expire", "cost",
                 "tenant")

    def __init__(self, inputs, group, future, deadline=None,
                 out_rows=None, trace=None, on_expire=None, cost=None,
                 tenant=None):
        self.inputs = inputs
        self.group = group
        self.future = future
        self.t_enqueue = time.monotonic()     # the deadlines' clock
        self.t_submit = time.perf_counter()   # the spans' clock
        self.deadline = deadline            # absolute time.monotonic()
        self.out_rows = out_rows
        self.trace = trace
        self.on_expire = on_expire
        self.cost = cost                    # padded elements (regulator)
        self.tenant = tenant                # resolved accounting label

    def expired(self, now=None):
        return self.deadline is not None and \
            (now if now is not None else time.monotonic()) >= self.deadline


class AdmissionController(object):
    def __init__(self, max_queue=256, overload_policy="reject",
                 sweep_interval=0.05, wake_hint=None, telemetry=None):
        if overload_policy not in ("reject", "shed-oldest", "shed_oldest"):
            raise MXNetError("unknown overload policy %r "
                             "(use 'reject' or 'shed-oldest')"
                             % (overload_policy,))
        self.max_queue = int(max_queue)
        self.overload_policy = overload_policy.replace("_", "-")
        self._sweep_interval = sweep_interval
        # GIL-churn control: with a wake_hint (the engine's max_batch),
        # admit only wakes the consumer when the queue STARTS (depth 1,
        # so the batching-window timer can run) or plausibly FILLS a
        # batch (depth >= hint); in between the consumer sleeps on its
        # own timed wait.  Cuts consumer wakeups from one-per-admit to
        # two-per-batch under bursty load.
        self._wake_hint = int(wake_hint) if wake_hint else None
        self._queue = collections.deque()
        # count of queued requests carrying a deadline, maintained at
        # every queue mutation: the expiry sweep runs on EVERY decode
        # scheduler iteration (sub-ms apart), and an O(queue) scan per
        # step to discover "nothing can expire" is pure hot-path waste
        self._n_deadlined = 0
        self._cond = named_condition("serve.admission")
        self._closed = False
        # monotonically increasing counters, guarded by _cond's lock
        self.admitted = 0
        self.rejected = 0
        self.shed = 0
        # regulator-pressure sheds, counted SEPARATELY from policy
        # sheds: the queue-saturation burn rule's numerator includes
        # mxnet_serve_shed_total, so regulator sheds feeding it would
        # be a positive feedback loop (shed -> burn -> tighten ->
        # shed) that ratchets the limit to the floor and never relaxes
        self.pressure_shed = 0
        self.expired = 0
        # optional telemetry bundle (engine._EngineTelemetry): the
        # registry mirrors of the counters above plus the queue-depth
        # gauge.  None when MXNET_TELEMETRY_ON=0 — the hot path then
        # makes zero instrument calls.  Instrument locks are leaves, so
        # updating them under _cond's lock cannot deadlock.
        self._telemetry = telemetry
        # overload-regulator pressure (serving/regulator.py): a
        # tightened effective queue limit below max_queue.  None =
        # unregulated — admit() then behaves byte-for-byte as before.
        self._pressure = None

    # ------------------------------------------------------------- producer
    def admit(self, req):
        """Enqueue a request or apply the overload policy.  Thread-safe;
        called from client threads."""
        if _faults.ACTIVE:
            # chaos seam (serving/faults.py): an admission stall
            # (hang) or front-door failure (raise) lands on the
            # SUBMITTING client, before any queue state changes
            _faults.trip("admission.admit")
        failures, reject = [], None
        tm = self._telemetry
        with self._cond:
            if self._closed:
                raise EngineClosedError("serving engine is closed")
            failures += self._sweep_locked()
            pressure = self._pressure
            if pressure is not None and len(self._queue) >= pressure \
                    and len(self._queue) < self.max_queue:
                # regulated overload below the hard bound: shed the
                # highest padded-element-cost request (the incoming
                # one included — if IT is the most expensive, reject
                # it rather than evict cheaper queued work)
                victim = max(list(self._queue) + [req],
                             key=self._cost_key)
                self.pressure_shed += 1
                if tm is not None:
                    tm.regulator_shed.inc()
                exc = ServerOverloadError(
                    "request shed by the overload regulator: queue at "
                    "the tightened limit (%d < max_queue %d) and this "
                    "is the highest-cost pending request"
                    % (pressure, self.max_queue))
                if victim is req:
                    reject = exc
                else:
                    self._queue.remove(victim)
                    if victim.deadline is not None:
                        self._n_deadlined -= 1
                    failures.append((victim, exc))
            elif len(self._queue) >= self.max_queue:
                if self.overload_policy == "shed-oldest":
                    victim = self._queue.popleft()
                    if victim.deadline is not None:
                        self._n_deadlined -= 1
                    self.shed += 1
                    if tm is not None:
                        tm.shed.inc()
                    failures.append((victim, ServerOverloadError(
                        "request shed after %.1f ms queued: queue full "
                        "(%d) under shed-oldest overload policy"
                        % ((time.monotonic() - victim.t_enqueue) * 1e3,
                           self.max_queue))))
                else:
                    self.rejected += 1
                    if tm is not None:
                        tm.rejected.inc()
                    reject = QueueFullError(
                        "serving queue full (%d pending): backpressure"
                        % self.max_queue)
            if reject is None:
                self._queue.append(req)
                if req.deadline is not None:
                    self._n_deadlined += 1
                self.admitted += 1
                if tm is not None:
                    tm.admitted.inc()
                if self._wake_hint is None or len(self._queue) == 1 \
                        or len(self._queue) >= self._wake_hint:
                    self._cond.notify()    # single consumer (the worker)
            if tm is not None:
                tm.queue_depth.set(len(self._queue))
        self._deliver(failures)
        if reject is not None:
            raise reject

    # ------------------------------------------------------------- consumer
    def take(self, max_batch, window_s):
        """Block until a batch is ready; return the oldest request's
        whole group (≤ ``max_batch``, oldest-first).

        Returns ``None`` when the controller is closed and drained.  The
        batching window runs from the oldest request's enqueue time: a
        full group dispatches immediately, a partial one waits at most
        ``window_s`` for company before going out undersized.
        """
        while True:
            failures, batch, decided = [], None, False
            with self._cond:
                failures += self._sweep_locked()
                if not self._queue:
                    if self._closed:
                        decided = True
                    else:
                        self._cond.wait(self._sweep_interval)
                else:
                    head = self._queue[0]
                    now = time.monotonic()
                    n_group = sum(1 for r in self._queue
                                  if r.group == head.group)
                    wait_until = head.t_enqueue + window_s
                    if n_group >= max_batch or now >= wait_until \
                            or self._closed:
                        decided = True
                        batch = self._pop_group_locked(head.group, max_batch)
                    else:
                        self._cond.wait(min(wait_until - now,
                                            self._sweep_interval))
            self._deliver(failures)
            if decided:
                return batch

    def poll(self, max_batch):
        """Non-blocking :meth:`take`: sweep deadlines, then pop the
        head request's group immediately — possibly an empty list.
        The continuous-batching decode worker admits between steps
        with this: a running batch must never block on the queue (and
        the embedded sweep keeps queued deadlines honest on every
        scheduler iteration, not just when a slot frees).

        Empty-queue fast path: no lock, no sweep (an empty queue has
        nothing to expire).  A request admitted concurrently is picked
        up by the next iteration's poll, one step (sub-ms) later."""
        if not self._queue:
            return []
        with self._cond:
            failures = self._sweep_locked()
            batch = []
            if self._queue:
                batch = self._pop_group_locked(self._queue[0].group,
                                               max_batch)
        self._deliver(failures)
        return batch

    def head(self):
        """``(requests queued, the oldest of them or None)``: what the
        decode scheduler decides on before it polls.  A request it
        leaves here stays under the sweep, the shed policies and the
        queue's bound like any other.  Empty-queue fast path as in
        :meth:`poll`."""
        if not self._queue:
            return 0, None
        with self._cond:
            return len(self._queue), (self._queue[0] if self._queue
                                      else None)

    def _pop_group_locked(self, group, max_batch):
        taken, keep = [], collections.deque()
        for r in self._queue:
            if r.group == group and len(taken) < max_batch:
                taken.append(r)
                if r.deadline is not None:
                    self._n_deadlined -= 1
            else:
                keep.append(r)
        self._queue = keep
        if self._telemetry is not None:
            self._telemetry.queue_depth.set(len(keep))
        return taken

    # ------------------------------------------------------------ pressure
    @staticmethod
    def _cost_key(r):
        """Cost-aware shed ranking: highest padded-element cost first,
        oldest first among equals (old work is least likely to still
        meet its deadline — the shed-oldest rationale)."""
        return (r.cost if r.cost is not None else 0, -r.t_enqueue)

    @property
    def pressure(self):
        return self._pressure

    def apply_pressure(self, limit):
        """Set (or withdraw, ``None``) the regulator's tightened queue
        limit, shedding cost-aware down to it immediately — a limit
        that only bites on the next admit would leave a deep queue
        burning the deadline budget for seconds after the regulator
        reacted.  Thread-safe; futures fail outside the lock."""
        failures = []
        tm = self._telemetry
        with self._cond:
            self._pressure = None if limit is None else max(1, int(limit))
            shed_to = self._pressure
            while shed_to is not None and len(self._queue) > shed_to:
                victim = max(self._queue, key=self._cost_key)
                self._queue.remove(victim)
                if victim.deadline is not None:
                    self._n_deadlined -= 1
                self.pressure_shed += 1
                if tm is not None:
                    tm.regulator_shed.inc()
                failures.append((victim, ServerOverloadError(
                    "request shed by the overload regulator after "
                    "%.1f ms queued: queue tightened to %d (max_queue "
                    "%d) under a firing burn-rate rule"
                    % ((time.monotonic() - victim.t_enqueue) * 1e3,
                       shed_to, self.max_queue))))
            if failures and tm is not None:
                tm.queue_depth.set(len(self._queue))
        self._deliver(failures)

    # -------------------------------------------------------------- expiry
    def _sweep_locked(self):
        """Drop expired requests from the queue; RETURNS the (future,
        exception) pairs for the caller to deliver AFTER releasing the
        lock — concurrent.futures runs done-callbacks synchronously in
        the completing thread, and a callback that re-enters this
        controller (submit-on-failure retry) would deadlock on the
        non-reentrant condition lock."""
        if not self._n_deadlined:
            return []
        now = time.monotonic()
        live, failures = collections.deque(), []
        for r in self._queue:
            if r.expired(now):
                self._n_deadlined -= 1
                self.expired += 1
                if self._telemetry is not None:
                    self._telemetry.expired.inc()
                failures.append((r, DeadlineExceededError(
                    "deadline exceeded after %.1f ms in queue"
                    % ((now - r.t_enqueue) * 1e3))))
            else:
                live.append(r)
        self._queue = live
        if failures and self._telemetry is not None:
            self._telemetry.queue_depth.set(len(live))
        return failures

    @staticmethod
    def _deliver(failures):
        """Fail futures OUTSIDE the condition lock (see _sweep_locked).
        ``failures`` holds (Request, exception) pairs so a sampled
        trace on a failed request still gets finished (abort) instead
        of silently vanishing from the trace store.

        Deadline expiry of a request that declared ``on_expire`` is
        not a failure: the handler renders the partial output (tokens
        generated so far + the ``expired`` flag) and the future
        RESOLVES with it — multi-step decode clients always get their
        partial generation back (see Request docstring)."""
        for req, exc in failures:
            result = None
            if req.on_expire is not None and \
                    isinstance(exc, DeadlineExceededError):
                try:
                    result = req.on_expire(exc)
                except Exception:   # handler bug: fall back to the error
                    result = None
            if result is None:
                _fail_future(req.future, exc)
                if req.trace is not None:
                    req.trace.abort(type(exc).__name__)
                continue
            if not req.future.cancelled():
                try:
                    req.future.set_result(result)
                except Exception:   # lost a cancel() race
                    pass
            if req.trace is not None:
                req.trace.abort("expired")

    def sweep(self):
        """Expire overdue queued requests now (also runs automatically
        on every admit/take)."""
        with self._cond:
            failures = self._sweep_locked()
        self._deliver(failures)

    def expire_request(self, req, detail=""):
        """Deliver deadline expiry to a request already POPPED from
        this queue (the replica router's routed-but-unseated window):
        the same partial-result contract (``on_expire``), trace abort,
        and counter accounting as the queued sweep, so stats() and the
        scraped expiry series stay one number however a deadline was
        hit."""
        exc = DeadlineExceededError(
            "deadline exceeded after %.1f ms%s"
            % ((time.monotonic() - req.t_enqueue) * 1e3,
               " (%s)" % detail if detail else ""))
        with self._cond:
            self.expired += 1
            if self._telemetry is not None:
                self._telemetry.expired.inc()
        self._deliver([(req, exc)])

    # ------------------------------------------------------------ lifecycle
    def close(self, drain=True):
        """Stop admitting.  With ``drain`` the worker keeps taking until
        the queue empties; otherwise pending futures fail immediately."""
        failures = []
        with self._cond:
            self._closed = True
            if not drain:
                while self._queue:
                    r = self._queue.popleft()
                    failures.append((r, EngineClosedError(
                        "engine closed before dispatch")))
                self._n_deadlined = 0
                if self._telemetry is not None:
                    self._telemetry.queue_depth.set(0)
            self._cond.notify_all()
        self._deliver(failures)

    @property
    def closed(self):
        return self._closed

    def __len__(self):
        with self._cond:
            return len(self._queue)

    def stats(self):
        with self._cond:
            return {"queue_depth": len(self._queue),
                    "max_queue": self.max_queue,
                    "pressure": self._pressure,
                    "overload_policy": self.overload_policy,
                    "admitted": self.admitted,
                    "rejected": self.rejected,
                    "shed": self.shed,
                    "pressure_shed": self.pressure_shed,
                    "expired": self.expired}

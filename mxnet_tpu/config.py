"""Runtime configuration — the MXNET_* environment-variable tier.

Reference: ~40 `MXNET_*` vars read via dmlc::GetEnv across the runtime
(docs/faq/env_var.md; engine type/threads src/engine/engine.cc:33,
threaded_engine_perdevice.cc:92-96, executor flags graph_executor.cc:40,
MXNET_BACKWARD_DO_MIRROR graph_executor.cc:282, profiler autostart
src/engine/profiler.cc:66, kvstore bigarray bound).

TPU-native redesign: one typed registry declares every variable with its
type, default, and doc (the dmlc::Parameter discipline applied to env
vars); `describe()` regenerates the env-var documentation so it can never
drift from the code.  Vars whose machinery collapsed into XLA (engine
type, thread pools per device, storage pools) are intentionally absent —
the table below IS the supported surface.
"""
from __future__ import annotations

import os

__all__ = ["get", "describe", "VARIABLES", "compile_cache_dir"]


class _Var(object):
    __slots__ = ("name", "vtype", "default", "doc")

    def __init__(self, name, vtype, default, doc):
        self.name = name
        self.vtype = vtype
        self.default = default
        self.doc = doc

    def read(self):
        raw = os.environ.get(self.name)
        if raw is None:
            return self.default
        if self.vtype is bool:
            return raw.strip().lower() not in ("", "0", "false", "no")
        return self.vtype(raw)


VARIABLES = {v.name: v for v in [
    _Var("MXNET_BACKWARD_DO_MIRROR", bool, False,
         "Trade FLOPs for memory: rematerialize forward activations "
         "during backward instead of storing them (the reference's "
         "mirror pass, graph_executor.cc:282; here jax.checkpoint around "
         "the fused step's forward)."),
    _Var("MXNET_FUSED_UNIT_MIN_FILTER", int, 0,
         "Minimum num_filter for unit_impl='fused' residual units to use "
         "the Pallas block-kernel tier (models/resnet.py); narrower "
         "units keep the XLA path.  0 = fuse every eligible unit."),
    _Var("MXNET_FUSED_UNIT_C3", str, "auto",
         "Middle-conv path inside fused units (ops/fused_unit.py): "
         "'auto' = the 2D row-layout Pallas kernels where their VMEM "
         "model fits, else the XLA segment; '2d'/'4d' force the row- or "
         "spatial-layout Pallas kernels (subject to their fit gates); "
         "'xla' = always the XLA segment.  An earlier chip record, "
         "since deleted, had 2d ahead of 4d and all of them behind "
         "plain XLA units on v5e, hence unit_impl='fused' is off by "
         "default."),
    _Var("MXNET_CPU_WORKER_NTHREADS", int, 4,
         "Default worker-thread count for host-side pipelines "
         "(ImageRecordIter preprocess_threads default; the reference's "
         "engine CPU worker pool knob, threaded_engine_perdevice.cc:92)."),
    _Var("MXNET_PROFILER_AUTOSTART", bool, False,
         "Start the profiler at import and dump on exit "
         "(src/engine/profiler.cc:66)."),
    _Var("MXNET_KVSTORE_BIGARRAY_BOUND", int, 1000000,
         "Arrays at least this large log a hint when pushed through the "
         "per-key kvstore veneer instead of the fused sharded step "
         "(the reference sharded such arrays across servers)."),
    _Var("MXNET_KVSTORE_HEARTBEAT_INTERVAL", float, 0.0,
         "Seconds between distributed-worker heartbeats (0 = off).  When "
         "on, a worker that misses MXNET_KVSTORE_HEARTBEAT_MISS beats is "
         "declared dead and every peer fail-stop aborts instead of "
         "hanging in the next collective (the ps-lite heartbeat analog, "
         "kvstore_dist.h:112-117; exposed via get_num_dead_node)."),
    _Var("MXNET_KVSTORE_HEARTBEAT_MISS", int, 5,
         "Missed-beat threshold before a distributed worker is declared "
         "dead by the heartbeat watchdog."),
    _Var("MXNET_ENFORCE_DETERMINISM", bool, False,
         "Fold a fixed seed into stochastic ops when no seed was set "
         "(reference MXNET_ENFORCE_DETERMINISM)."),
    _Var("MXNET_CONV_DOT_1X1", bool, False,
         "Lower channels-last 1x1 convolutions (and their dgrad/wgrad "
         "transposes) to explicit lax.dot_general MXU matmuls instead of "
         "XLA's conv codegen.  An earlier chip record, since deleted, "
         "had it SLOWER for ResNet-50 on v5e (80.2 vs 75.9 ms): the step "
         "is HBM-bound and the dot forms fuse worse.  Off by default; "
         "not measured on today's code."),
    _Var("MXNET_CONV1X1_FUSED_BWD", bool, False,
         "Compute a channels-last stride-1 1x1 convolution's dgrad AND "
         "wgrad in one Pallas kernel pass over the output gradient "
         "(XLA emits two fusions that each re-read dy from HBM; the step "
         "is bandwidth-bound).  Off by default: an earlier chip "
         "record, since deleted, had it slower than the two XLA "
         "fusions."),
    _Var("MXNET_SERVE_MAX_BATCH", int, 8,
         "Largest batch bucket the serving engine compiles and "
         "coalesces to (mxnet_tpu/serving).  Rounded up to a power of "
         "two; pending requests pad up to the smallest bucket that "
         "fits, so at most log2(max_batch)+1 programs exist per input "
         "signature."),
    _Var("MXNET_SERVE_MAX_QUEUE", int, 256,
         "Bound on the serving admission queue.  A full queue either "
         "rejects new work (QueueFullError backpressure) or sheds the "
         "oldest pending request, per MXNET_SERVE_OVERLOAD_POLICY."),
    _Var("MXNET_SERVE_BATCH_TIMEOUT_MS", float, 2.0,
         "Dynamic-batching window: a partial batch waits at most this "
         "long (measured from its oldest request's enqueue) for more "
         "compatible requests before dispatching undersized.  0 = "
         "dispatch immediately, trading occupancy for latency."),
    _Var("MXNET_SERVE_DEFAULT_DEADLINE_MS", float, 0.0,
         "Default per-request deadline for serving requests that do "
         "not pass deadline_ms explicitly; requests still queued past "
         "their deadline fail with DeadlineExceededError.  0 = no "
         "default deadline."),
    _Var("MXNET_SERVE_OVERLOAD_POLICY", str, "reject",
         "What the serving engine does when the admission queue is "
         "full: 'reject' raises QueueFullError to the submitting "
         "client (backpressure); 'shed-oldest' evicts the longest-"
         "queued request (its future fails with ServerOverloadError) "
         "to admit the new one — graceful degradation under overload."),
    _Var("MXNET_SERVE_REPLICAS", int, 1,
         "Data-parallel device replicas per serving engine "
         "(serving/replica.py, ROADMAP 2a): both ServingEngine and "
         "DecodeEngine own this many device replicas — each with its "
         "own compiled-program cache and device-resident params — and "
         "route work to the least-loaded one (one-shot: emptiest "
         "in-flight queue; decode: most free slots, requests pinned to "
         "their seated replica).  Needs that many addressable devices "
         "(XLA_FLAGS=--xla_force_host_platform_device_count=N gives a "
         "CPU host N); when the env asks for more replicas than "
         "devices exist the engine clamps with a warning.  1 = the "
         "single-device fast path, byte-for-byte the pre-replica "
         "engine."),
    _Var("MXNET_SERVE_SHARDING", str, "",
         "Model-parallel serving plan (serving + parallel/mesh.py, "
         "ROADMAP item 1): a ShardingPlan spec — inline JSON or a "
         "path to a JSON file — e.g. '{\"axes\": {\"tp\": 2}, "
         "\"param_rules\": [[\"fc.*weight$\", [null, \"tp\"]]]}'.  "
         "Each engine replica then owns a prod(axes)-device group in "
         "dp order and compiles every program (bucket programs, "
         "decode step, prefill buckets) under the plan: params upload "
         "as sharded device_put, per-slot decode state lays out per "
         "state_rules, and XLA inserts the collectives.  Composes "
         "with MXNET_SERVE_REPLICAS (N replicas x G-device plans "
         "needs N*G devices; never clamped).  Plans partitioning a "
         "padded data axis (batch_axis/seq_axis) are verdict-gated: "
         "cross-position or unproven axes REJECT at construction "
         "with a reason (analysis.check_sharding_plan; audit offline "
         "with tools/graph_lint.py --sharding-plan).  Empty = "
         "single-device replicas, byte-for-byte the unsharded "
         "engines."),
    _Var("MXNET_SERVE_SEQ_BUCKETS", str, "",
         "Comma-separated sequence-length buckets (e.g. '32,64,128') "
         "for the serving engine.  When set, per-example axis 0 is "
         "padded up to the next bucket so length-polymorphic traffic "
         "shares programs; outputs are un-padded on the same axis "
         "(model must be row-independent along it).  Empty = off: "
         "every distinct example shape is its own bucket."),
    _Var("MXNET_DECODE_SLOTS", int, 8,
         "Slot-pool capacity of the continuous-batching decode engine "
         "(serving/decode.py DecodeEngine): the persistent step program "
         "is compiled ONCE at this batch extent, per-slot state (KV "
         "cache / recurrent state) lives device-resident at this "
         "leading dim, and requests join/leave the running batch "
         "between steps with zero retraces."),
    _Var("MXNET_DECODE_MAX_LEN", int, 128,
         "Per-slot sequence capacity of the decode engine: the fixed "
         "O(1) per-token cache layout (PAPERS.md 2603.09555) allocates "
         "this many positions per slot up front; prompt length + "
         "generated tokens may not exceed it (requests finish with "
         "reason 'length' at the cap)."),
    _Var("MXNET_DECODE_SPEC_K", int, 0,
         "Speculative draft-k-verify decoding (serving/decode.py + "
         "serving/spec.py): with k > 0 and a draft model "
         "(DecodeEngine draft_sym=), every replica compiles ONE wider "
         "step program that drafts k continuation tokens in-graph, "
         "scores all k+1 positions with the target model in the same "
         "dispatch, and commits only the accepted prefix (exact "
         "greedy prefix match for GreedySampler — bitwise-identical "
         "to greedy_decode; standard rejection sampling for "
         "TemperatureSampler — seeded replays bitwise).  Accepted "
         "rows commit through the _cache_write_rows multi-token "
         "scatter when the verdict-gated selection adopts it "
         "(MXNET_CACHE_SCATTER_IMPL picks its backend impl).  0 (the "
         "default) is the single-token engine byte-identical to the "
         "pre-spec code.  DecodeEngine(spec_k=) overrides."),
    _Var("MXNET_DECODE_COALESCE_PREFILL", bool, True,
         "Coalesce concurrent decode joiners through the bucketed "
         "prefill path (serving/decode.py): requests joining in the "
         "same scheduler iteration whose prompts pad to the same pow2 "
         "seq bucket prefill in ONE dispatch (batch padded onto pow2 "
         "batch buckets, output state rows scattered into each "
         "request's slot) instead of one batch-1 dispatch each — the "
         "TTFT lever at concurrency (perf/decode_bench.py --prefill).  "
         "0 = the serial per-joiner prefill, byte-for-byte the "
         "pre-coalescing engine."),
    _Var("MXNET_CACHE_SCATTER_IMPL", str, "auto",
         "Implementation of the _cache_write_row scatter-at-index op "
         "(ops/cache.py): 'auto' = Pallas kernel on TPU for the rank-3 "
         "(slots, max_len, d) pool it tiles, vmapped "
         "jax.lax.dynamic_update_slice elsewhere; 'pallas' forces the "
         "kernel; 'interpret' runs the Pallas kernel in interpreter "
         "mode on any backend (the tests' bitwise pin of the kernel on "
         "CPU hosts); 'xla' forces the dynamic_update_slice fallback "
         "everywhere."),
    _Var("MXNET_OPT_SELECT_KERNELS", bool, True,
         "Fused-op selection stage of the graph optimizer "
         "(analysis/optimize.py 'select' pass): pattern-matches "
         "subgraphs that state a fused kernel's semantics the long way "
         "— today the one-hot-blend KV-cache row write, O(max_len*d) "
         "per token — and swaps in the dedicated registry op "
         "(_cache_write_row, O(d)) behind the same verdict gate as "
         "every other rewrite (re-analysis no worse, slot-axis "
         "row-locality preserved under pad-dirty seeding; a rejected "
         "plan serves the unmodified graph).  DecodeEngine applies it "
         "to the step graph it compiles; requires MXNET_SERVE_OPTIMIZE "
         "and MXNET_ANALYSIS_ON.  0 = diagnostic fusion hints only, "
         "no kernel swaps."),
    _Var("MXNET_ANALYSIS_ON", bool, True,
         "Run the static-analysis passes (mxnet_tpu.analysis) at "
         "Predictor/ServingEngine construction: the IR verifier always, "
         "plus the padding-soundness classifier for the engine's padded "
         "axes.  Findings warn by default; see MXNET_ANALYSIS_STRICT."),
    _Var("MXNET_ANALYSIS_STRICT", bool, False,
         "Escalate construction-time analysis findings from warnings to "
         "MXNetError: malformed graphs refuse to build, and a serving "
         "graph classified cross-position along a padded axis refuses "
         "the unsound bucketing instead of degrading it."),
    _Var("MXNET_MEMORY_PLAN", bool, True,
         "Run the static memory planner (analysis/memory.py) at "
         "ServingEngine/DecodeEngine construction: liveness-based "
         "peak-HBM prediction over the full warm program set, the "
         "donation/aliasing soundness gate over the decode slot pool, "
         "and the OOM preflight against the device budget — all "
         "BEFORE any compile.  Requires MXNET_ANALYSIS_ON.  Findings "
         "warn by default (MXNET_ANALYSIS_STRICT=1 raises); the "
         "planner only diagnoses, so served outputs are "
         "bitwise-identical with it on or off."),
    _Var("MXNET_MEMORY_BUDGET_BYTES", int, 0,
         "Per-device HBM budget in bytes for the memory planner's OOM "
         "preflight.  0 = auto-detect from "
         "device.memory_stats()['bytes_limit'] where the backend "
         "supports it (CPU does not: prediction still runs, capacity "
         "refusal is skipped).  Set explicitly to preflight against a "
         "target accelerator from any host."),
    _Var("MXNET_SERVE_REPAIR", bool, True,
         "Attempt an automatic masking repair (analysis/rewrite.py) "
         "before degrading a serving graph the padding pass classifies "
         "cross-position along the bucketed seq axis: SequenceMask "
         "nodes driven by a per-request valid-length input neutralize "
         "pad slots (-inf for softmax, 0 for sums, renormalized count "
         "for mean), and the repair is adopted only when re-analysis "
         "verdicts the rewritten graph row-local.  0 = always degrade "
         "as before (exact-length programs / max_batch=1)."),
    _Var("MXNET_SERVE_OPTIMIZE", bool, True,
         "Run the verdict-gated optimizing pass pipeline "
         "(analysis/optimize.py: algebraic identities, constant "
         "folding, CSE, dead-node elimination) over the graph the "
         "serving ProgramCache compiles.  A candidate is adopted ONLY "
         "when re-analysis verdicts — output shapes/dtypes and "
         "padded-axis soundness — are no worse than the input "
         "graph's, so accepted rewrites stay bitwise-identical to the "
         "unoptimized batch-1 Predictor.  Requires MXNET_ANALYSIS_ON "
         "(the acceptance protocol IS analysis); 0 = serve the graph "
         "exactly as handed in."),
    _Var("MXNET_SERVE_PAD_CHECK", bool, False,
         "Runtime padding-soundness probe (debug; doubles dispatch "
         "cost): every serving batch is dispatched twice — zero pads "
         "and sentinel-filled pads — and live output rows must match "
         "bitwise, catching cross-position contamination the static "
         "pass could not prove (serving/buckets.py run_pad_probe)."),
    _Var("MXNET_TELEMETRY_ON", bool, True,
         "Master switch for the runtime telemetry registry "
         "(mxnet_tpu.telemetry): metrics counters/gauges/histograms and "
         "request-scoped tracing across serving, executor, kvstore, and "
         "the input pipeline.  Off = instrumented call sites hold no "
         "instruments and make zero registry calls per request."),
    _Var("MXNET_TELEMETRY_TIMELINE", bool, True,
         "Unified fleet timeline (telemetry/timeline.py): a process-"
         "wide bounded ring of dual-stamped (wall + monotonic) events "
         "fed by every plane — span trees, per-replica dispatches, "
         "decode scheduler iterations and slot churn, lock holds, "
         "alert transitions, flight dumps, regulator limit moves, "
         "supervisor rehab/retire, injected faults.  Exported as "
         "Chrome trace_event JSON (GET /timeline?format=chrome, "
         "tools/telemetry_dump.py timeline, tools/request_autopsy.py)."
         "  Requires MXNET_TELEMETRY_ON; 0 = zero ring appends and "
         "bitwise-identical serving."),
    _Var("MXNET_TELEMETRY_TIMELINE_CAP", int, 16384,
         "Capacity of the timeline event ring (events, process-wide). "
         "Oldest events drop first; the drop count is reported in "
         "every export so a truncated window is never mistaken for a "
         "quiet one."),
    _Var("MXNET_TELEMETRY_TIMELINE_LOCK_MS", float, 1.0,
         "Minimum lock-hold duration (ms) the lock sanitizer records "
         "into the timeline ring.  Micro-holds below this flood the "
         "bounded window without carrying contention signal; 0 "
         "records every hold."),
    _Var("MXNET_TELEMETRY_SNAPSHOT_SECS", float, 0.0,
         "Interval for the periodic telemetry snapshot thread (0 = "
         "off).  Every interval the current metrics snapshot is "
         "written to MXNET_TELEMETRY_SNAPSHOT_PATH (atomic replace) or "
         "stdout, in MXNET_TELEMETRY_SNAPSHOT_FORMAT."),
    _Var("MXNET_TELEMETRY_SNAPSHOT_PATH", str, "",
         "Destination file for periodic telemetry snapshots; empty "
         "writes to stdout."),
    _Var("MXNET_TELEMETRY_SNAPSHOT_FORMAT", str, "prom",
         "Snapshot format: 'prom' (Prometheus text exposition) or "
         "'json' (metrics + finished traces, the document "
         "tools/telemetry_dump.py renders)."),
    _Var("MXNET_TELEMETRY_TRACE_SAMPLE", int, 64,
         "Baseline-floor period of the serving trace-retention chain "
         "(telemetry/sampling.py): every request is traced cheaply and "
         "retention is decided at finish — every Nth request is kept "
         "unconditionally, on top of the tail-biased and error-keep "
         "samplers.  1 keeps every request; 0 disables tracing "
         "entirely (no per-request TraceContext, no tail/error keeps)."),
    _Var("MXNET_TELEMETRY_TRACE_TAIL_K", int, 8,
         "Tail-biased trace retention: a finished request trace is "
         "retroactively kept when its end-to-end latency lands in the "
         "current top-K slowest or exceeds a moving p99 estimate, so "
         "every tail request has a span tree (the traffic p99 "
         "debugging actually needs).  0 disables the tail sampler, "
         "leaving only the periodic floor and error keep."),
    _Var("MXNET_TELEMETRY_TRACE_ERRORS", bool, True,
         "Keep the span tree of every request that failed (rejected, "
         "shed, expired, cancelled, dispatch error) regardless of the "
         "periodic/tail samplers — overloaded traffic is exactly what "
         "an operator debugs."),
    _Var("MXNET_TELEMETRY_PORT", int, -1,
         "Port for the live telemetry HTTP endpoint "
         "(telemetry/server.py: GET /metrics, /metrics.json, /traces, "
         "/traces/<id>, /healthz).  -1 = off; 0 = bind an ephemeral "
         "port (telemetry.server_address() reads it back).  Started "
         "at import when set, or lazily by ServingEngine construction "
         "— in which case the last engine's close() shuts it down "
         "(port and acceptor thread are released, never leaked)."),
    _Var("MXNET_TELEMETRY_SHARED_DIR", str, "",
         "Cross-host aggregation drop point: when set, KVStoreDist "
         "ranks periodically write their registry snapshot as "
         "telemetry_rank<N>.json under this (shared) directory, and "
         "`tools/telemetry_dump.py aggregate <dir>/telemetry_rank*.json` "
         "merges them into one rank-labeled document.  Empty = off."),
    _Var("MXNET_TELEMETRY_HISTORY_SECS", float, 1.0,
         "Sampling interval of the in-process time-series recorder "
         "(telemetry/recorder.py): every interval the metrics registry "
         "is snapshotted into a bounded in-memory ring, giving true "
         "rate()/delta()/windowed-quantile queries (GET /history) and "
         "the SLO alert evaluation tick with zero external infra.  "
         "Started lazily by the first ServingEngine/DecodeEngine (last "
         "close() stops it) or explicitly via "
         "telemetry.start_recorder().  0 = off."),
    _Var("MXNET_TELEMETRY_HISTORY_WINDOW", int, 600,
         "Ring capacity of the history recorder in samples (memory is "
         "bounded by construction: deque(maxlen=N)).  At the default "
         "1 s interval, 600 samples = a 10-minute trailing window — "
         "enough for the 60 s/600 s multiwindow burn-rate rules."),
    _Var("MXNET_TELEMETRY_ALERTS", bool, True,
         "Evaluate SLO alert rules (telemetry/alerts.py) against the "
         "history ring on every recorder sample.  Engines register "
         "default rules at construction (queue-saturation and "
         "deadline-miss burn rates, per-engine zero-progress watchdog "
         "and retrace-storm) and remove them at close(); rule states "
         "serve at GET /alerts, transitions stream over GET /events.  "
         "0 = rules are neither registered nor evaluated."),
    _Var("MXNET_TELEMETRY_ALERT_RULES", str, "",
         "Path to a declarative SLO alert-rules file: a JSON list (or "
         "{'rules': [...]} document) of AlertRule.from_dict dicts "
         "loaded into the default AlertManager when the history "
         "recorder starts (telemetry/alerts.py load_rules_file) — "
         "operators add burn-rate/threshold/absence/watchdog rules "
         "without redeploying.  Rules whose names are already "
         "registered are skipped (idempotent across engine-driven "
         "recorder rebuilds); a malformed file warns and loads "
         "nothing.  Empty = off."),
    _Var("MXNET_TELEMETRY_WATCHDOG_SECS", float, 30.0,
         "Zero-progress threshold for the engines' default watchdog "
         "alert rules: a worker heartbeat that is BUSY (work queued or "
         "a dispatch in flight) yet stamped no progress for this many "
         "seconds fires <kind>_engine<N>_stalled — a wedged dispatch "
         "or starved queue, named, not inferred."),
    _Var("MXNET_SERVE_EFFICIENCY", bool, True,
         "Serving efficiency plane (telemetry/goodput.py): per-"
         "compiled-program FLOPs ledger priced once at compile/AOT-"
         "load time (analysis/flops.py over the concrete padded "
         "shapes), per-dispatch counters decomposed into useful / "
         "padding / dead-slot / spec-rejected classes that sum "
         "exactly to total, live mxnet_serve_mfu and goodput_ratio "
         "gauges, and per-tenant accounting.  Requires "
         "MXNET_TELEMETRY_ON; 0 = no pricing, no ledger series, zero "
         "instrument calls on the dispatch path, serving "
         "bitwise-identical to the plane never existing."),
    _Var("MXNET_TELEMETRY_TENANTS_MAX", int, 32,
         "Bounded-cardinality guard on the per-tenant accounting "
         "series (telemetry/goodput.py): the first N distinct tenant "
         "ids an engine sees get their own {tenant=...} label; "
         "later tenants aggregate into tenant=\"other\" and each "
         "overflowed request increments "
         "mxnet_serve_tenant_overflow_total so the collapse is "
         "visible, not silent."),
    _Var("MXNET_AOT_CACHE_DIR", str, "",
         "Persistent AOT program-cache directory (serving/aot_cache.py)."
         "  When set, every serving program — one-shot bucket programs, "
         "decode step programs, prefill buckets, slot-row scatter "
         "kernels — is serialized (jax.export) to a content-addressed "
         "entry under this directory at first compile, and a restarted "
         "engine (or replica N+1 joining under load) loads the entry "
         "instead of retracing: warm restarts perform ZERO traces for "
         "previously-served buckets.  Entries are keyed by graph "
         "canonical form x input shapes/dtypes x policy x sharding x "
         "backend platform; corruption or fingerprint drift (jax/"
         "library version, analysis-verdict digest) REJECTS the entry "
         "and falls back to a fresh compile — never a stale program.  "
         "Empty = off (process-lifetime compilation, exactly the "
         "pre-cache behavior).  Manage with tools/aot_cache.py "
         "(list/verify/prune)."),
    _Var("MXNET_AOT_CACHE", bool, True,
         "Master switch for the persistent AOT program cache: 0 "
         "disables it even when MXNET_AOT_CACHE_DIR is set (kill "
         "switch for a corrupt or slow shared cache volume)."),
    _Var("MXNET_AOT_XLA_CACHE", str, "auto",
         "Also point jax's persistent compilation cache at "
         "MXNET_AOT_CACHE_DIR/xla (first engine wins; process-global)."
         "  The AOT entries skip Python tracing; this knob "
         "additionally skips XLA's compile of the deserialized "
         "module, so a warm restart loads executables instead of "
         "building them.  'auto' (default): enabled only when the "
         "serving entrypoint owns process bring-up — the first "
         "AOT-enabled engine is constructed before any of this "
         "library's graph programs has traced (executor."
         "xla_traces_ever() == 0), so flipping the process-wide jax "
         "config cannot surprise an application that compiled first "
         "(ROADMAP residual b1).  '1' forces it on regardless (the "
         "late-enable latch re-initializes jax's cache via "
         "compilation_cache.reset_cache, so programs compiled before "
         "the engine existed do not pin it off); '0' is the explicit "
         "opt-out.  An operator-set jax_compilation_cache_dir is "
         "never overridden."),
    _Var("MXNET_LOCK_SANITIZER", bool, False,
         "Runtime lock sanitizer (mxnet_tpu/locks.py, surfaced as "
         "serving.locks).  When on, every named_lock/named_rlock/"
         "named_condition the runtime constructs is a recording "
         "wrapper: each acquisition records the held-while-acquiring "
         "order edge from every lock the thread already holds "
         "(mxnet_lock_order_edges_total{src,dst}) and each release "
         "records the hold time (mxnet_lock_hold_seconds{lock}); "
         "observed edges merge into the static lock-order graph "
         "(tools/thread_lint.py --merge-observed) and "
         "locks.assert_no_inversions() fails a test run on any "
         "observed inversion.  Off (the default): the factories "
         "return the plain threading primitives — zero wrappers, "
         "zero instrument calls, serving byte-identical to the "
         "sanitizer never existing (tests pin it bitwise)."),
    _Var("MXNET_LOCK_SANITIZER_DUMP", str, "",
         "With MXNET_LOCK_SANITIZER=1: write the observed lock-order "
         "edges, hold-time stats, and any inversions to this path as "
         "JSON at interpreter exit (atomic replace) — the artifact "
         "the sanitizer subprocess smoke test and thread_lint "
         "--merge-observed consume.  Empty = no dump."),
    _Var("MXNET_FAULT_PLAN", str, "",
         "Deterministic fault-injection plan (serving/faults.py).  "
         "Either a JSON list of clause dicts or the compact grammar "
         "'site:action:k=v,k=v;...' — e.g. "
         "'decode.step:raise:on=5,replica=1;aot.load:corrupt:on=1'.  "
         "Sites: serve.dispatch, decode.step, decode.prefill, "
         "aot.load, admission.admit.  Actions: raise (FaultInjected), "
         "hang (hang_s seconds), corrupt (aot.load payload bytes).  "
         "Triggers: on=N (1-based Nth matching hit), after=N, "
         "every=K, times=M, p=P with seed=S (seeded, reproducible).  "
         "Empty = off: the injection sites are a single predicate "
         "check and serving behavior is byte-for-byte the uninjected "
         "engine."),
    _Var("MXNET_SUPERVISOR", bool, False,
         "Automatic replica probation (serving/supervisor.py).  When "
         "on, a refcounted supervisor thread watches every engine's "
         "replica health and drives rehabilitate() for retired "
         "replicas on an exponential-backoff-with-jitter clock "
         "(MXNET_SUPERVISOR_BACKOFF_MS doubling up to "
         "MXNET_SUPERVISOR_BACKOFF_MAX_MS, MXNET_SUPERVISOR_ATTEMPTS "
         "bounded attempts, then permanent retirement + alert).  Off "
         "by default: rehabilitation stays an operator verb."),
    _Var("MXNET_SUPERVISOR_BACKOFF_MS", float, 500.0,
         "Supervisor probation backoff base: the first rehab attempt "
         "for a freshly retired replica waits this long; each failed "
         "attempt doubles it (plus deterministic jitter)."),
    _Var("MXNET_SUPERVISOR_BACKOFF_MAX_MS", float, 30000.0,
         "Supervisor probation backoff ceiling."),
    _Var("MXNET_SUPERVISOR_ATTEMPTS", int, 5,
         "Failed rehab attempts before the supervisor permanently "
         "retires a replica (alert + flight bundle; an operator "
         "rehabilitate() call can still bring it back)."),
    _Var("MXNET_SUPERVISOR_INTERVAL_MS", float, 100.0,
         "Supervisor poll interval: how often replica health and due "
         "probation clocks are checked."),
    _Var("MXNET_REGULATOR", bool, False,
         "SLO-driven overload regulator (serving/regulator.py).  When "
         "on (and telemetry + the history recorder are running), each "
         "engine runs a regulator thread that reads the burn-rate "
         "rule states (serve_queue_saturation_burn, "
         "serve_deadline_miss_burn) each cycle and adapts the "
         "admission plane: firing tightens the effective queue limit "
         "multiplicatively (shedding the highest padded-element-cost "
         "requests first), resolution relaxes it back to the "
         "configured max_queue.  Off by default: admission behavior "
         "is byte-for-byte the unregulated engine."),
    _Var("MXNET_REGULATOR_INTERVAL_MS", float, 500.0,
         "Regulator evaluation interval."),
    _Var("MXNET_REGULATOR_MIN_QUEUE", int, 8,
         "Floor on the regulator's tightened admission-queue limit — "
         "overload control may shed aggressively but must never "
         "choke the queue below a dispatchable batch."),
    _Var("MXNET_AOT_CACHE_MAX_MB", float, 0.0,
         "Size budget for the persistent AOT cache volume.  > 0: "
         "after every store() the writer best-effort prunes entries "
         "oldest-first until the directory fits the budget (counted "
         "in mxnet_serve_aot_prunes_total; tolerant of concurrent "
         "writers — a vanished file is someone else's prune, not an "
         "error).  0 = unbounded (janitoring via tools/aot_cache.py "
         "prune)."),
    _Var("MXNET_FLIGHT_RING_MB", float, 4.0,
         "Binary ring-file flight-recorder window: with "
         "MXNET_FLIGHT_RECORDER_DIR set, the history recorder appends "
         "every sample to a preallocated fixed-size ring file "
         "(ring.bin, this many MB) so a SIGKILL/OOM leaves a readable "
         "trailing telemetry window no Python-level hook could have "
         "written.  Render with tools/telemetry_dump.py ring.  "
         "0 = off."),
    _Var("MXNET_FLIGHT_RECORDER_DIR", str, "",
         "Black-box post-mortem directory.  When set, any alert "
         "transition to firing (watchdog trips included) atomically "
         "dumps a flight bundle — trailing history window, rule "
         "states, retained traces, per-engine stats(), heartbeats, "
         "all-thread stacks via faulthandler — as flight_*.json under "
         "this directory (rate-limited, pruned to the newest 16), and "
         "fatal signals (SIGSEGV/SIGFPE/SIGABRT) append stacks to "
         "fatal_stacks.log via faulthandler.enable.  Read bundles "
         "back with tools/telemetry_dump.py bundle.  Empty = off."),
    _Var("MXNET_TELEMETRY_TRACE_CAPACITY", int, 256,
         "Bound on the in-process finished-trace store; beyond it the "
         "oldest span trees are evicted (long serving runs must not "
         "grow host memory without limit)."),
    _Var("MXNET_PROFILER_MAX_EVENTS", int, 1000000,
         "Bound on the in-memory profiler event buffer.  Beyond it the "
         "oldest events are dropped (and counted in the dump's "
         "otherData.dropped_events) so always-on profiling of long "
         "serving runs cannot grow host memory without limit."),
    _Var("MXNET_EXEC_BULK_EXEC_TRAIN", bool, True,
         "Accepted for API parity; execution is always one fused XLA "
         "program (the engine bulking machinery this toggled does not "
         "exist)."),
]}


def get(name):
    """Typed read of a registered MXNET_* variable."""
    if name not in VARIABLES:
        raise KeyError("unknown config variable %r (known: %s)"
                       % (name, sorted(VARIABLES)))
    return VARIABLES[name].read()


def compile_cache_dir():
    """Turn on JAX's persistent compilation cache for a script of this
    checkout and return the directory it lives in.

    The directory is placed from outside: where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it, and no
    directory is set in code.  Otherwise it is ``<checkout>/.jax_cache``
    — fixed, because the path is part of the cache key, so a temporary
    or per-process directory never hits.  Either way the size and
    compile-time thresholds are zeroed so the small serving programs
    are cached too.  Call it before the first compile."""
    import jax
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
        # jax latches "no cache" at the first compile that ran before a
        # directory was configured; start over so this one is used
        from jax.experimental.compilation_cache import compilation_cache
        compilation_cache.reset_cache()
    return path


def describe():
    """Markdown table of every supported env var (docs generated from the
    registry, dmlc::Parameter-style)."""
    lines = ["| variable | type | default | description |",
             "|---|---|---|---|"]
    for name in sorted(VARIABLES):
        v = VARIABLES[name]
        lines.append("| %s | %s | %r | %s |"
                     % (name, v.vtype.__name__, v.default, v.doc))
    return "\n".join(lines)

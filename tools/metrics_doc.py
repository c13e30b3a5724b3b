"""Generate ``docs/metrics.md`` from the LIVE metric registry — and
fail CI when the two drift.

Documentation that is typed by hand goes stale the week after it is
written; documentation *generated from the registry* cannot.  This
tool builds both serving-engine kinds with every observability plane
enabled (tracing retention, recorder + alerts, regulator, supervisor,
fault injection, lock sanitizer, goodput ledger, timeline), exercises
the training/kvstore/io instruments, then renders one table row per
registered metric family: name, type, label names, and the registry
help string — the authoritative "what can I scrape" index the README
links.

Modes::

  python tools/metrics_doc.py                  # rewrite docs/metrics.md
  python tools/metrics_doc.py --check          # exit 1 on drift (CI)
  python tools/metrics_doc.py --out -          # print to stdout

The tier-1 gate (``tests/test_timeline.py``) runs ``--check`` in a
subprocess: a new metric family landing without a regenerated
``docs/metrics.md`` fails the suite, which is the whole point — the
doc is a contract, not a courtesy.
"""
import argparse
import io
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOC_PATH = os.path.join(REPO, "docs", "metrics.md")
if REPO not in sys.path:        # `python tools/metrics_doc.py` puts
    sys.path.insert(0, REPO)    # tools/ first, not the repo root

# the construction recipe pins these BEFORE mxnet_tpu imports — the
# sanitizer and tracing tiers read them at plane-construction time
_ENV = {
    "JAX_PLATFORMS": "cpu",
    "MXNET_TELEMETRY": "1",
    "MXNET_TELEMETRY_TIMELINE": "1",
    "MXNET_TELEMETRY_TRACE_SAMPLE": "1",
    "MXNET_LOCK_SANITIZER": "1",
    # keep the builder hermetic: no HTTP server, no snapshot thread
    "MXNET_TELEMETRY_PORT": "0",
    "MXNET_TELEMETRY_SNAPSHOT_SECS": "0",
}

_HEADER = """\
# Metric reference

<!-- GENERATED FILE — do not edit by hand.
     Regenerate with:  python tools/metrics_doc.py
     CI gate:          python tools/metrics_doc.py --check -->

Every metric family the runtime can register, generated from the live
registry after constructing both serving-engine kinds (one-shot +
decode) with every observability plane on.  All families live in the
`mxnet_` namespace (`tools/telemetry_dump.py` renders them offline;
`GET /metrics` serves the Prometheus text form).

| family | type | labels | help |
|---|---|---|---|
"""


#: What the serving path reports outside the registry: the arguments of
#: the decode engine's timeline events (``telemetry/timeline.py``; one
#: ``decode.step`` a step, one ``decode.prefill`` a dispatch) and the
#: keys of ``stats()["decode"]`` the benchmark's readers and drivers
#: take.  Static text: the ring has no schema to generate it from.
_EVENTS = """
## Timeline event arguments and `stats()["decode"]` keys

Not registry families: arguments of the decode engine's events in the
timeline ring, and keys of `DecodeEngine.stats()["decode"]`.

| where | name | what it holds |
|---|---|---|
| `decode.step` | `live`, `tokens` | slots the step stepped; tokens delivered from it |
| `decode.step` | `dispatch_ms`, `read_ms` | the step's dispatch, an iteration back; the wait for its ids, after the step after it went out |
| `decode.step` | `ahead`, `discarded` | 1 if dispatched before the step before it was read; slot results whose request had left |
| `decode.step` | `held` | requests a free slot waited for that the writing iteration's decision left in their queue, for a prefill dispatch worth its cost (`serving/join_policy.py`); 0 where nothing was held |
| `decode.step` | `<name>_max`, `<name>_mean` | a counter output of the step graph past its states (`expert_load`: live rows an expert got) |
| `decode.step` | `expert_products`, `expert_routed` | (row, expert) products the step's `_moe_experts` nodes multiply at pool extent, by the op's own rule for the shapes built (every held expert over every slot on the plain path); `top_k` x live rows, summed over those nodes. Absent where the step has no expert layer |
| `decode.prefill` | `bucket`, `group`, `tokens`, `padded` | padded prompt length; requests in the dispatch; live prompt positions; batch x bucket |
| `decode.prefill` | `live` | slots decoding when the join of this dispatch began, which it stopped while it ran; 0 on an empty pool |
| `decode.prefill` | `fused_attention`, `attention_nodes` | `_gqa_prefill` nodes of the dispatched program that take the fused kernel; how many it has |
| `decode.prefill` | `row_states`, `cache_states` | states the dispatch's commit laid into the pool: plain rows (replaced whole: recurrent and convolution state) and positional caches (keys or values of every prompt position) |
| `stats()["decode"]` | `state_rows` | rows of each cache state a slot |
| `stats()["decode"]` | `row_state_bytes` | bytes of one slot's plain rows (the states that are no cache: recurrent rows, convolution rows, a state space's state; zeroed at a join, replaced by a prefill). The benchmark's `ssm_state_step_share` prices what the decode steps read and write of them at the peak HBM rate against the steps' device time |
| `stats()["decode"]` | `prefill_token_budget`, `prefill_programs` | positions a prefill dispatch may hold; (batch, bucket) programs warmed |
| `stats()["decode"]` | `steps_ahead`, `slot_steps_discarded` | totals of `ahead` and `discarded` |
| `stats()["decode"]` | `prefill_fused_attention`, `prefill_attention_nodes` | totals over the prefill dispatches |
| `stats()["decode"]` | `slot_steps_held` | total of `held` over the steps dispatched: seatable requests x steps left waiting |
| `stats()["decode"]` | `prefill_cost_ms` | `{bucket: {batch: ms}}`: what a prefill dispatch has cost the device (its span less what the step in flight still had to run), the median of its last five readings over the live dispatches; the table the join decision reads |
"""


def populate_registry():
    """Construct both engine kinds with all planes on and exercise the
    ancillary instruments, so the default registry holds every family
    the runtime registers on these paths.  Returns the registry.

    Must run under the env pins above (the CLI re-execs itself to
    guarantee them; tests call the CLI, never this directly)."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.serving import (DecodeEngine, ServingEngine, faults,
                                   regulator, supervisor)
    from mxnet_tpu.rnn.rnn_cell import LSTMCell

    telemetry.set_enabled(True)

    # --- one-shot engine, 2 replicas (replica + routing families) ---
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=8,
                                name="fc1")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    rng = np.random.default_rng(0)
    params = {"fc1_weight": mx.nd.array(
                  rng.standard_normal((8, 6)).astype(np.float32)),
              "fc1_bias": mx.nd.zeros((8,))}
    eng = ServingEngine(net, params, {}, {"data": (6,)},
                        ctx=[mx.cpu(0), mx.cpu(0)])
    eng.warmup()
    eng.predict(np.ones((6,), np.float32), timeout=60)

    # --- decode engine (slots/steps/TTFT/speculative families) ---
    tok = mx.sym.Variable("token")
    emb = mx.sym.Embedding(tok, input_dim=8, output_dim=4, name="emb")
    cell = LSTMCell(8, prefix="lstm_")
    out, (h2, c2) = cell(emb, [mx.sym.Variable("h"),
                               mx.sym.Variable("c")])
    logits = mx.sym.FullyConnected(out, num_hidden=8, name="out_fc")

    def w(*shape):
        return mx.nd.array(
            rng.standard_normal(shape).astype(np.float32) * 0.5)

    dparams = {"emb_weight": w(8, 4),
               "lstm_i2h_weight": w(32, 4),
               "lstm_i2h_bias": mx.nd.zeros((32,)),
               "lstm_h2h_weight": w(32, 8),
               "lstm_h2h_bias": mx.nd.zeros((32,)),
               "out_fc_weight": w(8, 8),
               "out_fc_bias": mx.nd.zeros((8,))}
    step_sym = mx.sym.Group([logits, h2, c2])
    state_info = [{"name": "h", "shape": (8,)},
                  {"name": "c", "shape": (8,)}]
    dec = DecodeEngine(step_sym, dparams, {}, state_info, num_slots=2)
    dec.submit([1, 2], max_new_tokens=2, request_id="doc",
               tenant="doc").result(timeout=60)

    # --- planes that register via their family helpers --------------
    reg = telemetry.registry()
    regulator._regulator_metric_families(reg)
    supervisor._supervisor_metric_families(reg)
    from mxnet_tpu.telemetry.goodput import efficiency_metric_families
    efficiency_metric_families(reg)
    # the faults family registers lazily on the first fire; count a
    # no-op site/action pair rather than destabilizing a live engine
    faults._tm_count("serve.dispatch", "raise")

    # --- recorder + alert rules (burn-rate gauges ride /alerts, but
    # the recorder's own series land in the registry) ----------------
    telemetry.start_recorder()
    # one synchronous rule evaluation: the alert-state gauges register
    # there, and leaving it to the recorder thread's timer would make
    # the generated doc depend on scheduling
    telemetry.default_manager().evaluate(telemetry.get_recorder())

    # --- training-loop / data / kvstore instruments ------------------
    from mxnet_tpu.telemetry.step import StepTimer
    st = StepTimer(loop="doc")
    with st.step():
        pass
    kv = mx.kv.create("local")
    kv.init("doc", mx.nd.zeros((2,)))
    kv.push("doc", mx.nd.ones((2,)))
    kv.pull("doc", out=mx.nd.zeros((2,)))
    it = mx.io.NDArrayIter(np.zeros((4, 2), np.float32), batch_size=2,
                           label_name=None)
    next(iter(it))

    # collect() flushes the lock sanitizer's pending holds into its
    # families (registered inside its collect callback)
    reg.collect()
    eng.close()
    dec.close()
    telemetry.stop_recorder()
    return reg


def render(reg):
    doc = reg.collect()
    buf = io.StringIO()
    buf.write(_HEADER)
    for name in sorted(doc):
        fam = doc[name]
        labels = sorted({k for s in fam["series"]
                         for k in s["labels"]})
        # fall back to the family's declared labelnames when no
        # series is live yet
        live = reg.get(name)
        declared = getattr(live, "labelnames", None) or ()
        labels = sorted(set(labels) | set(declared))
        help_text = (fam.get("doc") or "").replace("|", "\\|") \
            .replace("\n", " ")
        buf.write("| `%s` | %s | %s | %s |\n"
                  % (name, fam["kind"],
                     ", ".join("`%s`" % l for l in labels) or "—",
                     help_text))
    buf.write("\n%d families.\n" % len(doc))
    buf.write(_EVENTS)
    return buf.getvalue()


def family_names(markdown):
    """Family names documented in a metrics.md body."""
    import re
    return set(re.findall(r"^\| `(mxnet_[a-z0-9_]+)` \|", markdown,
                          re.M))


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="generate docs/metrics.md from the live registry")
    ap.add_argument("--out", default=DOC_PATH,
                    help="output path ('-' = stdout)")
    ap.add_argument("--check", action="store_true",
                    help="exit 1 if docs/metrics.md is missing a live "
                         "family (CI drift gate); writes nothing")
    args = ap.parse_args(argv)

    if os.environ.get("_MXNET_METRICS_DOC_CHILD") != "1":
        # re-exec under the pinned env: plane construction reads these
        # at import/instantiation time, so mutating os.environ after
        # import would silently under-populate the registry
        env = dict(os.environ, _MXNET_METRICS_DOC_CHILD="1", **_ENV)
        import subprocess
        return subprocess.call([sys.executable,
                                os.path.abspath(__file__)]
                               + (argv if argv is not None
                                  else sys.argv[1:]), env=env)

    reg = populate_registry()
    text = render(reg)
    if args.check:
        try:
            with open(DOC_PATH) as f:
                documented = family_names(f.read())
        except OSError:
            print("metrics-doc drift: %s does not exist — run "
                  "`python tools/metrics_doc.py`" % DOC_PATH,
                  file=sys.stderr)
            return 1
        live = family_names(text)
        missing = sorted(live - documented)
        stale = sorted(documented - live)
        if missing:
            print("metrics-doc drift: %d undocumented famil%s:\n  %s\n"
                  "run `python tools/metrics_doc.py` and commit the "
                  "result" % (len(missing),
                              "y" if len(missing) == 1 else "ies",
                              "\n  ".join(missing)), file=sys.stderr)
            return 1
        if stale:
            # families documented but no longer constructible: warn
            # only — a removed family should disappear on regen, but
            # it must not block unrelated work
            print("note: %d documented famil%s not in the live "
                  "registry: %s" % (len(stale),
                                    "y" if len(stale) == 1 else "ies",
                                    ", ".join(stale)), file=sys.stderr)
        print("docs/metrics.md covers all %d live families"
              % len(live))
        return 0
    if args.out == "-":
        sys.stdout.write(text)
    else:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        tmp = args.out + ".tmp.%d" % os.getpid()
        with open(tmp, "w") as f:
            f.write(text)
        os.replace(tmp, args.out)
        print("wrote %s (%d families)"
              % (args.out, len(family_names(text))))
    return 0


if __name__ == "__main__":
    sys.exit(main())

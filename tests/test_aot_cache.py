"""Persistent AOT program cache tests (mxnet_tpu/serving/aot_cache.py).

Coverage per the issue contract: warm restart of a ServingEngine AND a
DecodeEngine performs ZERO XLA compiles for previously-served buckets
(compile counters pinned) and serves bitwise-identically to the
cold-start engine; adversarial paths — truncated/corrupted entries,
metadata tampering, fingerprint drift, concurrent writers racing one
key — always degrade to a fresh compile (counted as REJECTS when the
entry was present-but-unusable, never a wrong output); the reject-rate
default alert rule fires and the flight bundle names the key; replica
probation/re-warm (rehabilitate) re-admits a failed replica only after
a bitwise probe; the reload-loop leak gate extends over cache handles;
and the tools/aot_cache.py CLI (list/verify/prune) plus the
restart-bench smoke (cold > warm == 0 compiles, timing advisory-only
per the README host-noise protocol).
"""
import importlib.util
import json
import os
import sys
import threading
import warnings

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import serving, telemetry
from mxnet_tpu.serving import (DecodeEngine, ServingEngine,
                               greedy_decode)
from mxnet_tpu.serving.aot_cache import AOTCache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _import_tool(name):
    path = os.path.join(REPO, "tools", "%s.py" % name)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _mlp(feature=6, hidden=16, classes=4, seed=0):
    net = mx.sym.FullyConnected(mx.sym.Variable("data"),
                                num_hidden=hidden, name="fc1")
    net = mx.sym.Activation(net, act_type="relu", name="relu1")
    net = mx.sym.FullyConnected(net, num_hidden=classes, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    rng = np.random.default_rng(seed)
    params = {
        "fc1_weight": mx.nd.array(
            rng.standard_normal((hidden, feature)).astype(np.float32)),
        "fc1_bias": mx.nd.zeros((hidden,)),
        "fc2_weight": mx.nd.array(
            rng.standard_normal((classes, hidden)).astype(np.float32)),
        "fc2_bias": mx.nd.zeros((classes,)),
    }
    return net, params


def _sum_state_model(vocab=16, d=8, seed=0):
    """Step + one-dispatch prefill pair (tests/test_decode.py's toy):
    covers the decode step program AND the prefill ProgramCache path
    through one cache directory."""
    tok = mx.sym.Variable("token")
    s = mx.sym.Variable("s")
    emb = mx.sym.Embedding(tok, input_dim=vocab, output_dim=d,
                           name="emb")
    s2 = s + emb
    logits = mx.sym.FullyConnected(s2, num_hidden=vocab, name="out_fc")
    step = mx.sym.Group([logits, s2])
    prompt = mx.sym.Variable("prompt")
    plen = mx.sym.Variable("plen")
    pemb = mx.sym.Embedding(prompt, input_dim=vocab, output_dim=d,
                            name="emb")
    masked = mx.sym.SequenceMask(pemb, use_sequence_length=True,
                                 sequence_length=plen, axis=1)
    srow = mx.sym.sum(masked, axis=1)
    plogits = mx.sym.FullyConnected(srow, num_hidden=vocab,
                                    name="out_fc")
    prefill = mx.sym.Group([plogits, srow])
    rng = np.random.default_rng(seed)
    params = {
        "emb_weight": mx.nd.array(
            rng.standard_normal((vocab, d)).astype(np.float32)),
        "out_fc_weight": mx.nd.array(
            rng.standard_normal((vocab, d)).astype(np.float32)),
        "out_fc_bias": mx.nd.zeros((vocab,)),
    }
    return step, prefill, params, [{"name": "s", "shape": (d,)}]


@pytest.fixture
def cache_dir(tmp_path, monkeypatch):
    d = str(tmp_path / "aot")
    monkeypatch.setenv("MXNET_AOT_CACHE_DIR", d)
    monkeypatch.setenv("MXNET_AOT_CACHE", "1")
    return d


def _entries(d, suffix=".json"):
    return sorted(n for n in os.listdir(d) if n.endswith(suffix))


# ---------------------------------------------------------------------------
# the acceptance contract: warm restart = 0 compiles, bitwise identical
# ---------------------------------------------------------------------------

def test_serving_engine_warm_restart_zero_compiles_bitwise(cache_dir):
    net, params = _mlp()
    rng = np.random.default_rng(1)
    X = rng.standard_normal((8, 6)).astype(np.float32)
    e1 = ServingEngine(net, params, {}, {"data": (6,)})
    w1 = e1.warmup()
    ref = [e1.predict(x, timeout=60) for x in X]
    st1 = e1.stats()["aot"]
    e1.close()
    assert w1 > 0
    assert st1["misses"] == w1 and st1["writes"] == w1
    assert st1["hits"] == 0 and st1["rejects"] == 0
    assert len(_entries(cache_dir)) == w1

    # the restart: same graph, same policy, same dir -> every bucket
    # program loads from disk; the compile counter NEVER moves
    e2 = ServingEngine(net, params, {}, {"data": (6,)})
    assert e2.warmup() == 0
    got = [e2.predict(x, timeout=60) for x in X]
    st2 = e2.stats()
    assert e2.compile_count == 0 and st2["retraces"] == 0
    assert st2["aot"]["hits"] == w1
    assert st2["aot"]["misses"] == 0 == st2["aot"]["rejects"]
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(a, b)
    e2.close()


def test_decode_engine_warm_restart_zero_compiles_bitwise(cache_dir):
    step, prefill, params, state_info = _sum_state_model()
    prompts = [[1], [2, 3], [4, 5, 6]]
    e1 = DecodeEngine(step, params, {}, state_info, num_slots=2,
                      max_len=16, default_deadline_ms=0,
                      prefill_sym=prefill)
    w1 = e1.warmup()
    ref = [e1.generate(p, max_new_tokens=4, timeout=120).tokens
           for p in prompts]
    e1.close()
    assert w1 > 0

    e2 = DecodeEngine(step, params, {}, state_info, num_slots=2,
                      max_len=16, default_deadline_ms=0,
                      prefill_sym=prefill)
    assert e2.warmup() == 0          # step + row-writes + prefill
    got = [e2.generate(p, max_new_tokens=4, timeout=120).tokens
           for p in prompts]
    st = e2.stats()["decode"]
    assert st["compile_count"] == 0
    assert st["aot"]["hits"] == w1 and st["aot"]["rejects"] == 0
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(a, b)
    # and the warm engine still matches the single-request ground truth
    prog = e2._program
    for p, t in zip(prompts, got):
        np.testing.assert_array_equal(
            t, greedy_decode(prog, p, 4, max_len=16))
    e2.close()


def test_cache_off_by_default_and_kill_switch(tmp_path, monkeypatch):
    net, params = _mlp()
    eng = ServingEngine(net, params, {}, {"data": (6,)})
    assert eng._aot is None
    assert eng.stats()["aot"] == {"enabled": False}
    eng.close()
    # kill switch beats a configured directory
    monkeypatch.setenv("MXNET_AOT_CACHE_DIR", str(tmp_path / "a"))
    monkeypatch.setenv("MXNET_AOT_CACHE", "0")
    eng = ServingEngine(net, params, {}, {"data": (6,)})
    assert eng._aot is None
    eng.close()
    assert not os.path.exists(str(tmp_path / "a"))


# ---------------------------------------------------------------------------
# adversarial entries: corruption, tampering, drift -> reject + recompile
# ---------------------------------------------------------------------------

def test_truncated_payload_rejected_recompiled_and_healed(cache_dir):
    net, params = _mlp()
    x = np.ones((6,), np.float32)
    e1 = ServingEngine(net, params, {}, {"data": (6,)})
    w1 = e1.warmup()
    want = e1.predict(x, timeout=60)
    e1.close()
    for n in _entries(cache_dir, ".bin"):
        p = os.path.join(cache_dir, n)
        with open(p, "r+b") as f:       # truncate mid-payload
            f.truncate(os.path.getsize(p) // 2)

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        e2 = ServingEngine(net, params, {}, {"data": (6,)})
        w2 = e2.warmup()
    st = e2.stats()["aot"]
    # every entry was present-but-unusable: counted as REJECTS (the
    # alertable "cold start that should have been warm"), not misses,
    # recompiled fresh, and re-persisted (the cache self-heals)
    assert w2 == w1
    assert st["rejects"] == w1 and st["hits"] == 0 and st["misses"] == 0
    assert st["writes"] == w1
    assert "hash mismatch" in st["last_reject"]["reason"]
    np.testing.assert_array_equal(e2.predict(x, timeout=60), want)
    e2.close()

    # healed: the NEXT restart is warm again
    e3 = ServingEngine(net, params, {}, {"data": (6,)})
    assert e3.warmup() == 0
    np.testing.assert_array_equal(e3.predict(x, timeout=60), want)
    e3.close()


def test_metadata_tamper_and_fingerprint_drift_never_hit(cache_dir):
    net, params = _mlp()
    e1 = ServingEngine(net, params, {}, {"data": (6,)})
    w1 = e1.warmup()
    e1.close()
    # tamper every entry's recorded library version: the validity
    # fingerprint no longer matches -> reject, never served
    for n in _entries(cache_dir):
        p = os.path.join(cache_dir, n)
        meta = json.load(open(p))
        meta["fingerprint"]["library"] = "9.9.9-drifted"
        json.dump(meta, open(p, "w"))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        e2 = ServingEngine(net, params, {}, {"data": (6,)})
        assert e2.warmup() == w1
    st = e2.stats()["aot"]
    assert st["rejects"] == w1 and st["hits"] == 0
    assert "drift" in st["last_reject"]["reason"]
    e2.close()

    # a hostile / unparseable metadata file is a reject too, and an
    # unknown entry version refuses forward-compat guessing
    keys = _entries(cache_dir)
    open(os.path.join(cache_dir, keys[0]), "w").write("{not json")
    meta_p = os.path.join(cache_dir, keys[1])
    m = json.load(open(meta_p))
    m["version"] = 99
    json.dump(m, open(meta_p, "w"))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        e3 = ServingEngine(net, params, {}, {"data": (6,)})
        e3.warmup()
    assert e3.stats()["aot"]["rejects"] >= 2
    e3.close()


def test_policy_changes_miss_instead_of_hitting(cache_dir):
    """A different bucket policy is a DIFFERENT key: cold misses,
    never a cross-policy hit."""
    net, params = _mlp()
    e1 = ServingEngine(net, params, {}, {"data": (6,)},
                       policy=serving.BucketPolicy(max_batch=2))
    w1 = e1.warmup()
    e1.close()
    assert w1 == 2
    # same graph, wider policy: the shared buckets (1, 2) still differ
    # in key (policy is a key component) -> all misses
    e2 = ServingEngine(net, params, {}, {"data": (6,)},
                       policy=serving.BucketPolicy(max_batch=4))
    assert e2.warmup() == 3
    st = e2.stats()["aot"]
    assert st["hits"] == 0 and st["misses"] == 3
    e2.close()


def test_entry_key_anatomy(tmp_path):
    """Every component the issue names — graph, shapes, dtypes,
    policy, sharding, backend kind — moves the key; nothing else
    does."""
    import jax
    cache = AOTCache(str(tmp_path), key_extra={"max_batch": 8})
    net, _ = _mlp()
    other, _ = _mlp(hidden=17)
    from mxnet_tpu.serving.aot_cache import graph_digest
    g, g2 = graph_digest(net), graph_digest(other)
    args = [jax.ShapeDtypeStruct((4, 6), np.float32)]
    k0 = cache.entry_key("serve", g, args)
    assert k0 == cache.entry_key("serve", g, args)      # stable
    assert k0 != cache.entry_key("serve", g2, args)     # graph
    assert k0 != cache.entry_key("prefill", g, args)    # kind
    assert k0 != cache.entry_key(
        "serve", g, [jax.ShapeDtypeStruct((8, 6), np.float32)])
    assert k0 != cache.entry_key(
        "serve", g, [jax.ShapeDtypeStruct((4, 6), np.float16)])
    c2 = AOTCache(str(tmp_path), key_extra={"max_batch": 4})
    assert k0 != c2.entry_key("serve", g, args)         # policy
    c3 = AOTCache(str(tmp_path), key_extra={"max_batch": 8},
                  sharding="mesh2x2")
    assert k0 != c3.entry_key("serve", g, args)         # sharding
    # the validity fingerprint is metadata, NOT key material: two
    # caches with different artifacts share keys (drift is a REJECT at
    # load, distinguishable from a miss — the alertable event)
    c4 = AOTCache(str(tmp_path), key_extra={"max_batch": 8},
                  artifact={"verdicts": {"seq": "row-local"}})
    assert k0 == c4.entry_key("serve", g, args)
    assert cache.fingerprint() != c4.fingerprint()
    # the speculative policy component (ISSUE 15): k and the draft
    # digest each move the key; its ABSENCE equals the pre-spec key,
    # so a pre-spec cache volume stays warm across the upgrade
    s1 = AOTCache(str(tmp_path),
                  key_extra={"max_batch": 8,
                             "spec": {"k": 2, "draft": "d1"}})
    s_k = AOTCache(str(tmp_path),
                   key_extra={"max_batch": 8,
                              "spec": {"k": 4, "draft": "d1"}})
    s_d = AOTCache(str(tmp_path),
                   key_extra={"max_batch": 8,
                              "spec": {"k": 2, "draft": "d2"}})
    ks1 = s1.entry_key("decode_step", g, args)
    assert ks1 != cache.entry_key("decode_step", g, args)   # present
    assert ks1 != s_k.entry_key("decode_step", g, args)     # k
    assert ks1 != s_d.entry_key("decode_step", g, args)     # draft
    assert ks1 == AOTCache(
        str(tmp_path),
        key_extra={"max_batch": 8,
                   "spec": {"k": 2, "draft": "d1"}},
        artifact={"spec": {"k": 2}}).entry_key(
            "decode_step", g, args)     # artifact still not keyed


def test_concurrent_writers_racing_same_keys(cache_dir):
    """Two engines warming the same graph concurrently race every
    bucket key: both must succeed, the surviving entries must verify
    clean, and a third engine must load fully warm."""
    net, params = _mlp()
    x = np.ones((6,), np.float32)
    errs = []
    outs = [None, None]

    def build(i):
        try:
            eng = ServingEngine(net, params, {}, {"data": (6,)})
            eng.warmup()
            outs[i] = eng.predict(x, timeout=60)
            eng.close()
        except Exception as e:          # pragma: no cover - fail loud
            errs.append(e)

    ts = [threading.Thread(target=build, args=(i,)) for i in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(120)
    assert not errs
    np.testing.assert_array_equal(outs[0], outs[1])
    from mxnet_tpu.serving.aot_cache import iter_entries, verify_entry
    checked = 0
    for key, _mp, bin_path, meta in iter_entries(cache_dir):
        assert verify_entry(key, meta, bin_path) == []
        checked += 1
    assert checked == 4                 # one entry per bucket, no dupes
    assert not [n for n in os.listdir(cache_dir) if ".tmp." in n]
    e3 = ServingEngine(net, params, {}, {"data": (6,)})
    assert e3.warmup() == 0
    np.testing.assert_array_equal(e3.predict(x, timeout=60), outs[0])
    e3.close()


def test_unwritable_cache_dir_degrades_to_uncached(tmp_path,
                                                   monkeypatch):
    """A cache volume that cannot be created must not break serving —
    the engine warms exactly like the pre-cache path."""
    blocker = tmp_path / "blocker"
    blocker.write_text("a file where a directory must go")
    monkeypatch.setenv("MXNET_AOT_CACHE_DIR",
                       str(blocker / "nested"))
    net, params = _mlp()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        eng = ServingEngine(net, params, {}, {"data": (6,)})
        w = eng.warmup()
    assert eng._aot is None and w > 0
    np.testing.assert_array_equal(
        eng.predict(np.ones((6,), np.float32), timeout=60),
        eng.predict(np.ones((6,), np.float32), timeout=60))
    eng.close()


# ---------------------------------------------------------------------------
# telemetry + alerting: rejects are pageable, series reclaim at close
# ---------------------------------------------------------------------------

def _close_train_loops():
    """A worker process runs several files, and a train loop of an
    earlier one (``fit()``, ``Trainer.step``) never closes its
    StepTimer: its watchdog rule and ``train.<loop>`` heartbeat stay in
    the process-wide tables.  They are not these engines' to reclaim,
    so the leak gates below start from tables without them."""
    from mxnet_tpu.telemetry import recorder, step as step_mod
    for _gen, st in list(step_mod._DEFAULT.values()):
        st.close()
    for loop in list(step_mod._HB_LOOPS):   # timers collected unclosed
        del step_mod._HB_LOOPS[loop]
        recorder.unregister_heartbeat("train.%s" % loop)
    mgr = telemetry.default_manager()
    for rule in mgr.rules():
        if rule.name.startswith("train_") and rule.name.endswith("_stalled"):
            while mgr.state_of(rule.name) is not None:
                mgr.remove_rule(rule.name)


@pytest.fixture
def _fresh_telemetry():
    telemetry.set_enabled(None)
    telemetry.reset()
    _close_train_loops()
    telemetry.stop_server()
    telemetry.stop_recorder()
    yield
    telemetry.stop_server()
    telemetry.stop_recorder()
    telemetry.set_enabled(None)
    telemetry.reset()


def test_aot_counters_and_default_rule_reclaimed(cache_dir,
                                                 _fresh_telemetry):
    reg = telemetry.registry()
    mgr = telemetry.default_manager()
    net, params = _mlp()
    eng = ServingEngine(net, params, {}, {"data": (6,)})
    el = eng._tm.engine_label
    eng.warmup()
    fam = reg.get("mxnet_serve_aot_misses_total")
    assert fam is not None
    vals = {v: i.value for v, i in fam.series()}
    assert vals[(el,)] == 4
    # the aot-reject rule registered alongside the engine defaults
    assert any(r.name == "serve_engine%s_aot_reject" % el
               for r in mgr.rules())
    eng.close()
    # reclaim: per-engine aot series AND the rule are gone
    for what in ("hits", "misses", "writes", "rejects"):
        fam = reg.get("mxnet_serve_aot_%s_total" % what)
        assert fam is None or fam.series() == []
    assert len(mgr) == 0


def test_reject_rule_fires_and_bundle_names_key(cache_dir, tmp_path,
                                                _fresh_telemetry,
                                                monkeypatch):
    """The satellite contract: a compile on a present-but-unusable key
    increments rejects, the default rule fires on its rate, and the
    flight bundle (which captures engine stats()) names the key."""
    monkeypatch.setenv("MXNET_FLIGHT_RECORDER_DIR",
                       str(tmp_path / "flight"))
    # park the background sampler: the rule must fire at THIS test's
    # explicit sample/evaluate, not at a racing 1s tick mid-warmup
    # (which would dump the only bundle — per-reason rate limit —
    # before the last reject happened)
    monkeypatch.setenv("MXNET_TELEMETRY_HISTORY_SECS", "3600")
    net, params = _mlp()
    e1 = ServingEngine(net, params, {}, {"data": (6,)})
    e1.warmup()
    e1.close()
    corrupted = [n[:-len(".bin")] for n in _entries(cache_dir, ".bin")]
    for n in _entries(cache_dir, ".bin"):
        open(os.path.join(cache_dir, n), "wb").write(b"garbage")

    telemetry.reset()                   # pristine counters for delta
    mgr = telemetry.default_manager()
    e2 = ServingEngine(net, params, {}, {"data": (6,)})
    try:
        rec = telemetry.get_recorder()
        assert rec is not None
        t0 = rec.sample_now()
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            e2.warmup()                 # rejects fire here
        rec.sample_now()
        mgr.evaluate(rec, now=t0 + 1.0)
        el = e2._tm.engine_label
        states = {s["name"]: s for s in mgr.states()}
        assert states["serve_engine%s_aot_reject"
                      % el]["state"] == "firing"
        assert e2.stats()["aot"]["last_reject"]["key"] in corrupted
        bundles = sorted(os.listdir(str(tmp_path / "flight")))
        assert bundles, "no flight bundle on the reject-rule firing"
        doc = json.load(open(str(tmp_path / "flight" / bundles[0])))
        blob = json.dumps(doc)
        # the bundle NAMES a rejected key (stats().aot.last_reject
        # rides the engine-stats capture)
        assert any(k in blob for k in corrupted)
    finally:
        e2.close()


def test_reload_loop_with_cache_reclaims_everything(cache_dir,
                                                    _fresh_telemetry):
    """The reload-loop leak gate extended over cache handles: N warm
    engine generations leak no registry series, no rules, no stray
    cache tmp files, and no file descriptors."""
    reg = telemetry.registry()
    mgr = telemetry.default_manager()
    net, params = _mlp()
    step, prefill, sparams, state_info = _sum_state_model()
    # generation 0 populates the cache and warms process-level lazies
    eng = ServingEngine(net, params, {}, {"data": (6,)})
    eng.warmup()
    eng.close()
    fd_dir = "/proc/self/fd"
    fds0 = len(os.listdir(fd_dir)) if os.path.isdir(fd_dir) else None
    for _ in range(3):
        se = ServingEngine(net, params, {}, {"data": (6,)})
        de = DecodeEngine(step, sparams, {}, state_info, num_slots=2,
                          max_len=16, default_deadline_ms=0,
                          prefill_sym=prefill)
        assert se.warmup() == 0         # fully warm generations
        se.predict(np.ones((6,), np.float32), timeout=60)
        de.generate([1, 2], max_new_tokens=2, timeout=120)
        se.close()
        de.close()
    for what in ("hits", "misses", "writes", "rejects"):
        fam = reg.get("mxnet_serve_aot_%s_total" % what)
        assert fam is None or fam.series() == [], what
    assert len(mgr) == 0
    assert telemetry.heartbeats() == {}
    assert not [n for n in os.listdir(cache_dir) if ".tmp." in n]
    if fds0 is not None:
        assert len(os.listdir(fd_dir)) <= fds0 + 3


# ---------------------------------------------------------------------------
# replica probation / re-warm (ROADMAP follow-up a2)
# ---------------------------------------------------------------------------

def test_serving_replica_rehabilitation_bitwise_gated(cache_dir):
    net, params = _mlp()
    eng = ServingEngine(net, params, {}, {"data": (6,)},
                        ctx=[mx.cpu(0), mx.cpu(0)])
    eng.warmup()
    x = np.ones((6,), np.float32)
    want = eng.predict(x, timeout=60)
    eng._replicas[0].cache.run = lambda *a, **k: (
        (_ for _ in ()).throw(RuntimeError("induced failure")))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        with pytest.raises(RuntimeError, match="induced"):
            eng.predict(x, timeout=60)
        assert not eng._replicas[0].healthy
        st0 = eng.stats()["aot"]
        sib_compiles0 = eng._replicas[1].cache.compile_count
        out = eng.rehabilitate()
    assert out[0]["ok"] is True and out[0]["reason"] is None
    assert out[0]["warmed"] > 0
    st = eng.stats()
    assert [r["healthy"] for r in st["replicas"]] == [True, True]
    assert st["replicas"][0]["probations"] == 1
    # the probation warmup drew every program from the AOT cache: the
    # replica re-entered service without ONE fresh trace — and the
    # probe's reference dispatch never injected a compile into the
    # live sibling (the probe key is one the sibling already served)
    assert st["aot"]["hits"] > st0["hits"]
    assert st["aot"]["misses"] == st0["misses"]
    assert eng._replicas[1].cache.compile_count == sib_compiles0
    # the single-replica alias follows the swapped cache
    assert eng._cache is eng._replicas[0].cache
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        for _ in range(6):              # both replicas serve again
            np.testing.assert_array_equal(eng.predict(x, timeout=60),
                                          want)
    assert sum(r["batches"] for r in eng.stats()["replicas"]) \
        == eng.stats()["batches"]
    eng.close()


def test_serving_rehabilitation_probe_divergence_stays_retired():
    """A rehab candidate whose probe batch diverges bitwise from the
    healthy sibling must NOT re-enter service."""
    net, params = _mlp()
    eng = ServingEngine(net, params, {}, {"data": (6,)},
                        ctx=[mx.cpu(0), mx.cpu(0)])
    eng.warmup()
    x = np.ones((6,), np.float32)
    eng._replicas[0].cache.run = lambda *a, **k: (
        (_ for _ in ()).throw(RuntimeError("induced failure")))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        with pytest.raises(RuntimeError):
            eng.predict(x, timeout=60)
        # poison the rebuild source: the fresh cache now computes with
        # different weights than the healthy sibling serves
        _net2, params2 = _mlp(seed=9)
        eng._ctor["arg_params"] = params2
        out = eng.rehabilitate()
    assert out[0]["ok"] is False
    assert "diverged bitwise" in out[0]["reason"]
    assert not eng._replicas[0].healthy
    eng.close()


def test_decode_replica_rehabilitation(cache_dir):
    step, prefill, params, state_info = _sum_state_model()
    eng = DecodeEngine(step, params, {}, state_info, num_slots=2,
                       max_len=16, default_deadline_ms=0,
                       prefill_sym=prefill,
                       ctx=[mx.cpu(0), mx.cpu(0)])
    eng.warmup()
    want = eng.generate([1, 2], max_new_tokens=4, timeout=120).tokens
    bad = eng._replicas[0]
    orig_step = bad.program.step
    bad.program.step = lambda *a, **k: (
        (_ for _ in ()).throw(RuntimeError("induced step failure")))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        # a request pinned to replica 0 eats the failure (resolving
        # with partial output, finish_reason "error")
        for _ in range(10):
            if not bad.healthy:
                break
            eng.generate([1], max_new_tokens=2, timeout=120)
        assert not bad.healthy
        out = eng.rehabilitate()
    assert out == [{"replica": "0", "ok": True, "reason": None}]
    st = eng.stats()["decode"]
    assert [r["healthy"] for r in st["replicas"]] == [True, True]
    assert st["replicas"][0]["probations"] == 1
    del orig_step
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        # the rehabilitated replica takes traffic again, bitwise
        for _ in range(4):
            got = eng.generate([1, 2], max_new_tokens=4,
                               timeout=120).tokens
            np.testing.assert_array_equal(got, want)
    eng.close()


def test_rehabilitation_needs_a_healthy_sibling():
    net, params = _mlp()
    eng = ServingEngine(net, params, {}, {"data": (6,)},
                        ctx=[mx.cpu(0), mx.cpu(0)])
    eng.warmup()
    for rep in eng._replicas:
        rep.cache.run = lambda *a, **k: (
            (_ for _ in ()).throw(RuntimeError("dead")))
    x = np.ones((6,), np.float32)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        for _ in range(2):
            with pytest.raises(RuntimeError):
                eng.predict(x, timeout=60)
        out = eng.rehabilitate()
    assert len(out) == 2
    assert all(not o["ok"] for o in out)
    assert all("sibling" in o["reason"] for o in out)
    eng.close()


# ---------------------------------------------------------------------------
# CLI: list / verify / prune
# ---------------------------------------------------------------------------

def test_cli_list_verify_prune(cache_dir, capsys):
    net, params = _mlp()
    eng = ServingEngine(net, params, {}, {"data": (6,)})
    w = eng.warmup()
    eng.close()
    tool = _import_tool("aot_cache")

    assert tool.main(["--dir", cache_dir, "list"]) == 0
    out = capsys.readouterr().out
    assert "serve" in out and ("%d entries" % w) in out
    assert tool.main(["--dir", cache_dir, "list", "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert len(doc["entries"]) == w and doc["total_bytes"] > 0

    assert tool.main(["--dir", cache_dir, "verify"]) == 0
    capsys.readouterr()

    # environment drift (entry written under another jax/library):
    # verify must flag it — load() would reject it, so "clean verify
    # == warm restart" demands a nonzero exit — and --no-env-check
    # must allow auditing another platform's volume
    metas = _entries(cache_dir)
    mp = os.path.join(cache_dir, metas[0])
    m = json.load(open(mp))
    saved = m["fingerprint"]["jax"]
    m["fingerprint"]["jax"] = "0.0.1-elsewhere"
    json.dump(m, open(mp, "w"))
    assert tool.main(["--dir", cache_dir, "verify"]) == 1
    assert "drift" in capsys.readouterr().out
    assert tool.main(["--dir", cache_dir, "verify",
                      "--no-env-check"]) == 0
    capsys.readouterr()
    m["fingerprint"]["jax"] = saved
    json.dump(m, open(mp, "w"))

    # corrupt one payload: verify must FAIL with a nonzero exit
    bins = _entries(cache_dir, ".bin")
    open(os.path.join(cache_dir, bins[0]), "ab").write(b"x")
    assert tool.main(["--dir", cache_dir, "verify"]) == 1
    out = capsys.readouterr().out
    assert "UNSOUND" in out and "hash mismatch" in out

    # prune by age removes everything (all entries are newborn, so
    # --max-age-s 0 catches them); dry-run first touches nothing
    assert tool.main(["--dir", cache_dir, "prune", "--max-age-s", "0",
                      "--dry-run"]) == 0
    capsys.readouterr()
    assert len(_entries(cache_dir)) == w
    assert tool.main(["--dir", cache_dir, "prune",
                      "--max-age-s", "0"]) == 0
    capsys.readouterr()
    assert _entries(cache_dir) == [] and _entries(cache_dir, ".bin") == []

    # size-budget prune: rebuild, then evict oldest-first to ~one entry
    eng = ServingEngine(net, params, {}, {"data": (6,)})
    eng.warmup()
    eng.close()
    sizes = [os.path.getsize(os.path.join(cache_dir, n))
             for n in _entries(cache_dir, ".bin")]
    keep_mb = (max(sizes) + 1) / (1024.0 * 1024.0)
    assert tool.main(["--dir", cache_dir, "prune",
                      "--max-total-mb", str(keep_mb)]) == 0
    capsys.readouterr()
    assert len(_entries(cache_dir)) >= 1
    assert len(_entries(cache_dir)) < w
    assert tool.main(["--dir", cache_dir, "verify"]) == 0
    capsys.readouterr()


def test_cli_no_dir_exits_2(monkeypatch, capsys):
    monkeypatch.delenv("MXNET_AOT_CACHE_DIR", raising=False)
    tool = _import_tool("aot_cache")
    with pytest.raises(SystemExit) as e:
        tool.main(["list"])
    assert e.value.code == 2
    capsys.readouterr()


# ---------------------------------------------------------------------------
# restart-bench smoke (tier-1 CI): cold > warm == 0, timing advisory
# ---------------------------------------------------------------------------

def test_restart_bench_smoke(tmp_path):
    perf_dir = os.path.join(REPO, "perf")
    sys.path.insert(0, perf_dir)
    try:
        import restart_bench
    finally:
        sys.path.remove(perf_dir)
    record = str(tmp_path / "BENCH_aot.json")
    # --no-xla-cache: jax's persistent compilation cache is
    # process-global config; the suite must stay hermetic
    rc = restart_bench.main([
        "--feature", "6", "--hidden", "16", "--layers", "2",
        "--classes", "3", "--requests", "4", "--step-hidden", "8",
        "--step-layers", "1", "--vocab", "11", "--decode-requests",
        "2", "--max-new", "3", "--no-xla-cache", "--record", record])
    assert rc == 0
    doc = json.load(open(record))
    for kind in ("serve", "decode"):
        assert doc[kind]["cold"]["compiles"] > 0
        assert doc[kind]["warm"]["compiles"] == 0       # the hard gate
        assert doc[kind]["bitwise_equal"] is True
        assert doc[kind]["warm"]["aot"]["hits"] \
            == doc[kind]["cold"]["compiles"]
        # timing is recorded for humans; NOT asserted (README
        # host-noise protocol: single samples on shared hosts)
        assert doc[kind]["ready_speedup"] > 0
    assert doc["cache_entries"] == (doc["serve"]["cold"]["compiles"]
                                    + doc["decode"]["cold"]["compiles"])

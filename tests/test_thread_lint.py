"""Concurrency soundness gate: static thread linter + lock sanitizer.

CI contract (mirrors test_graph_lint): `tools/thread_lint.py --strict`
must exit 0 over the whole installed package — every lock-order cycle,
blocking-call-under-lock, cond-wait and lifecycle-pairing finding is
either fixed or allowlisted with a written justification.  The
deliberate-defect fixtures under tests/fixtures/ pin that the linter
still FIRES (a lint that cannot fail gates nothing), and the runtime
sanitizer half (MXNET_LOCK_SANITIZER=1, mxnet_tpu/locks.py surfaced as
serving.locks) is pinned to observe zero inversions on a live engine
with the same outputs sanitizer-on vs -off.
"""
import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LINT = os.path.join(REPO, "tools", "thread_lint.py")
FIXTURES = os.path.join(REPO, "tests", "fixtures")


def _lint(*args, **kw):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(kw.pop("env", {}))
    return subprocess.run([sys.executable, LINT] + list(args),
                          capture_output=True, text=True, env=env,
                          cwd=REPO)


# -- the CI bar: the shipped tree lints clean under --strict -----------------

def test_tree_lints_clean_strict():
    """Exit 0 over the whole package: no unjustified findings.  The
    allowlist rows still print with their justifications — suppression
    moves the exit code, never hides the finding."""
    r = _lint("--strict")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "CLEAN" in r.stdout
    assert "0 errors, 0 warnings" in r.stdout


def test_tree_json_model_shape():
    """--json carries the full model: the serving/telemetry named
    locks, the hold-edge graph, and zero cycles."""
    r = _lint("--json")
    assert r.returncode == 0, r.stdout + r.stderr
    out = json.loads(r.stdout)
    ids = {l["id"] for l in out["locks"]}
    for name in ("serve.engine", "serve.route", "serve.programs.build",
                 "decode.replica", "supervisor.state",
                 "telemetry.family", "telemetry.registry"):
        assert name in ids, name
    assert out["cycles"] == []
    assert out["exit"] == 0
    # adopted names are marked as sanitizer-named (merge keys)
    named = {l["id"] for l in out["locks"] if l["named"]}
    assert "serve.engine" in named and "telemetry.family" in named


# -- deliberate defects must fire --------------------------------------------

def test_inversion_fixture_exits_1_without_strict():
    """A lock-order cycle is an ERROR: exit 1 even non-strict, with
    both witness sites named."""
    r = _lint("--files", os.path.join(FIXTURES, "lint_inversion.py"),
              "--no-allowlist")
    assert r.returncode == 1, r.stdout + r.stderr
    assert "lock-order cycle" in r.stdout
    assert "lint_inversion:ab" in r.stdout
    assert "lint_inversion:ba" in r.stdout


def test_inversion_fixture_json_finding():
    r = _lint("--files", os.path.join(FIXTURES, "lint_inversion.py"),
              "--no-allowlist", "--json")
    assert r.returncode == 1
    out = json.loads(r.stdout)
    assert out["exit"] == 1
    fds = [f for f in out["findings"] if f["pass"] == "lock-order"]
    assert len(fds) == 1 and fds[0]["severity"] == "error"
    assert len(out["cycles"]) == 1


def test_blocking_fixture_warns_strict_gates():
    """blocking-under-lock and cond-wait are WARNINGs: exit 0
    non-strict, exit 1 under --strict."""
    path = os.path.join(FIXTURES, "lint_blocking.py")
    r = _lint("--files", path, "--no-allowlist")
    assert r.returncode == 0, r.stdout + r.stderr
    r = _lint("--files", path, "--no-allowlist", "--strict", "--json")
    assert r.returncode == 1
    passes = {f["pass"] for f in json.loads(r.stdout)["findings"]}
    assert passes == {"lock-blocking", "cond-wait"}


def test_allowlist_suppresses_with_provenance(tmp_path):
    """An allowlist row keyed (pass, node, op) suppresses exactly its
    finding, keeps the justification attached, and the run goes
    strict-clean only when EVERY finding is justified."""
    path = os.path.join(FIXTURES, "lint_blocking.py")
    allow = [
        {"pass": "lock-blocking", "node": "lint_blocking:slow_under_lock",
         "op": "time.sleep",
         "justification": "fixture: sleep stands in for a bounded "
                          "single-flight build"},
        {"pass": "cond-wait", "node": "lint_blocking:wait_no_loop",
         "op": "lint_blocking.COND",
         "justification": "fixture: one-shot latch, notify cannot "
                          "precede the wait here"},
    ]
    ap = tmp_path / "allow.json"
    ap.write_text(json.dumps(allow))
    r = _lint("--files", path, "--strict", "--allowlist", str(ap),
              "--json")
    assert r.returncode == 0, r.stdout + r.stderr
    out = json.loads(r.stdout)
    assert out["findings"] == []
    assert len(out["suppressed"]) == 2
    assert all(f["suppressed_by"] for f in out["suppressed"])
    # drop one row -> the uncovered finding gates again
    ap.write_text(json.dumps(allow[:1]))
    r = _lint("--files", path, "--strict", "--allowlist", str(ap))
    assert r.returncode == 1


def test_bad_allowlist_exits_2(tmp_path):
    """TODO justifications and malformed rows are load failures (exit
    2), not silent suppressions."""
    ap = tmp_path / "allow.json"
    ap.write_text(json.dumps([
        {"pass": "lock-blocking", "node": "x",
         "justification": "TODO: justify later"}]))
    r = _lint("--allowlist", str(ap))
    assert r.returncode == 2
    assert "TODO" in r.stderr
    ap.write_text(json.dumps([{"pass": "lock-blocking"}]))
    assert _lint("--allowlist", str(ap)).returncode == 2
    assert _lint("--allowlist", str(tmp_path / "nope.json")) \
        .returncode == 2


def test_unparseable_source_exits_2(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("def broken(:\n")
    r = _lint("--files", str(bad), "--no-allowlist")
    assert r.returncode == 2
    assert "cannot analyze" in r.stderr


def test_merge_observed_closes_static_cycle(tmp_path):
    """Static analysis sees only fix.a -> fix.b; a sanitizer dump's
    observed fix.b -> fix.a edge closes the cycle on the SAME named
    nodes — the static/runtime graph join the named locks exist for."""
    dump = tmp_path / "obs.json"
    dump.write_text(json.dumps({"edges": [
        {"src": "fix.b", "dst": "fix.a", "site": "decode worker"}]}))
    path = os.path.join(FIXTURES, "lint_order_ab.py")
    r = _lint("--files", path, "--no-allowlist")
    assert r.returncode == 0, r.stdout + r.stderr     # acyclic alone
    r = _lint("--files", path, "--no-allowlist",
              "--merge-observed", str(dump))
    assert r.returncode == 1
    assert "observed" in r.stdout and "fix.a -> fix.b -> fix.a" \
        in r.stdout


# -- the sanitizer half ------------------------------------------------------

def test_sanitizer_off_returns_raw_primitives():
    """MXNET_LOCK_SANITIZER=0 (default): named_lock IS threading.Lock
    — zero wrapper objects, zero recording, nothing to pay on the
    dispatch path (the faults.py zero-overhead discipline)."""
    from mxnet_tpu.serving import locks as sl
    sl.disable()
    try:
        lk = sl.named_lock("t.off")
        assert type(lk) is type(threading.Lock())
        assert isinstance(sl.named_rlock("t.off2"),
                          type(threading.RLock()))
        cond = sl.named_condition("t.off3")
        assert isinstance(cond, threading.Condition)
        with lk:
            pass
        assert sl.observed_edges() == {}
        assert sl.hold_stats() == {}
    finally:
        sl.reset()


def test_sanitizer_records_edges_holds_and_inversions():
    from mxnet_tpu.serving import locks as sl
    sl.enable()
    try:
        a, b = sl.named_lock("t.a"), sl.named_lock("t.b")
        with a:
            with b:
                pass
        edges = sl.observed_edges()
        assert ("t.a", "t.b") in edges
        assert edges[("t.a", "t.b")]["count"] == 1
        assert sl.observed_inversions() == []
        sl.assert_no_inversions()
        hs = sl.hold_stats()
        assert hs["t.a"]["count"] == 1 and hs["t.b"]["count"] == 1
        assert hs["t.a"]["total_s"] >= hs["t.b"]["total_s"]
        # now the inversion
        with b:
            with a:
                pass
        inv = sl.observed_inversions()
        assert len(inv) == 1
        with pytest.raises(sl.LockInversionError):
            sl.assert_no_inversions()
    finally:
        sl.reset()


def test_sanitizer_condition_wait_releases_held_set():
    """Condition(wrapper) must pop the sanitizer held-set during
    wait(): a waiter holding only the condition's lock records no
    edge against the notifier's acquisitions."""
    from mxnet_tpu.serving import locks as sl
    sl.enable()
    try:
        cond = sl.named_condition("t.cv")
        other = sl.named_lock("t.other")
        done = []

        def notifier():
            with other:
                pass          # acquired while the waiter sleeps
            with cond:
                done.append(1)
                cond.notify()

        with cond:
            t = threading.Thread(target=notifier, daemon=True)
            t.start()
            while not done:
                cond.wait(5.0)
        t.join(5.0)
        # wait() released t.cv: the notifier's `other` acquisition
        # happened with an EMPTY held-set, no t.cv->t.other edge
        assert ("t.cv", "t.other") not in sl.observed_edges()
        assert sl.observed_inversions() == []
    finally:
        sl.reset()


_SMOKE = r"""
import json, os, sys
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import serving

net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=8,
                            name="fc1")
net = mx.sym.SoftmaxOutput(net, name="softmax")
rng = np.random.default_rng(7)
params = {
    "fc1_weight": mx.nd.array(
        rng.standard_normal((8, 6)).astype(np.float32)),
    "fc1_bias": mx.nd.zeros((8,)),
}
X = rng.standard_normal((32, 6)).astype(np.float32)
with serving.ServingEngine(net, params, {}, {"data": (6,)},
                           ctx=mx.cpu(), batch_timeout_ms=2.0) as eng:
    import threading
    outs = [None] * len(X)
    def client(t):
        for i in range(t, len(X), 4):
            outs[i] = eng.predict(X[i], timeout=30)
    ts = [threading.Thread(target=client, args=(t,)) for t in range(4)]
    for t in ts: t.start()
    for t in ts: t.join()
from mxnet_tpu import locks as L
from mxnet_tpu import telemetry
print(json.dumps({
    "outs": np.asarray(outs, np.float32).tolist(),
    "enabled": L.enabled(),
    "inversions": len(L.observed_inversions()),
    "edges": len(L.observed_edges()),
    "instrument_calls": telemetry.registry().instrument_calls(),
}))
"""


def _run_smoke(sanitizer):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               MXNET_LOCK_SANITIZER=sanitizer, MXNET_TELEMETRY_ON="0")
    r = subprocess.run([sys.executable, "-c", _SMOKE],
                       capture_output=True, text=True, env=env,
                       cwd=REPO, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_sanitizer_smoke_bitwise_identical_and_no_inversions():
    """The acceptance pin: a concurrent serving run under
    MXNET_LOCK_SANITIZER=1 observes zero inversions, and its outputs
    are those of the sanitizer-off run (the sanitizer may measure,
    never steer) — to a few ulp, not bitwise: four client threads
    coalesce into whatever batch extents the timing gives, and another
    extent is another XLA program (test_utils.few_ulp_tol).  Off-mode
    performs zero instrument calls and records nothing."""
    from mxnet_tpu.test_utils import assert_almost_equal, few_ulp_tol
    off = _run_smoke("0")
    on = _run_smoke("1")
    for got, want in zip(np.asarray(on["outs"], np.float32),
                         np.asarray(off["outs"], np.float32)):
        assert_almost_equal(got, want, *few_ulp_tol(want))
    assert not off["enabled"] and off["edges"] == 0
    assert off["instrument_calls"] == 0
    assert on["enabled"] and on["inversions"] == 0
    assert on["edges"] > 0          # engine locks really did nest


@pytest.mark.slow
@pytest.mark.parametrize("testfile", ["test_decode.py",
                                      "test_serving.py",
                                      "test_selfheal.py"])
def test_tier1_suites_under_sanitizer_no_inversions(testfile, tmp_path):
    """Full decode/serve/self-heal suites once under the sanitizer:
    zero observed lock-order inversions across everything tier-1
    exercises, via the MXNET_LOCK_SANITIZER_DUMP atexit report."""
    dump = tmp_path / "locks.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               MXNET_LOCK_SANITIZER="1",
               MXNET_LOCK_SANITIZER_DUMP=str(dump))
    r = subprocess.run(
        [sys.executable, "-m", "pytest",
         os.path.join("tests", testfile), "-q", "-m", "not slow",
         "-p", "no:cacheprovider"],
        capture_output=True, text=True, env=env, cwd=REPO,
        timeout=1200)
    assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-4000:]
    stats = json.loads(dump.read_text())
    assert stats["inversions"] == [], stats["inversions"]
    assert stats["edges"], "sanitizer observed no lock nesting at all"
    # and the observed edges merge into the static model cycle-free
    lint = _lint("--merge-observed", str(dump), "--strict")
    assert lint.returncode == 0, lint.stdout + lint.stderr

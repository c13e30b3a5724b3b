"""Persistent AOT program cache — restarts and scale-ups that cost
nothing (ROADMAP item 2).

Compilation has been a *process-lifetime* artifact since PR 1: every
serving program traces at first dispatch, so a reload loop pays the
full retrace storm and replica N+1 joining under load stalls on
compilation — the single worst production failure mode the ROADMAP
names.  TVM (arxiv 1802.04799) made deployment cheap by treating
compiled programs as *deployment artifacts*; this module does the same
for the serving tier's XLA programs: the compiled decode step of arxiv
2603.09555 is exactly the kind of program that should never be
compiled twice for the same (graph, shapes, dtypes, policy, backend).

Mechanism
---------
On a cache **miss**, the first compile of a program is routed through
``jax.export``: the one Python/jax trace that would have happened
anyway produces a serialized StableHLO module, written to a
content-addressed on-disk entry (atomic tmp+rename — concurrent
writers racing one key are safe, last rename wins and both payloads
are identical by construction).  On a **hit**, the entry is
deserialized and served through ``jax.jit(exported.call)`` — the
symbol-graph interpreter and per-op jax tracing are skipped entirely,
so the repo's compile counters (``CachedOp.trace_count``,
``StepProgram.trace_count`` — the numbers every compile-once test
pins) stay at ZERO for warm programs, and a warm engine serves
bitwise-identically to a cold one (same StableHLO, same executable).

Key anatomy (``entry_key``)
---------------------------
``sha256(kind x graph canonical form x flat input signature (shapes +
dtypes, params included) x policy x sharding x backend platform)``.
Weights are runtime *inputs* to every serving program, so a new
checkpoint with the same architecture hits the same entries — programs
are weight-independent deployment artifacts.

The *validity fingerprint* — jax/library versions, device kind, and
the analysis-artifact digest (padding verdicts, repair plan, optimizer
plan, bucket grid) — lives in the entry's metadata, NOT the key, and
is re-validated on load.  A mismatch is a **reject** (present but
unusable: the entry names a program this process must not serve), and
is counted separately from a miss so "cold start that should have been
warm" is an alertable event (``mxnet_serve_aot_rejects_total`` +
the ``serve_engine<N>_aot_reject`` default rule); folding those fields
into the key would silently turn drift into misses and the alert could
never fire.

Failure discipline: every cache code path degrades to a fresh compile
— a truncated payload, a hostile metadata file, a missing jax.export,
an unwritable directory all warn (at most once per cause) and fall
back to exactly the pre-cache behavior.  The cache can make a restart
cheap; it must never make serving wrong.

Fleet sharing caveat: entries are keyed by backend *platform*, and the
finer device kind is fingerprint-checked on load, so a shared cache
volume across a homogeneous fleet means one process compiles and the
fleet loads warm.  Heterogeneous fleets (mixed TPU generations) reject
each other's entries rather than serve a mis-targeted program.

Env knobs: ``MXNET_AOT_CACHE_DIR`` (empty = off),
``MXNET_AOT_CACHE=0`` (kill switch).  CLI: ``tools/aot_cache.py``
(list / verify / prune).
"""
from __future__ import annotations

import hashlib
import json
import os
import threading

from .locks import named_lock
import time
import warnings

import numpy as np

__all__ = ["AOTCache", "graph_digest", "artifact_digest",
           "resolve_kernel", "iter_entries", "verify_entry",
           "ENTRY_VERSION"]

ENTRY_VERSION = 1

# one warning per failure cause per process: a reload loop over a bad
# cache volume must not spam one warning per bucket per engine
_WARNED = set()
_WARN_LOCK = named_lock("aot.warn")


def _warn_once(cause, msg):
    with _WARN_LOCK:
        if cause in _WARNED:
            return
        _WARNED.add(cause)
    warnings.warn(msg)


def _sha(data):
    return hashlib.sha256(data).hexdigest()


def _canon(obj):
    """Canonical JSON for hashing: sorted keys, no whitespace drift."""
    return json.dumps(obj, sort_keys=True, separators=(",", ":"),
                      default=str)


def graph_digest(symbol):
    """Content digest of one symbol graph's canonical JSON form — the
    'graph' component of every entry key built over it."""
    return _sha(symbol.tojson().encode("utf-8"))


def artifact_digest(artifact):
    """Digest of the construction-time analysis artifact (verdicts,
    repair plan, optimizer plan, bucket grid) an engine bakes into its
    entries' validity fingerprint."""
    return _sha(_canon(artifact or {}).encode("utf-8"))


def _fingerprint(artifact):
    """The validity fingerprint checked (not keyed) on load."""
    import jax
    try:
        dev = jax.devices()[0]
        device_kind = getattr(dev, "device_kind", "unknown")
    except Exception:
        device_kind = "unknown"
    from .. import __version__ as _libver
    return {"jax": jax.__version__,
            "library": _libver,
            "device_kind": device_kind,
            "artifact": artifact_digest(artifact)}


def _signature(args):
    """Flat (shape, dtype) signature of one program's arguments, in
    argument order — concrete arrays and ShapeDtypeStructs both
    reduce to their avals."""
    sig = []
    for a in args:
        shape = tuple(int(d) for d in np.shape(a))
        dtype = str(np.dtype(getattr(a, "dtype", None) or
                             np.asarray(a).dtype))
        sig.append([list(shape), dtype])
    return sig


class AOTCache(object):
    """Content-addressed on-disk cache of AOT-serialized XLA programs.

    One instance per engine (shared by every replica's program caches,
    step programs, and prefill caches): the per-engine counters —
    ``hits`` / ``misses`` / ``writes`` / ``rejects`` — feed that
    engine's ``mxnet_serve_aot_*_total`` series and ``stats()["aot"]``
    block, and ``last_reject`` names the offending key so a flight
    bundle captured on the reject-rate alert carries the evidence.

    ``artifact`` is the engine's construction-time analysis artifact
    (verdict/repair/optimizer/bucket-grid summary): its digest rides
    every entry's validity fingerprint, so an entry written under
    different analysis conclusions is rejected on load, never served.
    ``key_extra`` folds engine policy (bucket grid, sampler kind,
    slot-pool geometry) into every entry key.
    """

    def __init__(self, directory, artifact=None, key_extra=None,
                 sharding="none"):
        self.dir = os.path.abspath(directory)
        self.enabled = True
        self.artifact = artifact or {}
        self.key_extra = key_extra or {}
        # "none" for single-device programs, the ShardingPlan spec dict
        # for pjit-sharded ones (ROADMAP residual b2): it rides every
        # entry KEY (canonical JSON) — two plans, or a plan and its
        # unsharded twin, can never hit each other's entries — and the
        # metadata verbatim, so `tools/aot_cache.py list` renders it
        self.sharding = sharding if isinstance(sharding, dict) \
            else str(sharding)
        self._fp = None                 # computed lazily (needs jax)
        self._lock = named_lock("aot.cache")
        self.hits = 0
        self.misses = 0
        self.writes = 0
        self.rejects = 0
        self.prunes = 0
        self.last_reject = None         # {"key","reason","time"}
        # write-path size budget (MXNET_AOT_CACHE_MAX_MB): > 0 prunes
        # oldest-first after every store() until the volume fits
        from .. import config
        self.max_bytes = int(
            config.get("MXNET_AOT_CACHE_MAX_MB") * (1 << 20))
        # bound telemetry children, set post-construction by the
        # engine's bundle (None with telemetry off): (hits, misses,
        # writes, rejects) counter instances
        self._tm = None
        try:
            os.makedirs(self.dir, exist_ok=True)
        except OSError as e:
            _warn_once(("mkdir", self.dir),
                       "AOT cache: cannot create %r (%r); persistent "
                       "compilation disabled for this process"
                       % (self.dir, e))
            self.enabled = False

    @classmethod
    def from_config(cls, artifact=None, key_extra=None, sharding="none"):
        """Build from the MXNET_AOT_CACHE* env tier; None when off."""
        from .. import config
        if not config.get("MXNET_AOT_CACHE"):
            return None
        directory = config.get("MXNET_AOT_CACHE_DIR").strip()
        if not directory:
            return None
        cache = cls(directory, artifact=artifact, key_extra=key_extra,
                    sharding=sharding)
        if not cache.enabled:
            return None
        # MXNET_AOT_XLA_CACHE: 'auto' (default) turns jax's persistent
        # compilation cache on ONLY when the serving entrypoint owns
        # process bring-up — this engine is being constructed before
        # any program traced, so flipping process-global jax config
        # cannot surprise an application that compiled first (ROADMAP
        # residual b1).  '1' forces it on (the reset_cache latch makes
        # late enabling effective anyway); '0' is the explicit opt-out.
        xla = str(config.get("MXNET_AOT_XLA_CACHE")).strip().lower()
        if xla in ("1", "true", "yes", "on"):
            _enable_xla_cache(os.path.join(cache.dir, "xla"))
        elif xla in ("auto", ""):
            from ..executor import xla_traces_ever
            if xla_traces_ever() == 0:
                _enable_xla_cache(os.path.join(cache.dir, "xla"))
        return cache

    # ------------------------------------------------------------ metrics
    def bind_telemetry(self, hits, misses, writes, rejects, prunes):
        """Attach the engine's bound ``mxnet_serve_aot_*_total``
        counter children and catch them up to events that happened
        before the telemetry bundle existed (nothing does today —
        program resolution is lazy, post-construction — but the
        catch-up keeps the counters honest if that ever changes)."""
        with self._lock:
            self._tm = (hits, misses, writes, rejects, prunes)
            for child, v in zip(self._tm, (self.hits, self.misses,
                                           self.writes, self.rejects,
                                           self.prunes)):
                if v:
                    child.inc(v)

    def _count(self, which, amount=1):
        with self._lock:
            setattr(self, which, getattr(self, which) + amount)
            tm = self._tm
        if tm is not None:
            tm[("hits", "misses", "writes", "rejects",
                "prunes").index(which)].inc(amount)

    def _reject(self, key, reason):
        self.last_reject = {"key": key, "reason": reason,
                            "time": time.time()}
        self._count("rejects")
        _warn_once(("reject", key, reason),
                   "AOT cache: entry %s is present but unusable (%s); "
                   "falling back to a fresh compile" % (key[:16], reason))

    def stats(self):
        with self._lock:
            return {"enabled": True,
                    "dir": self.dir, "hits": self.hits,
                    "misses": self.misses, "writes": self.writes,
                    "rejects": self.rejects, "prunes": self.prunes,
                    "max_bytes": self.max_bytes or None,
                    # key-anatomy visibility: the fused-op selection
                    # the engine's optimizer adopted (decode engines;
                    # None elsewhere).  It rides the validity
                    # FINGERPRINT via the artifact, so toggling
                    # selection between restarts REJECTS every entry
                    # the previous regime wrote instead of serving a
                    # stale program
                    "selection": (self.artifact or {}).get("selection"),
                    "last_reject": dict(self.last_reject)
                    if self.last_reject else None}

    # --------------------------------------------------------------- keys
    def fingerprint(self):
        if self._fp is None:
            self._fp = _fingerprint(self.artifact)
        return self._fp

    def entry_key(self, kind, graph, args, policy=None):
        """Content address of one program: ``kind`` (serve / prefill /
        decode_step / decode_set_row), the graph digest, the flat
        argument signature, the engine's policy extras (``policy``
        overrides ``key_extra`` — ``{}`` for universal kernels whose
        program cannot depend on engine policy), the sharding plan,
        and the backend platform."""
        import jax
        parts = {"v": ENTRY_VERSION, "kind": kind, "graph": graph,
                 "signature": _signature(args),
                 "policy": self.key_extra if policy is None else policy,
                 "sharding": self.sharding,
                 "platform": jax.default_backend()}
        return _sha(_canon(parts).encode("utf-8"))

    def _paths(self, key):
        return (os.path.join(self.dir, key + ".json"),
                os.path.join(self.dir, key + ".bin"))

    # ----------------------------------------------------------- load/store
    def load(self, key):
        """Load one entry: the deserialized ``jax.export.Exported`` on
        a hit, None on a miss (absent) OR a reject (present but
        unusable: corrupt payload, hash mismatch, fingerprint drift —
        counted and named, never served)."""
        meta_path, bin_path = self._paths(key)
        try:
            with open(meta_path, "rb") as f:
                meta = json.loads(f.read().decode("utf-8"))
        except FileNotFoundError:
            self._count("misses")
            return None
        except (OSError, ValueError, UnicodeDecodeError) as e:
            self._reject(key, "unreadable metadata (%r)" % (e,))
            return None
        try:
            payload = open(bin_path, "rb").read()
        except FileNotFoundError:
            # not corruption: a janitor prune removes metadata first,
            # so a loader racing it sees a vanished entry — a MISS,
            # never a paging reject
            self._count("misses")
            return None
        except OSError as e:
            self._reject(key, "unreadable payload (%r)" % (e,))
            return None
        from . import faults as _faults
        if _faults.ACTIVE:
            # chaos seam: a firing corrupt clause flips payload bytes
            # BEFORE the integrity checks — the hash mismatch below
            # must catch it, reject the entry, and self-heal with a
            # fresh compile (the path the aot_reject alert watches)
            payload = _faults.corrupt_bytes("aot.load", payload,
                                            key=key[:16])
        if not isinstance(meta, dict) \
                or meta.get("version") != ENTRY_VERSION:
            self._reject(key, "unknown entry version %r"
                         % (meta.get("version")
                            if isinstance(meta, dict) else None))
            return None
        if meta.get("sha256") != _sha(payload):
            self._reject(key, "payload hash mismatch (truncated or "
                              "corrupted entry)")
            return None
        got_fp = meta.get("fingerprint")
        if not isinstance(got_fp, dict):
            got_fp = {}                 # hostile metadata: full drift
        if got_fp != self.fingerprint():
            drift = [k for k in self.fingerprint()
                     if got_fp.get(k) != self.fingerprint()[k]]
            self._reject(key, "fingerprint drift (%s)"
                         % ",".join(sorted(drift)))
            return None
        try:
            from jax import export as jexport
            exported = jexport.deserialize(payload)
        except Exception as e:
            self._reject(key, "deserialization failed (%r)" % (e,))
            return None
        self._count("hits")
        return exported

    def store(self, key, payload, meta_extra=None):
        """Atomically persist one entry: payload first, metadata last
        (the metadata file is the commit marker a loader keys on), both
        via tmp+``os.replace`` so a reader never sees a torn write and
        two engines racing the same key both succeed."""
        meta_path, bin_path = self._paths(key)
        meta = {"version": ENTRY_VERSION, "key": key,
                "created": time.time(),
                "sha256": _sha(payload), "size": len(payload),
                "fingerprint": self.fingerprint(),
                "artifact": self.artifact,
                "policy": self.key_extra,
                "sharding": self.sharding}
        meta.update(meta_extra or {})
        tmp_suffix = ".tmp.%d.%d" % (os.getpid(),
                                     threading.get_ident())
        tmp = None
        try:
            for path, data in ((bin_path, payload),
                               (meta_path,
                                json.dumps(meta, indent=1,
                                           default=str).encode("utf-8"))):
                tmp = path + tmp_suffix
                with open(tmp, "wb") as f:
                    f.write(data)
                    f.flush()
                    os.fsync(f.fileno())
                os.replace(tmp, path)
                tmp = None
        except OSError as e:
            if tmp is not None:
                # a half-written tmp on a full volume must not pile up
                # (a reload loop would worsen the very disk pressure
                # that failed the write)
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
            _warn_once(("store", self.dir),
                       "AOT cache: cannot write under %r (%r); this "
                       "process keeps serving from its in-memory "
                       "programs" % (self.dir, e))
            return False
        self._count("writes")
        if self.max_bytes > 0:
            self._auto_prune(protect=key)
        return True

    def _auto_prune(self, protect=None):
        """Best-effort oldest-first eviction down to the
        ``MXNET_AOT_CACHE_MAX_MB`` budget, run on the write path
        (ROADMAP b3).  Concurrent-writer tolerant by construction:
        the commit-marker metadata file is removed FIRST (a reader
        racing it sees a vanished entry — a plain miss, never a
        paging reject; load() already has that contract) and every
        unlink tolerates ENOENT (the other writer's prune got there
        first).  ``protect`` exempts the just-written key — a store
        must never evict its own entry, however tight the budget."""
        try:
            entries = []
            for key, meta_path, bin_path, meta in iter_entries(self.dir):
                size = 0
                for p in (meta_path, bin_path):
                    try:
                        size += os.path.getsize(p)
                    except OSError:
                        pass
                entries.append((key, meta_path, bin_path, size))
            total = sum(e[3] for e in entries)
            for key, meta_path, bin_path, size in entries:
                if total <= self.max_bytes:
                    break
                if key == protect:
                    continue
                for p in (meta_path, bin_path):  # marker first
                    try:
                        os.unlink(p)
                    except OSError:
                        pass
                total -= size
                self._count("prunes")
        except Exception:
            # janitoring must never break the store that triggered it
            pass


_XLA_CACHE_SET = False


def _enable_xla_cache(directory):
    """MXNET_AOT_XLA_CACHE: point jax's persistent compilation cache
    at a subdirectory of the AOT cache volume, once per process (the
    first engine wins; an operator-set ``jax_compilation_cache_dir``
    is never overridden).  The AOT entries remove the Python/jax trace
    from a warm restart; this removes XLA's compile of the
    deserialized module too — the executable itself loads from disk.
    Thresholds are zeroed so small serving programs qualify."""
    global _XLA_CACHE_SET
    if _XLA_CACHE_SET:
        return
    import jax
    try:
        if jax.config.jax_compilation_cache_dir:
            _XLA_CACHE_SET = True       # operator already configured it
            return
        jax.config.update("jax_compilation_cache_dir", directory)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                          -1)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          0.0)
        # jax latches "cache disabled" at the first compile that ran
        # before the dir was configured (params upload, warmers);
        # re-initialize so the knob takes effect mid-process
        from jax._src import compilation_cache as _cc
        _cc.reset_cache()
        _XLA_CACHE_SET = True
    except Exception as e:
        _warn_once(("xla_cache",),
                   "AOT cache: cannot enable jax's persistent "
                   "compilation cache (%r); warm restarts still skip "
                   "tracing but pay the XLA compile" % (e,))


def _avals(args):
    """Arguments -> ShapeDtypeStructs for export tracing (concrete
    arrays pass through: jax.export takes either).

    Mesh shardings propagate: an argument committed under a
    ``NamedSharding`` (a ShardingPlan's param/state placement, or a
    data aval the program cache built with the plan's spec) keeps it,
    so the exported program records the pjit partitioning and a warm
    load serves the identical partitioned StableHLO.  Single-device
    commits deliberately do NOT propagate — an unsharded entry must
    stay device-anonymous so any replica (or a restarted process on a
    different device ordinal) can load it."""
    import jax
    from jax.sharding import NamedSharding
    out = []
    for a in args:
        if a is None:
            raise ValueError("unresolved argument slot")
        sharding = getattr(a, "sharding", None)
        if isinstance(sharding, NamedSharding):
            out.append(jax.ShapeDtypeStruct(
                tuple(np.shape(a)),
                np.dtype(getattr(a, "dtype", None)
                         or np.asarray(a).dtype),
                sharding=sharding))
            continue
        out.append(jax.ShapeDtypeStruct(
            tuple(np.shape(a)),
            np.dtype(getattr(a, "dtype", None) or np.asarray(a).dtype)))
    return out


def resolve_kernel(cache, jit_fn, kind, graph, args, meta_extra=None,
                   donate_argnums=(), universal=False):
    """Resolve one compiled program through the cache.

    Returns ``(kernel, source)`` where ``kernel`` is the callable the
    program cache's dispatch plan should hold and ``source`` is one of
    ``"hit"`` (loaded from disk — ZERO traces), ``"miss"`` (compiled
    fresh via one jax.export trace, persisted), or ``"off"`` (cache
    disabled or export unavailable — ``jit_fn`` verbatim, exactly the
    pre-cache path).

    The miss path serves through the same ``jax.jit(exported.call)``
    wrapper a hit does: cold and warm processes execute the identical
    serialized StableHLO, which is what makes the bitwise cache-parity
    contract trivially true rather than empirically hoped for.

    ``donate_argnums`` must repeat the original jit fn's donation
    spec: jax.export does NOT carry donation through the round trip
    (an outer ``jax.jit(exported.call)`` with no donate spec aliases
    nothing), so the caller's in-place-update contract — the decode
    slot pool living in HBM — is re-applied on the wrapper here.

    ``universal=True`` keys the entry WITHOUT the cache's per-engine
    policy extras — for kernels (row scatter) whose program cannot
    depend on engine policy, so every engine and sampler config
    shares one entry instead of re-persisting duplicates.
    """
    if cache is None or not cache.enabled:
        return jit_fn, "off"
    import jax
    try:
        key = cache.entry_key(kind, graph, args,
                              policy={} if universal else None)
    except Exception as e:
        _warn_once(("key", kind),
                   "AOT cache: cannot key a %s program (%r); serving "
                   "it uncached" % (kind, e))
        return jit_fn, "off"
    try:
        exported = cache.load(key)
    except Exception as e:
        # belt over load()'s own braces: NOTHING a cache volume can
        # contain may crash a dispatch — degrade to a fresh compile
        _warn_once(("load", kind),
                   "AOT cache: loading a %s entry failed (%r); "
                   "compiling fresh" % (kind, e))
        exported = None
    # hit and miss serve under the name of ``jit_fn``'s program, so
    # that the device's trace calls a loaded program what it calls a
    # freshly compiled one
    from ..base import named_program
    name = getattr(jit_fn, "__name__", "call")
    if exported is not None:
        return jax.jit(named_program(exported.call, name),
                       donate_argnums=donate_argnums), "hit"
    try:
        from jax import export as jexport
        exp = jexport.export(jit_fn)(*_avals(args))
        payload = exp.serialize()
    except Exception as e:
        _warn_once(("export", kind),
                   "AOT cache: jax.export cannot serialize a %s "
                   "program (%r); serving it uncached" % (kind, e))
        return jit_fn, "off"
    extra = dict(meta_extra or {}, kind=kind, graph=graph,
                 signature=_signature(args))
    if universal:
        # the entry's KEY was built with no engine policy — record
        # that truthfully (store() would otherwise stamp the cache's
        # key_extra, and tools/aot_cache.py list would render a
        # policy the key never contained)
        extra["policy"] = {}
    cache.store(key, payload, extra)
    return jax.jit(named_program(exp.call, name),
                   donate_argnums=donate_argnums), "miss"


# --------------------------------------------------------------------------
# offline entry inspection (tools/aot_cache.py)
# --------------------------------------------------------------------------

def iter_entries(directory):
    """Yield ``(key, meta_path, bin_path, meta_or_None)`` for every
    committed entry (metadata file present) under ``directory``,
    oldest first.  Unparseable metadata yields ``meta=None`` so
    ``verify`` can fail it instead of skipping it silently."""
    try:
        names = sorted(n for n in os.listdir(directory)
                       if n.endswith(".json"))
    except OSError:
        return
    entries = []
    for n in names:
        key = n[:-len(".json")]
        meta_path = os.path.join(directory, n)
        bin_path = os.path.join(directory, key + ".bin")
        try:
            meta = json.loads(open(meta_path, "rb").read()
                              .decode("utf-8"))
            if not isinstance(meta, dict):
                meta = None
        except (OSError, ValueError, UnicodeDecodeError):
            meta = None
        entries.append((key, meta_path, bin_path, meta))
    entries.sort(key=lambda e: (e[3] or {}).get("created", 0.0))
    for e in entries:
        yield e


def verify_entry(key, meta, bin_path, deep=True, env_check=True):
    """Offline integrity check of one entry: metadata shape, payload
    hash, (``deep``) an actual jax.export load, and (``env_check``)
    the environment half of the validity fingerprint — jax/library
    versions and device kind — against THIS process.  The last check
    is what makes "a clean verify means tomorrow's restart loads
    warm" true: a hash-sound entry written under a different jax is
    still one ``load()`` will reject.  The artifact half is engine-
    specific and unknowable offline, so it is not checked here.
    Returns a list of problem strings — empty means sound."""
    problems = []
    if meta is None:
        return ["unreadable or non-dict metadata"]
    if meta.get("version") != ENTRY_VERSION:
        problems.append("unknown entry version %r" % (meta.get("version"),))
    if meta.get("key") not in (None, key):
        problems.append("metadata key %r does not match filename"
                        % (meta.get("key"),))
    if env_check:
        fp = meta.get("fingerprint")
        fp = fp if isinstance(fp, dict) else {}
        cur = _fingerprint(None)
        drift = [k for k in ("jax", "library", "device_kind")
                 if fp.get(k) != cur[k]]
        if drift:
            problems.append(
                "fingerprint drift (%s): load() will reject this "
                "entry — a restart pays a cold compile"
                % ",".join(drift))
    try:
        payload = open(bin_path, "rb").read()
    except OSError as e:
        return problems + ["unreadable payload (%r)" % (e,)]
    if meta.get("size") is not None and meta["size"] != len(payload):
        problems.append("payload size %d != recorded %d"
                        % (len(payload), meta["size"]))
    if meta.get("sha256") != _sha(payload):
        problems.append("payload hash mismatch (truncated or corrupted)")
    elif deep:
        try:
            from jax import export as jexport
            jexport.deserialize(payload)
        except Exception as e:
            problems.append("deserialization failed (%r)" % (e,))
    return problems

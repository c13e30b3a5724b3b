"""What every driver shares: files found by name, the compile counters,
host spans that also go into the profiler's trace, the traced
sub-window, and the checks that decide ``correct``.
"""
import contextlib
import importlib.util
import json
import os
import shutil
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")
TRACE_SECONDS = 4.0        # the traced part of a --trace 1 window


def load_module(*parts):
    """The module in ``benchmark/<parts...>.py``, loaded by path (names
    of configurations and metrics hold ``-`` and ``.``)."""
    path = os.path.join(HERE, *parts) + ".py"
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    name = "bench_" + "_".join(parts).replace("-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def percentile(values, q):
    """Linear-interpolated percentile, q in [0, 100]."""
    v = sorted(values)
    if not v:
        return None
    k = (len(v) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (k - lo)


class CompileCounters(object):
    """Compile requests and persistent-cache hits and misses, off
    ``jax.monitoring``: a program XLA builds again without a new trace
    still shows as a request."""
    _EVENTS = {"/jax/compilation_cache/compile_requests_use_cache":
               "requests",
               "/jax/compilation_cache/cache_hits": "hits",
               "/jax/compilation_cache/cache_misses": "misses"}

    def __init__(self):
        import jax
        self.counts = {"requests": 0, "hits": 0, "misses": 0}
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_kw):
        key = self._EVENTS.get(event)
        if key:
            self.counts[key] += 1

    def snapshot(self):
        return dict(self.counts)

    def since(self, before):
        return {k: self.counts[k] - before[k] for k in before}


class Spans(object):
    """Host spans by name as (start, end) on ``time.perf_counter``.
    While a trace is being taken each span is also written into it as a
    ``bench:<name>`` annotation, which puts it on the device's clock."""

    def __init__(self):
        self.by_name = {}
        self.tracing = False
        self._lock = threading.Lock()

    @contextlib.contextmanager
    def span(self, name):
        ann = None
        if self.tracing:
            import jax
            ann = jax.profiler.TraceAnnotation("bench:" + name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            t1 = time.perf_counter()
            if ann is not None:
                ann.__exit__(None, None, None)
            with self._lock:
                self.by_name.setdefault(name, []).append((t0, t1))

    def wrap(self, obj, attr, name):
        """Replace ``obj.attr`` by a version of it inside a span: the
        program's layers are timed from outside, at their boundary."""
        inner = getattr(obj, attr)

        def timed(*args, **kwargs):
            with self.span(name):
                return inner(*args, **kwargs)
        setattr(obj, attr, timed)

    def within(self, name, lo, hi):
        return [(a, b) for a, b in self.by_name.get(name, [])
                if a >= lo and b <= hi]


class Tracer(object):
    """One ``jax.profiler`` trace of part of the window, kept at a fixed
    place inside the checkout and removed once it is reduced."""

    def __init__(self, cell, spans):
        self.dir = os.path.join(ROOT, ".bench_trace", cell)
        self.spans = spans
        self.started = self.stopped = None
        self._window = None

    def start(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.spans.tracing = True
        self._window = jax.profiler.TraceAnnotation("bench:window")
        self._window.__enter__()
        self.started = time.perf_counter()

    def stop(self):
        import jax
        self.stopped = time.perf_counter()
        self._window.__exit__(None, None, None)
        self.spans.tracing = False
        jax.profiler.stop_trace()

    @property
    def active(self):
        return self.started is not None and self.stopped is None

    def reduce(self):
        from benchmark import trace_reduce
        trace = trace_reduce.load(self.dir)
        window = [(s, s + d) for n, s, d in trace["spans"]
                  if n == "bench:window"]
        if not window:
            raise RuntimeError("the trace holds no bench:window span")
        out = trace_reduce.reduce(trace, window=window[0])
        shutil.rmtree(self.dir, ignore_errors=True)
        return out


class Checks(object):
    """The numbers compared with the plain reference, each beside its
    limit.  ``correct`` is every number at or under its limit."""

    def __init__(self):
        self.rows = []

    def add(self, name, value, limit):
        self.rows.append((name, float(value), float(limit)))

    @property
    def correct(self):
        return bool(self.rows) and all(
            v == v and v <= lim for _n, v, lim in self.rows)

    def as_dict(self):
        return {n: {"value": v, "limit": lim} for n, v, lim in self.rows}

    def lines(self):
        return ["check %s = %.6g (limit %.6g)%s" % (
            n, v, lim, "" if v == v and v <= lim else "  <-- OVER")
            for n, v, lim in self.rows]


def memory_peak_bytes(devices, log=None):
    """Peak HBM held on the fullest chip, as ``memory_stats()`` reports
    it: the arrays' high-water mark plus the region the TPU runtime
    reserves for the temporaries of loaded programs, which it counts
    apart (``peak_bytes_reserved``; 10.2 GB of a ResNet-50 step's
    11.2)."""
    peaks = []
    for d in devices:
        st = d.memory_stats() or {}
        if log is not None:
            log("memory stats of %s: %s" % (d, dict(st)))
        if "peak_bytes_in_use" in st:
            peaks.append(int(st["peak_bytes_in_use"])
                         + int(st.get("peak_bytes_reserved", 0)))
    return max(peaks) if peaks else None


def idle_share(obs):
    """Share of the traced window in which no operation ran on the
    device: 1 - union of the device's operation intervals over the
    window, mean over the cell's chips (the ``device_idle_share.*``
    readers, one name for each end-to-end metric it moves)."""
    t = obs.get("trace")
    if not t or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def leaf_gaps(got, want):
    """For every leaf the gap between the program's norm and the
    reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger (some gradients are all but zero).
    A leaf the program lacks counts as a norm of nought."""
    floor = percentile(list(want.values()), 50)
    return {k: abs(got.get(k, 0.0) - w) / max(w, floor, 1e-30)
            for k, w in want.items()}

"""The device's time by compiled program, and its idle time under the
program's own spans, from a ``jax.profiler`` trace.

Beside the "XLA Ops" line that ``trace_reduce`` reads, each
``/device:TPU:n`` plane has an "XLA Modules" line: the device's record of
every program run, named ``jit_<function>(<fingerprint>)``, with its
start and duration.  The program names its hot-path programs
(``mxnet_tpu.base.named_program``: ``jit_mx_decode_step``,
``jit_mx_decode_prefill``, ``jit_mx_forward_backward`` ...), so the runs
of a decode step and of a prefill can be told apart on the device.  The
program's own spans are the host annotations named ``mx:<span>``
(``mxnet_tpu/telemetry/timeline.py``), on the same clock.

This module adds to ``trace_reduce`` and changes nothing of it: the same
trace gives the same ``device_ops`` and ``idle_gaps``; :func:`reduce`
adds three keys.

- ``programs``: for each program name (fingerprint dropped) on the
  busiest chip inside the window, ``runs`` (runs that overlap it),
  ``device_s`` (their seconds inside it) and ``median_ms`` (the median
  of the runs that lie wholly inside it; None where none does), the
  programs with most seconds first;
- ``named_busy_share``: the share of the busiest chip's busy time (the
  union of its operations) that lies inside runs whose names start
  ``jit_mx_``;
- ``idle_gaps_program``: the idle gaps as ``idle_gaps`` divides them, by
  ``trace_reduce.apportion``'s rule (an instant goes to the innermost
  span that holds it), among the program's ``mx:`` spans in place of the
  benchmark's ``bench:`` ones.

    python3 benchmark/trace_programs.py <trace dir or .xplane.pb>
    python3 benchmark/trace_programs.py <trace> --trim <out.xplane.pb>

``--trim`` writes the trace with only what this module and
``trace_reduce`` read (:func:`trimmed`): the form a recorded trace takes
as a test fixture.
"""
import glob
import json
import os
import sys

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmark import trace_reduce as tr    # noqa: E402

MODULES_LINE = "XLA Modules"
MARK_PREFIX = "mx:"
NAMED_PREFIX = "jit_mx_"
OUTSIDE = "outside the program's spans"


def program_name(name):
    """'jit_call(8626864232011996728)' -> 'jit_call'."""
    return name.split("(", 1)[0].strip()


def newest_file(trace_dir):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise RuntimeError("no .xplane.pb under %s" % trace_dir)
    return files[-1]


def load_file(path, profile_data=None):
    """``trace_reduce.load_file``'s lists, and ``"modules"``:
    ``{plane name: [(program name, start, dur)]}``, and ``"marks"``: the
    host's ``mx:`` annotations as ``[(name, start, dur)]``."""
    if profile_data is None:
        from jax.profiler import ProfileData as profile_data
    trace = tr.load_file(path, profile_data)
    modules, marks = {}, []
    for plane in profile_data.from_file(path).planes:
        if plane.name.startswith(tr.DEVICE_PLANE):
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules[plane.name] = [
                        (program_name(e.name), float(e.start_ns),
                         float(e.duration_ns)) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                marks += [(e.name, float(e.start_ns), float(e.duration_ns))
                          for e in line.events
                          if e.name.startswith(MARK_PREFIX)]
    return dict(trace, modules=modules, marks=marks)


def load(path):
    """:func:`load_file` of ``path``, or of the newest trace under it."""
    return load_file(path if os.path.isfile(path) else newest_file(path))


def programs(runs, lo, hi):
    """``{name: {"runs", "device_s", "median_ms"}}`` of one chip's
    program runs ``[(name, start, dur)]`` in the window [lo, hi]."""
    out = {}
    for name, s, d in runs:
        a, b = max(s, lo), min(s + d, hi)
        if b <= a:
            continue
        p = out.setdefault(name, {"runs": 0, "device_ns": 0.0, "whole": []})
        p["runs"] += 1
        p["device_ns"] += b - a
        if lo <= s and s + d <= hi:
            p["whole"].append(d)
    ranked = sorted(out.items(), key=lambda kv: -kv[1]["device_ns"])
    return {name: {"runs": p["runs"], "device_s": p["device_ns"] / 1e9,
                   "median_ms": _median(p["whole"]) / 1e6
                   if p["whole"] else None}
            for name, p in ranked}


def _median(values):
    v = sorted(values)
    n = len(v)
    return v[n // 2] if n % 2 else (v[n // 2 - 1] + v[n // 2]) / 2.0


def named_busy_share(ops, runs, lo, hi, prefix=NAMED_PREFIX):
    """Share of the busy time of one chip's operations ``ops`` in [lo, hi]
    that lies inside its runs ``runs`` whose names start ``prefix``."""
    busy = tr.merge([(s, s + d) for _n, s, d in tr.clip(ops, lo, hi)])
    named = tr.merge([(s, s + d) for n, s, d in tr.clip(runs, lo, hi)
                      if n.startswith(prefix)])
    total = sum(b - a for a, b in busy)
    if not total:
        return None
    inside, j = 0.0, 0
    for a, b in busy:
        while j < len(named) and named[j][1] <= a:
            j += 1
        k = j
        while k < len(named) and named[k][0] < b:
            inside += min(b, named[k][1]) - max(a, named[k][0])
            k += 1
    return inside / total


def idle_gaps_program(ops, marks, lo, hi, top=10):
    """The idle gaps of one chip's operations in [lo, hi], divided among
    the ``mx:`` spans ``marks`` as ``trace_reduce.reduce`` divides them
    among the benchmark's: ``[[span, seconds]]``, most first."""
    union = tr.merge([(s, s + d) for _n, s, d in tr.clip(ops, lo, hi)])
    # apportion() drops the benchmark's prefix from a label: hand it the
    # names behind that prefix, and the label is the mx: name
    spans = [(tr.SPAN_PREFIX + n, s, d) for n, s, d in marks]
    edges = [lo] + [x for iv in union for x in iv] + [hi]
    gaps = {}
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            for what, ns in tr.apportion(a, b, spans).items():
                what = OUTSIDE if what == tr.OUTSIDE else what
                gaps[what] = gaps.get(what, 0.0) + ns
    return [[k, v / 1e9] for k, v in sorted(
        gaps.items(), key=lambda kv: -kv[1])[:top]]


def reduce(trace, window=None, top=10):
    """``trace_reduce.reduce`` of ``trace`` (a :func:`load_file` dict),
    with ``programs``, ``named_busy_share`` and ``idle_gaps_program`` of
    its busiest chip beside what it gives."""
    out = tr.reduce(trace, window=window, top=top)
    ops = trace["devices"][out["busiest"]]
    if window is None:
        lo = min(e[1] for evs in trace["devices"].values() for e in evs)
        hi = max(e[1] + e[2] for evs in trace["devices"].values()
                 for e in evs)
    else:
        lo, hi = window
    runs = trace.get("modules", {}).get(out["busiest"], [])
    out["programs"] = programs(runs, lo, hi)
    out["named_busy_share"] = named_busy_share(ops, runs, lo, hi)
    out["idle_gaps_program"] = idle_gaps_program(
        ops, trace.get("marks", []), lo, hi, top)
    return out


def reduce_window(trace):
    """:func:`reduce` over the trace's ``bench:window`` span, where it
    has one, as the benchmark's ``Tracer.reduce`` takes it."""
    window = [(s, s + d) for n, s, d in trace["spans"]
              if n == tr.SPAN_PREFIX + "window"]
    return reduce(trace, window=window[0] if window else None)


def _quoted(text):
    return '"%s"' % text.replace("\\", "\\\\").replace('"', '\\"') \
        .replace("\n", "\\n")


def trimmed(path, profile_data=None):
    """The trace at ``path`` as a serialized XSpace that holds only what
    the readers read: each TPU plane's "XLA Modules" and "XLA Ops" lines
    and the host's ``bench:`` and ``mx:`` annotations, every event with
    its name, start and duration and nothing else (a few steps of a
    recorded trace are megabytes, mostly the operations' statistics and
    the runtime's own host events)."""
    if profile_data is None:
        from jax.profiler import ProfileData as profile_data
    out = []
    for p_id, plane in enumerate(profile_data.from_file(path).planes, 1):
        if plane.name.startswith(tr.DEVICE_PLANE):
            lines = [(line.name, list(line.events)) for line in plane.lines
                     if line.name in (MODULES_LINE, tr.OPS_LINE)]
        elif plane.name.startswith("/host:"):
            lines = [(line.name, [e for e in line.events if e.name.startswith(
                (tr.SPAN_PREFIX, MARK_PREFIX))]) for line in plane.lines]
            lines = [(n, evs) for n, evs in lines if evs]
        else:
            continue
        names = {}
        body = []
        for l_id, (name, events) in enumerate(lines, 1):
            body.append("lines { id: %d name: %s timestamp_ns: 0"
                        % (l_id, _quoted(name)))
            for e in events:
                k = names.setdefault(e.name, len(names) + 1)
                body.append(" events { metadata_id: %d offset_ps: %d "
                            "duration_ps: %d }"
                            % (k, round(e.start_ns * 1e3),
                               round(e.duration_ns * 1e3)))
            body.append(" }\n")
        meta = ["event_metadata { key: %d value { id: %d name: %s } }\n"
                % (k, k, _quoted(n)) for n, k in names.items()]
        out.append("planes { id: %d name: %s\n%s%s}\n"
                   % (p_id, _quoted(plane.name), "".join(body),
                      "".join(meta)))
    return profile_data.text_proto_to_serialized_xspace("".join(out))


if __name__ == "__main__":
    if "--trim" in sys.argv:
        with open(sys.argv[sys.argv.index("--trim") + 1], "wb") as f:
            f.write(trimmed(sys.argv[1] if os.path.isfile(sys.argv[1])
                            else newest_file(sys.argv[1])))
        sys.exit(0)
    r = reduce_window(load(sys.argv[1]))
    print(json.dumps({k: r[k] for k in (
        "window_s", "busy_s", "busiest", "programs", "named_busy_share",
        "idle_gaps", "idle_gaps_program")}))

"""From a ``jax.profiler`` trace to numbers: device busy time, per-op
sums, and the device's idle time divided by what the host was doing in
it.

The reduction works on plain lists of ``(name, start_ns, duration_ns)``
so that it can be checked by hand; ``load`` turns an ``.xplane.pb`` into
those lists.  Device events are the "XLA Ops" line of each
``/device:TPU:n`` plane.  The "Async XLA Ops" line, which holds copies
and collectives in flight that overlap the ops, is not busy time of its
own and is not read: what a collective costs a step, and how much of it
nothing covers, has to come from that line and is a later reader's
(PERF.md section 7).  Host spans are the events the benchmark wrote itself with
``jax.profiler.TraceAnnotation`` under names starting ``bench:``; both
are on the profiler's one clock.
"""
import glob
import os

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench:"


def short_name(name):
    """'%fusion.1 = (bf16[128]...) fusion(...)' -> 'fusion.1'."""
    return name.split(" = ", 1)[0].lstrip("%").strip()[:80]


def load(trace_dir):
    """``{"devices": {plane name: [(name, start, dur)]},
    "spans": [(name, start, dur)]}`` from the newest trace under
    ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise RuntimeError("no .xplane.pb under %s" % trace_dir)
    return load_file(files[-1], ProfileData)


def load_file(path, profile_data=None):
    if profile_data is None:
        from jax.profiler import ProfileData as profile_data
    devices, spans = {}, []
    for plane in profile_data.from_file(path).planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        (e.name, float(e.start_ns), float(e.duration_ns))
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, float(e.start_ns), float(e.duration_ns))
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    return {"devices": devices, "spans": spans}


def merge(intervals):
    """Sorted disjoint union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def covered(intervals):
    return sum(e - s for s, e in merge(intervals))


def clip(events, lo, hi):
    """Events cut to the window [lo, hi]; those outside are dropped."""
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


OUTSIDE = "outside the benchmark's spans"


def apportion(lo, hi, spans):
    """The gap [lo, hi] divided among the benchmark's spans: each instant
    goes to the innermost (shortest) span that holds it, or to OUTSIDE.
    Returns {label: nanoseconds}."""
    inside = [(max(lo, s), min(hi, s + d), d, name)
              for name, s, d in spans if min(hi, s + d) > max(lo, s)]
    cuts = sorted({lo, hi} | {x for a, b, _d, _n in inside for x in (a, b)})
    out = {}
    for a, b in zip(cuts, cuts[1:]):
        held = [(d, name) for sa, sb, d, name in inside if sa <= a and sb >= b]
        what = min(held)[1][len(SPAN_PREFIX):] if held else OUTSIDE
        out[what] = out.get(what, 0.0) + (b - a)
    return out


def reduce(trace, window=None, top=10):
    """Numbers of one traced window.  ``window`` is (start_ns, end_ns)
    on the trace's clock; by default from the first to the last device
    event.  Times are seconds; per-chip numbers are means over the
    chips, and ``busiest`` names the chip with most busy time."""
    devices = trace["devices"]
    if not devices:
        raise RuntimeError("the trace holds no %s* plane with an %r line"
                           % (DEVICE_PLANE, OPS_LINE))
    if window is None:
        lo = min(e[1] for evs in devices.values() for e in evs)
        hi = max(e[1] + e[2] for evs in devices.values() for e in evs)
    else:
        lo, hi = window
    per_chip = {}
    for plane, events in devices.items():
        events = clip(events, lo, hi)
        ivals = [(s, s + d) for _n, s, d in events]
        per_chip[plane] = {
            "events": events, "busy_ns": covered(ivals), "n_ops": len(events)}
    if not any(c["n_ops"] for c in per_chip.values()):
        raise RuntimeError("no operation ran on the device in the traced "
                           "window")
    busiest = max(per_chip, key=lambda p: per_chip[p]["busy_ns"])
    chip = per_chip[busiest]
    sums = {}
    for name, _s, d in chip["events"]:
        sums[short_name(name)] = sums.get(short_name(name), 0.0) + d
    ops = sorted(sums.items(), key=lambda kv: -kv[1])[:top]
    union = merge([(s, s + d) for _n, s, d in chip["events"]])
    spans = [sp for sp in trace["spans"] if sp[0] != SPAN_PREFIX + "window"]
    edges = [lo] + [x for iv in union for x in iv] + [hi]
    gaps = {}
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            for what, ns in apportion(a, b, spans).items():
                gaps[what] = gaps.get(what, 0.0) + ns
    n = float(len(per_chip))
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(c["busy_ns"] for c in per_chip.values()) / n / 1e9,
        "chips": len(per_chip), "busiest": busiest,
        "n_ops": chip["n_ops"],
        "device_ops": [[k, v / 1e9] for k, v in ops],
        "idle_gaps": [[k, v / 1e9] for k, v in sorted(
            gaps.items(), key=lambda kv: -kv[1])[:top]],
    }

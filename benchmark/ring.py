"""The program's own spans and counters, read from its timeline ring
(``mxnet_tpu/telemetry/timeline.py``): the ring is process-wide and
outlives the engine and the module, so a reader reaches it after the
window, through ``timeline.peek()``.

An event's ``mono`` field is the ``time.perf_counter`` stamp of its
start, the clock of ``obs["window"]``.  A program that lacks the seam
(no ring, no such event, no such argument) reads as nothing, and so does
a ring whose oldest event is younger than the window's start: it has
evicted part of the window, and a number from a cut sample would mislead.
"""
from benchmark.harness import percentile


def events(obs, name, stamp=None):
    """The ring's events called ``name`` that started inside
    ``obs["window"]``, oldest first, or None where the window cannot be
    read whole.  ``stamp`` names an argument that holds the stamp to
    judge by in place of the event's own (a back-dated instant)."""
    try:
        from mxnet_tpu.telemetry import timeline
    except ImportError:
        return None
    tl = timeline.peek()
    held = tl.events() if tl is not None else []
    lo, hi = obs["window"]
    if not held or held[0]["mono"] > lo:
        return None
    out = []
    for e in held:
        if e["name"] != name:
            continue
        t = e["mono"] if stamp is None else (e.get("args") or {}).get(stamp)
        if t is not None and lo <= t <= hi:
            out.append(e)
    return out or None


def arg_percentile(obs, name, arg, q, stamp=None):
    """Percentile ``q`` of argument ``arg`` over :func:`events`; None
    where there is nothing to read or an event lacks the argument."""
    evs = events(obs, name, stamp)
    if evs is None:
        return None
    values = [(e.get("args") or {}).get(arg) for e in evs]
    if any(v is None for v in values):
        return None
    return percentile(values, q)

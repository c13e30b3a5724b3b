"""Share of the window's decode steps that were dispatched before the
step before them had been read: ``ahead`` of the ``decode.step`` spans.
Such a step's device time hides the host's read, its walk over the
slots and the building of the step after; a step dispatched onto an
idle pool, and every step of a speculative engine, reads 0."""
from benchmark import ring


def read(obs):
    evs = ring.events(obs, "decode.step")
    if evs is None:
        return None
    args = [e.get("args") or {} for e in evs]
    if any("ahead" not in a for a in args):
        return None
    return 100.0 * sum(a["ahead"] for a in args) / len(args)

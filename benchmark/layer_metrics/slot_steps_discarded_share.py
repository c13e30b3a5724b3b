"""Share of the window's live slot-steps whose result was thrown away:
``discarded`` of the ``decode.step`` spans (slot results of that step
whose request had left by the time it was read: the eos id, a deadline
or a raising callback is seen one step late) over their ``live``.  A
finish by length is known before the read and costs none."""
from benchmark import ring


def read(obs):
    evs = ring.events(obs, "decode.step")
    if evs is None:
        return None
    args = [e.get("args") or {} for e in evs]
    if any("discarded" not in a or "live" not in a for a in args):
        return None
    live = sum(a["live"] for a in args)
    if not live:
        return None
    return 100.0 * sum(a["discarded"] for a in args) / live

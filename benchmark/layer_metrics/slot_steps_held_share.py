"""Share of the window's slot-steps in which a free slot had a request
waiting for it and the scheduler left it waiting, for a prefill dispatch
worth its cost: sum of ``held`` of the ``decode.step`` spans over their
count times the pool's slots (``counts["slots"]``).  The price of fuller
prefill dispatches, paid in empty seats."""
from benchmark import ring


def read(obs):
    evs = ring.events(obs, "decode.step")
    slots = (obs.get("counts") or {}).get("slots")
    if evs is None or not slots:
        return None
    args = [e.get("args") or {} for e in evs]
    if any("held" not in a for a in args):
        return None
    return 100.0 * sum(a["held"] for a in args) / (len(args) * slots)

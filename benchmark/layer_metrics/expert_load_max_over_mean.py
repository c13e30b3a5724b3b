"""How unevenly a step's live rows fall on the experts: the busiest
(layer, expert)'s rows over the mean, each averaged over the window's
steps (``expert_load_max`` and ``expert_load_mean`` of the
``decode.step`` spans).  1 is even; a grouped product waits for the
busiest."""
from benchmark import ring


def read(obs):
    evs = ring.events(obs, "decode.step")
    if evs is None:
        return None
    args = [e.get("args") or {} for e in evs]
    if any("expert_load_max" not in a or "expert_load_mean" not in a
           for a in args):
        return None
    mean = sum(a["expert_load_mean"] for a in args)
    if not mean:
        return None
    return sum(a["expert_load_max"] for a in args) / mean

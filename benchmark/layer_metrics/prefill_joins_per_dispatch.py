"""Requests a prefill dispatch seated, over the window's dispatches that
stopped decoding slots: sum of ``group`` over the count of the
``decode.prefill`` spans whose ``live`` is above 0 (``live``: the slots
decoding when the dispatch's join began).  The ramp onto an empty pool
stops nobody and is left out.  1 where every request that ends brings a
dispatch of its own; higher where the scheduler holds joins back until a
dispatch is worth its fixed cost."""
from benchmark import ring


def read(obs):
    evs = ring.events(obs, "decode.prefill")
    if evs is None:
        return None
    args = [e.get("args") or {} for e in evs]
    if any("live" not in a or "group" not in a for a in args):
        return None
    groups = [a["group"] for a in args if a["live"] > 0]
    if not groups:
        return None
    return sum(groups) / float(len(groups))

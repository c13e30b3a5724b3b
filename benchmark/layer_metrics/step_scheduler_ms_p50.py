"""Median time of one scheduler iteration outside the step program:
the ``decode.step`` span's duration less its ``dispatch_ms`` and
``read_ms``, which leaves the deadline scan, the FLOPs ledger and the
walk over the slots that delivers the tokens and finishes requests."""
from benchmark import ring
from benchmark.harness import percentile


def read(obs):
    evs = ring.events(obs, "decode.step")
    if evs is None:
        return None
    own = []
    for e in evs:
        args = e.get("args") or {}
        if "dispatch_ms" not in args or "read_ms" not in args:
            return None
        own.append(1e3 * e["dur"] - args["dispatch_ms"] - args["read_ms"])
    return percentile(own, 50)

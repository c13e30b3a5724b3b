"""Median time the host spends inside ``fit``'s forward-and-backward
phase a step (the program's ``fit.fwd_bwd`` span: the batch's upload and
the dispatch of the fused program, not its run on the device)."""
from benchmark import ring
from benchmark.harness import percentile


def read(obs):
    evs = ring.events(obs, "fit.fwd_bwd")
    if evs is None:
        return None
    return 1e3 * percentile([e["dur"] for e in evs], 50)

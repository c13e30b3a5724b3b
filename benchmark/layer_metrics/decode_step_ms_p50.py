"""Median time of ``DecodeEngine._step_once`` in the window on the host
clock: the slot scan, the step program with its blocking read of the
sampled ids, and the walk over the slots that delivers the tokens."""
from benchmark.harness import percentile


def read(obs):
    lo, hi = obs["window"]
    spans = obs["spans"].within("DecodeEngine._step_once", lo, hi)
    if not spans:
        return None
    return 1e3 * percentile([b - a for a, b in spans], 50)

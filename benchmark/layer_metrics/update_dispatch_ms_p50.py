"""Median time the host spends inside ``Module.update`` a step in the
window: one updater dispatch per parameter.  Timed from outside, around
the bound Module's method."""
from benchmark.harness import percentile


def read(obs):
    lo, hi = obs["window"]
    spans = obs["spans"].within("Module.update", lo, hi)
    if not spans:
        return None
    return 1e3 * percentile([b - a for a, b in spans], 50)

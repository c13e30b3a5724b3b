"""Median gap between consecutive generated tokens of a request, pooled
over the window.  Recorded in the saturated cell; decides nothing."""
from benchmark.harness import percentile


def read(obs):
    if not obs.get("itl_s"):
        return None
    return 1e3 * percentile(obs["itl_s"], 50)

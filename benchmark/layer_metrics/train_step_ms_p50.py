"""Median time between ``batch_end_callback``s of ``Module.fit`` over the
window, on the host clock (each callback follows the metric's read of the
step's outputs)."""
from benchmark.harness import percentile


def read(obs):
    if not obs.get("step_s"):
        return None
    return 1e3 * percentile(obs["step_s"], 50)

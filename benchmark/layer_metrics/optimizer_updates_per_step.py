"""Updater calls a training step makes (median of the ``updates``
counter on the program's ``fit.optimizer`` span): one dispatch a
parameter that has a gradient, until the updates are fused."""
from benchmark import ring


def read(obs):
    return ring.arg_percentile(obs, "fit.optimizer", "updates", 50)

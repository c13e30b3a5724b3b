"""Median time the host spends building and enqueuing one decode step
(``StepProgram``: the flat argument vector with its five host vectors,
the kernel call returning), from the ``dispatch_ms`` argument of the
program's ``decode.step`` span."""
from benchmark import ring


def read(obs):
    return ring.arg_percentile(obs, "decode.step", "dispatch_ms", 50)

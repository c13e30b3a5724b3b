"""Median time the host is blocked reading one decode step's sampled
ids back (``np.asarray`` of the step's first output), from the
``read_ms`` argument of the program's ``decode.step`` span.  It includes
the device's own step time, about 5.2 ms, while the host waits for it:
what a step dispatched ahead of the read would hide is this number less
the device's time."""
from benchmark import ring


def read(obs):
    return ring.arg_percentile(obs, "decode.step", "read_ms", 50)

"""Median duration of one prefill dispatch in the window, from the
program's own ``decode.prefill`` span: building the padded batch, the
prefill program, laying its rows into the slot pool, and the read of
the first tokens."""
from benchmark import ring
from benchmark.harness import percentile


def read(obs):
    evs = ring.events(obs, "decode.prefill")
    if evs is None:
        return None
    return 1e3 * percentile([e["dur"] for e in evs], 50)

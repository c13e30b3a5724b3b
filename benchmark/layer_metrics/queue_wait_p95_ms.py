"""95th percentile, over the requests submitted in the window, of the
wait from ``submit`` to a slot (``queue_wait_ms`` of the program's
``decode.first_token`` event): the part of the time to first token
that admission and the slot pool hold."""
from benchmark import ring


def read(obs):
    return ring.arg_percentile(obs, "decode.first_token", "queue_wait_ms",
                               95, stamp="enqueued")

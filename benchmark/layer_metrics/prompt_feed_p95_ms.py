"""95th percentile, over the requests submitted in the window, of the
time from a slot to the first generated token (``prompt_feed_ms`` of the
program's ``decode.first_token`` event): the prompt fed one token a
step, or one prefill dispatch."""
from benchmark import ring


def read(obs):
    return ring.arg_percentile(obs, "decode.first_token", "prompt_feed_ms",
                               95, stamp="enqueued")

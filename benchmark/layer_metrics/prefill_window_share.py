"""Share of the window the scheduler spent inside prefill dispatches
(``decode.prefill`` spans summed over the window): while one runs, no
slot decodes."""
from benchmark import ring


def read(obs):
    evs = ring.events(obs, "decode.prefill")
    if evs is None or not obs.get("window_s"):
        return None
    return 100.0 * sum(e["dur"] for e in evs) / obs["window_s"]

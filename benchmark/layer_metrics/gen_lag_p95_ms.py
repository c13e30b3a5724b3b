"""How late the open loop's generator sent: 95th percentile of sent minus
due, on its own clock.  A starved generator must not read as a fast
server."""
from benchmark.harness import percentile


def read(obs):
    if not obs.get("gen_lag_s"):
        return None
    return 1e3 * percentile(obs["gen_lag_s"], 95)

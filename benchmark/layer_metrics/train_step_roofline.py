"""Least time a chip needs for its share of one training step (the larger
of required FLOPs over peak FLOP/s and required bytes over peak bytes/s)
over the device-busy time a step took in the trace."""


def read(obs):
    t, steps = obs.get("trace"), obs.get("traced", {}).get("steps")
    if not t or not steps or not t["busy_s"]:
        return None
    need, peaks = obs["required"], obs["peaks"]
    least = max(need["flops"] / peaks["flops_per_s_bf16"],
                need["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least * steps / t["busy_s"]

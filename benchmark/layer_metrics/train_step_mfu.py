"""Required forward and backward FLOPs of the traced steps over what the
chips could have done in the traced window at the bfloat16 peak."""


def read(obs):
    t, steps = obs.get("trace"), obs.get("traced", {}).get("steps")
    if not t or not steps:
        return None
    return 100.0 * obs["required"]["flops"] * steps / (
        t["window_s"] * obs["peaks"]["flops_per_s_bf16"])

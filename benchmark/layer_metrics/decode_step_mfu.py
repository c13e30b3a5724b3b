"""2 x matmul parameters x live slot-positions stepped in the traced
window, over what the chip could have done in it at the bfloat16 peak."""


def read(obs):
    t, traced = obs.get("trace"), obs.get("traced")
    if not t or not traced or not traced["steps"]:
        return None
    flops = sum(r["flops"] for r in traced["required"])
    return 100.0 * flops / (t["window_s"]
                            * obs["peaks"]["flops_per_s_bf16"])

"""Least time the chip needs for the decode steps of the traced window
(each step the larger of its required FLOPs over peak FLOP/s and bytes
over peak bytes/s; the weight read binds) over the device-busy time."""


def read(obs):
    t, traced = obs.get("trace"), obs.get("traced")
    if not t or not traced or not traced["steps"] or not t["busy_s"]:
        return None
    peaks = obs["peaks"]
    least = sum(max(r["flops"] / peaks["flops_per_s_bf16"],
                    r["bytes"] / peaks["hbm_bytes_per_s"])
                for r in traced["required"])
    return 100.0 * least / t["busy_s"]

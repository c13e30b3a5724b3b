"""Least time the traced decode steps' recurrent state needs at the
peak HBM rate (the ``state_bytes`` of each step's ``step_required``: a
live slot's conv and state space rows read and written) over the
device time of those steps (the traced window's busy time less what the
chip ran inside its prefill dispatches): how much of a step the state's
own traffic has to take, against which a kernel for the state space's
step is judged.  Nothing where the configuration counts no such bytes,
or a time is missing."""


def read(obs):
    t, traced = obs.get("trace"), obs.get("traced")
    if not t or not traced or traced.get("prefill_device_s") is None:
        return None
    state = [r["state_bytes"] for r in traced["required"]
             if "state_bytes" in r]
    steps_s = t["busy_s"] - traced["prefill_device_s"]
    if not state or steps_s <= 0:
        return None
    return 100.0 * sum(state) / obs["peaks"]["hbm_bytes_per_s"] / steps_s

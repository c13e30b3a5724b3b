"""FLOPs the traced window's prefill dispatches required (the
configuration's ``prefill_required`` over each dispatch's live prompt
lengths: band and causality counted, padding not) over what the chip
could have done at the bfloat16 peak in the time it ran operations
inside their ``mx:decode.prefill`` annotations (the prefill program and
the commit of its rows; not the host's part of the span)."""


def read(obs):
    traced = obs.get("traced") or {}
    spent, flops = traced.get("prefill_device_s"), traced.get("prefill_flops")
    if not spent or not flops:
        return None
    return 100.0 * flops / (spent * obs["peaks"]["flops_per_s_bf16"])

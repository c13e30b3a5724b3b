"""Share of the window's prefill dispatches whose every attention node
ran as the fused kernel: the ``decode.prefill`` spans whose
``fused_attention`` (how many ``_gqa_prefill`` nodes of the dispatched
program took the kernel, by the op's own predicate when the program was
built) equals their ``attention_nodes``.  Nothing where the program has
no such arguments, or no attention."""
from benchmark import ring


def read(obs):
    evs = ring.events(obs, "decode.prefill")
    if evs is None:
        return None
    args = [e.get("args") or {} for e in evs]
    if any(not a.get("attention_nodes") or "fused_attention" not in a
           for a in args):
        return None
    return 100.0 * sum(a["fused_attention"] == a["attention_nodes"]
                       for a in args) / len(args)

"""Share of the positions the window's prefill dispatches computed that
held no prompt token: 1 - ``tokens`` / ``padded`` of the
``decode.prefill`` spans (dead rows of a padded batch and each prompt's
overhang to its bucket)."""
from benchmark import ring


def read(obs):
    evs = ring.events(obs, "decode.prefill")
    if evs is None:
        return None
    args = [e.get("args") or {} for e in evs]
    if any("tokens" not in a or "padded" not in a for a in args):
        return None
    padded = sum(a["padded"] for a in args)
    if not padded:
        return None
    return 100.0 * (1.0 - sum(a["tokens"] for a in args) / padded)

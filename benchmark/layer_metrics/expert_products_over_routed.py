"""(row, expert) products the decode steps' expert layers multiplied
over the (row, expert) pairs their live rows were routed to
(``expert_products`` and ``expert_routed`` of the window's
``decode.step`` events, each summed).  Every held expert over every
slot, the plain path, reads experts / top_k at a full pool (8 for 32
experts, 4 a row); a grouped product that multiplies only the routed
pairs reads near 1."""
from benchmark import ring


def read(obs):
    evs = ring.events(obs, "decode.step")
    if evs is None:
        return None
    args = [e.get("args") or {} for e in evs]
    if any("expert_products" not in a or "expert_routed" not in a
           for a in args):
        return None
    routed = sum(a["expert_routed"] for a in args)
    if not routed:
        return None
    return sum(a["expert_products"] for a in args) / routed

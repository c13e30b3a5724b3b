"""Cache rows the program's states hold for the live slots of the
traced steps (``stats()``'s ``state_rows`` a slot, which a step that
reads each live slot's whole layout reads) over the rows
``step_required`` counts (the last ``window`` positions on a window
layer, the context on a global one).  A ratio of the layout to the
contexts served: paging or a shorter ``max_len`` moves it, a faster
kernel does not.  1 is a pool that holds only what a step must read."""


def read(obs):
    traced = obs.get("traced") or {}
    held = traced.get("cache_rows_held")
    need = traced.get("cache_rows_required")
    if not held or not need:
        return None
    return held / need

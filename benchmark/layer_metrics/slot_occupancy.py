"""Share of slot-positions stepped in the window that held a request:
live slots summed over the steps, over steps times slots.  Counted at the
step program's boundary from the ``valid`` vector the engine hands it."""


def read(obs):
    c = obs["counts"]
    if not c.get("steps_seen"):
        return None
    return 100.0 * c["live_positions"] / (c["steps_seen"] * c["slots"])

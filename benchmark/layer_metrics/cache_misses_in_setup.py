"""Persistent-cache misses before the window opened: programs that had
to be compiled.  Nought in every run of a checkout but the first."""


def read(obs):
    return float(obs["compile_setup"]["misses"])

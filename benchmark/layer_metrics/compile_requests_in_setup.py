"""Compile requests JAX counted before the window opened: programs
compiled or loaded from the persistent cache during set-up."""


def read(obs):
    return float(obs["compile_setup"]["requests"])

"""Seconds from the start of the process to the start of the window:
JAX start-up, graph generation, and the warm-up with any compiles."""


def read(run):
    return run.setup_s

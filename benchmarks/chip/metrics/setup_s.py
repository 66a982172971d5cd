"""Seconds from the process's start to the first step of the window: JAX
start, compiles or cache loads, the store build and every warm-up."""


def read(run):
    return run.setup_s

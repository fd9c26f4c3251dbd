"""Set-up seconds: process start to the window's open (JAX start, stream
generation, building the system, filling the pools, warming every shape)."""


def read(run):
    return run.setup_s

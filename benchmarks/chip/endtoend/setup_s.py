"""Seconds from the start of the process until the window opens: imports,
readings made from the seed, the served session built, and the set-up
rounds that fill state and compile or load every program."""


def read(run):
    return run.setup_s

"""Seconds from the process's start to the window's start: loading,
weights, engine, warm-up compiles and the traffic's pre-roll."""


def read(run):
    return run.setup_s

"""Dispatch (the jitted ``_prefill_verify`` call until it returns:
argument handling and the enqueue of the program, not its run): mean
host wall time of the ``step.launch`` phase per engine step in the
window, from the engine's own step trace events, in ms."""
from phases import host_ms


def read(run):
    return host_ms(run, "launch")

"""Dispatch, host side (``_ragged_dispatch``: the padded token, table,
cursor and length arrays and their transfers to the device): mean host
wall time of the ``step.prepare`` phase per engine step in the window,
from the engine's own step trace events, in ms."""
from phases import host_ms


def read(run):
    return host_ms(run, "prepare")

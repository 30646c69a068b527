"""Scheduler (``_account_chunks``, ``_accept_verify``, ``invalidate_lanes``,
``_collect`` with its radix inserts): mean host wall time of the
``step.account`` phase per engine step in the window, from the engine's
own step trace events, in ms."""
from phases import host_ms


def read(run):
    return host_ms(run, "account")

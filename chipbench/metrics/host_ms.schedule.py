"""Scheduler (``_admit_all``, ``_select_chunks``, ``_plan_verify``, with
the copy-on-write and preemption they do): mean host wall time of the
``step.schedule`` phase per engine step in the window, from the engine's
own step trace events, in ms."""
from phases import host_ms


def read(run):
    return host_ms(run, "schedule")

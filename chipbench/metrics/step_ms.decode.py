"""Dispatch (``_ragged_dispatch`` -> jitted ``_prefill_verify``): median
wall time of the window's engine steps that carry no prefill chunk, on
the harness clock, in ms."""
from runview import percentile


def read(run):
    return percentile([(s.t1 - s.t0) * 1e3 for s in run.window_steps()
                       if not s.mixed], 50)

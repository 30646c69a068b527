"""Model step, steps that carry a prefill chunk: the least time the
chip's peaks allow for the work they require, over their measured
time, in %."""


def read(run):
    return run.roofline_share(mixed=True)

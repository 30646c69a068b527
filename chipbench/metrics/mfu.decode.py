"""Model step, decode-only steps: the least time the chip's peaks allow
for the work the steps require (``counts.py``: the larger of the FLOP
and the byte bound), over their measured time, in %."""


def read(run):
    return run.roofline_share(mixed=False)

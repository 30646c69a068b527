"""Median of every gap between consecutive output tokens of a request,
where the later token came in the window, in ms."""
from runview import percentile


def read(run):
    return percentile(run.gaps_ms(), 50)

"""Device: 1 - (union of device-operation intervals) / (traced window),
from the profiler trace of the window's last seconds, in %."""


def read(run):
    t = run.devtrace
    if not t or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])

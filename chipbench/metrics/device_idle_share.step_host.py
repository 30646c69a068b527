"""Engine host: the share of the traced window in which no device op ran
while the host was inside the engine's step, in one of its phases
(``step.schedule``, ``step.prepare``, ``step.launch``, ``step.wait``,
``step.account``) or a garbage collection, each idle instant given to
the innermost host span covering it (``phases.py``), in %.  The sum over
the phases does not depend on how the profiler aligns the device's clock
with the host's; a split among them would."""
from phases import step_host_share


def read(run):
    return step_host_share(run)

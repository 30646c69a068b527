"""Scheduler (``PagedLLMEngine._preempt_youngest`` and the resume in
``_admit_setup``): the share of the window's prefill tokens that
preempted requests computed again on resume, in %: window change of
``recomputed_tokens`` over window change of ``prefill_tokens``.  None on
a program whose ``stats()`` has no ``recomputed_tokens``, or with no
prefill in the window."""


def read(run):
    if "recomputed_tokens" not in run.stats0:
        return None
    prefilled = run.stats1["prefill_tokens"] - run.stats0["prefill_tokens"]
    if not prefilled:
        return None
    again = run.stats1["recomputed_tokens"] - run.stats0["recomputed_tokens"]
    return 100.0 * again / prefilled

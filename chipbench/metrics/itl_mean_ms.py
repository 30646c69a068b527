"""Mean gap between consecutive output tokens of a request, where the
later token came in the window: all such gap time over all such gaps, in
ms.  Every step's time counts once for each row that waited through it,
so mixed steps (prefill chunks) weigh in as users feel them."""


def read(run):
    gaps = run.gaps_ms()
    return sum(gaps) / len(gaps) if gaps else None

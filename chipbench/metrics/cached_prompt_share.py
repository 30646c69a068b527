"""Scheduler and prefix cache (``serving/prefix_cache.py``): the share of
prompt tokens that the window did not have to prefill, in %:
1 - (window's change in ``prefill_tokens``) / (prompt tokens of the
requests first admitted in the window)."""


def read(run):
    prompt_len = {t.gen.rid: len(t.req.prompt) for t in run.log.tracks}
    admitted = sum(prompt_len.get(rid, 0)
                   for rid, ev in run.request_events().items()
                   if 0.0 <= ev.get("admitted", -1.0) < run.window_s)
    if not admitted:
        return None
    prefilled = run.stats1["prefill_tokens"] - run.stats0["prefill_tokens"]
    return 100.0 * (1.0 - prefilled / admitted)

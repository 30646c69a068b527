"""Warm-up: compile the step shapes this run's traffic can use.

The engine buckets every fused step to a dispatch signature (row bucket,
length bucket, block bucket) and compiles one program per signature.
Which signatures a run meets depends on its schedule, so set-up replays
the run's own requests on the host, through a second engine whose
dispatch is replaced by a stub that records the signature and returns
placeholder tokens; pool updates are stubbed the same way.  An open
loop's schedule depends on how long steps take, so the replay runs on a
virtual clock charged with each step's wall time from a fixed cost
model (the configuration's ``step_cost_ms``, host work included), once
for each pair of ``SCALES``: the model's fixed cost and its cost of
work are scaled apart, because a chip's decode steps and prefill steps
depart from the model by different factors.  Which rows share a step
with which chunk still turns on the chip's timing (on the chat cell, a
16-row step beside a 64- or 128-lane chunk that no replay met), so what
is warmed is every combination of the row, length and block buckets
that the replays met.  No time measured in this run feeds the replay,
so every run of a mix warms the same signatures.
Eager pool updates compile per shape too: ``invalidate_blocks`` is
warmed for every block count the replays released at once, and
``copy_blocks`` (copy-on-write) once.

Warming a signature runs the real engine's ``_ragged_dispatch`` over
null-block rows: every key/value write lands in the null block, which the
same dispatch scrubs, so the pool's contents do not change.
"""
from __future__ import annotations

import itertools
from collections import Counter

import numpy as np

from feed import Feeder, VirtualClock

# the replay runs once for each (fixed-cost scale, work scale) pair, so
# a run whose steps come out faster or slower than the model still meets
# no new signature inside its window
SCALES = tuple((b, w) for b in (0.8, 1.0, 1.25)
               for w in (0.4, 0.5, 0.6, 0.7, 0.85, 1.0, 1.2, 1.45, 1.75, 2.1))


def signature(engine, rows) -> tuple:
    """The engine's bucketing of one ragged dispatch (see
    ``PagedLLMEngine._ragged_dispatch``)."""
    longest = max(len(t) for t, _, _ in rows)
    return (engine._bucket_rows(len(rows)),
            1 if longest == 1 else engine._bucket_len(longest),
            engine._bucket_blocks(max(len(b) for _, _, b in rows)))


def step_seconds(sig, model: dict, scale=(1.0, 1.0)) -> float:
    """Modelled wall time of one step: a fixed cost, one per padded lane,
    and one per row and table block (the gather); ``scale`` multiplies
    the fixed cost and the rest."""
    r, c, nb = sig
    return 1e-3 * (scale[0] * model["base"] + scale[1] * (
        model["per_lane"] * r * c + model["per_row_block"] * r * nb))


def replay(make_engine, mix, reqs, window_s, model, scale=(1.0, 1.0)):
    """Run the schedule on a stubbed engine.  -> (signature counts, block
    counts released at once)."""
    import repro.serving.server as server

    eng = make_engine(pool=False)
    sigs, step_sigs, released = Counter(), [], set()

    def fake_dispatch(rows, state_rows=None, *, all_logits):
        sig = signature(eng, rows)
        sigs[sig] += 1
        step_sigs.append(sig)
        return np.zeros(sig[:2], np.int32)

    def fake_invalidate(pools, ids):
        released.add(len(ids))
        return pools

    def fake_copy(pools, *_):
        return pools

    clock = VirtualClock(-float(mix["preroll_s"]))

    def cost():
        clock.t += sum(step_seconds(s, model, scale) for s in step_sigs)
        step_sigs.clear()

    eng._ragged_dispatch = fake_dispatch
    saved = (server.invalidate_blocks, server.copy_blocks)
    server.invalidate_blocks, server.copy_blocks = fake_invalidate, fake_copy
    try:
        Feeder(eng, mix, reqs, clock, step_cost=cost).run(window_s)
    finally:
        server.invalidate_blocks, server.copy_blocks = saved
    return sigs, released


def warm_signature(engine, sig) -> None:
    """Compile (or load) one signature on the real engine and run it."""
    r, c, nb = sig
    rows = [(np.ones((c,), np.int32), 0, [0] * nb)] * r
    np.asarray(engine._ragged_dispatch(rows, None, all_logits=True))


def warm_pool_updates(engine, counts) -> None:
    """Eager pool updates on the null block (the results are dropped: the
    pool does not change)."""
    import jax
    from repro.serving.kvcache import copy_blocks, invalidate_blocks

    for n in sorted(counts):
        jax.block_until_ready(invalidate_blocks(engine.pools, [0] * n))
    jax.block_until_ready(copy_blocks(engine.pools, [0], [0]))


def warm(engine, make_engine, mix, reqs, window_s, model, log=print):
    """Replay the run's requests at every pair of scales, then warm every
    combination of the buckets they met.  -> the signatures warmed, in
    order."""
    counts, released = Counter(), set()
    for scale in SCALES:
        c, r = replay(make_engine, mix, reqs, window_s, model, scale)
        counts.update(c)
        released |= r
    rows, lens, blocks = (sorted({s[i] for s in counts}) for i in range(3))
    sigs = list(itertools.product(rows, lens, blocks))
    log(f"warm-up: {len(sigs)} signatures ({len(counts)} met in the "
        f"replays), {len(released)} release counts")
    for sig in sigs:
        warm_signature(engine, sig)
    warm_pool_updates(engine, released)
    return sigs

"""The feeding loop: feeds a traffic mix to ``PagedLLMEngine`` and stamps
every output token on the harness's own clock, after ``engine.step()``
returns (the step syncs: it pulls the greedy ids to the host).

Times are seconds from the window's start; the pre-roll runs at
negative times.  The loop is open: each request is submitted when it
falls due, with ``now`` set to its due time, and every latency is
measured from the due time.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from collections import deque
from typing import Callable, List, Optional


@dataclasses.dataclass
class Track:
    req: object           # traffic.Request
    gen: object           # the engine's GenRequest
    due: float
    stamps: List[float] = dataclasses.field(default_factory=list)
    done: bool = False


@dataclasses.dataclass
class StepRec:
    t0: float
    t1: float
    mixed: bool               # the step carried a prefill chunk
    decode_rows: list         # (start, 1) per decoding row
    new_tokens: int


class WallClock:
    def __init__(self, origin: float):
        self.origin = origin

    def now(self) -> float:
        return time.perf_counter() - self.origin

    def wait_until(self, t: float) -> None:
        dt = t - self.now()
        if dt > 0:
            time.sleep(dt)


class VirtualClock:
    """Time that advances only when told: for replaying a schedule."""

    def __init__(self, start: float):
        self.t = start

    def now(self) -> float:
        return self.t

    def wait_until(self, t: float) -> None:
        self.t = max(self.t, t)


@dataclasses.dataclass
class RunLog:
    tracks: List[Track]
    refused: List[object]     # traffic.Request
    steps: List[StepRec]
    late_s: List[float]       # submission time minus due time
    end: float


def _null(_name):
    return contextlib.nullcontext()


class Feeder:
    def __init__(self, engine, mix: dict, reqs: list, clock,
                 annotate: Callable = _null,
                 step_cost: Optional[Callable[[], None]] = None):
        self.engine = engine
        self.mix = mix
        self.reqs = reqs
        self.clock = clock
        self.annotate = annotate
        # called after each step (virtual replays advance their clock
        # there); None for wall-clock runs
        self.step_cost = step_cost
        self.hooks: List[Callable[[float], None]] = []

    def _submit(self, req, due, log, inflight):
        try:
            rid = self.engine.submit(req.prompt, max_new=req.max_new, now=due)
        except ValueError:
            log.refused.append(req)
            return
        gen = self.engine.queue[-1]
        if gen.rid != rid:
            raise RuntimeError("engine queued an unexpected request")
        tr = Track(req, gen, due)
        log.tracks.append(tr)
        inflight.append(tr)

    def run(self, window_s: float) -> RunLog:
        eng, clock, ann = self.engine, self.clock, self.annotate
        pending = deque(self.reqs)
        drain_to = window_s + self.mix["drain_s"]
        log = RunLog([], [], [], [], 0.0)
        inflight: List[Track] = []
        while True:
            now = clock.now()
            for h in self.hooks:
                h(now)
            if now >= window_s:
                if now >= drain_to:
                    break
                if all(t.stamps or t.due >= window_s for t in log.tracks) \
                        and (not pending or pending[0].due >= window_s):
                    break
            with ann("submit"):
                while pending and pending[0].due <= now:
                    r = pending.popleft()
                    log.late_s.append(now - r.due)
                    self._submit(r, r.due, log, inflight)
            if eng.idle:
                if not pending:
                    break
                with ann("wait_arrival"):
                    clock.wait_until(pending[0].due)
                continue
            seen = {id(t): len(t.gen.out_tokens) for t in inflight}
            pre_prefill = eng.prefill_tokens
            t0 = clock.now()
            with ann("engine.step"):
                eng.step(now=t0)
            if self.step_cost is not None:
                self.step_cost()
            t1 = clock.now()
            with ann("harness.account"):
                decode_rows, new = [], 0
                for tr in inflight:
                    before = seen[id(tr)]
                    n = len(tr.gen.out_tokens)
                    if n > before:
                        tr.stamps.extend([t1] * (n - before))
                        new += n - before
                        if before:
                            decode_rows.append(
                                (len(tr.req.prompt) + before - 1, 1))
                    if tr.gen.finished_at is not None:
                        tr.done = True
                inflight = [t for t in inflight if not t.done]
                log.steps.append(StepRec(t0, t1, eng.prefill_tokens
                                         > pre_prefill, decode_rows, new))
        log.end = clock.now()
        return log

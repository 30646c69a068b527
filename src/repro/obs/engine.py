"""Engine-side instrumentation facade.

``EngineObs`` binds an ``Observability`` bundle to one engine instance:
it pre-creates every instrument the step loop touches (no registry
lookups on the hot path) and forwards lifecycle transitions to the
trace recorder when one is attached.  The engines hold
``self.obs: EngineObs | None`` and guard every call with a plain
``if self.obs`` — instrumentation off is one attribute check (and a
shared no-op context for each step phase).

Metric names (the runtime half of the serving schema — the ``stats()``
gauge schema lives in ``serving/stats_schema.py``):

  engine_requests_total / engine_admissions_total /
  engine_preemptions_total / engine_finished_total          counters
  engine_prefill_tokens_total / engine_generated_tokens_total
  engine_recomputed_tokens_total (prefill tokens of preempted
    requests recomputed on resume; also in engine_prefill_tokens_total)
  engine_steps_total
  engine_queue_depth / engine_active / engine_free_blocks /
  engine_pool_occupancy                                     gauges
  engine_step_seconds                                       histogram
  engine_phase_seconds_total{phase=schedule|prepare|launch|
    wait|account}                                           counter
    (host wall time inside each phase of the paged engine's step;
    zero on the slot engine, which has no phases)
  request_ttft_seconds / request_e2e_seconds /
  request_intertoken_seconds                                histograms
  engine_spec_proposed_total / engine_spec_accepted_total /
  engine_spec_rollbacks_total                               counters
  engine_spec_accepted_tokens                               histogram
    (integer bounds 1..16: tokens emitted per verify row — the
    accepted-tokens-per-step distribution of speculative decoding)

Engine metrics carry an ``engine="slot"|"paged"`` label (two engines
can share one registry without colliding); the ``request_*`` histograms
are unlabeled — they are the fleet-wide latency distributions
``summarize_latencies`` reads.

Step phases: the paged engine wraps each phase of its step in
``with obs.phase(name)``, which opens a ``jax.profiler.TraceAnnotation``
named ``step.<name>`` with the engine's step number as its ``step``
argument — a span in the profiler's host plane, on the same clock as
the device ops — and adds the phase's wall time to the step's
``phase_ms`` (the trace's ``step`` event) and to
``engine_phase_seconds_total``.  While any ``EngineObs`` is alive and
attached, a ``gc.callbacks`` hook records each garbage collection as a
``gc`` span.

Result stamps: tokens, verify outcomes and finishes are stamped with
``ready`` — on a wall clock the step's ``now`` plus the wall time to
the end of its ``wait`` phase (when the ids reached the host); on a
logical clock (``Observability.mode == "sim"``, with or without a
trace) the step's ``now``, so sim traces stay byte-stable and step-
clock latencies stay whole steps.
"""
from __future__ import annotations

import contextlib
import gc
import time
import weakref

from jax import profiler

#: the paged engine's step phases, in order
PHASES = ("schedule", "prepare", "launch", "wait", "account")


class _GcSpans:
    """``gc.callbacks`` hook: each collection becomes a ``gc`` span on the
    profiler's host clock.  Registered while at least one ``EngineObs``
    holds it; collection is process-wide, so one hook serves them all."""

    def __init__(self):
        self.holders = 0
        self.open = None

    def __call__(self, phase, _info):
        if phase == "start":
            self.open = profiler.TraceAnnotation("gc")
            self.open.__enter__()
        else:
            self._close()

    def _close(self):
        if self.open is not None:
            self.open.__exit__(None, None, None)
            self.open = None

    def hold(self):
        if not self.holders:
            gc.callbacks.append(self)
        self.holders += 1

    def release(self):
        self.holders -= 1
        if not self.holders:
            gc.callbacks.remove(self)
            self._close()


GC_SPANS = _GcSpans()


class EngineObs:
    __slots__ = ("bundle", "trace", "wall",
                 "c_requests", "c_admissions", "c_preemptions",
                 "c_finished", "c_prefill_tokens", "c_recomputed",
                 "c_generated",
                 "c_steps", "g_queue", "g_active", "g_free_blocks",
                 "g_occupancy", "h_step", "h_ttft", "h_e2e", "h_gap",
                 "c_spec_proposed", "c_spec_accepted", "c_spec_rollbacks",
                 "h_spec_accepted", "c_phase",
                 "steps", "phase_s", "now0", "t0", "ready", "close",
                 "__weakref__")

    def __init__(self, bundle, kind: str, replica=None):
        self.bundle = bundle
        self.trace = bundle.trace
        # results are stamped on arrival unless the caller's clock is
        # logical (``Observability.mode``)
        self.wall = bundle.mode == "wall"
        m = bundle.metrics
        # engine metrics carry the replica id in the cluster tier so N
        # replicas can share one merged registry without colliding; the
        # request_* histograms stay unlabeled on purpose — they merge
        # into the fleet-wide latency distributions.
        lab = {"engine": kind} if replica is None else \
            {"engine": kind, "replica": str(replica)}
        self.c_requests = m.counter(
            "engine_requests_total", "requests submitted", lab)
        self.c_admissions = m.counter(
            "engine_admissions_total", "requests admitted (incl. resumes)",
            lab)
        self.c_preemptions = m.counter(
            "engine_preemptions_total", "requests preempted", lab)
        self.c_finished = m.counter(
            "engine_finished_total", "requests finished", lab)
        self.c_prefill_tokens = m.counter(
            "engine_prefill_tokens_total", "prompt tokens computed", lab)
        self.c_recomputed = m.counter(
            "engine_recomputed_tokens_total",
            "prefill tokens recomputed by preempted requests on resume",
            lab)
        self.c_generated = m.counter(
            "engine_generated_tokens_total", "output tokens emitted", lab)
        self.c_steps = m.counter(
            "engine_steps_total", "engine steps executed", lab)
        self.g_queue = m.gauge(
            "engine_queue_depth", "requests waiting for admission", lab)
        self.g_active = m.gauge(
            "engine_active", "requests currently decoding", lab)
        self.g_free_blocks = m.gauge(
            "engine_free_blocks", "free KV pool blocks", lab)
        self.g_occupancy = m.gauge(
            "engine_pool_occupancy", "used / total KV blocks", lab)
        self.h_step = m.histogram(
            "engine_step_seconds", "engine step dispatch wall time", lab)
        self.h_ttft = m.histogram(
            "request_ttft_seconds", "submit -> first output token")
        self.h_e2e = m.histogram(
            "request_e2e_seconds", "submit -> request finished")
        self.h_gap = m.histogram(
            "request_intertoken_seconds",
            "gap between consecutive output tokens of one request")
        self.c_spec_proposed = m.counter(
            "engine_spec_proposed_total",
            "drafted tokens sent to verification", lab)
        self.c_spec_accepted = m.counter(
            "engine_spec_accepted_total",
            "drafted tokens that matched the target argmax", lab)
        self.c_spec_rollbacks = m.counter(
            "engine_spec_rollbacks_total",
            "verify rows that rolled speculative lanes back", lab)
        self.h_spec_accepted = m.histogram(
            "engine_spec_accepted_tokens",
            "tokens emitted per verify row (accepted drafts + bonus)",
            lab, bounds=tuple(float(b) for b in range(1, 17)))
        self.c_phase = {p: m.counter(
            "engine_phase_seconds_total",
            "host wall time inside each engine step phase",
            {**lab, "phase": p}) for p in PHASES}
        self.steps = 0                 # steps recorded: the spans' id
        self.phase_s: dict = {}        # this step's phase wall times
        self.now0 = self.ready = 0.0
        self.t0 = 0.0
        GC_SPANS.hold()
        #: detach: releases the gc hook (also run if dropped attached)
        self.close = weakref.finalize(self, GC_SPANS.release)

    # ------------------------------------------------------ lifecycle
    def request_queued(self, rid: int, now: float, prompt_len: int,
                       max_new: int) -> None:
        self.c_requests.inc()
        if self.trace:
            self.trace.open_span(rid, now, prompt_len=prompt_len,
                                 max_new=max_new)
            self.trace.request(rid, "queued", now)

    def admitted(self, rid: int, now: float, resume: bool,
                 cached_blocks: int, cow: bool, recomputed: int = 0) -> None:
        """``recomputed``: of the positions whose KV a resumed request
        held before its preemption, those past its cached match, which
        its prefill will compute again."""
        self.c_admissions.inc()
        if self.trace:
            extra = {"recomputed_tokens": recomputed} if resume else {}
            self.trace.request(rid, "evicted_resume" if resume
                               else "admitted", now,
                               cached_blocks=cached_blocks, cow=cow, **extra)

    def prefill_chunk(self, rid: int, now: float, start: int,
                      take: int, recomputed: int = 0) -> None:
        """``recomputed``: lanes of the chunk whose KV the request held
        before a preemption."""
        self.c_prefill_tokens.inc(take)
        self.c_recomputed.inc(recomputed)
        if self.trace:
            self.trace.request(rid, "prefill_chunk", now, start=start,
                               take=take)

    def first_token(self, rid: int, now: float, ttft: float) -> None:
        self.c_generated.inc()
        self.h_ttft.observe(ttft)
        if self.trace:
            self.trace.request(rid, "first_token", now)

    def token(self, rid: int, now: float, gap) -> None:
        self.c_generated.inc()
        if gap is not None:
            self.h_gap.observe(gap)

    def spec_verify(self, rid: int, now: float, *, proposed: int,
                    accepted: int, emitted: int, rolled_back: int) -> None:
        """One speculative verify window resolved for ``rid``:
        ``proposed`` drafted tokens went in, ``accepted`` matched the
        target argmax, ``emitted`` tokens (accepted + bonus, EOS-
        truncated) came out, ``rolled_back`` written lanes were
        discarded.  Token counters are NOT touched here — the engine
        reports each emitted token through ``first_token``/``token``."""
        self.c_spec_proposed.inc(proposed)
        self.c_spec_accepted.inc(accepted)
        if rolled_back:
            self.c_spec_rollbacks.inc()
        self.h_spec_accepted.observe(emitted)
        if self.trace:
            self.trace.request(rid, "spec_verify", now, proposed=proposed,
                               accepted=accepted, emitted=emitted,
                               rolled_back=rolled_back)

    def preempted(self, rid: int, now: float, where: str) -> None:
        self.c_preemptions.inc()
        if self.trace:
            self.trace.request(rid, "preempted", now, where=where)

    def finished(self, rid: int, now: float, e2e: float,
                 tokens: int) -> None:
        self.c_finished.inc()
        self.h_e2e.observe(e2e)
        if self.trace:
            self.trace.close_span(rid, now, "finished", tokens=tokens)

    # ------------------------------------------------------------ step
    def begin_step(self, now: float, t0: float) -> None:
        """A step starts at ``now`` (caller clock), ``t0`` (perf_counter)."""
        self.now0 = self.ready = now
        self.t0 = t0
        self.phase_s = {}

    @contextlib.contextmanager
    def phase(self, name: str):
        """Span one phase of the current step (``PHASES``)."""
        with profiler.TraceAnnotation("step." + name, step=self.steps):
            t0 = time.perf_counter()
            yield
            t1 = time.perf_counter()
        self.phase_s[name] = self.phase_s.get(name, 0.0) + t1 - t0
        if name == "wait" and self.wall:
            self.ready = self.now0 + (t1 - self.t0)

    def step(self, now: float, wall_s: float, *, admitted: int,
             chunk_tokens: int, decode_batch: int, tokens: int,
             retraced: bool, queue_depth: int, active: int,
             free_blocks: int, pool_occupancy: float) -> None:
        self.c_steps.inc()
        self.h_step.observe(wall_s)
        self.g_queue.set(queue_depth)
        self.g_active.set(active)
        self.g_free_blocks.set(free_blocks)
        self.g_occupancy.set(pool_occupancy)
        # a step that opened no phase (the slot engine) reports none
        phases = {p: self.phase_s.get(p, 0.0) for p in PHASES} \
            if self.phase_s else None
        for p, v in (phases or {}).items():
            self.c_phase[p].inc(v)
        self.steps += 1
        if self.trace:
            self.trace.step(now, wall_s, phases, admitted=admitted,
                            chunk_tokens=chunk_tokens,
                            decode_batch=decode_batch, tokens=tokens,
                            retraced=retraced)
            self.trace.counter(now, "engine_occupancy",
                               queue_depth=queue_depth, active=active,
                               free_blocks=free_blocks)

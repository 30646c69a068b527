"""The Stratus serving pipeline, end to end, plus the beyond-paper LLM
continuous-batching engine.

``StratusApp`` wires the paper's Fig. 1/2 components in-process:

    client -> LoadBalancer (NGINX x3) -> flask service time -> Broker
    (Kafka x3 partitions) -> consumer job (micro-batched CNN inference,
    REAL jitted model execution, measured and charged to virtual time)
    -> ResultStore (CouchDB) -> flask poll -> client

Request outcomes mirror the paper's §III failure modes: fast 429 when the
balancer is saturated, 503 when a broker partition is full, 504 when the
result doesn't appear before the client timeout.

``LLMEngine`` is the slot-based continuous-batching baseline;
``PagedLLMEngine`` is the production path: block-paged KV pool +
admission-aware scheduling with preempt-and-requeue (see the class
docstring for the policy).
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.engine import EngineObs
from repro.serving.balancer import LoadBalancer, Overloaded
from repro.serving.broker import Broker, PartitionFull
from repro.serving.kvcache import (BlockAllocator, SlotManager, copy_blocks,
                                   invalidate_blocks, invalidate_lanes,
                                   scrub_null_block, write_chunk_tokens,
                                   write_slot)
from repro.serving.prefix_cache import MatchResult, PrefixCache
from repro.serving.spec_decode import make_drafter
from repro.serving.sim import Clock, QueuedResource
from repro.serving.store import ResultStore

#: ``stats()`` gauge schema: ``serving/stats_schema.py`` is THE
#: canonical key list (with ``validate()``, CI-asserted against both
#: engines).  Consumers read snapshots with ``.get()`` — dicts
#: persisted by older engines may omit newer keys.  Step-rate counters
#: and latency histograms are the ``repro/obs`` layer (pass
#: ``obs=Observability(...)`` to either engine).


# ---------------------------------------------------------------- Stratus


@dataclasses.dataclass
class AppConfig:
    """Calibrated to the paper's testbed (two small Chameleon VMs): 3 NGINX
    replicas serving a slow static bundle (~2.5 s), a single-message
    consumer (the paper-faithful default, ``max_batch=1``) behind 3 Kafka
    partitions.  The §Perf-serving iteration flips ``max_batch``/policy."""

    # NGINX tier (GET path, paper §III.B)
    nginx_replicas: int = 3
    nginx_concurrency: int = 3         # worker_connections per replica
    nginx_queue: int = 8               # listen backlog
    balancer_policy: str = "round_robin"
    static_service: float = 2.5        # paper: ~2.95 s GET at 10 users
    reject_latency: float = 0.3        # paper: ~306 ms mean at 98% fail
    # flask tier (POST path goes straight to Flask:30005 in the paper)
    flask_concurrency: int = 8
    flask_queue: int = 64
    flask_service: float = 0.05
    # kafka tier
    partitions: int = 3
    partition_depth: int = 256
    # consumer tier
    num_consumers: int = 1
    poll_interval: float = 0.05
    max_batch: int = 1                 # paper: one message at a time
    batch_wait: float = 0.02
    consume_base: float = 0.35         # per-call overhead (consumer job)
    consume_jitter: float = 0.5        # +- fraction of consume_base
    # client behaviour
    poll_store_every: float = 0.25
    client_timeout: float = 30.0


@dataclasses.dataclass
class Outcome:
    ok: bool
    status: int
    latency: float
    kind: str


class StratusApp:
    """The full pipeline under virtual time with real model execution."""

    def __init__(self, clock: Clock, predict_fn: Callable[[np.ndarray], np.ndarray],
                 cfg: AppConfig = AppConfig(), seed: int = 0, obs=None):
        self.clock = clock
        self.cfg = cfg
        self.predict_fn = predict_fn
        self.obs = obs
        metrics = obs.metrics if obs is not None else None
        self.balancer = LoadBalancer(cfg.nginx_replicas, cfg.nginx_concurrency,
                                     cfg.nginx_queue, cfg.balancer_policy,
                                     seed, metrics=metrics)
        self._nginx = [QueuedResource(clock, cfg.nginx_concurrency,
                                      cfg.nginx_queue, metrics=metrics,
                                      name=f"nginx-{i}")
                       for i in range(cfg.nginx_replicas)]
        self._flask = QueuedResource(clock, cfg.flask_concurrency,
                                     cfg.flask_queue, metrics=metrics,
                                     name="flask")
        self.broker = Broker(cfg.partitions, cfg.partition_depth, seed,
                             metrics=metrics)
        self.store = ResultStore()
        self._rng = np.random.default_rng(seed)
        self._req_id = 0
        for c in range(cfg.num_consumers):
            self._schedule_consumer(c)

    # ------------------------------------------------------------ client
    def get_page(self, done: Callable[[Outcome], None]) -> None:
        """GET / — static page through an NGINX replica (paper §III.B).
        The balancer policy picks the replica; the replica's worker pool +
        listen backlog decide accept vs 429."""
        t0 = self.clock.now
        try:
            replica = self.balancer.pick()
        except Overloaded:
            self.clock.schedule(self.cfg.reject_latency, lambda: done(
                Outcome(False, 429, self.cfg.reject_latency, "GET")))
            return
        res = self._nginx[replica.rid]

        def finish():
            self.balancer.release(replica)
            done(Outcome(True, 200, self.clock.now - t0, "GET"))

        if not res.submit(self.cfg.static_service, finish):
            self.balancer.release(replica)
            self.clock.schedule(self.cfg.reject_latency, lambda: done(
                Outcome(False, 429, self.cfg.reject_latency, "GET")))

    def post_predict(self, image: np.ndarray,
                     done: Callable[[Outcome], None]) -> None:
        """POST /predict — straight to the Flask backend (port 30005 in the
        paper; the front-end bypasses NGINX for API calls), then the Fig. 1
        pipeline: Kafka -> consumer -> CouchDB -> poll."""
        t0 = self.clock.now
        self._req_id += 1
        key = f"req-{self._req_id}"

        def after_flask():
            try:
                self.broker.produce({"key": key, "image": image},
                                    timestamp=self.clock.now)
            except PartitionFull:
                done(Outcome(False, 503, self.clock.now - t0, "POST"))
                return
            poll_result()

        def poll_result():
            if self.clock.now - t0 > self.cfg.client_timeout:
                done(Outcome(False, 504, self.clock.now - t0, "POST"))
                return
            doc = self.store.poll(key)
            if doc is not None:
                done(Outcome(True, 200, self.clock.now - t0, "POST"))
            else:
                self.clock.schedule(self.cfg.poll_store_every, poll_result)

        if not self._flask.submit(self.cfg.flask_service, after_flask):
            self.clock.schedule(self.cfg.reject_latency, lambda: done(
                Outcome(False, 429, self.cfg.reject_latency, "POST")))

    # ------------------------------------------------------------ consumer
    def _schedule_consumer(self, cid: int) -> None:
        self.clock.schedule(self.cfg.poll_interval,
                            lambda: self._consume(cid))

    def _consume(self, cid: int) -> None:
        """One consumer pass: drain up to ``max_batch`` records per owned
        partition, run the REAL model, write results, commit.  The next
        poll is scheduled after the virtual busy time (real inference wall
        time + per-call overhead with jitter)."""
        cfg = self.cfg
        busy = 0.0
        for p in range(cfg.partitions):
            if p % cfg.num_consumers != cid:
                continue
            records = self.broker.poll("stratus", p, cfg.max_batch)
            if not records:
                continue
            images = np.stack([r.value["image"] for r in records])
            t0 = time.perf_counter()
            probs = np.asarray(self.predict_fn(images))
            elapsed = time.perf_counter() - t0
            for r, pr in zip(records, probs):
                self.store.upsert_idempotent(
                    r.value["key"],
                    {"probs": pr, "digit": int(np.argmax(pr))})
            self.broker.commit("stratus", p, records[-1].offset + 1)
            jitter = 1.0 + cfg.consume_jitter * self._rng.uniform(-1, 1)
            busy += cfg.consume_base * jitter + elapsed
        self.clock.schedule(max(cfg.poll_interval, busy),
                            lambda: self._consume(cid))


# ---------------------------------------------------------------- LLM


@dataclasses.dataclass
class GenRequest:
    """One generation request.  ``first_token_at``, ``last_token_at``
    and ``finished_at`` are the ``now`` of the step that made the token
    or finished the request (the step's start on the caller's clock:
    the cluster tier's virtual clock and the benchmarks' TTFT read
    them); obs events stamp results when they reached the host instead
    (``EngineObs.ready``)."""

    rid: int
    prompt: np.ndarray                 # (S,) int32
    max_new: int
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    submitted: float = 0.0
    first_token_at: Optional[float] = None
    last_token_at: Optional[float] = None
    finished_at: Optional[float] = None


_NO_SPAN = contextlib.nullcontext()


def _no_phase(_name):
    return _NO_SPAN


class _EngineObsMixin:
    """Shared instrumentation plumbing for both engines: an optional
    ``EngineObs`` facade plus per-token timestamp tracking that feeds
    the TTFT / inter-token histograms.  ``self._phase(name)`` spans one
    phase of a step (``EngineObs.phase``); with no obs it is a shared
    no-op context, so the step builds no annotation and reads no
    clock."""

    obs: Optional[EngineObs] = None
    _engine_kind = "slot"

    def attach_obs(self, obs, replica=None) -> None:
        """Bind (or re-bind) an ``Observability`` bundle; ``None``
        detaches.  Benchmarks re-bind a fresh bundle between the cold
        (compile-inclusive) and warm measured passes so the histograms
        cover exactly one pass.  ``replica`` adds a per-replica label
        to the engine metrics (cluster tier) — request latency
        histograms stay unlabeled either way so replica snapshots
        merge into one fleet-wide distribution."""
        if self.obs is not None:
            self.obs.close()
        self.obs = EngineObs(obs, self._engine_kind, replica) \
            if obs is not None else None
        self._phase = self.obs.phase if self.obs else _no_phase

    def _note_token(self, req: GenRequest, now: float) -> None:
        """One output token emitted for ``req`` by the step at ``now``:
        track the first/last token timestamps and feed the TTFT and
        inter-token histograms.  ``first_token_at`` stays the step's
        ``now``; the histograms and obs events take ``obs.ready``, when
        the ids reached the host."""
        first = req.first_token_at is None
        if first:
            req.first_token_at = now
        if self.obs:
            now = self.obs.ready
            if first:
                self.obs.first_token(req.rid, now, now - req.submitted)
            else:
                gap = None if req.last_token_at is None \
                    else now - req.last_token_at
                self.obs.token(req.rid, now, gap)
        req.last_token_at = now


def check_rope_context(cfg, context: int) -> None:
    """Refuse a context past a LongRoPE config's original context: the
    engines apply its short-regime factors only."""
    rs = cfg.rope_scaling
    if rs is not None and context > rs.original_max_position:
        raise ValueError(
            f"{cfg.name}: context {context} exceeds the LongRoPE original "
            f"context of {rs.original_max_position} tokens; only the "
            "short-regime factors are served")


class LLMEngine(_EngineObsMixin):
    """Continuous-batching decode over the unified Model API."""

    _engine_kind = "slot"

    def __init__(self, model, params, num_slots: int = 4,
                 cache_max: int = 512, eos_id: Optional[int] = None,
                 obs=None):
        check_rope_context(model.cfg, cache_max)
        self.model = model
        self.params = params
        self.slots = SlotManager(num_slots)
        self.cache_max = cache_max
        self.eos_id = eos_id
        self.num_slots = num_slots
        cfg = model.cfg
        self.cache = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype),
            model.cache_abstract(num_slots, cache_max))
        self.pos = np.full((num_slots,), -1, np.int64)
        self.active: Dict[int, GenRequest] = {}
        self.queue: List[GenRequest] = []
        self._rid = 0
        self.admissions = 0
        self.finished_count = 0
        self.prefill_tokens = 0
        self.generated_tokens = 0
        self._decode_batch_last = 0
        self._prefill_sigs: set = set()
        self._decode_sigs: set = set()
        self.attach_obs(obs)

        self._prefill = jax.jit(
            lambda p, b: model.prefill(p, b, cache_max=cache_max))
        self._decode = jax.jit(model.decode_step)

    def submit(self, prompt: np.ndarray, max_new: int = 16,
               now: float = 0.0) -> int:
        self._rid += 1
        self.queue.append(GenRequest(self._rid, np.asarray(prompt, np.int32),
                                     max_new, submitted=now))
        if self.obs:
            self.obs.request_queued(self._rid, now, len(prompt), max_new)
        return self._rid

    @property
    def idle(self) -> bool:
        return not self.queue and not self.active

    def step(self, now: float = 0.0) -> List[GenRequest]:
        """Admit one queued request (prefill) OR advance all live slots by
        one token.  Returns finished requests."""
        if self.obs is None:
            return self._step(now)
        t0 = time.perf_counter()
        self.obs.begin_step(now, t0)
        pre = (self.admissions, self.prefill_tokens, self.generated_tokens,
               len(self._prefill_sigs) + len(self._decode_sigs))
        self._decode_batch_last = 0
        done = self._step(now)
        self.obs.step(
            now, time.perf_counter() - t0,
            admitted=self.admissions - pre[0],
            chunk_tokens=self.prefill_tokens - pre[1],
            decode_batch=self._decode_batch_last,
            tokens=self.generated_tokens - pre[2],
            retraced=len(self._prefill_sigs) + len(self._decode_sigs)
            > pre[3],
            queue_depth=len(self.queue), active=len(self.active),
            free_blocks=self.slots.num_free,
            pool_occupancy=len(self.active) / max(self.num_slots, 1))
        return done

    def _step(self, now: float) -> List[GenRequest]:
        if self.queue and self.slots.num_free > 0:
            return self._admit(now)
        if self.active:
            return self._decode_all(now)
        return []

    def _admit(self, now: float) -> List[GenRequest]:
        req = self.queue.pop(0)
        slot = self.slots.alloc()
        batch = {"tokens": req.prompt[None, :]}
        self._prefill_sigs.add(len(req.prompt))
        logits, cache1 = self._prefill(self.params, batch)
        self.cache = write_slot(self.cache, cache1, slot)
        self.pos[slot] = len(req.prompt)
        tok = int(np.argmax(np.asarray(logits)[0, -1]))
        self.admissions += 1
        self.prefill_tokens += len(req.prompt)
        self.generated_tokens += 1
        if self.obs:
            self.obs.admitted(req.rid, now, resume=False, cached_blocks=0,
                              cow=False)
            self.obs.prefill_chunk(req.rid, now, 0, len(req.prompt))
        req.out_tokens.append(tok)
        self._note_token(req, now)
        self.active[slot] = req
        return self._collect(now)

    def _decode_all(self, now: float) -> List[GenRequest]:
        live = self.slots.live
        tokens = np.zeros((self.num_slots, 1), np.int32)
        pos = np.maximum(self.pos, 0).astype(np.int32)
        for s in live:
            tokens[s, 0] = self.active[s].out_tokens[-1]
        self._decode_sigs.add(self.num_slots)
        self._decode_batch_last = len(live)
        logits, self.cache = self._decode(self.params, self.cache,
                                          jnp.asarray(tokens),
                                          jnp.asarray(pos))
        arr = np.asarray(logits)
        for s in live:
            req = self.active[s]
            tok = int(np.argmax(arr[s, 0]))
            req.out_tokens.append(tok)
            self.generated_tokens += 1
            self._note_token(req, now)
            self.pos[s] += 1
        return self._collect(now)

    def _collect(self, now: float) -> List[GenRequest]:
        done = []
        for s in list(self.active):
            req = self.active[s]
            hit_eos = self.eos_id is not None and req.out_tokens and \
                req.out_tokens[-1] == self.eos_id
            if len(req.out_tokens) >= req.max_new or hit_eos or \
                    int(self.pos[s]) + 1 >= self.cache_max:
                req.finished_at = now
                done.append(req)
                del self.active[s]
                self.slots.free(s)
                self.pos[s] = -1
                self.finished_count += 1
                if self.obs:
                    at = self.obs.ready
                    self.obs.finished(req.rid, at, at - req.submitted,
                                      len(req.out_tokens))
        return done

    def stats(self) -> Dict[str, float]:
        """Queue/capacity gauges per ``serving/stats_schema.py`` (slots
        stand in for blocks: one slot == cache_max tokens)."""
        live = len(self.active)
        return {
            "engine": "slot",
            "queue_depth": len(self.queue),
            "active": live,
            "free_blocks": self.slots.num_free,
            "used_blocks": live,
            "total_blocks": self.num_slots,
            "pool_occupancy": live / max(self.num_slots, 1),
            "preemptions": 0,
            "admissions": self.admissions,
            "finished": self.finished_count,
            "prefill_compiles": len(self._prefill_sigs),
            "decode_compiles": len(self._decode_sigs),
        }


# ---------------------------------------------------------------- paged LLM


@dataclasses.dataclass
class _PrefillState:
    """Chunk cursor for an admitted request whose prompt is still
    streaming into the KV pool.  ``seq`` is the full sequence to write
    (prompt + generated tokens on a preempt-resume); lanes ``[start,
    done)`` are already spliced; ``blocks`` are the request's own
    private blocks and ``all_blocks`` prepends the refcount-shared
    prefix-cache blocks.  ``start`` (= matched prefix + COW offset)
    never moves; ``done`` advances one chunk per step."""

    req: GenRequest
    seq: np.ndarray
    blocks: List[int]
    all_blocks: List[int]
    start: int
    done: int

    def completes(self, take: int) -> bool:
        """A chunk of ``take`` lanes from ``done`` ends the sequence, so
        the dispatch's ids hold the first output token."""
        return self.done + take == len(self.seq)


class PagedLLMEngine(_EngineObsMixin):
    """Continuous batching over a block-paged KV pool with an
    admission-aware scheduler.

    The step loop is a continuous-batching scheduler (Sarathi/vLLM
    chunked prefill): every ``step()`` admits ALL admissible queued
    requests (not one), advances every pending prefill by up to
    ``prefill_chunk`` tokens in ONE ragged bucketed dispatch (per-row
    cursors/lengths/tables — a single trace serves any mix of chunk
    progress), then advances the decode batch one token.  Long prompts
    therefore never stall running decodes: at most ``step_token_budget``
    prompt tokens enter each step (default one chunk's worth), so decode
    latency stays flat while the prefill backlog drains.
    ``scheduler="serial"`` restores the pre-continuous behaviour — admit
    at most one request per step, whole-prompt prefill, decode only on
    admission-free steps — kept as the benchmark baseline and for exact
    per-shape trace accounting.

    Versus ``LLMEngine`` (one contiguous ``cache_max`` strip per slot):

      * memory is a shared pool of ``num_blocks`` x ``block_size``-token
        blocks — a request holds exactly ``ceil(len/block_size)`` blocks,
        so short requests don't reserve ``cache_max`` tokens and
        concurrency is bounded by *live tokens*, not slot count;
      * admission: a queued request is admitted while the pool can cover
        its prefill blocks AND the running batch's next decode step
        (each active request may need one growth block when it crosses a
        block boundary) — backpressure instead of OOM;
      * on pool exhaustion mid-decode the *youngest* active request is
        preempted: its blocks are freed and it is requeued at the front,
        to resume later by re-prefilling prompt + generated tokens
        (greedy decode makes the resumed continuation token-identical);
      * with ``prefix_cache=True`` a radix tree over per-block token
        keys (``serving/prefix_cache.py``) maps previously computed full
        prompt blocks into new requests' block tables for free
        (refcounted sharing), prefilling **only the uncached suffix**
        via ``Model.prefill_paged``; a divergence inside a partially
        matched block is served copy-on-write, and refcount-0 cached
        blocks are LRU-evicted before any preemption.

    Occupancy/queue gauges are exposed via ``stats()`` for the balancer
    and the serve CLI (schema: module-level note above).

    Every prefill — fresh prompt, preempt-resume, prefix-cache suffix —
    routes through the ONE padding-masked entry ``Model.prefill_paged``:
    the suffix is right-padded up to a length bucket and the prefix
    block table 0-padded up to a block bucket, so the engine compiles
    O(#buckets) prefill variants instead of O(#distinct (suffix_len,
    prefix_blocks) pairs).  ``prefill_buckets``: "auto" (powers of two
    up to ``max_len``), "off" (exact shapes — one trace per distinct
    shape, the pre-bucketing behaviour), or an explicit ascending list
    of lengths.  ``decode_kernel``: True routes decode attention through
    the Pallas paged-attention kernel (``kernels/paged_attention.py``),
    False forces the jnp block gather, None follows the global kernel
    switch (TPU / ``REPRO_USE_KERNELS``).

    ``decode_fusion`` (default True, continuous scheduler only)
    completes the Sarathi fusion: spec-OFF decode rows ride the SAME
    ragged verify dispatch as prefill chunks, as length-1 windows —
    one XLA program per step whether speculation is on or off, and the
    dedicated decode entry (plus its Pallas kernel) stays idle.  Set
    False to restore the separate decode dispatch (the execution-layer
    benchmarks compare decode paths through it).
    """

    _engine_kind = "paged"

    def __init__(self, model, params, num_blocks: int = 32,
                 block_size: int = 16, max_batch: int = 8,
                 max_len: int = 256, eos_id: Optional[int] = None,
                 prefix_cache: bool = False,
                 prefill_buckets="auto",
                 decode_kernel: Optional[bool] = None,
                 prefill_chunk: int = 256,
                 step_token_budget: Optional[int] = None,
                 scheduler: str = "continuous",
                 spec_decode: str = "off", spec_k: int = 4,
                 draft_model=None, draft_params=None,
                 admission_window: int = 4,
                 decode_fusion: bool = True,
                 window_accounting: bool = True,
                 obs=None):
        if not model.supports_paged:
            raise ValueError(f"{model.cfg.name}: paged engine needs a "
                             "decoder-only token stack")
        if scheduler not in ("continuous", "serial"):
            raise ValueError(f"scheduler must be 'continuous' or 'serial', "
                             f"got {scheduler!r}")
        if prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1, "
                             f"got {prefill_chunk}")
        if spec_decode not in ("off", "ngram", "draft"):
            raise ValueError(f"spec_decode must be 'off', 'ngram' or "
                             f"'draft', got {spec_decode!r}")
        if spec_decode != "off" and scheduler != "continuous":
            raise ValueError("spec_decode needs scheduler='continuous' "
                             "(verify rows ride the ragged chunk dispatch)")
        if spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {spec_k}")
        if admission_window < 1:
            raise ValueError(f"admission_window must be >= 1, "
                             f"got {admission_window}")
        check_rope_context(model.cfg, max_len)
        self.model = model
        self.params = params
        self.block_size = block_size
        self.max_batch = max_batch
        self.max_len = max_len
        self.eos_id = eos_id
        self.scheduler = scheduler
        self.allocator = BlockAllocator(num_blocks, block_size)
        # hybrid stacks: recurrent layers get one fixed-size state slot
        # per engine row (+1 trash row for padded dispatch rows, index
        # max_batch) beside the block pool — same scheduler governs both
        self.has_state = model.paged_has_state
        self.pools = model.pool_init(num_blocks, block_size,
                                     state_batch=max_batch + 1)
        if self.has_state and spec_decode != "off":
            raise ValueError(
                f"{model.cfg.name}: spec_decode needs roll-backable KV — "
                "recurrent layer state cannot roll back on draft rejection")
        # sliding-window residency bound: when EVERY layer's KV reach is
        # bounded (no global-attention layer), a request only ever needs
        # ceil(W/block_size)+1 live blocks — out-of-window blocks are
        # freed eagerly (invalidate-on-release) so pool capacity
        # multiplies.  ``window_accounting=False`` keeps the window-blind
        # accounting (the benchmark baseline).
        self.window_accounting = bool(window_accounting)
        lw = model.paged_live_window() if self.window_accounting else None
        self.live_window = lw
        self.window_bound = None if lw is None else \
            -(-lw // block_size) + 1
        self.window_blocks_freed = 0
        if self.has_state or lw is not None:
            # recurrent state is not reconstructible from cached blocks,
            # and eagerly-freed window chains would publish dangling
            # block ids — radix prefix reuse is structurally off for
            # both (constructing with prefix_cache=True stays legal; the
            # stats gauge honestly reports prefix_cache=0)
            prefix_cache = False
        self.prefix_cache: Optional[PrefixCache] = \
            PrefixCache(block_size) if prefix_cache else None
        self.nb_max = -(-max_len // block_size)
        self.block_table = np.zeros((max_batch, self.nb_max), np.int32)
        self.pos = np.zeros((max_batch,), np.int64)
        self.active: Dict[int, GenRequest] = {}      # row -> request
        self.row_blocks: Dict[int, List[int]] = {}   # row -> physical blocks
        self.prefilling: Dict[int, _PrefillState] = {}   # row -> cursor
        self.queue: List[GenRequest] = []
        self._rid = 0
        self.preemptions = 0
        self.admissions = 0
        self.finished_count = 0
        self.peak_active = 0
        self.prefill_tokens = 0
        # prefill tokens recomputed by preempted requests on resume
        self.recomputed_tokens = 0
        self.generated_tokens = 0
        self.cow_copies = 0
        self._decode_batch_last = 0
        # preempted request -> how many leading positions' KV it held
        # (the most over its preemptions): resumed chunks below that mark
        # are counted in ``recomputed_tokens``
        self._held_kv: Dict[int, int] = {}
        self.admission_window = admission_window
        self.admission_skips = 0
        # speculative decoding: drafter proposes, target verifies in the
        # ragged dispatch, acceptance rolls the block table back
        self.drafter = make_drafter(spec_decode, draft_model=draft_model,
                                    draft_params=draft_params,
                                    max_len=max_len)
        self.spec_decode = spec_decode
        # decode fusion (Sarathi, completed): spec-OFF decode rows ride
        # the same ragged verify dispatch as prefill chunks, as length-1
        # windows (the last emitted token, zero drafts) — ONE XLA
        # program per step whether speculation is on or off.  The
        # serial scheduler keeps the separate decode dispatch (it is
        # the per-shape-accounting baseline), as does
        # ``decode_fusion=False`` (the execution-layer benchmarks
        # compare the dedicated decode dispatch paths).
        self.decode_fusion = bool(decode_fusion)
        self._fused_decode = scheduler == "continuous" and \
            (self.drafter is not None or self.decode_fusion)
        self.spec_k = spec_k
        self.spec_proposed = 0       # drafted tokens sent to verify
        self.spec_accepted = 0       # drafted tokens that matched argmax
        self.spec_emitted = 0        # tokens emitted by verify rows
        self.spec_verify_rows = 0    # verify rows dispatched
        self.spec_rollbacks = 0      # verify rows that rolled back lanes
        self.decode_kernel = decode_kernel
        self.buckets = self._resolve_buckets(prefill_buckets)
        # bucket-align the chunk so chunked dispatches land on the same
        # trace signatures whole-suffix dispatches already use
        self.prefill_chunk = self._bucket_len(min(prefill_chunk, max_len))
        # default budget = one chunk's worth of prompt tokens per step:
        # bounds the per-step prefill compute without starving admission
        self.step_token_budget = int(step_token_budget) if \
            step_token_budget else self.prefill_chunk
        self._prefill_sigs: set = set()   # _ragged_dispatch signatures
        self._decode_sigs: set = set()
        self.attach_obs(obs)

        # the ONE prefill entry (and its verify twin): padding-masked,
        # position-offset, reads any cached prefix through the
        # (bucket-padded) block table, and scatters the chunk's KV into
        # its pool homes in the SAME dispatch — per-step overhead then
        # matches a decode step's single fused call, which is what the
        # speculative speed gate measures against.  The verify variant
        # returns per-lane greedy tokens instead of last-valid logits:
        # acceptance is pure argmax comparison, so the argmax runs
        # on-device and only (rows, c_pad) int32 crosses to host
        # instead of full-vocab logits per lane.
        bs = block_size

        def _prefill_entry(all_logits):
            def go(p, b, pools, bt, sp, sl, srows, cm):
                logits, caches = model.prefill_paged(
                    p, b, pools, bt, sp, seq_len=sl, cache_max=cm,
                    all_logits=all_logits, state_rows=srows)
                # scatter indices derived on-device: lane j of row i
                # holds absolute position start+j, living in block
                # bt[i, (start+j)//bs]; invalid (padding) lanes route
                # to the null block, whose validity lanes are scrubbed
                # back to -1 below — no host-side index assembly
                r, c = b["tokens"].shape
                lane = jnp.arange(c, dtype=jnp.int32)[None, :]
                pos = sp[:, None] + lane
                valid = lane < sl[:, None]
                db = jnp.where(valid,
                               jnp.take_along_axis(
                                   bt, jnp.minimum(pos // bs,
                                                   bt.shape[1] - 1),
                                   axis=1), 0)
                sr = jnp.broadcast_to(
                    jnp.arange(r, dtype=jnp.int32)[:, None], (r, c))
                slan = jnp.broadcast_to(lane, (r, c))
                pools = write_chunk_tokens(pools, caches, sr.ravel(),
                                           slan.ravel(), db.ravel(),
                                           (pos % bs).ravel(),
                                           state_rows=srows)
                pools = scrub_null_block(pools)
                out = jnp.argmax(logits, axis=-1).astype(jnp.int32) \
                    if all_logits else logits
                return out, pools
            # a step that returns the pool consumes the pool it was
            # given: donated, the scatter updates it in place instead of
            # copying every K/V leaf into a fresh output buffer first.
            # Callers rebind ``self.pools`` to the result and keep no
            # reference to the pool they passed in.
            return jax.jit(go, static_argnums=7, donate_argnums=(2,))
        self._prefill_paged = _prefill_entry(False)
        self._prefill_verify = _prefill_entry(True)
        self._decode = jax.jit(
            lambda p, pools, bt, t, pos, act: model.decode_step_paged(
                p, pools, bt, t, pos, act, decode_kernel=decode_kernel),
            donate_argnums=(1,))

    def _resolve_buckets(self, spec) -> Optional[List[int]]:
        """"auto" / "off" / explicit ascending lengths -> bucket list
        (None = bucketing off).  Auto is powers of two capped by a final
        ``max_len`` bucket — no suffix can exceed it, so padding past it
        would only burn compute and force truncation at the splice.
        Explicit lists are clamped to ``max_len`` too; lengths past the
        top bucket run at exact shape."""
        if spec is None or spec == "off":
            return None
        if spec == "auto":
            b, out = 8, []
            while b < self.max_len:
                out.append(b)
                b *= 2
            out.append(self.max_len)
            return out
        out = sorted({min(int(b), self.max_len) for b in spec})
        if not out or min(out) < 1:
            raise ValueError(f"bad prefill_buckets: {spec!r}")
        return out

    def _bucket_len(self, n: int) -> int:
        """Smallest bucket >= n (exact length when off / past the top)."""
        if self.buckets is not None:
            for b in self.buckets:
                if b >= n:
                    return b
        return n

    def _bucket_blocks(self, n: int) -> int:
        """Prefix-block-count bucket: next power of two (>= 1 so a fresh
        prompt still carries a — fully masked — null-block table)."""
        if self.buckets is None:
            return max(n, 1)
        m = 1
        while m < n:
            m *= 2
        return m

    def _bucket_rows(self, n: int) -> int:
        """Ragged-batch row bucket: next power of two so the chunk
        dispatch compiles O(log max_batch) row variants as the backlog
        drains (exact row count when bucketing is off)."""
        if self.buckets is None:
            return max(n, 1)
        m = 1
        while m < n:
            m *= 2
        return m

    # ------------------------------------------------------------ client
    def submit(self, prompt: np.ndarray, max_new: int = 16,
               now: float = 0.0) -> int:
        prompt = np.asarray(prompt, np.int32)
        if len(prompt) + max_new > self.max_len:
            raise ValueError(f"prompt({len(prompt)}) + max_new({max_new}) "
                             f"exceeds max_len={self.max_len}")
        # a request that can never hold its final KV footprint would sit
        # at the queue head forever (admission can never cover it) — fail
        # at submit, not as a silent stall.
        worst = self.allocator.blocks_for(len(prompt) + max_new - 1)
        if worst > self.allocator.num_usable:
            raise ValueError(
                f"request needs {worst} blocks at completion but the pool "
                f"only has {self.allocator.num_usable}: pool too small")
        self._rid += 1
        self.queue.append(GenRequest(self._rid, prompt, max_new,
                                     submitted=now))
        if self.obs:
            self.obs.request_queued(self._rid, now, len(prompt), max_new)
        return self._rid

    @property
    def idle(self) -> bool:
        return not self.queue and not self.active and not self.prefilling

    def prefix_probe(self, prompt) -> int:
        """How many leading tokens of ``prompt`` this engine's radix
        cache could serve RIGHT NOW, without admitting anything —
        side-effect free (no LRU touch, no hit/miss accounting).  The
        cluster routing tier probes replicas with this to find (or
        verify) the longest cached match; 0 when the prefix cache is
        off or cold.  The last token is reserved exactly as the admit
        path reserves it: its logits produce the first output token,
        so it can never be served from cache."""
        if self.prefix_cache is None:
            return 0
        tokens = np.asarray(prompt, np.int32)[:-1]
        m = self.prefix_cache.probe(tokens)
        return len(m.blocks) * self.block_size + m.partial_len

    def stats(self) -> Dict[str, float]:
        """Gauges per the module-level stats schema."""
        alloc = self.allocator
        pc = self.prefix_cache
        return {
            "engine": "paged",
            "queue_depth": len(self.queue),
            "active": len(self.active),
            "prefilling": len(self.prefilling),
            "free_blocks": alloc.num_free,
            "used_blocks": alloc.num_live,
            "total_blocks": alloc.num_usable,
            "pool_occupancy": alloc.num_live / max(alloc.num_usable, 1),
            "preemptions": self.preemptions,
            "admissions": self.admissions,
            "finished": self.finished_count,
            "peak_active": self.peak_active,
            "prefill_tokens": self.prefill_tokens,
            "recomputed_tokens": self.recomputed_tokens,
            "prefix_cache": int(pc is not None),
            "hit_rate": pc.hit_rate if pc else 0.0,
            "cached_blocks": pc.cached_blocks if pc else 0,
            "evictions": pc.evictions if pc else 0,
            "cow_copies": self.cow_copies,
            "prefill_compiles": len(self._prefill_sigs),
            "decode_compiles": len(self._decode_sigs),
            "decode_kernel": int(self._decode_kernel_on()),
            "decode_fusion": int(self._fused_decode),
            "admission_skips": self.admission_skips,
            "window_blocks_freed": self.window_blocks_freed,
            "state_slots_used": (len(self.active) + len(self.prefilling))
                if self.has_state else 0,
            "spec_decode": self.spec_decode,
            "spec_k": self.spec_k if self.drafter is not None else 0,
            "accepted_tokens_per_step":
                self.spec_emitted / max(self.spec_verify_rows, 1),
            "draft_hit_rate":
                self.spec_accepted / max(self.spec_proposed, 1),
            "spec_rollbacks": self.spec_rollbacks,
        }

    def _decode_kernel_on(self) -> bool:
        """Is decode attention ACTUALLY running through the Pallas
        kernel?  Requesting it (``decode_kernel=True`` / the global
        switch) is not enough: quantized pools always take the jnp path,
        and off-TPU the ops layer falls back to the jnp reference unless
        interpret mode is forced — the gauge must not claim a kernel
        that never dispatched."""
        from repro.kernels.ops import kernel_path_active, kernels_enabled

        if self._fused_decode:
            # decode rides the fused ragged dispatch — the dedicated
            # decode entry (and its kernel) never runs
            return False
        requested = bool(self.decode_kernel) if \
            self.decode_kernel is not None else kernels_enabled()
        return requested and not self.model.cfg.kv_cache_quant and \
            kernel_path_active()

    # ------------------------------------------------------------ sched
    def _free_row(self) -> Optional[int]:
        for r in range(self.max_batch):
            if r not in self.active and r not in self.prefilling:
                return r
        return None

    def _next_step_block_need(self) -> int:
        """Blocks the running batch needs for its next decode step (a
        request crossing a block boundary needs one growth block)."""
        need = 0
        for row in self.active:
            if int(self.pos[row]) // self.block_size >= \
                    len(self.row_blocks[row]):
                need += 1
        return need

    def _seq_for(self, req: GenRequest) -> np.ndarray:
        """Prompt + already-generated tokens (a preempted request resumes
        by re-prefilling both; greedy decode keeps it token-identical)."""
        if not req.out_tokens:
            return req.prompt
        return np.concatenate([req.prompt,
                               np.asarray(req.out_tokens, np.int32)])

    def _match_for(self, req: GenRequest, probe: bool) -> MatchResult:
        """Cached-prefix match for a request.  The last sequence token is
        reserved: the uncached suffix must never be empty (its final
        logits produce the next output token)."""
        seq = self._seq_for(req)
        tokens = seq[:-1]
        if probe:
            return self.prefix_cache.probe(tokens)
        return self.prefix_cache.match(tokens)

    def _admission_ok(self, req: GenRequest) -> bool:
        seq_len = len(req.prompt) + len(req.out_tokens)
        need = self.allocator.blocks_for(seq_len)
        avail = self.allocator.num_free
        if self.prefix_cache is not None:
            m = self._match_for(req, probe=True)
            need -= len(m.blocks)             # mapped for free
            # refcount-0 cached blocks are evictable headroom — except
            # the ones this very request is about to take a hold on.
            protected = set(m.blocks)
            if m.partial_len:
                protected.add(m.partial_block)
            avail += self.prefix_cache.evictable(self.allocator,
                                                 frozenset(protected))
        if seq_len % self.block_size == 0:
            need += 1      # its own first decode step crosses a boundary
        free_after = avail - need
        if free_after < 0:
            # always keep making progress: force-admit only when nothing
            # else is running OR mid-prefill (their blocks are held)
            return not self.active and not self.prefilling
        if not self.active:
            return True
        return free_after >= self._next_step_block_need()

    def _alloc_or_evict(self, n: int) -> Optional[List[int]]:
        """Allocate ``n`` blocks, LRU-evicting refcount-0 cached blocks
        first when the free list falls short — eviction always precedes
        preemption."""
        got = self.allocator.alloc(n)
        if got is not None or self.prefix_cache is None:
            return got
        released = self.prefix_cache.evict(n - self.allocator.num_free,
                                           self.allocator)
        if released:
            self.pools = invalidate_blocks(self.pools, released)
        return self.allocator.alloc(n)

    def _free_blocks(self, blocks: List[int]) -> None:
        """Drop this request's hold; invalidate only the blocks whose
        last holder released (blocks the prefix cache still holds keep
        their KV readable for future matches).  0 entries are window-
        freed logical slots (already released) — skipped, never the
        null block being double-freed."""
        live = [b for b in blocks if b]
        released = self.allocator.free(live)
        if released:
            self.pools = invalidate_blocks(self.pools, released)

    def _window_shrink(self, blocks: List[int], next_pos: int,
                       table=None) -> None:
        """Eagerly release blocks that have slid wholly out of the live
        window.  ``blocks`` keeps its LENGTH — logical slot l stays at
        table column l so position arithmetic never shifts; freed
        entries become 0 (the null block, which every read masks) both
        in the list and in ``table`` (the engine's block_table row; None
        while prefilling — chunk dispatches carry their own ragged
        tables).  The write block ``next_pos // bs`` is always retained
        (the min with ``next_pos // bs`` guards the W <= bs case), so a
        request's live blocks never exceed ceil(W/bs)+1.  Window layers
        never publish to the radix tree (prefix cache is off for bounded
        stacks), so no freed block can carry a refcount>1 hold from
        sharing — but ``_free_blocks`` still routes through the
        allocator's refcounts, keeping the invariant checkable."""
        if self.window_bound is None:
            return
        bs = self.block_size
        dead = min(max(0, (next_pos - self.live_window + 1) // bs),
                   next_pos // bs)
        freed = []
        for l in range(min(dead, len(blocks))):
            if blocks[l]:
                freed.append(blocks[l])
                blocks[l] = 0
                if table is not None:
                    table[l] = 0
        if freed:
            self._free_blocks(freed)
            self.window_blocks_freed += len(freed)

    def step(self, now: float = 0.0) -> List[GenRequest]:
        """One scheduler step.  Continuous (default): admit every
        admissible queued request, advance all pending prefills by one
        token-budgeted ragged chunk dispatch, then advance the decode
        batch one token — decode latency stays flat while the prefill
        backlog drains.  Serial: admit at most one request per step,
        prefill its whole prompt, decode only on admission-free steps
        (the pre-continuous behaviour, kept as the benchmark baseline).
        Returns finished requests."""
        if self.obs is None:
            return self._step(now)
        t0 = time.perf_counter()
        self.obs.begin_step(now, t0)
        pre = (self.admissions, self.prefill_tokens, self.generated_tokens,
               len(self._prefill_sigs) + len(self._decode_sigs))
        self._decode_batch_last = 0
        done = self._step(now)
        alloc = self.allocator
        self.obs.step(
            now, time.perf_counter() - t0,
            admitted=self.admissions - pre[0],
            chunk_tokens=self.prefill_tokens - pre[1],
            decode_batch=self._decode_batch_last,
            tokens=self.generated_tokens - pre[2],
            retraced=len(self._prefill_sigs) + len(self._decode_sigs)
            > pre[3],
            queue_depth=len(self.queue),
            active=len(self.active) + len(self.prefilling),
            free_blocks=alloc.num_free,
            pool_occupancy=alloc.num_live / max(alloc.num_usable, 1))
        return done

    def _step(self, now: float) -> List[GenRequest]:
        """Phases (``EngineObs.phase``): schedule (admission, chunk and
        verify planning, block growth, copy-on-write, preemption) ->
        prepare (the dispatch's host arrays and their transfers) ->
        launch (the jitted call, until it returns) -> wait (the host
        blocked on the device's ids) -> account (cursors, acceptance,
        rollback, finishing).  The unfused path runs up to two
        dispatches a step and spans each the same way."""
        if self._fused_decode:
            return self._spec_step(now)
        ph = self._phase
        with ph("schedule"):
            self._admit_all(now)
        done: List[GenRequest] = []
        prefilled = bool(self.prefilling)
        if self.prefilling:
            self._prefill_chunks(now)
            # requests satisfied at prefill (max_new == 1 / max_len edge)
            # must leave before the decode below hands them another token
            with ph("account"):
                done = self._collect(now)
        if self.scheduler == "serial" and prefilled:
            return done
        if self.active:
            return done + self._decode_all(now)
        with ph("account"):
            return done + self._collect(now)

    def _admit_all(self, now: float) -> None:
        """Admit queued requests.  The continuous scheduler scans an
        ``admission_window``-deep prefix of the queue instead of just
        the head: a head that can't admit (pool too tight for its
        *suffix* block need, or deferred behind a prefix writer) no
        longer blocks a later request that CAN — in particular one
        whose prompt is largely radix-cached and so needs only a few
        suffix blocks (``_admission_ok`` already charges matched blocks
        as free).  Requests are still tried in FIFO order inside the
        window, so the head admits first whenever it fits.  Serial
        keeps strict head-only admission."""
        window = 1 if self.scheduler == "serial" else self.admission_window
        while self.queue and self._free_row() is not None:
            picked = None
            for i, req in enumerate(self.queue[:window]):
                if self._defer_for_prefix(req):
                    continue
                if self._admission_ok(req):
                    picked = i
                    break
            if picked is None:
                return
            if picked:
                self.admission_skips += 1
            self._admit_setup(self.queue.pop(picked), now)
            if self.scheduler == "serial":
                return

    def _defer_for_prefix(self, req: GenRequest) -> bool:
        """Hold a request back while a still-prefilling request is
        writing a prefix it shares: once the writer finishes and
        publishes its blocks to the radix tree, the held request admits
        with cache hits instead of recomputing the shared prefix.  (The
        serial scheduler got this ordering for free by admitting one
        request per step; pending prefills always progress, so deferral
        can never deadlock.)"""
        if self.prefix_cache is None or not self.prefilling:
            return False
        seq = self._seq_for(req)[:-1]         # last token never matchable
        if not len(seq):
            return False
        m = self._match_for(req, probe=True)
        matched = len(m.blocks) * self.block_size + m.partial_len
        for st in self.prefilling.values():
            n = min(len(seq), len(st.seq))
            eq = seq[:n] == st.seq[:n]
            common = int(n if eq.all() else np.argmin(eq))
            if common >= self.block_size and common > matched:
                return True
        return False

    def _admit_setup(self, req: GenRequest, now: float) -> None:
        """Claim a row + physical blocks for a queued request and queue
        its prompt for chunked prefill (no model dispatch here).
        Resume-aware: a preempted request re-prefills (or re-matches —
        its own blocks usually survive in the tree) its prompt plus
        everything it already generated (same greedy continuation)."""
        seq = self._seq_for(req)
        bs = self.block_size
        nb_total = self.allocator.blocks_for(len(seq))
        match = MatchResult([]) if self.prefix_cache is None else \
            self._match_for(req, probe=False)
        k, j = len(match.blocks), match.partial_len
        # take holds on the shared prefix + COW donor FIRST so eviction
        # inside _alloc_or_evict can never reclaim them out from under us
        for b in match.blocks:
            self.allocator.incref(b)
        if j:
            self.allocator.incref(match.partial_block)
        blocks = self._alloc_or_evict(nb_total - k)
        if blocks is None and j:
            # pathological fit: our hold on the COW donor is pinning the
            # last block a drained pool needs — forgo the partial match
            # (the donor becomes evictable again) and retry.
            self.allocator.free([match.partial_block])
            match, j = MatchResult(match.blocks), 0
            blocks = self._alloc_or_evict(nb_total - k)
        assert blocks is not None, "admission check guarantees capacity"
        row = self._free_row()
        start = k * bs + j
        if j:       # copy-on-write: private copy of the donor block
            self.pools = copy_blocks(self.pools, [match.partial_block],
                                     [blocks[0]])
            self.cow_copies += 1
            self.allocator.free([match.partial_block])       # drop COW hold
        all_blocks = match.blocks + blocks
        # the engine-side block_table row stays null until the prefill
        # completes: decode dispatches route every INACTIVE row's masked
        # write through its table row, which must hit the null block —
        # chunk dispatches carry their own ragged tables meanwhile.
        self.block_table[row, :] = 0
        self.pos[row] = 0
        self.prefilling[row] = _PrefillState(req, seq, blocks, all_blocks,
                                             start, start)
        self.admissions += 1
        self.peak_active = max(self.peak_active,
                               len(self.active) + len(self.prefilling))
        if self.obs:
            held = self._held_kv.get(req.rid)
            self.obs.admitted(req.rid, now, resume=held is not None,
                              cached_blocks=k, cow=bool(j),
                              recomputed=max(0, (held or 0) - start))

    def _select_chunks(self) -> tuple:
        """Pick this step's prefill chunks: oldest request first, each
        up to ``prefill_chunk`` tokens, total capped by
        ``step_token_budget`` (the oldest row always gets at least one
        token so the backlog can never stall).  The serial scheduler
        takes each request's whole remaining suffix instead.  Pure —
        touches no engine state.  -> ([(row, take)], budget_left)."""
        order = sorted(self.prefilling,
                       key=lambda r: self.prefilling[r].req.rid)
        budget = self.step_token_budget
        sel: List[tuple] = []                     # (row, take)
        for r in order:
            st = self.prefilling[r]
            remaining = len(st.seq) - st.done
            take = remaining if self.scheduler == "serial" else \
                min(self.prefill_chunk, remaining, budget)
            if take <= 0:
                break                             # budget exhausted
            budget -= take
            sel.append((r, take))
        if not sel and order:                     # budget < 1: still move
            r = order[0]
            st = self.prefilling[r]
            sel = [(r, min(self.prefill_chunk, len(st.seq) - st.done))]
            budget = 0
        return sel, max(budget, 0)

    def _ragged_dispatch(self, rows: List[tuple], state_rows=None, *,
                         all_logits: bool):
        """ONE bucketed masked dispatch over a ragged batch of rows —
        prefill chunks and (spec mode) verify windows share it.  Each
        row is ``(tokens, start, blocks)``: ``tokens`` (take,) land at
        absolute positions ``[start, start+take)`` and are scattered
        into ``blocks`` by the ``write_chunk_tokens`` fused into the
        same dispatch (indices derived on-device from starts/lens/
        table; padding lanes land in the scrubbed null block).  Rows
        pad to a power-of-two row bucket, tokens to a length bucket,
        tables to a block bucket; the trace signature is (row bucket,
        length bucket, block bucket, all_logits).  Returns the dispatch
        output — (rows, 1, V) last-valid logit slices, or (rows, c_pad)
        per-lane greedy tokens when ``all_logits`` (the verify entry
        argmaxes on-device: acceptance needs every window position but
        only as token ids).

        ``state_rows`` (one engine row per dispatch row, same order as
        ``rows``) maps hybrid-stack dispatch rows to their recurrent
        state slots; padding rows route to the trash slot (index
        ``max_batch``)."""
        ph = self._phase
        with ph("prepare"):
            toks, args = self._ragged_inputs(rows, state_rows, all_logits)
        fn = self._prefill_verify if all_logits else self._prefill_paged
        with ph("launch"):
            out, self.pools = fn(self.params, {"tokens": toks}, self.pools,
                                 *args)
        return out

    def _ragged_inputs(self, rows: List[tuple], state_rows,
                       all_logits: bool) -> tuple:
        """Host side of ``_ragged_dispatch``: the padded tokens, table,
        cursors, lengths and state rows, as numpy arrays.  The jitted
        step's own dispatch moves them to the device, which takes less
        host time than a Python-level device put apiece, on a path that
        every engine step waits for.
        -> (tokens, (table, starts, lens, state rows, c_pad))."""
        r_pad = self._bucket_rows(len(rows))
        # decode-only fused steps are all length-1 windows: dispatch at
        # c_pad=1 instead of padding every lane up to the first length
        # bucket (8x wasted attention compute on the hottest step shape)
        longest = max(len(t) for t, _, _ in rows)
        c_pad = 1 if longest == 1 else self._bucket_len(longest)
        nb_pad = self._bucket_blocks(max(len(b) for _, _, b in rows))
        toks = np.zeros((r_pad, c_pad), np.int32)
        starts = np.zeros((r_pad,), np.int32)
        # pad rows: 1 "valid" garbage token against the null table —
        # shape-legal, masked everywhere, discarded by the caller
        lens = np.ones((r_pad,), np.int32)
        bt = np.zeros((r_pad, nb_pad), np.int32)
        for i, (t, start, blocks) in enumerate(rows):
            toks[i, :len(t)] = t
            starts[i] = start
            lens[i] = len(t)
            bt[i, :len(blocks)] = blocks
        srows = np.full((r_pad,), self.max_batch, np.int32)   # trash slot
        if state_rows is not None:
            srows[:len(state_rows)] = state_rows
        self._prefill_sigs.add((r_pad, c_pad, nb_pad, all_logits))
        return toks, (bt, starts, lens, srows, c_pad)

    def _chunk_rows(self, sel: List[tuple]) -> List[tuple]:
        return [(self.prefilling[r].seq[self.prefilling[r].done:
                                        self.prefilling[r].done + take],
                 self.prefilling[r].done,
                 self.prefilling[r].all_blocks)
                for r, take in sel]

    def _account_chunks(self, sel: List[tuple], tok_at, now: float) -> None:
        """Advance chunk cursors after a dispatch; ``tok_at(i, take)``
        returns row i's final-lane greedy token for the first output
        token when the prefill completes."""
        for i, (r, take) in enumerate(sel):
            st = self.prefilling[r]
            again = max(0, min(st.done + take,
                               self._held_kv.get(st.req.rid, 0)) - st.done)
            if self.obs:
                self.obs.prefill_chunk(st.req.rid, now, st.done, take, again)
            last = st.completes(take)
            st.done += take
            self.prefill_tokens += take
            self.recomputed_tokens += again
            # window stacks: blocks the chunk just slid out of the live
            # window die immediately (next query position = st.done)
            self._window_shrink(st.all_blocks, st.done)
            if last:
                self._held_kv.pop(st.req.rid, None)
                self._finish_prefill(r, tok_at(i, take), now)

    def _prefill_chunks(self, now: float) -> None:
        """Advance every pending prefill by up to one chunk in ONE
        ragged bucketed dispatch (unfused path: serial scheduler or
        ``decode_fusion=False``; fused mode carries chunks in the
        verify dispatch in ``_spec_step``).  Logits come to the host
        only when a chunk completes its prompt."""
        ph = self._phase
        with ph("schedule"):
            sel, _ = self._select_chunks()
            rows = self._chunk_rows(sel)
            completes = any(self.prefilling[r].completes(take)
                            for r, take in sel)
        logits = self._ragged_dispatch(rows, [r for r, _ in sel],
                                       all_logits=False)
        with ph("wait"):
            arr = np.asarray(logits) if completes else None
        with ph("account"):
            self._account_chunks(
                sel, lambda i, take: int(np.argmax(arr[i, 0])), now)

    # ------------------------------------------------------------ spec
    def _spec_step(self, now: float) -> List[GenRequest]:
        """Fused step (speculation on OR plain decode fusion): ONE
        ragged dispatch carries this step's prefill chunks AND one
        verify row per decoding request — the last emitted token plus
        up to ``spec_k`` drafted tokens (zero with no drafter: plain
        decode as a length-1 window), run through the masked prefill
        entry at per-lane logits.  Acceptance keeps the longest drafted
        prefix matching the target's own greedy argmax plus the bonus
        token from the first mismatch, so output stays token-identical
        to non-speculative greedy decode by construction; rejected
        lanes roll back.  Drafted tokens are charged to the step token
        budget AFTER prefill chunks (chunked prefill keeps priority),
        but every decoding row always verifies at least its mandatory
        one-token window, so decode advances every step regardless.
        The per-token decode kernel is idle in spec mode — verify rows
        replace the decode dispatch entirely."""
        ph = self._phase
        with ph("schedule"):
            self._admit_all(now)
            sel, budget_left = self._select_chunks()
            verify = self._plan_verify(budget_left, now)
            # planning may preempt (growth under a dry pool) — drop
            # entries whose row was reclaimed
            sel = [(r, t) for r, t in sel if r in self.prefilling]
            verify = [(r, w) for r, w in verify if r in self.active]
            rows = self._chunk_rows(sel) + [
                (np.asarray(w, np.int32), int(self.pos[r]),
                 self.row_blocks[r]) for r, w in verify]
        if not rows:
            with ph("account"):
                return self._collect(now)
        self._decode_batch_last = len(verify)
        srows = [r for r, _ in sel] + [r for r, _ in verify]
        greedy = self._ragged_dispatch(rows, srows, all_logits=True)
        with ph("wait"):
            arr = np.asarray(greedy)              # (r_pad, c_pad) tokens
        with ph("account"):
            nchunk = len(sel)
            self._account_chunks(
                sel, lambda i, take: int(arr[i, take - 1]), now)
            stale_b, stale_l = [], []
            for j, (row, window) in enumerate(verify):
                self._accept_verify(row, window, arr[nchunk + j], now,
                                    stale_b, stale_l)
            if stale_b:
                self.pools = invalidate_lanes(self.pools,
                                              np.concatenate(stale_b),
                                              np.concatenate(stale_l))
            return self._collect(now)

    def _plan_verify(self, budget: int, now: float) -> List[tuple]:
        """Build this step's verify windows: for every decoding row
        (oldest first) the mandatory last-emitted token plus up to
        ``spec_k`` drafted tokens — capped by the request's remaining
        ``max_new`` (acceptance may emit the whole window, which must
        never overshoot the greedy stop) and by what's left of the
        step token budget.  ``_prepare_verify_row`` then secures
        private writable blocks for the window's lanes, shrinking the
        window / evicting / preempting as needed.  -> [(row, window)]."""
        plan: List[tuple] = []
        for row in sorted(self.active, key=lambda r: self.active[r].rid):
            if row not in self.active:
                continue        # preempted while preparing an earlier row
            req = self.active[row]
            remaining = req.max_new - len(req.out_tokens)
            # no drafter: plain fused decode — the mandatory one-token
            # window alone (the row still joins the ragged dispatch)
            cap = 0 if self.drafter is None else \
                min(self.spec_k, remaining - 1, budget)
            drafts = self.drafter.propose(self._seq_for(req), cap) \
                if cap > 0 else []
            take = self._prepare_verify_row(row, 1 + len(drafts), now)
            if take is None:
                continue        # the row itself got preempted
            budget -= take - 1
            plan.append((row, [req.out_tokens[-1]] + drafts[:take - 1]))
        return plan

    def _prepare_verify_row(self, row: int, take: int,
                            now: float) -> Optional[int]:
        """Secure private, writable KV lanes ``[pos, pos+take)`` for a
        verify row.  Grows the block table (evicting cold cached blocks
        first, then preempting the youngest — exactly the non-spec
        decode growth policy); when the pool can't cover the *drafted*
        lanes the window shrinks instead (speculation never preempts
        anyone plain decode wouldn't); a write-range block still shared
        with the radix tree or another request is copied to a private
        block first — speculative writes must never touch refcount>1
        blocks, their rollback would corrupt the other holders' KV.
        Returns the (possibly shrunk) window length, or None if the row
        itself was preempted."""
        bs = self.block_size
        while row in self.active:
            P = int(self.pos[row])
            blocks = self.row_blocks[row]
            need = self.allocator.blocks_for(P + take)
            if len(blocks) < need:
                got = self._alloc_or_evict(1)
                if got is not None:
                    blocks.append(got[0])
                    self.block_table[row, len(blocks) - 1] = got[0]
                    continue
                fit = len(blocks) * bs - P       # lanes already covered
                if fit >= 1:
                    take = min(take, fit)        # sacrifice drafts
                    continue
                if len(self.active) + len(self.prefilling) == 1:
                    raise RuntimeError(
                        "KV pool too small for a single request: "
                        f"{self.allocator.num_usable} usable blocks")
                self._preempt_youngest(now)
                continue
            shared = next((i for i in range(P // bs, need)
                           if self.allocator.refcount(blocks[i]) > 1),
                          None)
            if shared is None:
                return take
            got = self._alloc_or_evict(1)
            if got is None:
                if len(self.active) + len(self.prefilling) == 1:
                    raise RuntimeError(
                        "KV pool too small for a single request: "
                        f"{self.allocator.num_usable} usable blocks")
                self._preempt_youngest(now)
                continue
            self.pools = copy_blocks(self.pools, [blocks[shared]],
                                     [got[0]])
            self.allocator.free([blocks[shared]])   # refcount>1: not released
            blocks[shared] = got[0]
            self.block_table[row, shared] = got[0]
            self.cow_copies += 1
        return None

    def _accept_verify(self, row: int, window: List[int], row_greedy,
                       now: float, stale_b: List, stale_l: List) -> None:
        """Greedy acceptance + block-table rollback for one verify row.
        ``window[0]`` is the last emitted token (its KV lands at lane
        ``pos``), drafts follow; ``row_greedy`` (c_pad,) holds the
        target's greedy token at every window lane.  Accept drafts
        while draft == argmax, emit the bonus token from the first
        mismatch, truncate at EOS (non-spec decode would have stopped
        there).  KV lanes past the accepted cursor roll back: whole
        tail blocks are freed (invalidated on release), stale lanes
        inside the last kept block are appended to ``stale_b``/
        ``stale_l`` for the step's single batched pos-invalidation."""
        req = self.active[row]
        take = len(window)
        P = int(self.pos[row])
        g = row_greedy[:take]
        a = 0
        while a < take - 1 and window[a + 1] == int(g[a]):
            a += 1
        newly = [int(t) for t in window[1:a + 1]] + [int(g[a])]
        if self.eos_id is not None and self.eos_id in newly:
            newly = newly[:newly.index(self.eos_id) + 1]
        m = len(newly)
        rolled = take - m
        if self.drafter is not None:
            # plain fused decode (drafter off) must not shift the spec
            # gauges: its windows are always length 1, accept 0 drafts,
            # emit 1 — counting them would dilute every spec ratio
            self.spec_verify_rows += 1
            self.spec_proposed += take - 1
            self.spec_accepted += a
            self.spec_emitted += m
            if rolled > 0:
                self.spec_rollbacks += 1
        for t in newly:
            req.out_tokens.append(t)
            self.generated_tokens += 1
            self._note_token(req, now)
        self.pos[row] = P + m
        blocks = self.row_blocks[row]
        keep = self.allocator.blocks_for(P + m)
        if keep < len(blocks):
            tail = blocks[keep:]
            del blocks[keep:]
            self.block_table[row, keep:] = 0
            self._free_blocks(tail)
        stale_lo = P + m
        stale_hi = min(P + take, keep * self.block_size)
        if stale_hi > stale_lo:
            p = np.arange(stale_lo, stale_hi)
            stale_b.append(np.asarray(blocks, np.int32)
                           [p // self.block_size])
            stale_l.append((p % self.block_size).astype(np.int32))
        self._window_shrink(blocks, P + m, self.block_table[row])
        if self.obs and self.drafter is not None:
            self.obs.spec_verify(req.rid, self.obs.ready,
                                 proposed=take - 1, accepted=a, emitted=m,
                                 rolled_back=rolled)

    def _finish_prefill(self, row: int, tok: int, now: float) -> None:
        """Last chunk spliced: emit the first token and move the row to
        the decode batch."""
        st = self.prefilling.pop(row)
        req = st.req
        if self.prefix_cache is not None:
            # publish this request's full blocks (matched ones dedupe)
            self.prefix_cache.insert(st.seq, st.all_blocks, self.allocator)
        req.out_tokens.append(tok)
        self.generated_tokens += 1
        self._note_token(req, now)
        self.active[row] = req
        self.row_blocks[row] = list(st.all_blocks)
        self.block_table[row, :len(st.all_blocks)] = st.all_blocks
        self.pos[row] = len(st.seq)

    def _preempt_youngest(self, now: float = 0.0) -> None:
        """Evict the youngest admitted request — decoding OR mid-prefill
        (chunk granularity: a half-prefilled prompt just drops its
        blocks and re-chunks from its cursor start on resume)."""
        rows = {r: st.req for r, st in self.prefilling.items()}
        rows.update({r: req for r, req in self.active.items()})
        row = max(rows, key=lambda r: rows[r].rid)
        req = rows[row]
        where = "prefill" if row in self.prefilling else "decode"
        if row in self.prefilling:
            st = self.prefilling.pop(row)
            held = st.done
            self._free_blocks(st.all_blocks)
        else:
            held = int(self.pos[row])     # the last token's KV is unwritten
            del self.active[row]
            self._free_blocks(self.row_blocks.pop(row))
        self._held_kv[req.rid] = max(held, self._held_kv.get(req.rid, 0))
        self.block_table[row, :] = 0
        self.pos[row] = 0
        self.queue.insert(0, req)             # resumes as soon as blocks free
        self.preemptions += 1
        if self.obs:
            self.obs.preempted(req.rid, now, where)

    def _decode_all(self, now: float) -> List[GenRequest]:
        ph = self._phase
        # grow block tables for the next write, oldest request first;
        # evict cold cached blocks, then preempt the youngest, instead
        # of failing when the pool is dry.
        with ph("schedule"):
            self._grow_decode_blocks(now)
        if not self.active:
            return []

        with ph("prepare"):
            tokens = np.zeros((self.max_batch, 1), np.int32)
            pos = np.zeros((self.max_batch,), np.int32)
            active_mask = np.zeros((self.max_batch,), bool)
            for row, req in self.active.items():
                tokens[row, 0] = req.out_tokens[-1]
                pos[row] = self.pos[row]
                active_mask[row] = True
            self._decode_sigs.add((self.max_batch, self.nb_max))
            self._decode_batch_last = len(self.active)
            args = (jnp.asarray(self.block_table), jnp.asarray(tokens),
                    jnp.asarray(pos), jnp.asarray(active_mask))
        with ph("launch"):
            logits, self.pools = self._decode(self.params, self.pools,
                                              *args)
        with ph("wait"):
            arr = np.asarray(logits)
        with ph("account"):
            for row, req in self.active.items():
                req.out_tokens.append(int(np.argmax(arr[row, 0])))
                self.generated_tokens += 1
                self._note_token(req, now)
                self.pos[row] += 1
                self._window_shrink(self.row_blocks[row],
                                    int(self.pos[row]),
                                    self.block_table[row])
            return self._collect(now)

    def _grow_decode_blocks(self, now: float) -> None:
        for row in sorted(self.active, key=lambda r: self.active[r].rid):
            while row in self.active and \
                    int(self.pos[row]) // self.block_size >= \
                    len(self.row_blocks[row]):
                got = self._alloc_or_evict(1)
                if got is not None:
                    self.row_blocks[row].append(got[0])
                    self.block_table[row, len(self.row_blocks[row]) - 1] = \
                        got[0]
                elif len(self.active) + len(self.prefilling) == 1:
                    raise RuntimeError(
                        "KV pool too small for a single request: "
                        f"{self.allocator.num_usable} usable blocks")
                else:
                    self._preempt_youngest(now)

    def _collect(self, now: float) -> List[GenRequest]:
        done = []
        for row in list(self.active):
            req = self.active[row]
            hit_eos = self.eos_id is not None and req.out_tokens and \
                req.out_tokens[-1] == self.eos_id
            if len(req.out_tokens) >= req.max_new or hit_eos or \
                    int(self.pos[row]) + 1 >= self.max_len:
                req.finished_at = now
                done.append(req)
                del self.active[row]
                blocks = self.row_blocks.pop(row)
                if self.prefix_cache is not None:
                    # publish the GENERATED blocks too (prompt blocks
                    # were published at prefill finish): a multi-turn
                    # follow-up whose prompt embeds this turn's output
                    # then hits the tree — and its history gives n-gram
                    # drafting a hot lookup table on turn 2+.  Only
                    # KV-valid lanes count: the last emitted token was
                    # never written, so the key stops at ``pos``.
                    kv = int(self.pos[row])
                    self.prefix_cache.insert(self._seq_for(req)[:kv],
                                             blocks, self.allocator)
                self._free_blocks(blocks)
                self.block_table[row, :] = 0
                self.pos[row] = 0
                self.finished_count += 1
                if self.obs:
                    at = self.obs.ready
                    self.obs.finished(req.rid, at, at - req.submitted,
                                      len(req.out_tokens))
        return done

"""Observability layer tests: metrics registry, request tracing, the
stats()-gauge schema, and trace completeness under the engines.

The load-bearing contracts:

  * histograms use fixed log-spaced bounds, so merging snapshots is an
    exact element-wise add — never a re-binning approximation;
  * ``render()`` emits well-formed Prometheus text exposition;
  * every admitted request's trace span closes exactly once with
    monotone timestamps — through chunked prefill, prefix-cache hits
    (including copy-on-write), and preemption-resume;
  * both engines' ``stats()`` dicts carry exactly the keys
    ``serving/stats_schema.py`` declares (the schema IS the test);
  * attaching instrumentation never changes an output token;
  * the paged step's phases (``step.schedule`` ... ``step.account``)
    are timed into every step event and ``engine_phase_seconds_total``,
    and with no obs attached the step opens no span and hooks no gc.
"""
import gc
import json

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from conftest import reduced_cfg
from repro.models.api import Model
from repro.obs import (DEFAULT_BUCKETS, EngineObs, Histogram,
                       MetricsRegistry, Observability, TraceRecorder,
                       summarize_latencies, validate_chrome_trace)
from repro.obs.engine import GC_SPANS, PHASES
from repro.obs.trace import span_report
from repro.serving.server import LLMEngine, PagedLLMEngine
from repro.serving.stats_schema import validate


@pytest.fixture(scope="module")
def qwen_model(rng_key):
    cfg = reduced_cfg("qwen3-0.6b")
    model = Model(cfg)
    return model, model.init(rng_key)


def _drain(engine, now_step=0.0, max_steps=2000):
    outs, now = {}, 0.0
    for _ in range(max_steps):
        for r in engine.step(now=now):
            outs[r.rid] = list(r.out_tokens)
        now += now_step
        if engine.idle:
            break
    assert engine.idle
    return outs


# ------------------------------------------------------------- metrics


def test_counter_and_gauge_basics():
    m = MetricsRegistry()
    c = m.counter("c_total", "a counter")
    c.inc()
    c.inc(4)
    assert c.value == 5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = m.gauge("g", "a gauge")
    g.set(7)
    g.inc(2)
    g.dec()
    assert g.value == 8
    # get-or-create returns the same instrument
    assert m.counter("c_total") is c
    # one name, one type
    with pytest.raises(ValueError):
        m.gauge("c_total")


def test_histogram_observe_mean_quantile():
    h = Histogram()
    for v in (0.001, 0.002, 0.004, 0.01, 0.1):
        h.observe(v)
    assert h.count == 5
    assert h.mean == pytest.approx(0.0234, rel=1e-6)
    # quantiles land within one bucket (~1.33x) of the true value
    assert 0.0025 <= h.quantile(0.5) <= 0.006
    assert 0.05 <= h.quantile(0.99) <= 0.14
    assert h.quantile(0.0) >= 0.0
    # overflow clamps to the top bound
    h.observe(1e6)
    assert h.quantile(1.0) == DEFAULT_BUCKETS[-1]


def test_histogram_merge_is_exact():
    a, b = Histogram(), Histogram()
    rng = np.random.default_rng(0)
    va = rng.lognormal(-3, 1, 200)
    vb = rng.lognormal(-2, 1, 300)
    for v in va:
        a.observe(v)
    for v in vb:
        b.observe(v)
    ref = Histogram()
    for v in list(va) + list(vb):
        ref.observe(v)
    a.merge(b)
    assert a.counts == ref.counts          # element-wise exact, no re-bin
    assert a.count == ref.count
    assert a.sum == pytest.approx(ref.sum)
    with pytest.raises(ValueError):
        a.merge(Histogram(bounds=(1.0, 2.0)))


def test_render_prometheus_text():
    m = MetricsRegistry()
    m.counter("req_total", "requests", {"engine": "paged"}).inc(3)
    m.gauge("depth", "queue depth").set(2)
    m.histogram("lat_seconds", "latency", bounds=(0.1, 1.0)).observe(0.5)
    text = m.render()
    assert "# TYPE req_total counter" in text
    assert 'req_total{engine="paged"} 3' in text
    assert "# HELP depth queue depth" in text
    assert "depth 2" in text
    # histogram: cumulative le buckets + +Inf + sum/count
    assert 'lat_seconds_bucket{le="0.1"} 0' in text
    assert 'lat_seconds_bucket{le="1"} 1' in text
    assert 'lat_seconds_bucket{le="+Inf"} 1' in text
    assert "lat_seconds_sum 0.5" in text
    assert "lat_seconds_count 1" in text


def test_snapshot_merge_roundtrip():
    a = MetricsRegistry()
    a.counter("n_total").inc(2)
    a.gauge("g").set(5)
    a.histogram("h_seconds").observe(0.02)
    b = MetricsRegistry()
    b.counter("n_total").inc(3)
    b.histogram("h_seconds").observe(0.04)
    b.merge(a.snapshot())
    assert b.counter("n_total").value == 5          # counters add
    assert b.gauge("g").value == 5                  # gauges overwrite
    h = b.histogram("h_seconds")
    assert h.count == 2 and h.sum == pytest.approx(0.06)
    # snapshots survive a JSON round-trip (the BENCH/report path)
    c = MetricsRegistry()
    c.merge(json.loads(json.dumps(b.snapshot())))
    assert c.snapshot() == b.snapshot()


def test_summarize_latencies_reads_shared_histograms():
    m = MetricsRegistry()
    for v in (0.01, 0.02, 0.03):
        m.histogram("request_ttft_seconds").observe(v)
        m.histogram("request_e2e_seconds").observe(v * 10)
        m.histogram("request_intertoken_seconds").observe(v / 10)
    s = summarize_latencies(m)
    assert s["requests"] == 3
    assert s["mean_ttft_s"] == pytest.approx(0.02, rel=1e-4)
    assert s["mean_e2e_s"] == pytest.approx(0.2, rel=1e-4)
    assert s["p95_ttft_s"] >= s["mean_ttft_s"] * 0.7
    assert s["decode_gap_p95_over_median"] >= 1.0


# --------------------------------------------------------------- trace


def test_trace_recorder_chrome_shape_and_sim_determinism():
    def record(tr):
        tr.open_span(1, 0.0, prompt_len=4)
        tr.request(1, "queued", 0.0)
        tr.request(1, "admitted", 0.1)
        tr.request(1, "prefill_chunk", 0.1, start=0, take=4)
        tr.request(1, "first_token", 0.2)
        tr.step(0.2, 0.0123, admitted=1, tokens=1)
        tr.counter(0.2, "occ", queue_depth=0)
        tr.close_span(1, 0.3, "finished", tokens=2)
        return tr

    sim_a = record(TraceRecorder(mode="sim")).to_chrome()
    sim_b = record(TraceRecorder(mode="sim")).to_chrome()
    # sim mode: byte-stable export (wall durations zeroed)
    assert json.dumps(sim_a) == json.dumps(sim_b)
    assert validate_chrome_trace(sim_a, [1]) == []
    # ts in microseconds
    evs = [e for e in sim_a["traceEvents"] if e["ph"] == "E"]
    assert evs[0]["ts"] == pytest.approx(0.3 * 1e6)
    # wall mode keeps the measured step duration
    wall = record(TraceRecorder(mode="wall")).to_chrome()
    x = [e for e in wall["traceEvents"] if e["ph"] == "X"][0]
    assert x["dur"] == pytest.approx(0.0123 * 1e6, rel=1e-3)
    assert x["args"]["wall_ms"] == pytest.approx(12.3)
    with pytest.raises(ValueError):
        TraceRecorder(mode="cpu")


def test_validate_chrome_trace_catches_incomplete_spans():
    tr = TraceRecorder(mode="sim")
    tr.open_span(1, 0.0)
    tr.request(1, "prefill_chunk", 0.1)
    # no first_token, never closed
    problems = validate_chrome_trace(tr.to_chrome(), [1])
    assert any("closes=0" in p for p in problems)
    assert any("first_token" in p for p in problems)
    assert validate_chrome_trace({}, []) == ["missing traceEvents list"]


@settings(max_examples=20, deadline=None)
@given(st.lists(st.integers(0, 2), min_size=1, max_size=6),
       st.integers(1, 4))
def test_span_closure_property(preempt_counts, tokens_per_req):
    """Property: through any mix of preempt/resume cycles per request,
    every span closes exactly once, per-request timestamps are monotone,
    and every finished request carries prefill + first_token events."""
    obs = Observability.create(trace=True, trace_mode="sim")
    eo = EngineObs(obs, "paged")
    ts = 0.0

    def tick():
        nonlocal ts
        ts += 0.125
        return ts

    for rid, n_preempts in enumerate(preempt_counts, start=1):
        eo.request_queued(rid, tick(), prompt_len=8, max_new=tokens_per_req)
        eo.admitted(rid, tick(), resume=False, cached_blocks=0, cow=False)
        eo.prefill_chunk(rid, tick(), 0, 8)
        for _ in range(n_preempts):
            eo.preempted(rid, tick(), "prefill")
            eo.admitted(rid, tick(), resume=True, cached_blocks=0,
                        cow=False)
            eo.prefill_chunk(rid, tick(), 0, 8)
        eo.first_token(rid, tick(), 0.1)
        for _ in range(tokens_per_req - 1):
            eo.token(rid, tick(), 0.05)
        eo.finished(rid, tick(), ts, tokens_per_req)

    trace = obs.trace.to_chrome()
    rids = list(range(1, len(preempt_counts) + 1))
    assert validate_chrome_trace(trace, rids) == []
    rep = span_report(trace)
    last_ts = {}
    for ev in trace["traceEvents"]:
        if ev["ph"] == "M" or ev["pid"] != 1:
            continue
        assert ev["ts"] >= last_ts.get(ev["tid"], -1.0)
        last_ts[ev["tid"]] = ev["ts"]
    for rid, n_preempts in zip(rids, preempt_counts):
        rec = rep[rid]
        assert rec["opens"] == 1 and rec["closes"] == 1
        assert rec["outcome"] == "finished"
        assert rec["phases"].count("preempted") == n_preempts
        assert rec["phases"].count("evicted_resume") == n_preempts


# -------------------------------------------------------- stats schema


def test_stats_schema_rejects_drift():
    good = {"engine": "slot", "queue_depth": 0, "active": 0,
            "free_blocks": 2, "used_blocks": 0, "total_blocks": 2,
            "pool_occupancy": 0.0, "preemptions": 0, "admissions": 0,
            "finished": 0, "prefill_compiles": 0, "decode_compiles": 0}
    assert validate(dict(good)) == good
    with pytest.raises(ValueError, match="engine"):
        validate({**good, "engine": "gpu"})
    with pytest.raises(ValueError, match="missing"):
        validate({k: v for k, v in good.items() if k != "active"})
    with pytest.raises(ValueError, match="undeclared"):
        validate({**good, "bogus_gauge": 1})
    with pytest.raises(ValueError, match="undeclared"):
        validate({**good, "hit_rate": 0.5})      # paged-only key on slot
    with pytest.raises(ValueError, match="type mismatch"):
        validate({**good, "active": "two"})


def test_both_engines_stats_match_schema(qwen_model):
    """Satellite contract: the schema module and the engines cannot
    drift — validate() must accept both engines' live stats() at every
    lifecycle point (fresh, mid-flight, drained)."""
    model, params = qwen_model
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, model.cfg.vocab_size, 8).astype(np.int32)
               for _ in range(3)]

    paged = PagedLLMEngine(model, params, num_blocks=32, block_size=8,
                           max_batch=4, max_len=64, prefix_cache=True)
    slot = LLMEngine(model, params, num_slots=2, cache_max=32)
    for eng in (paged, slot):
        validate(eng.stats())
        for p in prompts:
            eng.submit(p, max_new=3)
        eng.step()
        validate(eng.stats())
        _drain(eng)
        validate(eng.stats())
        assert eng.stats()["finished"] == len(prompts)
        assert eng.stats()["admissions"] >= len(prompts)


# ------------------------------------------------- engine integration


def test_paged_engine_obs_counters_and_trace(qwen_model):
    """Chunked continuous batching under full instrumentation: counters
    agree with engine ground truth, the trace validates, and per-request
    timestamps are monotone under an advancing clock."""
    model, params = qwen_model
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, model.cfg.vocab_size, n).astype(np.int32)
               for n in (24, 9, 17)]
    obs = Observability.create(trace=True, trace_mode="sim")
    eng = PagedLLMEngine(model, params, num_blocks=64, block_size=8,
                         max_batch=8, max_len=96, prefill_chunk=8,
                         obs=obs)
    for p in prompts:
        eng.submit(p, max_new=4, now=0.0)
    outs = _drain(eng, now_step=0.5)

    m = obs.metrics
    lab = {"engine": "paged"}
    assert m.counter("engine_requests_total", labels=lab).value == 3
    assert m.counter("engine_admissions_total", labels=lab).value == \
        eng.admissions
    assert m.counter("engine_finished_total", labels=lab).value == 3
    assert m.counter("engine_generated_tokens_total", labels=lab).value == \
        sum(len(t) for t in outs.values())
    assert m.counter("engine_prefill_tokens_total", labels=lab).value == \
        eng.prefill_tokens
    assert m.counter("engine_steps_total", labels=lab).value > 0
    assert m.histogram("engine_step_seconds", labels=lab).count == \
        m.counter("engine_steps_total", labels=lab).value
    assert m.histogram("request_ttft_seconds").count == 3
    assert m.histogram("request_e2e_seconds").count == 3
    # 24-token prompt at chunk 8 -> >= 3 prefill_chunk events for rid 1
    trace = eng.obs.trace.to_chrome()
    assert validate_chrome_trace(trace, list(outs)) == []
    rep = span_report(trace)
    assert rep[1]["phases"].count("prefill_chunk") >= 3
    last_ts = {}
    for ev in trace["traceEvents"]:
        if ev["ph"] == "M" or ev["pid"] != 1:
            continue
        assert ev["ts"] >= last_ts.get(ev["tid"], -1.0)
        last_ts[ev["tid"]] = ev["ts"]
    # summarize reads the same histograms the engine wrote
    assert summarize_latencies(m)["requests"] == 3


def test_trace_complete_under_preemption_resume(qwen_model):
    """A preempted-then-resumed request's span still closes exactly once,
    with explicit preempted / evicted_resume instants in between."""
    model, params = qwen_model
    rng = np.random.default_rng(3)
    obs = Observability.create(trace=True, trace_mode="sim")
    eng = PagedLLMEngine(model, params, num_blocks=64, block_size=8,
                         max_batch=4, max_len=64, obs=obs)
    for _ in range(2):
        eng.submit(rng.integers(1, model.cfg.vocab_size, 12)
                   .astype(np.int32), max_new=4)
    for _ in range(10):                         # both admitted + decoding
        eng.step()
        if len(eng.active) == 2 and not eng.prefilling:
            break
    assert len(eng.active) == 2
    eng._preempt_youngest()                     # deterministic eviction
    outs = _drain(eng)
    assert len(outs) == 2
    trace = obs.trace.to_chrome()
    assert validate_chrome_trace(trace, [1, 2]) == []
    rep = span_report(trace)
    assert rep[2]["phases"].count("preempted") == 1
    assert rep[2]["phases"].count("evicted_resume") == 1
    assert rep[1]["phases"].count("preempted") == 0
    assert obs.metrics.counter("engine_preemptions_total",
                               labels={"engine": "paged"}).value == 1


def test_trace_admitted_args_carry_prefix_hits_and_cow(qwen_model):
    """Prefix-cache composition is visible in the trace: a request
    admitted over cached blocks reports cached_blocks > 0, and a
    divergence inside a partially matched block reports cow=True."""
    model, params = qwen_model
    rng = np.random.default_rng(9)
    base = rng.integers(1, model.cfg.vocab_size, 16).astype(np.int32)
    fork = base.copy()
    fork[12] = (fork[12] % (model.cfg.vocab_size - 1)) + 1   # in-block split
    obs = Observability.create(trace=True, trace_mode="sim")
    eng = PagedLLMEngine(model, params, num_blocks=64, block_size=8,
                         max_batch=4, max_len=64, prefix_cache=True,
                         obs=obs)
    eng.submit(base, max_new=2)
    _drain(eng)
    eng.submit(fork, max_new=2)
    outs = _drain(eng)
    assert 2 in outs
    admitted = [e for e in obs.trace.to_chrome()["traceEvents"]
                if e["name"] == "admitted" and e["tid"] == 2]
    assert len(admitted) == 1
    assert admitted[0]["args"]["cached_blocks"] >= 1
    assert admitted[0]["args"]["cow"] is True
    assert validate_chrome_trace(obs.trace.to_chrome(), [1, 2]) == []
    assert eng.cow_copies == 1


def test_slot_engine_obs_and_instrumentation_off_identity(qwen_model):
    """The slot engine emits the same metric/trace contract, and
    attaching instrumentation never changes an output token on either
    engine."""
    model, params = qwen_model
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, model.cfg.vocab_size, 8).astype(np.int32)
               for _ in range(3)]

    def run(make):
        eng = make()
        for p in prompts:
            eng.submit(p, max_new=3)
        return _drain(eng)

    obs = Observability.create(trace=True, trace_mode="sim")
    bare = run(lambda: LLMEngine(model, params, num_slots=2, cache_max=32))
    inst = run(lambda: LLMEngine(model, params, num_slots=2, cache_max=32,
                                 obs=obs))
    assert inst == bare
    lab = {"engine": "slot"}
    assert obs.metrics.counter("engine_finished_total",
                               labels=lab).value == 3
    assert validate_chrome_trace(obs.trace.to_chrome(), [1, 2, 3]) == []

    p_obs = Observability.create()
    p_bare = run(lambda: PagedLLMEngine(model, params, num_blocks=32,
                                        block_size=8, max_batch=4,
                                        max_len=32))
    p_inst = run(lambda: PagedLLMEngine(model, params, num_blocks=32,
                                        block_size=8, max_batch=4,
                                        max_len=32, obs=p_obs))
    assert p_inst == p_bare


def test_two_engines_share_one_registry(qwen_model):
    """Engine labels keep two engines' instruments disjoint inside one
    registry — the multi-replica aggregation story."""
    model, params = qwen_model
    obs = Observability.create()
    a = PagedLLMEngine(model, params, num_blocks=32, block_size=8,
                       max_batch=4, max_len=32, obs=obs)
    b = LLMEngine(model, params, num_slots=2, cache_max=32, obs=obs)
    rng = np.random.default_rng(2)
    for eng in (a, b):
        eng.submit(rng.integers(1, model.cfg.vocab_size, 8)
                   .astype(np.int32), max_new=2)
        _drain(eng)
    assert obs.metrics.counter("engine_finished_total",
                               labels={"engine": "paged"}).value == 1
    assert obs.metrics.counter("engine_finished_total",
                               labels={"engine": "slot"}).value == 1
    # the unlabeled request histograms pool across engines
    assert obs.metrics.histogram("request_e2e_seconds").count == 2


# ---------------------------------------------------------- step phases


def _phase_engine(model, params, obs, fused=True):
    eng = PagedLLMEngine(model, params, num_blocks=64, block_size=8,
                         max_batch=4, max_len=64, prefill_chunk=8,
                         decode_fusion=fused, obs=obs)
    rng = np.random.default_rng(4)
    for n in (20, 7, 13):
        eng.submit(rng.integers(1, model.cfg.vocab_size, n)
                   .astype(np.int32), max_new=4)
    return eng


def _step_events(obs):
    return [e for e in obs.trace.to_chrome()["traceEvents"]
            if e["ph"] == "X" and e["name"] == "step"]


@pytest.mark.parametrize("mode,fused", [("wall", True), ("wall", False),
                                        ("sim", True)])
def test_step_events_carry_phase_ms(qwen_model, mode, fused):
    """Every paged step event carries the five phases' wall times; in
    wall mode they fit inside the step's own wall time and add up to
    ``engine_phase_seconds_total``; in sim mode they are 0."""
    model, params = qwen_model
    obs = Observability.create(trace=True, trace_mode=mode)
    _drain(_phase_engine(model, params, obs, fused), now_step=1.0)
    steps = _step_events(obs)
    assert steps
    for ev in steps:
        ph = ev["args"]["phase_ms"]
        assert tuple(ph) == PHASES
        if mode == "sim":
            assert set(ph.values()) == {0.0}
        else:
            assert sum(ph.values()) <= ev["args"]["wall_ms"] + 0.01
    lab = {"engine": "paged"}
    totals = {p: obs.metrics.counter("engine_phase_seconds_total",
                                     labels={**lab, "phase": p}).value
              for p in PHASES}
    assert all(totals[p] > 0 for p in PHASES)
    assert 'engine_phase_seconds_total{engine="paged",phase="wait"}' \
        in obs.metrics.render()
    if mode == "wall":
        for p in PHASES:
            assert totals[p] == pytest.approx(
                sum(e["args"]["phase_ms"][p] for e in steps) / 1e3,
                abs=1e-6 * len(steps))


def test_sim_trace_stays_byte_stable_with_phases(qwen_model):
    model, params = qwen_model

    def export():
        obs = Observability.create(trace=True, trace_mode="sim")
        _drain(_phase_engine(model, params, obs), now_step=0.5)
        return json.dumps(obs.trace.to_chrome())

    assert export() == export()


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``; records names."""
    made: list = []

    def __init__(self, name, **kw):
        self.made.append((name, kw))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def test_no_obs_opens_no_span_and_hooks_no_gc(qwen_model, monkeypatch):
    import jax

    model, params = qwen_model
    made = []
    monkeypatch.setattr(_Annotations, "made", made)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Annotations)
    before = list(gc.callbacks)
    eng = _phase_engine(model, params, None)
    _drain(eng)
    assert not [n for n, _ in made if n.startswith("step.")]
    assert gc.callbacks == before
    # attached: the same patch point sees every phase, tagged with the
    # step number, and gc collections become spans
    eng.attach_obs(Observability.create())
    assert GC_SPANS in gc.callbacks
    rng = np.random.default_rng(5)
    eng.submit(rng.integers(1, model.cfg.vocab_size, 9).astype(np.int32),
               max_new=3)
    _drain(eng)
    names = {n for n, _ in made}
    assert {f"step.{p}" for p in PHASES} <= names
    assert [kw["step"] for n, kw in made if n == "step.wait"] == \
        list(range(eng.obs.steps))
    gc.collect()
    assert ("gc", {}) in made
    eng.attach_obs(None)
    assert gc.callbacks == before


def test_wall_ttft_counts_the_wait(qwen_model):
    """On a wall clock the TTFT observation runs to when the ids reached
    the host, so it covers the step's wait; ``first_token_at`` stays the
    step's start."""
    model, params = qwen_model
    obs = Observability.create(trace=True)
    eng = PagedLLMEngine(model, params, num_blocks=32, block_size=8,
                         max_batch=4, max_len=32, obs=obs)
    eng.submit(np.arange(1, 10, dtype=np.int32), max_new=2, now=0.0)
    k, req = 0, eng.queue[0]
    while req.first_token_at is None:
        k += 1
        eng.step(now=float(k))
    assert req.first_token_at == float(k)
    wait_ms = _step_events(obs)[-1]["args"]["phase_ms"]["wait"]
    ttft = obs.metrics.histogram("request_ttft_seconds")
    assert ttft.count == 1
    assert ttft.sum - req.first_token_at >= wait_ms / 1e3 > 0
    first = [e for e in obs.trace.to_chrome()["traceEvents"]
             if e["name"] == "first_token"]
    assert first[0]["ts"] == pytest.approx(ttft.sum * 1e6, abs=1e-3)


# ------------------------------------------- app tier + CLI rendering


def test_balancer_lifetime_counters_and_metrics():
    from repro.serving.balancer import LoadBalancer, Overloaded

    m = MetricsRegistry()
    lb = LoadBalancer(num_replicas=2, concurrency=1, queue_limit=0,
                      policy="least_loaded", metrics=m)
    r1, r2 = lb.pick(), lb.pick()
    with pytest.raises(Overloaded):
        lb.pick()
    lb.release(r1)
    s = lb.stats()
    assert s["picks"] == 2 and s["rejections"] == 1 and s["releases"] == 1
    # legacy aliases stay
    assert s["dispatched"] == 2 and s["rejected"] == 1
    lab = {"policy": "least_loaded"}
    assert m.counter("balancer_picks_total", labels=lab).value == 2
    assert m.counter("balancer_rejections_total", labels=lab).value == 1
    assert m.counter("balancer_releases_total", labels=lab).value == 1
    assert m.gauge("balancer_replica_in_flight",
                   labels={"replica": str(r2.rid)}).value == 1


def test_fmt_stats_renders_balancer_snapshot():
    from repro.launch.serve import _fmt_stats
    from repro.serving.balancer import LoadBalancer

    lb = LoadBalancer(num_replicas=2, concurrency=1, queue_limit=0)
    lb.pick()
    lb.attach_engine_stats(lambda: {"engine": "paged", "queue_depth": 1,
                                    "finished": 0})
    out = _fmt_stats(lb.stats())
    assert "picks=1" in out and "rejections=0" in out
    assert "releases=0" in out
    assert "[paged]" in out                   # nested engine line rendered
    # engine dicts still render directly
    assert "[slot]" in _fmt_stats({"engine": "slot"})


def test_broker_and_resource_metrics():
    from repro.serving.broker import Broker, PartitionFull
    from repro.serving.sim import Clock, QueuedResource

    m = MetricsRegistry()
    b = Broker(num_partitions=2, max_depth=2, seed=0, metrics=m)
    for _ in range(2):
        b.produce({"x": 1}, key="k")
    with pytest.raises(PartitionFull):
        b.produce({"x": 1}, key="k")
    b.poll("g", b.partition_for("k"))
    assert m.counter("broker_produced_total").value == 2
    assert m.counter("broker_rejected_total").value == 1
    assert m.counter("broker_polls_total").value == 1
    assert m.gauge("broker_partition_depth",
                   labels={"partition": str(b.partition_for("k"))}).value == 2

    clock = Clock()
    res = QueuedResource(clock, concurrency=1, queue_limit=4, metrics=m,
                         name="nginx-0")
    for _ in range(3):
        assert res.submit(1.0, lambda: None)
    clock.run()
    lab = {"resource": "nginx-0"}
    assert m.counter("resource_served_total", labels=lab).value == 3
    h = m.histogram("resource_wait_seconds", labels=lab)
    assert h.count == 3
    # two requests queued behind a 1-wide pool: waits of ~1s and ~2s
    assert h.sum == pytest.approx(3.0, rel=0.01)


def test_loadgen_report_reads_histogram():
    from repro.serving.loadgen import LoadGenerator
    from repro.serving.server import Outcome
    from repro.serving.sim import Clock

    m = MetricsRegistry()
    clock = Clock()

    def issue(done):
        clock.schedule(0.2, lambda: done(Outcome(True, 200, 0.2, "GET")))

    gen = LoadGenerator(clock, issue, users=4, spawn_rate=10.0,
                        duration=5.0, think_min=0.1, think_max=0.1,
                        kind="GET", metrics=m)
    rep = gen.run()
    assert rep.total > 0
    assert rep.mean_ms == pytest.approx(200.0, rel=1e-6)   # mean is exact
    assert 150.0 <= rep.median_ms <= 240.0   # quantile within its bucket
    lab = {"kind": "GET"}
    assert m.histogram("http_request_seconds", labels=lab).count == \
        rep.total
    assert m.counter("http_failures_total", labels=lab).value == 0

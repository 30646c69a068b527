"""Cluster serving tier tests (``serving/cluster.py``).

The contract: N broker-fed replicas behind the occupancy-aware balancer
are a pure routing layer — every request's greedy tokens are identical
to a single engine (and to the slot baseline), whatever replica served
it.  On top of identity: prefix-affinity actually routes a tenant's
requests to one replica and measurably raises per-replica radix hit
rates over policy-only routing; saturation rejects with 429 semantics
without corrupting broker offsets or stranding accepted requests;
replays are deterministic; ``stats()`` follows the ``cluster`` schema
kind; and the per-replica metrics registries merge exactly.
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from conftest import reduced_cfg
from repro.models.api import Model
from repro.obs import summarize_latencies
from repro.serving.balancer import LoadBalancer
from repro.serving.broker import Broker, PartitionFull
from repro.serving.cluster import Rejected, ServingCluster
from repro.serving.loadgen import multi_tenant_workload
from repro.serving.prefix_cache import chain_hashes
from repro.serving.server import LLMEngine, PagedLLMEngine
from repro.serving.stats_schema import validate


@pytest.fixture(scope="module")
def qwen_model(rng_key):
    cfg = reduced_cfg("qwen3-0.6b")
    model = Model(cfg)
    return model, model.init(rng_key)


@pytest.fixture(scope="module")
def workload(qwen_model):
    model, _ = qwen_model
    return multi_tenant_workload(num_tenants=3, num_bursts=2, burst_size=4,
                                 prefix_len=16,
                                 vocab_size=model.cfg.vocab_size,
                                 max_suffix=12, max_new=5, seed=2)


def _make(model, params, **kw):
    kw.setdefault("num_blocks", 48)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_len", 96)
    kw.setdefault("prefix_cache", True)
    return lambda i: PagedLLMEngine(model, params, **kw)


def _run(cluster, wl):
    """Submit the whole workload, drain, return outputs in submission
    order (None for rejected submissions)."""
    cids = []
    for i, (p, n) in enumerate(zip(wl.prompts, wl.max_news)):
        try:
            cids.append(cluster.submit(p, max_new=n, now=float(i)))
        except Rejected:
            cids.append(None)
    outs = {r.cid: r.out_tokens for r in cluster.drain(now=100.0)}
    return [outs.get(c) for c in cids]


# ------------------------------------------------------- token identity


def test_cluster_token_identity_one_vs_many_vs_slot(qwen_model, workload):
    """1-replica cluster, 3-replica cluster, and the slot baseline all
    emit exactly the tokens a bare paged engine emits — the broker,
    balancer, and affinity map route requests, never touch them."""
    model, params = qwen_model
    wl = workload

    ref = _make(model, params)(0)
    for p, n in zip(wl.prompts, wl.max_news):
        ref.submit(p, max_new=n)
    ref_outs = {}
    while not ref.idle:
        for r in ref.step():
            ref_outs[r.rid] = r.out_tokens
    ref_list = [ref_outs[i + 1] for i in range(len(wl.prompts))]

    slot = LLMEngine(model, params, num_slots=4, cache_max=96)
    for p, n in zip(wl.prompts, wl.max_news):
        slot.submit(p, max_new=n)
    slot_outs = {}
    while not slot.idle:
        for r in slot.step():
            slot_outs[r.rid] = r.out_tokens
    assert [slot_outs[i + 1] for i in range(len(wl.prompts))] == ref_list

    one = _run(ServingCluster(_make(model, params), 1, seed=0), wl)
    assert one == ref_list
    many = _run(ServingCluster(_make(model, params), 3, seed=0), wl)
    assert many == ref_list


# ----------------------------------------------------- affinity routing


def test_affinity_keeps_tenants_on_one_replica(qwen_model, workload):
    """With affinity on, every request of a tenant lands on the replica
    that served the tenant first (the chain-hash map), and the mean
    per-replica radix hit rate beats policy-only routing on the same
    workload — the headline cluster win."""
    model, params = qwen_model
    wl = workload

    on = ServingCluster(_make(model, params), 2, affinity=True, seed=0)
    assert _run(on, wl).count(None) == 0
    by_tenant = {}
    for (cid, rid, _), t in zip(on.route_log, wl.tenant_ids):
        by_tenant.setdefault(t, set()).add(rid)
    assert all(len(rids) == 1 for rids in by_tenant.values())
    # only a tenant's FIRST request can miss the affinity map
    s = validate(on.stats())
    assert s["affinity_misses"] <= wl.num_tenants
    assert s["affinity_hits"] == len(wl.prompts) - s["affinity_misses"]

    off = ServingCluster(_make(model, params), 2, affinity=False, seed=0)
    assert _run(off, wl).count(None) == 0
    assert off.stats()["affinity_hits"] == 0
    hit_on = np.mean([e.stats()["hit_rate"] for e in on.engines])
    hit_off = np.mean([e.stats()["hit_rate"] for e in off.engines])
    assert hit_on > hit_off

    # the routing layer agrees with the engines' own radix keys: the
    # affinity map is keyed by the same per-block tuples
    prompt = wl.prompts[0]
    assert len(chain_hashes(prompt[:-1], on.block_size)) == \
        (len(prompt) - 1) // on.block_size


def test_deterministic_replay(qwen_model, workload):
    """Two identical clusters fed the same submissions make identical
    routing decisions and emit identical tokens — the in-process driver
    loop has no hidden nondeterminism."""
    model, params = qwen_model
    a = ServingCluster(_make(model, params), 2, affinity=True, seed=3)
    b = ServingCluster(_make(model, params), 2, affinity=True, seed=3)
    outs_a, outs_b = _run(a, workload), _run(b, workload)
    assert a.route_log == b.route_log
    assert outs_a == outs_b


# ------------------------------------------------------- backpressure


def test_429_overload_keeps_accepted_requests_whole(qwen_model, workload):
    """Saturating the balancer rejects with 429 but never half-accepts:
    rejected submissions leave no broker record, every accepted ticket
    still finishes, and committed offsets end exactly at produced."""
    model, params = qwen_model
    wl = workload
    cl = ServingCluster(_make(model, params, max_batch=2), 2,
                        affinity=False, queue_limit=0, seed=0)
    outs = _run(cl, wl)
    accepted = sum(1 for o in outs if o is not None)
    rejected = outs.count(None)
    assert rejected > 0 and accepted == 4      # 2 replicas x max_batch 2
    s = validate(cl.stats())
    assert s["rejected_429"] == rejected
    assert s["submitted"] == accepted
    assert s["finished"] == accepted
    assert cl.broker.produced == accepted
    for p in range(2):
        assert cl.broker.depth(p, cl.GROUP) == 0   # all consumed+committed
    assert all(o is not None and len(o) > 0 for o in outs
               if o is not None)


def test_429_partition_full_cancels_balancer_hold(qwen_model, workload):
    """The broker-side 429 (partition full AFTER the balancer said yes)
    must roll the balancer's in-flight hold back, or the replica leaks
    phantom load and the next pick skews."""
    model, params = qwen_model
    cl = ServingCluster(_make(model, params), 2, affinity=False,
                        queue_limit=64, broker_depth=2, seed=0)
    outs = _run(cl, workload)
    rejected = outs.count(None)
    assert rejected > 0
    assert cl.balancer.cancelled == rejected
    assert all(r.in_flight == 0 for r in cl.balancer.replicas)
    assert cl.stats()["finished"] == len(outs) - rejected


# --------------------------------------------------------- stats schema


def test_cluster_stats_schema_two_way(qwen_model):
    """``validate`` accepts the live cluster dict and rejects drift in
    both directions for the ``cluster`` kind."""
    model, params = qwen_model
    cl = ServingCluster(_make(model, params), 2, seed=0)
    s = validate(cl.stats())
    assert s["engine"] == "cluster" and s["replicas"] == 2
    with pytest.raises(ValueError, match="undeclared"):
        validate({**s, "mystery": 1})
    missing = dict(s)
    del missing["affinity_hits"]
    with pytest.raises(ValueError, match="missing"):
        validate(missing)
    # engine-only keys are drift when they show up on a cluster dict
    with pytest.raises(ValueError, match="undeclared"):
        validate({**s, "hit_rate": 0.5})


# ------------------------------------------------- balancer scoring hook


def test_balancer_occupancy_aware_scoring_and_cancel():
    """Per-replica gauge sources turn least-loaded/p2c scoring
    occupancy-aware: queue depth adds to load, free blocks break ties;
    ``prefer`` overrides policy unless the replica is full; ``cancel``
    releases a hold without counting work served."""
    lb = LoadBalancer(2, concurrency=4, queue_limit=2,
                      policy="least_loaded", seed=0)
    lb.attach_engine_stats(lambda: {"queue_depth": 5, "free_blocks": 30},
                           rid=0)
    lb.attach_engine_stats(lambda: {"queue_depth": 0, "free_blocks": 10},
                           rid=1)
    assert lb.pick().rid == 1            # 0+0 queue beats 0+5 queue
    assert lb._score(lb.replicas[0]) == (5, -30)
    r0 = lb.pick(prefer=0)
    assert r0.rid == 0 and lb.affinity_picks == 1
    lb.cancel(r0)
    assert lb.replicas[0].in_flight == 0 and lb.replicas[0].served == 0
    st_ = lb.stats()
    assert st_["cancelled"] == 1
    assert set(st_["engines"]) == {0, 1}
    assert st_["engines"][0]["queue_depth"] == 5

    # prefer is a hint, not a bypass: a full preferred replica falls
    # back to the policy instead of over-admitting
    for _ in range(6):
        lb.pick(prefer=1)
    assert lb.replicas[1].full
    assert lb.pick(prefer=1).rid == 0


# -------------------------------------------------------- merged metrics


def test_merged_metrics_exact(qwen_model, workload):
    """The fleet registry is an exact fold of the per-replica
    snapshots: replica-labeled engine counters survive with their
    values, and the unlabeled request histograms sum into fleet-wide
    distributions covering every finished request."""
    model, params = qwen_model
    cl = ServingCluster(_make(model, params), 2, seed=0, obs=True)
    outs = _run(cl, workload)
    merged = cl.merged_metrics()
    per = [o.metrics.get("engine_finished_total",
                         {"engine": "paged", "replica": str(i)}).value
           for i, o in enumerate(cl.replica_obs)]
    assert sum(per) == len(outs)
    for i, v in enumerate(per):
        assert merged.get("engine_finished_total",
                          {"engine": "paged",
                           "replica": str(i)}).value == v
    lat = summarize_latencies(merged)
    assert lat["requests"] == len(outs)
    assert f'replica="1"' in merged.render()


def test_cluster_latencies_count_whole_steps(qwen_model, workload):
    """The cluster's metrics-only bundles take ``step(now)`` as a
    logical clock: driven on whole steps, every TTFT, gap and e2e
    observation is a whole number of steps (no wall seconds added)."""
    model, params = qwen_model
    cl = ServingCluster(_make(model, params), 2, seed=0, obs=True)
    for p, n in zip(workload.prompts[:6], workload.max_news[:6]):
        cl.submit(p, max_new=n, now=0.0)
    k = 0
    while not cl.idle:
        k += 1
        cl.step(now=float(k))
    merged = cl.merged_metrics()
    for name in ("request_ttft_seconds", "request_intertoken_seconds",
                 "request_e2e_seconds"):
        h = merged.histogram(name)
        assert h.count > 0
        assert h.sum == int(h.sum), name
    assert merged.histogram("request_ttft_seconds").sum >= 6


# ------------------------------------------ broker routing property test


@settings(max_examples=50, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 3), st.integers(0, 2)),
                min_size=1, max_size=60),
       st.integers(2, 4))
def test_broker_pinned_partition_property(ops_seq, partitions):
    """Property over produce(partition=)/poll/commit/reject: explicit
    pinning never re-shuffles (the record lands where the router said),
    offsets stay dense and strictly increasing per partition, committed
    never exceeds produced, and a full partition rejects without
    consuming an offset."""
    b = Broker(num_partitions=partitions, max_depth=4, seed=1)
    produced = {p: 0 for p in range(partitions)}
    committed = {p: 0 for p in range(partitions)}
    for op, arg in ops_seq:
        p = arg % partitions
        if op == 0:                       # pinned produce (the cluster path)
            try:
                got_p, off = b.produce("v", partition=p)
                assert got_p == p and off == produced[p]
                produced[p] += 1
            except PartitionFull:
                assert b.depth(p) == b.max_depth
        elif op == 1:                     # poll re-delivers uncommitted
            recs = b.poll("g", p, 8)
            offs = [r.offset for r in recs]
            assert offs == list(range(committed[p],
                                      committed[p] + len(offs)))
        elif op == 2:                     # commit everything polled so far
            recs = b.poll("g", p, 8)
            if recs:
                b.commit("g", p, recs[-1].offset + 1)
                committed[p] = recs[-1].offset + 1
        else:                             # out-of-range pin is an error
            with pytest.raises(ValueError):
                b.produce("v", partition=partitions)
    for p in range(partitions):
        assert committed[p] <= produced[p]
        assert b.depth(p, "g") == produced[p] - committed[p]

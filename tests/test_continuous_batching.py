"""Continuous-batching scheduler tests: chunked prefill correctness.

The contract under test: the continuous scheduler — multi-admission,
ragged chunked prefill under a per-step token budget, decode every step
— must be a pure scheduling change.  Greedy decode makes that checkable
bit-for-bit: every chunk size (block-aligned, unaligned, larger than
any prompt), every budget, preemption mid-prefill, and prefix-cache
composition must emit exactly the tokens the serial whole-prompt
scheduler emits.  On top of identity: admission batching actually
happens in one step, the budget actually bounds per-step prefill while
decode keeps advancing, and the chunk dispatch's retrace gauge agrees
with jax's real jit cache.
"""
import numpy as np
import pytest

from conftest import reduced_cfg
from repro.models.api import Model
from repro.serving.loadgen import mixed_length_workload
from repro.serving.server import PagedLLMEngine


@pytest.fixture(scope="module")
def qwen_model(rng_key):
    cfg = reduced_cfg("qwen3-0.6b")
    model = Model(cfg)
    return model, model.init(rng_key)


def _drain(engine, max_steps=2000):
    outs = {}
    for _ in range(max_steps):
        for r in engine.step():
            outs[r.rid] = list(r.out_tokens)
        if engine.idle:
            break
    assert engine.idle
    return outs


def _drive(model, params, prompts, max_news=None, **kw):
    engine = PagedLLMEngine(model, params, num_blocks=64, block_size=8,
                            max_batch=8, max_len=96, **kw)
    max_news = max_news or [6] * len(prompts)
    for p, n in zip(prompts, max_news):
        engine.submit(p, max_new=n)
    return engine, _drain(engine)


# --------------------------------------------------- chunk-size identity


@pytest.mark.parametrize("chunk_kw", [
    dict(prefill_chunk=8),                           # exactly one block
    dict(prefill_chunk=10, prefill_buckets="off"),   # block-unaligned
    dict(prefill_chunk=512),                         # > every prompt
])
def test_chunked_prefill_token_identity(qwen_model, chunk_kw):
    """Chunk size must never change a token: mid-block cursors, chunks
    that span block boundaries unaligned, and whole-prompt-in-one-chunk
    all reduce to the serial scheduler's outputs."""
    model, params = qwen_model
    wl = mixed_length_workload(num_requests=6,
                               vocab_size=model.cfg.vocab_size,
                               min_len=4, max_len=40, min_new=2, max_new=8,
                               seed=3)
    _, serial = _drive(model, params, wl.prompts, wl.max_news,
                       scheduler="serial",
                       **{k: v for k, v in chunk_kw.items()
                          if k != "prefill_chunk"})
    eng, chunked = _drive(model, params, wl.prompts, wl.max_news,
                          **chunk_kw)
    assert chunked == serial
    assert eng.allocator.num_live == 0


# ------------------------------------------------- multi-admission step


def test_single_step_admits_whole_burst(qwen_model):
    """A burst of short same-length prompts is admitted, prefilled (ONE
    ragged dispatch -> one trace), and first-decoded in a single
    continuous step; the serial scheduler needs a step per request."""
    model, params = qwen_model
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, model.cfg.vocab_size, 8).astype(np.int32)
               for _ in range(4)]

    eng = PagedLLMEngine(model, params, num_blocks=64, block_size=8,
                         max_batch=8, max_len=96)
    for p in prompts:
        eng.submit(p, max_new=4)
    eng.step()
    s = eng.stats()
    assert s["admissions"] == 4
    assert s["prefilling"] == 0              # every prompt fit one chunk
    assert s["active"] == 4                  # all decoding after one step
    assert s["prefill_compiles"] == 1        # one ragged dispatch, one sig

    serial = PagedLLMEngine(model, params, num_blocks=64, block_size=8,
                            max_batch=8, max_len=96, scheduler="serial")
    for p in prompts:
        serial.submit(p, max_new=4)
    serial.step()
    assert serial.stats()["admissions"] == 1


# ------------------------------------------------ preempt mid-prefill


def test_preempt_mid_prefill_resumes_identically(qwen_model):
    """Deterministic mid-prefill eviction: after one budgeted step the
    youngest request is still mid-chunk; preempting it must drop its
    blocks and requeue it, and the drain must still match a roomy
    engine bit-for-bit (resume re-chunks from the start cursor)."""
    model, params = qwen_model
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, model.cfg.vocab_size, 24).astype(np.int32)
               for _ in range(3)]

    roomy, ref_outs = _drive(model, params, prompts, [8] * 3)
    assert roomy.preemptions == 0

    eng = PagedLLMEngine(model, params, num_blocks=64, block_size=8,
                         max_batch=8, max_len=96, prefill_chunk=8,
                         step_token_budget=16)
    for p in prompts:
        eng.submit(p, max_new=8)
    eng.step()                               # budget 16 < 3x24: backlog
    assert eng.stats()["prefilling"] > 0
    live_before = eng.allocator.num_live
    eng._preempt_youngest()                  # must hit the prefilling arm
    assert eng.preemptions == 1
    assert eng.allocator.num_live < live_before
    outs = _drain(eng)
    assert outs == ref_outs
    assert eng.allocator.num_live == 0


def test_tight_pool_chunked_preemption_round_trip(qwen_model):
    """Pool pressure under chunked continuous admission: forced
    preempt-and-requeue (whichever arm it lands on) still finishes with
    the roomy pool's tokens and returns every block."""
    model, params = qwen_model
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, model.cfg.vocab_size, 8).astype(np.int32)
               for _ in range(4)]

    roomy, ref_outs = _drive(model, params, prompts, [12] * 4,
                             prefill_chunk=4, prefill_buckets="off")
    assert roomy.preemptions == 0

    tight = PagedLLMEngine(model, params, num_blocks=10, block_size=4,
                           max_batch=8, max_len=64, prefill_chunk=4,
                           prefill_buckets="off")
    for p in prompts:
        tight.submit(p, max_new=12)
    tight_outs = _drain(tight)
    assert tight.preemptions > 0
    assert tight_outs == ref_outs
    assert tight.allocator.num_live == 0


# ------------------------------------------- prefix-cache composition


def test_chunking_composes_with_prefix_cache(qwen_model):
    """Chunked suffix prefills start mid-sequence (cursor past the
    matched prefix blocks, COW offsets inside a partial block) and must
    still match the serial scheduler with the same cache — while the
    cache keeps actually hitting."""
    model, params = qwen_model
    cfg = model.cfg
    rng = np.random.default_rng(7)
    prefix = rng.integers(1, cfg.vocab_size, 24).astype(np.int32)
    prompts = [np.concatenate([prefix,
                               rng.integers(1, cfg.vocab_size, 3 + i)
                               .astype(np.int32)])
               for i in range(5)]

    _, serial = _drive(model, params, prompts, scheduler="serial",
                       prefix_cache=True)
    eng, chunked = _drive(model, params, prompts, prefix_cache=True,
                          prefill_chunk=8)
    assert chunked == serial
    assert eng.stats()["hit_rate"] > 0       # sharing survived chunking


# ------------------------------------------------- budget + retraces


def test_step_token_budget_bounds_prefill_and_decode_advances(qwen_model):
    """With a long prompt backlogged behind a decoding request, every
    continuous step prefills at most ``step_token_budget`` tokens AND
    the decoding request still gains one token per step — the flat
    decode latency the scheduler exists for."""
    model, params = qwen_model
    rng = np.random.default_rng(9)
    eng = PagedLLMEngine(model, params, num_blocks=64, block_size=8,
                         max_batch=8, max_len=96, prefill_chunk=8,
                         step_token_budget=8)
    assert eng.step_token_budget == 8
    eng.submit(rng.integers(1, model.cfg.vocab_size, 6).astype(np.int32),
               max_new=12)
    eng.step()                               # short prompt now decoding
    (short_req,) = eng.active.values()
    eng.submit(rng.integers(1, model.cfg.vocab_size, 40).astype(np.int32),
               max_new=4)
    while eng.prefilling or eng.queue:
        before_tokens = eng.prefill_tokens
        before_out = len(short_req.out_tokens)
        eng.step()
        assert eng.prefill_tokens - before_tokens <= 8
        if len(short_req.out_tokens) < short_req.max_new:
            assert len(short_req.out_tokens) == before_out + 1
    assert eng.prefill_tokens >= 40          # the backlog fully drained


def test_continuous_retrace_gauge_matches_jit_cache(qwen_model):
    """The ragged chunk dispatch's compile gauge must agree with jax's
    real jit cache, and bucketing must keep the trace count far below
    one-per-(rows, length, blocks) combination on a mixed workload.
    With decode fusion (the continuous default) decode rides the verify
    entry as length-1 windows: the gauge spans BOTH prefill jit entries
    and the separate decode program never compiles at all."""
    model, params = qwen_model
    wl = mixed_length_workload(num_requests=10,
                               vocab_size=model.cfg.vocab_size,
                               min_len=4, max_len=40, min_new=2, max_new=6,
                               seed=0)
    eng, _ = _drive(model, params, wl.prompts, wl.max_news,
                    prefill_chunk=16)
    s = eng.stats()
    assert s["decode_fusion"] == 1
    assert s["prefill_compiles"] == (eng._prefill_paged._cache_size()
                                     + eng._prefill_verify._cache_size())
    # fused dispatches add decode-only (c_pad=1) signatures next to the
    # chunk buckets — still O(#row x #len x #block buckets), nowhere
    # near one trace per step
    assert s["prefill_compiles"] <= 12
    assert s["decode_compiles"] == 0         # fused: one program per step

    # fusion off: back to the separate decode program (exactly one trace)
    off, _ = _drive(model, params, wl.prompts, wl.max_news,
                    prefill_chunk=16, decode_fusion=False)
    so = off.stats()
    assert so["decode_fusion"] == 0
    assert so["prefill_compiles"] == off._prefill_paged._cache_size()
    assert so["decode_compiles"] == 1


def test_ragged_inputs_are_padded_host_arrays(qwen_model):
    """The fused step's inputs leave ``_ragged_inputs`` as numpy arrays,
    for the jitted step's own dispatch to move, padded to the row, length
    and block buckets: padding rows carry one garbage lane, the null
    table and the trash state slot."""
    model, params = qwen_model
    eng = PagedLLMEngine(model, params, num_blocks=64, block_size=8,
                         max_batch=8, max_len=96)
    rows = [(np.array([5, 6, 7], np.int32), 0, [1]),
            (np.array([9], np.int32), 12, [2, 3])]
    toks, (bt, starts, lens, srows, c_pad) = eng._ragged_inputs(
        rows, None, True)
    for a in (toks, bt, starts, lens, srows):
        assert isinstance(a, np.ndarray) and a.dtype == np.int32
    r_pad = eng._bucket_rows(2)
    assert toks.shape == (r_pad, c_pad) and c_pad == eng._bucket_len(3)
    assert bt.shape == (r_pad, eng._bucket_blocks(2))
    np.testing.assert_array_equal(toks[0, :3], [5, 6, 7])
    np.testing.assert_array_equal(toks[1, :1], [9])
    np.testing.assert_array_equal(bt[:2, :2], [[1, 0], [2, 3]])
    np.testing.assert_array_equal(starts[:2], [0, 12])
    np.testing.assert_array_equal(lens, [3, 1] + [1] * (r_pad - 2))
    np.testing.assert_array_equal(srows, [eng.max_batch] * r_pad)
    assert not bt[2:].any() and not starts[2:].any()


def test_decode_fusion_token_identity_and_no_growth(qwen_model):
    """Decode fusion is a pure dispatch change: token-identical to the
    unfused continuous scheduler, and re-running the same workload on
    the warm engine compiles nothing new (one XLA program per step in
    steady state — the retrace gauge is the assertion)."""
    model, params = qwen_model
    wl = mixed_length_workload(num_requests=6,
                               vocab_size=model.cfg.vocab_size,
                               min_len=4, max_len=40, min_new=2, max_new=8,
                               seed=11)
    _, unfused = _drive(model, params, wl.prompts, wl.max_news,
                        decode_fusion=False)
    eng, fused = _drive(model, params, wl.prompts, wl.max_news)
    assert fused == unfused
    warm = eng.stats()["prefill_compiles"]
    for p, n in zip(wl.prompts, wl.max_news):
        eng.submit(p, max_new=n)
    again = _drain(eng)
    assert list(again.values()) == list(fused.values())
    assert eng.stats()["prefill_compiles"] == warm
    assert eng.stats()["decode_compiles"] == 0


# ------------------------------------------------------- pool donation


@pytest.mark.parametrize("kw", [
    dict(),                                  # fused: _prefill_verify
    dict(decode_fusion=False),               # _prefill_paged + _decode
    dict(scheduler="serial"),
], ids=["fused", "unfused", "serial"])
def test_step_consumes_the_pool_it_is_given(qwen_model, kw):
    """Every jitted step that returns the pool donates the pool it was
    given: the leaves held before a step are deleted after it and the
    engine's new pool is live.  A copy-on-write admission and a
    preemption afterwards still give the no-cache tokens, so no path
    reads a pool a step has consumed."""
    import jax

    model, params = qwen_model
    rng = np.random.default_rng(7)
    pa = rng.integers(1, model.cfg.vocab_size, 14).astype(np.int32)
    pb = pa.copy()                # 2 full blocks + 2 lanes of block 2 shared
    pb[10] = (int(pb[10]) % (model.cfg.vocab_size - 2)) + 1
    pc = rng.integers(1, model.cfg.vocab_size, 9).astype(np.int32)
    _, ref = _drive(model, params, [pa, pb, pc], **kw)

    eng = PagedLLMEngine(model, params, num_blocks=33, block_size=4,
                         max_batch=4, max_len=32, prefix_cache=True, **kw)
    outs = {}
    eng.submit(pa, max_new=6)
    while not eng.idle:          # one request: no eager update before a step
        held = jax.tree.leaves(eng.pools)
        outs.update((r.rid, list(r.out_tokens)) for r in eng.step())
        assert all(leaf.is_deleted() for leaf in held)
        assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(eng.pools))
    eng.submit(pb, max_new=6)
    eng.submit(pc, max_new=6)
    eng.step()
    assert eng.cow_copies == 1
    eng._preempt_youngest()
    assert eng.preemptions == 1
    outs.update(_drain(eng))
    assert outs == ref


# ------------------------------------------------------ knob validation


def test_scheduler_and_chunk_knob_validation(qwen_model):
    model, params = qwen_model
    with pytest.raises(ValueError, match="scheduler"):
        PagedLLMEngine(model, params, num_blocks=16, block_size=8,
                       max_batch=4, max_len=64, scheduler="eager")
    with pytest.raises(ValueError, match="prefill_chunk"):
        PagedLLMEngine(model, params, num_blocks=16, block_size=8,
                       max_batch=4, max_len=64, prefill_chunk=0)
    # the chunk snaps to a bucket (dispatches reuse whole-suffix sigs),
    # is capped by max_len, and defaults the per-step budget
    eng = PagedLLMEngine(model, params, num_blocks=16, block_size=8,
                         max_batch=4, max_len=64, prefill_chunk=10)
    assert eng.prefill_chunk == 16
    assert eng.step_token_budget == 16
    off = PagedLLMEngine(model, params, num_blocks=16, block_size=8,
                         max_batch=4, max_len=64, prefill_chunk=10,
                         prefill_buckets="off")
    assert off.prefill_chunk == 10           # exact when bucketing is off
    capped = PagedLLMEngine(model, params, num_blocks=16, block_size=8,
                            max_batch=4, max_len=64, prefill_chunk=512,
                            step_token_budget=7)
    assert capped.prefill_chunk == 64
    assert capped.step_token_budget == 7
    assert capped.stats()["prefilling"] == 0

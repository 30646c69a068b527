"""phi4-mini's rotary embedding (partial rotary under LongRoPE), its norm
epsilon, the refusal of contexts past LongRoPE's short regime, and the
count of prefill recomputed after a preemption.

The program is compared with the plain float32 reference that the chip
benchmark uses (``chipbench/configs/phi3_reference.py``), on logits, at
a reduced phi4 size: 2 layers, heads of 64 of which 48 rotate, random
LongRoPE factors (so the per-frequency division is really checked) and
the attention factor of a 131,072-token model over a 4,096-token
original context.  Every tolerance below is written with its reason.
"""
import dataclasses
import importlib.util
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import LongRoPE, get_config
from repro.models import layers
from repro.models.api import Model
from repro.models.attention import rope_args
from repro.obs import Observability
from repro.serving.server import LLMEngine, PagedLLMEngine, check_rope_context

CHIPBENCH = Path(__file__).resolve().parents[1] / "chipbench"

# Program and reference both compute in float32 (the reference at
# ``highest`` matmul precision); they differ only in the order of their
# sums: 1.5e-6 to 2.7e-6 of the logits' scale here.  1e-4 of the scale
# leaves over thirtyfold room above that, and sits far below what a
# rotary or scaling mistake moves (the controls below read 0.5 to 1.2 of
# the scale).
LOGIT_TOL = 1e-4


def _load(name, rel):
    spec = importlib.util.spec_from_file_location(name, CHIPBENCH / rel)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("phi3_reference", "configs/phi3_reference.py")
WEIGHTS = _load("chipbench_weights", "weights.py")


def small_phi4(**over):
    """Reduced phi4 with its rotary fraction, random LongRoPE factors and
    the published attention factor (131,072 over 4,096)."""
    cfg = get_config("phi4-mini-3.8b").reduced()
    n = cfg.rotary_dim // 2
    rng = np.random.default_rng(15)
    rs = LongRoPE(short_factor=tuple(rng.uniform(1.0, 6.0, n)),
                  original_max_position=4096)
    return dataclasses.replace(cfg, max_position=131_072, rope_scaling=rs,
                               **over)


def ref_dict(pc):
    """The reference's configuration (Hugging Face key names) for ``pc``."""
    rs = pc.rope_scaling
    out = {"hidden_size": pc.d_model, "intermediate_size": pc.d_ff,
           "num_hidden_layers": pc.num_layers,
           "num_attention_heads": pc.num_heads,
           "num_key_value_heads": pc.num_kv_heads,
           "head_dim": pc.resolved_head_dim, "vocab_size": pc.vocab_size,
           "rms_norm_eps": pc.norm_eps, "rope_theta": pc.rope_theta,
           "partial_rotary_factor": pc.partial_rotary_factor,
           "max_position_embeddings": pc.max_position}
    if rs is not None:
        out["original_max_position_embeddings"] = rs.original_max_position
        out["rope_scaling"] = {"type": "longrope",
                               "short_factor": list(rs.short_factor)}
    return out


@pytest.fixture(scope="module")
def phi4():
    """The reduced config, seeded random weights in the benchmark's own
    draw (norm scales jittered, so the epsilon and scales matter), and
    their reference view."""
    pc = small_phi4()
    params = WEIGHTS.make_params(
        jax.eval_shape(lambda: Model(pc).init(jax.random.PRNGKey(0))), 15)
    return pc, params, WEIGHTS.reference_view(params)


def ref_logits(view, pc, tokens):
    """The reference's logits at every position of ``tokens``."""
    with jax.default_matmul_precision("highest"):
        h = REF.final_hidden(view, ref_dict(pc), np.asarray(tokens))
        lg = jnp.einsum("sd,vd->sv", h[:len(tokens)],
                        view["embed"].astype(jnp.float32))
    return np.asarray(lg, np.float64)


def rel_err(got, want):
    scale = np.abs(want).max()
    assert scale > 0.1
    return np.abs(got - want).max() / scale


def forward_err(pc, params, view, program=None):
    """Largest relative logit error of the forward pass of ``program``
    (default ``pc``) against the reference of ``pc``."""
    tokens = np.random.default_rng(2).integers(1, pc.vocab_size, 300)
    with jax.default_matmul_precision("highest"):
        got, _ = Model(program or pc).forward(
            params, {"tokens": jnp.asarray(tokens[None])}, remat=False)
    return rel_err(np.asarray(got[0], np.float64), ref_logits(view, pc, tokens))


def engine_err(pc, params, view, program=None):
    """Serve two requests one after another through ``PagedLLMEngine``
    built for ``program`` (default ``pc``; fused step, prefix cache on:
    the second request shares two blocks and a copy-on-write partial
    block with the first), keep the program's logits of every dispatch,
    and compare those that predicted a served token with the reference
    of ``pc``: its full forward pass over prompt + served tokens.
    -> (largest relative error, positions compared)."""
    model = Model(program or pc)
    seen = []

    def keep(lg, sp, sl):
        seen.append((np.asarray(lg[0], np.float64), int(sp[0]), int(sl[0])))

    inner = model.prefill_paged

    def prefill_paged(p, b, pools, bt, sp, **kw):
        logits, caches = inner(p, b, pools, bt, sp, **kw)
        jax.debug.callback(keep, logits, sp, kw["seq_len"], ordered=True)
        return logits, caches

    model.prefill_paged = prefill_paged
    eng = PagedLLMEngine(model, params, num_blocks=64, block_size=16,
                         max_batch=4, max_len=512, prefix_cache=True)
    assert eng._fused_decode
    rng = np.random.default_rng(3)
    first = rng.integers(1, pc.vocab_size, 40).astype(np.int32)
    second = np.concatenate([first[:35], rng.integers(1, pc.vocab_size, 9)
                             .astype(np.int32)])
    worst, compared = 0.0, 0
    with jax.default_matmul_precision("highest"):
        for prompt, n in ((first, 24), (second, 12)):
            seen.clear()
            eng.submit(prompt, max_new=n)
            done = []
            while not eng.idle:
                done += eng.step()
            jax.effects_barrier()
            served = done[0].out_tokens
            assert len(served) == n
            want = ref_logits(view, pc, np.concatenate([prompt, served]))
            at = {}                     # position -> program logits there
            for lg, sp, sl in seen:
                for lane in range(sl):
                    at[sp + lane] = lg[lane]
            rows = range(len(prompt) - 1, len(prompt) - 1 + n)
            got = np.stack([at[p] for p in rows])
            worst = max(worst, rel_err(got, want[list(rows)]))
            compared += n
    assert eng.stats()["hit_rate"] > 0
    return worst, compared


def test_published_phi4_rope_and_norm():
    """The registered config carries the published partial rotary factor,
    LongRoPE context and norm epsilon: 96 of 128 dims rotate, 48 factors
    each, attention factor sqrt(1 + ln 32 / ln 4096)."""
    pc = get_config("phi4-mini-3.8b")
    assert (pc.partial_rotary_factor, pc.rotary_dim, pc.norm_eps) == \
        (0.75, 96, 1e-5)
    rs = pc.rope_scaling
    assert rs.original_max_position == 4096 and pc.max_position == 131_072
    assert len(rs.short_factor) == 48
    assert pc.rope_attention_factor == pytest.approx(1.19024, abs=1e-5)
    assert pc.rope_attention_factor == pytest.approx(
        math.sqrt(1 + math.log(32) / math.log(4096)), rel=1e-12)


def test_forward_logits_match_the_reference(phi4):
    """(a) The training/eval forward against the reference's logits."""
    assert forward_err(*phi4) <= LOGIT_TOL


def test_paged_prefill_and_decode_match_the_reference(phi4):
    """(b) Prefill, then greedy decode through the cache, on the engine's
    own fused step: every served position's logits against the
    reference's full forward pass."""
    err, compared = engine_err(*phi4)
    assert compared == 36
    assert err <= LOGIT_TOL


@pytest.mark.parametrize("drop", ["rotary_fraction", "attention_factor"])
def test_dropping_part_of_the_rope_fails_the_tolerance(phi4, drop):
    """(c) The same comparisons with the program built without the rotary
    fraction (every dim rotated, the factors extended with ones) or
    without the attention factor (no scaling of cos and sin) fail the
    tolerance that (a) and (b) meet, by a wide margin."""
    pc, params, view = phi4
    if drop == "rotary_fraction":
        rs = pc.rope_scaling
        pad = (1.0,) * (pc.resolved_head_dim // 2 - len(rs.short_factor))
        wrong = dataclasses.replace(
            pc, partial_rotary_factor=1.0, rope_scaling=dataclasses.replace(
                rs, short_factor=rs.short_factor + pad))
    else:
        wrong = dataclasses.replace(pc, max_position=4096)
        assert wrong.rope_attention_factor == 1.0
    assert forward_err(pc, params, view, program=wrong) > 100 * LOGIT_TOL
    assert engine_err(pc, params, view, program=wrong)[0] > 100 * LOGIT_TOL


def _rope_before(x, positions, theta):
    """``apply_rope`` as it was before partial rotary and LongRoPE."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "gemma3-4b"])
def test_full_rotary_builds_the_computation_it_did(arch):
    """(d) A config with no rotary fraction and no scaling builds the same
    computation as before (no slice, no concatenate of a pass-through, no
    multiply by 1) and the same bits, for each layer kind it has."""
    cfg = get_config(arch)
    hd = cfg.resolved_head_dim
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 7, 4, hd), jnp.bfloat16)
    pos = jnp.arange(7)[None].repeat(2, 0) + 1000
    for kind in sorted(set(cfg.kinds_for_layers)):
        kw = rope_args(cfg, kind)
        assert kw["rotary_dim"] == hd and kw["factors"] is None
        assert kw["scale"] == 1.0

        def now(x, p):
            return layers.apply_rope(x, p, **kw)

        def before(x, p):
            return _rope_before(x, p, kw["theta"])

        assert str(jax.make_jaxpr(now)(x, pos)) == \
            str(jax.make_jaxpr(before)(x, pos))
        assert np.array_equal(np.asarray(now(x, pos)),
                              np.asarray(before(x, pos)))


@pytest.mark.parametrize("engine,context,refused", [
    ("paged", 4097, True), ("slot", 4097, True), ("paged", 4096, False)])
def test_longrope_context_past_the_short_regime_is_refused(engine, context,
                                                          refused):
    """(e) Only the short factors are served, so a context past the
    original 4,096 tokens is refused when the engine is built."""
    model = Model(small_phi4(num_layers=1))
    params = model.init(jax.random.PRNGKey(0))
    if engine == "paged":
        def build():
            return PagedLLMEngine(model, params, num_blocks=8, block_size=16,
                                  max_batch=2, max_len=context)
    else:
        def build():
            return LLMEngine(model, params, num_slots=1, cache_max=context)
    if refused:
        with pytest.raises(ValueError, match="LongRoPE original context"):
            build()
    else:
        build()
    check_rope_context(get_config("phi4-mini-3.8b"), 3072)
    check_rope_context(get_config("qwen3-0.6b"), 40_960)


def _resumed(obs):
    """``recomputed_tokens`` of the trace's ``evicted_resume`` events;
    asserts that no first admission carries one."""
    events = obs.trace.to_chrome()["traceEvents"]
    assert all("recomputed_tokens" not in e["args"] for e in events
               if e.get("name") == "admitted")
    return [e["args"]["recomputed_tokens"] for e in events
            if e.get("name") == "evicted_resume"]


def _registry_count(obs):
    text = obs.metrics.render()
    line, = [ln for ln in text.splitlines()
             if ln.startswith('engine_recomputed_tokens_total{engine="paged"}')]
    return float(line.split()[-1])


def test_recomputed_tokens_count_the_resumed_prefill():
    """(f) A pool that runs dry while two requests decode preempts the
    younger once; on resume it prefills its prompt and the tokens it had
    generated again.  Exactly the positions whose KV it held are counted
    (prompt plus generated, less the last token, whose KV the next
    decode step would have written), in ``stats()``, the registry counter
    and the ``evicted_resume`` event, and nowhere else."""
    pc = get_config("qwen3-0.6b").reduced()
    model = Model(pc)
    params = model.init(jax.random.PRNGKey(0))
    obs = Observability.create(trace=True, trace_mode="sim")
    eng = PagedLLMEngine(model, params, num_blocks=9, block_size=4,
                         max_batch=2, max_len=32, prefill_chunk=8,
                         prefill_buckets="off", obs=obs)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(1, pc.vocab_size, 8).astype(np.int32)
               for _ in range(2)]
    held = []                     # KV positions each preempted one held
    preempt = eng._preempt_youngest

    def counting(now=0.0):
        youngest = max([*eng.active.values()]
                       + [st.req for st in eng.prefilling.values()],
                       key=lambda r: r.rid)
        assert youngest.out_tokens            # preempted while decoding
        held.append(len(youngest.prompt) + len(youngest.out_tokens) - 1)
        preempt(now)

    eng._preempt_youngest = counting
    for p in prompts:
        eng.submit(p, max_new=14)
    for step in range(200):
        eng.step(now=float(step))
        if eng.idle:
            break
    st = eng.stats()
    assert st["preemptions"] == 1 and len(held) == 1
    assert held[0] > len(prompts[1])           # it had decoded some tokens
    assert st["recomputed_tokens"] == held[0]
    assert st["prefill_tokens"] == sum(map(len, prompts)) + held[0] + 1
    assert _registry_count(obs) == held[0]
    assert _resumed(obs) == held


@pytest.mark.parametrize("times", [1, 2])
def test_recomputed_tokens_after_preemption_mid_prefill(times):
    """(f) A request preempted while its prompt is still streaming in
    counts only the chunks it had computed, not the rest of its prompt;
    preempted again on resume before it got back to that mark, it counts
    the lanes below the mark once per pass.  40-token prompt, 8-token
    chunks: preempted at 16 computed (and again at 8 of the 16), so the
    passes recompute 16, or 8 + 16."""
    pc = get_config("qwen3-0.6b").reduced()
    model = Model(pc)
    params = model.init(jax.random.PRNGKey(0))
    obs = Observability.create(trace=True, trace_mode="sim")
    eng = PagedLLMEngine(model, params, num_blocks=32, block_size=4,
                         max_batch=2, max_len=64, prefill_chunk=8,
                         prefill_buckets="off", obs=obs)
    prompt = np.random.default_rng(5).integers(
        1, pc.vocab_size, 40).astype(np.int32)
    eng.submit(prompt, max_new=4)
    clock = iter(range(200))

    def step_until(done):
        while True:
            eng.step(now=float(next(clock)))
            st, = eng.prefilling.values()
            if st.done == done:
                return

    step_until(16)
    eng._preempt_youngest()
    if times == 2:
        step_until(8)
        eng._preempt_youngest()
    while not eng.idle:
        eng.step(now=float(next(clock)))
    want = 16 if times == 1 else 8 + 16
    st = eng.stats()
    assert st["preemptions"] == times
    assert st["recomputed_tokens"] == want
    assert st["prefill_tokens"] == len(prompt) + 16 + (8 if times == 2 else 0)
    assert _registry_count(obs) == want
    assert _resumed(obs) == [16] * times


def test_recompute_share_reads_the_window_and_nothing_on_a_program_without_it():
    """The benchmark's reader: window change of ``recomputed_tokens``
    over window change of ``prefill_tokens``, in %; None where the
    program's stats lack the counter or nothing was prefilled."""
    reader = _load("recompute_share", "metrics/recompute_share.py")

    class Run:
        def __init__(self, s0, s1):
            self.stats0, self.stats1 = s0, s1

    assert reader.read(Run({"prefill_tokens": 100, "recomputed_tokens": 10},
                           {"prefill_tokens": 500,
                            "recomputed_tokens": 110})) == 25.0
    assert reader.read(Run({"prefill_tokens": 100},
                           {"prefill_tokens": 500})) is None
    assert reader.read(Run({"prefill_tokens": 5, "recomputed_tokens": 0},
                           {"prefill_tokens": 5,
                            "recomputed_tokens": 0})) is None

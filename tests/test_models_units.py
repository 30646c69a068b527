"""Unit tests for the layer library, MoE dispatch, SSM/RWKV recurrences,
and the sharding rules engine."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.base import get_config
from repro.models import layers, moe as moe_mod, rwkv6 as rwkv_mod, ssm as ssm_mod
from repro.models.module import ParamSpec, init_params, count_params, stack_specs
from repro.models.sharding import Rules
from jax.sharding import PartitionSpec as P

KEY = jax.random.PRNGKey(0)


# ------------------------------------------------------------ norms


def test_rmsnorm_unit_scale():
    p = {"scale": jnp.ones((8,))}
    x = jax.random.normal(KEY, (2, 3, 8)) * 10
    y = layers.norm_apply(p, x, "rmsnorm")
    ms = jnp.mean(jnp.square(y), -1)
    np.testing.assert_allclose(np.asarray(ms), 1.0, rtol=1e-3)


def test_layernorm_zero_mean():
    p = {"scale": jnp.ones((8,)), "bias": jnp.zeros((8,))}
    x = jax.random.normal(KEY, (2, 3, 8)) + 5
    y = layers.norm_apply(p, x, "layernorm")
    np.testing.assert_allclose(np.asarray(jnp.mean(y, -1)), 0.0, atol=1e-5)


# ------------------------------------------------------------ RoPE


def test_rope_preserves_norm():
    x = jax.random.normal(KEY, (1, 6, 2, 64))
    pos = jnp.arange(6)
    y = layers.apply_rope(x, pos, 10_000.0, rotary_dim=64)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(x), axis=-1),
        np.linalg.norm(np.asarray(y), axis=-1), rtol=1e-5)


def test_rope_relative_property():
    """<q_m, k_n> depends only on (m - n)."""
    q = jax.random.normal(KEY, (1, 1, 1, 32))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 1, 32))
    def dot_at(m, n):
        qq = layers.apply_rope(q, jnp.array([m]), 10_000.0, rotary_dim=32)
        kk = layers.apply_rope(k, jnp.array([n]), 10_000.0, rotary_dim=32)
        return float(jnp.sum(qq * kk))
    assert abs(dot_at(5, 3) - dot_at(9, 7)) < 1e-4
    assert abs(dot_at(5, 3) - dot_at(6, 3)) > 1e-6


# ------------------------------------------------------------ xent


def test_softmax_xent_uniform():
    logits = jnp.zeros((2, 3, 7))
    labels = jnp.zeros((2, 3), jnp.int32)
    loss = layers.softmax_xent(logits, labels)
    np.testing.assert_allclose(float(loss), np.log(7), rtol=1e-6)


def test_softmax_xent_mask():
    logits = jax.random.normal(KEY, (1, 4, 11))
    labels = jnp.array([[1, 2, 3, 4]])
    mask = jnp.array([[1, 1, 0, 0]], jnp.float32)
    l_mask = layers.softmax_xent(logits, labels, mask)
    l_first = layers.softmax_xent(logits[:, :2], labels[:, :2])
    np.testing.assert_allclose(float(l_mask), float(l_first), rtol=1e-6)


# ------------------------------------------------------------ MoE


def _moe_cfg(e=4, k=2, cf=None):
    cfg = get_config("dbrx-132b").reduced()
    return dataclasses.replace(
        cfg, num_experts=e, num_experts_per_tok=k,
        moe_capacity_factor=cf if cf else float(e) / k)


def test_moe_dropless_equals_dense_expert_sum():
    """With capacity e/k (dropless), the output must equal the explicit
    gate-weighted sum over selected experts."""
    cfg = _moe_cfg()
    p = init_params(moe_mod.moe_schema(cfg), KEY, "float32")
    x = jax.random.normal(KEY, (2, 10, cfg.d_model))
    y, aux = moe_mod.moe_apply(p, cfg, x)

    logits = jnp.einsum("bsd,de->bse", x, p["router"])
    probs = jax.nn.softmax(logits, -1)
    gv, ei = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    gv = gv / jnp.sum(gv, -1, keepdims=True)

    def expert(e_idx, xv):
        up = xv @ p["up"][e_idx]
        g = xv @ p["gate"][e_idx]
        return (jax.nn.silu(g) * up) @ p["down"][e_idx]

    expect = np.zeros(y.shape, np.float32)
    for b in range(2):
        for s in range(10):
            acc = 0
            for j in range(cfg.num_experts_per_tok):
                acc += float(gv[b, s, j]) * expert(int(ei[b, s, j]), x[b, s])
            expect[b, s] = np.asarray(acc)
    np.testing.assert_allclose(np.asarray(y), expect, atol=2e-4, rtol=1e-3)
    assert float(aux) > 0


def test_moe_capacity_drops_tokens():
    cfg = _moe_cfg(cf=0.3)     # tight capacity -> drops
    p = init_params(moe_mod.moe_schema(cfg), KEY, "float32")
    x = jax.random.normal(KEY, (1, 32, cfg.d_model))
    y, _ = moe_mod.moe_apply(p, cfg, x)
    # dropped tokens get zero MoE output; at cf=0.3 some row must be ~0
    norms = np.linalg.norm(np.asarray(y[0]), axis=-1)
    assert (norms < 1e-6).any()


def test_moe_aux_loss_balanced_router():
    """Uniform router -> aux == 1.0 (E * E * (1/E) * (1/E))."""
    cfg = _moe_cfg()
    p = init_params(moe_mod.moe_schema(cfg), KEY, "float32")
    p = {**p, "router": jnp.zeros_like(p["router"])}
    x = jax.random.normal(KEY, (2, 64, cfg.d_model))
    _, aux = moe_mod.moe_apply(p, cfg, x)
    assert 0.9 < float(aux) < 1.3


# ------------------------------------------------------------ SSM


def test_ssm_chunked_state_chaining():
    cfg = get_config("jamba-1.5-large-398b").reduced()
    p = init_params(ssm_mod.ssm_schema(cfg), KEY, "float32")
    x = jax.random.normal(KEY, (2, 24, cfg.d_model))
    y_full, s_full = ssm_mod.ssm_forward(p, cfg, x)
    y1, s1 = ssm_mod.ssm_forward(p, cfg, x[:, :12])
    y2, s2 = ssm_mod.ssm_forward(p, cfg, x[:, 12:], s1)
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate([y1, y2], 1)), np.asarray(y_full),
        atol=1e-5, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s2["h"]), np.asarray(s_full["h"]),
                               atol=1e-5, rtol=1e-4)


def test_ssm_decay_bounds():
    """State must decay (|h| bounded) under zero input."""
    cfg = get_config("jamba-1.5-large-398b").reduced()
    p = init_params(ssm_mod.ssm_schema(cfg), KEY, "float32")
    x = jnp.zeros((1, 50, cfg.d_model))
    state = ssm_mod.init_state(cfg, 1, jnp.float32)
    state = {**state, "h": jnp.ones_like(state["h"]) * 100}
    _, s2 = ssm_mod.ssm_forward(p, cfg, x, state)
    assert float(jnp.max(jnp.abs(s2["h"]))) < 100.0


# ------------------------------------------------------------ RWKV


def test_rwkv_channel_mix_token_shift():
    cfg = get_config("rwkv6-1.6b").reduced()
    p = init_params(rwkv_mod.channel_mix_schema(cfg), KEY, "float32")
    x = jax.random.normal(KEY, (1, 8, cfg.d_model))
    prev = jax.random.normal(jax.random.PRNGKey(2), (1, cfg.d_model))
    y, new_prev = rwkv_mod.channel_mix(p, cfg, x, prev)
    np.testing.assert_allclose(np.asarray(new_prev), np.asarray(x[:, -1]))
    # shifting changes output only via mu_k != 0
    p0 = {**p, "mu_k": jnp.zeros_like(p["mu_k"])}
    y0a, _ = rwkv_mod.channel_mix(p0, cfg, x, prev)
    y0b, _ = rwkv_mod.channel_mix(p0, cfg, x, prev * 100)
    np.testing.assert_allclose(np.asarray(y0a), np.asarray(y0b))


def test_rwkv_time_mix_state_chaining():
    cfg = get_config("rwkv6-1.6b").reduced()
    p = init_params(rwkv_mod.rwkv_schema(cfg), KEY, "float32")
    x = jax.random.normal(KEY, (1, 16, cfg.d_model))
    st = rwkv_mod.init_state(cfg, 1, jnp.float32)
    y_full, _ = rwkv_mod.rwkv_time_mix(p, cfg, x, st)
    y1, s1 = rwkv_mod.rwkv_time_mix(p, cfg, x[:, :8], st)
    y2, _ = rwkv_mod.rwkv_time_mix(p, cfg, x[:, 8:],
                                   {**st, "s": s1["s"], "x_tm": s1["x_tm"]})
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate([y1, y2], 1)), np.asarray(y_full),
        atol=1e-4, rtol=1e-3)


# ------------------------------------------------------------ sharding rules


def _rules():
    return Rules({"batch": ("pod", "data"), "heads": "model",
                  "d_ff": "model", "experts": "model",
                  "expert_ff": "model", "d_model": "data"},
                 {"pod": 2, "data": 16, "model": 16})


def test_rules_divisibility_filter():
    r = _rules()
    assert r.spec(("heads",), (64,)) == P("model")
    assert r.spec(("heads",), (8,)) == P(None)       # 8 % 16 != 0
    assert r.spec(("batch",), (1,)) == P(None)       # long_500k batch 1
    assert r.spec(("batch",), (256,)) == P(("pod", "data"))


def test_rules_dedup_first_wins():
    r = _rules()
    # activations: batch claims data; d_model falls back to replicated
    assert r.spec(("batch", None, "d_model"), (256, 128, 8192)) == \
        P(("pod", "data"), None, None)
    # weights: d_model gets data (FSDP)
    assert r.spec(("d_model", "d_ff"), (8192, 32768)) == P("data", "model")


def test_rules_expert_ff_fallback():
    r = _rules()
    # 16 experts divide -> experts takes model, expert_ff replicated
    assert r.spec(("experts", "d_model", "expert_ff"),
                  (16, 6144, 10752)) == P("model", "data", None)
    # grok: 8 experts don't divide -> expert_ff claims model
    assert r.spec(("experts", "d_model", "expert_ff"),
                  (8, 6144, 32768)) == P(None, "data", "model")


@settings(max_examples=50, deadline=None)
@given(st.lists(st.sampled_from(["batch", "heads", "d_ff", "d_model",
                                 "experts", None]), min_size=1, max_size=5),
       st.lists(st.sampled_from([1, 2, 8, 16, 64, 256]), min_size=1,
                max_size=5))
def test_rules_never_reuse_axis_property(logical, dims):
    n = min(len(logical), len(dims))
    logical, dims = tuple(logical[:n]), tuple(dims[:n])
    spec = _rules().spec(logical, dims)
    flat = []
    for part in spec:
        if part is None:
            continue
        flat.extend(part if isinstance(part, tuple) else (part,))
    assert len(flat) == len(set(flat))          # no mesh axis reused
    for i, part in enumerate(spec):             # divisibility respected
        if part is None:
            continue
        size = 1
        for a in (part if isinstance(part, tuple) else (part,)):
            size *= {"pod": 2, "data": 16, "model": 16}[a]
        assert dims[i] % size == 0


# ------------------------------------------------------------ module


def test_stack_specs_and_count():
    spec = {"w": ParamSpec((4, 8), ("d_model", "d_ff"), scale_dim=-2)}
    stacked = stack_specs(spec, 3)
    assert stacked["w"].shape == (3, 4, 8)
    assert stacked["w"].logical == ("layers", "d_model", "d_ff")
    assert count_params(stacked) == 96


def test_param_count_analytic_vs_actual():
    """configs/base.py param_count() must track the real initialized tree
    (within 2% — norms/small biases are approximated)."""
    from repro.models.api import Model
    for arch in ("qwen3-0.6b", "gemma3-4b", "dbrx-132b"):
        cfg = get_config(arch).reduced()
        model = Model(cfg)
        params = model.init(KEY)
        actual = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
        analytic = cfg.param_count()
        assert abs(actual - analytic) / actual < 0.15, (arch, actual, analytic)


# ------------------------------------------------------------ caches


def test_gemma3_local_layers_get_window_sized_cache():
    """attn_local slots cache only the sliding window; global slots cache
    the full length — the memory property long_500k depends on."""
    from repro.models import attention as attn
    cfg = get_config("gemma3-4b")
    full = attn.abstract_cache(cfg, "attn", 1, 32_768, "bfloat16")
    local = attn.abstract_cache(cfg, "attn_local", 1, 32_768, "bfloat16")
    assert full["k"].shape[1] == 32_768
    assert local["k"].shape[1] == cfg.sliding_window == 1024


def test_whisper_learned_positions_clamped():
    """decode_32k lowers for whisper by clamping positions to the table."""
    from repro.models.api import Model
    cfg = get_config("whisper-tiny").reduced()
    m = Model(cfg)
    params = m.init(KEY)
    import jax.numpy as jnp
    from repro.models import frontend as fe
    _, caches = m.prefill(
        params, {"tokens": jnp.ones((1, 4), jnp.int32),
                 "embeds": fe.fake_embeds(cfg, 1, cfg.dtype)}, cache_max=16)
    # position far beyond the learned table must not crash (clamped)
    logits, _ = m.decode_step(params, caches, jnp.ones((1, 1), jnp.int32),
                              jnp.array([cfg.max_position + 500], jnp.int32))
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))


def test_kv_quant_cache_is_smaller():
    from repro.models import attention as attn
    import numpy as np
    cfg = get_config("qwen1.5-110b")
    cfg_q = dataclasses.replace(cfg, kv_cache_quant=True)
    def nbytes(c):
        return sum(int(np.prod(l.shape)) * l.dtype.itemsize
                   for l in jax.tree.leaves(c))
    dense = nbytes(attn.abstract_cache(cfg, "attn", 4, 1024, "bfloat16"))
    quant = nbytes(attn.abstract_cache(cfg_q, "attn", 4, 1024, "bfloat16"))
    assert quant < 0.6 * dense

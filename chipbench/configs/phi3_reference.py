"""Plain reference of the Phi-3 decoder (``Phi3ForCausalLM``), for the
configs in this directory that name ``"reference": "phi3_reference"``.

Written from the equations, not from the program.  Each pre-norm block
is RMS norm (epsilon ``rms_norm_eps``), grouped-query attention, residual
add, RMS norm, SwiGLU MLP, residual add; the unembedding is the tied
embedding.  Rotary position embedding rotates only the first
``rotary_dim = head_dim x partial_rotary_factor`` dimensions of each
query and key head, as two halves of those dimensions; the rest pass
through unrotated.  Its inverse frequencies are ``theta^(-2i/rotary_dim)``
for ``i < rotary_dim / 2``.  Under ``rope_scaling`` of type
``longrope`` each is divided by its ``short_factor`` (contexts up to
``original_max_position_embeddings``; the engines serve no longer
ones), and cos and sin are multiplied by the attention factor
``sqrt(1 + ln(s) / ln(original_max_position_embeddings))``,
``s = max_position_embeddings / original_max_position_embeddings``
(1 when ``s <= 1``).  Everything runs in float32 at ``highest`` matmul
precision, one layer at a time.

Departures from the published model: the weights are random; separate
query, key and value matrices and separate gate and up matrices stand
for the fused ``qkv_proj`` and ``gate_up_proj`` (the same products); the
long regime (``long_factor``) is not computed.

``control=True`` computes the same model in float8 (e4m3, one absmax
scale per tensor, float32 accumulation), as ``dense_reference`` does:
the precision below the configuration's bfloat16 that the correctness
check must reject.  Weights are laid out as ``dense_reference``
describes (no ``q_norm``/``k_norm``).  The matmul, norm, float8 and
logit-gap helpers are ``dense_reference``'s own.
"""
from __future__ import annotations

import functools
import importlib.util
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np


def _load_dense():
    path = Path(__file__).with_name("dense_reference.py")
    spec = importlib.util.spec_from_file_location("dense_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_dense = _load_dense()
_q8, _mm, _rms = _dense._q8, _dense._mm, _dense._rms
PAD, Q_BLOCK, LOGIT_BLOCK = _dense.PAD, _dense.Q_BLOCK, _dense.LOGIT_BLOCK


def rope_tables(cfg):
    """-> (inverse frequencies as a tuple, rotary_dim, attention factor)
    of the configuration's rotary embedding in its short regime."""
    h = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // h
    rd = int(hd * float(cfg.get("partial_rotary_factor", 1.0)))
    inv = 1.0 / float(cfg["rope_theta"]) ** (
        np.arange(0, rd, 2, dtype=np.float64) / rd)
    scale = 1.0
    rs = cfg.get("rope_scaling")
    if rs:
        if rs["type"] != "longrope":
            raise ValueError(f"rope_scaling type {rs['type']!r} is not longrope")
        l0 = int(cfg["original_max_position_embeddings"])
        inv = inv / np.asarray(rs["short_factor"], np.float64)
        s = int(cfg["max_position_embeddings"]) / l0
        if s > 1.0:
            scale = math.sqrt(1.0 + math.log(s) / math.log(l0))
    return tuple(float(f) for f in inv), rd, scale


def _rope(x, pos, inv, rd, scale):
    """x (S, heads, hd): rotate the first ``rd`` dims as two halves,
    cos and sin scaled by ``scale``; the rest pass through."""
    half = rd // 2
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    cos = (jnp.cos(ang) * scale)[:, None, :]
    sin = (jnp.sin(ang) * scale)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rd], x[..., rd:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           -1)


@functools.partial(jax.jit, static_argnames=("hp", "control"))
def _layer(x, layers, i, hp, control):
    """One pre-norm block over x (S, d) float32; row j sits at position j."""
    heads, kv_heads, hd, eps, inv, rd, scale = hp
    w = {k: jax.lax.dynamic_index_in_dim(v, i, 0, keepdims=False)
         for k, v in layers.items()}
    s = x.shape[0]
    pos = jnp.arange(s, dtype=jnp.int32)
    h = _rms(x, w["attn_norm"], eps)
    q = _mm("sd,dhk->shk", h, w["wq"], control)
    k = _mm("sd,dhk->shk", h, w["wk"], control)
    v = _mm("sd,dhk->shk", h, w["wv"], control)
    q, k = _rope(q, pos, inv, rd, scale), _rope(k, pos, inv, rd, scale)
    group = heads // kv_heads
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    outs = []
    for lo in range(0, s, Q_BLOCK):
        qb = q[lo:lo + Q_BLOCK]
        sc = _mm("qhk,shk->hqs", qb, k, control) / math.sqrt(hd)
        qpos = pos[lo:lo + Q_BLOCK]
        sc = jnp.where(pos[None, None, :] <= qpos[None, :, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(_mm("hqs,shk->qhk", p, v, control))
    att = jnp.concatenate(outs, 0)
    x = x + _mm("shk,hkd->sd", att, w["wo"], control)
    h = _rms(x, w["mlp_norm"], eps)
    g = _mm("sd,df->sf", h, w["gate"], control)
    u = _mm("sd,df->sf", h, w["up"], control)
    return x + _mm("sf,fd->sd", jax.nn.silu(g) * u, w["down"], control)


def _hparams(cfg):
    h = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // h
    return (h, cfg["num_key_value_heads"], hd, float(cfg["rms_norm_eps"]),
            *rope_tables(cfg))


def final_hidden(weights, cfg, tokens, control=False):
    """Final-normed hidden states (S_pad, d) float32 for ``tokens`` (S,)."""
    s = len(tokens)
    s_pad = -(-s // PAD) * PAD
    tok = np.zeros((s_pad,), np.int32)
    tok[:s] = tokens
    x = jnp.take(weights["embed"], jnp.asarray(tok), axis=0).astype(jnp.float32)
    if control:
        x = _q8(x)
    hp = _hparams(cfg)
    for i in range(cfg["num_hidden_layers"]):
        x = _layer(x, weights["layers"], i, hp, control)
    return _rms(x, weights["final_norm"], hp[3])


def served_gaps(weights, cfg, prompt, served, control=False):
    """Run the reference over ``prompt`` followed by the ``served`` tokens.
    -> (gaps, control_gaps) as float64 arrays, one entry per served token:
    how far the served token's reference logit lies below the reference's
    best at that position, and the same for the float8 model's first
    choice (None unless ``control``)."""
    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(served, np.int32)])
    n = len(served)
    at = np.arange(len(prompt) - 1, len(prompt) - 1 + n)   # predicting rows
    h = final_hidden(weights, cfg, seq[:-1] if n else seq)
    h_c = final_hidden(weights, cfg, seq[:-1], control=True) if control else h
    gaps, cgaps = [], []
    for lo in range(0, n, LOGIT_BLOCK):
        idx = np.zeros((LOGIT_BLOCK,), np.int32)
        tok = np.zeros((LOGIT_BLOCK,), np.int32)
        m = min(LOGIT_BLOCK, n - lo)
        idx[:m] = at[lo:lo + m]
        tok[:m] = np.asarray(served[lo:lo + m], np.int32)
        ji = jnp.asarray(idx)
        g, c = _dense._logit_block(h[ji], weights["embed"], jnp.asarray(tok),
                                   h_c[ji], control)
        gaps.append(np.asarray(g, np.float64)[:m])
        cgaps.append(np.asarray(c, np.float64)[:m])
    gaps = np.concatenate(gaps) if gaps else np.zeros((0,))
    cgaps = np.concatenate(cgaps) if cgaps else np.zeros((0,))
    return gaps, (cgaps if control else None)

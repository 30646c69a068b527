"""Plain reference of a dense decoder-only transformer, for the configs in
this directory that name ``"reference": "dense_reference"``.

Written from the equations, not from the program: grouped-query attention
with rotary position embedding (half-split rotation, base ``rope_theta``),
an optional RMS norm of each query and key head (``qk_norm``), pre-norm
RMS residual blocks, a SwiGLU MLP and a tied embedding.  Everything runs
in float32 at ``highest`` matmul precision, one layer at a time, so a
model of several GB fits beside its bfloat16 weights.

``control=True`` computes the same model in float8 (e4m3, one absmax
scale per tensor, float32 accumulation): the precision below the
configuration's bfloat16 that the correctness check must reject.

Weights are a dict of arrays (any float dtype; upcast here):
``embed`` (V, d), ``final_norm`` (d,), and ``layers``, a dict of arrays
stacked over layers: ``attn_norm`` (L, d), ``wq`` (L, d, H, hd), ``wk``
and ``wv`` (L, d, KV, hd), ``wo`` (L, H, hd, d), ``q_norm`` and
``k_norm`` (L, hd) when ``qk_norm``, ``mlp_norm`` (L, d), ``gate`` and
``up`` (L, d, F), ``down`` (L, F, d).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
PAD = 1024         # sequences are padded to a multiple of this (few compiles)
Q_BLOCK = 512      # query rows per attention block
LOGIT_BLOCK = 256  # positions per unembedding block


def _q8(x):
    """Round to float8 e4m3 with one absmax scale for the tensor."""
    x = x.astype(jnp.float32)
    scale = F8_MAX / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(F8).astype(jnp.float32) / scale


def _mm(eq, a, b, control):
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if control:
        a, b = _q8(a), _q8(b)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _rope(x, pos, theta):
    """x (S, heads, hd); rotate the two halves of each head."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (np.arange(half, dtype=np.float64) / half))
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("hp", "control"))
def _layer(x, layers, i, hp, control):
    """One pre-norm block over x (S, d) float32; row j sits at position j."""
    heads, kv_heads, hd, eps, theta, qk_norm = hp
    w = {k: jax.lax.dynamic_index_in_dim(v, i, 0, keepdims=False)
         for k, v in layers.items()}
    s = x.shape[0]
    pos = jnp.arange(s, dtype=jnp.int32)
    h = _rms(x, w["attn_norm"], eps)
    q = _mm("sd,dhk->shk", h, w["wq"], control)
    k = _mm("sd,dhk->shk", h, w["wk"], control)
    v = _mm("sd,dhk->shk", h, w["wv"], control)
    if qk_norm:
        q = _rms(q, w["q_norm"], eps)
        k = _rms(k, w["k_norm"], eps)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
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
            float(cfg["rope_theta"]), bool(cfg.get("qk_norm")))


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


@functools.partial(jax.jit, static_argnames=("control",))
def _logit_block(h, embed, served, h_ctrl, control):
    """Gaps for one block of positions: the reference's best logit minus
    the served token's logit, and (``control``) minus the logit of the
    token the float8 model puts first."""
    logits = _mm("pd,vd->pv", h, embed, False)
    best = jnp.max(logits, -1)
    gap = best - jnp.take_along_axis(logits, served[:, None], -1)[:, 0]
    if not control:
        return gap, gap
    ctrl = jnp.argmax(_mm("pd,vd->pv", h_ctrl, embed, True), -1)
    return gap, best - jnp.take_along_axis(logits, ctrl[:, None], -1)[:, 0]


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
        g, c = _logit_block(h[ji], weights["embed"], jnp.asarray(tok),
                            h_c[ji], control)
        gaps.append(np.asarray(g, np.float64)[:m])
        cgaps.append(np.asarray(c, np.float64)[:m])
    gaps = np.concatenate(gaps) if gaps else np.zeros((0,))
    cgaps = np.concatenate(cgaps) if cgaps else np.zeros((0,))
    return gaps, (cgaps if control else None)

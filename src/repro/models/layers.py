"""Shared layer library: norms, RoPE, MLPs, embeddings.

All functions are pure; parameters come from ``ParamSpec`` schemas declared
next to each apply function.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.module import ParamSpec
from repro.models.sharding import shard

# ---------------------------------------------------------------- norms


def norm_schema(cfg: ModelConfig, dim: int = 0):
    d = dim or cfg.d_model
    if cfg.norm_kind == "layernorm":
        return {
            "scale": ParamSpec((d,), ("d_model",), init="ones"),
            "bias": ParamSpec((d,), ("d_model",), init="zeros"),
        }
    return {"scale": ParamSpec((d,), ("d_model",), init="ones")}


def norm_apply(p, x, kind: str, eps: float = 1e-6):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    if kind == "layernorm":
        mu = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.var(x32, axis=-1, keepdims=True)
        y = (x32 - mu) * jax.lax.rsqrt(var + eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:  # rmsnorm
        ms = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        y = x32 * jax.lax.rsqrt(ms + eps) * p["scale"].astype(jnp.float32)
    return y.astype(dt)


# ---------------------------------------------------------------- RoPE


def rope_angles(positions, head_dim: int, theta: float, factors=None):
    """positions: (...,) int -> (…, head_dim/2) angles.  ``factors``
    (head_dim/2,) divide the inverse frequencies (LongRoPE)."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    if factors is not None:
        freqs = freqs / jnp.asarray(factors, jnp.float32)
    return positions.astype(jnp.float32)[..., None] * freqs


def apply_rope(x, positions, theta: float, rotary_dim: int,
               factors=None, scale: float = 1.0):
    """x: (B, S, H, hd); positions: (B, S) or (S,).  Rotates the first
    ``rotary_dim`` dims of each head as two halves; the rest pass
    through.  ``factors`` divide the inverse frequencies and ``scale``
    multiplies cos and sin (LongRoPE)."""
    rd, hd = rotary_dim, x.shape[-1]
    ang = rope_angles(positions, rd, theta, factors)  # (B, S, rd/2)
    cos = jnp.cos(ang)[..., None, :]                 # (B, S, 1, rd/2)
    sin = jnp.sin(ang)[..., None, :]
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    xf = x.astype(jnp.float32)
    x1, x2 = jnp.split(xf[..., :rd] if rd < hd else xf, 2, axis=-1)
    parts = [x1 * cos - x2 * sin, x2 * cos + x1 * sin]
    if rd < hd:
        parts.append(xf[..., rd:])
    return jnp.concatenate(parts, axis=-1).astype(x.dtype)


# ---------------------------------------------------------------- MLP


def mlp_schema(cfg: ModelConfig, d_ff: int = 0):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    gated = cfg.mlp_kind in ("swiglu", "geglu")
    s = {
        "up": ParamSpec((d, f), ("d_model", "d_ff"), scale_dim=-2),
        "down": ParamSpec((f, d), ("d_ff", "d_model"), scale_dim=-2),
    }
    if gated:
        s["gate"] = ParamSpec((d, f), ("d_model", "d_ff"), scale_dim=-2)
    return s


def mlp_apply(p, x, kind: str):
    up = shard(jnp.einsum("bsd,df->bsf", x, p["up"]), "batch", "seq", "d_ff")
    if kind == "swiglu":
        g = jnp.einsum("bsd,df->bsf", x, p["gate"])
        h = jax.nn.silu(g) * up
    elif kind == "geglu":
        g = jnp.einsum("bsd,df->bsf", x, p["gate"])
        h = jax.nn.gelu(g) * up
    else:  # gelu
        h = jax.nn.gelu(up)
    out = jnp.einsum("bsf,fd->bsd", h, p["down"])
    return shard(out, "batch", "seq", "d_model")


# ---------------------------------------------------------------- embeddings


def embed_schema(cfg: ModelConfig):
    # "embed_d" (not "d_model"): FSDP-sharding the embedding's model dim
    # forces an involuntary full-remat reshard around the token gather
    # (measured on the 2x16x16 mesh); embeddings stay vocab-sharded only.
    s = {"tokens": ParamSpec((cfg.vocab_size, cfg.d_model), ("vocab", "embed_d"), init="embed")}
    if not cfg.tie_embeddings:
        s["unembed"] = ParamSpec(
            (cfg.d_model, cfg.vocab_size), ("embed_d", "vocab"), scale_dim=-2
        )
    if cfg.pos_kind == "learned":
        s["positions"] = ParamSpec(
            (cfg.max_position, cfg.d_model), (None, "embed_d"), init="embed"
        )
    return s


def embed_apply(p, cfg: ModelConfig, tokens, positions=None):
    x = jnp.take(p["tokens"], tokens, axis=0)
    if cfg.pos_kind == "learned":
        assert positions is not None
        x = x + jnp.take(p["positions"], positions, axis=0).astype(x.dtype)
    return shard(x, "batch", "seq", "d_model")


def unembed_apply(p, cfg: ModelConfig, x):
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, p["tokens"])
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, p["unembed"])
    return shard(logits, "batch", "seq", "vocab")


# ---------------------------------------------------------------- loss


def softmax_xent(logits, labels, mask=None):
    """Mean next-token cross-entropy in fp32. labels: int (B,S)."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)

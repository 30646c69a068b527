"""Attention: GQA / MQA, RoPE, qk-norm, causal + sliding-window, cross-attn,
KV-cache prefill/decode.

Three entry points per layer:
  * ``attn_apply``   — full-sequence (train / prefill), q-chunked so the
    lowered HLO never materializes the (S, S) score matrix (the chunk body
    is ``jax.checkpoint``-ed so the backward re-computes scores instead of
    saving them: flash-attention-by-remat on the jnp path; the Pallas
    kernel in ``repro.kernels`` is the TPU-target implementation).
  * ``attn_prefill`` — ``attn_apply`` + builds the decode cache.
  * ``attn_decode``  — one new token against a cache (ring buffer for
    sliding-window layers), per-sequence positions.

The KV cache for one layer is ``{"k": (B, C, KV, hd), "v": (B, C, KV, hd),
"pos": (B, C) int32}`` where ``pos`` holds the absolute position of each
slot (or -1 when empty).  Carrying positions explicitly makes ring-buffer
masking trivial and makes the cache self-describing.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import apply_rope
from repro.models.module import ParamSpec
from repro.models.sharding import shard

NEG_INF = -2.0 ** 30  # large-negative that survives bf16

# q-chunk override: the roofline cost-lowering path disables the q-chunk
# scan (cost_analysis counts scan bodies once — DESIGN.md §Roofline-method)
# by forcing one chunk.  contextvar so model code stays signature-stable.
import contextvars

_Q_CHUNK_OVERRIDE: contextvars.ContextVar[Optional[int]] = \
    contextvars.ContextVar("q_chunk_override", default=None)


def _kernels_on() -> bool:
    from repro.kernels.ops import kernels_enabled

    return kernels_enabled()


# ------------------------------------------------------------------ schema


def attn_schema(cfg: ModelConfig, cross: bool = False):
    hd = cfg.resolved_head_dim
    d = cfg.d_model
    s = {
        "wq": ParamSpec((d, cfg.num_heads, hd), ("d_model", "heads", "head_dim"), scale_dim=-3),
        "wk": ParamSpec((d, cfg.num_kv_heads, hd), ("d_model", "kv_heads", "head_dim"), scale_dim=-3),
        "wv": ParamSpec((d, cfg.num_kv_heads, hd), ("d_model", "kv_heads", "head_dim"), scale_dim=-3),
        "wo": ParamSpec((cfg.num_heads, hd, d), ("heads", "head_dim", "d_model"), scale_dim=-2),
    }
    if cfg.qkv_bias:
        s["bq"] = ParamSpec((cfg.num_heads, hd), ("heads", "head_dim"), init="zeros")
        s["bk"] = ParamSpec((cfg.num_kv_heads, hd), ("kv_heads", "head_dim"), init="zeros")
        s["bv"] = ParamSpec((cfg.num_kv_heads, hd), ("kv_heads", "head_dim"), init="zeros")
    if cfg.qk_norm and not cross:
        s["q_norm"] = ParamSpec((hd,), ("head_dim",), init="ones")
        s["k_norm"] = ParamSpec((hd,), ("head_dim",), init="ones")
    return s


# ------------------------------------------------------------------ helpers


def _rms(x, scale, eps=1e-6):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _theta_for(cfg: ModelConfig, kind: str) -> float:
    """Per-kind RoPE base: gemma3's local layers keep the 10k base while
    global layers use the long-context 1M base."""
    if kind == "attn_local" and cfg.rope_theta_local:
        return cfg.rope_theta_local
    return cfg.rope_theta


def rope_args(cfg: ModelConfig, kind: str) -> dict:
    """``apply_rope``'s arguments for a layer of ``kind``: its base, the
    rotated dims and, under LongRoPE, the short-regime factors and the
    attention factor (the engines refuse a context past
    ``original_max_position``, ``serving/server.py``)."""
    rs = cfg.rope_scaling
    return dict(theta=_theta_for(cfg, kind), rotary_dim=cfg.rotary_dim,
                factors=rs.short_factor if rs else None,
                scale=cfg.rope_attention_factor)


def _project_qkv(p, cfg: ModelConfig, x, positions, rope: bool,
                 kind: str = "attn"):
    """x (B,S,D) -> q (B,S,H,hd), k/v (B,S,KV,hd); RoPE at ``positions``."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    if cfg.qk_norm and "q_norm" in p:
        q = _rms(q, p["q_norm"], cfg.norm_eps)
        k = _rms(k, p["k_norm"], cfg.norm_eps)
    if rope:
        kw = rope_args(cfg, kind)
        q = apply_rope(q, positions, **kw)
        k = apply_rope(k, positions, **kw)
    q = shard(q, "batch", "seq", "heads", "head_dim")
    k = shard(k, "batch", "seq", "kv_heads", "head_dim")
    v = shard(v, "batch", "seq", "kv_heads", "head_dim")
    return q, k, v


def _repeat_kv(k, h: int, seq_name: str = "seq"):
    """(B,S,KV,hd) -> (B,S,H,hd).  Materializing the repeat costs G x the
    KV bytes but keeps every attention intermediate sharded by the FULL
    head count (KV alone often doesn't divide the ``model`` axis: 8 kv
    heads on a 16-way axis would replicate the (B,H?,Sq,Sk) score tensor —
    the dominant activation at 32k context)."""
    kv = k.shape[2]
    if kv != h:
        k = jnp.repeat(k, h // kv, axis=2)
    return shard(k, "batch", seq_name, "heads", "head_dim")


def _scores(q, k_rep, spec=("batch", "heads", None, None)):
    """q (B,Sq,H,hd), k_rep (B,Sk,H,hd) -> (B,H,Sq,Sk).

    ``spec`` controls the score sharding: head-sharded for train/prefill,
    kv_len-sharded for decode (flash-decoding: the 32k-500k KV length is
    the only axis with enough extent to fill the ``model`` axis when the
    query is a single token)."""
    s = jnp.einsum("bqhd,bshd->bhqs", q, k_rep)
    return shard(s, *spec)


def _attn_out(probs, v_rep):
    """probs (B,H,Sq,Sk), v_rep (B,Sk,H,hd) -> (B,Sq,H,hd)."""
    out = jnp.einsum("bhqs,bshd->bqhd", probs, v_rep)
    return shard(out, "batch", "seq", "heads", "head_dim")


def _softmax(scores, mask):
    scores = scores.astype(jnp.float32)
    scores = jnp.where(mask, scores, NEG_INF)
    m = jnp.max(scores, -1, keepdims=True)
    e = jnp.exp(scores - jax.lax.stop_gradient(m))
    z = jnp.sum(e, -1, keepdims=True)
    return e / jnp.maximum(z, 1e-30)


# ------------------------------------------------------------- full-seq


def attn_apply(
    p,
    cfg: ModelConfig,
    x,
    positions,
    *,
    kind: str = "attn",
    causal: bool = True,
    kv_override=None,
    q_chunk: int = 1024,
    bidir_prefix: int = 0,
    allow_kernel: bool = False,
):
    """Full-sequence attention.

    kind: "attn" (global) or "attn_local" (sliding window of
    ``cfg.sliding_window``).  ``kv_override=(k, v, kv_positions)`` switches
    to cross-attention (no causal mask, no RoPE on kv side here).
    ``bidir_prefix``: first N positions attend bidirectionally (PaliGemma
    prefix-LM: image patches + prompt are non-causal).
    """
    hd = cfg.resolved_head_dim
    rope = cfg.pos_kind == "rope"
    if kv_override is None:
        q, k, v = _project_qkv(p, cfg, x, positions, rope, kind)
        kv_pos = positions
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
        if cfg.qkv_bias:
            q = q + p["bq"]
        k, v, kv_pos = kv_override
        causal = False
    scale = 1.0 / math.sqrt(hd)
    window = cfg.sliding_window if kind == "attn_local" else 0

    b, s, h, _ = q.shape

    # TPU path: the Pallas flash kernel (kernels/flash_attention.py).
    # Conditions: INFERENCE only (``allow_kernel`` — the kernel has no
    # custom VJP, so the training path keeps the differentiable q-chunked
    # jnp formulation), standard contiguous positions (arange), no
    # prefix-LM bidirectional region, self-attention.
    if (allow_kernel and kv_override is None and not bidir_prefix
            and jnp.ndim(positions) == 1 and _kernels_on()):
        from repro.kernels import ops as kernel_ops

        out = kernel_ops.flash_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal=causal, window=window)
        out = shard(out.transpose(0, 2, 1, 3),
                    "batch", "seq", "heads", "head_dim")
        y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
        return shard(y, "batch", "seq", "d_model")

    q_chunk = _Q_CHUNK_OVERRIDE.get() or q_chunk
    nchunk = max(1, -(-s // q_chunk))
    q_chunk = -(-s // nchunk)
    pad = nchunk * q_chunk - s
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        qpos_full = jnp.pad(_bcast_pos(positions, b, s), ((0, 0), (0, pad)), constant_values=-1)
    else:
        qpos_full = _bcast_pos(positions, b, s)
    kpos = _bcast_pos(kv_pos, b, k.shape[1])

    qc = q.reshape(b, nchunk, q_chunk, h, hd).transpose(1, 0, 2, 3, 4)
    pc = qpos_full.reshape(b, nchunk, q_chunk).transpose(1, 0, 2)
    k_rep = _repeat_kv(k, h)
    v_rep = _repeat_kv(v, h)

    @jax.checkpoint
    def chunk_body(carry, xs):
        qi, pi = xs  # (B, qc, H, hd), (B, qc)
        # score spec includes the q-seq axis: under the "sp" rules it picks
        # up the model axis whenever the head count doesn't divide it.
        sc = _scores(qi, k_rep,
                     spec=("batch", "heads", "seq", None)) * scale
        kp = kpos[:, None, None, :]
        qp = pi[:, None, :, None]
        if causal:
            mask = (qp >= kp) & (kp >= 0)
            if bidir_prefix:
                mask = mask | ((kp >= 0) & (kp < bidir_prefix) & (qp >= 0))
        else:
            mask = kp >= 0
        if window:
            mask = mask & (qp - kp < window)
        probs = _softmax(sc, mask).astype(v.dtype)
        out = _attn_out(probs, v_rep)                # (B, qc, H, hd)
        return carry, out

    _, outs = jax.lax.scan(chunk_body, 0, (qc, pc))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(b, nchunk * q_chunk, h, hd)
    if pad:
        out = out[:, :s]
    out = shard(out, "batch", "seq", "heads", "head_dim")
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return shard(y, "batch", "seq", "d_model")


def _bcast_pos(positions, b, s):
    positions = jnp.asarray(positions, jnp.int32)
    if positions.ndim == 1:
        positions = jnp.broadcast_to(positions[None, :], (b, s))
    return positions


# ------------------------------------------------------------- caching


def cache_len_for(cfg: ModelConfig, kind: str, max_len: int) -> int:
    if kind == "attn_local" and cfg.sliding_window:
        return min(cfg.sliding_window, max_len)
    return max_len


def _quantize_kv(t):
    """(.., hd) bf16/f32 -> (int8 values, per-row absmax scale)."""
    a = jnp.max(jnp.abs(t.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(a, 1e-8) / 127.0
    q = jnp.clip(jnp.round(t.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale[..., 0].astype(jnp.float32)


def _dequantize_kv(q, scale, dtype):
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def init_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int, dtype):
    c = cache_len_for(cfg, kind, max_len)
    hd = cfg.resolved_head_dim
    kv = cfg.num_kv_heads
    cache = {"pos": jnp.full((batch, c), -1, jnp.int32)}
    if cfg.kv_cache_quant:
        cache.update({
            "k": jnp.zeros((batch, c, kv, hd), jnp.int8),
            "v": jnp.zeros((batch, c, kv, hd), jnp.int8),
            "k_s": jnp.zeros((batch, c, kv), jnp.float32),
            "v_s": jnp.zeros((batch, c, kv), jnp.float32),
        })
    else:
        cache.update({
            "k": jnp.zeros((batch, c, kv, hd), dtype),
            "v": jnp.zeros((batch, c, kv, hd), dtype),
        })
    return cache


def abstract_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int, dtype):
    concrete = jax.eval_shape(
        lambda: init_cache(cfg, kind, batch, max_len, jnp.dtype(dtype)))
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), concrete)


CACHE_LOGICAL = {
    "k": ("batch", "kv_len", "kv_heads", "head_dim"),
    "v": ("batch", "kv_len", "kv_heads", "head_dim"),
    "k_s": ("batch", "kv_len", "kv_heads"),
    "v_s": ("batch", "kv_len", "kv_heads"),
    "pos": ("batch", "kv_len"),
}


def cache_logical_for(cfg: ModelConfig):
    names = ["k", "v", "pos"] + (["k_s", "v_s"] if cfg.kv_cache_quant else [])
    return {k: CACHE_LOGICAL[k] for k in names}


def attn_prefill(p, cfg: ModelConfig, x, positions, *, kind: str,
                 cache_max: int, bidir_prefix: int = 0):
    """Full forward + cache construction.  Returns (y, cache)."""
    rope = cfg.pos_kind == "rope"
    b, s, _ = x.shape
    y = attn_apply(p, cfg, x, positions, kind=kind, bidir_prefix=bidir_prefix,
                   allow_kernel=True)
    # Rebuild k/v for the cache (cheap relative to attention itself; keeps
    # attn_apply free of cache plumbing).
    _, k, v = _project_qkv(p, cfg, x, positions, rope, kind)
    clen = cache_len_for(cfg, kind, cache_max)
    kpos = _bcast_pos(positions, b, s)
    entries = {"k": k, "v": v, "pos": kpos}
    if cfg.kv_cache_quant:
        entries["k"], entries["k_s"] = _quantize_kv(k)
        entries["v"], entries["v_s"] = _quantize_kv(v)
    cache = init_cache(cfg, kind, b, cache_max, k.dtype)
    if s >= clen:
        # keep the last ``clen`` positions; ring-align: slot j must hold
        # position with pos % clen == j — element i holds position take+i,
        # so it belongs at (take+i) % clen.
        take = s - clen
        roll = (take % clen) if clen else 0
        cache = {kk: jnp.roll(vv[:, take:], roll, axis=1)
                 for kk, vv in entries.items()}
    else:
        for kk, vv in entries.items():
            cache[kk] = jax.lax.dynamic_update_slice_in_dim(
                cache[kk], vv.astype(cache[kk].dtype), 0, 1)
    cache = {kk: shard(vv, *CACHE_LOGICAL[kk]) for kk, vv in cache.items()}
    return y, cache


def _decode_attn_read(p, cfg: ModelConfig, q, cache_k, cache_v, kpos, pos,
                      *, kind: str):
    """Masked one-token attention over an assembled cache view — the
    shared read tail of slot (contiguous) and paged (gathered) decode.
    q (B,1,H,hd), cache_k/v (B,L,KV,hd), kpos (B,L) absolute positions
    (-1 = invalid lane).  Returns y (B,1,D)."""
    hd = cfg.resolved_head_dim
    h = q.shape[2]
    scale = 1.0 / math.sqrt(hd)
    k_rep = _repeat_kv(cache_k, h, seq_name="kv_len")
    v_rep = _repeat_kv(cache_v, h, seq_name="kv_len")
    sc = _scores(q, k_rep, spec=("batch", None, None, "kv_len")) * scale
    kp = kpos[:, None, None, :]
    mask = (kp >= 0) & (kp <= pos[:, None, None, None])
    if kind == "attn_local" and cfg.sliding_window:
        mask = mask & (pos[:, None, None, None] - kp < cfg.sliding_window)
    probs = _softmax(sc, mask).astype(cache_v.dtype)
    out = _attn_out(probs, v_rep)              # (B,1,H,hd)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return shard(y, "batch", "seq", "d_model")


def attn_decode(p, cfg: ModelConfig, x, cache, pos, *, kind: str):
    """One-token decode.  x (B,1,D), pos (B,) absolute position of the new
    token.  Returns (y (B,1,D), new_cache)."""
    rope = cfg.pos_kind == "rope"
    q, k_new, v_new = _project_qkv(p, cfg, x, pos[:, None], rope, kind)
    clen = cache["k"].shape[1]
    slot = (pos % clen).astype(jnp.int32)

    def write(buf, new, slot1):
        return jax.lax.dynamic_update_slice_in_dim(
            buf, new.astype(buf.dtype), slot1, axis=0)

    new_cache = {}
    if cfg.kv_cache_quant:
        kq, ks = _quantize_kv(k_new)
        vq, vs = _quantize_kv(v_new)
        cache_kq = jax.vmap(write)(cache["k"], kq, slot)
        cache_vq = jax.vmap(write)(cache["v"], vq, slot)
        new_cache["k_s"] = jax.vmap(write)(cache["k_s"], ks, slot)
        new_cache["v_s"] = jax.vmap(write)(cache["v_s"], vs, slot)
        new_cache["k"], new_cache["v"] = cache_kq, cache_vq
        cache_k = _dequantize_kv(cache_kq, new_cache["k_s"], k_new.dtype)
        cache_v = _dequantize_kv(cache_vq, new_cache["v_s"], v_new.dtype)
    else:
        cache_k = jax.vmap(write)(cache["k"], k_new, slot)
        cache_v = jax.vmap(write)(cache["v"], v_new, slot)
        new_cache["k"], new_cache["v"] = cache_k, cache_v
    cache_pos = jax.vmap(write)(cache["pos"], pos[:, None], slot)
    new_cache["pos"] = cache_pos

    y = _decode_attn_read(p, cfg, q, cache_k, cache_v, cache_pos, pos,
                          kind=kind)
    new_cache = {kk: shard(vv, *CACHE_LOGICAL[kk])
                 for kk, vv in new_cache.items()}
    return y, new_cache


# ------------------------------------------------------------- paged cache
# Block-paged decode (the vLLM mechanism, XLA-shaped): one preallocated
# pool of fixed-size token blocks per layer, shared by every request.  A
# request's cache is a *block table* — a row of physical block ids — so
# short requests stop paying for ``cache_max``-length strips and the
# engine admits as many requests as free blocks allow.
#
# The pool for one layer reuses the batched-cache layout with
# ``batch -> num_blocks`` and ``kv_len -> block_size``:
#     {"k": (NB, bs, KV, hd), "v": (NB, bs, KV, hd), "pos": (NB, bs)}
# Physical block 0 is reserved as a permanently-invalid NULL block: its
# ``pos`` lanes stay -1 forever and block tables pad with 0, so gathers
# through padding can never win the attention mask.


def paged_pool_init(cfg: ModelConfig, num_blocks: int, block_size: int,
                    dtype):
    """Concrete zero pool for one attention layer (pos lanes -1)."""
    return init_cache(cfg, "attn", num_blocks, block_size, dtype)


def attn_decode_paged(p, cfg: ModelConfig, x, pool, block_table, pos, active,
                      *, kind: str, decode_kernel: Optional[bool] = None):
    """One-token decode against a block-paged KV pool.

    x (B,1,D); ``pool`` is the *shared* layer pool (leaves lead with the
    physical-block axis); ``block_table`` (B, nb) int32 maps each
    request's logical blocks to physical ids (0-padded); ``pos`` (B,)
    absolute position of the new token; ``active`` (B,) bool — inactive
    rows write ``pos = -1`` into the null block so their lanes never
    validate.  Returns (y (B,1,D), new_pool).

    ``decode_kernel`` selects the attention read: True routes through the
    Pallas paged-attention kernel (``kernels/paged_attention.py`` —
    block-table-indexed loads, online softmax, no materialized gather),
    False keeps the jnp block-gather below (the parity reference), None
    follows ``_kernels_on()``.  Quantized pools always take the jnp path
    (the kernel reads raw K/V lanes).  Inactive rows differ harmlessly
    between the two (kernel: zeros; gather: uniform-prob garbage) — both
    are discarded by the engine.
    """
    rope = cfg.pos_kind == "rope"
    hd = cfg.resolved_head_dim
    q, k_new, v_new = _project_qkv(p, cfg, x, pos[:, None], rope, kind)
    bs = pool["pos"].shape[1]
    b, nb = block_table.shape

    # scatter the new token's kv into (physical block, in-block offset).
    # Active rows own disjoint blocks so their writes never collide;
    # inactive rows all target the null block and write pos=-1 (their k/v
    # payloads race, but a -1 lane is masked regardless of payload).
    logical = (pos // bs).astype(jnp.int32)
    phys = jnp.take_along_axis(block_table, logical[:, None], axis=1)[:, 0]
    off = (pos % bs).astype(jnp.int32)
    pos_val = jnp.where(active, pos.astype(jnp.int32), -1)

    new_pool = {}
    if cfg.kv_cache_quant:
        kq, ks = _quantize_kv(k_new)
        vq, vs = _quantize_kv(v_new)
        new_pool["k"] = pool["k"].at[phys, off].set(kq[:, 0])
        new_pool["v"] = pool["v"].at[phys, off].set(vq[:, 0])
        new_pool["k_s"] = pool["k_s"].at[phys, off].set(ks[:, 0])
        new_pool["v_s"] = pool["v_s"].at[phys, off].set(vs[:, 0])
    else:
        new_pool["k"] = pool["k"].at[phys, off].set(
            k_new[:, 0].astype(pool["k"].dtype))
        new_pool["v"] = pool["v"].at[phys, off].set(
            v_new[:, 0].astype(pool["v"].dtype))
    new_pool["pos"] = pool["pos"].at[phys, off].set(pos_val)

    use_kernel = _kernels_on() if decode_kernel is None else bool(decode_kernel)
    if use_kernel and not cfg.kv_cache_quant:
        from repro.kernels import ops as kernel_ops

        window = cfg.sliding_window if kind == "attn_local" else 0
        out = kernel_ops.paged_attention(
            q[:, 0], new_pool["k"], new_pool["v"], new_pool["pos"],
            block_table, pos.astype(jnp.int32), window=window)
        y = jnp.einsum("bhk,hkd->bd", out, p["wo"])[:, None]
        return shard(y, "batch", "seq", "d_model"), new_pool

    # gather-based read: (B, nb, bs, ...) -> (B, nb*bs, ...) logical view
    kv = cfg.num_kv_heads
    if cfg.kv_cache_quant:
        cache_k = _dequantize_kv(new_pool["k"][block_table],
                                 new_pool["k_s"][block_table], k_new.dtype)
        cache_v = _dequantize_kv(new_pool["v"][block_table],
                                 new_pool["v_s"][block_table], v_new.dtype)
    else:
        cache_k = new_pool["k"][block_table]
        cache_v = new_pool["v"][block_table]
    cache_k = cache_k.reshape(b, nb * bs, kv, hd)
    cache_v = cache_v.reshape(b, nb * bs, kv, hd)
    kpos = new_pool["pos"][block_table].reshape(b, nb * bs)

    y = _decode_attn_read(p, cfg, q, cache_k, cache_v, kpos, pos, kind=kind)
    return y, new_pool


def attn_prefill_paged(p, cfg: ModelConfig, x, positions, pool, block_table,
                       start_pos, *, kind: str = "attn", cache_max: int,
                       seq_len=None):
    """Padding-masked position-offset prefill against a block-paged pool
    — the ONE paged prefill entry point (fresh prompts, preempt-resume,
    and prefix-cache suffixes all route here).

    ``kind`` selects global ("attn") vs sliding-window ("attn_local")
    masking; window layers add the band term ``qpos - kpos <
    cfg.sliding_window`` over absolute positions and RoPE with the local
    base (``_theta_for``), matching ``attn_apply``/``attn_decode_paged``
    so chunked paged prefill stays token-identical to the slot path.

    x (B,S,D) holds a ragged batch of uncached suffix *chunks* — one
    row per request, each row's first token at absolute position
    ``start_pos`` (scalar, or (B,) for per-row offsets under continuous
    batching); ``positions`` are the absolute positions ``start_pos +
    [0..S)``, shaped (S,) for a scalar offset or (B,S) per row.  For a
    fresh prompt ``start_pos`` is 0 and ``block_table`` is all null
    blocks (every pool lane masked), which degenerates to a plain causal
    prefill.  The prefix KV — earlier chunks of the same prompt, blocks
    matched from the prefix cache, or both — is read from ``pool``
    through ``block_table`` (B, nb).  Pool lanes at positions ``>=
    start_pos`` (per row) are treated as invalid, as are ``pos = -1``
    lanes: that one guard covers both a COW copy's diverged donor tail
    and a chunked prefill's not-yet-written own-block lanes, since a
    row's pool can only hold valid entries below its chunk cursor.

    ``seq_len`` (B,) int32 is the *valid* suffix length when ``x`` is
    right-padded up to a length bucket (None = all S tokens valid).
    Padded lanes are masked as keys (their cache ``pos`` is written -1,
    so the engine's splice invalidates rather than publishes them) and
    their query rows produce garbage that the caller discards — this is
    what lets the engine compile O(#buckets) prefill variants instead of
    O(#distinct suffix lengths).

    Returns (y (B,S,D), suffix cache sized ``cache_max``) — the cache
    has the same layout as ``attn_prefill``'s, holding only the valid
    suffix entries (absolute ``pos`` lanes), for the engine to splice
    into the suffix's physical blocks via ``write_prefill_blocks``.
    """
    rope = cfg.pos_kind == "rope"
    hd = cfg.resolved_head_dim
    kv = cfg.num_kv_heads
    b, s, _ = x.shape
    window = cfg.sliding_window if kind == "attn_local" else 0
    q, k, v = _project_qkv(p, cfg, x, positions, rope, kind)

    bs = pool["pos"].shape[-1]
    nb = block_table.shape[1]
    if cfg.kv_cache_quant:
        pk = _dequantize_kv(pool["k"][block_table], pool["k_s"][block_table],
                            k.dtype)
        pv = _dequantize_kv(pool["v"][block_table], pool["v_s"][block_table],
                            v.dtype)
    else:
        pk = pool["k"][block_table].astype(k.dtype)
        pv = pool["v"][block_table].astype(v.dtype)
    pk = pk.reshape(b, nb * bs, kv, hd)
    pv = pv.reshape(b, nb * bs, kv, hd)
    ppos = pool["pos"][block_table].reshape(b, nb * bs)
    # per-row cursor guard: lanes at/past the row's start are invalid
    # (diverged COW tails AND own-block lanes a later chunk will write)
    sp = jnp.expand_dims(jnp.asarray(start_pos, jnp.int32), -1)  # (B,1)|(1,)
    ppos = jnp.where(ppos < sp, ppos, -1)

    qpos = _bcast_pos(positions, b, s)             # (B,S) absolute
    if seq_len is not None:
        lane_valid = jnp.arange(s, dtype=jnp.int32)[None, :] < \
            jnp.asarray(seq_len, jnp.int32)[:, None]
        kpos_suffix = jnp.where(lane_valid, qpos, -1)   # pad keys never win
    else:
        kpos_suffix = qpos
    k_all = jnp.concatenate([pk, k], axis=1)
    v_all = jnp.concatenate([pv, v], axis=1)
    kpos_all = jnp.concatenate([ppos, kpos_suffix], axis=1)

    from repro.kernels import ops as kernel_ops

    out = kernel_ops.paged_prefill(q, k_all, v_all, kpos_all, qpos,
                                   window=window)
    out = shard(out, "batch", "seq", "heads", "head_dim")
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    y = shard(y, "batch", "seq", "d_model")

    # suffix cache, same construction as attn_prefill's short-seq branch
    entries = {"k": k, "v": v, "pos": kpos_suffix}
    if cfg.kv_cache_quant:
        entries["k"], entries["k_s"] = _quantize_kv(k)
        entries["v"], entries["v_s"] = _quantize_kv(v)
    cache = init_cache(cfg, "attn", b, cache_max, k.dtype)
    for kk, vv in entries.items():
        cache[kk] = jax.lax.dynamic_update_slice_in_dim(
            cache[kk], vv.astype(cache[kk].dtype), 0, 1)
    cache = {kk: shard(vv, *CACHE_LOGICAL[kk]) for kk, vv in cache.items()}
    return y, cache


# ------------------------------------------------------------- cross-attn
# Whisper decoder cross-attention over encoder output.  The encoder k/v are
# computed once (at prefill) and stored in the cache under "xk"/"xv".


def cross_kv(p, enc_out):
    k = jnp.einsum("bsd,dhk->bshk", enc_out, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", enc_out, p["wv"])
    return k, v


def cross_apply(p, cfg: ModelConfig, x, k, v):
    """Cross-attention with precomputed encoder k/v (no mask, no RoPE)."""
    hd = cfg.resolved_head_dim
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if cfg.qkv_bias:
        q = q + p["bq"]
    h = q.shape[2]
    k_rep = _repeat_kv(k, h, seq_name="frames")
    v_rep = _repeat_kv(v, h, seq_name="frames")
    sc = _scores(q, k_rep) / math.sqrt(hd)
    mask = jnp.ones(sc.shape, bool)
    probs = _softmax(sc, mask).astype(v.dtype)
    out = _attn_out(probs, v_rep)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])

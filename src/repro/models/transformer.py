"""Layer blocks + period-scanned stacks.

Heterogeneous architectures (gemma3's 5:1 local:global, jamba's 1:7
attn:mamba with MoE every 2nd layer) are handled by scanning over *periods*:

    P = lcm(len(cfg.layer_kinds), cfg.moe_every)

Every period has the same per-slot structure (kind_j, moe_j for j < P), so
parameters stack to ``(n_periods, ...)`` leaves and the whole depth lowers
as ONE ``lax.scan`` whose body applies P blocks — the HLO stays O(P) in
size regardless of depth (80-layer configs compile in seconds under 512
SPMD partitions).  Layers that don't fill a whole period ("remainder") are
applied unrolled after the scan.

Caches/states follow the same layout: ``{"periods": {"slot{j}": stacked
cache}, "rem": {"layer{i}": cache}}`` — the decode step scans over periods
with the per-slot cache as scan xs/ys.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import rwkv6 as rwkv_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import mlp_apply, mlp_schema, norm_apply, norm_schema
from repro.models.module import ParamSpec, stack_specs
from repro.models.sharding import shard


# ------------------------------------------------------------------ periods


def period_len(cfg: ModelConfig) -> int:
    k = len(cfg.layer_kinds)
    m = cfg.moe_every if cfg.num_experts else 1
    return math.lcm(k, m)


def layout(cfg: ModelConfig) -> Tuple[int, int, int]:
    """-> (P, n_periods, n_rem)."""
    p = period_len(cfg)
    return p, cfg.num_layers // p, cfg.num_layers % p


def slot_sig(cfg: ModelConfig, j: int) -> Tuple[str, bool]:
    """(kind, is_moe) for in-period slot j (== for absolute layer j)."""
    kind = cfg.layer_kinds[j % len(cfg.layer_kinds)]
    return kind, cfg.layer_is_moe(j)


def signatures(cfg: ModelConfig) -> Dict[Tuple[str, bool], int]:
    """Unique layer signatures -> count over the whole stack (for the
    compositional roofline).  Enc-dec (whisper): decoder layers count
    twice (self + cross attention, same arithmetic shape) plus the
    encoder stack — a documented approximation for the one 37M-param
    audio config."""
    if cfg.is_encoder_decoder:
        return {("attn", False): 2 * cfg.num_layers + cfg.encoder_layers}
    out: Dict[Tuple[str, bool], int] = {}
    for i in range(cfg.num_layers):
        sig = slot_sig(cfg, i)
        out[sig] = out.get(sig, 0) + 1
    return out


# ------------------------------------------------------------------ blocks


def block_schema(cfg: ModelConfig, kind: str, moe: bool):
    s: Dict[str, Any] = {"norm1": norm_schema(cfg), "norm2": norm_schema(cfg)}
    if kind in ("attn", "attn_local"):
        s["mix"] = attn.attn_schema(cfg)
    elif kind == "mamba":
        s["mix"] = ssm_mod.ssm_schema(cfg)
    elif kind == "rwkv6":
        s["mix"] = rwkv_mod.rwkv_schema(cfg)
        s["ffn"] = rwkv_mod.channel_mix_schema(cfg)
        return s
    else:
        raise ValueError(kind)
    s["ffn"] = moe_mod.moe_schema(cfg) if moe else mlp_schema(cfg)
    return s


def _ffn(p, cfg: ModelConfig, x, moe: bool):
    if moe:
        return moe_mod.moe_apply(p["ffn"], cfg, x)
    return mlp_apply(p["ffn"], x, cfg.mlp_kind), jnp.float32(0.0)


def block_apply(p, cfg: ModelConfig, x, positions, *, kind: str, moe: bool,
                bidir_prefix: int = 0):
    """Train/eval forward (no cache).  -> (x, aux_loss)."""
    h = norm_apply(p["norm1"], x, cfg.norm_kind, cfg.norm_eps)
    if kind in ("attn", "attn_local"):
        y = attn.attn_apply(p["mix"], cfg, h, positions, kind=kind,
                            bidir_prefix=bidir_prefix)
    elif kind == "mamba":
        y = ssm_mod.ssm_apply(p["mix"], cfg, h)
    else:  # rwkv6
        y, _ = rwkv_mod.rwkv_time_mix(
            p["mix"], cfg, h, rwkv_mod.init_state(cfg, x.shape[0], x.dtype))
        x = x + y
        h2 = norm_apply(p["norm2"], x, cfg.norm_kind, cfg.norm_eps)
        y2, _ = rwkv_mod.channel_mix(
            p["ffn"], cfg, h2, jnp.zeros(h2.shape[:1] + h2.shape[2:], h2.dtype))
        return x + y2, jnp.float32(0.0)
    x = x + y
    h = norm_apply(p["norm2"], x, cfg.norm_kind, cfg.norm_eps)
    y, aux = _ffn(p, cfg, h, moe)
    return x + y, aux


def block_prefill(p, cfg: ModelConfig, x, positions, *, kind: str, moe: bool,
                  cache_max: int, bidir_prefix: int = 0):
    """Forward + build the decode cache.  -> (x, aux, cache)."""
    h = norm_apply(p["norm1"], x, cfg.norm_kind, cfg.norm_eps)
    if kind in ("attn", "attn_local"):
        y, cache = attn.attn_prefill(p["mix"], cfg, h, positions, kind=kind,
                                     cache_max=cache_max,
                                     bidir_prefix=bidir_prefix)
    elif kind == "mamba":
        y, cache = ssm_mod.ssm_forward(p["mix"], cfg, h)
    else:  # rwkv6
        st = rwkv_mod.init_state(cfg, x.shape[0], x.dtype)
        y, part = rwkv_mod.rwkv_time_mix(p["mix"], cfg, h, st)
        x = x + y
        h2 = norm_apply(p["norm2"], x, cfg.norm_kind, cfg.norm_eps)
        y2, x_cm = rwkv_mod.channel_mix(p["ffn"], cfg, h2, st["x_cm"])
        cache = {"s": part["s"], "x_tm": part["x_tm"], "x_cm": x_cm}
        return x + y2, jnp.float32(0.0), cache
    x = x + y
    h = norm_apply(p["norm2"], x, cfg.norm_kind, cfg.norm_eps)
    y, aux = _ffn(p, cfg, h, moe)
    return x + y, aux, cache


def block_decode(p, cfg: ModelConfig, x, cache, pos, *, kind: str, moe: bool):
    """One-token step.  x (B,1,D), pos (B,).  -> (x, new_cache)."""
    h = norm_apply(p["norm1"], x, cfg.norm_kind, cfg.norm_eps)
    if kind in ("attn", "attn_local"):
        y, cache = attn.attn_decode(p["mix"], cfg, h, cache, pos, kind=kind)
    elif kind == "mamba":
        y, cache = ssm_mod.ssm_decode(p["mix"], cfg, h, cache)
    else:  # rwkv6
        y, part = rwkv_mod.rwkv_time_mix(p["mix"], cfg, h, cache)
        x = x + y
        h2 = norm_apply(p["norm2"], x, cfg.norm_kind, cfg.norm_eps)
        y2, x_cm = rwkv_mod.channel_mix(p["ffn"], cfg, h2, cache["x_cm"])
        return x + y2, {"s": part["s"], "x_tm": part["x_tm"], "x_cm": x_cm}
    x = x + y
    h = norm_apply(p["norm2"], x, cfg.norm_kind, cfg.norm_eps)
    y, _ = _ffn(p, cfg, h, moe)
    return x + y, cache


def block_cache_abstract(cfg: ModelConfig, kind: str, batch: int,
                         cache_max: int, dtype):
    if kind in ("attn", "attn_local"):
        return attn.abstract_cache(cfg, kind, batch, cache_max, dtype)
    if kind == "mamba":
        return ssm_mod.abstract_state(cfg, batch, dtype)
    return rwkv_mod.abstract_state(cfg, batch, dtype)


def block_cache_logical(cfg: ModelConfig, kind: str):
    if kind in ("attn", "attn_local"):
        return attn.cache_logical_for(cfg)
    if kind == "mamba":
        return dict(ssm_mod.STATE_LOGICAL)
    return dict(rwkv_mod.STATE_LOGICAL)


# ------------------------------------------------------------------ stack


def stack_schema(cfg: ModelConfig):
    p, n_per, n_rem = layout(cfg)
    periods = {
        f"slot{j}": block_schema(cfg, *slot_sig(cfg, j)) for j in range(p)
    }
    s: Dict[str, Any] = {"periods": stack_specs(periods, n_per) if n_per else {}}
    s["rem"] = {
        f"layer{j}": block_schema(cfg, *slot_sig(cfg, n_per * p + j))
        for j in range(n_rem)
    }
    return s


def _remat(fn, enable: bool):
    """Full recompute per scanned period: the scan carry (one (B,S,D)
    residual per layer) is the only thing saved.  At 1M tokens x d=8192
    the dots_with_no_batch_dims policy saved ~290 GB/device of MLP/attn
    intermediates (measured, EXPERIMENTS.md §Dry-run) — recompute is the
    only policy that fits the 100B+ configs at 16 GB/chip."""
    if not enable:
        return fn
    return jax.checkpoint(fn)


def stack_apply(params, cfg: ModelConfig, x, positions, *,
                bidir_prefix: int = 0, remat: bool = True):
    """Full-stack forward.  -> (x, total_aux)."""
    p, n_per, n_rem = layout(cfg)

    def body(carry, period_params):
        x, aux = carry
        for j in range(p):
            kind, moe = slot_sig(cfg, j)
            x, a = block_apply(period_params[f"slot{j}"], cfg, x, positions,
                               kind=kind, moe=moe, bidir_prefix=bidir_prefix)
            aux = aux + a
        return (x, aux), None

    body = _remat(body, remat)
    aux0 = jnp.float32(0.0)
    if n_per:
        (x, aux0), _ = jax.lax.scan(body, (x, aux0), params["periods"])
    for j in range(n_rem):
        kind, moe = slot_sig(cfg, n_per * p + j)
        x, a = block_apply(params["rem"][f"layer{j}"], cfg, x, positions,
                           kind=kind, moe=moe, bidir_prefix=bidir_prefix)
        aux0 = aux0 + a
    return x, aux0


def stack_prefill(params, cfg: ModelConfig, x, positions, *, cache_max: int,
                  bidir_prefix: int = 0):
    """-> (x, aux, caches)."""
    p, n_per, n_rem = layout(cfg)

    def body(carry, period_params):
        x, aux = carry
        caches = {}
        for j in range(p):
            kind, moe = slot_sig(cfg, j)
            x, a, c = block_prefill(period_params[f"slot{j}"], cfg, x,
                                    positions, kind=kind, moe=moe,
                                    cache_max=cache_max,
                                    bidir_prefix=bidir_prefix)
            caches[f"slot{j}"] = c
            aux = aux + a
        return (x, aux), caches

    aux = jnp.float32(0.0)
    period_caches = {}
    if n_per:
        (x, aux), period_caches = jax.lax.scan(body, (x, aux), params["periods"])
    rem_caches = {}
    for j in range(n_rem):
        kind, moe = slot_sig(cfg, n_per * p + j)
        x, a, c = block_prefill(params["rem"][f"layer{j}"], cfg, x, positions,
                                kind=kind, moe=moe, cache_max=cache_max,
                                bidir_prefix=bidir_prefix)
        rem_caches[f"layer{j}"] = c
        aux = aux + a
    return x, aux, {"periods": period_caches, "rem": rem_caches}


def stack_decode(params, cfg: ModelConfig, x, caches, pos):
    """-> (x, new_caches)."""
    p, n_per, n_rem = layout(cfg)

    def body(x, xs):
        period_params, period_caches = xs
        new = {}
        for j in range(p):
            kind, moe = slot_sig(cfg, j)
            x, c = block_decode(period_params[f"slot{j}"], cfg, x,
                                period_caches[f"slot{j}"], pos,
                                kind=kind, moe=moe)
            new[f"slot{j}"] = c
        return x, new

    new_period_caches = {}
    if n_per:
        x, new_period_caches = jax.lax.scan(
            body, x, (params["periods"], caches["periods"]))
    new_rem = {}
    for j in range(n_rem):
        kind, moe = slot_sig(cfg, n_per * p + j)
        x, c = block_decode(params["rem"][f"layer{j}"], cfg, x,
                            caches["rem"][f"layer{j}"], pos,
                            kind=kind, moe=moe)
        new_rem[f"layer{j}"] = c
    return x, {"periods": new_period_caches, "rem": new_rem}


def _state_read(pool, rows, start_pos, batch):
    """Gather per-request recurrent-state slots from a state pool.

    ``pool`` leaves lead with the slot axis (state_batch rows); ``rows``
    (B,) int32 maps each dispatch row to its slot.  Rows whose chunk
    starts at absolute position 0 read zeros instead of the slot — that
    covers fresh admissions AND preempt-resume re-prefills without any
    host-side slot reset (the stale slot contents are simply never
    observed)."""
    sp = jnp.broadcast_to(jnp.asarray(start_pos, jnp.int32), (batch,))
    fresh = sp == 0

    def read(leaf):
        v = leaf[rows]
        m = fresh.reshape((batch,) + (1,) * (v.ndim - 1))
        return jnp.where(m, jnp.zeros_like(v), v)

    return {k: read(v) for k, v in pool.items()}


def _recurrent_fwd(p, cfg: ModelConfig, x, st, *, kind: str, moe: bool,
                   seq_len=None):
    """Shared mamba/rwkv6 block body over an explicit state dict.
    -> (x, new_state)."""
    h = norm_apply(p["norm1"], x, cfg.norm_kind, cfg.norm_eps)
    if kind == "mamba":
        y, new_st = ssm_mod.ssm_forward(p["mix"], cfg, h, st, seq_len=seq_len)
        x = x + y
        h = norm_apply(p["norm2"], x, cfg.norm_kind, cfg.norm_eps)
        y, _ = _ffn(p, cfg, h, moe)
        return x + y, new_st
    # rwkv6: two-norm structure — channel-mix replaces the FFN
    y, part = rwkv_mod.rwkv_time_mix(p["mix"], cfg, h, st, seq_len=seq_len)
    x = x + y
    h2 = norm_apply(p["norm2"], x, cfg.norm_kind, cfg.norm_eps)
    y2, x_cm = rwkv_mod.channel_mix(p["ffn"], cfg, h2, st["x_cm"],
                                    seq_len=seq_len)
    return x + y2, {"s": part["s"], "x_tm": part["x_tm"], "x_cm": x_cm}


def block_decode_paged(p, cfg: ModelConfig, x, pool, block_table, pos, active,
                       *, kind: str, moe: bool, decode_kernel=None):
    """One-token step against a block-paged pool.  Attention layers read
    the block-paged KV pool; mamba/rwkv6 layers read per-request state
    slots (row i of the dispatch IS slot i — the state pool just carries
    one extra trash row for padded prefill dispatches).  ``decode_kernel``:
    Pallas kernel vs jnp gather (attn_decode_paged)."""
    if kind in ("attn", "attn_local"):
        h = norm_apply(p["norm1"], x, cfg.norm_kind, cfg.norm_eps)
        y, pool = attn.attn_decode_paged(p["mix"], cfg, h, pool, block_table,
                                         pos, active, kind=kind,
                                         decode_kernel=decode_kernel)
        x = x + y
        h = norm_apply(p["norm2"], x, cfg.norm_kind, cfg.norm_eps)
        y, _ = _ffn(p, cfg, h, moe)
        return x + y, pool
    if kind not in ("mamba", "rwkv6"):
        raise ValueError(f"paged decode: unsupported layer kind {kind!r}")
    b = x.shape[0]
    st = {k: v[:b] for k, v in pool.items()}
    out, new_st = _recurrent_fwd(p, cfg, x, st, kind=kind, moe=moe)

    def upd(leaf, old, new):
        keep = active.reshape((b,) + (1,) * (new.ndim - 1))
        return leaf.at[:b].set(jnp.where(keep, new, old).astype(leaf.dtype))

    pool = {k: upd(pool[k], st[k], new_st[k]) for k in pool}
    return out, pool


def stack_decode_paged(params, cfg: ModelConfig, x, pools, block_table, pos,
                       active, decode_kernel=None):
    """-> (x, new_pools).  Same period scan as ``stack_decode``; the block
    table is shared by every layer (one allocation per request covers the
    whole stack — each layer owns its own physical pool, indexed by the
    same table)."""
    p, n_per, n_rem = layout(cfg)

    def body(x, xs):
        period_params, period_pools = xs
        new = {}
        for j in range(p):
            kind, moe = slot_sig(cfg, j)
            x, c = block_decode_paged(period_params[f"slot{j}"], cfg, x,
                                      period_pools[f"slot{j}"], block_table,
                                      pos, active, kind=kind, moe=moe,
                                      decode_kernel=decode_kernel)
            new[f"slot{j}"] = c
        return x, new

    new_period_pools = {}
    if n_per:
        x, new_period_pools = jax.lax.scan(
            body, x, (params["periods"], pools["periods"]))
    new_rem = {}
    for j in range(n_rem):
        kind, moe = slot_sig(cfg, n_per * p + j)
        x, c = block_decode_paged(params["rem"][f"layer{j}"], cfg, x,
                                  pools["rem"][f"layer{j}"], block_table,
                                  pos, active, kind=kind, moe=moe,
                                  decode_kernel=decode_kernel)
        new_rem[f"layer{j}"] = c
    return x, {"periods": new_period_pools, "rem": new_rem}


def block_prefill_paged(p, cfg: ModelConfig, x, positions, pool, block_table,
                        start_pos, *, kind: str, moe: bool, cache_max: int,
                        seq_len=None, state_rows=None):
    """Suffix-chunk prefill for one layer against its paged pool: each
    row attends to its cached prefix (through ``block_table`` — earlier
    chunks and/or prefix-cache matches) plus the chunk itself, and emits
    the chunk's decode cache for the engine to splice.  Ragged batches:
    ``start_pos`` may be (B,) per-row cursors with ``positions`` (B,S);
    ``seq_len`` (B,) gives valid lanes when x is padded to a bucket.
    Recurrent layers (mamba/rwkv6) carry their chunk-entry state in
    per-request slots instead of blocks: ``state_rows`` (B,) maps each
    dispatch row to its slot, and the emitted "cache" is the chunk-exit
    state for the engine to scatter back."""
    if kind in ("attn", "attn_local"):
        h = norm_apply(p["norm1"], x, cfg.norm_kind, cfg.norm_eps)
        y, cache = attn.attn_prefill_paged(p["mix"], cfg, h, positions, pool,
                                           block_table, start_pos, kind=kind,
                                           cache_max=cache_max,
                                           seq_len=seq_len)
        x = x + y
        h = norm_apply(p["norm2"], x, cfg.norm_kind, cfg.norm_eps)
        y, _ = _ffn(p, cfg, h, moe)
        return x + y, cache
    if kind not in ("mamba", "rwkv6"):
        raise ValueError(f"paged prefill: unsupported layer kind {kind!r}")
    b = x.shape[0]
    rows = (jnp.arange(b, dtype=jnp.int32) if state_rows is None
            else jnp.asarray(state_rows, jnp.int32))
    st = _state_read(pool, rows, start_pos, b)
    sl = None if seq_len is None else jnp.asarray(seq_len, jnp.int32)
    return _recurrent_fwd(p, cfg, x, st, kind=kind, moe=moe, seq_len=sl)


def stack_prefill_paged(params, cfg: ModelConfig, x, positions, pools,
                        block_table, start_pos, cache_max: int,
                        seq_len=None, state_rows=None):
    """-> (x, caches).  Same period scan as ``stack_decode_paged`` with
    the per-slot pools as scan xs; the per-layer suffix caches come out
    as scan ys, mirroring ``stack_prefill``'s cache layout.  For
    recurrent slots the "cache" is the chunk-exit state (B, ...) and
    ``state_rows`` maps dispatch rows to state-pool slots."""
    p, n_per, n_rem = layout(cfg)

    def body(x, xs):
        period_params, period_pools = xs
        caches = {}
        for j in range(p):
            kind, moe = slot_sig(cfg, j)
            x, c = block_prefill_paged(period_params[f"slot{j}"], cfg, x,
                                       positions, period_pools[f"slot{j}"],
                                       block_table, start_pos, kind=kind,
                                       moe=moe, cache_max=cache_max,
                                       seq_len=seq_len, state_rows=state_rows)
            caches[f"slot{j}"] = c
        return x, caches

    period_caches = {}
    if n_per:
        x, period_caches = jax.lax.scan(
            body, x, (params["periods"], pools["periods"]))
    rem_caches = {}
    for j in range(n_rem):
        kind, moe = slot_sig(cfg, n_per * p + j)
        x, c = block_prefill_paged(params["rem"][f"layer{j}"], cfg, x,
                                   positions, pools["rem"][f"layer{j}"],
                                   block_table, start_pos, kind=kind,
                                   moe=moe, cache_max=cache_max,
                                   seq_len=seq_len, state_rows=state_rows)
        rem_caches[f"layer{j}"] = c
    return x, {"periods": period_caches, "rem": rem_caches}


def stack_pool_init(cfg: ModelConfig, num_blocks: int, block_size: int,
                    dtype, state_batch: int = 1):
    """Concrete block pools for the whole stack, mirroring the cache
    layout (period-stacked leaves lead with ``n_periods``).  Pools are
    built at full ``block_size`` for every layer — sliding-window layers
    keep correctness through the window mask, not a ring clamp (rings
    don't compose with block reuse).  Recurrent layers (mamba/rwkv6) get
    fixed-size per-request state slots instead of blocks: ``state_batch``
    rows (the engine passes max_batch+1 — one slot per engine row plus a
    trash row for padded dispatch rows)."""
    p, n_per, n_rem = layout(cfg)

    def one(kind):
        if kind == "mamba":
            return ssm_mod.init_state(cfg, state_batch, dtype)
        if kind == "rwkv6":
            return rwkv_mod.init_state(cfg, state_batch, dtype)
        if kind not in ("attn", "attn_local"):
            raise ValueError(f"paged pools: unsupported layer kind {kind!r}")
        return attn.paged_pool_init(cfg, num_blocks, block_size, dtype)

    def stacked(tree):
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n_per,) + a.shape), tree)

    periods = {
        f"slot{j}": stacked(one(slot_sig(cfg, j)[0])) for j in range(p)
    } if n_per else {}
    rem = {
        f"layer{j}": one(slot_sig(cfg, n_per * p + j)[0])
        for j in range(n_rem)
    }
    return {"periods": periods, "rem": rem}


def stack_cache_abstract(cfg: ModelConfig, batch: int, cache_max: int, dtype):
    p, n_per, n_rem = layout(cfg)

    def stacked(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((n_per,) + s.shape, s.dtype), tree)

    periods = {
        f"slot{j}": stacked(
            block_cache_abstract(cfg, slot_sig(cfg, j)[0], batch, cache_max, dtype))
        for j in range(p)
    } if n_per else {}
    rem = {
        f"layer{j}": block_cache_abstract(
            cfg, slot_sig(cfg, n_per * p + j)[0], batch, cache_max, dtype)
        for j in range(n_rem)
    }
    return {"periods": periods, "rem": rem}


def stack_cache_logical(cfg: ModelConfig):
    p, n_per, n_rem = layout(cfg)

    def with_layers(tree):
        return {k: ("layers",) + v for k, v in tree.items()}

    periods = {
        f"slot{j}": with_layers(block_cache_logical(cfg, slot_sig(cfg, j)[0]))
        for j in range(p)
    } if n_per else {}
    rem = {
        f"layer{j}": block_cache_logical(cfg, slot_sig(cfg, n_per * p + j)[0])
        for j in range(n_rem)
    }
    return {"periods": periods, "rem": rem}


# ------------------------------------------------------------------ enc-dec
# Whisper-tiny: 4+4 layers — unrolled (no scan machinery needed).


def encoder_layer_schema(cfg: ModelConfig):
    return {
        "norm1": norm_schema(cfg),
        "attn": attn.attn_schema(cfg),
        "norm2": norm_schema(cfg),
        "ffn": mlp_schema(cfg),
    }


def decoder_layer_schema(cfg: ModelConfig):
    return {
        "norm1": norm_schema(cfg),
        "attn": attn.attn_schema(cfg),
        "norm_x": norm_schema(cfg),
        "cross": attn.attn_schema(cfg, cross=True),
        "norm2": norm_schema(cfg),
        "ffn": mlp_schema(cfg),
    }


def encdec_schema(cfg: ModelConfig):
    return {
        "encoder": {
            f"layer{i}": encoder_layer_schema(cfg) for i in range(cfg.encoder_layers)
        },
        "enc_pos": ParamSpec((cfg.encoder_frames, cfg.d_model), (None, "d_model"),
                             init="embed"),
        "enc_norm": norm_schema(cfg),
        "decoder": {
            f"layer{i}": decoder_layer_schema(cfg) for i in range(cfg.num_layers)
        },
    }


def encoder_apply(params, cfg: ModelConfig, frames):
    """``params`` is the full encdec tree; frames (B, F, D) from the stubbed
    audio frontend -> encoder output."""
    x = frames + params["enc_pos"][None].astype(frames.dtype)
    pos = jnp.arange(frames.shape[1], dtype=jnp.int32)
    for i in range(cfg.encoder_layers):
        p = params["encoder"][f"layer{i}"]
        h = norm_apply(p["norm1"], x, cfg.norm_kind, cfg.norm_eps)
        x = x + attn.attn_apply(p["attn"], cfg, h, pos, causal=False)
        h = norm_apply(p["norm2"], x, cfg.norm_kind, cfg.norm_eps)
        x = x + mlp_apply(p["ffn"], h, cfg.mlp_kind)
    return norm_apply(params["enc_norm"], x, cfg.norm_kind, cfg.norm_eps)


def decoder_apply(params, cfg: ModelConfig, x, positions, enc_out):
    """Full-sequence decoder (train)."""
    cross_kvs = {
        i: attn.cross_kv(params[f"layer{i}"]["cross"], enc_out)
        for i in range(cfg.num_layers)
    }
    for i in range(cfg.num_layers):
        p = params[f"layer{i}"]
        h = norm_apply(p["norm1"], x, cfg.norm_kind, cfg.norm_eps)
        x = x + attn.attn_apply(p["attn"], cfg, h, positions)
        h = norm_apply(p["norm_x"], x, cfg.norm_kind, cfg.norm_eps)
        k, v = cross_kvs[i]
        x = x + attn.cross_apply(p["cross"], cfg, h, k, v)
        h = norm_apply(p["norm2"], x, cfg.norm_kind, cfg.norm_eps)
        x = x + mlp_apply(p["ffn"], h, cfg.mlp_kind)
    return x


def decoder_prefill(params, cfg: ModelConfig, x, positions, enc_out,
                    cache_max: int):
    caches = {}
    for i in range(cfg.num_layers):
        p = params[f"layer{i}"]
        h = norm_apply(p["norm1"], x, cfg.norm_kind, cfg.norm_eps)
        y, c = attn.attn_prefill(p["attn"], cfg, h, positions, kind="attn",
                                 cache_max=cache_max)
        x = x + y
        h = norm_apply(p["norm_x"], x, cfg.norm_kind, cfg.norm_eps)
        k, v = attn.cross_kv(p["cross"], enc_out)
        x = x + attn.cross_apply(p["cross"], cfg, h, k, v)
        h = norm_apply(p["norm2"], x, cfg.norm_kind, cfg.norm_eps)
        x = x + mlp_apply(p["ffn"], h, cfg.mlp_kind)
        caches[f"layer{i}"] = {"self": c, "xk": k, "xv": v}
    return x, caches


def decoder_decode(params, cfg: ModelConfig, x, caches, pos):
    new = {}
    for i in range(cfg.num_layers):
        p = params[f"layer{i}"]
        c = caches[f"layer{i}"]
        h = norm_apply(p["norm1"], x, cfg.norm_kind, cfg.norm_eps)
        y, sc = attn.attn_decode(p["attn"], cfg, h, c["self"], pos, kind="attn")
        x = x + y
        h = norm_apply(p["norm_x"], x, cfg.norm_kind, cfg.norm_eps)
        x = x + attn.cross_apply(p["cross"], cfg, h, c["xk"], c["xv"])
        h = norm_apply(p["norm2"], x, cfg.norm_kind, cfg.norm_eps)
        x = x + mlp_apply(p["ffn"], h, cfg.mlp_kind)
        new[f"layer{i}"] = {"self": sc, "xk": c["xk"], "xv": c["xv"]}
    return x, new

"""Paged-attention decode as a Pallas TPU kernel.

One-token decode against a block-paged KV pool: each request's KV lives
in ``ceil(len/block_size)`` physical blocks of a shared pool, addressed
through a per-request block table.  The jnp reference path materializes
the gathered ``(B, nb*bs, KV, hd)`` logical cache view in HBM every
step; this kernel instead streams K/V blocks straight from the pool into
VMEM — the block table rides in as a scalar-prefetch operand so the
BlockSpec index maps resolve ``logical block j of request b -> physical
block`` *before* the DMA is issued (the vLLM mechanism, Pallas-shaped).

Grid ``(B, nb)``: the minor axis walks a request's logical blocks
sequentially on-core, carrying an online-softmax accumulator (running
max / denominator / weighted-value sum) in VMEM scratch — masked tail
lanes (``pos`` = -1: never written, freed, or null-block padding) and
lanes beyond the query's position are excluded both from the max and the
sum, so partially filled tail blocks and 0-padded block tables are
handled with no host-side fixup.

Layout: q (B, H, hd) — one token per request; k/v pools
(NB, bs, KV, hd); pos pool (NB, bs) int32 absolute positions (-1 =
invalid lane); block_table (B, nb) int32 (0-padded: physical block 0 is
the permanently-invalid null block); pos (B,) int32 position of the new
token.  GQA: H % KV == 0; the q-head group of each kv head is sliced
statically so every dot stays a plain 2-D ``dot_general`` (no batched
dots for Mosaic to chew on).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0 ** 30


def _kernel(bt_ref, pos_ref, q_ref, k_ref, v_ref, kpos_ref, o_ref,
            acc_ref, m_ref, l_ref, *, scale: float, kv: int, nb: int,
            window: int):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)            # (H, hd)
    k = k_ref[0].astype(jnp.float32)            # (bs, KV, hd)
    v = v_ref[0].astype(jnp.float32)
    kpos = kpos_ref[0, 0]                       # (bs,)
    h = q.shape[0]
    g = h // kv

    p_now = pos_ref[b]
    valid = (kpos >= 0) & (kpos <= p_now)
    if window:
        valid = valid & (p_now - kpos < window)

    # per-kv-head 2-D dots; head order matches _repeat_kv (head i -> kv
    # head i // g), so rows concatenate back to the full H axis.
    s = jnp.concatenate([
        jax.lax.dot_general(q[i * g:(i + 1) * g], k[:, i, :],
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        for i in range(kv)
    ], axis=0) * scale                          # (H, bs)
    s = jnp.where(valid[None, :], s, NEG_INF)

    m_prev = m_ref[...]                         # (H,)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    p = jnp.where(valid[None, :], p, 0.0)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
    pv = jnp.concatenate([
        jax.lax.dot_general(p[i * g:(i + 1) * g], v[:, i, :],
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
        for i in range(kv)
    ], axis=0)                                  # (H, hd)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + pv
    m_ref[...] = m_new

    @pl.when(j == nb - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def paged_attention(q, k_pool, v_pool, kpos_pool, block_table, pos, *,
                    window: int = 0, interpret: bool = False):
    """q (B,H,hd), k/v pools (NB,bs,KV,hd), kpos_pool (NB,bs) int32,
    block_table (B,nb) int32 (0-padded), pos (B,) int32 -> (B,H,hd).

    All-invalid rows (e.g. an inactive request whose table is all null
    blocks) return zeros."""
    b, h, hd = q.shape
    nb = block_table.shape[1]
    bs, kv = k_pool.shape[1], k_pool.shape[2]
    scale = 1.0 / math.sqrt(hd)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nb),
        in_specs=[
            pl.BlockSpec((1, h, hd), lambda bi, ji, bt, ps: (bi, 0, 0)),
            pl.BlockSpec((1, bs, kv, hd),
                         lambda bi, ji, bt, ps: (bt[bi, ji], 0, 0, 0)),
            pl.BlockSpec((1, bs, kv, hd),
                         lambda bi, ji, bt, ps: (bt[bi, ji], 0, 0, 0)),
            # unit middle axis: a (1, bs) block over (NB, bs) breaks the
            # TPU tiling rule (second-minor dim neither 8-aligned nor full)
            pl.BlockSpec((1, 1, bs),
                         lambda bi, ji, bt, ps: (bt[bi, ji], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, hd), lambda bi, ji, bt, ps: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, hd), jnp.float32),
            pltpu.VMEM((h,), jnp.float32),
            pltpu.VMEM((h,), jnp.float32),
        ],
    )
    kern = functools.partial(_kernel, scale=scale, kv=kv, nb=nb,
                             window=window)
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, hd), q.dtype),
        interpret=interpret,
    )(jnp.asarray(block_table, jnp.int32), jnp.asarray(pos, jnp.int32),
      q, k_pool, v_pool, kpos_pool.reshape(-1, 1, bs))

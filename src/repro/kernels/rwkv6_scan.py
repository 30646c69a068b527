"""Chunked WKV6 recurrence as a Pallas TPU kernel.

The attention-free RWKV-6 core is a per-channel-decay linear recurrence:

    o_t[j] = sum_i r_t[i] (S[i,j] + u[i] k_t[i] v_t[j])
    S      = diag(w_t) S + k_t (x) v_t          (S: (hd, hd) per head)

TPU adaptation: instead of a token-at-a-time scan (sequential, VPU-bound),
the sequence is processed in chunks of T tokens.  Within a chunk the
recurrence has a closed parallel form in terms of cumulative log-decays
L_t = sum_{tau<=t} log w_tau:

    cross[t]  = (r_t * exp(L_{t-1})) @ S_in                 (MXU matmul)
    intra[t]  = sum_{tau<t} P[t,tau] v_tau,
                P[t,tau] = sum_i r_t[i] k_tau[i] exp(L_{t-1,i} - L_{tau,i})
    bonus[t]  = (sum_i r_t[i] u[i] k_t[i]) v_t
    S_out     = diag(exp(L_T)) S_in + (k * exp(L_T - L))^T @ v

Every exponent is a *difference* of cumulative log-decays with the later
index on the left, hence <= 0 — no overflow regardless of how aggressive
the data-dependent decay gets (this is why the naive "divide by cumprod"
chunking is NOT used).  The (T, T, hd) decay-difference tensor is the VMEM
working set: T=32, hd=64 -> 256 KiB fp32, well inside the ~16 MiB VMEM
budget alongside the (hd, hd) carried state.

Grid: (B*H, n_chunks); the chunk axis is minor (sequential on-core), so the
state lives in VMEM scratch across chunk steps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dot(a, b, contract=((1,), (0,))):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, o_ref, sfin_ref,
            s_ref, *, nc: int, chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = s0_ref[...].astype(jnp.float32)

    r = r_ref[...].astype(jnp.float32)          # (T, hd)
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)          # (1, hd)
    s = s_ref[...]                              # (hd, hd)
    hd = s.shape[0]

    # Everything below stays 2-D (or 3-D with the lane axis last) and
    # uses no cumsum, which Mosaic cannot lower: the prefix sums and the
    # diagonal decay of the state are matmuls at HIGHEST precision
    # (their rounding errors would otherwise be exponentiated).
    logw = jnp.log(jnp.maximum(w, 1e-30))
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    big_l = _dot((t_idx >= s_idx).astype(jnp.float32), logw)   # L_t (1-based)
    l_prev = big_l - logw                                       # L_{t-1}

    # cross-chunk contribution (decayed state read)
    cross = _dot(r * jnp.exp(l_prev), s)

    # intra-chunk: P[t,tau] = sum_i r[t,i] k[tau,i] exp(L_{t-1,i}-L_{tau,i})
    diff = l_prev[:, None, :] - big_l[None, :, :]        # (T, T, hd), <= 0 on tau<t
    tri = jax.lax.broadcasted_iota(jnp.int32, diff.shape, 0) > \
        jax.lax.broadcasted_iota(jnp.int32, diff.shape, 1)   # strict lower
    decay = jnp.where(tri, jnp.exp(diff), 0.0)
    p = jnp.sum(r[:, None, :] * k[None, :, :] * decay, axis=-1)   # (T, T)
    intra = _dot(p, v)

    # self (bonus) term
    rku = jnp.sum(r * u * k, axis=-1, keepdims=True)     # (T, 1)
    o_ref[...] = (cross + intra + rku * v).astype(o_ref.dtype)

    # state update: S' = diag(exp(L_T)) S + (k * exp(L_T - L))^T @ v
    l_tot = big_l[chunk - 1:chunk, :]                     # (1, hd)
    k_dec = k * jnp.exp(l_tot - big_l)
    row = jax.lax.broadcasted_iota(jnp.int32, (hd, hd), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (hd, hd), 1)
    diag = jnp.where(row == col, jnp.exp(l_tot), 0.0)    # diag(exp(L_T))
    s_new = _dot(diag, s) + _dot(k_dec, v, ((0,), (0,)))
    s_ref[...] = s_new

    @pl.when(ci == nc - 1)
    def _finish():
        sfin_ref[...] = s_new


def rwkv6_scan(r, k, v, w, u, s0=None, *, chunk: int = 32,
               interpret: bool = False):
    """r/k/v/w (B,H,S,hd) (w = decay in (0,1)), u (H,hd),
    s0 (B,H,hd,hd) fp32 or None.  -> (out (B,H,S,hd), s_final fp32)."""
    b, h, s, hd = r.shape
    if s0 is None:
        s0 = jnp.zeros((b, h, hd, hd), jnp.float32)
    chunk = min(chunk, max(8, s))
    pad = (-s) % chunk
    if pad:
        # identity extension: w=1 (no decay), r/k/v = 0.
        zpad = ((0, 0), (0, 0), (0, pad), (0, 0))
        r = jnp.pad(r, zpad)
        k = jnp.pad(k, zpad)
        v = jnp.pad(v, zpad)
        w = jnp.pad(w, zpad, constant_values=1.0)
    sp = s + pad
    nc = sp // chunk

    bh = b * h
    rf = r.reshape(bh, sp, hd)
    kf = k.reshape(bh, sp, hd)
    vf = v.reshape(bh, sp, hd)
    wf = w.reshape(bh, sp, hd)
    # (bh, 1, hd): a squeezed-row (hd,) block over (bh, hd) breaks the
    # TPU tiling rule (second-minor dim neither 8-aligned nor full)
    uf = jnp.broadcast_to(u[None], (b, h, hd)).reshape(bh, 1, hd)
    s0f = s0.reshape(bh, hd, hd)

    kern = functools.partial(_kernel, nc=nc, chunk=chunk)
    out, sfin = pl.pallas_call(
        kern,
        grid=(bh, nc),
        in_specs=[
            pl.BlockSpec((None, chunk, hd), lambda i, c: (i, c, 0)),
            pl.BlockSpec((None, chunk, hd), lambda i, c: (i, c, 0)),
            pl.BlockSpec((None, chunk, hd), lambda i, c: (i, c, 0)),
            pl.BlockSpec((None, chunk, hd), lambda i, c: (i, c, 0)),
            pl.BlockSpec((None, 1, hd), lambda i, c: (i, 0, 0)),
            pl.BlockSpec((None, hd, hd), lambda i, c: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, chunk, hd), lambda i, c: (i, c, 0)),
            pl.BlockSpec((None, hd, hd), lambda i, c: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sp, hd), r.dtype),
            jax.ShapeDtypeStruct((bh, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        interpret=interpret,
    )(rf, kf, vf, wf, uf, s0f)
    out = out.reshape(b, h, sp, hd)[:, :, :s]
    return out, sfin.reshape(b, h, hd, hd)

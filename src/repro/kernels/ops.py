"""Public kernel entry points.

Each op dispatches: Pallas-interpret when
``REPRO_FORCE_PALLAS_INTERPRET=1`` (kernel-path testing on CPU), else the
compiled Pallas kernel on TPU, else the pure-jnp reference.  Interpret
mode comes from that variable alone, never from the platform, so a TPU
run never interprets a kernel by accident.  The reference IS the
semantics; tests assert the kernel path matches it over shape/dtype
sweeps.
"""
from __future__ import annotations

import functools
import os

import jax

from repro.kernels import conv2d as _conv
from repro.kernels import flash_attention as _fa
from repro.kernels import paged_attention as _pa
from repro.kernels import ref as _ref
from repro.kernels import rwkv6_scan as _rwkv


def _platform() -> str:
    return jax.devices()[0].platform


def _force_interpret() -> bool:
    return os.environ.get("REPRO_FORCE_PALLAS_INTERPRET", "0") == "1"


def _use_kernel() -> bool:
    return _platform() == "tpu" or _force_interpret()


def kernels_enabled() -> bool:
    """Should the MODEL forward path route through the Pallas kernels?
    True on TPU, or when REPRO_USE_KERNELS=1 (CPU: interpret mode —
    kernel-path integration testing)."""
    return _platform() == "tpu" or \
        os.environ.get("REPRO_USE_KERNELS", "0") == "1"


def kernel_path_active() -> bool:
    """Would an op below dispatch to Pallas right now (TPU, or forced
    interpret) rather than its jnp reference?  Gauges that claim "the
    kernel ran" must check this, not just the model-side switch."""
    return _use_kernel()


@functools.partial(jax.jit, static_argnames=("causal", "window"))
def _fa_ref_jit(q, k, v, causal, window):
    return _ref.flash_attention_ref(q, k, v, causal=causal, window=window)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128):
    """q (B,H,Sq,hd), k/v (B,KV,Sk,hd) -> (B,H,Sq,hd)."""
    if _use_kernel():
        return _fa.flash_attention(q, k, v, causal=causal, window=window,
                                   block_q=block_q, block_k=block_k,
                                   interpret=_force_interpret())
    return _fa_ref_jit(q, k, v, causal, window)


@functools.partial(jax.jit, static_argnames=("window",))
def _pa_ref_jit(q, k_pool, v_pool, kpos_pool, block_table, pos, window):
    return _ref.paged_attention_ref(q, k_pool, v_pool, kpos_pool,
                                    block_table, pos, window=window)


def paged_attention(q, k_pool, v_pool, kpos_pool, block_table, pos, *,
                    window: int = 0):
    """One-token paged decode: q (B,H,hd) against k/v pools
    (NB,bs,KV,hd) through block_table (B,nb) -> (B,H,hd)."""
    if _use_kernel():
        return _pa.paged_attention(q, k_pool, v_pool, kpos_pool,
                                   block_table, pos, window=window,
                                   interpret=_force_interpret())
    return _pa_ref_jit(q, k_pool, v_pool, kpos_pool, block_table, pos,
                       window)


@functools.partial(jax.jit, static_argnames=("window",))
def _pp_ref_jit(q, k, v, kpos, qpos, window):
    return _ref.paged_prefill_ref(q, k, v, kpos, qpos, window=window)


def paged_prefill(q, k, v, kpos, qpos, *, window: int = 0):
    """Ragged-batch chunked-prefill attention: q (B,S,H,hd) against
    assembled keys k/v (B,L,KV,hd) with absolute key/query positions
    kpos (B,L) / qpos (B,S) -> (B,S,H,hd).  Per-row raggedness (chunk
    length, prefix size, position offset) lives entirely in the position
    arrays — see ``ref.paged_prefill_ref`` for the semantics.  ``window``
    > 0 applies the sliding-window band mask over absolute positions.

    No Pallas kernel exists for this op yet: the decode kernel's
    online-softmax block loop extends to S>1 query lanes but hasn't been
    written (ROADMAP), so BOTH dispatch arms run the jnp reference.  The
    call sites are already kernel-shaped — when the kernel lands, only
    this function changes.
    """
    return _pp_ref_jit(q, k, v, kpos, qpos, window)


def rwkv6_scan(r, k, v, w, u, s0=None, *, chunk: int = 32):
    """Chunked WKV6; returns (out, final_state)."""
    if _use_kernel():
        return _rwkv.rwkv6_scan(r, k, v, w, u, s0, chunk=chunk,
                                interpret=_force_interpret())
    return _ref.rwkv6_scan_ref(r, k, v, w, u, s0)


def conv2d(x, w):
    """Valid NHWC conv, stride 1."""
    if _use_kernel():
        return _conv.conv2d(x, w, interpret=_force_interpret())
    return _ref.conv2d_ref(x, w)

"""Serving-side KV-cache slot manager for continuous-batching LLM decode.

The model's decode cache is a batched pytree (leading batch axis = slots).
``SlotManager`` tracks which slots are live; ``write_slot`` /
``clear_slot`` splice a single request's prefill cache into the batched
cache.  Freed slots are *not* zeroed eagerly — their ``pos`` lanes are
invalidated (set to -1 / zero state) so stale keys can never win the
attention mask; the slot is reused by the next prefill.

This is the TPU-native shape of vLLM's insight: on GPUs, paged KV blocks
fight fragmentation of a global HBM pool; under XLA, buffers are static,
so the equivalent mechanism is a fixed slot-batched cache with masked
liveness + in-place splicing (dynamic_update_slice), which keeps every
decode step a single fixed-shape XLA program.
"""
from __future__ import annotations

from collections import deque
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp


class SlotManager:
    def __init__(self, num_slots: int):
        self.num_slots = num_slots
        self._free: List[int] = list(range(num_slots))
        self._live: set = set()

    def alloc(self) -> Optional[int]:
        if not self._free:
            return None
        s = self._free.pop(0)
        self._live.add(s)
        return s

    def free(self, slot: int) -> None:
        self._live.discard(slot)
        self._free.append(slot)
        self._free.sort()

    @property
    def live(self) -> List[int]:
        return sorted(self._live)

    @property
    def num_free(self) -> int:
        return len(self._free)


def write_slot(batched_cache: Any, single_cache: Any, slot: int) -> Any:
    """Splice a (B=1)-batched cache pytree into slot ``slot``.

    Handles the stacked-period layout: leaves whose rank matches have the
    batch axis at position 0 (rem layers / encdec) or 1 (period-stacked,
    leading ``n_periods``).  The single cache comes from ``Model.prefill``
    with batch 1, so the batch axis is the one of size 1 whose batched
    counterpart is ``num_slots``-sized.
    """

    def splice(big, small):
        axis = _batch_axis(big.shape, small.shape)
        idx = [0] * big.ndim
        idx[axis] = slot
        return jax.lax.dynamic_update_slice(big, small.astype(big.dtype),
                                            tuple(idx))

    return jax.tree.map(splice, batched_cache, single_cache)


def _batch_axis(big_shape, small_shape) -> int:
    for i, (b, s) in enumerate(zip(big_shape, small_shape)):
        if s == 1 and b != s:
            return i
    # identical shapes: batch axis is wherever caller said; default 0
    for i, (b, s) in enumerate(zip(big_shape, small_shape)):
        if b != s:
            return i
    return 0


def _is_logical(x) -> bool:
    return isinstance(x, tuple) and all(
        isinstance(e, (str, type(None))) for e in x)


# ---------------------------------------------------------------- paged
# Block-paged pool management (the vLLM mechanism, XLA-shaped).  The
# model side lives in models/attention.py: one preallocated pool of
# fixed-size token blocks per layer, gather-based reads through a
# per-request block table.  This side owns the physical-block free list
# and the host<->pool splices.


class BlockAllocator:
    """Free-list + reference counts over physical KV blocks.  Block 0 is
    the reserved NULL block (block tables pad with it; its pos lanes stay
    -1 forever), so allocatable ids are ``1..num_blocks-1``.

    A block may be held by several owners at once — N requests sharing a
    prompt prefix plus the prefix cache.  ``alloc`` hands out blocks at
    refcount 1, ``incref`` adds a holder, ``free`` drops one hold per
    listed block and returns a block to the free list only when the last
    holder lets go.

    The free list is a FIFO deque: ``free`` appends, ``alloc`` pops from
    the left — O(1) per block (no sort) and deterministic (blocks are
    reused in the order they were released).
    """

    def __init__(self, num_blocks: int, block_size: int):
        assert num_blocks >= 2, "need >= 1 allocatable block + null block"
        assert block_size >= 1
        self.num_blocks = num_blocks
        self.block_size = block_size
        self._free: deque = deque(range(1, num_blocks))
        self._ref: Dict[int, int] = {}

    def blocks_for(self, n_tokens: int) -> int:
        """Blocks needed to hold ``n_tokens`` cache entries (>= 1)."""
        return max(1, -(-n_tokens // self.block_size))

    def alloc(self, n: int) -> Optional[List[int]]:
        """Pop ``n`` blocks at refcount 1, or None (all-or-nothing) if
        the pool can't cover the request."""
        if n > len(self._free):
            return None
        blocks = [self._free.popleft() for _ in range(n)]
        for b in blocks:
            self._ref[b] = 1
        return blocks

    def incref(self, block: int) -> None:
        """Add a holder to a live block (sharing a cached prefix)."""
        assert block in self._ref, f"incref of free block {block}"
        self._ref[block] += 1

    def refcount(self, block: int) -> int:
        return self._ref.get(block, 0)

    def free(self, blocks: List[int]) -> List[int]:
        """Drop one hold per listed block.  Returns the blocks whose last
        holder just released them (i.e. the ones that actually went back
        to the free list and need their pool lanes invalidated)."""
        released = []
        for b in blocks:
            assert b in self._ref, f"double free of block {b}"
            self._ref[b] -= 1
            if self._ref[b] == 0:
                del self._ref[b]
                self._free.append(b)
                released.append(b)
        return released

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_live(self) -> int:
        return len(self._ref)

    @property
    def num_usable(self) -> int:
        return self.num_blocks - 1          # null block is never usable


def write_prefill_blocks(pools: Any, single_cache: Any, block_ids: List[int],
                         block_size: int, offset: int = 0,
                         valid_len: Optional[int] = None) -> Any:
    """Splice a (B=1) prefill cache into the request's physical blocks.

    ``single_cache`` comes from ``Model.prefill`` (or
    ``Model.prefill_paged``).  With ``valid_len=None`` (exact-size
    contract) the cache's kv_len axis must equal ``len(block_ids) *
    block_size - offset`` — any mismatch is a caller bug and asserts.
    A caller whose cache is padded to a length bucket passes
    ``valid_len`` (its count of VALID lanes, pre-``offset``); the axis
    is then reconciled: a longer cache is truncated — the declared
    valid lanes must fit the blocks, so only ``pos = -1`` padding lanes
    can be cut — and a shorter one is extended with invalid lanes (-1
    for integer leaves, 0 otherwise), which is safe because freed
    blocks are invalidated on release, so a block handed out by the
    allocator never carries stale valid positions.  Unfilled lanes
    carry ``pos = -1`` and overwrite any lanes the splice does reach.

    ``offset`` supports copy-on-write resumption inside a partially
    matched block: the cache's first lane lands at in-block offset
    ``offset`` of ``block_ids[0]`` and that block's first ``offset``
    lanes are left untouched (they hold the prefix KV copied from the
    shared donor block by ``copy_blocks``).
    """
    assert 0 <= offset < block_size, (offset, block_size)
    ids = jnp.asarray(block_ids, jnp.int32)
    want = len(block_ids) * block_size
    if valid_len is not None:
        assert offset + valid_len <= want, \
            f"valid lanes {offset}+{valid_len} overflow " \
            f"{len(block_ids)} blocks x {block_size}"

    def write(pool_leaf, cache_leaf):
        ax = _batch_axis(pool_leaf.shape, cache_leaf.shape)
        small = jnp.squeeze(cache_leaf, ax)        # seq axis now at ``ax``
        if offset:
            pad = [(0, 0)] * small.ndim
            pad[ax] = (offset, 0)
            small = jnp.pad(small, pad)            # pad lanes masked below
        have = small.shape[ax]
        assert valid_len is not None or have == want, \
            (have, want, "pass valid_len for bucket-padded caches")
        if have > want:
            small = jax.lax.slice_in_dim(small, 0, want, axis=ax)
        elif have < want:
            pad = [(0, 0)] * small.ndim
            pad[ax] = (0, want - have)
            fill = -1 if jnp.issubdtype(small.dtype, jnp.integer) else 0
            small = jnp.pad(small, pad, constant_values=fill)
        shp = small.shape
        nb = len(block_ids)
        small = small.reshape(shp[:ax] + (nb, block_size) + shp[ax + 1:])
        idx = (slice(None),) * ax + (ids,)
        small = small.astype(pool_leaf.dtype)
        if offset:
            cur = pool_leaf[idx]
            lane = jnp.arange(nb * block_size).reshape(nb, block_size)
            keep = (lane < offset).reshape(
                (1,) * ax + (nb, block_size) + (1,) * (small.ndim - ax - 2))
            small = jnp.where(keep, cur, small)
        return pool_leaf.at[idx].set(small)

    return jax.tree.map(write, pools, single_cache)


def write_chunk_tokens(pools: Any, caches: Any, src_rows: Any,
                       src_lanes: Any, dst_blocks: Any,
                       dst_lanes: Any, state_rows: Any = None) -> Any:
    """Batched ragged-chunk writeback: scatter every valid token of a
    ragged chunk-batch prefill cache (``Model.prefill_paged`` under
    continuous batching) into its (physical block, lane) pool home —
    one gather + one scatter per pool leaf for the WHOLE batch, instead
    of a per-row slice-and-splice (whose eager-op count per step made
    chunked steps several times slower than pure-decode steps).

    ``src_rows[t], src_lanes[t]`` address token ``t`` on the cache's
    (batch, seq) axes; ``dst_blocks[t], dst_lanes[t]`` its pool home.
    Only the listed lanes are touched: lanes outside the chunk keep what
    they held, which is safe because released blocks are invalidated
    (``pos -> -1``) before reuse — the invariant decode growth writes
    already rely on — and it preserves copy-on-write prefix lanes before
    a mid-block resume point without a keep-mask.  Callers may pad the
    index arrays to a bucket by repeating a valid entry: duplicate
    (block, lane) pairs carry identical values, so the scatter is
    idempotent.

    Recurrent-state leaves (``h``/``conv``/``s``/``x_tm``/``x_cm``) hold
    per-request slots instead of token blocks: the cache carries one
    chunk-exit state per dispatch row and ``state_rows`` (B,) maps row i
    to its pool slot.  The engine routes padded dispatch rows to the
    pool's trash slot (its last row), so duplicate scatters there are
    harmless garbage.

    Layout (see transformer.stack_prefill_paged): "periods" leaves have
    batch at axis 1 behind the leading ``n_periods`` axis, "rem" leaves
    at axis 0; pool leaves put (num_blocks, block_size) at those same
    axes.
    """
    sr = jnp.asarray(src_rows, jnp.int32)
    sl = jnp.asarray(src_lanes, jnp.int32)
    db = jnp.asarray(dst_blocks, jnp.int32)
    dl = jnp.asarray(dst_lanes, jnp.int32)
    rows = None if state_rows is None else jnp.asarray(state_rows, jnp.int32)

    def walk(pnode, cnode, axis):
        out = {}
        for name, pleaf in pnode.items():
            if isinstance(pleaf, dict):
                out[name] = walk(pleaf, cnode[name], axis)
                continue
            pre = (slice(None),) * axis
            # the period axis is indexed too, so every update is a point
            # write with no window over it: a window over the period axis
            # makes the TPU compiler relayout the whole ``pos`` leaf
            # before the scatter and back after it
            per = (jnp.arange(pleaf.shape[0], dtype=jnp.int32)[:, None],) \
                if axis else ()
            if name in _STATE_LEAVES:
                assert rows is not None, "state pools need state_rows"
                out[name] = pleaf.at[per + (rows,)].set(
                    cnode[name].astype(pleaf.dtype))
            else:
                vals = cnode[name][pre + (sr, sl)].astype(pleaf.dtype)
                out[name] = pleaf.at[per + (db, dl)].set(vals)
        return out

    return {"periods": walk(pools.get("periods", {}),
                            caches.get("periods", {}), 1),
            "rem": walk(pools.get("rem", {}), caches.get("rem", {}), 0)}


# trailing (non-block) axes per pool-leaf name: leaves are shaped
# (..., num_blocks, block_size, *tail) with period-stacked variants
# carrying a leading n_periods axis, so the block axis is located from
# the right.
_POOL_LEAF_TAIL = {"pos": 0, "k_s": 1, "v_s": 1, "k": 2, "v": 2}

# recurrent-state pool leaves (mamba h/conv, rwkv6 s/x_tm/x_cm): slot
# axis instead of (num_blocks, block_size) — block-addressed ops skip
# them (state moves by slot, never by block id).
_STATE_LEAVES = frozenset({"h", "conv", "s", "x_tm", "x_cm"})


def copy_blocks(pools: Any, src_ids: List[int], dst_ids: List[int]) -> Any:
    """Copy whole physical blocks ``src -> dst`` in every layer pool —
    the copy-on-write mechanism: before a request writes into a block it
    shares with the prefix cache (divergence inside a partially matched
    block), the engine copies the donor block into a private one.  Any
    diverged tail lanes copied along are overwritten or mask-invalidated
    by the subsequent ``write_prefill_blocks(..., offset=j)``, and reads
    in between mask them via ``pos >= start``."""
    src = jnp.asarray(src_ids, jnp.int32)
    dst = jnp.asarray(dst_ids, jnp.int32)

    def walk(node):
        out = {}
        for name, leaf in node.items():
            if isinstance(leaf, dict):
                out[name] = walk(leaf)
            elif name in _STATE_LEAVES:
                out[name] = leaf                # slots, not blocks: no-op
            else:
                ax = leaf.ndim - 2 - _POOL_LEAF_TAIL[name]
                pre = (slice(None),) * ax
                out[name] = leaf.at[pre + (dst,)].set(leaf[pre + (src,)])
        return out

    return walk(pools)


def invalidate_blocks(pools: Any, block_ids: List[int]) -> Any:
    """Kill freed blocks' attention validity (pos lanes -> -1) so a block
    handed to a *growing* request mid-decode can't leak its previous
    owner's positions (prefill splices overwrite whole blocks; growth
    writes one lane at a time)."""
    ids = jnp.asarray(block_ids, jnp.int32)

    def walk(node):
        if isinstance(node, dict):
            return {k: (v.at[..., ids, :].set(-1) if k == "pos" else walk(v))
                    for k, v in node.items()}
        return node

    return walk(pools)


def scrub_null_block(pools: Any) -> Any:
    """Reset the null block's validity lanes (``pos[..., 0, :] -> -1``).
    Block 0 is the engine's garbage sink: padded block-table entries
    point at it, and the fused ragged-dispatch writeback routes every
    invalid (padding) lane's scatter there instead of branching on the
    host.  Its k/v payload may hold arbitrary garbage, but its ``pos``
    lanes must stay -1 or padded table reads could un-mask — calling
    this inside the same fused dispatch restores the invariant."""
    def walk(node):
        if isinstance(node, dict):
            return {k: (v.at[..., 0, :].set(-1) if k == "pos" else walk(v))
                    for k, v in node.items()}
        return node

    return walk(pools)


def invalidate_lanes(pools: Any, block_ids: Any, lanes: Any) -> Any:
    """Kill individual (block, lane) pairs' attention validity (pos ->
    -1).  The speculative-decode rollback path uses this for the
    partially-accepted tail of the *last kept* block: rejected drafted
    tokens were written into lanes past the accepted cursor, and while
    every read already masks them (prefill masks pool lanes ``>=
    start_pos``, decode masks ``pos > query``), invalidating them keeps
    the pool's ``pos`` lanes an exact record of valid KV — the same
    invariant ``invalidate_blocks`` maintains for whole freed blocks.
    Only ``pos`` leaves are touched (k/v payload lanes are inert once
    ``pos`` is -1), so the update is O(num_blocks * block_size) ints per
    layer, not a pool copy."""
    ids = jnp.asarray(block_ids, jnp.int32)
    ln = jnp.asarray(lanes, jnp.int32)

    def walk(node):
        if isinstance(node, dict):
            return {k: (v.at[..., ids, ln].set(-1) if k == "pos" else walk(v))
                    for k, v in node.items()}
        return node

    return walk(pools)


def invalidate_slot(batched_cache: Any, cache_logical: Any, slot: int) -> Any:
    """Kill a slot's attention validity: position lanes -> -1, states -> 0.

    ``cache_logical`` mirrors the cache structure with logical-axis name
    tuples at the leaves (Model.cache_logical()).
    """
    leaves, treedef = jax.tree.flatten(batched_cache)
    logicals = jax.tree.flatten(cache_logical, is_leaf=_is_logical)[0]
    assert len(leaves) == len(logicals)

    out = []
    for leaf, logical in zip(leaves, logicals):
        # period-stacked leaves carry a leading "layers" axis before batch
        axis = 1 if (logical and logical[0] == "layers") else 0
        names = logical[1:] if axis else logical
        row = jax.lax.index_in_dim(leaf, slot, axis, keepdims=True)
        is_pos = jnp.issubdtype(leaf.dtype, jnp.integer) and "kv_len" in names
        fill = jnp.full_like(row, -1) if is_pos else jnp.zeros_like(row)
        out.append(jax.lax.dynamic_update_slice_in_dim(leaf, fill, slot,
                                                       axis=axis))
    return jax.tree.unflatten(treedef, out)

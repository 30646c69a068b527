"""Unified model API.

``Model(cfg)`` wraps any assigned architecture behind one interface:

    schema()                       parameter ParamSpec pytree
    init(key)                      concrete parameters
    abstract_params()              ShapeDtypeStructs (dry-run)
    param_pspecs(rules)            PartitionSpec pytree
    loss(params, batch)            scalar + metrics        (train_step core)
    forward(params, batch)         logits
    prefill(params, batch)         (last_logits, caches)
    decode_step(params, caches, tokens, pos)  (logits, caches)
    input_specs(shape)             abstract batch for lower()
    cache_abstract(batch, maxlen)  abstract decode cache
    cache_pspecs(rules)            cache PartitionSpecs

Batch dict keys: "tokens" (B,S) int32, "labels" (B,S) int32 (train),
"embeds" (B,N,W) for audio/vlm frontends.  For VLM the projected patch
embeddings are *prefixed* to the token embeddings (prefix-LM attention);
for whisper "embeds" is the encoder input.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.configs.shapes import InputShape
from repro.models import frontend as fe
from repro.models import transformer as tf
from repro.models.layers import (embed_apply, embed_schema, norm_apply,
                                 norm_schema, softmax_xent, unembed_apply)
from repro.models.module import (abstract_params, init_params, param_pspecs,
                                 ParamSpec)
from repro.models.sharding import Rules, shard


class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # ------------------------------------------------------------ schema
    def schema(self):
        cfg = self.cfg
        s: Dict[str, Any] = {"embed": embed_schema(cfg)}
        s.update(fe.frontend_schema(cfg))
        if cfg.is_encoder_decoder:
            s["encdec"] = tf.encdec_schema(cfg)
        else:
            s["stack"] = tf.stack_schema(cfg)
        s["final_norm"] = norm_schema(cfg)
        return s

    def init(self, key, dtype: Optional[str] = None):
        return init_params(self.schema(), key, dtype or self.cfg.dtype)

    def abstract_params(self, dtype: Optional[str] = None):
        return abstract_params(self.schema(), dtype or self.cfg.dtype)

    def param_pspecs(self, rules: Rules):
        return param_pspecs(self.schema(), rules)

    # ------------------------------------------------------------ helpers
    def _positions(self, s: int):
        pos = jnp.arange(s, dtype=jnp.int32)
        return jnp.minimum(pos, self.cfg.max_position - 1) if (
            self.cfg.pos_kind == "learned") else pos

    def _embed_tokens(self, params, tokens, positions):
        return embed_apply(params["embed"], self.cfg, tokens, positions)

    def _inputs(self, params, batch):
        """-> (x (B,S,D), positions (S,), prefix_len)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        if cfg.frontend == "vision":
            pre = fe.project(params, cfg, batch["embeds"])
            s_total = pre.shape[1] + tokens.shape[1]
            positions = self._positions(s_total)
            tok_x = self._embed_tokens(params, tokens,
                                       positions[pre.shape[1]:][None])
            x = jnp.concatenate([pre.astype(tok_x.dtype), tok_x], axis=1)
            return shard(x, "batch", "seq", "d_model"), positions, pre.shape[1]
        positions = self._positions(tokens.shape[1])
        x = self._embed_tokens(params, tokens, positions[None])
        return x, positions, 0

    # ------------------------------------------------------------ forward
    def forward(self, params, batch, *, remat: bool = True):
        cfg = self.cfg
        if cfg.is_encoder_decoder:
            enc = tf.encoder_apply(params["encdec"], cfg,
                                   batch["embeds"].astype(jnp.dtype(cfg.dtype)))
            positions = self._positions(batch["tokens"].shape[1])
            x = self._embed_tokens(params, batch["tokens"], positions[None])
            x = tf.decoder_apply(params["encdec"]["decoder"], cfg, x,
                                 positions, enc)
            aux = jnp.float32(0.0)
        else:
            x, positions, prefix = self._inputs(params, batch)
            x, aux = tf.stack_apply(params["stack"], cfg, x, positions,
                                    bidir_prefix=prefix, remat=remat)
            if prefix:
                x = x[:, prefix:]
        x = norm_apply(params["final_norm"], x, cfg.norm_kind, cfg.norm_eps)
        logits = unembed_apply(params["embed"], cfg, x)
        return logits, aux

    def loss(self, params, batch, *, remat: bool = True):
        logits, aux = self.forward(params, batch, remat=remat)
        xent = softmax_xent(logits, batch["labels"], batch.get("mask"))
        total = xent + self.cfg.router_aux_coef * aux
        return total, {"loss": total, "xent": xent, "aux": aux}

    # ------------------------------------------------------------ prefill
    def prefill(self, params, batch, *, cache_max: int):
        cfg = self.cfg
        if cfg.is_encoder_decoder:
            enc = tf.encoder_apply(params["encdec"], cfg,
                                   batch["embeds"].astype(jnp.dtype(cfg.dtype)))
            positions = self._positions(batch["tokens"].shape[1])
            x = self._embed_tokens(params, batch["tokens"], positions[None])
            x, caches = tf.decoder_prefill(params["encdec"]["decoder"], cfg, x,
                                           positions, enc, cache_max)
        else:
            x, positions, prefix = self._inputs(params, batch)
            x, _, caches = tf.stack_prefill(params["stack"], cfg, x, positions,
                                            cache_max=cache_max,
                                            bidir_prefix=prefix)
        x = norm_apply(params["final_norm"], x, cfg.norm_kind, cfg.norm_eps)
        logits = unembed_apply(params["embed"], cfg, x[:, -1:, :])
        return logits, caches

    def decode_step(self, params, caches, tokens, pos):
        """tokens (B,1) int32, pos (B,) absolute position.  -> (logits, caches)."""
        cfg = self.cfg
        posc = jnp.minimum(pos, cfg.max_position - 1) if (
            cfg.pos_kind == "learned") else pos
        x = self._embed_tokens(params, tokens, posc[:, None])
        if cfg.is_encoder_decoder:
            x, caches = tf.decoder_decode(params["encdec"]["decoder"], cfg, x,
                                          caches, posc)
        else:
            x, caches = tf.stack_decode(params["stack"], cfg, x, caches, posc)
        x = norm_apply(params["final_norm"], x, cfg.norm_kind, cfg.norm_eps)
        logits = unembed_apply(params["embed"], cfg, x)
        return logits, caches

    # ------------------------------------------------------------ paged
    @property
    def supports_paged(self) -> bool:
        """Block-paged decode covers decoder-only token stacks: attention
        layers (global or sliding-window) page their KV through the block
        pool, and recurrent layers (mamba/rwkv6) carry fixed-size
        per-request state slots beside it.  The enc-dec/vision paths
        carry non-token caches — those stay on the slot engine."""
        cfg = self.cfg
        return (not cfg.is_encoder_decoder and cfg.frontend == "none"
                and all(k in ("attn", "attn_local", "mamba", "rwkv6")
                        for k in cfg.kinds_for_layers))

    @property
    def paged_has_state(self) -> bool:
        """Does the paged stack carry recurrent (non-KV) layer state?
        State cannot be rebuilt from cached blocks, so engines must
        disable radix prefix reuse and draft-rollback spec decoding."""
        return self.supports_paged and any(
            k in ("mamba", "rwkv6") for k in self.cfg.kinds_for_layers)

    def paged_live_window(self) -> Optional[int]:
        """Token window bounding every layer's KV residency, or None when
        some layer reads unboundedly far back (any global-attention
        layer).  When bounded, a request only ever needs
        ceil(W/block_size)+1 live blocks — engines may eagerly free
        blocks that have slid wholly out of the window."""
        cfg = self.cfg
        if not self.supports_paged:
            return None
        w = 1                                  # mamba/rwkv6: state, no KV
        for k in cfg.kinds_for_layers:
            if k == "attn":
                return None
            if k == "attn_local":
                if not cfg.sliding_window:
                    return None                # window 0 = global
                w = max(w, cfg.sliding_window)
        return w

    def pool_init(self, num_blocks: int, block_size: int,
                  dtype: Optional[str] = None, state_batch: int = 1):
        """Concrete block pools for every layer (pos lanes -1).  Block 0
        is the reserved null block — allocators must never hand it out.
        ``state_batch`` sizes the recurrent-state slot axis (engine rows
        plus one trash row); ignored by pure-attention stacks."""
        if not self.supports_paged:
            raise ValueError(f"{self.cfg.name}: paged decode unsupported "
                             "(needs a decoder-only token stack)")
        return tf.stack_pool_init(self.cfg, num_blocks, block_size,
                                  jnp.dtype(dtype or self.cfg.dtype),
                                  state_batch=state_batch)

    def prefill_paged(self, params, batch, pools, block_table, start_pos, *,
                      cache_max: int, seq_len=None, all_logits: bool = False,
                      state_rows=None):
        """Padding-masked position-offset prefill — the paged engine's
        single prefill entry (fresh prompts, preempt-resume, prefix-cache
        suffixes, and continuous-batching prefill chunks).
        ``batch["tokens"]`` (B,S) holds a ragged batch of uncached
        suffix chunks, right-padded up to a length bucket; row i's first
        token sits at absolute position ``start_pos`` (scalar, or (B,)
        int32 with one cursor per row) and ``seq_len`` (B,) int32 gives
        each row's valid length (None = all S valid).  The cached prefix
        KV — earlier chunks of the same prompt and/or prefix-cache
        matches — is read from ``pools`` through ``block_table``
        (0-padded to a block bucket; pool lanes at positions ``>=
        start_pos`` are masked per row so a COW block's diverged tail or
        a not-yet-written own-block lane can never win, and null blocks
        never validate).  -> (last-VALID-token logits, suffix caches
        sized ``cache_max`` whose padded lanes carry ``pos`` -1) —
        splice the caches into each row's physical blocks with one
        batched ``write_chunk_tokens`` scatter (single request:
        ``write_prefill_blocks``).

        ``all_logits=True`` returns (B,S,V) logits for every lane
        instead of the last-valid-token slice — the speculative-decode
        verify path needs per-position argmax over the whole window
        (padded lanes carry garbage; callers mask by ``seq_len``).

        ``state_rows`` (B,) int32 maps dispatch rows to recurrent-state
        slots (hybrid stacks); the returned caches for recurrent layers
        are chunk-exit states to scatter back via those rows."""
        cfg = self.cfg
        if not self.supports_paged:
            raise ValueError(f"{cfg.name}: paged prefill unsupported "
                             "(needs a decoder-only token stack)")
        s = batch["tokens"].shape[1]
        sp = jnp.asarray(start_pos, jnp.int32)
        # scalar cursor -> (S,); per-row (B,) cursors -> (B,S)
        positions = jnp.expand_dims(sp, -1) + jnp.arange(s, dtype=jnp.int32)
        positions = positions if positions.ndim == 2 else \
            positions.reshape(s)
        posc = jnp.minimum(positions, cfg.max_position - 1) if (
            cfg.pos_kind == "learned") else positions
        x = self._embed_tokens(params, batch["tokens"],
                               posc if posc.ndim == 2 else posc[None])
        x, caches = tf.stack_prefill_paged(params["stack"], cfg, x, posc,
                                           pools, block_table, start_pos,
                                           cache_max, seq_len=seq_len,
                                           state_rows=state_rows)
        x = norm_apply(params["final_norm"], x, cfg.norm_kind, cfg.norm_eps)
        if all_logits:
            return unembed_apply(params["embed"], cfg, x), caches
        if seq_len is None:
            last = x[:, -1:, :]
        else:
            idx = (jnp.asarray(seq_len, jnp.int32) - 1)[:, None, None]
            last = jnp.take_along_axis(x, idx, axis=1)
        logits = unembed_apply(params["embed"], cfg, last)
        return logits, caches

    def decode_step_paged(self, params, pools, block_table, tokens, pos,
                          active, *, decode_kernel=None):
        """Paged one-token step.  tokens (B,1) int32, pos (B,) absolute
        position, block_table (B, nb) int32, active (B,) bool.
        ``decode_kernel``: True = Pallas paged-attention kernel, False =
        jnp block gather, None = follow the global kernel switch.
        -> (logits, new_pools)."""
        cfg = self.cfg
        posc = jnp.minimum(pos, cfg.max_position - 1) if (
            cfg.pos_kind == "learned") else pos
        x = self._embed_tokens(params, tokens, posc[:, None])
        x, pools = tf.stack_decode_paged(params["stack"], cfg, x, pools,
                                         block_table, posc, active,
                                         decode_kernel=decode_kernel)
        x = norm_apply(params["final_norm"], x, cfg.norm_kind, cfg.norm_eps)
        logits = unembed_apply(params["embed"], cfg, x)
        return logits, pools

    # ------------------------------------------------------------ abstract
    def input_specs(self, shape: InputShape, dtype: Optional[str] = None
                    ) -> Dict[str, Any]:
        """Abstract batch for ``jax.jit(...).lower()`` — no allocation."""
        cfg = self.cfg
        dt = jnp.dtype(dtype or cfg.dtype)
        b = shape.global_batch
        n_front = fe.frontend_tokens(cfg)
        if shape.mode == "decode":
            specs = {
                "tokens": jax.ShapeDtypeStruct((b, 1), jnp.int32),
                "pos": jax.ShapeDtypeStruct((b,), jnp.int32),
                "caches": self.cache_abstract(b, shape.seq_len),
            }
            return specs
        s = shape.seq_len - (n_front if cfg.frontend == "vision" else 0)
        specs = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32)}
        if shape.mode == "train":
            specs["labels"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
        if n_front:
            specs["embeds"] = jax.ShapeDtypeStruct(
                (b, n_front, fe.embed_dim(cfg)), dt)
        return specs

    def cache_abstract(self, batch: int, cache_max: int,
                       dtype: Optional[str] = None):
        cfg = self.cfg
        dt = dtype or cfg.dtype
        if cfg.is_encoder_decoder:
            hd = cfg.resolved_head_dim
            out = {}
            for i in range(cfg.num_layers):
                out[f"layer{i}"] = {
                    "self": tf.block_cache_abstract(cfg, "attn", batch,
                                                    cache_max, dt),
                    "xk": jax.ShapeDtypeStruct(
                        (batch, cfg.encoder_frames, cfg.num_kv_heads, hd),
                        jnp.dtype(dt)),
                    "xv": jax.ShapeDtypeStruct(
                        (batch, cfg.encoder_frames, cfg.num_kv_heads, hd),
                        jnp.dtype(dt)),
                }
            return out
        return tf.stack_cache_abstract(cfg, batch, cache_max, dt)

    def cache_logical(self):
        cfg = self.cfg
        if cfg.is_encoder_decoder:
            cross = ("batch", "frames", "kv_heads", "head_dim")
            out = {}
            for i in range(cfg.num_layers):
                out[f"layer{i}"] = {
                    "self": tf.block_cache_logical(cfg, "attn"),
                    "xk": cross,
                    "xv": cross,
                }
            return out
        return tf.stack_cache_logical(cfg)

    def cache_pspecs(self, rules: Rules, batch: int, cache_max: int):
        logical = self.cache_logical()
        abstract = self.cache_abstract(batch, cache_max)

        def is_logical(x):
            return isinstance(x, tuple) and all(
                isinstance(e, (str, type(None))) for e in x)

        return jax.tree.map(
            lambda lg, ab: rules.spec(lg, ab.shape), logical, abstract,
            is_leaf=is_logical)

    # ------------------------------------------------------------ info
    def layer_signatures(self):
        return tf.signatures(self.cfg)


def make_model(cfg: ModelConfig) -> Model:
    return Model(cfg)

"""Work that a serving step requires, from the configuration's sizes.

The counts are of the algorithm, not of today's implementation:

* matmul FLOPs are 2 x (weights a token passes through) per token;
* attention reads, for a token at absolute position p, the p + 1 keys
  and values before and including it (causal), at 4 x heads x head_dim
  FLOPs per key per layer (scores and the value contraction);
* logits are counted only for lanes whose logits are used: one per
  prefill chunk (its last token) and one per decode row;
* bytes are every weight once per step (the tied embedding is read in
  full by the unembedding), the key/value cache of each row's live
  context once, the new tokens' keys/values written once, and the
  embedding rows of the step's tokens.  Nothing is counted for gathers
  padded to a bucket, for padded rows or lanes, or for copies of the
  pool that the implementation makes.

``cfg`` is a configuration file's dict (Hugging Face key names).
"""
from __future__ import annotations

BF16 = 2


def _dims(cfg):
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    return d, h, kv, hd, cfg["intermediate_size"], cfg["vocab_size"], \
        cfg["num_hidden_layers"]


def layer_matmul_params(cfg) -> int:
    """Weights a token passes through in one decoder layer's matmuls."""
    d, h, kv, hd, f, _, _ = _dims(cfg)
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f


def weight_bytes(cfg) -> int:
    """Every weight of the model, stored in bfloat16."""
    d, h, kv, hd, f, v, n = _dims(cfg)
    norms = 2 * d + (2 * hd if cfg.get("qk_norm") else 0)
    emb = v * d * (1 if cfg.get("tie_word_embeddings") else 2)
    return BF16 * (n * (layer_matmul_params(cfg) + norms) + emb + d)


def kv_bytes_per_token(cfg) -> int:
    d, h, kv, hd, f, v, n = _dims(cfg)
    return n * 2 * kv * hd * BF16


def row_work(cfg, start: int, n: int) -> tuple:
    """FLOPs and cache bytes of one row that processes ``n`` tokens at
    absolute positions ``[start, start + n)`` and takes the logits of its
    last token.  -> (flops, bytes), weights excluded."""
    d, h, kv, hd, f, v, layers = _dims(cfg)
    keys = n * start + n * (n + 1) // 2          # sum over p of (p + 1)
    flops = 2 * n * layers * layer_matmul_params(cfg) \
        + 4 * h * hd * layers * keys + 2 * d * v
    kvb = kv_bytes_per_token(cfg)
    bytes_ = start * kvb + n * kvb + n * d * BF16
    return flops, bytes_


def step_work(cfg, rows) -> tuple:
    """``rows``: iterable of (start, n) for every row of one step.
    -> (flops, bytes) the step requires."""
    flops, bytes_ = 0, weight_bytes(cfg)
    for start, n in rows:
        f, b = row_work(cfg, start, n)
        flops += f
        bytes_ += b
    return flops, bytes_


def least_time(flops: float, bytes_: float, peaks: dict) -> float:
    """Roofline bound: the larger of compute time and memory time."""
    return max(flops / peaks["flops_bf16"], bytes_ / peaks["hbm_bytes_per_s"])

"""phi4-mini-3.8b [dense] — partial-rotary LongRoPE SwiGLU GQA [arXiv:2412.08905].

Sizes from microsoft/Phi-4-mini-instruct ``config.json`` (``Phi3ForCausalLM``):
three quarters of each 128-dim head rotate (48 frequencies), under LongRoPE
scaling from a 4,096-token original context to 131,072.  The published
``short_factor`` array is not in this repository; the 1.0 factors below
stand in for it (same work, other numbers).  Contexts past 4,096 tokens
(the ``long_factor`` regime) are refused by the engines.
"""
from repro.configs.base import LongRoPE, ModelConfig

ROTARY_FREQS = 48       # 128 x 0.75 / 2

CONFIG = ModelConfig(
    name="phi4-mini-3.8b",
    arch_type="dense",
    source="https://huggingface.co/microsoft/Phi-4-mini-instruct/blob/main/config.json "
           "(arXiv:2412.08905): 32L d=3072 24H kv=8 d_ff=8192 vocab=200064",
    num_layers=32,
    d_model=3072,
    num_heads=24,
    num_kv_heads=8,
    head_dim=128,
    d_ff=8192,
    vocab_size=200_064,
    mlp_kind="swiglu",
    norm_kind="rmsnorm",
    norm_eps=1e-5,
    pos_kind="rope",
    rope_theta=10_000.0,
    partial_rotary_factor=0.75,
    rope_scaling=LongRoPE(short_factor=(1.0,) * ROTARY_FREQS,
                          original_max_position=4096),
    tie_embeddings=True,
    layer_kinds=("attn",),
    max_position=131_072,
)

"""counts.py against hand-worked steps of the configuration."""
import json

import counts
from tiny import CHIPBENCH


def cfg(name):
    return json.loads((CHIPBENCH / "configs" / f"{name}.json").read_text())


def test_qwen3_layer_and_weight_sizes():
    c = cfg("qwen3-0.6b")
    # q 1024*16*128 + k,v 2*1024*8*128 + o 16*128*1024 + mlp 3*1024*3072
    assert counts.layer_matmul_params(c) == 2097152 + 2097152 + 2097152 + 9437184
    layer = 15728640 + 2 * 1024 + 2 * 128
    assert counts.weight_bytes(c) == 2 * (28 * layer + 151936 * 1024 + 1024)
    assert counts.kv_bytes_per_token(c) == 28 * 2 * 8 * 128 * 2 == 114688


def test_qwen3_decode_step_by_hand():
    c = cfg("qwen3-0.6b")
    # two decode rows: tokens at positions 99 and 1999
    f, b = counts.step_work(c, [(99, 1), (1999, 1)])
    per_tok = 2 * 28 * 15728640
    attn = 4 * 16 * 128 * 28 * (100 + 2000)
    logits = 2 * 2 * 1024 * 151936
    assert f == 2 * per_tok + attn + logits
    assert b == counts.weight_bytes(c) + (99 + 1999 + 2) * 114688 + 2 * 2048


def test_qwen3_prefill_chunk_by_hand():
    c = cfg("qwen3-0.6b")
    per_layer = 1024 * 16 * 128 + 2 * 1024 * 8 * 128 + 16 * 128 * 1024 \
        + 3 * 1024 * 3072
    assert counts.layer_matmul_params(c) == per_layer
    # a 256-token chunk after a 1,024-token cached prefix: keys visible to
    # the chunk's tokens are 1025 .. 1280
    f, b = counts.row_work(c, 1024, 256)
    keys = sum(range(1025, 1281))
    assert f == 2 * 256 * 28 * per_layer + 4 * 16 * 128 * 28 * keys \
        + 2 * 1024 * 151936
    kv = 28 * 2 * 8 * 128 * 2
    assert b == 1024 * kv + 256 * kv + 256 * 1024 * 2


def test_least_time_takes_the_larger_bound():
    peaks = {"flops_bf16": 100.0, "hbm_bytes_per_s": 10.0}
    assert counts.least_time(1000, 50, peaks) == 10.0
    assert counts.least_time(100, 500, peaks) == 50.0

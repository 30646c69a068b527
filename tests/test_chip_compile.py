"""Ahead-of-time compiles for a described TPU v5e: the Pallas kernels and
the paged engine's fused step.

The TPU compiler is installed wherever libtpu is, even with no chip
attached, so these tests lower and compile each kernel for one chip of a
described ``v5e:2x2`` topology at the widths of the configs that use it.
They catch what interpret mode cannot: block shapes the TPU tiling rule
refuses, VMEM overuse, and compiles that do not finish.  Nothing runs, so
they say nothing about results or times.  The fused step's compile is
read for what the program moves: a step must update its KV pool in place.

The topology is described inside a module-scoped fixture, never at import:
only one process may load libtpu at a time, and every test worker imports
this file.  Keep every such compile in this one file.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.kernels.conv2d import conv2d
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_attention import paged_attention
from repro.kernels.rwkv6_scan import rwkv6_scan
from repro.models.api import Model
from repro.serving.server import PagedLLMEngine

pytestmark = pytest.mark.kernels


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else libtpu logs to /tmp
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no libtpu, or another process holds it
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


# qwen3-0.6b attention widths: 16 heads of 128, 8 KV heads
H, KV, HD = 16, 8, 128
BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


def test_paged_attention_compiles_qwen3_widths(one_chip, no_compile_cache):
    b, num_blocks, bs, nb = 8, 1025, 16, 64
    _compile(lambda q, k, v, kp, bt, pos: paged_attention(
                 q, k, v, kp, bt, pos),
             one_chip,
             ((b, H, HD), BF16),
             ((num_blocks, bs, KV, HD), BF16),
             ((num_blocks, bs, KV, HD), BF16),
             ((num_blocks, bs), I32),
             ((b, nb), I32),
             ((b,), I32))


def test_flash_attention_compiles_qwen3_widths(one_chip, no_compile_cache):
    s = 512
    _compile(lambda q, k, v: flash_attention(q, k, v, causal=True),
             one_chip,
             ((1, H, s, HD), BF16),
             ((1, KV, s, HD), BF16),
             ((1, KV, s, HD), BF16))


def test_rwkv6_scan_compiles_rwkv6_widths(one_chip, no_compile_cache):
    # rwkv6-1.6b: d_model 2048 in heads of 64 -> 32 heads; the model
    # feeds the scan float32
    b, h, s, hd = 1, 32, 256, 64
    _compile(lambda r, k, v, w, u: rwkv6_scan(r, k, v, w, u),
             one_chip,
             *[((b, h, s, hd), F32)] * 4,
             ((h, hd), F32))


@pytest.mark.parametrize("batch", [1, 32, 64])
def test_conv2d_compiles_mnist_cnn(batch, one_chip, no_compile_cache):
    # mnist-cnn: Conv2D 32x3x3 over 28x28x1 (batch 64 is the paper's)
    _compile(conv2d, one_chip,
             ((batch, 28, 28, 1), F32),
             ((3, 3, 1, 32), F32))


# the chat cell's pool (2,049 blocks of 16) at qwen3 widths, two layers
POOL_BLOCKS, BLOCK = 2049, 16


@pytest.mark.parametrize("rows,lanes,blocks", [
    (16, 1, 128),          # decode: 16 rows of one lane
    (2, 256, 128),         # mixed: two 256-lane chunks
], ids=["decode", "mixed"])
def test_fused_step_updates_pool_in_place(rows, lanes, blocks, one_chip,
                                          no_compile_cache):
    """The engine's fused step consumes the pool it is given: no
    instruction copies a whole pool leaf, and the compiled program
    aliases at least the pool's bytes from input to output."""
    model = Model(dataclasses.replace(get_config("qwen3-0.6b"),
                                      num_layers=2))

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0))))
    pools = on_chip(jax.eval_shape(
        lambda: model.pool_init(POOL_BLOCKS, BLOCK, state_batch=33)))
    eng = PagedLLMEngine(model, params, num_blocks=4, block_size=BLOCK,
                         max_batch=32, max_len=2560)
    i32 = [jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
           for s in ((rows, lanes), (rows, blocks), (rows,))]
    compiled = eng._prefill_verify.lower(
        params, {"tokens": i32[0]}, pools, i32[1], *[i32[2]] * 3,
        lanes).compile()

    leaves = jax.tree.leaves(pools)
    leaf_shapes = {f"[{','.join(map(str, a.shape))}]" for a in leaves}
    copies = re.findall(r"= \w+(\[[\d,]*\])\S* copy\(",
                        compiled.as_text())
    assert copies                     # the pattern reads this HLO
    assert not leaf_shapes & set(copies)
    pool_bytes = sum(a.size * np.dtype(a.dtype).itemsize for a in leaves)
    assert compiled.memory_analysis().alias_size_in_bytes >= pool_bytes

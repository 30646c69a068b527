"""Ahead-of-time compiles of the Pallas kernels for a described TPU v5e.

The TPU compiler is installed wherever libtpu is, even with no chip
attached, so these tests lower and compile each kernel for one chip of a
described ``v5e:2x2`` topology at the widths of the configs that use it.
They catch what interpret mode cannot: block shapes the TPU tiling rule
refuses, VMEM overuse, and compiles that do not finish.  Nothing runs, so
they say nothing about results or times.

The topology is described inside a module-scoped fixture, never at import:
only one process may load libtpu at a time, and every test worker imports
this file.  Keep every such compile in this one file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.conv2d import conv2d
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_attention import paged_attention
from repro.kernels.rwkv6_scan import rwkv6_scan

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

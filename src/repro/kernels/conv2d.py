"""MXU-blocked valid conv2d as a Pallas TPU kernel — the paper's CNN
hot-spot (Sec. II-C: Conv2D 32x3x3 over 28x28 MNIST).

TPU adaptation: the wrapper unfolds the KxK taps in XLA (im2col) into a
``(B*H_out*W_out, K*K*C_in)`` patch matrix, zero-pads its rows to a
multiple of the row block, and the kernel runs it as one row-blocked
``(block_rows, K*K*C_in) x (K*K*C_in, C_out)`` matmul per grid step with
the whole filter resident in VMEM.  Every block is 2-D with its last dim
equal to the full array dim, so it meets the TPU tiling rule at any
batch and compiles in seconds (tests/test_chip_compile.py).  An
image-blocked NHWC layout puts C_in = 1 on the lane axis, which pads
every pixel to a 128-lane tile; its compile did not finish within a
minute at batch 8 and up.  For MNIST the patch matrix is 9x the image
bytes (~24 KiB per image in float32).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, w_ref, o_ref):
    # HIGHEST: Mosaic's default runs float32 operands through the MXU at
    # bfloat16 precision (~3e-3 relative error on the chip)
    o_ref[...] = jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), w_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


def conv2d(x, w, *, block_rows: int = 1024, interpret: bool = False):
    """x (B,H,W,Cin) x w (KH,KW,Cin,Cout) -> (B,H',W',Cout), valid, stride 1."""
    b, h, wd, cin = x.shape
    kh, kw, _, cout = w.shape
    h_out, w_out = h - kh + 1, wd - kw + 1

    # (B, H', W', KH*KW*Cin), tap-major then channel — matches w's
    # (KH, KW, Cin) flattening below
    patches = jnp.concatenate(
        [x[:, i:i + h_out, j:j + w_out, :]
         for i in range(kh) for j in range(kw)], axis=-1)
    rows = b * h_out * w_out
    kdim = kh * kw * cin
    patches = patches.reshape(rows, kdim)
    block_rows = min(block_rows, -(-rows // 8) * 8)
    pad = (-rows) % block_rows
    if pad:
        patches = jnp.pad(patches, ((0, pad), (0, 0)))

    out = pl.pallas_call(
        _kernel,
        grid=((rows + pad) // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, kdim), lambda i: (i, 0)),
            pl.BlockSpec((kdim, cout), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, cout), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows + pad, cout), x.dtype),
        interpret=interpret,
    )(patches, w.reshape(kdim, cout))
    return out[:rows].reshape(b, h_out, w_out, cout)

"""LRN Pallas kernel — the Norm module (paper Table III, 'LRN').

Across-channel local response normalization (AlexNet / Caffe form), Eq. 6's
⟨M_I, T, S, α, β⟩ tuple:

    y = x / (k + (α/n) · Σ_{window n over channels} x²) ^ β

The FPGA module used 3 DSPs + LUT math at 269 MHz.  On the TPU the channel
window sum is one MXU matmul of x² against a banded C x C 0/1 matrix
(``band[c', c] = |c - c'| <= n/2``, built in-kernel from iotas), so no
lane-shifted slices are needed; the rest is a VPU elementwise pipeline.
The wrapper views the activation as (pixels, C) rows, and the grid runs
over row blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_BLOCK_ROWS = 512


def _lrn_kernel(x_ref, o_ref, *, local_size: int, alpha: float, beta: float,
                k: float):
    x = x_ref[...].astype(jnp.float32)       # (BR, C)
    c = x.shape[-1]
    src = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    dst = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    band = (jnp.abs(dst - src) <= local_size // 2).astype(jnp.float32)
    # float32 contraction: this is a sum, not a learned product, and one
    # bf16 pass would round each x² to 8 bits
    acc = jnp.dot(jnp.square(x), band, preferred_element_type=jnp.float32,
                  precision=jax.lax.Precision.HIGHEST)
    denom = jnp.power(k + (alpha / local_size) * acc, beta)
    o_ref[...] = (x / denom).astype(o_ref.dtype)


def lrn_pallas(
    x: jax.Array,
    *,
    local_size: int = 5,
    alpha: float = 1e-4,
    beta: float = 0.75,
    k: float = 2.0,
    interpret: bool = False,
) -> jax.Array:
    """x: (N, H, W, C) NHWC."""
    c = x.shape[-1]
    rows = x.size // c
    br = min(_BLOCK_ROWS, -(-rows // 8) * 8)
    padded = -(-rows // br) * br
    flat = jnp.pad(x.reshape(rows, c), ((0, padded - rows), (0, 0)))
    kernel = functools.partial(
        _lrn_kernel, local_size=local_size, alpha=alpha, beta=beta, k=k)
    out = pl.pallas_call(
        kernel,
        grid=(padded // br,),
        in_specs=[pl.BlockSpec((br, c), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((padded, c), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(flat)
    return out[:rows].reshape(x.shape)

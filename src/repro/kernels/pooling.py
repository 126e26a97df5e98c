"""Pooling Pallas kernel — the Pool module (paper Table III, 'Pooling').

The FPGA module was a comparator tree at 304.5 MHz with zero DSPs; the TPU
analogue is a VPU reduction.  Per-image grid.  The stride is taken out of
the kernel: the wrapper splits the image into its ``s x s`` stride phases,

    phases[p, q, a, b, c] = x[s*a + p, s*b + q, c],

so window tap (i, j) of every output is the unit-stride slice
``phases[i % s, j % s, i//s : i//s + OH, j//s : j//s + OW]``.  The taps are
unrolled statically and reduced with max / add.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _pool_kernel(x_ref, o_ref, *, window: int, stride: int, oh: int, ow: int,
                 pool_type: str):
    # x_ref (1, s, s, Hs, Ws, C) stride phases; o_ref (1, OH, OW, C)
    s = stride
    out = None
    for i in range(window):
        for j in range(window):
            tap = x_ref[0, i % s, j % s, i // s:i // s + oh,
                        j // s:j // s + ow]
            if pool_type == "max":
                out = tap if out is None else jnp.maximum(out, tap)
            else:
                tap = tap.astype(jnp.float32)
                out = tap if out is None else out + tap
    if pool_type != "max":
        out = out / (window * window)
    o_ref[0] = out.astype(o_ref.dtype)


def pool_pallas(
    x: jax.Array,
    *,
    window: int = 3,
    stride: int = 2,
    pool_type: str = "max",
    interpret: bool = False,
) -> jax.Array:
    """x: (N, H, W, C) NHWC, VALID padding."""
    n, h, w, c = x.shape
    s = stride
    oh = (h - window) // s + 1
    ow = (w - window) // s + 1
    # phase extents: every tap slice [i//s, i//s + OH) must fit; the padded
    # cells are never read by a VALID window
    hs = max(-(-h // s), oh + (window - 1) // s)
    ws = max(-(-w // s), ow + (window - 1) // s)
    xp = jnp.pad(x, ((0, 0), (0, hs * s - h), (0, ws * s - w), (0, 0)))
    xp = xp.reshape(n, hs, s, ws, s, c).transpose(0, 2, 4, 1, 3, 5)
    kernel = functools.partial(
        _pool_kernel, window=window, stride=s, oh=oh, ow=ow,
        pool_type=pool_type)
    return pl.pallas_call(
        kernel,
        grid=(n,),
        in_specs=[pl.BlockSpec((1, s, s, hs, ws, c),
                               lambda i: (i, 0, 0, 0, 0, 0))],
        out_specs=pl.BlockSpec((1, oh, ow, c), lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, oh, ow, c), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(xp)

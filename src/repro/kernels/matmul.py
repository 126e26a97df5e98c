"""Tiled MXU matmul Pallas kernel — the FC module (paper Table III, 'FC').

TPU-native design: grid (M/bm, N/bn, K/bk) with the K dimension innermost so
the fp32 accumulator tile stays resident in VMEM scratch across the K loop
(the 'revisiting' pattern).  Block shapes are multiples of the MXU's 128x128
systolic tile; default blocks keep the VMEM working set
bm*bk + bk*bn + bm*bn fp32 words well under the ~16 MiB budget.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _matmul_kernel(x_ref, w_ref, o_ref, acc_ref, *, nk: int, activation: str,
                   bias_ref=None):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], w_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(2) == nk - 1)
    def _finish():
        acc = acc_ref[...]
        if bias_ref is not None:
            acc = acc + bias_ref[...].astype(jnp.float32)
        if activation == "relu":
            acc = jnp.maximum(acc, 0.0)
        elif activation == "sigmoid":
            acc = jax.nn.sigmoid(acc)
        elif activation == "tanh":
            acc = jnp.tanh(acc)
        o_ref[...] = acc.astype(o_ref.dtype)


def matmul_pallas(
    x: jax.Array,
    w: jax.Array,
    bias: Optional[jax.Array] = None,
    *,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 512,
    activation: str = "none",
    interpret: bool = False,
) -> jax.Array:
    """(M, K) @ (K, N) [+ bias, activation].  Shapes must divide the blocks;
    `ops.matmul` pads arbitrary shapes to alignment and unpads the result."""
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (
        f"unaligned matmul {x.shape} @ {w.shape} with blocks {(bm, bn, bk)}")
    nk = k // bk

    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
    ]
    args = [x, w]
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)))
        args.append(bias.reshape(1, n))
        kernel = functools.partial(
            _matmul_with_bias_kernel, nk=nk, activation=activation)
    else:
        kernel = functools.partial(_matmul_kernel, nk=nk, activation=activation)

    return pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*args)


def _matmul_with_bias_kernel(x_ref, w_ref, b_ref, o_ref, acc_ref, *, nk: int,
                             activation: str):
    _matmul_kernel(x_ref, w_ref, o_ref, acc_ref, nk=nk, activation=activation,
                   bias_ref=b_ref)

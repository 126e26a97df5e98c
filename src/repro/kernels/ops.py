"""Jit'd public wrappers around the Pallas kernels.

Responsibilities:
* pad arbitrary shapes to kernel block alignment and unpad results;
* pick the execution mode from the backend: Mosaic (`interpret=False`) on a
  TPU, the Pallas interpreter on the CPU (tests), and an error anywhere
  else — a kernel never silently runs as something other than itself;
* expose a uniform signature the execution-engine registry
  (core/engines.py) can build against.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .conv2d import conv2d_pallas
from .flash_attention import flash_attention_pallas
from .lrn import lrn_pallas
from .matmul import matmul_pallas
from .paged_attention import paged_attention_pallas
from .pooling import pool_pallas


def default_interpret() -> bool:
    """Interpret mode on the CPU, compiled kernels on a TPU; any other
    backend has no Pallas TPU lowering and no business interpreting."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"Pallas TPU kernels cannot run on backend "
                       f"{backend!r}: use a TPU, or the CPU in interpret mode")


def _pad_to(x: jax.Array, axis: int, multiple: int) -> jax.Array:
    size = x.shape[axis]
    rem = (-size) % multiple
    if rem == 0:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, rem)
    return jnp.pad(x, pads)


@functools.partial(jax.jit, static_argnames=(
    "activation", "block_m", "block_n", "block_k", "interpret"))
def matmul(x: jax.Array, w: jax.Array, bias: Optional[jax.Array] = None, *,
           activation: str = "none", block_m: int = 256, block_n: int = 256,
           block_k: int = 512, interpret: Optional[bool] = None) -> jax.Array:
    """(M, K) @ (K, N) via the tiled MXU kernel; arbitrary shapes."""
    interpret = default_interpret() if interpret is None else interpret
    m, k = x.shape
    _, n = w.shape
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    # round blocks to hardware tiles where the problem allows
    xp = _pad_to(_pad_to(x, 0, bm), 1, bk)
    wp = _pad_to(_pad_to(w, 0, bk), 1, bn)
    bp = _pad_to(bias, 0, bn) if bias is not None else None
    out = matmul_pallas(xp, wp, bp, block_m=bm, block_n=bn, block_k=bk,
                        activation=activation, interpret=interpret)
    return out[:m, :n]


@functools.partial(jax.jit, static_argnames=(
    "stride", "padding", "activation", "interpret"))
def conv2d(x: jax.Array, w: jax.Array, bias: Optional[jax.Array] = None, *,
           stride: int = 1, padding: int = 0, activation: str = "none",
           interpret: Optional[bool] = None) -> jax.Array:
    interpret = default_interpret() if interpret is None else interpret
    return conv2d_pallas(x, w, bias, stride=stride, padding=padding,
                         activation=activation, interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "window", "stride", "pool_type", "interpret"))
def pool(x: jax.Array, *, window: int = 3, stride: int = 2,
         pool_type: str = "max", interpret: Optional[bool] = None) -> jax.Array:
    interpret = default_interpret() if interpret is None else interpret
    return pool_pallas(x, window=window, stride=stride, pool_type=pool_type,
                       interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "local_size", "alpha", "beta", "k", "interpret"))
def lrn(x: jax.Array, *, local_size: int = 5, alpha: float = 1e-4,
        beta: float = 0.75, k: float = 2.0,
        interpret: Optional[bool] = None) -> jax.Array:
    interpret = default_interpret() if interpret is None else interpret
    return lrn_pallas(x, local_size=local_size, alpha=alpha, beta=beta, k=k,
                      interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "block_q", "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    block_q: int = 512, block_k: int = 512,
                    interpret: Optional[bool] = None) -> jax.Array:
    """q: (B, HQ, S, D); k/v: (B, HK, T, D).  Pads S/T to block multiples."""
    interpret = default_interpret() if interpret is None else interpret
    b, hq, s, d = q.shape
    t = k.shape[2]
    bq, bk = min(block_q, s), min(block_k, t)
    sp, tp = s + (-s) % bq, t + (-t) % bk
    if sp != s or tp != t:
        # pad queries and keys at the END; padded query rows are sliced off,
        # and the kernel masks the padded keys past the real length t
        q = jnp.pad(q, ((0, 0), (0, 0), (0, sp - s), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, tp - t), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, tp - t), (0, 0)))
    out = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 block_q=bq, block_k=bk, kv_len=t,
                                 interpret=interpret)
    return out[:, :, :s, :]


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q: jax.Array, k_arena: jax.Array, v_arena: jax.Array,
                    block_tables: jax.Array, pos: jax.Array, *,
                    interpret: Optional[bool] = None,
                    steps=None) -> jax.Array:
    """Paged decode attention: q (B, HQ, 1, D) against block arenas
    (TB, HK, BS, D) read through (B, NB) block tables, live pages only
    (``pos < 0``: an empty slot).  ``steps``: the kernel's step list
    (``paged_attention.paged_attention_steps``) when the caller shares one
    across layers."""
    interpret = default_interpret() if interpret is None else interpret
    return paged_attention_pallas(q, k_arena, v_arena, block_tables, pos,
                                  interpret=interpret, steps=steps)


# convenience: FC layer matching the paper's Eq. 1 (vector-matrix + f)
def fc(x: jax.Array, w: jax.Array, b: Optional[jax.Array] = None, *,
       activation: str = "none", interpret: Optional[bool] = None) -> jax.Array:
    if x.ndim > 2:
        x = x.reshape(x.shape[0], -1)
    if activation == "softmax":  # softmax handled outside the MXU kernel
        y = matmul(x, w, b, activation="none", interpret=interpret)
        return jax.nn.softmax(y, axis=-1)
    return matmul(x, w, b, activation=activation, interpret=interpret)

"""Conv2D Pallas kernel — the Conv module (paper Table III, 'Conv Layer').

TPU-native rethink of the FPGA line-buffer + systolic MAC array.  The
kernel only ever runs a stride-1 'VALID' convolution, and it takes that
convolution as KH accumulated MXU matmuls per image — one per kernel row:

    acc += x[i : i + OH] (OH*OWp, KW*IC)  @  w[i] (KW*IC, OC_block)

Everything the TPU's vector layout cannot do cheaply happens in the jitted
wrapper, as plain XLA data movement:

* a stride ``s > 1`` becomes a space-to-depth rearrangement: the input is
  regrouped into ``s*s`` phases on the channel axis and the filters are
  regrouped to match, giving the equal stride-1 convolution with a
  ``ceil(KH/s) x ceil(KW/s)`` kernel (AlexNet Conv1: 11x11/4 on 224² x 3 ->
  3x3/1 on 57² x 48);
* the KW column taps are laid side by side on the channel axis ("width
  im2col"), so the kernel slices the image only along its leading row axis;
* the output width is padded to the sublane tile (8 rows for 4-byte
  dtypes, 16 for 2-byte), so folding (rows, width) into the matmul's M
  dimension is a layout no-op; the wrapper drops the padded columns.

Grid is (batch, OC blocks): the image block is revisited across the OC
blocks without another DMA, and 128-wide OC blocks keep the filter tile of
every AlexNet layer inside the VMEM budget.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _sublanes(dtype_bytes: int) -> int:
    return 32 // dtype_bytes            # (8, 128) f32 tile, (16, 128) bf16


def _conv2d_kernel(x_ref, w_ref, b_ref, o_ref, *, kh: int, oh: int,
                   activation: str):
    # x_ref (1, OH+KH-1, OWp, KW*IC); w_ref (KH, KW*IC, BOC); b_ref (1, BOC)
    owp, kc = x_ref.shape[2], x_ref.shape[3]
    acc = None
    for i in range(kh):                  # static unroll: one row tap each
        rows = x_ref[0, i:i + oh].reshape(oh * owp, kc)
        part = jnp.dot(rows, w_ref[i], preferred_element_type=jnp.float32)
        acc = part if acc is None else acc + part
    acc = acc + b_ref[...].astype(jnp.float32)
    if activation == "relu":
        acc = jnp.maximum(acc, 0.0)
    elif activation == "sigmoid":
        acc = jax.nn.sigmoid(acc)
    elif activation == "tanh":
        acc = jnp.tanh(acc)
    o_ref[0] = acc.reshape(oh, owp, -1).astype(o_ref.dtype)


def _space_to_depth(x: jax.Array, w: jax.Array, stride: int):
    """The stride-1 equivalent of a stride-``s`` convolution.

    x: (N, H, W, C); w: (OC, IC, KH, KW).  Returns x' of shape
    (N, ceil(H/s), ceil(W/s), s*s*C) and w' of shape
    (OC, s*s*IC, ceil(KH/s), ceil(KW/s)) with
    ``x'[n, a, b, (p, q, c)] = x[n, s*a + p, s*b + q, c]`` and
    ``w'[o, (p, q, c), u, v] = w[o, c, s*u + p, s*v + q]`` (zero past KH/KW),
    so the VALID stride-1 conv of (x', w') holds every output of the
    original in its leading rows and columns.
    """
    s = stride
    n, h, wd, c = x.shape
    oc, ic, kh, kw = w.shape
    hs, ws, ku, kv = -(-h // s), -(-wd // s), -(-kh // s), -(-kw // s)
    x = jnp.pad(x, ((0, 0), (0, hs * s - h), (0, ws * s - wd), (0, 0)))
    x = x.reshape(n, hs, s, ws, s, c).transpose(0, 1, 3, 2, 4, 5)
    w = jnp.pad(w, ((0, 0), (0, 0), (0, ku * s - kh), (0, kv * s - kw)))
    w = w.reshape(oc, ic, ku, s, kv, s).transpose(0, 3, 5, 1, 2, 4)
    return (x.reshape(n, hs, ws, s * s * c),
            w.reshape(oc, s * s * ic, ku, kv))


def _oc_block(oc: int) -> int:
    return _LANES if oc % _LANES == 0 else oc


def conv2d_pallas(
    x: jax.Array,
    w: jax.Array,
    bias: Optional[jax.Array] = None,
    *,
    stride: int = 1,
    padding: int = 0,
    activation: str = "none",
    interpret: bool = False,
) -> jax.Array:
    """x: (N, H, W, IC); w: (OC, IC, KH, KW) — Table I layout.  Returns NHWC."""
    n, h, wdt, ic = x.shape
    oc, ic2, kh, kw = w.shape
    assert ic == ic2, (x.shape, w.shape)
    if padding:
        x = jnp.pad(x, ((0, 0), (padding, padding), (padding, padding), (0, 0)))
        h, wdt = h + 2 * padding, wdt + 2 * padding
    oh = (h - kh) // stride + 1
    ow = (wdt - kw) // stride + 1
    if stride > 1:
        x, w = _space_to_depth(x, w, stride)
        _, ic, kh, kw = w.shape
    # width im2col: column tap j of output column c is input column c + j;
    # pad the output width to the sublane tile so (rows, width) folds into M
    owp = _round_up(ow, _sublanes(x.dtype.itemsize))
    rows = oh + kh - 1
    x = x[:, :rows]
    need_w = owp + kw - 1
    if x.shape[2] < need_w:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, need_w - x.shape[2]), (0, 0)))
    xw = jnp.concatenate([x[:, :, j:j + owp, :] for j in range(kw)], axis=-1)
    # (OC, IC, KH, KW) -> (KH, KW, IC, OC) -> (KH, KW*IC, OC): tap-major rows
    w_rows = jnp.transpose(w, (2, 3, 1, 0)).reshape(kh, kw * ic, oc)
    if bias is None:
        bias = jnp.zeros((oc,), dtype=jnp.float32)
    boc = _oc_block(oc)

    kernel = functools.partial(_conv2d_kernel, kh=kh, oh=oh,
                               activation=activation)
    out = pl.pallas_call(
        kernel,
        grid=(n, oc // boc),
        in_specs=[
            pl.BlockSpec((1, rows, owp, kw * ic), lambda i, j: (i, 0, 0, 0)),
            pl.BlockSpec((kh, kw * ic, boc), lambda i, j: (0, 0, j)),
            pl.BlockSpec((1, boc), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, oh, owp, boc), lambda i, j: (i, 0, 0, j)),
        out_shape=jax.ShapeDtypeStruct((n, oh, owp, oc), xw.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(xw, w_rows, bias.reshape(1, oc))
    return out[:, :, :ow]


def conv2d_vmem_bytes(h: int, w: int, ic: int, oc: int, kh: int, kw: int,
                      stride: int, dtype_bytes: int = 4) -> int:
    """Static VMEM working-set estimate for the Table III resource analogue:
    the double-buffered image, filter and output blocks plus the fp32
    accumulator and one row-tap slice, lanes padded to 128.  ``h``/``w``
    are the padded input size."""
    oh = (h - kh) // stride + 1
    ow = (w - kw) // stride + 1
    if stride > 1:
        ic, kh, kw = stride * stride * ic, -(-kh // stride), -(-kw // stride)
    owp = _round_up(ow, _sublanes(dtype_bytes))
    kc = _round_up(kw * ic, _LANES)
    boc = _round_up(_oc_block(oc), _LANES)
    x_bytes = (oh + kh - 1) * owp * kc * dtype_bytes
    w_bytes = kh * kc * boc * dtype_bytes
    out_bytes = oh * owp * boc * dtype_bytes
    acc_bytes = oh * owp * boc * 4
    tap_bytes = oh * owp * kc * dtype_bytes
    return 2 * (x_bytes + w_bytes + out_bytes) + acc_bytes + tap_bytes

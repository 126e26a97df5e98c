"""Flash attention Pallas kernel (beyond-paper engine for the LM archs).

Online-softmax tiling over KV blocks so 32k-token prefill never materializes
the (S, T) score matrix in HBM.  TPU-native choices:

* grid (B*HQ, S/bq, T/bk) with the KV dimension innermost ('arbitrary'),
  running max / denominator / output accumulator in VMEM scratch — the same
  revisiting pattern as the matmul kernel;
* GQA handled in the BlockSpec index maps (each query head reads its
  kv-group's block; KV is never repeated in HBM);
* causal and sliding-window masking by block predicate: blocks entirely
  outside the mask are skipped (`pl.when`), the diagonal blocks mask
  elementwise with broadcasted_iota;
* m/l scratch kept (bq, 128) lane-replicated, the canonical TPU layout.

Used for training and prefill (S == T).  Decode (S == 1) uses the pure-JAX
dot attention in models/ — a 1-row matmul gains nothing from tiling.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_LANES = 128


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  nk: int, bq: int, bk: int, scale: float, causal: bool,
                  window: Optional[int], kv_len: Optional[int]):
    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # block-level skip: entirely above the causal diagonal or left of window
    run = jnp.bool_(True)
    if causal:
        run &= ik * bk <= iq * bq + bq - 1
    if window is not None:
        run &= (ik + 1) * bk - 1 >= iq * bq - (window - 1)
    if kv_len is not None:
        run &= ik * bk < kv_len

    @pl.when(run)
    def _body():
        q = q_ref[...][0].astype(jnp.float32)          # (bq, d)
        k = k_ref[...][0].astype(jnp.float32)          # (bk, d)
        v = v_ref[...][0].astype(jnp.float32)          # (bk, d)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

        qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), dtype=bool)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        if kv_len is not None:
            mask &= kpos < kv_len
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_scr[:, :1]                           # (bq, 1)
        l_prev = l_scr[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)      # (bq, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new) * mask                   # re-mask kills exp(0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ik == nk - 1)
    def _finish():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_scr[...] / l_safe)[None].astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 512,
    kv_len: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """q: (B, HQ, S, D); k/v: (B, HK, T, D) with HQ % HK == 0.  S % bq == 0,
    T % bk == 0 (ops.py pads otherwise).  ``kv_len`` < T masks the padded
    key rows past it.  Returns (B, HQ, S, D)."""
    b, hq, s, d = q.shape
    _, hk, t, _ = k.shape
    assert hq % hk == 0, (hq, hk)
    group = hq // hk
    bq, bk = min(block_q, s), min(block_k, t)
    assert s % bq == 0 and t % bk == 0, (s, t, bq, bk)
    scale = scale if scale is not None else 1.0 / (d ** 0.5)

    qf = q.reshape(b * hq, s, d)
    kf = k.reshape(b * hk, t, d)
    vf = v.reshape(b * hk, t, d)

    def kv_index(bh, iq, ik):
        batch, qh = bh // hq, bh % hq
        return (batch * hk + qh // group, ik, 0)

    kernel = functools.partial(
        _flash_kernel, nk=t // bk, bq=bq, bk=bk, scale=scale, causal=causal,
        window=window, kv_len=kv_len if kv_len is not None and kv_len < t
        else None)
    out = pl.pallas_call(
        kernel,
        grid=(b * hq, s // bq, t // bk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, bk, d), kv_index),
            pl.BlockSpec((1, bk, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, hq, s, d)

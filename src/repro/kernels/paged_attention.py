"""Paged-attention decode Pallas kernel (vLLM-style block-table gather).

The serving engine's paged KV layout stores each layer's K/V in one
``(total_blocks + 1, n_kv_heads, block_size, head_dim)`` arena; a slot's
logical sequence is scattered across non-contiguous physical blocks named
by its block table.  This kernel attends a single decode query against
that layout WITHOUT materializing the dense per-slot row in HBM: the block
table is a scalar-prefetch operand, so each grid step's BlockSpec index
map dereferences ``block_tables[slot, j]`` and the DMA engine fetches
exactly one physical KV page into VMEM per step.  Online softmax (running
max / denominator / accumulator in VMEM scratch, same revisiting pattern
as kernels/flash_attention.py) folds the pages together.

TPU-native choices:

* grid (B * HK, NB) with the block dimension innermost ('arbitrary');
  GQA query groups ride along as rows of the (G, D) q tile, so KV is
  fetched once per kv head, never repeated;
* blocks past the slot's position are skipped wholesale (`pl.when` on the
  block start), the boundary block masks elementwise with
  broadcasted_iota;
* arena pages are (block_size, head_dim) tiles — block_size >= 8 keeps
  fp32 sublane alignment.

The pure-jnp oracle is kernels/ref.py:paged_attention_ref (gather through
the table, then dense decode attention); it is also what the serving
engine runs on CPU, where bit-identity with the dense KV path is asserted.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_LANES = 128


def _paged_kernel(bt_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, hk: int, nb: int, bs: int,
                  scale: float):
    bh, j = pl.program_id(0), pl.program_id(1)
    b = bh // hk
    p = pos_ref[b]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # skip blocks entirely past the slot's current position
    @pl.when(j * bs <= p)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)            # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)            # (bs, D)
        v = v_ref[0, 0].astype(jnp.float32)            # (bs, D)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        g = q.shape[0]
        kpos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (g, bs), 1)
        mask = kpos <= p
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_scr[:, :1]                          # (G, 1)
        l_prev = l_scr[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        pprob = jnp.exp(s - m_new) * mask              # re-mask kills exp(0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(pprob, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
            pprob, v, preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == nb - 1)
    def _finish():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_scr[...] / l_safe)[None, None].astype(o_ref.dtype)


def paged_attention_pallas(
    q: jax.Array,
    k_arena: jax.Array,
    v_arena: jax.Array,
    block_tables: jax.Array,
    pos: jax.Array,
    *,
    scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    """q: (B, HQ, 1, D); arenas: (TB, HK, BS, D) with HQ % HK == 0;
    block_tables: (B, NB) int32; pos: (B,) int32.  Returns (B, HQ, 1, D).

    Entries of ``block_tables`` past a slot's written blocks may be any
    valid arena index (the position mask hides them); the trailing trash
    page convention of the serving arena satisfies that for free.
    """
    b, hq, s1, d = q.shape
    assert s1 == 1, "paged decode kernel is single-query (decode step)"
    tb, hk, bs, _ = k_arena.shape
    assert hq % hk == 0, (hq, hk)
    group = hq // hk
    nb = block_tables.shape[1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)

    qg = q.reshape(b, hk, group, d)                    # kv-grouped queries
    bt = block_tables.astype(jnp.int32)
    pos32 = pos.astype(jnp.int32)

    def q_index(bh, j, bt_ref, pos_ref):
        del j, bt_ref, pos_ref
        return (bh // hk, bh % hk, 0, 0)

    def kv_index(bh, j, bt_ref, pos_ref):
        del pos_ref
        return (bt_ref[bh // hk, j], bh % hk, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                         # block_tables, pos
        grid=(b * hk, nb),
        in_specs=[
            pl.BlockSpec((1, 1, group, d), q_index),
            pl.BlockSpec((1, 1, bs, d), kv_index),
            pl.BlockSpec((1, 1, bs, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, 1, group, d), q_index),
        scratch_shapes=[
            pltpu.VMEM((group, _LANES), jnp.float32),
            pltpu.VMEM((group, _LANES), jnp.float32),
            pltpu.VMEM((group, d), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_kernel, hk=hk, nb=nb, bs=bs,
                               scale=scale)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hk, group, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(bt, pos32, qg, k_arena, v_arena)
    return out.reshape(b, hq, 1, d)

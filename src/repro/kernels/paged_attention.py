"""Paged-attention decode Pallas kernel: live pages only, all heads of a
page at once.

The serving engine's paged KV layout stores each layer's K/V in one
``(total_blocks + 1, n_kv_heads, block_size, head_dim)`` arena; a slot's
logical sequence is scattered across non-contiguous physical pages named
by its block table.  This kernel attends each slot's single decode query
against that layout without materializing a dense row in HBM:

* the work is a list of (slot, chunk) steps, built from the positions
  before the call: a chunk is ``P`` consecutive logical pages of one
  slot, and a slot has ``ceil((pos + 1) / (P * BS))`` chunks, or one empty
  step when ``pos < 0``.  The grid runs over that list, so its length
  follows the live context, not the block table's width;
* each step brings in ``P`` whole pages ``(HK, BS, D)`` of K and of V
  through ``P`` block specs whose index maps read a scalar-prefetched
  page list.  A page past the slot's position repeats the index its
  operand had the step before, so the pipeline copies nothing for it and
  its rows hold a page already read; the scores of the chunk's rows past
  the position are masked, so their probabilities are exactly 0;
* ``P`` comes from the page's bytes (:func:`pages_per_chunk`), so a step
  moves about :data:`CHUNK_BYTES` of each arena whatever the model's head
  geometry;
* the pipeline fetches the next step's pages (the same slot's next chunk
  or the next slot's first) while the current ones are folded into an
  online softmax held in f32 in VMEM.

Manual page copies (``make_async_copy`` from an ``ANY``-space arena)
would need no page list, but Mosaic refuses to slice an HBM arena whose
minor dimension is narrower than the 128-lane tile (MiniCPM's head_dim
64); block specs move such pages whole.

Products: K and V enter in their arena dtype and accumulate in f32.  With
GQA (``G = HQ / HK > 1``) the query group rides as the rows of a
``(HK, G, D)`` tile into head-batched MXU products; the probabilities go
into the V product as two bf16 terms (``p = hi + lo``), so they are not
rounded to bf16 as the reference rounds them.  With MHA (``G == 1``) the
scores are a head-batched MXU product with the chunk's tokens as rows,
and the V product runs on the VPU in f32.

A slot with ``pos < 0`` is empty: nothing is read and its output is zeros.

The pure-jnp oracle is kernels/ref.py:paged_attention_ref (gather through
the table, then dense decode attention); it is also what the serving
engine runs on the CPU, where bit-identity with the dense KV path is
asserted.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_LANES = 128

# bytes of one arena (K or V) a step should move: large enough that the
# per-step fixed cost (pipeline bookkeeping, a softmax rescale) is small
# beside the copy, small enough that a short context wastes little.  On a
# TPU v5e, 256 KiB read the benchmark's live contexts 11-25% faster than
# 64-128 KiB for MiniCPM's 72 KiB pages and no slower for qwen's 8 KiB ones
CHUNK_BYTES = 256 * 1024


def pages_per_chunk(page_bytes: int, n_pages: int) -> int:
    """Pages a step copies: ``ceil(CHUNK_BYTES / page_bytes)``, at least
    one and at most the block table's width ``n_pages``."""
    return max(1, min(n_pages, -(-CHUNK_BYTES // page_bytes)))


def live_pages(pos: jax.Array, block_size: int, n_pages: int) -> jax.Array:
    """Pages a slot at position ``pos`` attends: ``ceil((pos + 1) / BS)``,
    0 for an empty slot (``pos < 0``)."""
    return jnp.clip(pos // block_size + 1, 0, n_pages)


def _arena_ppc(k_arena, n_pages: int) -> int:
    _, hk, bs, d = k_arena.shape[-4:]
    return pages_per_chunk(hk * bs * d * k_arena.dtype.itemsize, n_pages)


def paged_attention_steps(k_arena, block_tables, pos):
    """The kernel's step list for these positions: what the grid walks.

    Depends only on the positions, the table and the arena's page shape,
    so a model step computes it once and hands it to every layer's call
    (``steps=``) instead of rebuilding it per layer.  ``k_arena`` may carry
    leading layer axes."""
    bs = k_arena.shape[-2]
    return _step_list(pos.astype(jnp.int32),
                      block_tables.astype(jnp.int32), bs=bs,
                      ppc=_arena_ppc(k_arena, block_tables.shape[1]))


def _step_list(pos, block_tables, *, bs: int, ppc: int):
    """The (slot, chunk) steps and the pages each step brings in.

    Returns ``(n_steps, slot_of, chunk_of, pages)``: ``slot_of``/
    ``chunk_of`` have ``B * ceil(NB / P)`` entries (the most steps any
    positions need), of which the first ``n_steps`` are live; ``pages``
    is the flattened ``(steps, P)`` physical page of each operand, with a
    dead page repeating its operand's previous page (no copy)."""
    b, nb = block_tables.shape
    cap = b * -(-nb // ppc)
    live = live_pages(pos, bs, nb)
    steps = jnp.maximum(-(-live // ppc), 1)            # an empty slot: 1
    ends = jnp.cumsum(steps)
    t = jnp.arange(cap, dtype=jnp.int32)
    slot_of = jnp.minimum(jnp.sum(ends[None, :] <= t[:, None], axis=1),
                          b - 1).astype(jnp.int32)
    chunk_of = (t - (ends - steps)[slot_of]).astype(jnp.int32)
    page = chunk_of[:, None] * ppc + jnp.arange(ppc, dtype=jnp.int32)
    keep = (page < live[slot_of][:, None]) & (t < ends[-1])[:, None]
    phys = block_tables[slot_of[:, None], jnp.minimum(page, nb - 1)]
    # a dead operand repeats the page it held at its last live step (the
    # pipeline skips a copy whose index did not change); before any live
    # step it takes the first operand's page
    last = jax.lax.cummax(jnp.where(keep, t[:, None], -1), axis=0)
    held = jnp.take_along_axis(phys, jnp.maximum(last, 0), axis=0)
    pages = jnp.where(last >= 0, held, phys[:, :1])
    return ends[-1], slot_of, chunk_of, pages.reshape(-1)


def _exact(dtype):
    """The MXU precision that keeps products of ``dtype`` operands exact
    in f32, whatever ``jax.default_matmul_precision`` the caller set:
    bf16 products are exact in one pass (and Mosaic takes no other
    precision for bf16 operands); f32 operands need the f32 passes."""
    return (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)


def _before(shape, axis, first, pos):
    """Mask of the chunk's tokens at or before ``pos`` along ``axis``
    (token ``first`` at index 0), built at the shape it is used at."""
    return first + jax.lax.broadcasted_iota(jnp.int32, shape, axis) <= pos


def _fold_gqa(q, k, v, first, pos, scale, m_prev, l_prev, acc):
    """Online-softmax fold of one chunk for G > 1 (MXU products)."""
    s = jnp.einsum("hgd,htd->hgt", q.astype(k.dtype), k,
                   preferred_element_type=jnp.float32,
                   precision=_exact(k.dtype)) * scale
    s = jnp.where(_before(s.shape, 2, first, pos), s, _NEG_INF)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + jnp.sum(p, axis=2, keepdims=True)
    p_hi = p.astype(v.dtype)
    p_lo = (p - p_hi.astype(jnp.float32)).astype(v.dtype)
    pv = (jnp.einsum("hgt,htd->hgd", p_hi, v,
                     preferred_element_type=jnp.float32,
                     precision=_exact(v.dtype))
          + jnp.einsum("hgt,htd->hgd", p_lo, v,
                       preferred_element_type=jnp.float32,
                       precision=_exact(v.dtype)))
    return m_new, l_new, alpha * acc + pv


def _fold_mha(q, k, v, first, pos, scale, m_prev, l_prev, acc):
    """Online-softmax fold of one chunk for G == 1: scores with the chunk's
    tokens as rows (one column per head), V product on the VPU in f32."""
    s = jnp.einsum("htd,hgd->htg", k, q.astype(k.dtype),
                   preferred_element_type=jnp.float32,
                   precision=_exact(k.dtype)) * scale
    s = jnp.where(_before(s.shape, 1, first, pos), s, _NEG_INF)   # (HK,T,1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    pv = jnp.sum(p * v.astype(jnp.float32), axis=1, keepdims=True)
    return m_new, l_new, alpha * acc + pv


def _paged_kernel(n_ref, slot_ref, chunk_ref, pages_ref, pos_ref, q_ref,
                  *refs, nb: int, bs: int, ppc: int, scale: float):
    k_refs, v_refs = refs[:ppc], refs[ppc:2 * ppc]
    o_ref, m_scr, l_scr, acc_scr = refs[2 * ppc:]
    del pages_ref
    t = pl.program_id(0)
    slot, chunk = slot_ref[t], chunk_ref[t]
    pos = pos_ref[slot]
    n_live = live_pages(pos, bs, nb)
    n_chunks = jnp.maximum(-(-n_live // ppc), 1)
    in_list = t < n_ref[0]

    @pl.when(in_list & (chunk == 0))
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(in_list & (chunk * ppc < n_live))
    def _fold():
        k = jnp.concatenate([r[0] for r in k_refs], axis=1)   # (HK, T, D)
        v = jnp.concatenate([r[0] for r in v_refs], axis=1)
        first = chunk * k.shape[1]                     # first token
        q = q_ref[0]                                   # (HK, G, D)
        if q.shape[1] == 1:
            m_prev, l_prev = m_scr[:, :1, :1], l_scr[:, :1, :1]
            m_new, l_new, acc = _fold_mha(q, k, v, first, pos, scale, m_prev,
                                          l_prev, acc_scr[...])
        else:
            m_prev, l_prev = m_scr[:, :, :1], l_scr[:, :, :1]
            m_new, l_new, acc = _fold_gqa(q, k, v, first, pos, scale, m_prev,
                                          l_prev, acc_scr[...])
        acc_scr[...] = acc
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(in_list & (chunk == n_chunks - 1))
    def _finish():
        l = l_scr[:, :, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)


def paged_attention_pallas(
    q: jax.Array,
    k_arena: jax.Array,
    v_arena: jax.Array,
    block_tables: jax.Array,
    pos: jax.Array,
    *,
    scale: float | None = None,
    interpret: bool = False,
    steps=None,
) -> jax.Array:
    """q: (B, HQ, 1, D); arenas: (TB, HK, BS, D) with HQ % HK == 0;
    block_tables: (B, NB) int32; pos: (B,) int32, ``< 0`` for an empty
    slot.  Returns (B, HQ, 1, D).  ``steps``: the
    :func:`paged_attention_steps` of these arguments, when the caller
    already has it.

    Entries of ``block_tables`` past a slot's position are never read, so
    they may hold any valid page index (the serving table pads with 0)."""
    b, hq, s1, d = q.shape
    assert s1 == 1, "paged decode kernel is single-query (decode step)"
    tb, hk, bs, _ = k_arena.shape
    assert hq % hk == 0, (hq, hk)
    group = hq // hk
    nb = block_tables.shape[1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    ppc = _arena_ppc(k_arena, nb)

    pos32 = pos.astype(jnp.int32)
    if steps is None:
        steps = paged_attention_steps(k_arena, block_tables, pos32)
    n_steps, slot_of, chunk_of, pages = steps
    # the grid is the live list on the chip; the interpreter takes no
    # traced grid, so it walks the whole list and skips the dead tail
    grid = (slot_of.shape[0],) if interpret else (n_steps,)

    def slot_map(t, n_ref, slot_ref, chunk_ref, pages_ref, pos_ref):
        return (slot_ref[t], 0, 0, 0)

    def page_map(i):
        def index(t, n_ref, slot_ref, chunk_ref, pages_ref, pos_ref):
            return (pages_ref[t * ppc + i], 0, 0, 0)
        return index

    page_specs = [pl.BlockSpec((1, hk, bs, d), page_map(i))
                  for i in range(ppc)]
    slot_spec = pl.BlockSpec((1, hk, group, d), slot_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=grid,
        in_specs=[slot_spec] + page_specs + page_specs,
        out_specs=slot_spec,
        scratch_shapes=[
            pltpu.VMEM((hk, group, _LANES), jnp.float32),
            pltpu.VMEM((hk, group, _LANES), jnp.float32),
            pltpu.VMEM((hk, group, d), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_kernel, nb=nb, bs=bs, ppc=ppc,
                               scale=scale)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hk, group, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(n_steps.reshape(1), slot_of, chunk_of, pages, pos32,
      q.reshape(b, hk, group, d), *([k_arena] * ppc), *([v_arena] * ppc))
    return out.reshape(b, hq, 1, d)

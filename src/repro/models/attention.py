"""Attention implementations (the CNNLab 'engine' axis for transformers).

Three engines, selected per-layer by the scheduler / config:

* ``dot``      — plain masked dot-product attention (XLA).  O(S·T) score
                 materialization; right choice for short sequences.
* ``chunked``  — memory-efficient online-softmax attention in pure lax
                 (Rabe–Staats / flash algorithm as an XLA scan).  Portable to
                 any backend — this is what the multi-pod dry-run lowers —
                 and never materializes more than (bq, bk) scores per step.
* ``pallas``   — kernels/flash_attention.py (Mosaic on real TPUs).

All take q: (B, HQ, S, D), k/v: (B, HK, T, D) with HQ % HK == 0 and compute
GQA without repeating KV in memory.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..kernels import ops as kops

_NEG_INF = -1e30


def _bhint(t, batch_axes, dim=0):
    """Pin the batch dim of attention internals.  GSPMD drops the batch
    shard through some flash-bwd einsums on TP-mode archs (measured:
    (B_global, H, S, bk) f32 buffers on llama-3.2-vision train)."""
    if batch_axes is None:
        return t
    from jax.sharding import PartitionSpec as P
    spec = [None] * t.ndim
    spec[dim] = batch_axes
    return jax.lax.with_sharding_constraint(t, P(*spec))


def _gqa_fold(q, hk):
    b, hq, s, d = q.shape
    return q.reshape(b, hk, hq // hk, s, d)


def dot_attention(q, k, v, *, causal: bool = True,
                  window: Optional[int] = None,
                  q_offset: int = 0) -> jax.Array:
    """q_offset: absolute position of q[..., 0, :] relative to k's start."""
    b, hq, s, d = q.shape
    hk, t = k.shape[1], k.shape[2]
    qg = _gqa_fold(q, hk).astype(jnp.float32)
    scale = 1.0 / (d ** 0.5)
    logits = jnp.einsum("bkgsd,bktd->bkgst", qg, k.astype(jnp.float32)) * scale
    qpos = jnp.arange(s) + q_offset
    kpos = jnp.arange(t)
    mask = jnp.ones((s, t), dtype=bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        mask &= kpos[None, :] > qpos[:, None] - window
    logits = jnp.where(mask[None, None, None], logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgst,bktd->bkgsd", probs, v.astype(jnp.float32))
    return out.reshape(b, hq, s, d).astype(q.dtype)


def _chunk_mask(s, bk, ik, t_real, causal, window):
    qpos = jnp.arange(s)
    kpos = ik * bk + jnp.arange(bk)
    mask = jnp.broadcast_to(kpos[None, :] < t_real, (s, bk))  # kill padding
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        mask &= kpos[None, :] > qpos[:, None] - window
    return mask


def _pad_kv(k, v, bk):
    t_real = k.shape[2]
    pad_t = (-t_real) % bk
    if pad_t:                                   # e.g. cross-attn over 6404
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_t), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_t), (0, 0)))
    return k, v, t_real


def _chunked_fwd(q, k, v, causal, window, kv_chunk, batch_axes=None):
    """Online-softmax forward.  Returns (out (B,HK,G,S,D) f32, lse)."""
    b, hq, s, d = q.shape
    hk = k.shape[1]
    g = hq // hk
    bk = min(kv_chunk, k.shape[2])
    k, v, t_real = _pad_kv(k, v, bk)
    nk = k.shape[2] // bk
    scale = 1.0 / (d ** 0.5)
    # per-chunk casts only: upcasting full K/V would hold an f32 copy of
    # the whole (B,HK,T,D) tensors alive across the scan
    qg = _gqa_fold(q, hk).astype(jnp.float32) * scale       # (B,HK,G,S,D)
    kc = k.reshape(b, hk, nk, bk, d)
    vc = v.reshape(b, hk, nk, bk, d)

    def step(carry, inputs):
        m, l, acc = carry
        kb, vb, ik = inputs                                  # (B,HK,bk,D)
        logits = jnp.einsum("bkgsd,bktd->bkgst", qg,
                            kb.astype(jnp.float32))          # (B,HK,G,S,bk)
        logits = _bhint(logits, batch_axes)
        mask = _chunk_mask(s, bk, ik, t_real, causal, window)
        logits = jnp.where(mask[None, None, None], logits, _NEG_INF)
        m_cur = jnp.max(logits, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_cur)
        p = jnp.exp(logits - m_new) * mask[None, None, None]
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = _bhint(acc * alpha + jnp.einsum(
            "bkgst,bktd->bkgsd", p, vb.astype(jnp.float32)), batch_axes)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hk, g, s, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hk, g, s, 1), jnp.float32)
    a0 = jnp.zeros((b, hk, g, s, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0),
        (jnp.moveaxis(kc, 2, 0), jnp.moveaxis(vc, 2, 0), jnp.arange(nk)))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = acc / l_safe
    lse = jnp.where(l == 0.0, 0.0, m + jnp.log(l_safe))     # (B,HK,G,S,1)
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _chunked_attention_cv(q, k, v, causal, window, kv_chunk, batch_axes):
    out, _ = _chunked_fwd(q, k, v, causal, window, kv_chunk, batch_axes)
    b, hq, s, d = q.shape
    return out.reshape(b, hq, s, d).astype(q.dtype)


def _cv_fwd(q, k, v, causal, window, kv_chunk, batch_axes):
    out, lse = _chunked_fwd(q, k, v, causal, window, kv_chunk, batch_axes)
    b, hq, s, d = q.shape
    res = (q, k, v, out.astype(q.dtype), lse)
    return out.reshape(b, hq, s, d).astype(q.dtype), res


def _cv_bwd(causal, window, kv_chunk, batch_axes, res, dout):
    """Flash backward: recompute p per chunk from (q, k, v, lse) — saves
    O(S) residuals instead of the inner scan's per-step carries (this is
    what keeps 32k-token training under the HBM budget)."""
    q, k, v, out_f, lse = res
    b, hq, s, d = q.shape
    hk = k.shape[1]
    g = hq // hk
    bk = min(kv_chunk, k.shape[2])
    k, v, t_real = _pad_kv(k, v, bk)
    nk = k.shape[2] // bk
    scale = 1.0 / (d ** 0.5)
    qg = _gqa_fold(q, hk).astype(jnp.float32) * scale        # (B,HK,G,S,D)
    kc = jnp.moveaxis(k.reshape(b, hk, nk, bk, d), 2, 0)
    vc = jnp.moveaxis(v.reshape(b, hk, nk, bk, d), 2, 0)
    dof = _gqa_fold(dout, hk).astype(jnp.float32)            # (B,HK,G,S,D)
    of = out_f.astype(jnp.float32)                           # already folded
    delta = jnp.sum(dof * of, axis=-1, keepdims=True)        # (B,HK,G,S,1)

    def step(dq_acc, inputs):
        kb, vb, ik = inputs
        kb = kb.astype(jnp.float32)
        vb = vb.astype(jnp.float32)
        logits = _bhint(jnp.einsum("bkgsd,bktd->bkgst", qg, kb), batch_axes)
        mask = _chunk_mask(s, bk, ik, t_real, causal, window)
        p = jnp.exp(jnp.where(mask[None, None, None], logits, _NEG_INF)
                    - lse) * mask[None, None, None]          # (B,HK,G,S,bk)
        dv_j = _bhint(jnp.einsum("bkgst,bkgsd->bktd", p, dof), batch_axes)
        dp = _bhint(jnp.einsum("bkgsd,bktd->bkgst", dof, vb), batch_axes)
        ds = p * (dp - delta)                                # d(logits)
        dq_acc = _bhint(dq_acc + jnp.einsum("bkgst,bktd->bkgsd", ds, kb)
                        * scale, batch_axes)
        dk_j = _bhint(jnp.einsum("bkgst,bkgsd->bktd", ds, qg), batch_axes)
        return dq_acc, (dk_j, dv_j)

    dq0 = jnp.zeros((b, hk, g, s, d), jnp.float32)
    dq, (dk_c, dv_c) = jax.lax.scan(
        step, dq0, (kc, vc, jnp.arange(nk)))
    dk = jnp.moveaxis(dk_c, 0, 2).reshape(b, hk, nk * bk, d)[:, :, :t_real]
    dv = jnp.moveaxis(dv_c, 0, 2).reshape(b, hk, nk * bk, d)[:, :, :t_real]
    return (dq.reshape(b, hq, s, d).astype(q.dtype),
            dk.astype(k.dtype), dv.astype(v.dtype))


_chunked_attention_cv.defvjp(_cv_fwd, _cv_bwd)


def chunked_attention(q, k, v, *, causal: bool = True,
                      window: Optional[int] = None,
                      q_chunk: int = 2048, kv_chunk: int = 2048,
                      batch_axes=None) -> jax.Array:
    """Flash algorithm as pure lax, with a flash custom-vjp backward.

    Forward: scan over KV chunks with online softmax — never materializes
    more than (B, H, S, kv_chunk) scores.  Backward: recomputes each chunk's
    probabilities from the saved log-sum-exp (the flash-attention backward),
    so AD saves O(S) residuals rather than per-chunk scan carries.
    """
    del q_chunk  # the q dimension stays batched; kept for API compat
    return _chunked_attention_cv(q, k, v, causal, window, kv_chunk,
                                 batch_axes)


def pallas_attention(q, k, v, *, causal: bool = True,
                     window: Optional[int] = None) -> jax.Array:
    return kops.flash_attention(q, k, v, causal=causal, window=window)


def decode_attention(q, k_cache, v_cache, *, pos: jax.Array,
                     window: Optional[int] = None) -> jax.Array:
    """Single-token attention against a cache.

    q: (B, HQ, 1, D); caches: (B, HK, T, D).  `pos` is the absolute position
    of the current token — a scalar shared by the batch, or a (B,) vector of
    per-slot positions (continuous-batching: each cache row belongs to a
    different request).  For windowed layers the cache is a rolling buffer
    of size T == window written at pos % T; validity = slot was written.
    """
    b, hq, _, d = q.shape
    hk, t = k_cache.shape[1], k_cache.shape[2]
    qg = _gqa_fold(q, hk)[:, :, :, 0]                        # (B,HK,G,D)
    scale = 1.0 / (d ** 0.5)
    # IMPORTANT: do NOT upcast the cache — einsum in cache dtype with fp32
    # accumulation.  An .astype(f32) on the cache gets loop-hoisted out of
    # the layer scan by XLA and materializes an f32 copy of the ENTIRE
    # stacked cache (measured: +5.6 GB/device on minicpm decode_32k).
    logits = jnp.einsum("bkgd,bktd->bkgt", qg.astype(k_cache.dtype),
                        k_cache,
                        preferred_element_type=jnp.float32) * scale
    slots = jnp.arange(t)
    pos_a = jnp.asarray(pos)
    cap = pos_a if window is None else jnp.minimum(pos_a, t - 1)
    valid = slots <= cap[..., None]          # (t,) scalar | (B, t) per-slot
    mask = (valid[None, None, None] if valid.ndim == 1
            else valid[:, None, None, :])
    logits = jnp.where(mask, logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgt,bktd->bkgd", probs.astype(v_cache.dtype),
                     v_cache, preferred_element_type=jnp.float32)
    return out.reshape(b, hq, 1, d).astype(q.dtype)


PAGED_IMPLS = ("auto", "ref", "pallas")


def resolve_paged_impl(impl: str, backend: Optional[str] = None) -> str:
    """The paged decode implementation ``impl`` names: ``"auto"`` is the
    Pallas kernel when compiling for a TPU and the reference on any other
    backend (``backend`` defaults to jax's default backend)."""
    if impl not in PAGED_IMPLS:
        raise ValueError(f"unknown paged attention impl {impl!r}")
    if impl != "auto":
        return impl
    backend = jax.default_backend() if backend is None else backend
    return "pallas" if backend == "tpu" else "ref"


def paged_kernel_steps(k_arena, block_tables, pos, active):
    """The Pallas kernel's step list for one engine step, shared by every
    layer's :func:`paged_decode_attention` (``steps=``); ``k_arena`` is any
    layer's arena, stacked or not."""
    from ..kernels.paged_attention import paged_attention_steps
    return paged_attention_steps(k_arena, block_tables,
                                 jnp.where(active, pos, -1))


def paged_decode_attention(q, k_arena, v_arena, block_tables, pos, *,
                           max_seq: int, impl: str = "auto",
                           active: Optional[jax.Array] = None,
                           steps=None) -> jax.Array:
    """Single-token attention against a block-paged cache.

    q: (B, HQ, 1, D); arenas: (total_blocks + 1, HK, BS, D) — fixed-size
    physical KV pages plus a trailing trash page inactive slots write to;
    block_tables: (B, NB) int32 logical->physical page map; pos: (B,)
    per-slot absolute position of the current token.

    ``impl="ref"`` gathers the slot rows through the table and runs
    :func:`decode_attention` on them — bit-identical to the dense slot
    cache by construction (the gathered row equals the dense row at every
    attended position, and masked positions contribute exact zeros either
    way), which is the serving engine's correctness contract on CPU.
    ``impl="pallas"`` runs kernels/paged_attention.py, which reads only
    each slot's live pages (online softmax — allclose, not bit-identical);
    a slot that ``active`` (B,) marks idle reads no page there and gets
    zeros, since its output is discarded; ``steps`` is the kernel's step
    list from :func:`paged_kernel_steps` of the same arguments.
    ``impl="auto"`` is the kernel on a TPU and the reference elsewhere
    (:func:`resolve_paged_impl`).
    """
    impl = resolve_paged_impl(impl)
    if impl == "pallas":
        if active is not None:
            pos = jnp.where(active, pos, -1)
        out = kops.paged_attention(q, k_arena, v_arena, block_tables, pos,
                                   steps=steps)
        return out.astype(q.dtype)
    from ..kernels.ref import paged_gather
    k = paged_gather(k_arena, block_tables, max_seq)
    v = paged_gather(v_arena, block_tables, max_seq)
    return decode_attention(q, k, v, pos=pos, window=None)


def decode_attention_multi(q, k_cache, v_cache, *, pos: jax.Array
                           ) -> jax.Array:
    """M-token verification attention against a cache.

    q: (B, HQ, M, D); caches: (B, HK, T, D).  ``pos`` is a (B,) vector of
    per-slot absolute positions of the FIRST query token: query m of slot b
    sits at position pos[b] + m and attends over cache slots <= pos[b] + m
    (the cache must already hold the window's K/V at positions
    pos..pos+M-1).  This is :func:`decode_attention` with a query axis —
    speculative verification feeds the k drafted tokens plus the committed
    chain head in one step instead of k+1 sequential single-token steps.
    Windowed layers are unsupported: the rolling buffer's write-back
    overlaps itself inside one multi-token window.
    """
    b, hq, m, d = q.shape
    hk, t = k_cache.shape[1], k_cache.shape[2]
    qg = _gqa_fold(q, hk)                                    # (B,HK,G,M,D)
    scale = 1.0 / (d ** 0.5)
    # same no-upcast discipline as decode_attention (see the comment there)
    logits = jnp.einsum("bkgmd,bktd->bkgmt", qg.astype(k_cache.dtype),
                        k_cache,
                        preferred_element_type=jnp.float32) * scale
    slots = jnp.arange(t)
    qpos = jnp.asarray(pos)[:, None] + jnp.arange(m)         # (B, M)
    valid = slots[None, None] <= qpos[:, :, None]            # (B, M, T)
    logits = jnp.where(valid[:, None, None], logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgmt,bktd->bkgmd", probs.astype(v_cache.dtype),
                     v_cache, preferred_element_type=jnp.float32)
    return out.reshape(b, hq, m, d).astype(q.dtype)


def paged_decode_attention_multi(q, k_arena, v_arena, block_tables, pos, *,
                                 max_seq: int) -> jax.Array:
    """M-token verification attention against a block-paged cache.

    Shapes as :func:`paged_decode_attention` with q: (B, HQ, M, D).  Only
    the reference gather path exists: verification reuses the serving
    engine's bit-identity contract (gathered rows equal dense rows at
    every attended position), and a fused multi-query Pallas kernel is a
    follow-on once speculation runs on a real TPU.
    """
    from ..kernels.ref import paged_gather
    k = paged_gather(k_arena, block_tables, max_seq)
    v = paged_gather(v_arena, block_tables, max_seq)
    return decode_attention_multi(q, k, v, pos=pos)


ATTENTION_ENGINES = {
    "dot": dot_attention,
    "chunked": chunked_attention,
    "pallas": pallas_attention,
}


def attend(q, k, v, *, impl: str = "dot", causal: bool = True,
           window: Optional[int] = None, q_chunk: int = 2048,
           kv_chunk: int = 2048, batch_axes=None) -> jax.Array:
    if impl == "chunked":
        return chunked_attention(q, k, v, causal=causal, window=window,
                                 q_chunk=q_chunk, kv_chunk=kv_chunk,
                                 batch_axes=batch_axes)
    if impl == "pallas":
        return pallas_attention(q, k, v, causal=causal, window=window)
    return dot_attention(q, k, v, causal=causal, window=window)

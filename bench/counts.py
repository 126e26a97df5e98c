"""Operations and bytes a serving step needs, computed from shapes.

FLOPs count the matmuls: every weight matrix once per token (2 per
multiply-add, the tied head included, the embedding gather free) and
attention's two products over the keys a token attends (its own included).

Bytes are what the step cannot avoid moving:

* parameters: the ``nbytes`` of the arrays the program holds, read once per
  step; when they are stored wider than the compute dtype, a program may
  instead cast them once per compiled burst and read the narrow copy on
  every step, so a burst of ``b`` steps needs
  ``min(b * stored, stored + (b + 1) * narrow)``, the cheaper of the two;
* KV: the cached keys and values the live contexts attend, plus the new
  token's write, at the arena's own dtype.  Slots with no request, and
  positions past a slot's context, are not needed, whatever a gather reads.

Nothing assumes a dtype: a program that stores narrower parameters or KV
halves the count with its arrays.
"""
from __future__ import annotations

import dataclasses
from typing import List, Sequence, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class StepCounts:
    matmul_params: int            # weight elements one token multiplies
    attn_flops_per_key: int       # FLOPs per (token, attended key)
    param_bytes: int              # as stored
    param_bytes_narrow: int       # at the compute dtype
    kv_bytes_per_token: int       # K and V of one position, every layer

    def token_flops(self, pos: np.ndarray) -> np.ndarray:
        """FLOPs of one token at position ``pos`` (it attends pos+1
        keys)."""
        return 2 * self.matmul_params + self.attn_flops_per_key * (pos + 1)

    def bucket_param_bytes(self, b: int) -> int:
        """Parameter bytes one compiled burst of ``b`` steps needs."""
        direct = b * self.param_bytes
        if self.param_bytes_narrow >= self.param_bytes:
            return direct
        return min(direct, self.param_bytes + (b + 1) * self.param_bytes_narrow)


def step_counts(*, n_layers: int, d_model: int, n_heads: int,
                n_kv_heads: int, head_dim: int, d_ff: int, vocab: int,
                param_leaves: Sequence, compute_itemsize: int,
                kv_itemsize: int) -> StepCounts:
    """``param_leaves``: the program's parameter arrays (anything with
    ``size`` and ``dtype``)."""
    q, kv = n_heads * head_dim, n_kv_heads * head_dim
    per_layer = d_model * q + 2 * d_model * kv + q * d_model \
        + 3 * d_model * d_ff
    stored = sum(int(a.size) * np.dtype(a.dtype).itemsize
                 for a in param_leaves)
    narrow = sum(int(a.size) * min(np.dtype(a.dtype).itemsize,
                                   compute_itemsize) for a in param_leaves)
    return StepCounts(
        matmul_params=n_layers * per_layer + vocab * d_model,
        attn_flops_per_key=n_layers * 4 * q,
        param_bytes=stored,
        param_bytes_narrow=narrow,
        kv_bytes_per_token=n_layers * 2 * kv * kv_itemsize)


def burst_counts(c: StepCounts, buckets: Sequence[int],
                 positions: Sequence[int]) -> List[Tuple[int, int, float,
                                                         float]]:
    """Per compiled bucket of a dispatched burst: ``(steps, tokens, flops,
    bytes)``.

    ``buckets``: the burst sizes the dispatch ran, in order; ``positions``:
    the live slots' positions at the burst's first step."""
    pos = np.asarray(positions, np.int64)
    out = []
    start = 0
    for b in buckets:
        p = pos[None, :] + np.arange(start, start + b, dtype=np.int64)[:, None]
        flops = float(c.token_flops(p).sum())
        kv = float(((p + 1) * c.kv_bytes_per_token).sum())
        out.append((b, b * len(pos), flops,
                    kv + float(c.bucket_param_bytes(b))))
        start += b
    return out

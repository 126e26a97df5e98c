"""Operations and bytes a serving burst needs, computed from shapes.

Each model module's ``step_counts`` gives, from its configuration file's
shapes, the FLOPs of one token at a position (``token_flops``) and the KV
bytes it needs (``token_kv_bytes``: what it attends and its own write, at
the arena's own dtype; slots with no request, and positions past a slot's
context, are not needed, whatever a gather reads), and the parameter bytes
of a compiled burst (``bucket_param_bytes``, from ``param_bytes`` and the
rule below).  ``burst_counts`` sums them over a dispatched burst.

Parameters are the ``nbytes`` of the arrays the program holds, read once
per step; when they are stored wider than the compute dtype, a program may
instead cast them once per compiled burst and read the narrow copy on
every step, so a burst of ``b`` steps needs
``min(b * stored, stored + (b + 1) * narrow)``, the cheaper of the two.

Nothing assumes a dtype: a program that stores narrower parameters or KV
halves the count with its arrays.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np


def param_bytes(param_leaves: Sequence, compute_itemsize: int
                ) -> Tuple[int, int]:
    """``(stored, narrow)``: the bytes of the program's parameter arrays
    (anything with ``size`` and ``dtype``) as stored, and at the compute
    dtype where that is narrower."""
    stored = sum(int(a.size) * np.dtype(a.dtype).itemsize
                 for a in param_leaves)
    narrow = sum(int(a.size) * min(np.dtype(a.dtype).itemsize,
                                   compute_itemsize) for a in param_leaves)
    return stored, narrow


def bucket_param_bytes(b: int, stored: int, narrow: int) -> int:
    """Parameter bytes one compiled burst of ``b`` steps needs."""
    direct = b * stored
    if narrow >= stored:
        return direct
    return min(direct, stored + (b + 1) * narrow)


def burst_counts(c, buckets: Sequence[int],
                 positions: Sequence[int]) -> List[Tuple[int, int, float,
                                                         float]]:
    """Per compiled bucket of a dispatched burst: ``(steps, tokens, flops,
    bytes)``.

    ``c``: a model module's ``step_counts``; ``buckets``: the burst sizes
    the dispatch ran, in order; ``positions``: the live slots' positions
    at the burst's first step."""
    pos = np.asarray(positions, np.int64)
    out = []
    start = 0
    for b in buckets:
        p = pos[None, :] + np.arange(start, start + b, dtype=np.int64)[:, None]
        flops = float(c.token_flops(p).sum())
        kv = float(c.token_kv_bytes(p).sum())
        out.append((b, b * len(pos), flops,
                    kv + float(c.bucket_param_bytes(b))))
        start += b
    return out

"""Plain float32 reference: the building blocks every model module's
``Reference`` uses, and the comparison of served tokens with it.

Straight ``jax.numpy`` at ``Precision.HIGHEST``: no kernels, no cache, no
batching, nothing imported from the program.  A model's reference
regenerates the weights from the seed one layer at a time and runs whole
sequences through that layer before making the next, so it fits beside
nothing else on the chip; every number comes from the configuration file,
the weights are the published dtype's, not the program's.

``quant="fp8"`` is the control: every weight matmul takes float8 (e4m3,
one scale per tensor) operands, the next precision below the bfloat16 the
configurations state.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0                      # largest finite float8_e4m3fn


def _fp8(x: jax.Array) -> jax.Array:
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def mm(a, b, quant: Optional[str]):
    if quant == "fp8":
        a, b = _fp8(a), _fp8(b)
    elif quant is not None:
        raise ValueError(f"unknown reference precision {quant!r}")
    return jnp.dot(a, b, precision=HIGHEST)


def rmsnorm(x, delta, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + delta)


def rope(x, theta):
    """x: (S, heads, hd); position of row s is s."""
    s, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def served_gaps(ref, samples, *, control: bool = False):
    """Widest gaps over served tokens; ``ref`` is a model module's
    ``Reference``.

    ``samples`` are ``(prompt, output)`` pairs.  Position plen-1+j of
    prompt+output predicts output[j].  Returns the program's widest gap,
    and with ``control`` also the widest gap of the tokens the float8
    reference puts first at the same positions."""
    seqs, nexts, masks = [], [], []
    for prompt, output in samples:
        seq = np.concatenate([prompt, output[:-1]]).astype(np.int32)
        nxt = np.zeros_like(seq)
        nxt[len(prompt) - 1:] = output
        m = np.zeros(len(seq), bool)
        m[len(prompt) - 1:] = True
        seqs.append(seq)
        nexts.append(nxt)
        masks.append(m)
    alts = None
    if control:
        alts = [a[:len(s)] for a, s in zip(ref.argmax(seqs, "fp8"), seqs)]
    res = ref.gaps(seqs, nexts, alts)
    prog = max(float(ga[:len(m)][m].max()) for (ga, _), m in zip(res, masks))
    if not control:
        return prog
    ctrl = max(float(gb[:len(m)][m].max()) for (_, gb), m in zip(res, masks))
    return prog, ctrl

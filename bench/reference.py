"""Plain float32 reference of the dense decoder the cells serve.

Straight ``jax.numpy`` at ``Precision.HIGHEST``: no kernels, no cache, no
batching, nothing imported from the program.  It regenerates the weights
from the seed (``bench/weights.py``) one layer at a time and runs whole
sequences through that layer before making the next, so it fits beside
nothing else on the chip.

It follows the published architecture (RMSNorm, RoPE with rotate-half,
grouped-query attention with q/k/v biases where the model has them, SwiGLU,
a head tied to the embedding, and MiniCPM's muP scalars), with every number
from the configuration file: the weights are the published dtype's, not
the program's.

``quant="fp8"`` is the control: every weight matmul takes float8 (e4m3,
one scale per tensor) operands, the next precision below the bfloat16 the
configurations state.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .weights import Dims, make_globals, make_layer

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0                      # largest finite float8_e4m3fn


def _fp8(x: jax.Array) -> jax.Array:
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(a, b, quant: Optional[str]):
    if quant == "fp8":
        a, b = _fp8(a), _fp8(b)
    elif quant is not None:
        raise ValueError(f"unknown reference precision {quant!r}")
    return jnp.dot(a, b, precision=HIGHEST)


def _rmsnorm(x, delta, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + delta)


def _rope(x, theta):
    """x: (S, heads, hd); position of row s is s."""
    s, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def layer_forward(h, w, dims: Dims, quant: Optional[str]):
    """One decoder layer over a whole sequence h: (S, d_model)."""
    s = h.shape[0]
    hd, hq, hk = dims.head_dim, dims.n_heads, dims.n_kv_heads
    x = _rmsnorm(h, w["ln1"], dims.norm_eps)
    q = _mm(x, w["wq"], quant)
    k = _mm(x, w["wk"], quant)
    v = _mm(x, w["wv"], quant)
    if dims.qkv_bias:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    q = _rope(q.reshape(s, hq, hd), dims.rope_theta)
    k = _rope(k.reshape(s, hk, hd), dims.rope_theta)
    v = v.reshape(s, hk, hd)
    q = q.reshape(s, hk, hq // hk, hd)
    scores = jnp.einsum("skgd,tkd->kgst", q, k,
                        precision=HIGHEST) / np.sqrt(hd)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    att = jnp.einsum("kgst,tkd->skgd", probs, v, precision=HIGHEST)
    rs = dims.residual_scale
    h = h + rs * _mm(att.reshape(s, hq * hd), w["wo"], quant)
    x = _rmsnorm(h, w["ln2"], dims.norm_eps)
    gate = _mm(x, w["w_gate"], quant)
    up = _mm(x, w["w_up"], quant)
    return h + rs * _mm(jax.nn.silu(gate) * up, w["w_down"], quant)


class Reference:
    """Teacher-forced logits of whole sequences, made layer by layer.

    ``seqs`` are token sequences of at most ``seq_len`` tokens; each is
    padded at the end to ``seq_len`` (causal attention keeps the padding
    out of every real position), so one compiled program serves every
    sequence."""

    def __init__(self, dims: Dims, key: jax.Array, seq_len: int):
        self.dims, self.key, self.seq_len = dims, key, seq_len
        # the key is an argument, not a constant, so one compiled program
        # (and one persistent-cache entry) serves every seed
        self._layer_w = jax.jit(lambda k, i: make_layer(k, i, dims))
        self._globals = jax.jit(lambda k: make_globals(k, dims))
        self._layer = jax.jit(layer_forward, static_argnums=(2, 3))

        def head(h, g, quant):
            x = _rmsnorm(h, g["final_norm"], dims.norm_eps) * dims.logit_scale
            return _mm(x, g["embed"].T, quant)

        def gaps(h, g, toks_a, toks_b):
            """Reference logit gaps: best minus the logit of toks_a[t] (and
            of toks_b[t]) at each position t."""
            logits = head(h, g, None)
            best = jnp.max(logits, axis=-1)
            pick = lambda t: jnp.take_along_axis(logits, t[:, None], 1)[:, 0]
            return best - pick(toks_a), best - pick(toks_b)

        def argmax(h, g, quant):
            return jnp.argmax(head(h, g, quant), axis=-1).astype(jnp.int32)

        self._gaps = jax.jit(gaps)
        self._argmax = jax.jit(argmax, static_argnums=(2,))

    def _pad(self, seq: np.ndarray) -> jax.Array:
        if len(seq) > self.seq_len:
            raise ValueError(f"sequence of {len(seq)} tokens exceeds "
                             f"{self.seq_len}")
        row = np.zeros((self.seq_len,), np.int32)
        row[:len(seq)] = seq
        return jnp.asarray(row)

    def hidden(self, seqs: Sequence[np.ndarray], quant: Optional[str] = None
               ) -> Tuple[List[jax.Array], dict]:
        g = self._globals(self.key)
        hs = [jnp.take(g["embed"], self._pad(s), axis=0) * self.dims.scale_emb
              for s in seqs]
        for i in range(self.dims.n_layers):
            w = self._layer_w(self.key, i)
            hs = [self._layer(h, w, self.dims, quant) for h in hs]
            del w
        return hs, g

    def gaps(self, seqs: Sequence[np.ndarray], next_tokens: Sequence[np.ndarray],
             alt_tokens: Optional[Sequence[np.ndarray]] = None):
        """Per sequence, the gap at each position between the reference's
        best logit and that of ``next_tokens`` (and ``alt_tokens``).
        Token arrays are aligned with ``seqs`` (position t predicts
        token t+1) and padded like them."""
        hs, g = self.hidden(seqs)
        out = []
        for j, h in enumerate(hs):
            a = self._pad(next_tokens[j])
            b = a if alt_tokens is None else self._pad(alt_tokens[j])
            ga, gb = self._gaps(h, g, a, b)
            out.append((np.asarray(ga), np.asarray(gb)))
        return out

    def argmax(self, seqs: Sequence[np.ndarray], quant: str) -> List[np.ndarray]:
        """The token each position puts first, computed at ``quant``."""
        hs, g = self.hidden(seqs, quant)
        return [np.asarray(self._argmax(h, g, quant)) for h in hs]


def served_gaps(ref: Reference, samples, *, control: bool = False):
    """Widest gaps over served tokens.

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

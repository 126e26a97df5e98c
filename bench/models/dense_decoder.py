"""The dense decoder: RMSNorm, RoPE with rotate-half, grouped-query
attention with q/k/v biases where the model has them, a SwiGLU MLP, a head
tied to the embedding, and MiniCPM's muP scalars (Qwen2, MiniCPM).

Weights: the canonical layout is per layer, with Hugging Face semantics:
each RMSNorm gain is ``1 + delta`` (the delta is stored), q/k/v biases where
the model has them, the MLP as gate/up/down.  Layer ``i`` is drawn from its
own key, so the reference can make one layer at a time, and the program's
weights are the same numbers drawn for all layers at once.  They are the
published checkpoint's: drawn in float32 and rounded to the dtype the file
states (``torch_dtype``), whatever the program stores.  Where the model has
muP's scalars the reference applies them as the published code does; the
program has none, so the weights handed to it carry them folded in, as a
conversion of the checkpoint would (``program_params``).

The reference is straight ``jax.numpy`` at ``Precision.HIGHEST`` with every
number from the file; ``quant="fp8"`` takes float8 operands in every weight
matmul, the control.  The module imports nothing of the program at its top,
so the weights and the reference load and run without it; only
``program_config`` and ``small`` read the program's registry.
"""
from __future__ import annotations

import dataclasses
import math
from typing import TYPE_CHECKING, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import counts as counts_lib
from bench.reference import HIGHEST, mm, rmsnorm, rope
from bench.weights import published

if TYPE_CHECKING:
    from repro.models import transformer as T


@dataclasses.dataclass(frozen=True)
class Dims:
    """The shape, the published precision and the init of a dense
    decoder."""
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    qkv_bias: bool
    rope_theta: float
    norm_eps: float
    weight_dtype: str = "float32"     # the checkpoint's storage dtype
    matrix_std: float = 0.02
    embed_std: float = 0.02
    bias_std: float = 0.05
    norm_std: float = 0.05
    # muP (MiniCPM): input embedding x scale_emb, each residual branch x
    # residual_scale, the normed hidden state x logit_scale before the head
    scale_emb: float = 1.0
    residual_scale: float = 1.0
    logit_scale: float = 1.0


def dims_from_config(config: Dict) -> Dims:
    """The configuration file's numbers, nothing of the program's."""
    init = config["init"]
    mup = config.get("mup")
    return Dims(
        n_layers=config["num_hidden_layers"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], d_ff=config["intermediate_size"],
        vocab=config["vocab_size"], qkv_bias=config["attention_bias"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        weight_dtype=config["torch_dtype"],
        matrix_std=init["matrix_std"], embed_std=init["embed_std"],
        bias_std=init["bias_std"], norm_std=init["norm_std"],
        scale_emb=float(config.get("scale_emb", 1.0)),
        residual_scale=(config["scale_depth"] / math.sqrt(mup["num_layers"])
                        if mup else 1.0),
        logit_scale=(config["dim_model_base"] / config["hidden_size"]
                     if mup else 1.0))


# ----------------------------------------------------------------- program

def program_config(config: Dict) -> T.ModelConfig:
    """The registry entry of the file's width (the published one, or the
    smoke one for a file cut by ``small``), cut in depth and given the norm
    epsilon as the file says (options the program has), then checked key
    by key against the published numbers the file holds."""
    from repro.configs import registry
    spec = registry.get(config["arch"])
    cfg = (spec.smoke if config["hidden_size"] == spec.smoke.d_model
           else spec.config)
    cfg = dataclasses.replace(cfg, n_layers=config["num_hidden_layers"],
                              norm_eps=float(config["rms_norm_eps"]),
                              # as serving.api.serve runs it
                              scan_chunk=min(cfg.scan_chunk, 16))
    want = {"d_model": config["hidden_size"],
            "n_heads": config["num_attention_heads"],
            "n_kv_heads": config["num_key_value_heads"],
            "hd": config["head_dim"], "d_ff": config["intermediate_size"],
            "vocab": config["vocab_size"],
            "qkv_bias": config["attention_bias"],
            "tie_embeddings": config["tie_word_embeddings"],
            "rope_theta": float(config["rope_theta"]),
            "norm_eps": float(config["rms_norm_eps"]),
            "block_pattern": ("attn",), "n_experts": 0, "gated_mlp": True,
            "attn_window": None,
            "compute_dtype": jnp.dtype(config["compute_dtype"])}
    got = {k: getattr(cfg, k) for k in want}
    got["compute_dtype"] = jnp.dtype(got["compute_dtype"])
    bad = {k: (got[k], v) for k, v in want.items() if got[k] != v}
    stored = jnp.dtype(cfg.param_dtype)
    if stored not in (jnp.dtype(config["torch_dtype"]), jnp.dtype("float32")):
        bad["param_dtype"] = (stored, config["torch_dtype"])
    if bad:
        raise ValueError(f"{config['arch']}: the program's configuration "
                         f"differs from the file's (program, file): {bad}")
    return cfg


def small(config: Dict) -> Dict:
    """The file cut to the registry's smoke widths, depth and RoPE base."""
    from repro.configs import registry
    cfg = dict(config)
    smoke = registry.get(cfg["arch"]).smoke
    if "mup" in cfg:
        # muP scales the embedding up: matrices as wide in effect as at the
        # published width keep what the layers add to the residual stream
        # at its size against it, so the tokens depend on every layer
        widen = (cfg["hidden_size"] / smoke.d_model) ** 0.5
        cfg["init"] = dict(cfg["init"],
                           matrix_std=cfg["init"]["matrix_std"] * widen)
    cfg.update(hidden_size=smoke.d_model, num_hidden_layers=smoke.n_layers,
               num_attention_heads=smoke.n_heads,
               num_key_value_heads=smoke.n_kv_heads, head_dim=smoke.hd,
               intermediate_size=smoke.d_ff, vocab_size=smoke.vocab,
               rope_theta=smoke.rope_theta)
    if "dim_model_base" in cfg:          # muP's logit scale as published
        cfg["dim_model_base"] = smoke.d_model * 256 // 2304
    return cfg


# ----------------------------------------------------------------- weights

def make_layer(key: jax.Array, i, dims: Dims) -> Dict[str, jax.Array]:
    """Layer ``i``'s published weights, in float32 (``i`` may be traced)."""
    ks = jax.random.split(jax.random.fold_in(key, i), 12)
    d, h, hk, hd, f = (dims.d_model, dims.n_heads, dims.n_kv_heads,
                       dims.head_dim, dims.d_ff)
    normal = lambda k, shape, std: jax.random.normal(k, shape,
                                                     jnp.float32) * std
    m = dims.matrix_std
    w = {
        "ln1": normal(ks[0], (d,), dims.norm_std),
        "wq": normal(ks[1], (d, h * hd), m),
        "wk": normal(ks[2], (d, hk * hd), m),
        "wv": normal(ks[3], (d, hk * hd), m),
        "wo": normal(ks[4], (h * hd, d), m),
        "ln2": normal(ks[5], (d,), dims.norm_std),
        "w_gate": normal(ks[6], (d, f), m),
        "w_up": normal(ks[7], (d, f), m),
        "w_down": normal(ks[8], (f, d), m),
    }
    if dims.qkv_bias:
        w["bq"] = normal(ks[9], (h * hd,), dims.bias_std)
        w["bk"] = normal(ks[10], (hk * hd,), dims.bias_std)
        w["bv"] = normal(ks[11], (hk * hd,), dims.bias_std)
    return published(w, dims.weight_dtype)


def make_globals(key: jax.Array, dims: Dims) -> Dict[str, jax.Array]:
    """The published embedding (tied to the head) and final norm, in
    float32."""
    ke, kn = jax.random.split(jax.random.fold_in(key, 1 << 20))
    return published({
        "embed": jax.random.normal(ke, (dims.vocab, dims.d_model),
                                   jnp.float32) * dims.embed_std,
        "final_norm": jax.random.normal(kn, (dims.d_model,),
                                        jnp.float32) * dims.norm_std,
    }, dims.weight_dtype)


def program_params(key: jax.Array, config: Dict, param_dtype) -> Dict:
    """All weights in the serving program's tree (stacked layers, norm
    gains stored as deltas), muP's scalars folded in, in the dtype the
    program stores.  Meant to run under one ``jax.jit``.

    The fold: the embedding carries ``scale_emb``; the output projections
    of attention and MLP (no bias) carry ``residual_scale``; the final
    norm's gain carries ``logit_scale / scale_emb``, which undoes the
    embedding's scale on the tied head."""
    dims = dims_from_config(config)
    layers = jax.vmap(lambda i: make_layer(key, i, dims))(
        jnp.arange(dims.n_layers))
    g = make_globals(key, dims)
    layers["wo"] = layers["wo"] * dims.residual_scale
    layers["w_down"] = layers["w_down"] * dims.residual_scale
    fold = dims.logit_scale / dims.scale_emb
    final_norm = (g["final_norm"] if fold == 1.0
                  else (1.0 + g["final_norm"]) * fold - 1.0)
    attn = {k: layers[k] for k in ("wq", "wk", "wv", "wo", "bq", "bk", "bv")
            if k in layers}
    block = {"ln1": layers["ln1"], "attn": attn, "ln2": layers["ln2"],
             "mlp": {k: layers[k] for k in ("w_gate", "w_up", "w_down")}}
    tree = {"embed": g["embed"] * dims.scale_emb,
            "final_norm": final_norm,
            "decoder": {"blocks": (block,), "rem": ()}}
    return jax.tree.map(lambda a: a.astype(param_dtype), tree)


# --------------------------------------------------------------- reference

def layer_forward(h, w, dims: Dims, quant: Optional[str]):
    """One decoder layer over a whole sequence h: (S, d_model)."""
    s = h.shape[0]
    hd, hq, hk = dims.head_dim, dims.n_heads, dims.n_kv_heads
    x = rmsnorm(h, w["ln1"], dims.norm_eps)
    q = mm(x, w["wq"], quant)
    k = mm(x, w["wk"], quant)
    v = mm(x, w["wv"], quant)
    if dims.qkv_bias:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    q = rope(q.reshape(s, hq, hd), dims.rope_theta)
    k = rope(k.reshape(s, hk, hd), dims.rope_theta)
    v = v.reshape(s, hk, hd)
    q = q.reshape(s, hk, hq // hk, hd)
    scores = jnp.einsum("skgd,tkd->kgst", q, k,
                        precision=HIGHEST) / np.sqrt(hd)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    att = jnp.einsum("kgst,tkd->skgd", probs, v, precision=HIGHEST)
    rs = dims.residual_scale
    h = h + rs * mm(att.reshape(s, hq * hd), w["wo"], quant)
    x = rmsnorm(h, w["ln2"], dims.norm_eps)
    gate = mm(x, w["w_gate"], quant)
    up = mm(x, w["w_up"], quant)
    return h + rs * mm(jax.nn.silu(gate) * up, w["w_down"], quant)


class Reference:
    """Teacher-forced logits of whole sequences, made layer by layer: the
    weights are regenerated from the seed one layer at a time, and whole
    sequences run through that layer before the next is made, so it fits
    beside nothing else on the chip.

    ``seqs`` are token sequences of at most ``seq_len`` tokens; each is
    padded at the end to ``seq_len`` (causal attention keeps the padding
    out of every real position), so one compiled program serves every
    sequence."""

    def __init__(self, config: Dict, key: jax.Array, seq_len: int):
        dims = dims_from_config(config)
        self.dims, self.key, self.seq_len = dims, key, seq_len
        # the key is an argument, not a constant, so one compiled program
        # (and one persistent-cache entry) serves every seed
        self._layer_w = jax.jit(lambda k, i: make_layer(k, i, dims))
        self._globals = jax.jit(lambda k: make_globals(k, dims))
        self._layer = jax.jit(layer_forward, static_argnums=(2, 3))

        def head(h, g, quant):
            x = rmsnorm(h, g["final_norm"], dims.norm_eps) * dims.logit_scale
            return mm(x, g["embed"].T, quant)

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


# ------------------------------------------------------------------ counts

@dataclasses.dataclass(frozen=True)
class StepCounts:
    """FLOPs count every weight matrix once per token (2 per multiply-add,
    the tied head included, the embedding gather free) and attention's two
    products over the keys a token attends, its own included; KV bytes are
    the cached keys and values a token attends plus its own write, every
    layer, at the arena's dtype."""
    matmul_params: int            # weight elements one token multiplies
    attn_flops_per_key: int       # FLOPs per (token, attended key)
    param_bytes: int              # as stored
    param_bytes_narrow: int       # at the compute dtype
    kv_bytes_per_token: int       # K and V of one position, every layer

    def token_flops(self, pos: np.ndarray) -> np.ndarray:
        """FLOPs of one token at position ``pos`` (it attends pos+1
        keys)."""
        return 2 * self.matmul_params + self.attn_flops_per_key * (pos + 1)

    def token_kv_bytes(self, pos: np.ndarray) -> np.ndarray:
        """KV bytes one token at position ``pos`` needs."""
        return (pos + 1) * self.kv_bytes_per_token

    def bucket_param_bytes(self, b: int) -> int:
        return counts_lib.bucket_param_bytes(b, self.param_bytes,
                                             self.param_bytes_narrow)


def step_counts(config: Dict, cfg: T.ModelConfig, params,
                kv_itemsize: int) -> StepCounts:
    """From the file's shapes and the program's parameter arrays
    (``params``: any tree of leaves with ``size`` and ``dtype``)."""
    d = config["hidden_size"]
    q = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    n_layers = config["num_hidden_layers"]
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * config["intermediate_size"]
    stored, narrow = counts_lib.param_bytes(
        jax.tree.leaves(params), jnp.dtype(cfg.compute_dtype).itemsize)
    return StepCounts(
        matmul_params=n_layers * per_layer + config["vocab_size"] * d,
        attn_flops_per_key=n_layers * 4 * q,
        param_bytes=stored,
        param_bytes_narrow=narrow,
        kv_bytes_per_token=n_layers * 2 * kv * kv_itemsize)

"""Random weights from the seed, made by the benchmark and not the program.

The canonical layout is per layer, with Hugging Face semantics: each
RMSNorm gain is ``1 + delta`` (the delta is stored), q/k/v biases where the
model has them, and the SwiGLU MLP as gate/up/down.  Layer ``i`` is drawn
from its own key, so the reference can make one layer at a time, and the
program's weights are the same numbers drawn for all layers at once.

The weights are the published checkpoint's: drawn in float32 and rounded
to the dtype the configuration file states (``torch_dtype``), whatever the
program stores.  Where the model has muP's scalars (MiniCPM) the reference
applies them as the published code does; the program has none, so the
weights handed to it carry them folded in, as a conversion of the
checkpoint would (``program_params``).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict

import jax
import jax.numpy as jnp


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


def root_key(seed: int) -> jax.Array:
    """A key for any whole-number seed, including ones past 32 bits."""
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _published(w: Dict, dims: Dims) -> Dict:
    """Rounded to the checkpoint's dtype, held in float32."""
    dt = jnp.dtype(dims.weight_dtype)
    return {k: v.astype(dt).astype(jnp.float32) for k, v in w.items()}


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
    return _published(w, dims)


def make_globals(key: jax.Array, dims: Dims) -> Dict[str, jax.Array]:
    """The published embedding (tied to the head) and final norm, in
    float32."""
    ke, kn = jax.random.split(jax.random.fold_in(key, 1 << 20))
    return _published({
        "embed": jax.random.normal(ke, (dims.vocab, dims.d_model),
                                   jnp.float32) * dims.embed_std,
        "final_norm": jax.random.normal(kn, (dims.d_model,),
                                        jnp.float32) * dims.norm_std,
    }, dims)


def program_params(key: jax.Array, dims: Dims, param_dtype) -> Dict:
    """All weights in the serving program's tree (stacked layers, norm
    gains stored as deltas), muP's scalars folded in, in the dtype the
    program stores.  Meant to run under one ``jax.jit``.

    The fold: the embedding carries ``scale_emb``; the output projections
    of attention and MLP (no bias) carry ``residual_scale``; the final
    norm's gain carries ``logit_scale / scale_emb``, which undoes the
    embedding's scale on the tied head."""
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

"""Unified config-driven transformer family.

One implementation covers all ten assigned architectures:

* dense decoder (llama-arch: deepseek-coder, minicpm, qwen2, granite)
* MoE decoder (mixtral 8e, phi3.5-moe 16e) — models/moe.py
* SSM decoder (falcon-mamba) — models/ssm.py Mamba-1 blocks
* hybrid (recurrentgemma: RG-LRU + local attention, 1:2)
* encoder–decoder (seamless-m4t backbone; audio frontend is a stub that
  feeds precomputed frame embeddings)
* VLM (llama-3.2-vision backbone: gated cross-attention image layers every
  Nth layer; patch embeddings stubbed)

Design notes:
* layers execute through `lax.scan` over the repeating *super-block* (the
  unit of the layer pattern), so HLO size is O(1) in depth and remat policy
  applies per super-block;
* everything is pure functions over explicit param pytrees; `init_params`
  runs under `jax.eval_shape` for the allocation-free dry-run;
* decode carries a cache pytree scanned alongside the stacked params.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from . import attention as attn_lib
from . import moe as moe_lib
from . import ssm as ssm_lib
from .layers import apply_rope, rmsnorm, softmax_cross_entropy

PyTree = Any


def _constrain_act(x: jax.Array, cfg: "ModelConfig") -> jax.Array:
    """Pin (B, S, D) activations to the policy's batch/seq sharding."""
    if cfg.act_sharding is None:
        return x
    from jax.sharding import PartitionSpec as P
    b_ax, s_ax = cfg.act_sharding
    s_ax = s_ax if x.shape[1] > 1 else None     # decode: single position
    return jax.lax.with_sharding_constraint(x, P(b_ax, s_ax, None))


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str = "model"
    n_layers: int = 12
    d_model: int = 512
    n_heads: int = 8
    n_kv_heads: int = 8
    d_ff: int = 2048
    vocab: int = 32000
    head_dim: int = 0                       # 0 -> d_model // n_heads
    qkv_bias: bool = False
    gated_mlp: bool = True                  # SwiGLU (3 mats) vs plain (2)
    attn_window: Optional[int] = None       # SWA width (None = full)
    block_pattern: Tuple[str, ...] = ("attn",)   # unit: attn|rec|mamba|xattn
    cross_attn_every: int = 0               # vision: xattn every Nth layer
    # MoE
    n_experts: int = 0
    moe_top_k: int = 2
    capacity_factor: float = 1.25
    # SSM
    ssm_state: int = 16
    ssm_conv: int = 4
    ssm_expand: int = 2
    scan_chunk: int = 256
    # encoder-decoder
    encoder_decoder: bool = False
    enc_layers: int = 0
    # misc
    rope_theta: float = 1e4
    norm_eps: float = 1e-6
    tie_embeddings: bool = True
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    attention_impl: str = "chunked"         # dot | chunked | pallas
    attn_chunk: int = 1024
    # paged serving decode: "ref" (gather + dense decode attention; bit-
    # identical to the dense slot cache), "pallas" (kernels/
    # paged_attention.py, reads only the live pages) or "auto" (the kernel
    # when compiling for a TPU, the reference elsewhere)
    paged_attention_impl: str = "auto"
    remat: bool = True
    frontend: str = "none"                  # none | audio | vision
    img_seq: int = 6404                     # vision stub: 4 tiles x 1601
    # microbatching: split the global batch into this many sequential
    # microbatches per step (gradient accumulation) — the production lever
    # for fitting train-step activation memory in HBM
    grad_accum: int = 1
    # activation sharding constraint (batch_axes, seq_axes) — mesh axis names
    # injected by launch/steps.py; pins (B, S, D) activations so GSPMD does
    # not trade the batch shard for a param-storage shard (ZeRO-3 semantics)
    act_sharding: Optional[Tuple[Any, Any]] = None
    # logits sharding constraint (vocab mesh axes) — perf knob for the loss
    logits_vocab_shard: Optional[Any] = None

    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // max(self.n_heads, 1))

    def pattern_unit(self) -> Tuple[str, ...]:
        if self.cross_attn_every > 0:
            return tuple(["attn"] * (self.cross_attn_every - 1) + ["xattn"])
        return self.block_pattern

    def layer_types(self) -> List[str]:
        unit = self.pattern_unit()
        out = [unit[i % len(unit)] for i in range(self.n_layers)]
        return out

    @property
    def n_super(self) -> int:
        return self.n_layers // len(self.pattern_unit())

    @property
    def n_rem(self) -> int:
        return self.n_layers % len(self.pattern_unit())

    def mamba_args(self) -> ssm_lib.MambaArgs:
        return ssm_lib.MambaArgs(self.d_model, self.ssm_state, self.ssm_conv,
                                 self.ssm_expand, self.scan_chunk)

    def rglru_args(self) -> ssm_lib.RGLRUArgs:
        return ssm_lib.RGLRUArgs(self.d_model, self.ssm_conv, self.ssm_expand,
                                 chunk=self.scan_chunk)

    def moe_args(self) -> moe_lib.MoEArgs:
        return moe_lib.MoEArgs(self.d_model, self.d_ff, self.n_experts,
                               self.moe_top_k, self.capacity_factor)


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------
def _dense(key, shape, dtype, fan_in=None):
    fan_in = fan_in or shape[0]
    return (jax.random.normal(key, shape, jnp.float32)
            * (2.0 / fan_in) ** 0.5).astype(dtype)


def _init_attn(key, cfg: ModelConfig, cross: bool = False) -> Dict:
    ks = jax.random.split(key, 4)
    d, h, hk, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    p = {
        "wq": _dense(ks[0], (d, h * hd), cfg.param_dtype),
        "wk": _dense(ks[1], (d, hk * hd), cfg.param_dtype),
        "wv": _dense(ks[2], (d, hk * hd), cfg.param_dtype),
        "wo": _dense(ks[3], (h * hd, d), cfg.param_dtype),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((h * hd,), cfg.param_dtype)
        p["bk"] = jnp.zeros((hk * hd,), cfg.param_dtype)
        p["bv"] = jnp.zeros((hk * hd,), cfg.param_dtype)
    return p


def _init_mlp(key, cfg: ModelConfig) -> Dict:
    if cfg.n_experts > 0:
        return moe_lib.init_moe_params(key, cfg.moe_args(), cfg.param_dtype)
    ks = jax.random.split(key, 3)
    d, f = cfg.d_model, cfg.d_ff
    p = {
        "w_up": _dense(ks[1], (d, f), cfg.param_dtype),
        "w_down": _dense(ks[2], (f, d), cfg.param_dtype),
    }
    if cfg.gated_mlp:
        p["w_gate"] = _dense(ks[0], (d, f), cfg.param_dtype)
    return p


def _init_block(key, btype: str, cfg: ModelConfig, with_cross: bool) -> Dict:
    ks = jax.random.split(key, 6)
    d = cfg.d_model
    zeros = lambda: jnp.zeros((d,), cfg.param_dtype)
    if btype == "attn":
        p = {"ln1": zeros(), "attn": _init_attn(ks[0], cfg),
             "ln2": zeros(), "mlp": _init_mlp(ks[1], cfg)}
        if with_cross:
            p["lnx"] = zeros()
            p["xattn"] = _init_attn(ks[2], cfg, cross=True)
        return p
    if btype == "xattn":  # vision: gated cross-attention layer
        return {"ln1": zeros(), "xattn": _init_attn(ks[0], cfg, cross=True),
                "ln2": zeros(), "mlp": _init_mlp(ks[1], cfg),
                "gate_attn": jnp.zeros((), cfg.param_dtype),
                "gate_mlp": jnp.zeros((), cfg.param_dtype)}
    if btype == "rec":
        return {"ln1": zeros(),
                "rec": ssm_lib.init_rglru_params(ks[0], cfg.rglru_args(),
                                                 cfg.param_dtype),
                "ln2": zeros(), "mlp": _init_mlp(ks[1], cfg)}
    if btype == "mamba":
        return {"ln1": zeros(),
                "mamba": ssm_lib.init_mamba_params(ks[0], cfg.mamba_args(),
                                                   cfg.param_dtype)}
    raise ValueError(btype)


def _init_stack(key, cfg: ModelConfig, with_cross: bool) -> Dict:
    """Scanned super-block stacks + remainder layers."""
    unit = cfg.pattern_unit()
    kb, kr = jax.random.split(key)
    blocks = []
    for j, btype in enumerate(unit):
        keys = jax.random.split(jax.random.fold_in(kb, j), max(cfg.n_super, 1))
        init_one = functools.partial(_init_block, btype=btype, cfg=cfg,
                                     with_cross=with_cross)
        blocks.append(jax.vmap(lambda k: init_one(k))(keys))
    rem = []
    for i in range(cfg.n_rem):
        btype = unit[i % len(unit)]
        rem.append(_init_block(jax.random.fold_in(kr, i), btype, cfg,
                               with_cross))
    return {"blocks": tuple(blocks), "rem": tuple(rem)}


def init_params(key: jax.Array, cfg: ModelConfig) -> Dict:
    ke, kd, kenc, kh = jax.random.split(key, 4)
    params: Dict[str, Any] = {
        "embed": (jax.random.normal(ke, (cfg.vocab, cfg.d_model), jnp.float32)
                  * 0.02).astype(cfg.param_dtype),
        "final_norm": jnp.zeros((cfg.d_model,), cfg.param_dtype),
        "decoder": _init_stack(kd, cfg, with_cross=cfg.encoder_decoder),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = _dense(kh, (cfg.d_model, cfg.vocab),
                                   cfg.param_dtype)
    if cfg.encoder_decoder:
        enc_cfg = dataclasses.replace(
            cfg, n_layers=cfg.enc_layers or cfg.n_layers,
            block_pattern=("attn",), cross_attn_every=0, encoder_decoder=False)
        params["encoder"] = _init_stack(kenc, enc_cfg, with_cross=False)
        params["enc_final_norm"] = jnp.zeros((cfg.d_model,), cfg.param_dtype)
    return params


# ---------------------------------------------------------------------------
# Blocks (full-sequence mode)
# ---------------------------------------------------------------------------
def _weight(w, cfg: ModelConfig):
    """A parameter in the compute dtype, under the ``weight_cast`` scope so
    a device trace names the cast wherever XLA puts it (parameters stored
    wider are cast once per program, hoisted out of the layer scan)."""
    with jax.named_scope("weight_cast"):
        return w.astype(cfg.compute_dtype)


def _project_qkv(p, h_in, cfg: ModelConfig, kv_src=None):
    src = h_in if kv_src is None else kv_src
    q = jnp.dot(h_in, _weight(p["wq"], cfg))
    k = jnp.dot(src, _weight(p["wk"], cfg))
    v = jnp.dot(src, _weight(p["wv"], cfg))
    if "bq" in p:
        q = q + _weight(p["bq"], cfg)
        k = k + _weight(p["bk"], cfg)
        v = v + _weight(p["bv"], cfg)
    b, s = h_in.shape[0], h_in.shape[1]
    t = src.shape[1]
    q = q.reshape(b, s, cfg.n_heads, cfg.hd).transpose(0, 2, 1, 3)
    k = k.reshape(b, t, cfg.n_kv_heads, cfg.hd).transpose(0, 2, 1, 3)
    v = v.reshape(b, t, cfg.n_kv_heads, cfg.hd).transpose(0, 2, 1, 3)
    return q, k, v


def _merge_heads(out, p, cfg: ModelConfig):
    b, h, s, hd = out.shape
    out = out.transpose(0, 2, 1, 3).reshape(b, s, h * hd)
    return jnp.dot(out, _weight(p["wo"], cfg))


def _self_attention(p, x, cfg: ModelConfig, *, causal: bool,
                    positions: jax.Array, emit_cache: bool):
    h_in = rmsnorm(x, p["ln1"], cfg.norm_eps)
    q, k, v = _project_qkv(p["attn"], h_in, cfg)
    q = apply_rope(q, positions[None, None], cfg.rope_theta)
    k = apply_rope(k, positions[None, None], cfg.rope_theta)
    out = attn_lib.attend(q, k, v, impl=cfg.attention_impl, causal=causal,
                          window=cfg.attn_window, kv_chunk=cfg.attn_chunk)
    x = x + _merge_heads(out, p["attn"], cfg)
    cache = None
    if emit_cache:
        w = cfg.attn_window
        if w is not None and k.shape[2] > w:
            # rolling buffer: keep the last `w` positions, laid out so that
            # slot (pos % w) holds position pos — matches decode writes
            t = k.shape[2]
            idx = (jnp.arange(w) + (t // w) * w)
            idx = jnp.where(idx < t, idx, idx - w)
            k, v = k[:, :, idx], v[:, :, idx]
        cache = {"k": k.astype(cfg.compute_dtype),
                 "v": v.astype(cfg.compute_dtype)}
    return x, cache


def _cross_attention(p, x, kv_feats, cfg: ModelConfig, key: str = "xattn"):
    h_in = rmsnorm(x, p["lnx" if key == "xattn" and "lnx" in p else "ln1"],
                   cfg.norm_eps)
    q, k, v = _project_qkv(p[key], h_in, cfg, kv_src=kv_feats)
    out = attn_lib.attend(q, k, v, impl="dot" if x.shape[1] == 1 else
                          cfg.attention_impl, causal=False,
                          kv_chunk=cfg.attn_chunk)
    return _merge_heads(out, p[key], cfg)


def _mlp_core(p, h_in, cfg: ModelConfig):
    if cfg.gated_mlp:
        h = jax.nn.silu(jnp.dot(h_in, _weight(p["w_gate"], cfg))) * \
            jnp.dot(h_in, _weight(p["w_up"], cfg))
    else:
        h = jax.nn.gelu(jnp.dot(h_in, _weight(p["w_up"], cfg)))
    return jnp.dot(h, _weight(p["w_down"], cfg))


@jax.named_scope("mlp")
def _mlp(p, x, cfg: ModelConfig):
    h_in = rmsnorm(x, p["ln2"], cfg.norm_eps)
    if cfg.n_experts > 0:
        bax = cfg.act_sharding[0] if cfg.act_sharding else None
        return x + moe_lib.moe_apply(p["mlp"], h_in, cfg.moe_args(),
                                     cfg.compute_dtype, batch_axes=bax)
    return x + _mlp_core(p["mlp"], h_in, cfg)


def _apply_block(btype: str, p, x, cfg: ModelConfig, *, causal: bool,
                 positions: jax.Array, cross_feats=None,
                 emit_cache: bool = False):
    """Full-sequence block application.  Returns (x, cache_or_None)."""
    cache = None
    if btype == "attn":
        x, cache = _self_attention(p, x, cfg, causal=causal,
                                   positions=positions, emit_cache=emit_cache)
        if "xattn" in p and cross_feats is not None:      # enc-dec decoder
            x = x + _cross_attention(p, x, cross_feats, cfg)
        x = _mlp(p, x, cfg)
    elif btype == "xattn":                                 # vision layer
        g_a = jnp.tanh(p["gate_attn"].astype(jnp.float32)).astype(x.dtype)
        x = x + g_a * _cross_attention(p, x, cross_feats, cfg, key="xattn")
        h_in = rmsnorm(x, p["ln2"], cfg.norm_eps)
        g_m = jnp.tanh(p["gate_mlp"].astype(jnp.float32)).astype(x.dtype)
        x = x + g_m * _mlp_core(p["mlp"], h_in, cfg)
    elif btype == "rec":
        h_in = rmsnorm(x, p["ln1"], cfg.norm_eps)
        y = ssm_lib.rglru_apply(p["rec"], h_in, cfg.rglru_args(),
                                cfg.compute_dtype, return_state=emit_cache)
        if emit_cache:
            y, state = y
            cache = {"rec": state}
        x = x + y
        x = _mlp(p, x, cfg)
    elif btype == "mamba":
        h_in = rmsnorm(x, p["ln1"], cfg.norm_eps)
        y = ssm_lib.mamba_apply(p["mamba"], h_in, cfg.mamba_args(),
                                cfg.compute_dtype, return_state=emit_cache)
        if emit_cache:
            y, state = y
            cache = {"mamba": state}
        x = x + y
    else:
        raise ValueError(btype)
    return x, cache


def _run_stack(stack, x, cfg: ModelConfig, *, causal: bool,
               positions: jax.Array, cross_feats=None,
               emit_cache: bool = False):
    """Scan over super-blocks, then the remainder layers."""
    unit = cfg.pattern_unit()

    def super_block(carry, xs):
        h = carry
        caches = []
        for j, btype in enumerate(unit):
            h, c = _apply_block(btype, xs[j], h, cfg, causal=causal,
                                positions=positions, cross_feats=cross_feats,
                                emit_cache=emit_cache)
            h = _constrain_act(h, cfg)
            caches.append(c if c is not None else 0)
        return h, tuple(caches)

    body = super_block
    if cfg.remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable)
    caches = None
    if cfg.n_super > 0:
        x, caches = jax.lax.scan(body, x, stack["blocks"])
    rem_caches = []
    for i, p in enumerate(stack["rem"]):
        btype = unit[i % len(unit)]
        x, c = _apply_block(btype, p, x, cfg, causal=causal,
                            positions=positions, cross_feats=cross_feats,
                            emit_cache=emit_cache)
        rem_caches.append(c if c is not None else 0)
    return x, (caches, tuple(rem_caches))


# ---------------------------------------------------------------------------
# Public entry points: forward / loss / prefill / decode
# ---------------------------------------------------------------------------
def encode(params, cfg: ModelConfig, enc_inputs: jax.Array) -> jax.Array:
    """Encoder over precomputed frontend embeddings (B, T, D)."""
    enc_cfg = dataclasses.replace(
        cfg, n_layers=cfg.enc_layers or cfg.n_layers,
        block_pattern=("attn",), cross_attn_every=0, encoder_decoder=False)
    x = _constrain_act(enc_inputs.astype(cfg.compute_dtype), enc_cfg)
    positions = jnp.arange(x.shape[1])
    x, _ = _run_stack(params["encoder"], x, enc_cfg, causal=False,
                      positions=positions)
    return rmsnorm(x, params["enc_final_norm"], cfg.norm_eps)


def forward(params, cfg: ModelConfig, tokens: jax.Array, *,
            enc_inputs: Optional[jax.Array] = None,
            img_embeds: Optional[jax.Array] = None,
            emit_cache: bool = False):
    """Full-sequence forward.  tokens: (B, S) int32 -> logits (B, S, V).

    Returns (logits, cache) — cache is None unless emit_cache (prefill)."""
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.compute_dtype)
    x = _constrain_act(x, cfg)
    positions = jnp.arange(tokens.shape[1])
    cross_feats = None
    if cfg.encoder_decoder:
        assert enc_inputs is not None, "enc-dec model needs encoder inputs"
        cross_feats = encode(params, cfg, enc_inputs)
    elif cfg.frontend == "vision":
        assert img_embeds is not None, "vision model needs image embeddings"
        cross_feats = img_embeds.astype(cfg.compute_dtype)

    x, caches = _run_stack(params["decoder"], x, cfg, causal=True,
                           positions=positions, cross_feats=cross_feats,
                           emit_cache=emit_cache)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    logits = jnp.dot(x, head.astype(cfg.compute_dtype))
    if cfg.logits_vocab_shard is not None and cfg.act_sharding is not None:
        from jax.sharding import PartitionSpec as P
        b_ax, s_ax = cfg.act_sharding
        logits = jax.lax.with_sharding_constraint(
            logits, P(b_ax, s_ax, cfg.logits_vocab_shard))
    if not emit_cache:
        return logits, None
    cache = {"layers": caches, "pos": jnp.array(tokens.shape[1], jnp.int32),
             "cross": cross_feats}
    return logits, cache


def loss_fn(params, cfg: ModelConfig, batch: Dict[str, jax.Array]) -> jax.Array:
    logits, _ = forward(params, cfg, batch["tokens"],
                        enc_inputs=batch.get("enc_inputs"),
                        img_embeds=batch.get("img_embeds"))
    return softmax_cross_entropy(logits, batch["labels"], batch.get("mask"))


# ------------------------------- decode -----------------------------------
def init_cache(cfg: ModelConfig, batch: int, max_seq: int) -> Dict:
    """Abstract-init-friendly cache pytree matching `_run_stack` emissions."""
    unit = cfg.pattern_unit()
    t = min(cfg.attn_window or max_seq, max_seq)

    def one(btype):
        if btype in ("attn",):
            return {"k": jnp.zeros((batch, cfg.n_kv_heads, t, cfg.hd),
                                   cfg.compute_dtype),
                    "v": jnp.zeros((batch, cfg.n_kv_heads, t, cfg.hd),
                                   cfg.compute_dtype)}
        if btype == "rec":
            return {"rec": ssm_lib.rglru_init_state(cfg.rglru_args(), batch)}
        if btype == "mamba":
            return {"mamba": ssm_lib.mamba_init_state(cfg.mamba_args(), batch)}
        if btype == "xattn":
            # cross-attn reads cache["cross"]; keep a scannable placeholder
            return jnp.zeros((), jnp.int32)
        raise ValueError(btype)

    def stacked(btype):
        c = one(btype)
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (cfg.n_super,) + a.shape), c)

    blocks = tuple(stacked(b) for b in unit)
    rem = tuple(one(unit[i % len(unit)]) for i in range(cfg.n_rem))
    cross = None
    if cfg.encoder_decoder:
        enc_t = max_seq
        cross = jnp.zeros((batch, enc_t, cfg.d_model), cfg.compute_dtype)
    elif cfg.frontend == "vision":
        cross = jnp.zeros((batch, cfg.img_seq, cfg.d_model), cfg.compute_dtype)
    return {"layers": (blocks, rem), "pos": jnp.zeros((), jnp.int32),
            "cross": cross}


def _decode_attn_block(p, x, cache, cfg: ModelConfig, pos, cross_feats):
    """`pos` is a scalar (whole batch at one position — static batching) or a
    (B,) vector of per-slot positions (continuous batching)."""
    with jax.named_scope("attention"):
        h_in = rmsnorm(x, p["ln1"], cfg.norm_eps)
        q, k, v = _project_qkv(p["attn"], h_in, cfg)
        pos_a = jnp.asarray(pos)
        per_slot = pos_a.ndim == 1
        posq = (pos_a[:, None, None] if per_slot
                else jnp.full((1, 1, 1), pos_a))
        q = apply_rope(q, posq, cfg.rope_theta)
        k = apply_rope(k, posq, cfg.rope_theta)
        t = cache["k"].shape[2]
        if cfg.attn_window is not None:
            slot = pos_a % t                    # rolling buffer
        else:
            slot = jnp.minimum(pos_a, t - 1)
        with jax.named_scope("kv_write"):
            # one-hot (select-based) cache write: elementwise over the time
            # dim, so a time-SHARDED cache updates locally —
            # dynamic_update_slice at a traced index would force GSPMD to
            # all-gather the cache (measured: +10 GB temp per decode step
            # on kv-unshardable archs)
            onehot = (jnp.arange(t) == slot[..., None])  # (t,) | (B, t)
            onehot = (onehot[:, None, :, None] if per_slot
                      else onehot[None, None, :, None])
            k_cache = jnp.where(onehot, k.astype(cache["k"].dtype),
                                cache["k"])
            v_cache = jnp.where(onehot, v.astype(cache["v"].dtype),
                                cache["v"])
        with jax.named_scope("core"):
            out = attn_lib.decode_attention(q, k_cache, v_cache, pos=pos,
                                            window=cfg.attn_window)
        x = x + _merge_heads(out, p["attn"], cfg)
    if "xattn" in p and cross_feats is not None:
        x = x + _cross_attention(p, x, cross_feats, cfg)
    x = _mlp(p, x, cfg)
    return x, {"k": k_cache, "v": v_cache}


def _decode_block(btype, p, x, cache, cfg: ModelConfig, pos, cross_feats):
    if btype == "attn":
        return _decode_attn_block(p, x, cache, cfg, pos, cross_feats)
    if btype == "xattn":
        g_a = jnp.tanh(p["gate_attn"].astype(jnp.float32)).astype(x.dtype)
        x = x + g_a * _cross_attention(p, x, cross_feats, cfg, key="xattn")
        h_in = rmsnorm(x, p["ln2"], cfg.norm_eps)
        g_m = jnp.tanh(p["gate_mlp"].astype(jnp.float32)).astype(x.dtype)
        x = x + g_m * _mlp_core(p["mlp"], h_in, cfg)
        return x, cache
    if btype == "rec":
        h_in = rmsnorm(x, p["ln1"], cfg.norm_eps)
        y, rec = ssm_lib.rglru_step(p["rec"], h_in, cache["rec"],
                                    cfg.rglru_args(), cfg.compute_dtype)
        x = x + y
        x = _mlp(p, x, cfg)
        return x, {"rec": rec}
    if btype == "mamba":
        h_in = rmsnorm(x, p["ln1"], cfg.norm_eps)
        y, st = ssm_lib.mamba_step(p["mamba"], h_in, cache["mamba"],
                                   cfg.mamba_args(), cfg.compute_dtype)
        return x + y, {"mamba": st}
    raise ValueError(btype)


def _decode_step_impl(params, cfg: ModelConfig, cache: Dict,
                      tokens: jax.Array, active: Optional[jax.Array], *,
                      block_step=None, arena_passthrough: bool = False,
                      pos_increment: int = 1):
    """Shared decode-step body.  With ``active=None`` this is the static
    path (scalar `pos`, whole batch advances); with an (B,) ``active`` mask
    it is the continuous-batching path (per-slot (B,) `pos`, inactive slots
    keep cache and position bit-for-bit).

    ``block_step(btype, p, h, c) -> (h, new_c)`` overrides the per-layer
    step (the paged layout substitutes its attention block);
    ``arena_passthrough`` exempts attention K/V dicts from the per-slot
    keep-select — paged arenas are page-major, not slot-major, and their
    writes are already active-guarded by trash-page routing.  There is
    exactly one copy of everything else (embed, keep semantics, the
    super-block scan, final norm, logits head), so a fix here cannot split
    the layouts' bit-identity."""
    unit = cfg.pattern_unit()
    pos = cache["pos"]
    cross_feats = cache.get("cross")
    b = tokens.shape[0]
    if block_step is None:
        def block_step(btype, p, h, c):
            return _decode_block(btype, p, h, c, cfg, pos, cross_feats)

    if active is None:
        keep = lambda new, old: new
    else:
        def keep(new, old):
            if arena_passthrough and isinstance(new, dict) and "k" in new:
                return new
            def sel(n, o):
                if getattr(n, "ndim", 0) == 0 or n.shape[0] != b:
                    return n                # scannable placeholders (xattn)
                return jnp.where(
                    active.reshape((b,) + (1,) * (n.ndim - 1)), n, o)
            return jax.tree.map(sel, new, old)

    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens,
                     axis=0).astype(cfg.compute_dtype)
        x = _constrain_act(x, cfg)

    blocks_cache, rem_cache = cache["layers"]

    def super_block(h, xs):
        ps, cs = xs
        new_cs = []
        for j, btype in enumerate(unit):
            h, nc = block_step(btype, ps[j], h, cs[j])
            h = _constrain_act(h, cfg)
            new_cs.append(keep(nc, cs[j]))
        return h, tuple(new_cs)

    new_blocks = blocks_cache
    if cfg.n_super > 0:
        # the scan's own work (each layer's parameters and cache sliced out
        # of the stacked arrays, its new cache written back) is named
        # ``layer_scan``; the blocks inside carry their own scopes
        with jax.named_scope("layer_scan"):
            x, new_blocks = jax.lax.scan(
                super_block, x, (params["decoder"]["blocks"], blocks_cache))
    new_rem = []
    for i, p in enumerate(params["decoder"]["rem"]):
        btype = unit[i % len(unit)]
        x, nc = block_step(btype, p, x, rem_cache[i])
        new_rem.append(keep(nc, rem_cache[i]))

    with jax.named_scope("head"):
        x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        head = params.get("lm_head")
        if head is None:
            head = params["embed"].T
        logits = jnp.dot(x, _weight(head, cfg))
    # copy-and-update: layout-specific keys (e.g. paged block_tables)
    # survive; the dense cache carries exactly layers/pos/cross either way
    new_cache = dict(cache)
    new_cache["layers"] = (new_blocks, tuple(new_rem))
    new_cache["pos"] = (pos + pos_increment if active is None
                        else jnp.where(active, pos + pos_increment, pos))
    return logits, new_cache


def decode_step(params, cfg: ModelConfig, cache: Dict, tokens: jax.Array):
    """One decode step.  tokens: (B, 1) -> (logits (B, 1, V), new cache)."""
    return _decode_step_impl(params, cfg, cache, tokens, active=None)


# --------------------- slot-indexed decode (serving) -----------------------
def init_slot_cache(cfg: ModelConfig, n_slots: int, max_seq: int) -> Dict:
    """Cache for the continuous-batching engine: each batch row is a *slot*
    owned by (at most) one in-flight request, with its own position counter.
    Identical layout to `init_cache` except ``pos`` is per-slot (B,)."""
    cache = init_cache(cfg, n_slots, max_seq)
    cache["pos"] = jnp.zeros((n_slots,), jnp.int32)
    return cache


def reset_slot_state(cfg: ModelConfig, cache: Dict, slot: int) -> Dict:
    """Clear one slot's per-request state before binding a new request.

    Attention KV entries need no clearing in either layout (per-slot
    position masks hide stale entries — paged arenas additionally never
    alias live blocks), but recurrent SSM states (rec/mamba) carry no
    position and WOULD leak across tenants — those are zeroed, matching
    `init_cache`.  The cache is rebuilt by copy-and-update so every key
    the layout carries (e.g. the paged layout's ``block_tables``)
    survives."""
    def zero_slot(c, axis):
        if not (isinstance(c, dict) and ("rec" in c or "mamba" in c)):
            return c
        def z(leaf):
            idx = (slice(None),) * axis + (slot,)
            return leaf.at[idx].set(jnp.zeros_like(leaf[idx]))
        return jax.tree.map(z, c)

    blocks, rem = cache["layers"]
    blocks = tuple(zero_slot(c, 1) for c in blocks)     # (n_super, B, ...)
    rem = tuple(zero_slot(c, 0) for c in rem)           # (B, ...)
    out = dict(cache)
    out["layers"] = (blocks, rem)
    out["pos"] = cache["pos"].at[slot].set(0)
    return out


def decode_step_slots(params, cfg: ModelConfig, cache: Dict,
                      tokens: jax.Array, active: jax.Array):
    """One engine step over independent slots.

    tokens: (B, 1) int32 — per-slot next token (prompt token while the slot
    is prefilling, previously sampled token while decoding; ignored for
    inactive slots).  active: (B,) bool.  cache["pos"]: (B,) int32 per-slot
    positions.  Inactive slots keep their cache and position bit-for-bit.

    The per-slot math is exactly `decode_step`'s (same rope/write/mask ops,
    vectorized over `pos`), which is what makes continuous-batching outputs
    token-identical to the static replay path.
    """
    return _decode_step_impl(params, cfg, cache, tokens, active=active)


# ----------------- block-paged slot decode (serving, paged layout) ----------
def init_slot_cache_paged(cfg: ModelConfig, n_slots: int, max_seq: int, *,
                          block_size: int = 16,
                          total_blocks: Optional[int] = None) -> Dict:
    """Physically block-paged cache for the continuous-batching engine.

    Attention layers store K/V in one ``(total_blocks + 1, n_kv_heads,
    block_size, head_dim)`` arena per layer/K-V instead of dense
    ``(n_slots, heads, max_seq, head_dim)`` rows; a slot's sequence lives
    in the physical pages its ``block_tables`` row names (block ``j``
    holds positions ``[j * block_size, (j + 1) * block_size)``), so the
    pool can be provisioned for tokens-in-flight rather than
    ``n_slots x max_seq``.  The trailing arena page (index
    ``total_blocks``) is the *trash page*: inactive slots' writes are
    routed there, never read back (no block table names it).  Recurrent
    SSM states and cross-attention rows stay slot-major (they are O(1) in
    sequence length).  Layout is shared across layers — one table indexes
    every layer's arena.

    Sliding-window layers are not paged yet (the dense rolling buffer
    reuses slots in place; paging it needs in-kernel modular gather) —
    configs with ``attn_window`` must serve on the dense layout.
    """
    if cfg.attn_window is not None:
        raise ValueError(
            "paged KV layout does not support sliding-window attention "
            "(rolling-buffer slots); serve this config with the dense "
            "layout")
    unit = cfg.pattern_unit()
    blocks_per_slot = -(-max_seq // block_size)
    if total_blocks is None:
        total_blocks = n_slots * blocks_per_slot

    def one(btype):
        if btype in ("attn",):
            arena = jnp.zeros((total_blocks + 1, cfg.n_kv_heads, block_size,
                               cfg.hd), cfg.compute_dtype)
            return {"k": arena, "v": arena}
        if btype == "rec":
            return {"rec": ssm_lib.rglru_init_state(cfg.rglru_args(),
                                                    n_slots)}
        if btype == "mamba":
            return {"mamba": ssm_lib.mamba_init_state(cfg.mamba_args(),
                                                      n_slots)}
        if btype == "xattn":
            return jnp.zeros((), jnp.int32)
        raise ValueError(btype)

    def stacked(btype):
        c = one(btype)
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (cfg.n_super,) + a.shape), c)

    blocks = tuple(stacked(b) for b in unit)
    rem = tuple(one(unit[i % len(unit)]) for i in range(cfg.n_rem))
    cross = None
    if cfg.encoder_decoder:
        cross = jnp.zeros((n_slots, max_seq, cfg.d_model), cfg.compute_dtype)
    elif cfg.frontend == "vision":
        cross = jnp.zeros((n_slots, cfg.img_seq, cfg.d_model),
                          cfg.compute_dtype)
    return {"layers": (blocks, rem),
            "pos": jnp.zeros((n_slots,), jnp.int32),
            "cross": cross,
            "block_tables": jnp.zeros((n_slots, blocks_per_slot), jnp.int32)}


def _decode_attn_block_paged(p, x, cache, cfg: ModelConfig, pos,
                             cross_feats, block_tables, active, max_seq,
                             kernel_steps=None):
    """Paged counterpart of `_decode_attn_block`: identical q/k/v math
    (same rope over the same per-slot positions), but the new token's K/V
    is scattered into its slot's tail page and attention gathers through
    the block table.  Inactive slots' writes route to the trash page, so
    live pages are never clobbered (the dense path's `keep` select has no
    slot-major arena axis to apply to)."""
    with jax.named_scope("attention"):
        h_in = rmsnorm(x, p["ln1"], cfg.norm_eps)
        q, k, v = _project_qkv(p["attn"], h_in, cfg)
        pos_a = jnp.asarray(pos)
        assert pos_a.ndim == 1, \
            "paged decode is per-slot (continuous batching)"
        posq = pos_a[:, None, None]
        q = apply_rope(q, posq, cfg.rope_theta)
        k = apply_rope(k, posq, cfg.rope_theta)

        with jax.named_scope("kv_write"):
            b = x.shape[0]
            bs = cache["k"].shape[-2]
            nb = block_tables.shape[1]
            trash = cache["k"].shape[0] - 1
            j = jnp.clip(pos_a // bs, 0, nb - 1)
            off = pos_a % bs
            phys = block_tables[jnp.arange(b), j]
            phys = jnp.where(active, phys, trash)
            heads = jnp.arange(cfg.n_kv_heads)[None, :]
            k_arena = cache["k"].at[phys[:, None], heads, off[:, None]].set(
                k[:, :, 0, :].astype(cache["k"].dtype))
            v_arena = cache["v"].at[phys[:, None], heads, off[:, None]].set(
                v[:, :, 0, :].astype(cache["v"].dtype))

        with jax.named_scope("core"):
            out = attn_lib.paged_decode_attention(
                q, k_arena, v_arena, block_tables, pos_a, max_seq=max_seq,
                impl=cfg.paged_attention_impl, active=active,
                steps=kernel_steps)
        x = x + _merge_heads(out, p["attn"], cfg)
    if "xattn" in p and cross_feats is not None:
        x = x + _cross_attention(p, x, cross_feats, cfg)
    x = _mlp(p, x, cfg)
    return x, {"k": k_arena, "v": v_arena}


def decode_step_slots_paged(params, cfg: ModelConfig, cache: Dict,
                            tokens: jax.Array, active: jax.Array, *,
                            max_seq: int):
    """One engine step over independent slots on the block-paged cache.

    Same contract as :func:`decode_step_slots` — tokens (B, 1), active
    (B,) bool, per-slot ``cache["pos"]`` — plus ``cache["block_tables"]``
    (B, NB) naming each slot's physical pages.  ``max_seq`` (static) trims
    the gathered rows to the dense layout's sequence axis so outputs are
    BIT-IDENTICAL to `decode_step_slots` on the equivalent dense cache
    (asserted in tests/test_paged.py and the serving bench).  Inactive
    slots keep position, recurrent state and their live pages bit-for-bit
    (their KV write lands in the trash page).

    Everything except the attention block step is the one shared
    `_decode_step_impl` body the dense paths run."""
    pos = cache["pos"]
    cross_feats = cache.get("cross")
    block_tables = cache["block_tables"]
    kernel_steps = None
    if attn_lib.resolve_paged_impl(cfg.paged_attention_impl) == "pallas":
        # the kernel's step list depends only on positions and tables:
        # build it once here, not once per layer inside the layer scan
        arena = next(c["k"] for c in sum(cache["layers"], ())
                     if isinstance(c, dict) and "k" in c)
        kernel_steps = attn_lib.paged_kernel_steps(arena, block_tables, pos,
                                                   active)

    def block_step(btype, p, h, c):
        if btype == "attn":
            return _decode_attn_block_paged(p, h, c, cfg, pos, cross_feats,
                                            block_tables, active, max_seq,
                                            kernel_steps)
        return _decode_block(btype, p, h, c, cfg, pos, cross_feats)

    return _decode_step_impl(params, cfg, cache, tokens, active,
                             block_step=block_step, arena_passthrough=True)


def _multi_attn_block_paged(p, x, cache, cfg: ModelConfig, pos,
                            cross_feats, block_tables, active, max_seq):
    """Verification-window counterpart of `_decode_attn_block_paged`: x is
    (B, M, d) — M consecutive tokens per slot at positions pos..pos+M-1 —
    whose K/V is scattered into the slot's pages in one shot, and each
    query attends over cache slots <= its own position (the freshly
    written window prefix included, exactly as M sequential single-token
    steps would see it).  Inactive slots' writes route to the trash page;
    their offsets collide across slots there, which is harmless — the
    trash page is never attended."""
    with jax.named_scope("attention"):
        h_in = rmsnorm(x, p["ln1"], cfg.norm_eps)
        q, k, v = _project_qkv(p["attn"], h_in, cfg)         # (B,H,M,hd)
        pos_a = jnp.asarray(pos)
        assert pos_a.ndim == 1, \
            "paged decode is per-slot (continuous batching)"
        b, m = x.shape[0], x.shape[1]
        wpos = pos_a[:, None] + jnp.arange(m)                # (B, M)
        posq = wpos[:, None, :]
        q = apply_rope(q, posq, cfg.rope_theta)
        k = apply_rope(k, posq, cfg.rope_theta)

        with jax.named_scope("kv_write"):
            bs = cache["k"].shape[-2]
            nb = block_tables.shape[1]
            trash = cache["k"].shape[0] - 1
            j = jnp.clip(wpos // bs, 0, nb - 1)
            off = wpos % bs
            phys = jnp.take_along_axis(block_tables, j, axis=1)  # (B, M)
            phys = jnp.where(active[:, None], phys, trash)
            heads = jnp.arange(cfg.n_kv_heads)[None, None, :]
            # window positions are distinct per slot and slots own disjoint
            # pages, so the M-way scatter has no live-page collisions
            k_arena = cache["k"].at[phys[:, :, None], heads,
                                    off[:, :, None]].set(
                k.transpose(0, 2, 1, 3).astype(cache["k"].dtype))
            v_arena = cache["v"].at[phys[:, :, None], heads,
                                    off[:, :, None]].set(
                v.transpose(0, 2, 1, 3).astype(cache["v"].dtype))

        with jax.named_scope("core"):
            out = attn_lib.paged_decode_attention_multi(
                q, k_arena, v_arena, block_tables, pos_a, max_seq=max_seq)
        x = x + _merge_heads(out, p["attn"], cfg)
    if "xattn" in p and cross_feats is not None:
        x = x + _cross_attention(p, x, cross_feats, cfg)
    x = _mlp(p, x, cfg)
    return x, {"k": k_arena, "v": v_arena}


def decode_multi_step_slots_paged(params, cfg: ModelConfig, cache: Dict,
                                  tokens: jax.Array, active: jax.Array, *,
                                  max_seq: int, advance: bool = True):
    """M-token engine step over independent slots on the block-paged cache.

    tokens: (B, M) int32 — M *consecutive* chain tokens per slot starting
    at ``cache["pos"]`` — against the same cache contract as
    :func:`decode_step_slots_paged`.  Returns (logits (B, M, V), cache):
    logits[:, i] conditions on tokens[:, :i+1], so feeding the committed
    head plus k drafted tokens yields the target's greedy continuation at
    every window offset in ONE step — speculative verification — and
    feeding a prompt chunk replays prefill M tokens at a time (draft
    enrollment).

    ``advance`` (static): True moves each active slot's position by M (the
    enrollment/replay feed); False leaves ``pos`` untouched so the caller
    can commit only the accepted prefix (speculative verify).  Positions
    pos+c..pos+M-1 then hold *stale* K/V from the rejected tail — safe
    because every later feed starts at the committed position and rewrites
    forward before attention ever reaches them (attention masks
    kv_slot <= query position).

    Requires an all-attention config: recurrent/SSM state advances
    token-serially and has no slot-local multi-token step.
    """
    if any(t != "attn" for t in cfg.layer_types()):
        raise ValueError(
            "multi-token slot step requires an all-attention config: "
            "recurrent/SSM layer state has no multi-token slot step")
    pos = cache["pos"]
    cross_feats = cache.get("cross")
    block_tables = cache["block_tables"]

    def block_step(btype, p, h, c):
        return _multi_attn_block_paged(p, h, c, cfg, pos, cross_feats,
                                       block_tables, active, max_seq)

    return _decode_step_impl(
        params, cfg, cache, tokens, active, block_step=block_step,
        arena_passthrough=True,
        pos_increment=tokens.shape[1] if advance else 0)


# ---------------------------------------------------------------------------
# Accounting (roofline MODEL_FLOPS)
# ---------------------------------------------------------------------------
def count_params(cfg: ModelConfig) -> int:
    d, h, hk, hd, f = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd, cfg.d_ff
    attn = d * h * hd + 2 * d * hk * hd + h * hd * d
    if cfg.qkv_bias:
        attn += h * hd + 2 * hk * hd
    mats = 3 if cfg.gated_mlp else 2
    if cfg.n_experts > 0:
        mlp = cfg.n_experts * mats * d * f + d * cfg.n_experts
    else:
        mlp = mats * d * f
    ma = cfg.mamba_args()
    mamba = (d * 2 * ma.d_inner + ma.d_inner * d + ma.d_inner * ma.d_conv
             + ma.d_inner * (ma.dt_rank + 2 * ma.d_state)
             + ma.dt_rank * ma.d_inner + ma.d_inner * ma.d_state + ma.d_inner)
    ra = cfg.rglru_args()
    rec = (d * 2 * ra.d_inner + ra.d_inner * d + 2 * ra.d_inner * ra.d_inner
           + 2 * ra.d_inner + ra.d_inner * ra.d_conv)
    per_type = {"attn": attn + mlp, "xattn": attn + mlp, "rec": rec + mlp,
                "mamba": mamba}
    total = sum(per_type[t] for t in cfg.layer_types())
    total += cfg.vocab * d
    if not cfg.tie_embeddings:
        total += d * cfg.vocab
    if cfg.encoder_decoder:
        total += (cfg.enc_layers or cfg.n_layers) * (attn + mlp)
        total += cfg.n_layers * attn          # decoder cross-attention
    return total


def count_active_params(cfg: ModelConfig) -> int:
    """MoE: only top_k experts per token count toward 6·N·D."""
    if cfg.n_experts == 0:
        return count_params(cfg)
    full = count_params(cfg)
    d, f = cfg.d_model, cfg.d_ff
    mats = 3 if cfg.gated_mlp else 2
    inactive = (cfg.n_experts - cfg.moe_top_k) * mats * d * f
    return full - len([t for t in cfg.layer_types() if t in ("attn", "xattn")]
                      ) * inactive

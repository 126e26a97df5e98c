"""FLOPs and bytes from shapes (the dense decoder's ``step_counts`` and
bench/counts.py), against hand counts for both configurations, with the
program's own parameter arrays."""
import dataclasses
import functools
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import counts, spec  # noqa: E402
from repro.models import transformer as T  # noqa: E402

MODEL = spec.load_model("dense_decoder")

# hand counts: (matmul weights a token multiplies, parameters stored,
# attention FLOPs per attended key, KV bytes per token at bf16)
HAND = {
    # 28 x (1536*1536*2 + 2*1536*256 + 3*1536*8960) + 151936*1536
    "qwen2_1_5b": (1_543_569_408, 1_543_714_304, 28 * 4 * 1536,
                   28 * 2 * 256 * 2),
    # 10 x (2304*2304*4 + 3*2304*5760) + 122753*2304
    "minicpm_2b": (893_290_752, 893_339_136, 10 * 4 * 2304,
                   10 * 2 * 2304 * 2),
}


def _counts(name, param_dtype=None):
    config = json.loads((ROOT / f"bench/configs/{name}.json").read_text())
    cfg = MODEL.program_config(config)
    if param_dtype is not None:
        cfg = dataclasses.replace(cfg, param_dtype=param_dtype)
    shapes = jax.eval_shape(functools.partial(T.init_params, cfg=cfg),
                            jax.random.PRNGKey(0))
    return MODEL.step_counts(config, cfg, shapes, kv_itemsize=2)


@pytest.mark.parametrize("name", sorted(HAND))
def test_counts_match_hand_counts(name):
    mm, n_params, attn, kv = HAND[name]
    c = _counts(name)
    assert c.matmul_params == mm
    assert c.attn_flops_per_key == attn
    assert c.kv_bytes_per_token == kv
    assert c.param_bytes == 4 * n_params          # stored float32
    assert c.param_bytes_narrow == 2 * n_params
    assert c.token_flops(0) == 2 * mm + attn
    assert c.token_flops(99) == 2 * mm + 100 * attn
    assert c.token_kv_bytes(99) == 100 * kv


@pytest.mark.parametrize("name", sorted(HAND))
def test_bf16_parameters_halve_the_bytes(name):
    f32, bf16 = _counts(name), _counts(name, jnp.bfloat16)
    assert 2 * bf16.param_bytes == f32.param_bytes
    for b in (1, 2, 4, 32):
        # nothing to cast: every step reads the stored bf16 weights
        assert bf16.bucket_param_bytes(b) == b * bf16.param_bytes


def test_bucket_bytes_take_the_cheaper_of_reading_and_casting():
    c = _counts("qwen2_1_5b")
    s = c.param_bytes
    assert c.bucket_param_bytes(1) == s
    assert c.bucket_param_bytes(2) == 2 * s           # 2s < s + 1.5s
    assert c.bucket_param_bytes(4) == 3.5 * s         # s + 5 * s/2
    assert c.bucket_param_bytes(32) == 17.5 * s


def test_burst_counts_follow_each_live_slot():
    c = _counts("minicpm_2b")
    m, a, kv, s = (c.matmul_params, c.attn_flops_per_key,
                   c.kv_bytes_per_token, c.param_bytes)
    parts = counts.burst_counts(c, [2, 1], [0, 10])
    # bucket of 2 steps: slot positions {0, 1} and {10, 11}
    assert parts[0] == (2, 4, 4 * 2 * m + (1 + 2 + 11 + 12) * a,
                        (1 + 2 + 11 + 12) * kv + 2 * s)
    # then one step at {2, 12}
    assert parts[1] == (1, 2, 2 * 2 * m + (3 + 13) * a, (3 + 13) * kv + s)

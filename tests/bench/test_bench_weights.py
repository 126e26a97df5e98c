"""The program's weights and the reference's are the same numbers, to
float32 rounding (one compiled program fuses the scaling differently from
another): all layers drawn at once for the program, one at a time for the
reference."""
import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import weights  # noqa: E402
from repro.configs import registry  # noqa: E402

DIMS = weights.Dims(n_layers=3, d_model=32, n_heads=4, n_kv_heads=2,
                    head_dim=8, d_ff=48, vocab=64, qkv_bias=True,
                    rope_theta=1e4, norm_eps=1e-6)


def test_program_tree_holds_each_layer_as_drawn_alone():
    key = weights.root_key(2**33 + 11)
    tree = jax.jit(lambda k: weights.program_params(k, DIMS, jnp.float32))(key)
    block = tree["decoder"]["blocks"][0]
    flat = {**{k: block[k] for k in ("ln1", "ln2")}, **block["attn"],
            **block["mlp"]}
    one_layer = jax.jit(lambda k, i: weights.make_layer(k, i, DIMS))
    for i in range(DIMS.n_layers):
        alone = one_layer(key, i)
        assert set(alone) == set(flat)
        for k, v in alone.items():
            np.testing.assert_allclose(np.asarray(flat[k][i]), np.asarray(v),
                                       rtol=1e-6, atol=1e-8)
    g = jax.jit(lambda k: weights.make_globals(k, DIMS))(key)
    np.testing.assert_allclose(np.asarray(tree["embed"]),
                               np.asarray(g["embed"]), rtol=1e-6, atol=1e-8)


def test_seeds_past_32_bits_differ():
    a = weights.make_globals(weights.root_key(5), DIMS)["embed"]
    b = weights.make_globals(weights.root_key(5 + 2**32), DIMS)["embed"]
    assert not np.array_equal(np.asarray(a), np.asarray(b))


def test_weights_are_rounded_to_the_published_dtype():
    dims = dataclasses.replace(DIMS, weight_dtype="bfloat16")
    w = weights.make_layer(weights.root_key(3), 1, dims)
    for v in w.values():
        v = np.asarray(v)
        np.testing.assert_array_equal(
            v, np.asarray(jnp.asarray(v).astype(jnp.bfloat16)
                          .astype(jnp.float32)))


@pytest.mark.parametrize("config", ["qwen2_1_5b", "minicpm_2b"])
def test_dims_come_from_the_configuration_file(config):
    c = json.loads((ROOT / "bench/configs" / f"{config}.json").read_text())
    d = weights.dims_from_config(c)
    assert d.norm_eps == c["rms_norm_eps"]
    assert d.weight_dtype == c["torch_dtype"]
    assert (d.n_layers, d.d_model, d.head_dim) == (
        c["num_hidden_layers"], c["hidden_size"], c["head_dim"])
    if "mup" in c:
        assert d.scale_emb == c["scale_emb"]
        assert d.logit_scale == c["dim_model_base"] / c["hidden_size"]
        # the depth scale of the published depth, not of the layers held
        assert d.residual_scale == c["scale_depth"] / c["mup"]["num_layers"] ** 0.5


@pytest.mark.parametrize("stored,ok", [(jnp.float32, True),
                                       (jnp.bfloat16, True),
                                       (jnp.float8_e4m3fn, False),
                                       (jnp.int8, False)])
def test_program_must_store_the_published_weights_exactly(monkeypatch, stored,
                                                           ok):
    """The reference holds the published dtype whatever the program
    stores; a program that stores narrower is refused, not compared."""
    from bench import program
    c = json.loads((ROOT / "bench/configs/qwen2_1_5b.json").read_text())
    spec = registry.get("qwen2_1_5b")
    monkeypatch.setattr(registry, "get", lambda name: dataclasses.replace(
        spec, config=dataclasses.replace(spec.config, param_dtype=stored)))
    if ok:
        assert program.model_config(c).norm_eps == c["rms_norm_eps"]
    else:
        with pytest.raises(ValueError, match="param_dtype"):
            program.model_config(c)

"""The dense decoder's model module gives exactly the numbers the benchmark
gave before models became modules: for both configurations, the weights at
the smoke size (program tree and the reference's layer by layer, by
checksum), the reference's gaps and float8 argmax of two fixed sequences,
and the counts of fixed bursts at the full size.

``testdata/dense_decoder_golden.json`` was recorded from the tree before
the move, on the CPU; a leaf's checksum is the SHA-256 of its dtype, its
shape and its bytes."""
import functools
import hashlib
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import counts, spec, weights  # noqa: E402
from repro.models import transformer as T  # noqa: E402

GOLDEN = json.loads(
    (ROOT / "tests/bench/testdata/dense_decoder_golden.json").read_text())
CONFIGS = sorted(GOLDEN["configs"])
MODEL = spec.load_model("dense_decoder")


def _config(name):
    c = json.loads((ROOT / f"bench/configs/{name}.json").read_text())
    assert c["model"] == "dense_decoder"
    return c


def _smoke(name):
    """The cut the numbers were recorded at: the registry's smoke widths and
    depth with the file's RoPE base."""
    c = _config(name)
    return dict(MODEL.small(c), rope_theta=c["rope_theta"])


def _digest(a) -> str:
    a = np.asarray(a)
    return hashlib.sha256(str(a.dtype).encode() + str(a.shape).encode()
                          + a.tobytes()).hexdigest()


def _digests(tree):
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): _digest(v) for p, v in flat}


@pytest.mark.parametrize("name", CONFIGS)
def test_weights_are_the_recorded_ones(name):
    gold = GOLDEN["configs"][name]
    config = _smoke(name)
    key = weights.root_key(GOLDEN["seed"])
    make = jax.jit(functools.partial(MODEL.program_params, config=config,
                                     param_dtype=jnp.float32))
    assert _digests(make(key)) == gold["program_params"]
    dims = MODEL.dims_from_config(config)
    layer = jax.jit(lambda k, i: MODEL.make_layer(k, i, dims))
    assert [_digests(layer(key, i)) for i in range(dims.n_layers)] == \
        gold["layers"]
    assert _digests(jax.jit(lambda k: MODEL.make_globals(k, dims))(key)) == \
        gold["globals"]


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_gaps_are_the_recorded_ones(name):
    gold = GOLDEN["configs"][name]
    ref = MODEL.Reference(_smoke(name),
                          weights.root_key(GOLDEN["seed"]), GOLDEN["seq_len"])
    seqs, nexts, alts = ([np.asarray(s, np.int32) for s in gold[k]]
                         for k in ("sequences", "next_tokens", "alt_tokens"))
    for (ga, gb), (wa, wb) in zip(ref.gaps(seqs, nexts, alts), gold["gaps"],
                                  strict=True):
        np.testing.assert_array_equal(ga, np.asarray(wa, np.float32))
        np.testing.assert_array_equal(gb, np.asarray(wb, np.float32))
    for got, want in zip(ref.argmax(seqs, "fp8"), gold["argmax_fp8"],
                         strict=True):
        np.testing.assert_array_equal(got, np.asarray(want, np.int32))


@pytest.mark.parametrize("name", CONFIGS)
def test_burst_counts_are_the_recorded_ones(name):
    config = _config(name)
    cfg = MODEL.program_config(config)
    shapes = jax.eval_shape(functools.partial(T.init_params, cfg=cfg),
                            jax.random.PRNGKey(0))
    c = MODEL.step_counts(config, cfg, shapes, kv_itemsize=2)
    got = [counts.burst_counts(c, b, p) for b, p in GOLDEN["bursts"]]
    assert json.loads(json.dumps(got)) == GOLDEN["configs"][name][
        "burst_counts"]

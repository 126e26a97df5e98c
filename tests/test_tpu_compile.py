"""The Pallas kernels of the main paths, compiled for one TPU v5e chip.

No chip is attached: the TPU compiler that ships with libtpu compiles for a
described ``v5e:2x2`` topology, and refuses what the chip would refuse
(unsupported vector layouts, more VMEM than a kernel may use).  Shapes are
the real ones: AlexNet's layers at ``alexnet_full_spec`` geometry,
qwen2_1_5b's attention widths, and paged decode at both benchmark cells'
serving shapes.  Nothing runs, so these check compilation,
not results (tests/test_kernels.py checks results in interpret mode).

This is the only file that describes the chip.  The topology is built in a
module-scoped fixture, never at import time, and these tests stay in one
file, so that only the worker that runs them loads the TPU library.
"""
import os

import pytest

import jax
import jax.numpy as jnp

from repro.core.layer_model import alexnet_full_spec
from repro.kernels import ops

IMAGES = 8
HQ, HK, HEAD_DIM = 12, 2, 128            # qwen2_1_5b attention widths
# the benchmark cells' paged decode at their serving shapes, bf16 arenas:
# (slots, query heads, KV heads, head_dim, arena pages incl. the trash page)
PAGED_SERVING = {"paged_attention_minicpm_2b": (16, 36, 36, 64, 641),
                 "paged_attention_qwen2_1_5b": (32, HQ, HK, HEAD_DIM, 4097)}
LAYERS = {s.name: s for s in alexnet_full_spec()}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out of it
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)


def _f32(*shapes):
    return [(s, jnp.float32) for s in shapes]


def _layer_case(name):
    spec = LAYERS[name]
    if spec.kind == "conv":
        h, w, c = spec.m_i
        oc = spec.m_k[0]
        return ((lambda x, wt, b: ops.conv2d(
            x, wt, b, stride=spec.stride, padding=spec.padding,
            activation="relu", interpret=False)),
            _f32((IMAGES, h, w, c), spec.m_k, (oc,)))
    if spec.kind == "pool":
        return ((lambda x: ops.pool(x, window=spec.window,
                                    stride=spec.stride, interpret=False)),
                _f32((IMAGES,) + spec.m_i))
    if spec.kind == "norm":
        return ((lambda x: ops.lrn(x, local_size=spec.local_size,
                                   interpret=False)),
                _f32((IMAGES,) + spec.m_i))
    return ((lambda x, wt, b: ops.fc(x, wt, b, activation=spec.activation,
                                     interpret=False)),
            _f32((IMAGES, spec.n_in), (spec.n_in, spec.k_o), (spec.k_o,)))


def _attention_case(name):
    if name == "flash_attention":
        s = 2048
        return ((lambda q, k, v: ops.flash_attention(q, k, v, causal=True,
                                                     interpret=False)),
                _f32((1, HQ, s, HEAD_DIM), (1, HK, s, HEAD_DIM),
                     (1, HK, s, HEAD_DIM)))
    bs, nb = 16, 128                          # 2048 tokens a slot
    fn = (lambda q, ka, va, bt, p: ops.paged_attention(
        q, ka, va, bt, p, interpret=False))
    if name in PAGED_SERVING:
        b, hq, hk, d, tb = PAGED_SERVING[name]
        return fn, ([((b, hq, 1, d), jnp.bfloat16)]
                    + [((tb, hk, bs, d), jnp.bfloat16)] * 2
                    + [((b, nb), jnp.int32), ((b,), jnp.int32)])
    b = 8
    tb = b * nb + 1
    return fn, (_f32((b, HQ, 1, HEAD_DIM), (tb, HK, bs, HEAD_DIM),
                     (tb, HK, bs, HEAD_DIM)) + [((b, nb), jnp.int32),
                                                ((b,), jnp.int32)])


@pytest.mark.parametrize("name", list(LAYERS) + ["flash_attention",
                                                 "paged_attention"]
                         + list(PAGED_SERVING))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = (_layer_case(name) if name in LAYERS
                  else _attention_case(name))
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name", list(PAGED_SERVING))
def test_paged_serving_compiles_under_highest_matmul_precision(one_chip,
                                                               name):
    """chip_smoke.py checks kernels under ``default_matmul_precision(
    "highest")``; Mosaic takes no such precision for bf16 operands, so the
    kernel pins its own."""
    fn, shapes = _attention_case(name)
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes]
    with jax.default_matmul_precision("highest"):
        compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_alexnet_pallas_plan_compiles_for_v5e(one_chip, monkeypatch):
    """The all-pallas CNNLab plan is one program holding one Mosaic kernel
    per layer.  ops picks interpret mode from the backend, which is the CPU
    here, so the test steers it to the chip's choice."""
    from repro.core import engines, plan, scheduler
    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    net = alexnet_full_spec()
    apply = plan.compile_plan(scheduler.schedule(net,
                                                 (engines.PALLAS_ENGINE,)))
    assert apply.engines == ("pallas",) * len(net)
    params = jax.eval_shape(lambda k: plan.init_network_params(net, k),
                            jax.random.PRNGKey(0))
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), params)
    x = jax.ShapeDtypeStruct((IMAGES,) + net.layers[0].m_i, jnp.float32,
                             sharding=one_chip)
    text = jax.jit(apply).lower(x, params).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == len(net)

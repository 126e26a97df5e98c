"""Integration tests: end-to-end training (loss actually decreases on
structured data), checkpoint-resume exactness, serve loop, train CLI with
preemption, sharding policy resolution."""
import functools
import subprocess
import sys
import os

import jax
import numpy as np
import pytest

from repro.configs import get
from repro.data import DataConfig, SyntheticLM
from repro.models import transformer as T
from repro.models import sharding as shard_lib
from repro.optim import adamw, schedules


def _tiny_cfg():
    return T.ModelConfig(
        name="tiny", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
        d_ff=128, vocab=64, scan_chunk=16, attention_impl="dot", remat=False)


def test_training_reduces_loss_on_structured_data():
    cfg = _tiny_cfg()
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    init_opt, update = adamw.make_optimizer(
        schedules.cosine_schedule(1e-2, 10, 150))
    opt = init_opt(params)
    pipe = SyntheticLM(DataConfig(global_batch=8, seq_len=32, vocab=64))

    @jax.jit
    def step(p, o, b):
        loss, grads = jax.value_and_grad(T.loss_fn)(p, cfg, b)
        newp, newo, _ = update(grads, o, p)
        return newp, newo, loss

    losses = []
    for _ in range(150):
        b = next(pipe)
        params, opt, loss = step(params, opt, b)
        losses.append(float(loss))
    # bigram data has ~log(8)=2.08 nats of true entropy; start is ~log(64)=4.16
    assert losses[0] > 3.5
    assert min(losses[-10:]) < losses[0] - 0.8, losses[::15]


def test_checkpoint_resume_bitexact(tmp_path):
    """Train 6 steps straight vs 3 + save + restore + 3: identical params."""
    from repro.checkpoint import save_checkpoint, restore_latest
    cfg = _tiny_cfg()
    init_opt, update = adamw.make_optimizer(schedules.constant(1e-3))

    @jax.jit
    def step(p, o, b):
        loss, grads = jax.value_and_grad(T.loss_fn)(p, cfg, b)
        newp, newo, _ = update(grads, o, p)
        return newp, newo, loss

    def fresh():
        p = T.init_params(jax.random.PRNGKey(0), cfg)
        return p, init_opt(p)

    dc = DataConfig(global_batch=4, seq_len=16, vocab=64)
    # run A: 6 straight steps
    pa, oa = fresh()
    pipe = SyntheticLM(dc)
    for _ in range(6):
        pa, oa, _ = step(pa, oa, next(pipe))
    # run B: 3 steps, checkpoint, restore, 3 more
    pb, ob = fresh()
    pipe_b = SyntheticLM(dc)
    for _ in range(3):
        pb, ob, _ = step(pb, ob, next(pipe_b))
    d = str(tmp_path / "ck")
    save_checkpoint(d, 3, {"p": pb, "o": ob},
                    extra={"data": pipe_b.state()})
    pc, oc = fresh()
    pipe_c = SyntheticLM(dc)
    _, state, extra = restore_latest(d, {"p": pc, "o": oc})
    pc, oc = state["p"], state["o"]
    pipe_c.restore(extra["data"])
    for _ in range(3):
        pc, oc, _ = step(pc, oc, next(pipe_c))
    for a, b in zip(jax.tree.leaves(pa), jax.tree.leaves(pc)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_grad_accum_matches_full_batch():
    from repro.launch.steps import _accum_grads
    cfg = _tiny_cfg()
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    b = next(SyntheticLM(DataConfig(global_batch=8, seq_len=16, vocab=64)))
    loss_full, grads_full = jax.value_and_grad(T.loss_fn)(params, cfg, b)
    loss_acc, grads_acc = _accum_grads(params, cfg, b, n=4)
    assert float(loss_full) == pytest.approx(float(loss_acc), rel=1e-4)
    for a, g in zip(jax.tree.leaves(grads_acc), jax.tree.leaves(grads_full)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(g),
                                   rtol=5e-2, atol=1e-4)


def test_train_cli_runs_and_resumes(tmp_path):
    env = dict(os.environ, PYTHONPATH="src")
    ck = str(tmp_path / "ckpt")
    cmd = [sys.executable, "-m", "repro.launch.train", "--arch", "qwen2_1_5b",
           "--scale", "smoke", "--steps", "6", "--batch", "2", "--seq", "32",
           "--ckpt-dir", ck, "--ckpt-interval", "2", "--log-every", "2"]
    r = subprocess.run(cmd, capture_output=True, text=True, cwd="/root/repo",
                       env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "loss" in r.stdout
    # resume: starts from step 5 (last checkpoint), runs to 8
    cmd2 = [c if c != "6" else "8" for c in cmd]
    r2 = subprocess.run(cmd2, capture_output=True, text=True, cwd="/root/repo",
                        env=env, timeout=600)
    assert r2.returncode == 0, r2.stderr[-2000:]
    assert "[restore] resumed at step" in r2.stdout


def test_serve_cli_generates(tmp_path):
    env = dict(os.environ, PYTHONPATH="src")
    base = [sys.executable, "-m", "repro.launch.serve", "--arch",
            "qwen2_1_5b", "--scale", "smoke", "--batch", "2", "--prompt-len",
            "8", "--gen-len", "8", "--requests", "4"]
    # default path: continuous-batching engine (repro.serving)
    r = subprocess.run(base + ["--slots", "2", "--rate", "100"],
                       capture_output=True, text=True, cwd="/root/repo",
                       env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "requests_done: 4" in r.stdout
    assert "requests_dropped: 0" in r.stdout
    # legacy fallback: static batching
    r = subprocess.run(base + ["--static-batching"], capture_output=True,
                       text=True, cwd="/root/repo", env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "served 4 requests" in r.stdout


# --------------------------------------------------------------- sharding
def test_policy_tp_vs_fsdp_mode():
    from jax.sharding import Mesh
    devs = np.asarray(jax.devices()[:1]).reshape(1, 1)
    mesh = Mesh(devs, ("data", "model"))
    # single-device mesh: everything resolves to replicated but must not error
    for arch_name in ("qwen2_1_5b", "mixtral_8x7b", "falcon_mamba_7b"):
        cfg = get(arch_name).config
        policy = shard_lib.make_policy(cfg, mesh)
        shapes = jax.eval_shape(
            functools.partial(T.init_params, cfg=cfg), jax.random.PRNGKey(0))
        sh = shard_lib.param_shardings(cfg, policy, shapes)
        assert len(jax.tree.leaves(sh)) == len(jax.tree.leaves(shapes))


def test_resolver_divisibility_fallbacks():
    from jax.sharding import Mesh, PartitionSpec as P
    devs = np.asarray(jax.devices() * 1)[:1]
    # fake 16x16 mesh shape via Mesh of 1 device can't be built; test the
    # resolver's pure logic with a mocked mesh-shape mapping instead
    class FakeMesh:
        shape = {"data": 16, "model": 16}
    pol = shard_lib.ShardingPolicy(mesh=FakeMesh(), tp_mode=True)
    # heads=56 (deepseek): not divisible by 16 -> replicated
    assert pol.resolve((7168, 7168), ["embed", "heads"]) == P("data", "model")
    assert pol.resolve((7168, 56 * 128), ["embed", "heads"])[1] == "model"
    # kv_heads=8: replicated on a 16-way axis
    spec = pol.resolve((4096, 8 * 128), ["embed", "kv_heads"])
    assert spec[1] == "model"  # 1024 % 16 == 0 -> sharded (flattened dim)
    # expert=16 divides -> 'model'; then ff can't reuse 'model'
    spec = pol.resolve((16, 4096, 6400), ["expert", "embed", "ff"])
    assert spec[0] == "model" and spec[2] is None
    # expert=8 does not divide 16 -> ff gets 'model'
    spec = pol.resolve((8, 4096, 14336), ["expert", "embed", "ff"])
    assert spec[0] is None and spec[2] == "model"


def test_cache_shardings_kv_and_ssm():
    from jax.sharding import PartitionSpec as P
    class FakeMesh:
        shape = {"data": 16, "model": 16}
    cfg = get("mixtral_8x7b").config
    pol = shard_lib.ShardingPolicy(mesh=FakeMesh(), tp_mode=True)
    cache = jax.eval_shape(lambda: T.init_cache(cfg, 128, max_seq=4096))
    sh = shard_lib.cache_shardings(cfg, pol, cache)
    flat = jax.tree_util.tree_flatten_with_path(sh)[0]
    kv = [s for p, s in flat if any(getattr(q, "key", "") == "k" for q in p)]
    assert kv, "kv cache leaves missing"
    spec = getattr(kv[0], "spec", kv[0])   # FakeMesh returns bare P
    # mixtral kv=8 heads won't shard over 16 -> time dim takes 'model'
    assert spec[3] == "model" and spec[1] == "data"


# ------------------------------------------------------ compilation cache
_CACHE_PROBE = """
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compilation_cache
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
print(enable_compilation_cache())
jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((32, 32))).block_until_ready()
"""


def test_compilation_cache_lands_in_env_dir(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, entries land there and only
    there; the helper sets no directory of its own."""
    from repro.launch.compile_cache import CHECKOUT_CACHE_DIR
    before = (set(os.listdir(CHECKOUT_CACHE_DIR))
              if CHECKOUT_CACHE_DIR.exists() else set())
    env = dict(os.environ, PYTHONPATH="src", JAX_ENABLE_COMPILATION_CACHE="1",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    r = subprocess.run([sys.executable, "-c", _CACHE_PROBE],
                       capture_output=True, text=True, env=env, timeout=300,
                       cwd=CHECKOUT_CACHE_DIR.parent)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip() == str(tmp_path)
    assert os.listdir(tmp_path)
    after = (set(os.listdir(CHECKOUT_CACHE_DIR))
             if CHECKOUT_CACHE_DIR.exists() else set())
    assert after == before


def test_compilation_cache_defaults_to_checkout(monkeypatch):
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        path = compile_cache.enable_compilation_cache()
        assert path == str(compile_cache.CHECKOUT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.CHECKOUT_CACHE_DIR.name == ".jax_cache"
        assert (compile_cache.CHECKOUT_CACHE_DIR.parent / "pyproject.toml"
                ).exists()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)

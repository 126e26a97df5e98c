"""Training driver.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2_1_5b \
        --steps 200 --batch 8 --seq 128 --scale smoke --ckpt-dir /tmp/ckpt

Production features wired in:
  * pjit over the mesh (host mesh on CPU; production mesh on pods);
  * checkpoint/restore with atomic publish, keep-k, elastic resharding
    (restart with a different mesh reshard-restores);
  * preemption handling (SIGTERM -> checkpoint -> clean exit);
  * straggler detection (EWMA step timer);
  * gradient accumulation (--grad-accum) and int8 gradient compression with
    error feedback (--grad-compression int8) for cross-pod all-reduce;
  * WSD or cosine schedule per the arch registry.

XLA collective/compute overlap: on real TPU runtimes, enable the
latency-hiding scheduler with
  LIBTPU_INIT_ARGS="--xla_tpu_enable_async_collective_fusion=true" and
  XLA_FLAGS="--xla_tpu_enable_latency_hiding_scheduler=true" — documented
here because this container's CPU backend ignores them.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import time

import jax

from ..checkpoint import CheckpointManager
from ..configs import registry
from ..data import DataConfig, make_pipeline
from ..models import sharding as shard_lib
from ..models import transformer as T
from ..optim import adamw, compression, schedules
from ..runtime import PreemptionHandler, StepTimer
from .compile_cache import enable_compilation_cache
from .mesh import make_host_mesh, make_production_mesh


def _schedule(name: str, steps: int):
    if name == "wsd":
        return schedules.wsd_schedule(3e-3, max(steps // 20, 1),
                                      int(steps * 0.7), int(steps * 0.25))
    return schedules.cosine_schedule(3e-3, max(steps // 20, 1), steps)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2_1_5b")
    ap.add_argument("--scale", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--grad-compression", default="none",
                    choices=["none", "int8"])
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-interval", type=int, default=50)
    ap.add_argument("--corpus", default="")
    ap.add_argument("--mesh", default="host", choices=["host", "pod",
                                                       "multipod"])
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    enable_compilation_cache()

    arch = registry.get(args.arch)
    cfg = arch.smoke if args.scale == "smoke" else arch.config
    assert cfg is not None, f"{args.arch} has no LM config"
    if args.seq % max(cfg.scan_chunk, 1):
        cfg = dataclasses.replace(cfg, scan_chunk=min(cfg.scan_chunk,
                                                      args.seq))

    mesh = (make_host_mesh() if args.mesh == "host" else
            make_production_mesh(multi_pod=args.mesh == "multipod"))
    policy = shard_lib.make_policy(cfg, mesh)

    # ---- data -------------------------------------------------------
    dcfg = DataConfig(global_batch=args.batch, seq_len=args.seq,
                      vocab=cfg.vocab, frontend=cfg.frontend,
                      d_model=cfg.d_model, img_seq=cfg.img_seq,
                      enc_len=args.seq)
    pipe = make_pipeline(dcfg, corpus=args.corpus or None)

    # ---- state ------------------------------------------------------
    init_opt, update = adamw.make_optimizer(
        _schedule(arch.lr_schedule, args.steps))
    p_shapes = jax.eval_shape(
        functools.partial(T.init_params, cfg=cfg), jax.random.PRNGKey(0))
    p_sh = shard_lib.param_shardings(cfg, policy, p_shapes)

    with mesh:
        params = jax.jit(functools.partial(T.init_params, cfg=cfg),
                         out_shardings=p_sh)(jax.random.PRNGKey(0))
        opt_state = init_opt(params)
        err_fb = (compression.init_error(params)
                  if args.grad_compression == "int8" else None)

    # ---- restore (elastic: shardings are the *current* mesh's) ------
    start_step = 0
    ckpt = CheckpointManager(args.ckpt_dir, args.ckpt_interval) \
        if args.ckpt_dir else None
    if ckpt:
        restored = ckpt.restore({"params": params, "opt": opt_state})
        if restored:
            start_step, state, extra = restored
            params, opt_state = state["params"], state["opt"]
            with mesh:
                params = jax.device_put(params, p_sh)
            if "data" in extra:
                pipe.restore(extra["data"])
            print(f"[restore] resumed at step {start_step}")

    # ---- step -------------------------------------------------------
    def train_step(params, opt_state, err, batch):
        def lf(p):
            return T.loss_fn(p, cfg, batch)
        loss, grads = jax.value_and_grad(lf)(params)
        if err is not None:
            grads, err = compression.compressed_allreduce_update(grads, err)
        new_p, new_o, metrics = update(grads, opt_state, params)
        return new_p, new_o, err, {"loss": loss, **metrics}

    jstep = jax.jit(train_step, donate_argnums=(0, 1, 2))

    timer = StepTimer()
    preempt = PreemptionHandler()
    t_start = time.time()
    step = start_step
    for step in range(start_step, args.steps):
        batch = next(pipe)
        timer.start()
        with mesh:
            params, opt_state, err_fb, metrics = jstep(
                params, opt_state, err_fb, batch)
        metrics = jax.device_get(metrics)
        straggler = timer.stop(step)
        if straggler:
            print(straggler)
        if step % args.log_every == 0 or step == args.steps - 1:
            print(f"step {step:5d} loss {float(metrics['loss']):.4f} "
                  f"lr {float(metrics['lr']):.2e} "
                  f"gnorm {float(metrics['grad_norm']):.3f}", flush=True)
        if ckpt:
            ckpt.maybe_save(step, {"params": params, "opt": opt_state},
                            extra={"data": pipe.state()})
        if preempt.should_stop:
            print("[preempt] saving final checkpoint and exiting")
            if ckpt:
                ckpt.maybe_save(step, {"params": params, "opt": opt_state},
                                extra={"data": pipe.state()}, force=True)
            break

    if ckpt and not preempt.should_stop:
        ckpt.maybe_save(step, {"params": params, "opt": opt_state},
                        extra={"data": pipe.state()}, force=True)
    dt = time.time() - t_start
    n = max(step - start_step + 1, 1)
    print(f"done: {n} steps in {dt:.1f}s ({dt / n * 1e3:.0f} ms/step); "
          f"stragglers flagged: {len(timer.stragglers)}")


if __name__ == "__main__":
    main()

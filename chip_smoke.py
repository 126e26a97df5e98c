#!/usr/bin/env python3
"""Smoke run of the system's two main paths on a TPU, in one process.

    python chip_smoke.py              # one chip
    python chip_smoke.py --multichip  # a host with >= 2 chips

One chip, in order:

1. device check: the first device must be a TPU whose ``device_kind`` has
   a pricing model (``core.device_models.BY_DEVICE_KIND``);
2. kernels: every Pallas kernel of the two paths, compiled for the chip
   (``interpret=False``), against its ``kernels/ref.py`` oracle, at the
   AlexNet (``alexnet_full_spec``) and qwen2_1_5b widths;
3. CNNLab: the AlexNet plan with every layer on ``xla``, every layer on
   ``pallas``, and as ``scheduler.schedule`` assigns it over
   ``DEFAULT_ENGINES``, each compiled with ``plan.compile_plan`` and run on
   a batch of 224x224 images; the outputs must agree;
4. serving: qwen2_1_5b at full width (28 layers, random weights from a
   seed) through ``serving.api.serve``, with the paged and then the dense
   KV layout; every request must complete with its full token count, and
   the two layouts must emit identical tokens request by request.

``--multichip`` runs only disaggregated serving (prefill on chip 0, decode
on chip 1) and the colocated run on chip 0 it must match token for token.

Times printed here are smoke numbers, not metrics.  Any failed check exits
non-zero; the last line of a passing run is one JSON object naming the
device.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

IMAGES = 8                       # CNNLab batch
SERVE = dict(arch="qwen2_1_5b", scale="full", requests=8, slots=4,
             prompt_len=32, gen_len=16, rate=1e9)
# Kernels, oracles and plans all run at "highest" matmul precision (float32
# contraction on the MXU; the default is a single bf16 pass, ~1e-2 off at
# these sizes), so the checks test the math.  (rtol, atol); the inputs are
# scaled so every output is O(1) (LRN: O(10), where its window term
# matters).
MXU_TOL = (5e-4, 5e-4)
POOL_TOL = (0.0, 0.0)
LRN_TOL = (1e-4, 1e-4)
PLAN_TOL = (1e-3, 1e-7)


def say(*parts) -> None:
    print(*parts, flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"[chip_smoke] FAILED: {what}")


def device_check():
    import jax
    devs = jax.devices()
    d = devs[0]
    say(f"[device] platform={d.platform} kind={d.device_kind!r} "
        f"count={len(devs)} jax={jax.__version__}")
    if d.platform != "tpu":
        raise SystemExit(f"[chip_smoke] no TPU: jax found {d.platform!r}")
    from repro.core.device_models import for_device_kind
    model = for_device_kind(d.device_kind)
    say(f"[device] {d.device_kind!r} is priced on device model "
        f"{model.name!r}")
    return devs


class CompileClock:
    """Seconds of XLA backend compilation, and persistent-cache hits, while
    the block runs (jax.monitoring events; a cache hit skips the compile)."""

    def __enter__(self):
        import jax.monitoring as mon
        self.seconds, self.cache_hits = 0.0, 0
        self.t0 = time.perf_counter()

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += secs

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        self._listeners = (on_duration, on_event)
        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)
        return self

    def __exit__(self, *exc):
        import jax.monitoring as mon
        self.wall = time.perf_counter() - self.t0
        mon.unregister_event_duration_listener(self._listeners[0])
        mon.unregister_event_listener(self._listeners[1])

    def line(self) -> str:
        return (f"wall {self.wall:.2f}s, xla compile {self.seconds:.2f}s, "
                f"rest {self.wall - self.seconds:.2f}s, "
                f"{self.cache_hits} persistent-cache hits")


# --------------------------------------------------------------- kernels
def _kernel_cases(key):
    import jax
    import jax.numpy as jnp

    from repro.configs import registry
    from repro.core.layer_model import alexnet_full_spec
    from repro.kernels import ops, ref

    keys = iter(jax.random.split(key, 64))

    def normal(shape, scale=1.0):
        return jax.random.normal(next(keys), shape, jnp.float32) * scale

    cases = []
    for spec in alexnet_full_spec():
        if spec.kind == "conv":
            h, w, c = spec.m_i
            oc, ic, kh, kw = spec.m_k
            args = (normal((IMAGES, h, w, c)),
                    normal((oc, ic, kh, kw), (2.0 / (ic * kh * kw)) ** 0.5),
                    normal((oc,), 0.1))
            kw_ = dict(stride=spec.stride, padding=spec.padding,
                       activation="relu")
            cases.append((f"conv2d {spec.name}", args,
                          lambda x, w, b, kw_=kw_: ops.conv2d(
                              x, w, b, interpret=False, **kw_),
                          lambda x, w, b, kw_=kw_: ref.conv2d_ref(
                              x, w, b, **kw_), MXU_TOL))
        elif spec.kind == "pool":
            h, w, c = spec.m_i
            kw_ = dict(window=spec.window, stride=spec.stride)
            cases.append((f"pool {spec.name}", (normal((IMAGES, h, w, c)),),
                          lambda x, kw_=kw_: ops.pool(x, interpret=False,
                                                      **kw_),
                          lambda x, kw_=kw_: ref.maxpool_ref(x, **kw_),
                          POOL_TOL))
        elif spec.kind == "norm":
            h, w, c = spec.m_i
            kw_ = dict(local_size=spec.local_size, alpha=spec.alpha,
                       beta=spec.beta)
            cases.append((f"lrn {spec.name}",
                          (normal((IMAGES, h, w, c), 100.0),),
                          lambda x, kw_=kw_: ops.lrn(x, interpret=False,
                                                     **kw_),
                          lambda x, kw_=kw_: ref.lrn_ref(x, **kw_),
                          LRN_TOL))
        elif spec.kind == "fc" and spec.name in ("FC6", "FC7"):
            args = (normal((IMAGES, spec.n_in)),
                    normal((spec.n_in, spec.k_o), (2.0 / spec.n_in) ** 0.5),
                    normal((spec.k_o,), 0.1))
            cases.append((f"matmul {spec.name}", args,
                          lambda x, w, b: ops.fc(x, w, b, activation="relu",
                                                 interpret=False),
                          lambda x, w, b: ref.fc_ref(x, w, b,
                                                     activation="relu"),
                          MXU_TOL))

    # the served model's attention widths (qwen2_1_5b: 12 query heads, 2 KV
    # heads, head_dim 128)
    cfg = registry.get(SERVE["arch"]).config
    hq, hk, d = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    s = 2048
    cases.append((f"flash_attention S={s} D={d}",
                  (normal((1, hq, s, d)), normal((1, hk, s, d)),
                   normal((1, hk, s, d))),
                  lambda q, k, v: ops.flash_attention(q, k, v, causal=True,
                                                      interpret=False),
                  lambda q, k, v: ref.attention_ref(q, k, v, causal=True),
                  MXU_TOL))
    b, bs, nb = 8, 16, 128                     # 8 slots x 2048 tokens
    tb = b * nb + 1                            # + trash page
    table = jax.random.permutation(next(keys), tb - 1)[:b * nb] + 1
    paged_args = (normal((b, hq, 1, d)), normal((tb, hk, bs, d)),
                  normal((tb, hk, bs, d)),
                  table.reshape(b, nb).astype(jnp.int32),
                  jax.random.randint(next(keys), (b,), 0, nb * bs,
                                     jnp.int32))
    cases.append((f"paged_attention B={b} T={nb * bs} block={bs}",
                  paged_args,
                  lambda q, ka, va, bt, p: ops.paged_attention(
                      q, ka, va, bt, p, interpret=False),
                  lambda q, ka, va, bt, p: ref.paged_attention_ref(
                      q, ka, va, bt, p),
                  MXU_TOL))
    return cases


def kernel_phase(key) -> None:
    import jax
    import jax.numpy as jnp
    for name, args, kernel, oracle, (rtol, atol) in _kernel_cases(key):
        with jax.default_matmul_precision("highest"):
            compiled = jax.jit(kernel).lower(*args).compile()
            got = compiled(*args)
            want = jax.jit(oracle)(*args)
        check("tpu_custom_call" in compiled.as_text(),
              f"{name}: no Mosaic kernel in the compiled program")
        check(got.shape == want.shape,
              f"{name}: shape {got.shape} != oracle {want.shape}")
        diff = jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32))
        err = float(jnp.max(diff))
        ok = bool(jnp.all(jnp.isfinite(got))) and bool(
            jnp.all(diff <= atol + rtol * jnp.abs(want)))
        say(f"[kernel] {name:<46} out {tuple(got.shape)} "
            f"max_abs_err={err:.3e} (rtol={rtol:g}, atol={atol:g}) "
            f"{'ok' if ok else 'FAIL'}")
        check(ok, f"{name}: outside tolerance of its oracle")


# ---------------------------------------------------------------- CNNLab
def cnnlab_phase(key) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core import engines, plan, scheduler
    from repro.core.layer_model import alexnet_full_spec

    net = alexnet_full_spec()
    k_params, k_x = jax.random.split(key)
    params = plan.init_network_params(net, k_params)
    x = jax.random.normal(k_x, (IMAGES,) + net.layers[0].m_i, jnp.float32)
    plans = {
        "xla": scheduler.schedule(net, (engines.XLA_ENGINE,)),
        "pallas": scheduler.schedule(net, (engines.PALLAS_ENGINE,)),
        "scheduled": scheduler.schedule(net, engines.DEFAULT_ENGINES),
    }
    say("[cnnlab] schedule(alexnet_full_spec, DEFAULT_ENGINES): "
        + ", ".join(f"{a.spec.name}->{a.engine}"
                    for a in plans["scheduled"].assignments))
    outs = {}
    for name, p in plans.items():
        apply = plan.compile_plan(p)
        with jax.default_matmul_precision("highest"):
            t0 = time.perf_counter()
            compiled = jax.jit(apply).lower(x, params).compile()
            t_compile = time.perf_counter() - t0
            t0 = time.perf_counter()
            out = compiled(x, params).block_until_ready()
            t_run = time.perf_counter() - t0
        n_kernels = compiled.as_text().count(
            'custom_call_target="tpu_custom_call"')
        n_pallas = sum(e == "pallas" for e in apply.engines)
        say(f"[cnnlab] plan {name}: ran on "
            + ", ".join(f"{a.spec.name}:{e}" for a, e in
                        zip(p.assignments, apply.engines))
            + f"; {n_kernels} Mosaic kernels in the program; "
            f"compile {t_compile:.2f}s, run {t_run:.3f}s (smoke)")
        check(n_kernels == n_pallas,
              f"plan {name}: {n_kernels} Mosaic kernels for {n_pallas} "
              f"pallas layers")
        check(out.shape == (IMAGES, net.layers[-1].k_o)
              and bool(jnp.all(jnp.isfinite(out))),
              f"plan {name}: output {out.shape} not finite")
        outs[name] = out
    rtol, atol = PLAN_TOL
    for name in ("pallas", "scheduled"):
        diff = jnp.abs(outs[name] - outs["xla"])
        ok = bool(jnp.all(diff <= atol + rtol * jnp.abs(outs["xla"])))
        same_top1 = bool(jnp.all(jnp.argmax(outs[name], -1)
                                 == jnp.argmax(outs["xla"], -1)))
        say(f"[cnnlab] {name} vs xla: max_abs_err={float(jnp.max(diff)):.3e}"
            f" (rtol={rtol:g}, atol={atol:g}) top1_equal={same_top1} "
            f"{'ok' if ok else 'FAIL'}")
        check(ok, f"plan {name} output not allclose to the xla plan")


# --------------------------------------------------------------- serving
def _serve(tag: str, *, kv_layout: str = "paged", **placement):
    """One ``serve()`` run of the SERVE workload; checks that every request
    completed with its full token count.  Returns (rid -> tokens, report)."""
    from repro.serving.api import ServeOptions, serve

    opts = ServeOptions()
    w = opts.workload
    w.arch, w.scale, w.requests = (SERVE["arch"], SERVE["scale"],
                                   SERVE["requests"])
    w.prompt_len, w.gen_len, w.rate = (SERVE["prompt_len"], SERVE["gen_len"],
                                       SERVE["rate"])
    opts.engine.slots = SERVE["slots"]
    opts.engine.kv_layout = kv_layout
    for k, v in placement.items():
        setattr(opts.placement, k, v)
    with CompileClock() as clock:
        report = serve(opts)
    summ = report.summary
    say(f"[serve] {tag}: {clock.line()} (smoke, not metrics)")
    say(f"[serve] {tag} summary (smoke): " + json.dumps(
        {k: summ[k] for k in ("requests_done", "requests_dropped", "steps",
                              "tokens_in", "tokens_out", "elapsed_s")}))
    check(summ["requests_done"] == SERVE["requests"]
          and summ["requests_dropped"] == 0,
          f"{tag}: {summ['requests_done']}/{SERVE['requests']} requests "
          f"done, {summ['requests_dropped']} dropped")
    for r in report.requests:
        check(len(r.output) == r.max_new_tokens,
              f"{tag}: request {r.rid} emitted {len(r.output)} of "
              f"{r.max_new_tokens} tokens")
    outputs = {rid: [int(t) for t in out]
               for rid, out in report.outputs.items()}
    gc.collect()
    return outputs, report


def _compare(tag: str, got: dict, want: dict) -> None:
    differ = sorted(rid for rid in want.keys() | got.keys()
                    if got.get(rid) != want.get(rid))
    say(f"[serve] {tag}: {len(want) - len(differ)}/{len(want)} requests "
        f"token-identical" + (f"; differ: {differ}" if differ else ""))
    check(not differ, f"{tag}: outputs differ for requests {differ}")


def serving_phase() -> None:
    say(f"[serve] qwen2_1_5b: {SERVE}")
    paged, _ = _serve("paged", kv_layout="paged")
    dense, _ = _serve("dense", kv_layout="dense")
    _compare("paged vs dense", paged, dense)


def multichip_phase(devs) -> None:
    check(len(devs) >= 2, f"--multichip needs >= 2 chips, found {len(devs)}")
    say(f"[multichip] qwen2_1_5b: {SERVE}")
    colocated, _ = _serve("colocated")
    disagg, report = _serve("disagg", placement="disagg",
                            device_assignment="auto")
    devices = report.devices or {}
    say(f"[multichip] assignment: prefill -> {devices.get('prefill')}, "
        f"decode -> {devices.get('decode')}")
    check(len(set(devices.values())) == 2
          and all(v.startswith("tpu:") for v in devices.values()),
          f"disagg did not run on two distinct tpu devices: {devices}")
    h = report.handoff
    say(f"[multichip] hand-off: {h['n_handoffs']} hand-offs, "
        f"{h['bytes_moved']} bytes moved")
    _compare("disagg vs colocated", disagg, colocated)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--multichip", action="store_true",
                    help="run only disaggregated serving across two chips "
                         "and its colocated reference")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devs = device_check()
    import jax

    from repro.launch.compile_cache import enable_compilation_cache
    say(f"[device] compilation cache: {enable_compilation_cache()}")
    t0 = time.perf_counter()
    if args.multichip:
        multichip_phase(devs)
    else:
        key = jax.random.PRNGKey(args.seed)
        k_kernels, k_cnnlab = jax.random.split(key)
        for name, phase in (("kernels", lambda: kernel_phase(k_kernels)),
                            ("cnnlab", lambda: cnnlab_phase(k_cnnlab)),
                            ("serving", serving_phase)):
            t = time.perf_counter()
            phase()
            gc.collect()
            say(f"[phase] {name} passed in {time.perf_counter() - t:.1f}s")
    say(f"[chip_smoke] all phases passed in {time.perf_counter() - t0:.1f}s")
    d = devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind, "count": len(devs)}}),
        flush=True)


if __name__ == "__main__":
    main()

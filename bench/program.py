"""Everything the benchmark takes from the program under test: its model
configuration, its serving loop (built as ``serving.api.serve`` builds it)
and the ``Request`` objects it serves.

``BenchLoop`` is ``EngineLoop`` with the hooks ``OpenLoopDriver`` calls
wrapped, without touching the program: each hook is a host span
(``jax.profiler.TraceAnnotation``, on the profiler's clock) when tracing,
and feeds the counters the per-layer metrics read.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.launch.mesh import make_host_mesh
from repro.models import sharding as shard_lib
from repro.models import transformer as T
from repro.serving.engine_loop import EngineLoop, SlotEngine
from repro.serving.request import Request

from . import counts as counts_lib
from . import weights as weights_lib


class StopServing(Exception):
    """Raised from a loop hook to end a run once its requests are in."""


def model_config(config: Dict) -> T.ModelConfig:
    """The program's configuration for a configuration file: the registry
    entry it names, cut in depth and given the norm epsilon as the file
    says (options the program has), then checked key by key against the
    published numbers the file holds.  The program must store the weights
    at least as exactly as the checkpoint's ``torch_dtype`` holds them."""
    cfg = registry.get(config["arch"]).config
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


def make_params(cfg: T.ModelConfig, dims: weights_lib.Dims, seed: int):
    """The seed's weights (``dims`` from the configuration file), made on
    the device in one jitted call, in the program's tree and storage
    dtype, with the shardings ``api.build_params`` gives them."""
    mesh = make_host_mesh()
    shapes = jax.eval_shape(functools.partial(T.init_params, cfg=cfg),
                            jax.random.PRNGKey(0))
    shardings = shard_lib.param_shardings(
        cfg, shard_lib.make_policy(cfg, mesh), shapes)
    make = jax.jit(functools.partial(weights_lib.program_params, dims=dims,
                                     param_dtype=cfg.param_dtype),
                   out_shardings=shardings)
    with mesh:
        params = make(weights_lib.root_key(seed))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), shapes)
    if got != want:
        raise ValueError("the benchmark's weights do not match the "
                         "program's parameter tree")
    return params, mesh


def requests(triples) -> List[Request]:
    return [Request(rid=i, prompt=p, max_new_tokens=o, arrival=t)
            for i, (t, p, o) in enumerate(triples)]


def buckets(burst: int) -> List[int]:
    """The compiled burst sizes ``SlotEngine.dispatch`` runs a burst as."""
    out = []
    while burst > 0:
        b = min(SlotEngine.MAX_BUCKET, 1 << (burst.bit_length() - 1))
        out.append(b)
        burst -= b
    return out


@dataclasses.dataclass
class Dispatch:
    t: float
    steps: int
    tokens: int
    flops: float
    bound_s: float                # least time the buckets need at peak
    traced: bool


class BenchLoop(EngineLoop):
    """The colocated loop with its driver hooks wrapped."""

    def __init__(self, cfg, params, *, peaks: Dict, annotate: bool, **kw):
        super().__init__(cfg, params, **kw)
        self.counts = step_counts(cfg, params, self)
        self.peaks = peaks
        self.annotate = annotate
        self.dispatches: List[Dispatch] = []
        self.kv_unused: List[tuple] = []      # (t, reserved-unused share)
        self.queue_depth: List[tuple] = []    # (t, queued requests)
        self.now: float = 0.0
        self.clock: Optional[Callable[[], float]] = None
        # called with the driver's time at each iteration's start / end
        self.on_iteration_start: Callable[[float], None] = lambda t: None
        self.on_iteration_end: Callable[[float], None] = lambda t: None
        self.tracing = False

    def _t(self) -> float:
        return self.clock() if self.clock is not None else self.now

    def _span(self, name: str):
        if self.annotate:
            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()

    def admit(self, queue, now, metrics):
        self.now = now
        self.on_iteration_start(now)
        self.queue_depth.append((now, len(queue)))
        with self._span("admit"):
            return super().admit(queue, now, metrics)

    def dispatch(self, throttle, budget):
        eng = self.engine
        live = [s for s, r in enumerate(eng.slots)
                if r is not None and eng.active[s]]
        pos = [int(eng.steps_done[s]) + eng.slots[s].shared_tokens
               for s in live]
        t = self._t()
        with self._span("dispatch"):
            burst = super().dispatch(throttle, budget)
        if burst > 0:
            parts = counts_lib.burst_counts(self.counts, buckets(burst), pos)
            bound = sum(max(f / self.peaks["bf16_flops_per_s"],
                            b / self.peaks["hbm_bytes_per_s"])
                        for _, _, f, b in parts)
            self.dispatches.append(Dispatch(
                t, burst, sum(p[1] for p in parts),
                sum(p[2] for p in parts), bound, self.tracing))
        return burst

    def sample(self, metrics):
        with self._span("sample"):
            super().sample(metrics)
        pool = self.pool
        leased = pool.allocated_block_count * pool.block_size
        self.kv_unused.append(
            (self._t(), (leased - pool.written_tokens)
             / (pool.total_blocks * pool.block_size)))

    def scan(self, clock, metrics, sink):
        self.clock = clock
        with self._span("scan"):
            super().scan(clock, metrics, sink)
        self.on_iteration_end(clock())


class CompileClock:
    """Seconds of XLA backend compilation, compilations, and persistent-
    cache hits while the block runs (``jax.monitoring`` events)."""

    def __enter__(self):
        import jax.monitoring as mon
        self.seconds, self.compiles, self.cache_hits = 0.0, 0, 0
        self.t0 = time.perf_counter()

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += secs
                self.compiles += 1

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


def step_counts(cfg: T.ModelConfig, params, loop: EngineLoop
                ) -> counts_lib.StepCounts:
    kv_leaf = jax.tree.leaves(loop.engine.cache["layers"])[0]
    return counts_lib.step_counts(
        n_layers=cfg.n_layers, d_model=cfg.d_model, n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads, head_dim=cfg.hd, d_ff=cfg.d_ff,
        vocab=cfg.vocab, param_leaves=jax.tree.leaves(params),
        compute_itemsize=jnp.dtype(cfg.compute_dtype).itemsize,
        kv_itemsize=np.dtype(kv_leaf.dtype).itemsize)

"""One run of one cell: set-up, lead-in, the measured window, the drain,
then the check against the reference.

Timeline (the serving driver's clock, 0 = the start of the lead-in):
``[0, lead_in)`` load builds up; ``[lead_in, lead_in + seconds)`` is the
window; after it the run goes on, under the same load, until every counted
request is done or the drain cap passes.  The traffic file says which
requests count: those that arrive, are admitted or finish in the window
(``count_by``).
"""
from __future__ import annotations

import dataclasses
import gc
import json
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional

import numpy as np

from . import spec as spec_lib

CACHE_DIR = spec_lib.ROOT / ".jax_cache"
TRACE_SECONDS = 3.0
# fewest served tokens a run compares with the reference
MIN_COMPARED = 100


@dataclasses.dataclass
class ReqRecord:
    rid: int
    arrival: float
    prompt_len: int
    max_new: int
    admitted: Optional[float] = None
    first: Optional[float] = None
    done: Optional[float] = None
    tokens: List[int] = dataclasses.field(default_factory=list)
    counted: bool = False


@dataclasses.dataclass
class TraceInfo:
    window_s: float               # host seconds the profiler recorded
    busy_s: float                 # device seconds with an operation running
    burst_s: Optional[float]      # device seconds of the burst programs
    steps: int
    tokens: int
    flops: float
    bound_s: float                # least time of those steps at peak
    device_ops: list
    idle_gaps: list


@dataclasses.dataclass
class Run:
    """What a metric reader sees."""
    seconds: float
    window: tuple
    stop: float
    setup_s: float
    requests: List[ReqRecord]
    window_tokens: int
    deliveries: list              # (driver time, tokens) of every delta
    iteration_ends: list          # driver time at each iteration's end
    dispatches: list
    kv_unused: list
    queue_depth: list
    peaks: Dict
    trace: Optional[TraceInfo] = None

    @property
    def counted(self) -> List[ReqRecord]:
        return [r for r in self.requests if r.counted]

    def in_window(self, t: float) -> bool:
        return self.window[0] <= t < self.window[1]


def configure_jax() -> None:
    """A persistent compilation cache at a fixed path in the checkout that
    keeps every program, however fast it compiled."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def chips_or_exit(chips: int):
    """The chips the cell asks for, or exit: no accelerator, no result."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"[bench] needs {chips} TPU chip(s); JAX has "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        sys.exit(3)
    if chips != 1:
        print("[bench] this harness drives one-chip cells only",
              file=sys.stderr)
        sys.exit(3)
    return devices[:chips]


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class Profiler:
    """Records ``[start, stop)`` of the driver's clock with jax.profiler,
    switched at iteration boundaries, where the device has finished the
    previous burst (streaming syncs there)."""

    def __init__(self, loop, start: float, stop: float):
        self.loop, self.start, self.stop = loop, start, stop
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.t0 = self.t1 = None
        loop.on_iteration_start = self._begin
        loop.on_iteration_end = self._end

    def _begin(self, t: float) -> None:
        import jax
        if self.t0 is None and t >= self.start:
            # host spans, no Python call tracing (it costs the host more
            # than the loop it would explain)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.t0 = time.perf_counter()
            self.loop.tracing = True

    def _end(self, t: float) -> None:
        import jax
        if self.t0 is not None and self.t1 is None and t >= self.stop:
            self.t1 = time.perf_counter()
            self.loop.tracing = False
            jax.profiler.stop_trace()

    def finish(self) -> None:
        """Stop a trace the run ended inside."""
        import jax
        if self.t0 is not None and self.t1 is None:
            self.t1 = time.perf_counter()
            self.loop.tracing = False
            jax.profiler.stop_trace()

    def read(self) -> Optional[TraceInfo]:
        from . import trace as trace_lib
        if self.t0 is None:
            return None
        t = time.perf_counter()
        summary = trace_lib.reduce_trace(
            trace_lib.events(trace_lib.find_xplane(self.dir)))
        say(f"trace read in {time.perf_counter() - t:.3f}s")
        traced = [d for d in self.loop.dispatches if d.traced]
        return TraceInfo(
            window_s=self.t1 - self.t0, busy_s=summary.busy_s,
            burst_s=trace_lib.module_time(summary, "jit_burst"),
            steps=sum(d.steps for d in traced),
            tokens=sum(d.tokens for d in traced),
            flops=sum(d.flops for d in traced),
            bound_s=sum(d.bound_s for d in traced),
            device_ops=summary.device_ops, idle_gaps=summary.idle_gaps)

    def close(self) -> None:
        self.finish()
        shutil.rmtree(self.dir, ignore_errors=True)


def drive(loop, reqs, *, lead_in: float, seconds: float, drain_cap: float,
          count_by: str) -> tuple:
    """Serve ``reqs`` until the window's requests are in; returns the
    request records, the driver's time when the run stopped, and a dict
    of the window's delivered tokens, every delivery and every
    iteration's end."""
    from repro.serving.request import RequestState

    from .program import StopServing
    t0, t1 = lead_in, lead_in + seconds
    recs = {r.rid: ReqRecord(r.rid, r.arrival, r.prompt_len,
                             r.max_new_tokens) for r in reqs}
    by_rid = {r.rid: r for r in reqs}
    state = {"stop": None, "window_tokens": 0, "deliveries": [],
             "iteration_ends": []}

    def on_delta(d):
        rec = recs[d.rid]
        if d.tokens:
            if rec.first is None:
                rec.first = d.t
            rec.tokens.extend(d.tokens)
            state["deliveries"].append((d.t, len(d.tokens)))
            if t0 <= d.t < t1:
                state["window_tokens"] += len(d.tokens)
        if d.done:
            rec.done = d.t

    stamp = {"arrival": lambda r: r.arrival,
             "admitted": lambda r: r.t_admitted,
             "done": lambda r: r.t_done}[count_by]

    def counted(r) -> bool:
        t = stamp(r)
        return t is not None and t0 <= t < t1

    def check(t: float) -> None:
        if t < t1:
            return
        if t >= t1 + drain_cap or all(
                r.state is RequestState.DONE for r in reqs if counted(r)):
            state["stop"] = t
            raise StopServing

    prev_end = loop.on_iteration_end

    def on_end(t):
        state["iteration_ends"].append(t)
        prev_end(t)
        check(t)

    loop.on_iteration_end = on_end
    try:
        loop.run(reqs, now_fn=time.perf_counter, on_delta=on_delta)
    except StopServing:
        pass
    finally:
        loop.on_iteration_end = prev_end
    for rid, rec in recs.items():
        r = by_rid[rid]
        rec.admitted = r.t_admitted
        rec.counted = counted(r)
    stop = state["stop"]
    if stop is None:                     # the stream ran dry first
        stop = max([r.done for r in recs.values() if r.done is not None]
                   + [t1])
    return list(recs.values()), stop, state


def warm_requests(n: int, vocab: int):
    """One short request per slot, so every per-slot host path the window
    drives (bind, pull, release at each slot index) has run once."""
    from .program import requests
    rng = np.random.default_rng(0)
    return requests([(0.0, rng.integers(0, vocab, 16, dtype=np.int32), 4)
                     for _ in range(n)])


def sample_requests(recs: List[ReqRecord], prompts: Dict[int, np.ndarray],
                    n: int, seed: int):
    """The seed's sample of finished counted requests, the longest among
    them, as ``(prompt, output)`` pairs."""
    done = [r for r in recs if r.counted and r.done is not None
            and len(r.tokens) == r.max_new]
    if not done:
        return []
    longest = max(done, key=lambda r: (r.prompt_len + r.max_new, r.rid))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 7])
    pick = [rest[i] for i in rng.permutation(len(rest))[:n - 1]]
    return [(prompts[r.rid], np.asarray(r.tokens, np.int32))
            for r in [longest] + pick]


def describe(run: Run) -> str:
    """One line on how the run went, for the log."""
    w = [d for d in run.dispatches if run.in_window(d.t)]
    q = [n for t, n in run.queue_depth if run.in_window(t)]
    counted = run.counted
    return (f"window {run.window[0]:.1f}-{run.window[1]:.1f}s, stop "
            f"{run.stop:.1f}s: {len(counted)} counted, "
            f"{sum(r.done is not None for r in counted)} done; "
            f"{len(w)} bursts, {sum(d.steps for d in w)} steps, "
            f"{sum(d.tokens for d in w)} tokens stepped, "
            f"{run.window_tokens} delivered; queue depth first/last/max "
            f"{q[0] if q else 0}/{q[-1] if q else 0}/{max(q) if q else 0}")


def free_device_memory() -> None:
    import jax
    gc.collect()
    for a in jax.live_arrays():
        a.delete()
    gc.collect()


def build(cell: spec_lib.Cell, *, seed: int, peaks: Dict, annotate: bool):
    """The seed's weights and the serving loop, every burst program
    compiled (or loaded) and each slot warmed by one short request.
    Returns ``(cfg, loop, mesh)``."""
    from . import program
    from .weights import dims_from_config
    cfg = program.model_config(cell.config)
    sv = cell.config["serving"]
    params, mesh = program.make_params(cfg, dims_from_config(cell.config),
                                       seed)
    loop = program.BenchLoop(
        cfg, params, peaks=peaks, annotate=annotate, n_slots=sv["n_slots"],
        max_seq=sv["max_seq"], block_size=sv["block_size"],
        total_blocks=sv["total_blocks"], kv_layout=sv["kv_layout"])
    del params
    with mesh:
        loop.warmup()
        loop.run(warm_requests(sv["n_slots"], cfg.vocab),
                 on_delta=lambda d: None)
    loop.dispatches.clear()
    loop.kv_unused.clear()
    loop.queue_depth.clear()
    return cfg, loop, mesh


def serve(cell: spec_lib.Cell, cfg, loop, mesh, *, seed: int,
          seconds: float, trace: bool, t_start: float, peaks: Dict,
          devices, mix: Optional[Dict] = None):
    """Offer the seed's traffic and measure.  Returns ``(run, prompts,
    memory_peak_bytes)``; the loop is spent afterwards."""
    from . import program
    mix = cell.traffic if mix is None else mix
    triples = cell.generator.generate(mix, seed=seed, vocab=cfg.vocab,
                                      window_s=seconds)
    reqs = program.requests(triples)
    prompts = {r.rid: r.prompt for r in reqs}
    lead_in = float(mix["lead_in_s"])
    prof = (Profiler(loop, lead_in + seconds - min(TRACE_SECONDS, seconds),
                     lead_in + seconds) if trace else None)
    setup_s = time.perf_counter() - t_start
    say(f"set-up {setup_s:.3f}s; {len(reqs)} requests offered")
    try:
        with program.CompileClock() as cc, mesh:
            recs, stop, delivered = drive(
                loop, reqs, lead_in=lead_in, seconds=seconds,
                drain_cap=float(mix["drain_cap_s"]),
                count_by=mix["count_by"])
        say(f"compiles in the run {cc.compiles} ({cc.seconds:.3f}s)")
        tinfo = None
        if prof is not None:
            t = time.perf_counter()
            prof.finish()
            say(f"profiler stopped in {time.perf_counter() - t:.3f}s")
            tinfo = prof.read()
        # the CPU backend reports no memory statistics (tests only)
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices)
    finally:
        if prof is not None:
            prof.close()
    run = Run(seconds=seconds, window=(lead_in, lead_in + seconds),
              stop=stop, setup_s=setup_s, requests=recs,
              window_tokens=delivered["window_tokens"],
              deliveries=delivered["deliveries"],
              iteration_ends=delivered["iteration_ends"],
              dispatches=loop.dispatches,
              kv_unused=loop.kv_unused, queue_depth=loop.queue_depth,
              peaks=peaks, trace=tinfo)
    say(describe(run))
    return run, prompts, peak


def check(cell: spec_lib.Cell, run: Run, prompts, *, seed: int,
          control: bool = False):
    """Compare a sample of served requests with the reference, once the
    program's device memory is freed.  Returns ``(correct, checks)``;
    with ``control`` the checks also hold the float8 control's gap."""
    from . import reference
    from .weights import dims_from_config, root_key
    free_device_memory()
    corr = cell.config["correct"]
    samples = sample_requests(run.requests, prompts,
                              corr["sample_requests"], seed)
    ref = reference.Reference(dims_from_config(cell.config), root_key(seed),
                              cell.config["serving"]["max_seq"])
    gap, ctrl = None, None               # nothing finished: not correct
    if samples:
        gap = reference.served_gaps(ref, samples, control=control)
        if control:
            gap, ctrl = gap
    limit = corr["max_logit_gap"]
    n_cmp = sum(len(o) for _, o in samples)
    short = sum(1 for r in run.counted
                if r.done is not None and len(r.tokens) != r.max_new)
    checks = {
        "served_tokens_compared": {"value": n_cmp, "limit": MIN_COMPARED},
        "requests_with_wrong_token_count": {"value": short, "limit": 0},
        "widest_logit_gap": {"value": gap, "limit": limit},
    }
    if control:
        checks["control_widest_logit_gap"] = {"value": ctrl, "limit": limit}
    correct = (limit is not None and gap is not None and gap <= limit
               and n_cmp >= MIN_COMPARED and short == 0)
    return correct, checks


def result(cell: spec_lib.Cell, run: Run, *, correct: bool, checks: Dict,
           peak: int, devices, trace: bool) -> Dict:
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = m.read(run)
        if v is not None:
            metrics[m.name] = {"value": float(v), "unit": m.unit}
    dev = devices[0]
    out = {"correct": bool(correct), "attempted": len(run.counted),
           "failed": sum(1 for r in run.counted if r.done is None),
           "metrics": metrics,
           "device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(devices), "memory_peak_bytes": int(peak)}}
    if run.trace is not None:
        out["device"]["busy_s"] = run.trace.busy_s
        out["device"]["window_s"] = run.trace.window_s
        out["breakdown"] = {"device_ops": run.trace.device_ops,
                            "idle_gaps": run.trace.idle_gaps}
    out["checks"] = checks
    return out


def run_cell(cell: spec_lib.Cell, *, seed: int, seconds: float, trace: bool,
             t_start: float, peaks: Dict, devices, fault=None,
             control: bool = False) -> Dict:
    """Set up, serve, check.  Returns the result line as a dict.
    ``fault`` (tests only) breaks the served path before the window."""
    cfg, loop, mesh = build(cell, seed=seed, peaks=peaks, annotate=trace)
    if fault is not None:
        fault(loop)
    run, prompts, peak = serve(cell, cfg, loop, mesh, seed=seed,
                               seconds=seconds, trace=trace, t_start=t_start,
                               peaks=peaks, devices=devices)
    del loop
    t = time.perf_counter()
    correct, checks = check(cell, run, prompts, seed=seed,
                            control=control)
    say(f"reference check in {time.perf_counter() - t:.3f}s")
    return result(cell, run, correct=correct, checks=checks, peak=peak,
                  devices=devices, trace=trace)


def main(argv=None, *, t_start: float) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec_lib.load_cell(args.workload)
    configure_jax()
    devices = chips_or_exit(cell.chips)
    peaks = spec_lib.load_peaks(devices[0].device_kind)
    out = run_cell(cell, seed=args.seed, seconds=args.seconds,
                   trace=bool(args.trace), t_start=t_start, peaks=peaks,
                   devices=devices)
    for name, c in out["checks"].items():
        say(f"check {name} {c['value']} limit {c['limit']}")
    print(json.dumps(out), flush=True)
    return 0

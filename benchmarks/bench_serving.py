"""Serving benchmark: continuous batching vs the legacy static-batch server.

    PYTHONPATH=src python -m benchmarks.bench_serving --scale smoke

Offers the same open-loop mixed-length workload (repro.serving.request) to
both paths and writes ``BENCH_serving.json``: throughput (tok/s, req/s),
TTFT/latency percentiles and the continuous/static speedup per offered
load, plus a per-request bit-identity check of the greedy outputs (the two
paths run the same decode math, so tokens must match exactly — the
continuous engine runs the default block-paged KV layout, so every load's
check also gates paged-vs-dense numerics).  The ``paged`` section
quantifies the layout itself: KV bytes resident paged vs dense at equal
slots, the slot count a paged pool fits in the dense byte budget, the
saturation-throughput cost of the page gather, and paged/dense
bit-identity in colocated and disaggregated modes.  The ``prefix``
section prices prefix sharing at a dense-equal block budget: under 50%
and 90% prefix-shared traffic, refcounted shared pages with copy-on-write
tails must raise peak concurrent slots (and cut TTFT) versus the same
pool without sharing, bit-identically.  The ``streaming``
section compares incremental (burst-boundary) token delivery against the
completion pull in both colocated and disaggregated modes — streamed
deltas must concatenate to exactly the completion rows, and the honest
(host-visible) TTFT is reported next to the old dispatch-time stamp.
The ``observability`` section prices the tracing layer: NullTracer and
fully traced throughput relative to the untraced baseline (the NullTracer
ratio is the gated overhead bound) plus bit-identity of every traced run
and trace-health counts (spans balanced, lifecycle coverage, ring drops).
The ``adaptive`` section closes the loop: under an injected admission
mispricing that clamps the token budget to 1, the watchdog's mid-run
re-pricing must recover throughput and TTFT (bit-identically — admission
policy never changes outputs), and tracer+watchdog throughput must stay
within the gated overhead of tracer-only.  The ``speculative`` section
drives draft-model speculative decoding through the programmatic API
(``repro.serving.api.serve``): a forced-depth run must stay bit-identical
to plain decode with its accepted-token rate measured, the
analyzer-priced run must fall back to plain serving when speculation
prices worse at these smoke shapes, and an adversarially de-rated draft
device must price speculation off outright.

Static batching groups requests by prompt length (the legacy server is
rectangular), waits for a full batch to arrive, and decodes every batch to
its longest generation — short requests pay head-of-line blocking, and the
accelerator idles between generations.  The continuous engine refills slots
the moment a request finishes, which is where the speedup comes from.
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.serve import Server
from repro.models import transformer as T
from repro.obs import Observability, Tracer
from repro.serving import (DisaggregatedEngineLoop, EngineLoop, ServeMetrics,
                           place_phases, prefix_shared_workload,
                           synthetic_workload)

SMOKE_CFG = T.ModelConfig(
    name="bench-serving-smoke", n_layers=4, d_model=96, n_heads=6,
    n_kv_heads=2, d_ff=192, vocab=512, qkv_bias=True, attention_impl="dot",
    scan_chunk=16, remat=False)

PROMPT_LENS = (8, 16)
GEN_LENS = (4, 8, 16, 64)

# best-of-N repetitions for the observability overhead ratios: sub-second
# smoke runs jitter by a few percent on a shared host, and the gated
# NullTracer bound must measure tracing cost, not scheduler noise
_OBS_REPS = 5


def _workload(n: int, rate: float, vocab: int, seed: int):
    return synthetic_workload(n, rate=rate, vocab=vocab,
                              prompt_lens=PROMPT_LENS, gen_lens=GEN_LENS,
                              seed=seed)


def run_static(cfg, params, requests, *, batch: int, max_len: int,
               metrics: ServeMetrics) -> Dict[int, List[int]]:
    """Legacy path: rectangular batches per prompt length, decode to the
    batch's longest generation.  Returns rid -> greedy tokens."""
    server = Server(cfg, params, None, max_len)
    # batch formation: per prompt-length group, in arrival order
    groups: Dict[int, List] = {}
    for r in sorted(requests, key=lambda r: (r.arrival, r.rid)):
        groups.setdefault(r.prompt_len, []).append(r)
    batches = []
    for plen, grp in groups.items():
        for i in range(0, len(grp), batch):
            chunk = grp[i:i + batch]
            batches.append((max(r.arrival for r in chunk), chunk))
    batches.sort(key=lambda b: b[0])

    # warm up compiles (one decode width + one prefill per prompt length)
    for plen in groups:
        server.generate(jnp.zeros((batch, plen), jnp.int32), 2)

    outputs: Dict[int, List[int]] = {}
    t0 = time.perf_counter()
    for ready, chunk in batches:
        now = time.perf_counter() - t0
        if now < ready:                  # static batching waits for a full
            time.sleep(ready - now)      # batch before launching it
        rows = [r.prompt for r in chunk]
        while len(rows) < batch:         # rectangular pad: repeat last row
            rows.append(rows[-1])
        prompts = jnp.asarray(np.stack(rows))
        gmax = max(r.max_new_tokens for r in chunk)
        toks = np.asarray(server.generate(prompts, gmax))
        done = time.perf_counter() - t0
        for j, r in enumerate(chunk):
            outputs[r.rid] = toks[j, :r.max_new_tokens].tolist()
            r.output = outputs[r.rid]
            # tokens only land at batch end: dispatch and host visibility
            # coincide for the static path
            r.t_first_token = done
            r.t_first_dispatch = done
            r.t_done = done
            metrics.observe(r)
        metrics.n_steps += prompts.shape[1] + gmax
    metrics.elapsed_s = time.perf_counter() - t0
    return outputs


def run_continuous(cfg, params, requests, *, slots: int, max_len: int
                   ) -> ServeMetrics:
    engine = EngineLoop(cfg, params, n_slots=slots, max_seq=max_len)
    engine.warmup()                      # compile all burst buckets
    return engine.run(requests)


def kv_cache_bytes(cfg, n_slots: int, max_len: int, *, layout: str,
                   block_size: int = 16, total_blocks=None) -> int:
    """Resident attention-KV bytes of a slot cache under `layout`, computed
    from cache leaf shapes via eval_shape (nothing is allocated).  Counts
    only the attention K/V storage — the axis the paged layout changes;
    the paged figure includes its trash page (it is resident too)."""
    if layout == "paged":
        shapes = jax.eval_shape(
            lambda: T.init_slot_cache_paged(cfg, n_slots, max_len,
                                            block_size=block_size,
                                            total_blocks=total_blocks))
    else:
        shapes = jax.eval_shape(
            lambda: T.init_slot_cache(cfg, n_slots, max_len))
    blocks, rem = shapes["layers"]
    total = 0
    for c in list(blocks) + list(rem):
        if isinstance(c, dict) and "k" in c:
            for leaf in jax.tree.leaves(c):
                total += int(np.prod(leaf.shape)) * leaf.dtype.itemsize
    return total


def run_paged(cfg, params, baselines: Dict, *, n_requests: int, slots: int,
              max_len: int, seed: int, block_size: int = 16) -> Dict:
    """Paged vs dense KV layout on the saturation workload.

    The paged pool is provisioned for tokens-in-flight (mean per-request
    block footprint x slots) instead of the ``slots x max_seq`` dense
    worst case, so the section reports the KV bytes actually resident at
    equal ``n_slots``, the slot count a paged pool could host inside the
    dense byte budget, and the saturation-throughput cost of the page
    gather.  Correctness contract: per-request greedy outputs are
    bit-identical between the layouts in both colocated and disaggregated
    modes (``baselines`` supplies :func:`run_disaggregation`'s paged runs,
    reused so those serving runs + warmup compiles aren't paid twice)."""
    bps = -(-max_len // block_size)
    dense_reqs = _workload(n_requests, 1e9, cfg.vocab, seed)
    # provision the paged arena for tokens-in-flight: the workload's mean
    # per-request block footprint x slots (deterministic generator, so
    # dense_reqs is the same draw every layout serves)
    mean_blocks = float(np.mean([-(-r.total_tokens // block_size)
                                 for r in dense_reqs]))
    provisioned = max(int(np.ceil(mean_blocks * slots)), bps)
    dense_eng = EngineLoop(cfg, params, n_slots=slots, max_seq=max_len,
                           block_size=block_size, kv_layout="dense")
    dense_eng.warmup()
    m_dense = dense_eng.run(dense_reqs)

    paged_reqs = _workload(n_requests, 1e9, cfg.vocab, seed)
    paged_eng = EngineLoop(cfg, params, n_slots=slots, max_seq=max_len,
                           block_size=block_size, kv_layout="paged",
                           total_blocks=provisioned)
    paged_eng.warmup()
    m_paged = paged_eng.run(paged_reqs)

    ddense_reqs = _workload(n_requests, 1e9, cfg.vocab, seed)
    ddense = DisaggregatedEngineLoop(
        cfg, params, n_prefill_slots=max(slots // 2, 1),
        n_decode_slots=slots, max_seq=max_len, block_size=block_size,
        kv_layout="dense")
    ddense.warmup()
    ddense.run(ddense_reqs)
    _, dpaged_reqs = baselines["disaggregated"]   # paged (default layout)

    out_d = {r.rid: r.output for r in dense_reqs}
    out_p = {r.rid: r.output for r in paged_reqs}
    out_dd = {r.rid: r.output for r in ddense_reqs}
    out_dp = {r.rid: r.output for r in dpaged_reqs}

    bytes_dense = kv_cache_bytes(cfg, slots, max_len, layout="dense")
    bytes_paged = kv_cache_bytes(cfg, slots, max_len, layout="paged",
                                 block_size=block_size,
                                 total_blocks=provisioned)
    d, p = m_dense.summary(), m_paged.summary()
    section = {
        "block_size": block_size,
        "blocks_per_slot": bps,
        "total_blocks": provisioned,
        "dense_equiv_blocks": slots * bps,
        "kv_bytes_dense": bytes_dense,
        "kv_bytes_paged": bytes_paged,
        "kv_bytes_ratio": bytes_paged / bytes_dense,
        # slots a paged pool of this per-slot footprint fits in the dense
        # byte budget (the capacity headroom paging buys at equal memory)
        "achievable_n_slots_at_dense_budget": int(
            bytes_dense // max(bytes_paged / slots, 1)),
        "dense": d,
        "paged": p,
        "tok_per_s_ratio": p["tok_per_s"] / d["tok_per_s"],
        "bit_identical_colocated": out_d == out_p,
        "bit_identical_disaggregated": out_dd == out_dp,
    }
    section["all_identical"] = (section["bit_identical_colocated"]
                                and section["bit_identical_disaggregated"])
    print(f"[bench_serving] paged: {bytes_paged} KV bytes resident vs "
          f"{bytes_dense} dense ({section['kv_bytes_ratio']:.2f}x, "
          f"{section['achievable_n_slots_at_dense_budget']} slots at the "
          f"dense budget); saturation {p['tok_per_s']:.1f} vs "
          f"{d['tok_per_s']:.1f} tok/s "
          f"({section['tok_per_s_ratio']:.2f}x); "
          f"bit_identical={section['all_identical']}", flush=True)
    return section


def run_prefix(cfg, params, *, n_requests: int, seed: int,
               block_size: int = 16) -> Dict:
    """Prefix sharing vs unshared paging at a dense-equal KV budget.

    Workload: the chat/agent system-prompt pattern — a ``shared_frac`` of
    requests front-load one common 48-token prefix (3 full blocks) ahead of
    a unique suffix (``prefix_shared_workload``).  Both runs get the *same*
    constrained pool: 16 engine slots but only enough blocks to hold 8
    dense residents (``total_blocks = 8 x blocks_per_slot``), so block
    supply — not slot count — caps concurrency.  Without sharing every
    request draws its full footprint from the free list and at most 8 ever
    run at once; with sharing, once an early resident has written and
    published the common prefix blocks, later arrivals map onto them
    (refcounted, copy-on-write at the divergent tail) and draw only their
    unique blocks, so more land in flight and the queue drains sooner.

    Reported per shared-traffic fraction: peak concurrent slots and the
    ratio (the admitted-capacity win), TTFT p50 and queue-wait ratios, the
    prefix-cache hit/skip/COW counters, and bit-identity — shared KV pages
    hold exactly the values the request would have written itself, so
    greedy outputs must match the unshared run token for token.  The 90%
    fraction's capacity win and its bit-identity are the gated claims."""
    shared_prefix_len = 3 * block_size           # 48: full-block chain
    suffix_lens = (block_size // 2, block_size)  # unique tail, 1 block
    gen_lens = (4, 8, 16)
    max_len = shared_prefix_len + max(suffix_lens) + max(gen_lens)
    bps = -(-max_len // block_size)
    n_slots = 16
    dense_slots = 8                              # the byte budget: 8 dense
    total_blocks = dense_slots * bps             # residents, 16 slot leases

    def _workload_p(frac):
        return prefix_shared_workload(
            n_requests, rate=1e9, vocab=cfg.vocab,
            shared_prefix_len=shared_prefix_len, shared_frac=frac,
            suffix_lens=suffix_lens, gen_lens=gen_lens, seed=seed)

    def _run(frac, sharing):
        reqs = _workload_p(frac)
        eng = EngineLoop(cfg, params, n_slots=n_slots, max_seq=max_len,
                         block_size=block_size, kv_layout="paged",
                         total_blocks=total_blocks, prefix_sharing=sharing)
        eng.warmup()
        m = eng.run(reqs)
        return eng, m, {r.rid: r.output for r in reqs}

    section: Dict[str, object] = {
        "block_size": block_size,
        "blocks_per_slot": bps,
        "n_slots": n_slots,
        "total_blocks": total_blocks,
        "dense_equivalent_slots": dense_slots,
        "shared_prefix_len": shared_prefix_len,
        "n_requests": n_requests,
    }
    identical = []
    for frac in (0.5, 0.9):
        off_eng, m_off, out_off = _run(frac, False)
        on_eng, m_on, out_on = _run(frac, True)
        off, on = m_off.summary(), m_on.summary()
        st_off, st_on = off_eng.pool.stats(), on_eng.pool.stats()
        bit_identical = out_off == out_on
        identical.append(bit_identical)
        entry = {
            "unshared": off,
            "shared": on,
            "peak_slots_unshared": st_off["peak_slots_in_use"],
            "peak_slots_shared": st_on["peak_slots_in_use"],
            "admitted_slots_ratio": (st_on["peak_slots_in_use"]
                                     / max(st_off["peak_slots_in_use"], 1)),
            "ttft_p50_ratio": off["ttft_p50_s"] / on["ttft_p50_s"],
            "tok_per_s_ratio": on["tok_per_s"] / off["tok_per_s"],
            "prefix_hits": st_on["prefix_hits"],
            "tokens_prefill_skipped": st_on["tokens_prefill_skipped"],
            "cow_copies": st_on["cow_copies"],
            "bit_identical": bit_identical,
        }
        section[f"shared_frac_{int(frac * 100)}"] = entry
        print(f"[bench_serving] prefix[{frac:.0%} shared]: peak "
              f"{st_on['peak_slots_in_use']} slots shared vs "
              f"{st_off['peak_slots_in_use']} unshared "
              f"({entry['admitted_slots_ratio']:.2f}x) at the "
              f"{dense_slots}-dense-slot block budget; ttft p50 "
              f"{entry['ttft_p50_ratio']:.2f}x better, "
              f"{entry['prefix_hits']} hits / "
              f"{entry['tokens_prefill_skipped']} prefill tokens skipped / "
              f"{entry['cow_copies']} cow copies, "
              f"bit_identical={bit_identical}", flush=True)
    section["all_identical"] = all(identical)
    return section


def run_disaggregation(cfg, params, *, n_requests: int, slots: int,
                       max_len: int, seed: int):
    """Disaggregated vs colocated on the same saturation workload + the
    placement analyzer's call on the paper engine set.  Returns the JSON
    section plus the (metrics, requests) completion-pull baselines that
    :func:`run_streaming` builds on.

    Both loops run the same engine pair (the buildable XLA engine for both
    phases), so per-request outputs must be bit-identical — the hand-off
    is exact state migration, not an approximation.  The tok/s ratio is
    the measured cost of the phase boundary on this host; the placement
    table is what the trade-off analyzer would pick per objective."""
    colo_reqs = _workload(n_requests, 1e9, cfg.vocab, seed)
    dis_reqs = _workload(n_requests, 1e9, cfg.vocab, seed)

    colo = EngineLoop(cfg, params, n_slots=slots, max_seq=max_len)
    colo.warmup()
    c_metrics = colo.run(colo_reqs)

    dis = DisaggregatedEngineLoop(
        cfg, params, n_prefill_slots=max(slots // 2, 1),
        n_decode_slots=slots, max_seq=max_len)
    dis.warmup()
    d_metrics = dis.run(dis_reqs)

    bit_identical = ({r.rid: r.output for r in colo_reqs}
                     == {r.rid: r.output for r in dis_reqs})
    # completion-pull baselines run_streaming reuses (same workload/config),
    # so the bench doesn't pay these runs + warmup compiles twice
    baselines = {"colocated": (c_metrics, colo_reqs),
                 "disaggregated": (d_metrics, dis_reqs)}
    placements = {}
    for objective in ("latency", "energy", "perf_density"):
        d = place_phases(cfg, objective=objective,
                         prompt_len=max(PROMPT_LENS),
                         gen_len=max(GEN_LENS), batch=slots)
        placements[objective] = {
            "prefill_engine": d.prefill_engine,
            "decode_engine": d.decode_engine,
            "colocated": d.colocated,
            "value": d.best.value,
            "handoff_s": d.best.handoff.t_transfer,
        }
    c, dd = c_metrics.summary(), d_metrics.summary()
    out = {
        "colocated": c,
        "disaggregated": dd,
        "tok_per_s_ratio": dd["tok_per_s"] / c["tok_per_s"],
        "bit_identical": bit_identical,
        "handoff": dis.handoff.stats(),
        "placement": placements,
    }
    print(f"[bench_serving] disaggregation: colocated {c['tok_per_s']:.1f} "
          f"tok/s vs disaggregated {dd['tok_per_s']:.1f} tok/s "
          f"({out['tok_per_s_ratio']:.2f}x, {dis.handoff.n_handoffs} "
          f"handoffs, bit_identical={bit_identical})", flush=True)
    return out, baselines


def run_streaming(cfg, params, baselines: Dict, *, n_requests: int,
                  slots: int, max_len: int, seed: int) -> Dict:
    """Streaming vs completion-pull token delivery on the same workload,
    colocated and disaggregated.

    Streaming syncs the device chain at burst boundaries and emits newly
    readable tokens as deltas, so TTFT measures *delivered* tokens; the
    completion path only surfaces a request's row when it finishes (its
    first token becomes host-visible with its last).  ``ttft_dispatch``
    keeps the old dispatch-time stamp in both modes, so the section
    quantifies the gap the dispatch-stamped metric used to hide.  The
    correctness contract: streamed outputs are bit-identical to the
    completion-pull rows, and the deltas concatenate to exactly those rows.

    ``baselines`` is :func:`run_disaggregation`'s completion-pull runs
    (same workload, config and seed), reused here so the bench doesn't pay
    those serving runs and warmup compiles a second time.
    """
    section: Dict[str, Dict] = {}
    for mode, mk in (
            ("colocated",
             lambda: EngineLoop(cfg, params, n_slots=slots, max_seq=max_len)),
            ("disaggregated",
             lambda: DisaggregatedEngineLoop(
                 cfg, params, n_prefill_slots=max(slots // 2, 1),
                 n_decode_slots=slots, max_seq=max_len))):
        m_comp, comp_reqs = baselines[mode]
        strm_reqs = _workload(n_requests, 1e9, cfg.vocab, seed)
        strm_eng = mk()
        strm_eng.warmup()
        deltas: Dict[int, List[int]] = {}
        m_strm = strm_eng.run(
            strm_reqs,
            on_delta=lambda d: deltas.setdefault(d.rid, []).extend(d.tokens))

        comp_out = {r.rid: r.output for r in comp_reqs}
        strm_out = {r.rid: r.output for r in strm_reqs}
        gaps = [r.ttft - r.ttft_dispatch for r in strm_reqs
                if r.ttft is not None and r.ttft_dispatch is not None]
        c, s = m_comp.summary(), m_strm.summary()
        section[mode] = {
            "completion": c,
            "streaming": s,
            "bit_identical": comp_out == strm_out,
            "delta_concat_identical": deltas == comp_out,
            "ttft_dispatch_leq_ttft": all(
                r.ttft_dispatch <= r.ttft for r in comp_reqs + strm_reqs
                if r.ttft is not None and r.ttft_dispatch is not None),
            # host-visibility gap the dispatch-stamped TTFT used to hide
            # (None, not NaN: the report must stay strict JSON)
            "ttft_gap_p50_s": (float(np.percentile(np.asarray(gaps), 50))
                               if gaps else None),
            "sync_cost_tok_per_s_ratio": s["tok_per_s"] / c["tok_per_s"],
        }
        print(f"[bench_serving] streaming[{mode}]: ttft p50 "
              f"{s['ttft_p50_s']*1e3:.1f}ms streamed vs "
              f"{c['ttft_p50_s']*1e3:.1f}ms completion-pull "
              f"(dispatch stamp {s['ttft_dispatch_p50_s']*1e3:.1f}ms); "
              f"{s['tokens_streamed']} tokens in {s['stream_deltas']} "
              f"deltas, sync cost "
              f"{section[mode]['sync_cost_tok_per_s_ratio']:.2f}x, "
              f"bit_identical={section[mode]['bit_identical']}", flush=True)
    section["all_identical"] = all(
        section[m]["bit_identical"] and section[m]["delta_concat_identical"]
        and section[m]["ttft_dispatch_leq_ttft"]
        for m in ("colocated", "disaggregated"))
    return section


def run_observability(cfg, params, baselines: Dict, *, n_requests: int,
                      slots: int, max_len: int, seed: int) -> Dict:
    """Cost and correctness of the observability layer on the saturation
    workload.

    Three colocated configurations of the same workload: an untraced
    baseline, a run with the default ``NullTracer`` constructed
    explicitly (the tracing-off tax: guard branches only), and a fully
    traced run (ring-buffer ``Tracer`` plus per-iteration registry
    sampling), plus a traced disaggregated run so the hand-off span is
    exercised.  Tracing happens strictly between device dispatches, so
    every run must stay bit-identical to the untraced outputs
    (``baselines`` supplies :func:`run_disaggregation`'s reference rows);
    the NullTracer throughput ratio is the gated overhead bound (the
    traced ratio is reported, not gated — a full ring-buffer trace is a
    debugging artifact, not the steady state).  A sub-second smoke run's
    tok/s jitters by several percent on a shared host, so the reps are
    interleaved round-robin across the three configurations (every config
    samples the same host-load windows) and each reports its best rep —
    the standard min-time estimator — rather than one sample."""
    _, untraced_reqs = baselines["colocated"]
    untraced_out = {r.rid: r.output for r in untraced_reqs}
    _, dis_reqs = baselines["disaggregated"]
    dis_out = {r.rid: r.output for r in dis_reqs}

    def _mk(obs):
        eng = EngineLoop(cfg, params, n_slots=slots, max_seq=max_len,
                         obs=obs)
        eng.warmup()
        return eng

    traced_obs = Observability(tracer=Tracer())
    engines = {"untraced": _mk(None),       # EngineLoop's default obs
               "null": _mk(Observability()),
               "traced": _mk(traced_obs)}
    best: Dict[str, object] = {}
    outs: Dict[str, Dict[int, List[int]]] = {}
    for _ in range(_OBS_REPS):
        for key, eng in engines.items():
            reqs = _workload(n_requests, 1e9, cfg.vocab, seed)
            m = eng.run(reqs)
            if key not in best or m.summary()["tok_per_s"] > \
                    best[key].summary()["tok_per_s"]:
                best[key] = m
            rows = {r.rid: r.output for r in reqs}
            assert outs.setdefault(key, rows) == rows   # deterministic reps
    m_untraced, m_null, m_traced = (best["untraced"], best["null"],
                                    best["traced"])
    plain_out, null_out, traced_out = (outs["untraced"], outs["null"],
                                       outs["traced"])

    dtraced_obs = Observability(tracer=Tracer())
    dtraced_reqs = _workload(n_requests, 1e9, cfg.vocab, seed)
    dtraced = DisaggregatedEngineLoop(
        cfg, params, n_prefill_slots=max(slots // 2, 1),
        n_decode_slots=slots, max_seq=max_len, obs=dtraced_obs)
    dtraced.warmup()
    dtraced.run(dtraced_reqs)
    dtraced_out = {r.rid: r.output for r in dtraced_reqs}

    names = {e.name for e in traced_obs.tracer.events}
    dnames = {e.name for e in dtraced_obs.tracer.events}
    lifecycle = {"queued", "prefill", "decode", "burst", "sync",
                 "first_token", "done"}
    u, nl, tr = (m_untraced.summary(), m_null.summary(),
                 m_traced.summary())
    section = {
        "untraced": u,
        "null_tracer": nl,
        "traced": tr,
        # gated bound: the cost of shipping with tracing compiled in but
        # off; the traced ratio is informational
        "overhead_ratio_null": nl["tok_per_s"] / u["tok_per_s"],
        "overhead_ratio_traced": tr["tok_per_s"] / u["tok_per_s"],
        "bit_identical_null": untraced_out == plain_out == null_out,
        "bit_identical_traced": untraced_out == traced_out,
        "bit_identical_traced_disagg": dis_out == dtraced_out,
        "trace_events": len(traced_obs.tracer),
        "trace_events_disagg": len(dtraced_obs.tracer),
        "trace_dropped": traced_obs.tracer.n_dropped,
        "trace_spans_balanced": (traced_obs.tracer.n_open == 0
                                 and dtraced_obs.tracer.n_open == 0),
        "lifecycle_spans_present": lifecycle <= names,
        "handoff_span_present": "handoff" in dnames,
        "metrics_series_points": traced_obs.registry.n_samples,
    }
    section["all_identical"] = (section["bit_identical_null"]
                                and section["bit_identical_traced"]
                                and section["bit_identical_traced_disagg"]
                                and section["trace_spans_balanced"]
                                and section["lifecycle_spans_present"]
                                and section["handoff_span_present"])
    print(f"[bench_serving] observability: null-tracer "
          f"{section['overhead_ratio_null']:.3f}x, traced "
          f"{section['overhead_ratio_traced']:.3f}x of untraced tok/s; "
          f"{section['trace_events']} events "
          f"({section['trace_dropped']} dropped), "
          f"bit_identical={section['all_identical']}", flush=True)
    return section


def run_adaptive(cfg, params, baselines: Dict, *, n_requests: int,
                 slots: int, max_len: int, seed: int) -> Dict:
    """The watchdog control loop under an injected pricing error, plus the
    overhead of running it.

    Drifted-cost scenario: admission is priced on a device model de-rated
    (``drift_scaled_device``) until the analytic step time at batch 2 is
    4x the step SLO, so the static token budget clamps to 1 and the loop
    serializes.  The real hardware is far faster than that price, so the
    watchdog's EWMA of observed/priced crosses the gate, the driver hands
    the alert to ``on_drift``, and the batcher re-prices from telemetry
    (ratio-scaled analytic first, fitted latency(batch) curve once two
    batch sizes were observed) — the budget refits against the same SLO
    and the run recovers full batching mid-flight.  Gated: re-pricing must
    improve saturation throughput AND p50 TTFT, at least one alert and one
    re-price must fire, and both runs must stay bit-identical to the
    untouched baseline (admission policy must never change outputs).

    Overhead: tracer+watchdog vs tracer-only throughput on the undrifted
    configuration, interleaved best-of-``_OBS_REPS`` like the
    observability section (the watchdog syncs each burst to time it — that
    sync is the cost being gated)."""
    from repro.core import device_models
    from repro.obs import PerfWatchdog
    from repro.serving.batcher import step_time_model
    from repro.serving.placement import drift_scaled_device

    _, base_reqs = baselines["colocated"]
    base_out = {r.rid: r.output for r in base_reqs}

    # de-rate the pricing device until batch 2 breaks the step SLO: the
    # static budget pins to 1 while the hardware could batch freely
    slo = 0.1
    base_dev = device_models.get("tpu-v5e")
    factor = 4.0 * slo / step_time_model(cfg, max_len, 2, device=base_dev)
    drifted = drift_scaled_device(base_dev, factor)

    def _run(watchdog):
        obs = (Observability(watchdog=watchdog)
               if watchdog is not None else None)
        eng = EngineLoop(cfg, params, n_slots=slots, max_seq=max_len,
                         device_model=drifted, step_slo_s=slo, obs=obs)
        eng.warmup()                     # timing the schedule, not jit
        reqs = _workload(n_requests, 1e9, cfg.vocab, seed)
        m = eng.run(reqs)
        return eng, m, {r.rid: r.output for r in reqs}

    off_eng, m_off, out_off = _run(None)
    wd = PerfWatchdog()
    on_eng, m_on, out_on = _run(wd)

    # overhead leg: same undrifted tracer-only vs tracer+watchdog engines,
    # reps interleaved so both sample the same host-load windows
    def _mk(obs):
        eng = EngineLoop(cfg, params, n_slots=slots, max_seq=max_len,
                         obs=obs)
        eng.warmup()
        return eng
    engines = {"traced": _mk(Observability(tracer=Tracer())),
               "watchdog": _mk(Observability(tracer=Tracer(),
                                             watchdog=PerfWatchdog()))}
    best: Dict[str, float] = {}
    outs: Dict[str, Dict[int, List[int]]] = {}
    for _ in range(_OBS_REPS):
        for key, eng in engines.items():
            reqs = _workload(n_requests, 1e9, cfg.vocab, seed)
            m = eng.run(reqs)
            best[key] = max(best.get(key, 0.0), m.summary()["tok_per_s"])
            rows = {r.rid: r.output for r in reqs}
            assert outs.setdefault(key, rows) == rows   # deterministic reps

    off, on = m_off.summary(), m_on.summary()
    section = {
        "scenario": {
            "step_slo_s": slo,
            "misprice_factor": factor,
            "priced_device": drifted.name,
        },
        "static_priced": off,
        "adaptive": on,
        "tok_per_s_ratio": on["tok_per_s"] / off["tok_per_s"],
        # >1: re-pricing cut the median time-to-first-token
        "ttft_p50_ratio": off["ttft_p50_s"] / on["ttft_p50_s"],
        "n_alerts": len(wd.alerts),
        "n_reprices": len(wd.reprices),
        "token_budget_static": off_eng.batcher.token_budget,
        "token_budget_final": on_eng.batcher.token_budget,
        "price_source_final": on_eng.batcher.price_source,
        "overhead_ratio_watchdog": best["watchdog"] / best["traced"],
        "bit_identical_static": base_out == out_off,
        "bit_identical_adaptive": base_out == out_on,
        "bit_identical_overhead": outs["traced"] == outs["watchdog"]
                                  == base_out,
    }
    section["all_identical"] = (section["bit_identical_static"]
                                and section["bit_identical_adaptive"]
                                and section["bit_identical_overhead"])
    print(f"[bench_serving] adaptive: drifted-cost {on['tok_per_s']:.1f} "
          f"tok/s watchdog-on vs {off['tok_per_s']:.1f} off "
          f"({section['tok_per_s_ratio']:.2f}x, ttft p50 "
          f"{section['ttft_p50_ratio']:.2f}x better), budget "
          f"{section['token_budget_static']} -> "
          f"{section['token_budget_final']} "
          f"({section['price_source_final']}, {section['n_alerts']} alerts, "
          f"{section['n_reprices']} reprices); watchdog overhead "
          f"{section['overhead_ratio_watchdog']:.3f}x traced; "
          f"bit_identical={section['all_identical']}", flush=True)
    return section


def _multidevice_section(*, n_requests: int, slots: int, seed: int) -> Dict:
    """Two-device serving legs (the child side of :func:`run_multidevice`).

    Meant to run in a subprocess under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=2`` so the phase
    engines land on two real XLA devices; degrades to whatever devices
    are visible (``n_devices``/``distinct_devices`` report which world the
    numbers came from, and the gate adapts).  Legs: colocated baseline,
    cross-device disagg with the async hand-off, the same with
    ``--sync-handoff`` (prefill blocks on every transfer — the stall
    baseline the overlap win is measured against), and a mid-run
    placement migration (decode device model priced ~1e6x too fast, the
    watchdog's placement re-run flips decode onto the prefill engine and
    live-migrates in-flight slots).  Every leg must stay bit-identical to
    colocated serving."""
    from repro.core import engines as engines_lib
    from repro.launch.mesh import device_assignment, device_label
    from repro.obs import PerfWatchdog
    from repro.profiling.transfer import measure_link_bandwidth
    from repro.serving.placement import drift_scaled_device

    cfg = SMOKE_CFG
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    max_len = max(PROMPT_LENS) + max(GEN_LENS)
    asn = device_assignment()
    link = (measure_link_bandwidth(asn.prefill, asn.decode)
            if asn.distinct else None)

    colo = EngineLoop(cfg, params, n_slots=slots, max_seq=max_len)
    colo.warmup()

    def _mk_dis(async_handoff, assignment=asn):
        d = DisaggregatedEngineLoop(
            cfg, params, n_prefill_slots=max(slots // 2, 1),
            n_decode_slots=slots, max_seq=max_len, assignment=assignment,
            async_handoff=async_handoff)
        d.warmup()
        return d

    # interleaved best-of reps, like the observability section: the
    # overlap/stall split and the two-device throughput ratio are the
    # gated numbers, and sub-second runs jitter on a shared host.  The
    # "shared" leg is the same disagg loop with both phases on the
    # default device — the throughput baseline the distinct assignment
    # must not lose to (the disagg loop itself already pays the phase
    # boundary; that cost is the `disaggregation` section's claim)
    engines = {"colocated": colo, "async": _mk_dis(True),
               "sync": _mk_dis(False), "shared": _mk_dis(True, None)}
    best: Dict[str, ServeMetrics] = {}
    outs: Dict[str, Dict[int, List[int]]] = {}
    for _ in range(3):
        for key, eng in engines.items():
            reqs = _workload(n_requests, 1e9, cfg.vocab, seed)
            m = eng.run(reqs)
            if key not in best or m.summary()["tok_per_s"] > \
                    best[key].summary()["tok_per_s"]:
                best[key] = m
            rows = {r.rid: r.output for r in reqs}
            assert outs.setdefault(key, rows) == rows   # deterministic reps

    # mid-run migration leg: equal phase pools so the flip has spare
    # prefill capacity to migrate decode slots into, smaller workload so
    # slots are in flight (not queued) when the drift alert lands
    mig_n = min(n_requests, 2 * slots)
    mig_reqs = _workload(mig_n, 1e9, cfg.vocab, seed + 1)
    colo.run(mig_reqs)
    mig_ref = {r.rid: r.output for r in mig_reqs}
    wd = PerfWatchdog()
    dis_m = DisaggregatedEngineLoop(
        cfg, params, n_prefill_slots=slots, n_decode_slots=slots,
        max_seq=max_len, assignment=asn,
        obs=Observability(watchdog=wd),
        prefill_device=engines_lib.XLA_ENGINE.device,
        decode_device=drift_scaled_device(engines_lib.K40_LM_ENGINE.device,
                                          1e-6),
        prefill_placement_engine_name="xla",
        decode_placement_engine_name="k40-roofline")
    dis_m.warmup()
    mig_run = _workload(mig_n, 1e9, cfg.vocab, seed + 1)
    mm = dis_m.run(mig_run)
    migration = {
        "n_requests": mig_n,
        "n_done": mm.n_done,
        "n_dropped": mm.n_dropped,
        "n_live_migrations": dis_m.handoff.n_live_migrations,
        "n_alerts": len(wd.alerts),
        "decode_target": dis_m.decode_target,
        "requests_preserved": mm.n_done == mig_n and mm.n_dropped == 0,
        "bit_identical": {r.rid: r.output for r in mig_run} == mig_ref,
    }

    sync_stall = engines["sync"].handoff.stall_s
    async_stall = engines["async"].handoff.stall_s
    c, a, s, sh = (best["colocated"].summary(), best["async"].summary(),
                   best["sync"].summary(), best["shared"].summary())
    section = {
        "n_devices": len(jax.devices()),
        "distinct_devices": asn.distinct,
        "assignment": {"prefill": device_label(asn.prefill),
                       "decode": device_label(asn.decode)},
        "measured_link_bw": None if link is None else link["link_bw"],
        "colocated": c,
        "disagg_async": a,
        "disagg_sync": s,
        "disagg_shared_device": sh,
        "tok_per_s_ratio_vs_colocated": a["tok_per_s"] / c["tok_per_s"],
        "tok_per_s_ratio_vs_sync": a["tok_per_s"] / s["tok_per_s"],
        # the gated two-device claim: real cross-device hand-offs must not
        # cost throughput against the same loop on one shared device
        "tok_per_s_ratio_vs_shared": a["tok_per_s"] / sh["tok_per_s"],
        "handoff_async": engines["async"].handoff.stats(),
        "handoff_sync": engines["sync"].handoff.stats(),
        "sync_stall_s": sync_stall,
        "async_stall_s": async_stall,
        "async_overlap_s": engines["async"].handoff.overlap_s,
        # the gated overlap win: time decode blocked on transfers, async
        # over the blocking baseline (<= 0.5 means the pipeline hid at
        # least half the measured transfer time)
        "stall_ratio": async_stall / max(sync_stall, 1e-12),
        "bit_identical_async": outs["async"] == outs["colocated"],
        "bit_identical_sync": outs["sync"] == outs["colocated"],
        "bit_identical_shared": outs["shared"] == outs["colocated"],
        "migration": migration,
    }
    section["all_identical"] = (section["bit_identical_async"]
                                and section["bit_identical_sync"]
                                and section["bit_identical_shared"]
                                and migration["bit_identical"])
    return section


def run_multidevice(*, n_requests: int, slots: int, seed: int) -> Dict:
    """The ``multidevice`` section: async hand-off overlap, two-device
    throughput and mid-run migration.

    On the CPU backend the legs run in a child process that splits the
    host into two devices: ``--xla_force_host_platform_device_count``
    only works before the first jax import, and this process already
    initialized its backend (the same world the CI multidevice job and
    ``tests/test_multidevice.py`` exercise).  A failed child fails the
    bench.  On an accelerator the legs run in this process, on its real
    devices — a child would contend with this process for the chips."""
    if jax.default_backend() != "cpu":
        section = _multidevice_section(n_requests=n_requests, slots=slots,
                                       seed=seed)
        section["forced_subprocess"] = False
    else:
        import subprocess
        import sys

        from repro.launch.mesh import forced_host_device_env

        cmd = [sys.executable, "-m", "benchmarks.bench_serving",
               "--multidevice-child", "--requests", str(n_requests),
               "--slots", str(slots)]
        proc = subprocess.run(cmd, env=forced_host_device_env(2),
                              capture_output=True, text=True, timeout=1800)
        if proc.returncode != 0:
            raise RuntimeError(
                f"multidevice child exited {proc.returncode}: "
                f"{proc.stderr[-2000:]}")
        section = json.loads(proc.stdout.strip().splitlines()[-1])
        section["forced_subprocess"] = True
    mig = section["migration"]
    print(f"[bench_serving] multidevice[{section['assignment']['prefill']}"
          f"|{section['assignment']['decode']}]: async "
          f"{section['disagg_async']['tok_per_s']:.1f} tok/s "
          f"({section['tok_per_s_ratio_vs_shared']:.2f}x shared-device, "
          f"{section['tok_per_s_ratio_vs_colocated']:.2f}x colocated), "
          f"stall {section['async_stall_s']*1e3:.2f}ms async vs "
          f"{section['sync_stall_s']*1e3:.2f}ms sync "
          f"(ratio {section['stall_ratio']:.2f}); migration "
          f"{mig['n_live_migrations']} live / {mig['n_done']} done; "
          f"bit_identical={section['all_identical']}", flush=True)
    return section


def run_speculative() -> Dict:
    """Draft-model speculative decoding through the programmatic serving
    API (``repro.serving.api.serve``), plus the analyzer's pricing calls.

    Four legs.  (1) *Forced*: the registry pairing — ``qwen2_1_5b``
    drafting for ``granite_34b`` at smoke scale — with ``draft_k=2``;
    greedy verification makes speculative outputs bitwise the plain
    run's, and the measured accepted-token rate is reported.  (2)
    *Priced*: the same pair with the depth left to the trade-off
    analyzer; at these smoke shapes the projected draft+verify cost
    loses to plain decode, so the gated claim is the *fallback* — the
    run must serve plain, bit-identically, and record why.  (3)
    *Adversarial price*: a draft device de-rated 100x must price
    speculation off even at a 0.95 acceptance prior.  (4) The
    full-scale registry pair's pricing table across acceptance rates
    (informational: where speculation wins once the draft really is
    ~20x cheaper than the target)."""
    from repro.configs import registry
    from repro.core.device_models import get as get_device
    from repro.serving.api import ServeOptions, serve
    from repro.serving.placement import (choose_speculation,
                                         drift_scaled_device)

    target, draft = "granite_34b", "qwen2_1_5b"
    shape = dict(arch=target, requests=6, slots=4, prompt_len=8,
                 gen_len=16, rate=1e9)

    def _opts(**overrides):
        o = ServeOptions()
        flat = o.flat_fields()
        for key, v in {**shape, **overrides}.items():
            setattr(getattr(o, flat[key]), key, v)
        o.validate()
        return o

    plain = serve(_opts())
    forced = serve(_opts(speculate=True, draft_arch=draft, draft_k=2))
    priced = serve(_opts(speculate=True, draft_arch=draft))
    st = forced.speculation

    tgt_cfg = registry.get(target).config
    draft_cfg = registry.get(draft).config
    slow_draft = drift_scaled_device(get_device("tpu-v5e"), 100.0)
    adversarial = choose_speculation(
        tgt_cfg, draft_cfg, kv_len=1024, n_tokens=8, acceptance=0.95,
        draft_name=draft, draft_device=slow_draft)
    pricing = {}
    for alpha in (0.5, 0.8, 0.95):
        d = choose_speculation(tgt_cfg, draft_cfg, kv_len=1024,
                               n_tokens=8, acceptance=alpha,
                               draft_name=draft)
        pricing[f"acceptance_{int(alpha * 100)}"] = d.summary()

    p, f, pr = plain.summary, forced.summary, priced.summary
    section = {
        "target": target,
        "draft": draft,
        "scale": "smoke",
        "workload": shape,
        "plain": p,
        "forced": f,
        "speculation": st,
        "accepted_token_rate": st["acceptance_rate"],
        "n_rounds": st["n_rounds"],
        "tok_per_s_ratio_forced": f["tok_per_s"] / p["tok_per_s"],
        "bit_identical_forced": forced.outputs == plain.outputs,
        "priced": priced.speculation,
        "priced_engaged": bool(priced.speculation["engaged"]),
        "priced_fallback": bool(
            priced.speculation.get("priced_fallback", False)),
        "tok_per_s_ratio_priced": pr["tok_per_s"] / p["tok_per_s"],
        "bit_identical_priced": priced.outputs == plain.outputs,
        "adversarial": {"draft_derate_factor": 100.0, "acceptance": 0.95,
                        "decision": adversarial.summary()},
        "pricing_full_scale": pricing,
    }
    section["all_identical"] = (section["bit_identical_forced"]
                                and section["bit_identical_priced"])
    print(f"[bench_serving] speculative[{draft}->{target}]: forced k=2 "
          f"{st['n_rounds']} rounds, acceptance "
          f"{st['acceptance_rate']:.2f}, "
          f"{section['tok_per_s_ratio_forced']:.2f}x plain tok/s; priced "
          f"leg {'engaged' if section['priced_engaged'] else 'fell back'} "
          f"({section['tok_per_s_ratio_priced']:.2f}x); adversarial "
          f"use={adversarial.use}; "
          f"bit_identical={section['all_identical']}", flush=True)
    return section


def run_bench(*, n_requests: int, slots: int, rates: List[float],
              seed: int = 7) -> Dict:
    cfg = SMOKE_CFG
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    max_len = max(PROMPT_LENS) + max(GEN_LENS)
    results = {"config": {
        "model": cfg.name, "n_requests": n_requests, "slots": slots,
        "prompt_lens": list(PROMPT_LENS), "gen_lens": list(GEN_LENS),
        "max_len": max_len,
    }, "loads": []}
    for rate in rates:
        static_reqs = _workload(n_requests, rate, cfg.vocab, seed)
        cont_reqs = _workload(n_requests, rate, cfg.vocab, seed)

        s_metrics = ServeMetrics()
        s_out = run_static(cfg, params, static_reqs, batch=slots,
                           max_len=max_len, metrics=s_metrics)
        c_metrics = run_continuous(cfg, params, cont_reqs, slots=slots,
                                   max_len=max_len)
        c_out = {r.rid: r.output for r in cont_reqs}
        bit_identical = all(s_out[rid] == c_out[rid] for rid in s_out)

        s, c = s_metrics.summary(), c_metrics.summary()
        speedup = c["tok_per_s"] / s["tok_per_s"]
        results["loads"].append({
            "offered_rate_req_s": rate,
            "static": s,
            "continuous": c,
            "speedup_tok_per_s": speedup,
            "bit_identical": bit_identical,
        })
        print(f"[bench_serving] rate={rate:g} req/s: static "
              f"{s['tok_per_s']:.1f} tok/s vs continuous "
              f"{c['tok_per_s']:.1f} tok/s -> {speedup:.2f}x "
              f"(bit_identical={bit_identical})", flush=True)
    results["disaggregation"], baselines = run_disaggregation(
        cfg, params, n_requests=n_requests, slots=slots, max_len=max_len,
        seed=seed)
    results["paged"] = run_paged(
        cfg, params, baselines, n_requests=n_requests, slots=slots,
        max_len=max_len, seed=seed)
    results["prefix"] = run_prefix(
        cfg, params, n_requests=max(n_requests * 2 // 3, 8), seed=seed)
    results["streaming"] = run_streaming(
        cfg, params, baselines, n_requests=n_requests, slots=slots,
        max_len=max_len, seed=seed)
    results["observability"] = run_observability(
        cfg, params, baselines, n_requests=n_requests, slots=slots,
        max_len=max_len, seed=seed)
    results["adaptive"] = run_adaptive(
        cfg, params, baselines, n_requests=n_requests, slots=slots,
        max_len=max_len, seed=seed)
    results["multidevice"] = run_multidevice(
        n_requests=n_requests, slots=slots, seed=seed)
    results["speculative"] = run_speculative()
    results["max_speedup"] = max(l["speedup_tok_per_s"]
                                 for l in results["loads"])
    results["all_bit_identical"] = all(
        [l["bit_identical"] for l in results["loads"]]
        + [results["disaggregation"]["bit_identical"],
           results["paged"]["all_identical"],
           results["prefix"]["all_identical"],
           results["streaming"]["all_identical"],
           results["observability"]["all_identical"],
           results["adaptive"]["all_identical"],
           results["multidevice"]["all_identical"],
           results["speculative"]["all_identical"]])
    return results


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", default="smoke", choices=["smoke", "tiny"])
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--rates", type=float, nargs="+", default=None,
                    help="offered loads (req/s); 1e9 ~= saturation")
    ap.add_argument("--out", default="BENCH_serving.json")
    # internal: run only the multidevice legs and print their JSON on the
    # last stdout line (run_multidevice spawns this under the forced
    # two-device XLA flag, which must precede the first jax import)
    ap.add_argument("--multidevice-child", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args()

    n = args.requests or (16 if args.scale == "tiny" else 48)
    if args.multidevice_child:
        section = _multidevice_section(n_requests=n, slots=args.slots,
                                       seed=7)
        print(json.dumps(section, allow_nan=False))
        return
    rates = args.rates or ([1e9] if args.scale == "tiny" else [16.0, 1e9])
    results = run_bench(n_requests=n, slots=args.slots, rates=rates)
    with open(args.out, "w") as f:
        # strict JSON: a NaN stat leaking into the report is a bug (see
        # ServeMetrics.summary on zero-completion runs), not a value
        json.dump(results, f, indent=2, allow_nan=False)
    print(f"[bench_serving] wrote {args.out}: max speedup "
          f"{results['max_speedup']:.2f}x, bit_identical="
          f"{results['all_bit_identical']}")
    if not results["all_bit_identical"]:
        raise SystemExit("continuous outputs diverged from static path")


if __name__ == "__main__":
    main()

"""Serving driver: continuous-batching engine loop (default) or the legacy
static-batch server (``--static-batching``).

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2_1_5b \
        --scale smoke --slots 8 --requests 32 --rate 16

This module is only the CLI skin: every flag maps 1:1 onto a field of
:class:`repro.serving.api.ServeOptions` and ``main()`` does nothing but
parse -> ``ServeOptions.from_args`` -> ``validate`` -> ``serve``.  The
serving logic itself — engine construction, placement, speculation,
observability exports — lives in :func:`repro.serving.api.serve`, the
same entry point benchmarks and tests drive programmatically.

Continuous path (repro.serving): an open-loop arrival stream feeds a
slot-based KV pool; the batcher prices admission with core/cost_model.py and
the jitted engine step interleaves prefill with the running decode batch.
``--kv-layout paged`` (default) stores KV in fixed-size physical blocks
gathered through per-slot block tables (vLLM-style paging; outputs stay
bit-identical to ``--kv-layout dense``), so ``--total-blocks`` can
provision the pool for tokens-in-flight instead of slots x max_seq.
``--placement auto`` additionally runs the phase-placement DSE
(repro.serving.placement): prefill and decode are priced separately over
the engine set and the serving loop disaggregates onto the winning pair
(explicit control: ``--placement disagg --prefill-engine X
--decode-engine Y``).
Static path: requests accumulate into a batch; prefill replays the prompt
into a max_len cache; decode emits one token per step for the whole batch —
the queue refills only between generations (head-of-line blocking).

Speculative decoding (``--speculate``): a draft model proposes k tokens
per slot and the target verifies all k in ONE multi-position step over
the paged KV cache, committing only the accepted prefix — greedy outputs
stay bit-identical to plain decode.  The trade-off analyzer
(repro.serving.placement.choose_speculation) prices draft steps + the
verify step against plain decode at the measured-or-prior acceptance
rate, picks the depth k, and falls back to plain decode when speculation
prices worse; an online acceptance tracker re-prices mid-run and can
veto a drafting model that stops earning its keep.  ``--draft-k K``
forces depth K regardless of price (the CI/identity knob).

Observability (``--trace``, ``--metrics-out``, ``--feed-cache``): the
continuous path can record every request's lifecycle spans into a Chrome
trace-event JSON (load it in Perfetto / ``chrome://tracing``), dump the
metrics-registry snapshot (counters, histogram summaries, sampled KV/queue
time series), and feed the observed decode-burst step timings back into the
profiling cache as measured points — the telemetry leg of ROADMAP's
online-recalibration item.

Watchdog (``--watchdog``, ``--slo-report``): the online performance
watchdog compares each burst's observed step time against the admission
price, fits piecewise-linear latency(batch) curves from the telemetry, and
— when the EWMA divergence crosses the gate — re-prices admission mid-run
(and records fresh placement advice on the disaggregated path).
``--misprice FACTOR`` injects a known pricing error for CI; ``--slo-report``
prints per-request-class TTFT/TPOT SLO attainment afterwards.

On the production mesh, params/caches shard per models/sharding.py — the
same shardings the dry-run validates for the decode_32k / long_500k cells.
"""
from __future__ import annotations

import argparse

from ..serving import placement as placement_lib
from .compile_cache import enable_compilation_cache
# re-exported for compatibility: the static server and param builder grew
# up here before the programmatic API extracted them
from ..serving.api import (ServeOptions, ServeReport, Server,  # noqa: F401
                           build_params, serve)


def build_parser() -> argparse.ArgumentParser:
    """The serve CLI's argument parser (module-level so tests and the docs
    consistency gate can introspect the flag set without running a
    server).  Every dest matches a ServeOptions leaf field; flags whose
    absence matters to validation default to None and get their effective
    default (noted in the help) inside serve()."""
    ap = argparse.ArgumentParser(prog="repro.launch.serve",
                                 description=__doc__.splitlines()[0])
    ap.add_argument("--arch", default="qwen2_1_5b")
    ap.add_argument("--scale", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--batch", type=int, default=4,
                    help="static path: batch size")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--mesh", default="host", choices=["host", "pod",
                                                       "multipod"])
    ap.add_argument("--static-batching", action="store_true",
                    help="legacy fallback: static batches instead of the "
                         "continuous engine")
    ap.add_argument("--slots", type=int, default=8,
                    help="continuous path: KV pool slots")
    ap.add_argument("--kv-layout", default="paged",
                    choices=["dense", "paged"],
                    help="continuous path: KV cache layout — paged stores "
                         "KV in fixed-size physical blocks gathered "
                         "through per-slot block tables (vLLM-style; "
                         "outputs bit-identical to dense), dense keeps "
                         "physically max_seq-long slot rows")
    ap.add_argument("--total-blocks", type=int, default=None,
                    help="paged layout: physical KV blocks to provision "
                         "(default: the dense equivalent; smaller values "
                         "provision for tokens-in-flight and admission "
                         "defers when pages run out)")
    ap.add_argument("--prefix-sharing", action="store_true",
                    help="continuous path, paged layout: maintain a prefix "
                         "index over prompt-token block prefixes and map "
                         "matching prefixes onto already-written KV pages "
                         "(refcounted, copy-on-write at a divergent tail) — "
                         "shared prefixes skip prefill and draw no fresh "
                         "blocks, so more requests fit the same pool")
    ap.add_argument("--shared-prefix-len", type=int, default=None,
                    metavar="N",
                    help="workload: front-load one common N-token prefix "
                         "onto --shared-frac of the requests (the chat/"
                         "agent system-prompt pattern prefix sharing "
                         "exploits); default: fully unique prompts")
    ap.add_argument("--shared-frac", type=float, default=None,
                    help="workload: fraction of requests carrying the "
                         "--shared-prefix-len common prefix (default 0.9; "
                         "requires --shared-prefix-len)")
    ap.add_argument("--rate", type=float, default=16.0,
                    help="continuous path: offered load (req/s)")
    ap.add_argument("--stream", action="store_true",
                    help="continuous path: stream tokens incrementally — "
                         "the engine syncs at burst boundaries and prints "
                         "each request's newly readable tokens (TTFT then "
                         "measures delivered tokens; costs one host sync "
                         "per burst)")
    ap.add_argument("--step-slo-ms", type=float, default=None,
                    help="continuous path: per-step latency objective the "
                         "cost model prices admission against")
    ap.add_argument("--device-model", default="tpu-v5e",
                    help="continuous path: core/device_models entry used to "
                         "price admission")
    ap.add_argument("--calibrated-cache", default=None, metavar="PATH",
                    help="price admission on a profiling-calibrated device "
                         "model fitted from this profile cache "
                         "(repro.profiling) instead of nominal constants")
    ap.add_argument("--calibrated-engine", default=None,
                    help="engine whose measurements to calibrate from when "
                         "--calibrated-cache is given (default xla)")
    ap.add_argument("--placement", default="colocated",
                    choices=["colocated", "disagg", "auto"],
                    help="auto: price prefill/decode separately over the "
                         "placement engine set (repro.serving.placement) "
                         "and run the winning pair; disagg: force the "
                         "disaggregated loop on --prefill-engine/"
                         "--decode-engine")
    ap.add_argument("--placement-objective", default="latency",
                    choices=list(placement_lib.OBJECTIVES),
                    help="objective the phase placement minimizes")
    ap.add_argument("--prefill-engine", default=None, metavar="ENGINE",
                    help="engine (core/engines name) whose device model "
                         "prices the prefill phase (implies --placement "
                         "disagg unless auto)")
    ap.add_argument("--decode-engine", default=None, metavar="ENGINE",
                    help="engine whose device model prices the decode phase")
    ap.add_argument("--prefill-slots", type=int, default=None,
                    help="disaggregated path: prefill-engine slots "
                         "(default: --slots)")
    ap.add_argument("--device-assignment", default="single",
                    choices=["single", "auto"],
                    help="disaggregated path: auto pins the prefill and "
                         "decode engines onto distinct jax devices when "
                         ">= 2 are visible (params + KV arenas live per "
                         "phase, hand-offs become real inter-device "
                         "copies; on CPU hosts set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N); "
                         "single keeps everything on the default device")
    ap.add_argument("--sync-handoff", action="store_true",
                    help="disaggregated path: adopt each phase hand-off "
                         "immediately after dispatch instead of letting "
                         "the transfer overlap the prefill engine's next "
                         "bursts (the synchronous baseline the async "
                         "hand-off is measured against)")
    ap.add_argument("--handoff-link-bw", type=float, default=None,
                    metavar="BYTES_PER_S",
                    help="disaggregated path: price phase hand-offs at "
                         "this link bandwidth instead of the device "
                         "models' datasheet fallback (wins over "
                         "--measure-link-bw)")
    ap.add_argument("--measure-link-bw", nargs="?", default=None,
                    const=True, metavar="PATH",
                    help="measure an actual inter-device jax.device_put "
                         "of a representative page batch between the two "
                         "phase devices at startup, record it in the "
                         "profile cache (default path: the "
                         "REPRO_PROFILE_CACHE cache) for "
                         "place_phases(price=\"measured\"), and price "
                         "this run's hand-offs with it")
    ap.add_argument("--persist-curves", default=None, metavar="PATH",
                    help="continuous path: prime admission pricing from "
                         "the latency(batch) curve a previous run fed "
                         "into this profile cache (source="
                         "serving-telemetry), and flush this run's burst "
                         "telemetry back on exit — a restarted server "
                         "prices from the last run's curve instead of "
                         "re-warming")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="continuous path: record per-request lifecycle "
                         "spans + engine burst/sync spans and write a "
                         "Chrome trace-event JSON (open in Perfetto or "
                         "chrome://tracing)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="continuous path: dump the metrics-registry "
                         "snapshot (counters, histogram summaries, sampled "
                         "KV-occupancy/queue-depth time series) as JSON")
    ap.add_argument("--feed-cache", nargs="?", default=None,
                    const=True, metavar="PATH",
                    help="continuous path: feed observed decode-burst step "
                         "timings back into the profiling cache as measured "
                         "points (default path: the REPRO_PROFILE_CACHE "
                         "profile cache), so price=\"measured\" learns from "
                         "this run's traffic; with --speculate also "
                         "persists the measured acceptance rate the "
                         "analyzer prices later runs on")
    ap.add_argument("--watchdog", action="store_true",
                    help="continuous path: run the online performance "
                         "watchdog — compare observed burst step times "
                         "against the priced cost model, fit latency(batch) "
                         "curves from telemetry, and re-price admission "
                         "mid-run when the EWMA divergence crosses the gate")
    ap.add_argument("--drift-gate", type=float, default=None,
                    help="watchdog: observed/priced EWMA ratio (or its "
                         "inverse) that raises a DriftAlert (default 1.5; "
                         "requires --watchdog)")
    ap.add_argument("--misprice", type=float, default=None, metavar="FACTOR",
                    help="debug/CI: scale the admission device model's "
                         "throughput down by FACTOR (drift_scaled_device) "
                         "so the priced step time is FACTOR x too slow — "
                         "an injected mispricing the watchdog must detect "
                         "and correct (FACTOR < 1 prices too FAST, so the "
                         "drifted device looks slow and placement moves "
                         "work off it; requires --watchdog)")
    ap.add_argument("--misprice-phase", default=None,
                    choices=["both", "prefill", "decode"],
                    help="--misprice scope on the disaggregated path: "
                         "misprice only one phase's device model so "
                         "exactly that stream drifts (the deterministic "
                         "trigger for mid-run placement actuation; "
                         "default both, requires --misprice)")
    ap.add_argument("--slo-report", action="store_true",
                    help="continuous path: print per-request-class "
                         "(short/medium/long by generation length) "
                         "TTFT/TPOT SLO attainment after the run")
    ap.add_argument("--slo-ttft-ms", type=float, default=None,
                    help="--slo-report: time-to-first-token objective "
                         "(ms, default 2000; requires --slo-report)")
    ap.add_argument("--slo-tpot-ms", type=float, default=None,
                    help="--slo-report: time-per-output-token objective "
                         "(ms, default 200; requires --slo-report)")
    ap.add_argument("--speculate", action="store_true",
                    help="continuous path, paged layout: draft-model "
                         "speculative decoding — the draft proposes k "
                         "tokens per slot, the target verifies all k in "
                         "one multi-position step over the paged cache "
                         "(greedy outputs stay bit-identical to plain "
                         "decode); the trade-off analyzer prices the "
                         "draft and depth against plain decode at the "
                         "measured-or-prior acceptance rate and serves "
                         "plain when speculation prices worse")
    ap.add_argument("--draft-arch", default=None, metavar="ARCH",
                    help="--speculate: registry arch proposing draft "
                         "tokens (default qwen2_1_5b; must share the "
                         "target's vocab)")
    ap.add_argument("--draft-k", type=int, default=None, metavar="K",
                    help="--speculate: force draft depth K and skip the "
                         "analyzer's engage/veto pricing (the CI and "
                         "bit-identity knob)")
    return ap


def main() -> None:
    ap = build_parser()
    args = ap.parse_args()
    options = ServeOptions.from_args(args)
    try:
        options.validate()
    except ValueError as err:
        ap.error(str(err))
    enable_compilation_cache()
    try:
        serve(options, verbose=True)
    except ValueError as err:
        raise SystemExit(f"[serve] {err}")


if __name__ == "__main__":
    main()

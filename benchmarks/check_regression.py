"""Benchmark regression gate: fresh BENCH_serving.json vs the committed one.

    PYTHONPATH=src python -m benchmarks.check_regression \
        --baseline BENCH_serving.json --fresh /tmp/BENCH_serving.json

Fails (exit 1) when the fresh run regresses >``--threshold`` (default 20%)
on throughput at saturation.  Raw tok/s is not comparable across hosts
(the committed baseline and a CI runner are different machines), so the
default gate compares the *continuous-over-static speedup* at the highest
offered rate — both paths run on the same host in the same process, so
their ratio is a machine-normalized throughput measure.

``--absolute`` additionally gates raw tok/s against a *per-host recorded
baseline*: ``benchmarks/baselines/<host-key>.json``, keyed like the
profiling cache (jax version + backend) plus the platform triple and the
visible hardware (CPU model digest + core count), so a baseline recorded
on one machine never gates a different one — unlike-keyed hosts record
their own floors.  The first run on a
host records the baseline (``--record-absolute``); later runs on the same
host must stay within the threshold of it.  CI persists the baselines
directory across runs with ``actions/cache`` so ephemeral runners gate
against their own image's history.

Correctness gates always apply: every load's continuous outputs must be
bit-identical to static, the disaggregated run's outputs must be
bit-identical to colocated, the ``paged`` section must be present and
well-formed — paged outputs bit-identical to dense in colocated and
disaggregated modes and ``kv_bytes_paged`` strictly below
``kv_bytes_dense`` at equal slots — the ``prefix`` section must be
present and well-formed (shared outputs bit-identical to unshared at both
traffic mixes, prefix hits actually fired, and a >=1.5x peak-slots or p50
TTFT win at 90% shared traffic under the dense-equal block budget) — and
the ``streaming`` section must be
present and well-formed (streamed outputs bit-identical to the completion
pull, deltas concatenating to exactly the completion rows,
``ttft_dispatch <= ttft``) — so a malformed BENCH_serving.json fails the
gate instead of slipping through.  The ``observability`` section must be
present and well-formed: traced runs bit-identical to untraced, spans
balanced with full lifecycle coverage, and the NullTracer throughput
ratio at or above the overhead floor.  Every required stat is checked
with :func:`_num`, which rejects NaN/inf — a zero-completion run's
``None`` percentiles fail the gate instead of sailing through as NaN.

The ``adaptive`` section must be present and well-formed: under the
bench's injected admission mispricing the watchdog must have fired
(>=1 alert, >=1 re-price, token budget raised) and improved both
saturation throughput and p50 TTFT bit-identically, and tracer+watchdog
throughput must hold the same overhead floor as the NullTracer bound.

The ``multidevice`` section must be present and well-formed: every leg
bit-identical to colocated serving, and — when the run saw two distinct
devices — the async hand-off must hide at least half the transfer stall
the blocking baseline pays, distinct-device throughput must hold the
shared-device floor, and the watchdog-actuated mid-run migration must
complete every request with >=1 in-flight slot live-migrated.

The ``speculative`` section must be present and well-formed: the
forced-depth speculative run bit-identical to plain decode with >=1
round and a measured accepted-token rate in [0, 1], the analyzer-priced
run either beating plain throughput within the regression budget or
explicitly falling back to plain decode, and the adversarially de-rated
draft device pricing speculation off.

``--trace trace.json`` gates a Chrome trace-event file written by
``serve --trace`` (``--fresh`` becomes optional): strict JSON (NaN and
Infinity literals rejected), non-empty well-formed ``traceEvents``, no
unclosed spans, and at least one span/instant per request-lifecycle
stage (``--require-handoff`` adds the disaggregated hand-off span;
``--require-watchdog`` adds the drift_alert + reprice instants a
``serve --watchdog --misprice`` run must emit).
"""
from __future__ import annotations

import argparse
import json
import math
import os
import platform
import sys
from typing import List, Tuple

DEFAULT_BASELINES_DIR = os.path.join(os.path.dirname(__file__), "baselines")


def _num(v) -> bool:
    """True only for finite real numbers: a required stat that is None,
    NaN or inf is a malformed report, not a value (bool is an int
    subclass, so it is rejected explicitly)."""
    return (isinstance(v, (int, float)) and not isinstance(v, bool)
            and math.isfinite(v))


def host_key() -> str:
    """Stable identifier for 'the machine + numeric environment this bench
    ran on': platform triple + visible hardware (CPU model where the OS
    reports one, core count) + python + the profiling cache's environment
    key (jax version, backend).  Absolute tok/s is only comparable within
    one such key — different hardware hashes to a different key and
    records its own baseline instead of being gated by another machine's
    floor."""
    import hashlib

    import jax

    cpu = platform.processor() or platform.machine()
    hw = hashlib.sha256(cpu.encode()).hexdigest()[:8]
    return "-".join([
        platform.system().lower(), platform.machine(),
        f"cpu{os.cpu_count()}x{hw}",
        f"py{sys.version_info[0]}.{sys.version_info[1]}",
        f"jax{jax.__version__}", jax.default_backend(),
    ])


def saturation_load(results: dict) -> dict:
    return max(results["loads"], key=lambda l: l["offered_rate_req_s"])


# per-mode summaries the streaming section must carry, with the numeric
# fields the TTFT/TPOT comparison reads (ServeMetrics.summary keys)
_STREAMING_SUMMARY_KEYS = ("tok_per_s", "ttft_p50_s", "ttft_dispatch_p50_s",
                           "tpot_p50_s", "tokens_streamed", "stream_deltas",
                           "tokens_out")
_STREAMING_BOOL_KEYS = ("bit_identical", "delta_concat_identical",
                        "ttft_dispatch_leq_ttft")


# numeric fields the paged section must carry (bench run_paged keys) and
# the per-layout summaries the throughput comparison reads
_PAGED_NUMERIC_KEYS = ("block_size", "blocks_per_slot", "total_blocks",
                       "dense_equiv_blocks", "kv_bytes_dense",
                       "kv_bytes_paged", "kv_bytes_ratio",
                       "achievable_n_slots_at_dense_budget",
                       "tok_per_s_ratio")
_PAGED_BOOL_KEYS = ("bit_identical_colocated", "bit_identical_disaggregated",
                    "all_identical")


def validate_paged(fresh: dict) -> List[Tuple[str, bool, str]]:
    """Schema + correctness checks for the ``paged`` section: well-formed
    summaries, paged-vs-dense bit-identity in both serving modes, and KV
    bytes resident strictly below dense at equal slots."""
    checks: List[Tuple[str, bool, str]] = []
    section = fresh.get("paged")
    if not isinstance(section, dict):
        return [("paged section present", False,
                 f"missing or not an object: {type(section).__name__}")]
    problems: List[str] = []
    for k in _PAGED_NUMERIC_KEYS:
        if not _num(section.get(k)):
            problems.append(f"{k}: not a finite number")
    for k in _PAGED_BOOL_KEYS:
        if not isinstance(section.get(k), bool):
            problems.append(f"{k}: not a bool")
    for layout in ("dense", "paged"):
        summ = section.get(layout)
        if not isinstance(summ, dict):
            problems.append(f"{layout}: missing summary")
            continue
        for k in ("tok_per_s", "tokens_out", "requests_done"):
            if not _num(summ.get(k)):
                problems.append(f"{layout}.{k}: not a finite number")
    checks.append(("paged section schema", not problems,
                   "; ".join(problems) if problems else
                   "layout summaries + memory accounting well-formed"))
    if problems:
        return checks
    checks.append((
        "paged outputs bit-identical to dense",
        section["bit_identical_colocated"]
        and section["bit_identical_disaggregated"],
        ", ".join(f"{k}={section[k]}" for k in _PAGED_BOOL_KEYS[:2])))
    checks.append((
        "paged KV bytes resident strictly below dense",
        section["kv_bytes_paged"] < section["kv_bytes_dense"],
        f"paged {section['kv_bytes_paged']} vs dense "
        f"{section['kv_bytes_dense']} bytes "
        f"({section['kv_bytes_ratio']:.2f}x) at equal n_slots"))
    return checks


def validate_streaming(fresh: dict) -> List[Tuple[str, bool, str]]:
    """Schema + correctness checks for the ``streaming`` section."""
    checks: List[Tuple[str, bool, str]] = []
    section = fresh.get("streaming")
    if not isinstance(section, dict):
        return [("streaming section present", False,
                 f"missing or not an object: {type(section).__name__}")]
    problems: List[str] = []
    for mode in ("colocated", "disaggregated"):
        entry = section.get(mode)
        if not isinstance(entry, dict):
            problems.append(f"{mode}: missing")
            continue
        for kind in ("completion", "streaming"):
            summ = entry.get(kind)
            if not isinstance(summ, dict):
                problems.append(f"{mode}.{kind}: missing summary")
                continue
            for k in _STREAMING_SUMMARY_KEYS:
                if not _num(summ.get(k)):
                    problems.append(f"{mode}.{kind}.{k}: not a finite "
                                    f"number")
        for k in _STREAMING_BOOL_KEYS:
            if not isinstance(entry.get(k), bool):
                problems.append(f"{mode}.{k}: not a bool")
        strm = entry.get("streaming")
        if isinstance(strm, dict) and isinstance(
                strm.get("tokens_streamed"), (int, float)):
            # streaming mode must deliver every output token incrementally
            if strm["tokens_streamed"] != strm.get("tokens_out"):
                problems.append(
                    f"{mode}: streamed {strm['tokens_streamed']} of "
                    f"{strm.get('tokens_out')} output tokens")
    checks.append(("streaming section schema", not problems,
                   "; ".join(problems) if problems else
                   "colocated + disaggregated, completion + streaming "
                   "summaries well-formed"))
    for mode in ("colocated", "disaggregated"):
        entry = section.get(mode)
        if not isinstance(entry, dict):
            continue
        ok = all(entry.get(k) is True for k in _STREAMING_BOOL_KEYS)
        checks.append((
            f"streamed outputs identical to completion pull ({mode})", ok,
            ", ".join(f"{k}={entry.get(k)}" for k in _STREAMING_BOOL_KEYS)))
    return checks


# the capacity win prefix sharing must show at 90% shared traffic and a
# dense-equal block budget: >=1.5x peak concurrent slots, or equivalently
# >=1.5x lower p50 TTFT (the same win read off the latency axis)
PREFIX_CAPACITY_FLOOR = 1.5

_PREFIX_NUMERIC_KEYS = ("block_size", "blocks_per_slot", "n_slots",
                        "total_blocks", "dense_equivalent_slots",
                        "shared_prefix_len", "n_requests")
_PREFIX_ENTRY_NUMERIC_KEYS = ("peak_slots_unshared", "peak_slots_shared",
                              "admitted_slots_ratio", "ttft_p50_ratio",
                              "tok_per_s_ratio", "prefix_hits",
                              "tokens_prefill_skipped", "cow_copies")


def validate_prefix(fresh: dict) -> List[Tuple[str, bool, str]]:
    """Schema + correctness checks for the ``prefix`` section: well-formed
    per-fraction entries, shared outputs bit-identical to unshared at both
    traffic mixes, prefix hits actually fired, and the 90%-shared capacity
    win at or above :data:`PREFIX_CAPACITY_FLOOR` on peak admitted slots
    or p50 TTFT at the dense-equal block budget."""
    checks: List[Tuple[str, bool, str]] = []
    section = fresh.get("prefix")
    if not isinstance(section, dict):
        return [("prefix section present", False,
                 f"missing or not an object: {type(section).__name__}")]
    problems: List[str] = []
    for k in _PREFIX_NUMERIC_KEYS:
        if not _num(section.get(k)):
            problems.append(f"{k}: not a finite number")
    if not isinstance(section.get("all_identical"), bool):
        problems.append("all_identical: not a bool")
    for frac in ("shared_frac_50", "shared_frac_90"):
        entry = section.get(frac)
        if not isinstance(entry, dict):
            problems.append(f"{frac}: missing")
            continue
        for k in _PREFIX_ENTRY_NUMERIC_KEYS:
            if not _num(entry.get(k)):
                problems.append(f"{frac}.{k}: not a finite number")
        if not isinstance(entry.get("bit_identical"), bool):
            problems.append(f"{frac}.bit_identical: not a bool")
        for kind in ("unshared", "shared"):
            summ = entry.get(kind)
            if not isinstance(summ, dict):
                problems.append(f"{frac}.{kind}: missing summary")
                continue
            for k in ("tok_per_s", "ttft_p50_s", "tokens_out",
                      "requests_done"):
                if not _num(summ.get(k)):
                    problems.append(f"{frac}.{kind}.{k}: not a finite "
                                    f"number")
    checks.append(("prefix section schema", not problems,
                   "; ".join(problems) if problems else
                   "50% + 90% shared-traffic entries well-formed"))
    if problems:
        return checks
    checks.append((
        "shared outputs bit-identical to unshared",
        section["all_identical"]
        and all(section[f]["bit_identical"]
                for f in ("shared_frac_50", "shared_frac_90")),
        ", ".join(f"{f}={section[f]['bit_identical']}"
                  for f in ("shared_frac_50", "shared_frac_90"))))
    e90 = section["shared_frac_90"]
    checks.append((
        "prefix cache actually shared pages",
        e90["prefix_hits"] >= 1 and e90["tokens_prefill_skipped"] >= 1,
        f"{e90['prefix_hits']} hits, {e90['tokens_prefill_skipped']} "
        f"prefill tokens skipped, {e90['cow_copies']} cow copies at 90%"))
    win = max(e90["admitted_slots_ratio"], e90["ttft_p50_ratio"])
    checks.append((
        "prefix sharing capacity win at dense-equal budget",
        win >= PREFIX_CAPACITY_FLOOR,
        f"90% shared: {e90['peak_slots_shared']} vs "
        f"{e90['peak_slots_unshared']} peak slots "
        f"({e90['admitted_slots_ratio']:.2f}x), ttft p50 "
        f"{e90['ttft_p50_ratio']:.2f}x better "
        f"(floor {PREFIX_CAPACITY_FLOOR}x on either axis)"))
    return checks


# the overhead floor the NullTracer path must hold: tracing compiled in
# but switched off may cost at most 2% of untraced saturation throughput
OBS_OVERHEAD_FLOOR = 0.98

_OBS_RATIO_KEYS = ("overhead_ratio_null", "overhead_ratio_traced")
_OBS_BOOL_KEYS = ("bit_identical_null", "bit_identical_traced",
                  "bit_identical_traced_disagg", "trace_spans_balanced",
                  "lifecycle_spans_present", "handoff_span_present",
                  "all_identical")
_OBS_COUNT_KEYS = ("trace_events", "trace_events_disagg", "trace_dropped",
                   "metrics_series_points")


def validate_observability(fresh: dict) -> List[Tuple[str, bool, str]]:
    """Schema + correctness checks for the ``observability`` section:
    well-formed summaries, traced runs bit-identical to untraced with
    balanced full-lifecycle spans, and the NullTracer throughput ratio at
    or above :data:`OBS_OVERHEAD_FLOOR` (the traced ratio is reported but
    not gated — a full ring-buffer trace is a debugging artifact)."""
    checks: List[Tuple[str, bool, str]] = []
    section = fresh.get("observability")
    if not isinstance(section, dict):
        return [("observability section present", False,
                 f"missing or not an object: {type(section).__name__}")]
    problems: List[str] = []
    for k in _OBS_RATIO_KEYS + _OBS_COUNT_KEYS:
        if not _num(section.get(k)):
            problems.append(f"{k}: not a finite number")
    for k in _OBS_BOOL_KEYS:
        if not isinstance(section.get(k), bool):
            problems.append(f"{k}: not a bool")
    for run in ("untraced", "null_tracer", "traced"):
        summ = section.get(run)
        if not isinstance(summ, dict):
            problems.append(f"{run}: missing summary")
            continue
        for k in ("tok_per_s", "tokens_out", "requests_done"):
            if not _num(summ.get(k)):
                problems.append(f"{run}.{k}: not a finite number")
    checks.append(("observability section schema", not problems,
                   "; ".join(problems) if problems else
                   "untraced + null-tracer + traced summaries well-formed"))
    if problems:
        return checks
    checks.append((
        "traced outputs bit-identical to untraced",
        section["all_identical"],
        ", ".join(f"{k}={section[k]}" for k in _OBS_BOOL_KEYS[:3])))
    checks.append((
        "trace spans balanced with full lifecycle coverage",
        section["trace_spans_balanced"]
        and section["lifecycle_spans_present"]
        and section["handoff_span_present"]
        and section["trace_events"] > 0,
        f"{section['trace_events']} events colocated, "
        f"{section['trace_events_disagg']} disaggregated, "
        f"{section['trace_dropped']} dropped"))
    checks.append((
        "null-tracer overhead within budget",
        section["overhead_ratio_null"] >= OBS_OVERHEAD_FLOOR,
        f"null-tracer {section['overhead_ratio_null']:.3f}x of untraced "
        f"tok/s (floor {OBS_OVERHEAD_FLOOR}; traced "
        f"{section['overhead_ratio_traced']:.3f}x, not gated)"))
    return checks


# the adaptive (watchdog) section: numeric/bool schema plus the gated
# control-loop outcomes — re-pricing must recover throughput AND TTFT
# under the injected mispricing, with at least one alert + re-price, and
# tracer+watchdog throughput must hold the same overhead floor the
# NullTracer bound uses
_ADAPTIVE_NUMERIC_KEYS = ("tok_per_s_ratio", "ttft_p50_ratio", "n_alerts",
                          "n_reprices", "token_budget_static",
                          "token_budget_final", "overhead_ratio_watchdog")
_ADAPTIVE_BOOL_KEYS = ("bit_identical_static", "bit_identical_adaptive",
                       "bit_identical_overhead", "all_identical")


def validate_adaptive(fresh: dict) -> List[Tuple[str, bool, str]]:
    """Schema + correctness checks for the ``adaptive`` section: the
    watchdog's mid-run re-pricing must beat the statically mispriced run
    on saturation throughput and p50 TTFT (bit-identically — admission
    policy never changes outputs), must actually have fired (>=1 alert,
    >=1 re-price, budget raised), and the tracer+watchdog overhead ratio
    must stay at or above :data:`OBS_OVERHEAD_FLOOR`."""
    checks: List[Tuple[str, bool, str]] = []
    section = fresh.get("adaptive")
    if not isinstance(section, dict):
        return [("adaptive section present", False,
                 f"missing or not an object: {type(section).__name__}")]
    problems: List[str] = []
    for k in _ADAPTIVE_NUMERIC_KEYS:
        if not _num(section.get(k)):
            problems.append(f"{k}: not a finite number")
    for k in _ADAPTIVE_BOOL_KEYS:
        if not isinstance(section.get(k), bool):
            problems.append(f"{k}: not a bool")
    for run in ("static_priced", "adaptive"):
        summ = section.get(run)
        if not isinstance(summ, dict):
            problems.append(f"{run}: missing summary")
            continue
        for k in ("tok_per_s", "ttft_p50_s", "tokens_out", "requests_done"):
            if not _num(summ.get(k)):
                problems.append(f"{run}.{k}: not a finite number")
    checks.append(("adaptive section schema", not problems,
                   "; ".join(problems) if problems else
                   "static + adaptive summaries well-formed"))
    if problems:
        return checks
    checks.append((
        "watchdog control loop fired",
        section["n_alerts"] >= 1 and section["n_reprices"] >= 1
        and section["token_budget_final"] > section["token_budget_static"],
        f"{section['n_alerts']} alerts, {section['n_reprices']} reprices, "
        f"budget {section['token_budget_static']} -> "
        f"{section['token_budget_final']} "
        f"({section.get('price_source_final')})"))
    checks.append((
        "re-pricing improves the drifted-cost run",
        section["tok_per_s_ratio"] > 1.0 and section["ttft_p50_ratio"] > 1.0,
        f"tok/s {section['tok_per_s_ratio']:.2f}x, ttft p50 "
        f"{section['ttft_p50_ratio']:.2f}x better with the watchdog on"))
    checks.append((
        "adaptive outputs bit-identical",
        section["all_identical"],
        ", ".join(f"{k}={section[k]}" for k in _ADAPTIVE_BOOL_KEYS[:3])))
    checks.append((
        "watchdog overhead within budget",
        section["overhead_ratio_watchdog"] >= OBS_OVERHEAD_FLOOR,
        f"tracer+watchdog {section['overhead_ratio_watchdog']:.3f}x of "
        f"tracer-only tok/s (floor {OBS_OVERHEAD_FLOOR})"))
    return checks


# the multidevice section: real per-phase device assignment + the async
# hand-off.  The overlap and throughput gates apply when the run actually
# saw two distinct devices (the CPU bench's child forces two host devices,
# so there a shared assignment fails; a one-chip accelerator run keeps the
# schema + bit-identity gates only): the double-buffered hand-off must
# hide at least half the transfer stall the blocking baseline pays
# (async/sync stall ratio, gated only when the sync baseline's stall
# clears an absolute measurement floor), and the distinct assignment must
# not lose throughput against the same disagg loop sharing one device.
# The watchdog-actuated migration leg is gated in both worlds — its
# trigger is the mispriced device *model*, not the device count: every
# request completes, at least one in-flight slot live-migrates, and
# outputs stay bit-identical to colocated serving.
MULTIDEVICE_STALL_CEILING = 0.5
MULTIDEVICE_STALL_FLOOR_S = 1e-3

_MULTIDEVICE_NUMERIC_KEYS = ("n_devices", "tok_per_s_ratio_vs_colocated",
                             "tok_per_s_ratio_vs_sync",
                             "tok_per_s_ratio_vs_shared", "sync_stall_s",
                             "async_stall_s", "async_overlap_s",
                             "stall_ratio")
_MULTIDEVICE_BOOL_KEYS = ("distinct_devices", "bit_identical_async",
                          "bit_identical_sync", "bit_identical_shared",
                          "all_identical", "forced_subprocess")
_MULTIDEVICE_SUMMARIES = ("colocated", "disagg_async", "disagg_sync",
                          "disagg_shared_device")
_MIGRATION_NUMERIC_KEYS = ("n_requests", "n_done", "n_dropped",
                           "n_live_migrations", "n_alerts")
_MIGRATION_BOOL_KEYS = ("requests_preserved", "bit_identical")


def validate_multidevice(fresh: dict, *,
                         threshold: float) -> List[Tuple[str, bool, str]]:
    """Schema + correctness checks for the ``multidevice`` section: async
    hand-off overlap vs the blocking baseline, distinct-device throughput
    vs the shared-device loop, and mid-run migration preserving in-flight
    slots — every leg bit-identical to colocated serving."""
    checks: List[Tuple[str, bool, str]] = []
    section = fresh.get("multidevice")
    if not isinstance(section, dict):
        return [("multidevice section present", False,
                 f"missing or not an object: {type(section).__name__}")]
    problems: List[str] = []
    for k in _MULTIDEVICE_NUMERIC_KEYS:
        if not _num(section.get(k)):
            problems.append(f"{k}: not a finite number")
    for k in _MULTIDEVICE_BOOL_KEYS:
        if not isinstance(section.get(k), bool):
            problems.append(f"{k}: not a bool")
    asn = section.get("assignment")
    if not (isinstance(asn, dict)
            and isinstance(asn.get("prefill"), str)
            and isinstance(asn.get("decode"), str)):
        problems.append("assignment: missing prefill/decode device labels")
    link = section.get("measured_link_bw")
    if link is not None and not (_num(link) and link > 0):
        problems.append("measured_link_bw: neither null nor a positive "
                        "number")
    for leg in _MULTIDEVICE_SUMMARIES:
        summ = section.get(leg)
        if not isinstance(summ, dict):
            problems.append(f"{leg}: missing summary")
            continue
        for k in ("tok_per_s", "tokens_out", "requests_done"):
            if not _num(summ.get(k)):
                problems.append(f"{leg}.{k}: not a finite number")
    mig = section.get("migration")
    if not isinstance(mig, dict):
        problems.append("migration: missing")
    else:
        for k in _MIGRATION_NUMERIC_KEYS:
            if not _num(mig.get(k)):
                problems.append(f"migration.{k}: not a finite number")
        for k in _MIGRATION_BOOL_KEYS:
            if not isinstance(mig.get(k), bool):
                problems.append(f"migration.{k}: not a bool")
        if not isinstance(mig.get("decode_target"), str):
            problems.append("migration.decode_target: not a string")
    checks.append(("multidevice section schema", not problems,
                   "; ".join(problems) if problems else
                   f"{section.get('n_devices')} devices "
                   f"({asn.get('prefill')} | {asn.get('decode')}), four "
                   f"serving legs + migration well-formed"))
    if problems:
        return checks

    checks.append((
        "multidevice outputs bit-identical to colocated",
        section["all_identical"],
        ", ".join(f"{k}={section[k]}"
                  for k in _MULTIDEVICE_BOOL_KEYS[1:4])
        + f", migration={mig['bit_identical']}"))

    distinct = section["distinct_devices"]
    if distinct:
        # a sync stall too small to measure cannot anchor a ratio — the
        # overlap gate needs the blocking baseline to have actually paid
        # a visible transfer cost
        if section["sync_stall_s"] >= MULTIDEVICE_STALL_FLOOR_S:
            checks.append((
                "async hand-off hides the transfer stall",
                section["stall_ratio"] <= MULTIDEVICE_STALL_CEILING,
                f"async stall {section['async_stall_s']*1e3:.2f}ms vs sync "
                f"{section['sync_stall_s']*1e3:.2f}ms "
                f"(ratio {section['stall_ratio']:.2f}, ceiling "
                f"{MULTIDEVICE_STALL_CEILING}; overlap "
                f"{section['async_overlap_s']*1e3:.2f}ms)"))
        else:
            checks.append((
                "async hand-off hides the transfer stall",
                True,
                f"sync stall {section['sync_stall_s']*1e3:.2f}ms below the "
                f"{MULTIDEVICE_STALL_FLOOR_S*1e3:.0f}ms measurement floor; "
                f"ratio not gated"))
        floor = 1.0 - threshold
        checks.append((
            "distinct-device throughput holds the shared-device floor",
            section["tok_per_s_ratio_vs_shared"] >= floor,
            f"{section['tok_per_s_ratio_vs_shared']:.2f}x the same loop on "
            f"one device (floor {floor:.2f}x; vs sync hand-off "
            f"{section['tok_per_s_ratio_vs_sync']:.2f}x, vs colocated "
            f"{section['tok_per_s_ratio_vs_colocated']:.2f}x)"))
    else:
        # the forced-CPU child always sees two devices; only an
        # accelerator host with a single chip may share one
        checks.append((
            "multidevice ran on distinct devices",
            not section["forced_subprocess"],
            f"{section['n_devices']} visible device(s), shared assignment; "
            f"overlap + throughput gates skipped "
            f"(forced_subprocess={section['forced_subprocess']})"))

    checks.append((
        "mid-run migration preserves in-flight slots",
        mig["requests_preserved"] and mig["n_live_migrations"] >= 1
        and mig["bit_identical"],
        f"{mig['n_done']}/{mig['n_requests']} done, "
        f"{mig['n_dropped']} dropped, {mig['n_live_migrations']} live "
        f"migrations, {mig['n_alerts']} alerts, decode -> "
        f"{mig['decode_target']} engine"))
    return checks


# the speculative section: forced-depth speculative decoding must stay
# bit-identical to plain decode with real rounds and a sane measured
# accepted-token rate; the analyzer-priced run must either beat plain
# throughput (within the regression budget) or have explicitly fallen
# back to plain decode; and the adversarially de-rated draft device must
# price speculation off
_SPECULATIVE_NUMERIC_KEYS = ("accepted_token_rate", "n_rounds",
                             "tok_per_s_ratio_forced",
                             "tok_per_s_ratio_priced")
_SPECULATIVE_BOOL_KEYS = ("bit_identical_forced", "bit_identical_priced",
                          "priced_engaged", "priced_fallback",
                          "all_identical")
_SPECULATIVE_ROUND_KEYS = ("n_rounds", "n_proposed", "n_accepted",
                           "n_committed")


def validate_speculative(fresh: dict, *,
                         threshold: float) -> List[Tuple[str, bool, str]]:
    """Schema + correctness checks for the ``speculative`` section:
    forced-depth speculation bit-identical to plain decode with >=1 round
    and an accepted-token rate in [0, 1], the analyzer-priced run holding
    the plain-decode throughput floor (or explicitly falling back), and
    the adversarial draft pricing rejecting speculation."""
    checks: List[Tuple[str, bool, str]] = []
    section = fresh.get("speculative")
    if not isinstance(section, dict):
        return [("speculative section present", False,
                 f"missing or not an object: {type(section).__name__}")]
    problems: List[str] = []
    for k in _SPECULATIVE_NUMERIC_KEYS:
        if not _num(section.get(k)):
            problems.append(f"{k}: not a finite number")
    for k in _SPECULATIVE_BOOL_KEYS:
        if not isinstance(section.get(k), bool):
            problems.append(f"{k}: not a bool")
    for run in ("plain", "forced"):
        summ = section.get(run)
        if not isinstance(summ, dict):
            problems.append(f"{run}: missing summary")
            continue
        for k in ("tok_per_s", "tokens_out", "requests_done"):
            if not _num(summ.get(k)):
                problems.append(f"{run}.{k}: not a finite number")
    spec = section.get("speculation")
    if not isinstance(spec, dict):
        problems.append("speculation: missing round accounting")
    else:
        for k in _SPECULATIVE_ROUND_KEYS:
            if not _num(spec.get(k)):
                problems.append(f"speculation.{k}: not a finite number")
    adv = section.get("adversarial")
    adv_decision = adv.get("decision") if isinstance(adv, dict) else None
    if not (isinstance(adv_decision, dict)
            and isinstance(adv_decision.get("use"), bool)):
        problems.append("adversarial.decision: missing or no 'use' bool")
    checks.append(("speculative section schema", not problems,
                   "; ".join(problems) if problems else
                   "plain + forced + priced runs and pricing decisions "
                   "well-formed"))
    if problems:
        return checks
    checks.append((
        "speculative outputs bit-identical to plain decode",
        section["all_identical"],
        f"forced={section['bit_identical_forced']}, "
        f"priced={section['bit_identical_priced']}"))
    rate = section["accepted_token_rate"]
    checks.append((
        "speculative rounds actually ran",
        section["n_rounds"] >= 1 and 0.0 <= rate <= 1.0,
        f"{section['n_rounds']} rounds, "
        f"{spec['n_accepted']}/{spec['n_proposed']} proposals accepted "
        f"(rate {rate:.2f}), {spec['n_committed']} tokens committed"))
    floor = 1.0 - threshold
    priced_ok = (section["priced_fallback"]
                 or section["tok_per_s_ratio_priced"] >= floor)
    checks.append((
        "priced speculation holds the plain-decode floor",
        priced_ok,
        (f"analyzer fell back to plain decode "
         f"({section['tok_per_s_ratio_priced']:.2f}x plain tok/s)"
         if section["priced_fallback"] else
         f"engaged at {section['tok_per_s_ratio_priced']:.2f}x plain "
         f"tok/s (floor {floor:.2f}x; forced leg "
         f"{section['tok_per_s_ratio_forced']:.2f}x, not gated)")))
    checks.append((
        "adversarial draft price rejects speculation",
        adv_decision["use"] is False,
        f"draft device de-rated {adv['draft_derate_factor']:g}x at "
        f"acceptance {adv['acceptance']:.2f} -> use={adv_decision['use']}"))
    return checks


# every request lifecycle stage a serve --trace file must cover: complete
# ("X") spans and instant ("i") markers emitted by the obs tracer
_TRACE_REQUIRED_SPANS = ("queued", "prefill", "decode", "burst", "sync")
_TRACE_REQUIRED_INSTANTS = ("first_token", "done")
# what a serve --watchdog --misprice trace must additionally carry: the
# detection and action instants of the re-pricing control loop
_TRACE_WATCHDOG_INSTANTS = ("drift_alert", "reprice")


def validate_trace(path: str, *, require_handoff: bool = False,
                   require_watchdog: bool = False
                   ) -> List[Tuple[str, bool, str]]:
    """Schema gate for a Chrome trace-event file written by
    ``serve --trace``: strict JSON, well-formed events, no unclosed
    spans, and at least one span per request-lifecycle stage."""
    def _reject(const):
        raise ValueError(f"non-finite JSON constant {const!r}")

    try:
        with open(path) as f:
            trace = json.load(f, parse_constant=_reject)
    except (OSError, ValueError) as e:
        return [("trace is strict JSON", False, f"{path}: {e}")]
    checks = [("trace is strict JSON", True, path)]

    events = trace.get("traceEvents") if isinstance(trace, dict) else None
    if not isinstance(events, list) or not events:
        checks.append(("trace has events", False,
                       "traceEvents missing, not a list, or empty"))
        return checks

    problems: List[str] = []
    spans: dict = {}
    instants: dict = {}
    for i, ev in enumerate(events):
        if not isinstance(ev, dict) or not isinstance(ev.get("ph"), str) \
                or not isinstance(ev.get("name"), str):
            problems.append(f"event {i}: malformed")
            continue
        ph, name = ev["ph"], ev["name"]
        if ph == "M":                    # process_name metadata
            continue
        if not _num(ev.get("ts")):
            problems.append(f"event {i} ({name}): ts not a finite number")
        if ph == "X":
            if not _num(ev.get("dur")) or ev["dur"] < 0:
                problems.append(f"event {i} ({name}): bad dur")
            spans[name] = spans.get(name, 0) + 1
        elif ph == "i":
            instants[name] = instants.get(name, 0) + 1
    checks.append(("trace events well-formed", not problems,
                   "; ".join(problems[:5]) if problems else
                   f"{len(events)} events, {sum(spans.values())} spans"))

    other = trace.get("otherData", {})
    n_open = other.get("n_open", 0) if isinstance(other, dict) else 0
    checks.append(("trace spans balanced", n_open == 0,
                   f"{n_open} unclosed spans at export"))

    required = list(_TRACE_REQUIRED_SPANS)
    if require_handoff:
        required.append("handoff")
    required_instants = list(_TRACE_REQUIRED_INSTANTS)
    if require_watchdog:
        required_instants.extend(_TRACE_WATCHDOG_INSTANTS)
    missing = ([f"span:{n}" for n in required if not spans.get(n)]
               + [f"instant:{n}" for n in required_instants
                  if not instants.get(n)])
    checks.append(("trace covers the request lifecycle", not missing,
                   "missing " + ", ".join(missing) if missing else
                   ", ".join(f"{n}x{spans[n]}" for n in required)))
    return checks


def absolute_baseline_metrics(fresh: dict) -> dict:
    """The raw-throughput figures a host baseline records/gates."""
    sat = saturation_load(fresh)
    out = {"continuous_tok_per_s": sat["continuous"]["tok_per_s"]}
    paged = fresh.get("paged")
    if isinstance(paged, dict) and isinstance(paged.get("paged"), dict):
        out["paged_tok_per_s"] = paged["paged"].get("tok_per_s")
    return out


def check_absolute(fresh: dict, *, threshold: float, baselines_dir: str,
                   record: bool) -> List[Tuple[str, bool, str]]:
    """Gate raw tok/s against this host's recorded baseline (recording it
    first when absent and ``record`` is set — a host's first run defines
    its floor, later runs must hold it)."""
    key = host_key()
    path = os.path.join(baselines_dir, f"{key}.json")
    metrics = absolute_baseline_metrics(fresh)
    if not os.path.exists(path):
        if record:
            os.makedirs(baselines_dir, exist_ok=True)
            with open(path, "w") as f:
                json.dump({"host_key": key, "metrics": metrics}, f, indent=2)
            return [("absolute tok/s vs host baseline", True,
                     f"no baseline for {key}; recorded {path}")]
        return [("absolute tok/s vs host baseline", True,
                 f"no baseline recorded for {key} "
                 f"(run with --record-absolute to create one); skipped")]
    with open(path) as f:
        recorded = json.load(f)
    checks: List[Tuple[str, bool, str]] = []
    for name, base_v in recorded.get("metrics", {}).items():
        fresh_v = metrics.get(name)
        if not _num(base_v) or not _num(fresh_v):
            checks.append((f"absolute {name} vs host baseline", False,
                           f"baseline {base_v!r} vs fresh {fresh_v!r}: "
                           f"not comparable"))
            continue
        floor = base_v * (1.0 - threshold)
        checks.append((
            f"absolute {name} vs host baseline ({key})",
            fresh_v >= floor,
            f"fresh {fresh_v:.1f} vs recorded {base_v:.1f} "
            f"(floor {floor:.1f} at {threshold:.0%} regression budget)"))
    return checks


def compare(baseline: dict, fresh: dict, *, threshold: float,
            absolute: bool, baselines_dir: str = DEFAULT_BASELINES_DIR,
            record_absolute: bool = False) -> List[Tuple[str, bool, str]]:
    """Returns [(check name, ok, detail), ...]."""
    checks: List[Tuple[str, bool, str]] = []
    base_l, fresh_l = saturation_load(baseline), saturation_load(fresh)

    base_s = base_l["speedup_tok_per_s"]
    fresh_s = fresh_l["speedup_tok_per_s"]
    floor = base_s * (1.0 - threshold)
    checks.append((
        "saturation speedup (continuous/static)",
        fresh_s >= floor,
        f"fresh {fresh_s:.2f}x vs baseline {base_s:.2f}x "
        f"(floor {floor:.2f}x at {threshold:.0%} regression budget)"))

    if absolute:
        checks.extend(check_absolute(fresh, threshold=threshold,
                                     baselines_dir=baselines_dir,
                                     record=record_absolute))

    checks.append(("all loads bit-identical to static",
                   all(l["bit_identical"] for l in fresh["loads"]),
                   f"{sum(l['bit_identical'] for l in fresh['loads'])}/"
                   f"{len(fresh['loads'])} loads"))
    dis = fresh.get("disaggregation")
    if dis is not None:
        checks.append(("disaggregated bit-identical to colocated",
                       bool(dis["bit_identical"]),
                       f"{dis['handoff']['n_handoffs']} handoffs, "
                       f"{dis['handoff']['bytes_moved']} bytes"))
    checks.extend(validate_paged(fresh))
    checks.extend(validate_prefix(fresh))
    checks.extend(validate_streaming(fresh))
    checks.extend(validate_observability(fresh))
    checks.extend(validate_adaptive(fresh))
    checks.extend(validate_multidevice(fresh, threshold=threshold))
    checks.extend(validate_speculative(fresh, threshold=threshold))
    return checks


def build_parser() -> argparse.ArgumentParser:
    """The regression gate's argument parser (module-level so tests and
    the docs consistency gate can introspect the flag set)."""
    ap = argparse.ArgumentParser(prog="benchmarks.check_regression",
                                 description=__doc__.splitlines()[0])
    ap.add_argument("--baseline", default="BENCH_serving.json",
                    help="committed benchmark results (the reference)")
    ap.add_argument("--fresh", default=None,
                    help="freshly generated benchmark results to gate "
                         "(required unless --trace is given)")
    ap.add_argument("--threshold", type=float, default=0.20,
                    help="allowed fractional regression (default 0.20)")
    ap.add_argument("--absolute", action="store_true",
                    help="also gate raw tok/s against this host's recorded "
                         "baseline (benchmarks/baselines/<host-key>.json)")
    ap.add_argument("--record-absolute", action="store_true",
                    help="with --absolute: record this host's baseline "
                         "when none exists yet (first run on a host "
                         "defines its floor)")
    ap.add_argument("--baselines-dir", default=DEFAULT_BASELINES_DIR,
                    help="directory of per-host absolute baselines")
    ap.add_argument("--trace", default=None,
                    help="gate a serve --trace Chrome trace-event file "
                         "(schema + lifecycle coverage)")
    ap.add_argument("--require-handoff", action="store_true",
                    help="with --trace: require the disaggregated "
                         "hand-off span")
    ap.add_argument("--require-watchdog", action="store_true",
                    help="with --trace: require the watchdog's "
                         "drift_alert + reprice instants (a serve "
                         "--watchdog --misprice run must have detected "
                         "and corrected the injected drift)")
    return ap


def main() -> None:
    ap = build_parser()
    args = ap.parse_args()
    if args.fresh is None and args.trace is None:
        ap.error("at least one of --fresh / --trace is required")

    checks: List[Tuple[str, bool, str]] = []
    if args.fresh is not None:
        with open(args.baseline) as f:
            baseline = json.load(f)
        with open(args.fresh) as f:
            fresh = json.load(f)
        checks.extend(compare(baseline, fresh,
                              threshold=args.threshold,
                              absolute=args.absolute,
                              baselines_dir=args.baselines_dir,
                              record_absolute=args.record_absolute))
    if args.trace is not None:
        checks.extend(validate_trace(
            args.trace, require_handoff=args.require_handoff,
            require_watchdog=args.require_watchdog))

    failed = False
    for name, ok, detail in checks:
        print(f"[check_regression] {'PASS' if ok else 'FAIL'}: "
              f"{name} — {detail}")
        failed |= not ok
    if failed:
        sys.exit(1)
    print("[check_regression] OK")


if __name__ == "__main__":
    main()

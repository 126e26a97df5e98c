"""Continuous-batching engine loop + serving metrics.

One jitted step (`decode_step_slots`) advances every active slot by one
token per iteration — prefilling slots consume their next prompt token,
decoding slots consume their previously sampled token — so prefill work
interleaves with the running decode batch instead of stalling it, and a
finished request's slot is refilled at the next completion boundary (no
inter-batch idle, no head-of-line blocking on the longest generation).

Token feeding is device-resident: the fused step selects each slot's next
token from an uploaded prompt buffer (while ``pos < prompt_len``) or from
the previous argmax, and scatters sampled tokens into a per-slot output
buffer.  The host never syncs per step — request completion is
deterministic in step count (greedy decoding, known lengths), so the loop
dispatches a *burst* of steps up to the next completion boundary and only
then pulls the finished slots' output rows.  This keeps per-step overhead
at dispatch cost, matching the static server's async decode chain.

The slot state + burst machinery lives in :class:`SlotEngine` so one
deployment can run several engines: :class:`EngineLoop` composes a single
SlotEngine (colocated serving), while
:class:`~repro.serving.disagg.DisaggregatedEngineLoop` composes two — a
prefill engine and a decode engine — and migrates slots between them
(`export_slot`/`import_slot`) at the phase boundary.

The open-loop scaffolding (arrival drain, idle fast-forward skew clock,
pending-aware burst capping, completion scan, metrics, streaming channel)
lives in :mod:`repro.serving.driver`; this module provides the loop hooks
the driver calls.  The loop is driven by a clock function so tests can run
it reproducibly; the CLI and benchmark use wall time, which is what the
open-loop arrival process (request.synthetic_workload) is offered against.
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models import transformer as T
from ..obs import Observability, default_clock
from .batcher import ContinuousBatcher
from .driver import (OpenLoopDriver, ServeMetrics, StreamDelta, TokenSink,
                     burst_size, sample_pools)
from .kv_pool import KVPool
from .request import Request, RequestState

__all__ = ["EngineLoop", "ServeMetrics", "SlotEngine", "StreamDelta",
           "TokenSink"]


# ---- shared trace instrumentation (colocated + disaggregated loops) -------

def wire_pool_events(pool: KVPool, tracer) -> None:
    """Surface the pool's block-lease events as per-request trace instants
    (``kv_alloc``/``kv_free`` on the request's own tid)."""
    if not tracer.enabled:
        return

    def on_event(kind, rid, n_blocks):
        tracer.instant("kv_" + kind, track="requests", tid=rid, cat="kv",
                       args={"blocks": n_blocks})

    pool.on_event = on_event


def trace_admission(obs, batcher, decision, n_active: int) -> None:
    """Close each admitted request's ``queued`` span (arrival -> admission)
    with the priced cost the batcher admitted it against, and mark drops."""
    tracer = obs.tracer
    if not tracer.enabled:
        return
    if decision.admitted:
        priced = batcher.priced_step_s(n_active)
        for req in decision.admitted:
            req.priced_step_s = priced
            tracer.span("queued", req.arrival, req.t_admitted,
                        track="requests", tid=req.rid, cat="request",
                        args={"priced_step_s": priced,
                              "token_budget": batcher.token_budget,
                              "phase": batcher.phase,
                              "blocks": batcher.pool.blocks_needed(
                                  req.total_tokens),
                              "shared_tokens": req.shared_tokens})
    for req in decision.dropped:
        tracer.instant("dropped", track="requests", tid=req.rid,
                       cat="request",
                       args={"reason": "deadline-or-never-fits"})


def trace_phase_flip(tracer, req, now: float) -> None:
    """Prefill span: admission -> the first decode burst's dispatch."""
    if tracer.enabled:
        tracer.span("prefill", req.t_admitted, now, track="requests",
                    tid=req.rid, cat="request",
                    args={"prompt_len": req.prompt_len})


def trace_completion(tracer, req) -> None:
    """Decode span (dispatch -> done, priced vs observed per-step cost) +
    the ``done`` instant."""
    if not tracer.enabled:
        return
    if req.t_first_dispatch is not None and req.t_done is not None:
        dur = req.t_done - req.t_first_dispatch
        steps = max(req.max_new_tokens - 1, 1)
        tracer.span("decode", req.t_first_dispatch, req.t_done,
                    track="requests", tid=req.rid, cat="request",
                    args={"priced_step_s": req.priced_step_s,
                          "observed_step_s": dur / steps,
                          "tokens": len(req.output)})
    tracer.instant("done", track="requests", tid=req.rid, cat="request",
                   t=req.t_done,
                   args={"latency_s": (None if req.t_done is None
                                       else req.t_done - req.arrival)})


def count_burst(obs, eng: "SlotEngine", burst: int,
                mask: np.ndarray) -> Dict[str, int]:
    """Add a burst's prompt and output tokens (before it is dispatched) to
    the registry's engine counters; returns them as the ``burst`` span's
    args."""
    prefill_tokens, prefill_steps, decode_tokens = eng.token_split(burst,
                                                                   mask)
    reg = obs.registry
    reg.counter("engine_prefill_tokens").inc(prefill_tokens)
    reg.counter("engine_prefill_steps").inc(prefill_steps)
    reg.counter("engine_decode_tokens").inc(decode_tokens)
    return {"steps": burst, "n_active": int(mask.sum()),
            "prefill_tokens": prefill_tokens,
            "prefill_steps": prefill_steps, "decode_tokens": decode_tokens}


def _fused_step(step_fn, params, cfg, cache, prompts, plens, last_tok,
                out_buf, active):
    """Device-side feed + step + sample + output scatter.

    prompts: (B, P_max) int32; plens/last_tok: (B,) int32; out_buf:
    (B, G_max) int32; active: (B,) bool.  cache["pos"] counts tokens fed
    per slot, so pos < plen selects the prompt, else the last sample.
    ``step_fn`` is the layout's slot step (`decode_step_slots` dense,
    `decode_step_slots_paged` paged) — same contract, bit-identical
    outputs."""
    b = prompts.shape[0]
    pos = cache["pos"]
    with jax.named_scope("feed_sample"):
        prompt_tok = prompts[jnp.arange(b),
                             jnp.minimum(pos, prompts.shape[1] - 1)]
        tok = jnp.where(pos < plens, prompt_tok, last_tok)
    logits, cache = step_fn(params, cfg, cache, tok[:, None], active)
    with jax.named_scope("feed_sample"):
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        # the sample is output index (pos - plen + 1); valid once the final
        # prompt token has been fed (same schedule as the static replay
        # path)
        idx = pos - plens + 1
        write = active & (idx >= 0) & (idx < out_buf.shape[1])
        safe_idx = jnp.clip(idx, 0, out_buf.shape[1] - 1)
        row = out_buf[jnp.arange(b), safe_idx]
        out_buf = out_buf.at[jnp.arange(b), safe_idx].set(
            jnp.where(write, nxt, row))
        last_tok = jnp.where(active, nxt, last_tok)
    return cache, last_tok, out_buf


class SlotEngine:
    """Device-resident slot state + jitted burst machinery for one engine.

    Owns the slot cache, the prompt/output buffers, the per-slot step
    schedule and the compiled burst buckets.  The per-slot math is exactly
    `decode_step`'s, so outputs are bit-identical whether a request lives
    its whole life in one SlotEngine (colocated) or is exported from a
    prefill engine and imported into a decode engine mid-flight.

    Invariants: under the paged layout the pool's lease order IS the block
    table — :meth:`bind` uploads ``KVPool.block_table`` verbatim, so
    logical block ``j`` of a slot always resolves through lease entry
    ``j`` (prefix sharing changes *which* physical pages a lease maps, not
    this contract).  The engine writes KV only at each slot's current
    position, so pages behind ``pos`` are immutable — what makes published
    prefix pages safe to share — and pending COW copies are materialized
    in :meth:`bind` before the slot's first write.  The engine never reads
    the host clock: burst timing is the caller's concern (injected
    clocks), and :meth:`sync` is a pure wait that cannot change outputs.

    ``device`` pins the engine to one physical device: params, the KV
    arenas and every per-slot buffer are committed there, so jitted
    bursts run on that device and two SlotEngines on distinct devices
    execute concurrently (the disaggregated loop's throughput win).
    ``device=None`` keeps the legacy behaviour — everything on jax's
    default device, nothing committed.
    """

    # largest scanned burst compiled; bounds compile count (power-of-two
    # buckets 1..MAX_BUCKET)
    MAX_BUCKET = 32

    def __init__(self, cfg: T.ModelConfig, params, pool: KVPool, *,
                 kv_layout: str = "dense", name: str = "engine",
                 device=None):
        if kv_layout not in ("dense", "paged"):
            raise ValueError(f"unknown kv_layout {kv_layout!r}")
        self.cfg = cfg
        self.device = device
        self.params = (params if device is None
                       else jax.device_put(params, device))
        self.pool = pool
        self.kv_layout = kv_layout
        self.name = name                 # labels this engine's trace track
        n_slots = pool.n_slots
        if kv_layout == "paged":
            self.cache = T.init_slot_cache_paged(
                cfg, n_slots, pool.max_seq, block_size=pool.block_size,
                total_blocks=pool.total_blocks)
            self._step_fn = functools.partial(T.decode_step_slots_paged,
                                              max_seq=pool.max_seq)
        else:
            self.cache = T.init_slot_cache(cfg, n_slots, pool.max_seq)
            self._step_fn = T.decode_step_slots
        if device is not None:
            self.cache = jax.device_put(self.cache, device)
        self.max_prompt = pool.max_seq
        self.max_gen = pool.max_seq
        self._prompts = jnp.zeros((n_slots, self.max_prompt), jnp.int32)
        self._plens = jnp.zeros((n_slots,), jnp.int32)
        self._last_tok = jnp.zeros((n_slots,), jnp.int32)
        self._out_buf = jnp.zeros((n_slots, self.max_gen), jnp.int32)
        if device is not None:
            (self._prompts, self._plens, self._last_tok, self._out_buf) = \
                jax.device_put((self._prompts, self._plens, self._last_tok,
                                self._out_buf), device)
        self._burst_fns: Dict[int, Callable] = {}
        self.slots: List[Optional[Request]] = [None] * n_slots
        # host-side schedule state: active steps done / total per slot, the
        # prompt steps each slot has left, plus the dispatch mask (bind
        # sets, release clears)
        self.steps_done = np.zeros((n_slots,), np.int64)
        self.steps_total = np.zeros((n_slots,), np.int64)
        self.prompt_left = np.zeros((n_slots,), np.int64)
        self.active = np.zeros((n_slots,), bool)

    def _burst_fn(self, k: int) -> Callable:
        """Jitted scan of k fused steps — one dispatch per bucket instead of
        per token, so burst cost is dominated by device compute."""
        fn = self._burst_fns.get(k)
        if fn is None:
            cfg = self.cfg
            step_fn = self._step_fn

            def burst(p, c, pr, pl, lt, ob, a):
                def body(carry, _):
                    c, lt, ob = carry
                    return (_fused_step(step_fn, p, cfg, c, pr, pl, lt, ob,
                                        a), None)
                (c, lt, ob), _ = jax.lax.scan(body, (c, lt, ob), None,
                                              length=k)
                return c, lt, ob

            fn = jax.jit(burst, donate_argnums=(1, 4, 5))
            self._burst_fns[k] = fn
        return fn

    def warmup(self) -> None:
        """Compile every burst bucket.  An all-inactive step leaves
        positions, live KV state and buffers bit-identical (the paged
        layout's trash page is the only thing written, and it is never
        read), so this is state-neutral."""
        idle = jnp.zeros((self.pool.n_slots,), bool)
        b = 1
        while b <= self.MAX_BUCKET:
            (self.cache, self._last_tok, self._out_buf) = self._burst_fn(b)(
                self.params, self.cache, self._prompts, self._plens,
                self._last_tok, self._out_buf, idle)
            b *= 2

    @property
    def n_active(self) -> int:
        return sum(r is not None for r in self.slots)

    def active_requests(self):
        return (r for r in self.slots if r is not None)

    def copy_page(self, src: int, dst: int) -> None:
        """Copy-on-write: duplicate one physical KV page (every attention
        layer's K and V arena) so a writer diverging inside a shared tail
        page gets a private copy before its first write."""
        if self.kv_layout != "paged":
            raise ValueError("copy_page needs the paged KV layout")

        def one(c, stacked):
            if isinstance(c, dict) and "k" in c:
                if stacked:
                    return jax.tree.map(
                        lambda a: a.at[:, dst].set(a[:, src]), c)
                return jax.tree.map(lambda a: a.at[dst].set(a[src]), c)
            return c

        blocks, rem = self.cache["layers"]
        cache = dict(self.cache)
        cache["layers"] = (tuple(one(c, True) for c in blocks),
                           tuple(one(c, False) for c in rem))
        self.cache = cache

    def bind(self, req: Request, *, steps_total: int,
             start_pos: int = 0) -> None:
        """Upload the request's prompt into its slot and reset per-request
        state (position counter + recurrent SSM states; attention KV rows
        need no clearing — per-slot position masks hide stale entries).
        ``steps_total`` is the number of engine steps this request runs on
        THIS engine (plen + gen - 1 colocated; plen for a prefill phase —
        each minus the shared prefix under prefix sharing).

        ``start_pos`` > 0 binds at an offset (prefix sharing): positions
        ``[0, start_pos)`` are already served by shared pages in the
        slot's block table, so the first fed token is
        ``prompt[start_pos]`` and prefill for the shared prefix is
        skipped.  Pending COW page copies are materialized here, before
        the slot's first write."""
        if start_pos and self.kv_layout != "paged":
            raise ValueError("bind at an offset (prefix sharing) requires "
                             "the paged KV layout")
        s = req.slot
        row = np.zeros((self.max_prompt,), np.int32)
        row[:req.prompt_len] = req.prompt
        self._prompts = self._prompts.at[s].set(jnp.asarray(row))
        self._plens = self._plens.at[s].set(req.prompt_len)
        if self.kv_layout == "paged":
            for src, dst in self.pool.consume_cow(req.rid):
                self.copy_page(src, dst)
            # upload the slot's logical->physical page map (lease order IS
            # the block table)
            table = self.pool.block_table(
                req.rid, pad_to=self.cache["block_tables"].shape[1])
            cache = dict(self.cache)
            cache["block_tables"] = cache["block_tables"].at[s].set(
                jnp.asarray(table))
            self.cache = cache
        self.cache = T.reset_slot_state(self.cfg, self.cache, s)
        if start_pos:
            cache = dict(self.cache)
            cache["pos"] = cache["pos"].at[s].set(start_pos)
            self.cache = cache
        self.slots[s] = req
        self.steps_done[s] = 0
        self.steps_total[s] = steps_total
        self.prompt_left[s] = req.prompt_len - start_pos
        self.active[s] = True

    def token_split(self, burst: int, mask: np.ndarray) -> tuple:
        """``(prefill_tokens, prefill_steps, decode_tokens)`` of a burst of
        ``burst`` steps over the ``mask`` slots: a slot with ``r`` prompt
        steps left feeds ``min(r, burst)`` prompt tokens and the rest of
        its steps output tokens; ``prefill_steps`` is how many of the
        burst's steps carry any prompt token."""
        fed = np.minimum(self.prompt_left[mask], burst)
        prefill = int(fed.sum())
        return (prefill, int(fed.max(initial=0)),
                burst * int(mask.sum()) - prefill)

    def dispatch(self, burst: int, active_np: np.ndarray) -> None:
        """Dispatch `burst` fused steps over the active slots (bucketed
        power-of-two scans, no host sync).

        The mask is copied first: on the CPU a suitably aligned numpy
        array becomes a device array without a copy, so a caller flipping
        ``active_np`` (bind, release) before the queued bursts execute
        would change which slots they step."""
        active_dev = jnp.asarray(active_np.copy())
        k = burst
        while k > 0:
            b = min(self.MAX_BUCKET, 1 << (k.bit_length() - 1))
            (self.cache, self._last_tok, self._out_buf) = self._burst_fn(b)(
                self.params, self.cache, self._prompts, self._plens,
                self._last_tok, self._out_buf, active_dev)
            k -= b
        self.steps_done[active_np] += burst
        self.prompt_left[active_np] = np.maximum(
            self.prompt_left[active_np] - burst, 0)
        for s, req in enumerate(self.slots):
            if req is not None and active_np[s]:
                self.pool.note_write(req.rid, burst)

    def sync(self) -> None:
        """Block until every dispatched burst has executed.  Waits only —
        nothing is read or written — so outputs are bit-identical with or
        without the sync; the telemetry feedback path calls this so burst
        timings measure device wall time, not enqueue time."""
        jax.block_until_ready((self.cache, self._last_tok, self._out_buf))

    def pull_output(self, slot: int) -> np.ndarray:
        """Sync and read one slot's sampled-token row."""
        return np.asarray(self._out_buf[slot])

    def pull_outputs(self) -> np.ndarray:
        """Sync and read the whole (n_slots, max_gen) output buffer — one
        host sync per burst boundary, shared by every streaming slot."""
        return np.asarray(self._out_buf)

    def release(self, req: Request) -> None:
        """Free the request's slot + pool lease on this engine."""
        self.pool.free(req.rid)
        self.slots[req.slot] = None
        self.active[req.slot] = False

    # ---- slot hand-off (phase disaggregation) ----------------------------
    def _layer_take(self, take_slot, take_arena):
        """Map the layout-appropriate extractor over each layer cache:
        paged attention layers carry block arenas (page-granular take),
        everything else is slot-major (slot-granular take)."""
        blocks, rem = self.cache["layers"]

        def one(c, stacked):
            if (self.kv_layout == "paged" and isinstance(c, dict)
                    and "k" in c):
                return jax.tree.map(lambda a: take_arena(a, stacked), c)
            take = take_slot[1] if stacked else take_slot[0]
            return jax.tree.map(take, c)

        return (tuple(one(c, True) for c in blocks),
                tuple(one(c, False) for c in rem))

    def export_slot(self, s: int) -> Dict:
        """Snapshot every per-slot tensor a request needs to resume on
        another engine: KV state / recurrent states / position, the
        per-slot cross-attention features (vision/enc-dec caches), the
        prompt row + feed state, and the sampled-output row.  This is the
        payload the placement analyzer prices with the offload-overhead
        model.

        Dense layout ships the slot's whole ``max_seq`` KV rows; the paged
        layout ships only the pages that actually hold written tokens
        (``kv_tokens`` of them), so the hand-off payload scales with the
        prompt, not the reservation."""
        # slot-invariant entries (no slot axis) must be COPIED, not
        # aliased: the async hand-off holds snapshots across bursts, and
        # the burst donates the engine's buffers — an alias would be a
        # deleted buffer by adoption time.  Slices already allocate fresh
        # buffers; only the passthrough branches alias.
        snap = lambda a: a.copy() if hasattr(a, "ndim") else a
        take_r = lambda a: a[s] if getattr(a, "ndim", 0) >= 1 else snap(a)
        take_b = lambda a: a[:, s] if getattr(a, "ndim", 0) >= 2 else snap(a)
        state = {
            "layout": self.kv_layout,
            "pos": self.cache["pos"][s],
            "cross": None,
            "prompt": self._prompts[s],
            "plen": self._plens[s],
            "last_tok": self._last_tok[s],
            "out_row": self._out_buf[s],
        }
        cross = self.cache.get("cross")
        if cross is not None:
            state["cross"] = cross[s]
        if self.kv_layout == "paged":
            req = self.slots[s]
            lease = self.pool.lease(req.rid)
            n_used = math.ceil(lease.written_tokens / self.pool.block_size)
            phys = jnp.asarray(np.asarray(lease.blocks[:n_used], np.int32))
            take_arena = lambda a, stacked: (a[:, phys] if stacked
                                             else a[phys])
            state["kv_tokens"] = lease.written_tokens
        else:
            take_arena = None
        state["blocks"], state["rem"] = self._layer_take(
            (take_r, take_b), take_arena)
        return state

    def import_slot(self, s: int, state: Dict, *,
                    dest_blocks: Optional[List[int]] = None,
                    skip_blocks: int = 0) -> None:
        """Install an exported slot snapshot into slot ``s`` (bit-exact:
        the imported request decodes the same tokens it would have
        produced had it stayed on the exporting engine).

        The cache is rebuilt by copy-and-update of ``self.cache`` so every
        key the layout carries survives the migration (a literal rebuild
        used to silently drop unknown keys), and per-slot cross-attention
        rows are migrated rather than shared.  Paged layout: the shipped
        pages land in this engine's arena at ``dest_blocks`` (the slot's
        new lease, logical order) and the slot's block table is rebuilt
        from that lease — physical page ids never migrate across engines.
        ``skip_blocks`` leading logical pages are NOT landed (prefix
        sharing: the destination lease already maps them onto shared
        pages holding bit-identical content, which must not be written).
        """
        if self.device is not None:
            # commit the snapshot here before any at[].set — mixing arrays
            # committed to different devices in one op is an error, and a
            # snapshot that already finished its async device_put makes
            # this a no-op
            state = state_to_device(state, self.device)
        layout = state.get("layout", "dense")
        if layout != self.kv_layout:
            raise ValueError(
                f"exported slot uses the {layout!r} KV layout but the "
                f"importing engine runs {self.kv_layout!r} — phase engines "
                f"must share a layout for exact migration")
        cross = self.cache.get("cross")
        if cross is not None and state.get("cross") is None:
            raise ValueError(
                "cross-attention cache present on the importing engine "
                "but the exported slot carries no cross row — the "
                "exporting engine was built for a different config")
        if cross is None and state.get("cross") is not None:
            raise ValueError(
                "exported slot carries a cross-attention row but the "
                "importing engine has no cross cache — silently dropping "
                "it would corrupt the migrated request (engines built for "
                "different configs)")
        set_b = lambda a, v: (a.at[:, s].set(v)
                              if getattr(a, "ndim", 0) >= 2 else a)
        set_r = lambda a, v: (a.at[s].set(v)
                              if getattr(a, "ndim", 0) >= 1 else a)
        if self.kv_layout == "paged":
            if dest_blocks is None:
                raise ValueError("paged import needs dest_blocks (the "
                                 "slot's lease on this engine)")
            n_used = math.ceil(int(state["kv_tokens"])
                               / self.pool.block_size)
            if n_used > len(dest_blocks):
                raise ValueError(
                    f"snapshot carries {n_used} written pages but the "
                    f"destination lease holds {len(dest_blocks)} blocks")
            skip = min(skip_blocks, n_used)
            phys = jnp.asarray(np.asarray(dest_blocks[skip:n_used],
                                          np.int32))
            set_arena = {
                True: lambda a, v: a.at[:, phys].set(v[:, skip:n_used]),
                False: lambda a, v: a.at[phys].set(v[skip:n_used]),
            }
        else:
            set_arena = None

        def set_layer(c, v, stacked):
            if (self.kv_layout == "paged" and isinstance(c, dict)
                    and "k" in c):
                return jax.tree.map(set_arena[stacked], c, v)
            return jax.tree.map(set_b if stacked else set_r, c, v)

        blocks, rem = self.cache["layers"]
        cache = dict(self.cache)
        cache["layers"] = (
            tuple(set_layer(c, v, True)
                  for c, v in zip(blocks, state["blocks"])),
            tuple(set_layer(c, v, False)
                  for c, v in zip(rem, state["rem"])))
        cache["pos"] = self.cache["pos"].at[s].set(state["pos"])
        if cross is not None:
            cache["cross"] = cross.at[s].set(state["cross"])
        if self.kv_layout == "paged":
            table = np.zeros((cache["block_tables"].shape[1],), np.int32)
            table[:len(dest_blocks)] = dest_blocks
            cache["block_tables"] = cache["block_tables"].at[s].set(
                jnp.asarray(table))
        self.cache = cache
        self._prompts = self._prompts.at[s].set(state["prompt"])
        self._plens = self._plens.at[s].set(state["plen"])
        self._last_tok = self._last_tok.at[s].set(state["last_tok"])
        self._out_buf = self._out_buf.at[s].set(state["out_row"])

    def adopt(self, req: Request, state: Dict, *, steps_total: int,
              skip_blocks: int = 0) -> None:
        """Take over a migrated request: install its snapshot into the slot
        the pool already assigned (``req.slot``) and reset the per-slot
        schedule for the steps this engine owes.  ``skip_blocks`` passes
        through to :meth:`import_slot` (prefix-shared leading pages)."""
        s = req.slot
        dest = (self.pool.lease(req.rid).blocks
                if self.kv_layout == "paged" else None)
        self.import_slot(s, state, dest_blocks=dest,
                         skip_blocks=skip_blocks)
        self.slots[s] = req
        self.steps_done[s] = 0
        self.steps_total[s] = steps_total
        self.prompt_left[s] = max(req.prompt_len - req.n_fed, 0)
        self.active[s] = True

    @staticmethod
    def state_nbytes(state: Dict) -> int:
        """Byte size of an exported slot snapshot (the hand-off payload).
        Non-array metadata (layout tag, written-token count) is free."""
        return sum(int(leaf.nbytes) for leaf in jax.tree.leaves(state)
                   if hasattr(leaf, "nbytes"))


def state_to_device(state: Dict, device) -> Dict:
    """Commit every array leaf of an exported slot snapshot to ``device``
    (non-array metadata — layout tag, token counts — passes through).

    ``jax.device_put`` *dispatches* the copy and returns immediately, so
    calling this right after :meth:`SlotEngine.export_slot` starts the
    cross-device transfer in the background: the exporting engine can keep
    computing while the bytes drain, and the adopting engine blocks only
    on whatever is still in flight (the async hand-off).  Re-committing an
    array already on ``device`` is a no-op, so the defensive call inside
    :meth:`SlotEngine.import_slot` costs nothing on the fast path."""
    return jax.tree.map(
        lambda x: (jax.device_put(x, device)
                   if isinstance(x, jax.Array) else x), state)


def snapshot_ready(state: Dict) -> bool:
    """True when every array in a dispatched snapshot has resolved on its
    destination device (non-blocking — the overlap probe)."""
    return all(leaf.is_ready() for leaf in jax.tree.leaves(state)
               if isinstance(leaf, jax.Array))


def snapshot_wait(state: Dict) -> None:
    """Block until a dispatched snapshot's transfer completes (the stall
    the hand-off ledger charges to the adopting engine)."""
    for leaf in jax.tree.leaves(state):
        if isinstance(leaf, jax.Array):
            leaf.block_until_ready()


class EngineLoop:
    """Colocated serving: one SlotEngine runs both phases of every request.

    The open-loop scaffolding lives in :class:`~repro.serving.driver.
    OpenLoopDriver`; this class provides the colocated hook implementations
    (admission binds both phases onto the one engine, completion pulls the
    whole output row).
    """

    def __init__(self, cfg: T.ModelConfig, params, *, n_slots: int,
                 max_seq: int, block_size: int = 16,
                 total_blocks: Optional[int] = None,
                 kv_layout: str = "paged",
                 device_name: str = "tpu-v5e",
                 device_model=None,
                 step_slo_s: Optional[float] = None,
                 token_budget: Optional[int] = None,
                 prefix_sharing: bool = False,
                 obs: Optional[Observability] = None):
        if prefix_sharing:
            if kv_layout != "paged":
                raise ValueError("prefix sharing maps physical pages — it "
                                 "requires kv_layout='paged'")
            if any(t != "attn" for t in cfg.layer_types()):
                raise ValueError(
                    "prefix sharing requires an all-attention config: "
                    "recurrent/cross layer state is slot-local and cannot "
                    "be reconstructed from shared KV pages")
        self.cfg = cfg
        self.kv_layout = kv_layout
        self.prefix_sharing = prefix_sharing
        self.obs = obs if obs is not None else Observability()
        self.pool = KVPool(n_slots, max_seq, block_size=block_size,
                           total_blocks=total_blocks,
                           prefix_sharing=prefix_sharing)
        self.batcher = ContinuousBatcher(
            cfg, self.pool, device_name=device_name,
            device_model=device_model, step_slo_s=step_slo_s,
            token_budget=token_budget)
        self.engine = SlotEngine(cfg, params, self.pool,
                                 kv_layout=kv_layout, name="colocated")
        wire_pool_events(self.pool, self.obs.tracer)

    def warmup(self) -> None:
        self.engine.warmup()

    @property
    def n_active(self) -> int:
        return self.engine.n_active

    @property
    def batchers(self):
        """Admission batchers, uniform with the disaggregated loop's."""
        return (self.batcher,)

    def run(self, requests: List[Request], *,
            now_fn: Callable[[], float] = default_clock,
            max_steps: Optional[int] = None,
            on_delta: Optional[Callable[[StreamDelta], None]] = None
            ) -> ServeMetrics:
        """Serve `requests` (an arrival-stamped open-loop stream) to
        completion via the shared open-loop driver.  Returns the aggregate
        metrics; ``on_delta`` streams newly readable tokens at burst
        boundaries."""
        return OpenLoopDriver(self).run(requests, now_fn=now_fn,
                                        max_steps=max_steps,
                                        on_delta=on_delta)

    # ---- OpenLoopDriver hooks --------------------------------------------
    def start_run(self) -> None:
        pass                             # all per-run state lives on engines

    def in_flight(self) -> bool:
        return self.engine.n_active > 0

    def runnable(self) -> bool:
        return self.engine.n_active > 0

    def backlogged(self, queue: List[Request]) -> bool:
        return False                     # only pending arrivals throttle

    def admit(self, queue: List[Request], now: float,
              metrics: ServeMetrics) -> None:
        track = f"engine:{self.engine.name}"
        with self.obs.tracer.scope("admit", track=track,
                                   cat="engine") as note:
            decision = self.batcher.admit(queue, self.engine.n_active, now)
            metrics.drop(len(decision.dropped))
            for req in decision.admitted:
                # greedy decoding with known lengths: completion is
                # deterministic — the final sample lands after
                # plen + gen - 1 active steps (minus any prefix-shared
                # tokens, whose prefill is skipped by binding at an offset)
                shared = self.pool.shared_tokens(req.rid)
                req.shared_tokens = shared
                self.engine.bind(
                    req, start_pos=shared,
                    steps_total=(req.prompt_len - shared
                                 + req.max_new_tokens - 1))
            note(admitted=len(decision.admitted))
        trace_admission(self.obs, self.batcher, decision,
                        self.engine.n_active)
        return decision

    def dispatch(self, throttle: bool, budget: Optional[int]) -> int:
        # burst: dispatch steps to the next completion boundary without
        # any host sync; the device chain pipelines behind dispatch
        eng = self.engine
        remaining = eng.steps_total - eng.steps_done
        burst = burst_size(int(remaining[eng.active].min()),
                           throttle=throttle, budget=budget)
        if burst <= 0:
            return 0
        tracer, fb, wd = self.obs.tracer, self.obs.feedback, self.obs.watchdog
        n_active = eng.n_active
        timed = fb is not None or wd is not None
        args = count_burst(self.obs, eng, burst, eng.active)
        with tracer.scope("burst", track=f"engine:{eng.name}", cat="engine",
                          args={**args, "synced": timed}):
            t0 = tracer.now() if timed else 0.0
            eng.dispatch(burst, eng.active)
            if timed:
                # telemetry feedback / the watchdog want device wall time
                # per step, so wait for the burst (a pure wait: outputs
                # stay bit-identical)
                eng.sync()
                dt = tracer.now() - t0
                if fb is not None:
                    fb.observe_burst(n_active, burst, dt)
                if wd is not None:
                    wd.observe_burst(
                        eng.name, self.batcher.phase, n_tokens=n_active,
                        steps=burst, elapsed_s=dt,
                        priced_step_s=self.batcher.priced_step_s(n_active))
        return burst

    def on_drift(self, alert, watchdog) -> None:
        """Watchdog action leg: re-price admission from observed telemetry.

        Installs the best pricing the watchdog can offer — a fitted
        latency(batch) curve once >= 2 batch sizes were observed, the
        analytic shape scaled by the observed divergence ratio otherwise —
        and refits the token budget against the stored step SLO.  Pure
        admission policy: per-request greedy outputs are schedule-
        independent, so re-pricing never changes what is generated.
        """
        fn, source = watchdog.step_time_fn(
            alert.engine, alert.phase, self.batcher.analytic_step_s)
        if source == "analytic":
            return                       # nothing observed: keep the model
        detail = self.batcher.reprice(fn, source=source)
        watchdog.note_reprice(alert, detail)

    def sample(self, metrics: ServeMetrics) -> None:
        occ, util = sample_pools((self.pool,))
        metrics.occupancy.append(occ)
        metrics.utilization.append(util)

    def scan(self, clock: Callable[[], float], metrics: ServeMetrics,
             sink: TokenSink) -> None:
        eng = self.engine
        tracer = self.obs.tracer
        now = clock()
        for s, req in enumerate(eng.slots):
            if req is None:
                continue
            # shared-prefix tokens count as fed: the KV exists and the
            # feed pointer started past them
            req.n_fed = int(eng.steps_done[s]) + req.shared_tokens
            if (req.state is RequestState.PREFILL
                    and req.n_fed >= req.prompt_len):
                # the burst containing the first sample has been dispatched
                # (host-visible stamping happens in the sink)
                req.state = RequestState.DECODE
                req.t_first_dispatch = now
                trace_phase_flip(tracer, req, now)
        sink.drain(eng, clock)           # streaming: burst-boundary sync
        for s, req in enumerate(eng.slots):
            if req is None:
                continue
            if eng.steps_done[s] >= eng.steps_total[s]:
                # completion boundary: sync and pull this slot's tokens
                with tracer.scope("sync", track=f"engine:{eng.name}",
                                  cat="engine", args={"kind": "completion"}):
                    row = eng.pull_output(s)
                req.state = RequestState.DONE
                req.t_done = clock()
                sink.finish(req, row[:req.max_new_tokens], req.t_done)
                eng.release(req)
                metrics.observe(req)
                trace_completion(tracer, req)

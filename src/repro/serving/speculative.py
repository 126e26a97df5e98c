"""Draft-model speculative decoding on the paged serving path.

The paper's trade-off move — price the same work on a cheap and an
expensive engine and offload by measured trade-off — applied to the
decode hot path: a small *draft* model proposes k tokens per slot, the
*target* verifies all k in ONE multi-token step over its paged KV cache
(`decode_multi_step_slots_paged`), and only the accepted prefix commits.

Round math (greedy verification, per slot, from committed position pos0
whose chain head token is ``last_tok``):

* the draft autoregressively proposes d_1..d_k (k+1 sequential draft
  steps — the extra feed writes draft KV for d_k so a fully-accepted
  round leaves the draft cache one rollback away from the new head);
* the target feeds [last_tok, d_1..d_k] at positions pos0..pos0+k in one
  step, producing greedy continuations g_1..g_{k+1} where g_j conditions
  on the window prefix up to input j;
* accepted a = longest prefix with d_i == g_i; committed
  c = min(a + 1, rem) — the +1 is the target's own token (the correction
  after a rejection, the bonus token after full acceptance);
* pos += c, the new chain head is g_c, and both caches roll their
  position back to the committed prefix.  Positions pos0+c..pos0+k hold
  *stale* K/V from the rejected tail — harmless, because every later
  feed starts at the committed position and rewrites forward before
  attention ever reads them (attention masks kv_slot <= query position).

Every committed token is a target greedy continuation of the same
committed chain plain decode walks, so outputs are BIT-IDENTICAL to
non-speculative decode by construction; expected committed tokens per
round is sum_{i=1..k} alpha^i + 1 for per-token acceptance rate alpha
(`core.cost_model.expected_tokens_per_round`).

Safety gate: a slot only enters a round while ``rem >= k`` (rem = steps
still owed), which pins the verify window's top position pos0+k inside
the slot's page lease (pos + rem == total_tokens - 1 <= max_seq - 1).
The pool's block table pads with physical page 0, so an overflow write
would corrupt another request's pages — the gate makes that impossible
instead of masking it.  Tail slots (rem < k) finish via plain bursts.

The draft engine needs no KVPool: it is provisioned dense-equivalently
(slot s statically owns pages [s*bps, (s+1)*bps)), its cache is a
throwaway mirror of the committed chain, and a rejection rollback is a
position move.  Draft slot indices equal target slot indices, so the
loops' active masks line up.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models import transformer as T
from .engine_loop import EngineLoop

DEFAULT_DRAFT_ARCH = "qwen2_1_5b"
DEFAULT_DRAFT_K = 2
# acceptance prior used before any measurement exists for a (draft,
# target) pair — optimistic enough to let speculation engage so the
# online tracker can measure the real rate and veto it
DEFAULT_ACCEPTANCE_PRIOR = 0.8


def validate_speculation(target_cfg, draft_cfg, *, kv_layout: str,
                         prefix_sharing: bool) -> None:
    """Raise on serving configurations speculation cannot run under."""
    if kv_layout != "paged":
        raise ValueError("speculative decoding verifies k+1 positions "
                         "against the block-paged cache — it requires "
                         "kv_layout='paged'")
    if prefix_sharing:
        raise ValueError(
            "speculative decoding is incompatible with prefix sharing: "
            "shared-offset binds break the draft's committed-chain replay "
            "and a rejected window must never land in refcounted pages")
    for role, cfg in (("target", target_cfg), ("draft", draft_cfg)):
        if any(t != "attn" for t in cfg.layer_types()):
            raise ValueError(
                f"speculative decoding requires an all-attention {role} "
                f"config ({cfg.name!r}): recurrent/SSM state has no "
                f"multi-token verify or rollback")
    if target_cfg.vocab != draft_cfg.vocab:
        raise ValueError(
            f"draft vocab {draft_cfg.vocab} != target vocab "
            f"{target_cfg.vocab}: proposals would not be target tokens")


@dataclasses.dataclass
class SpecPlan:
    """Everything the serving loop needs to speculate: the draft model,
    the depth, and how the decision was made (forced CLI depth vs the
    trade-off analyzer's `choose_speculation`)."""
    draft_cfg: T.ModelConfig
    draft_params: object
    k: int = DEFAULT_DRAFT_K
    draft_name: str = DEFAULT_DRAFT_ARCH
    decision: object = None      # placement.SpeculationDecision | None
    forced: bool = False         # --draft-k: speculate regardless of price
    tracker: object = None       # obs.watchdog.AcceptanceTracker | None


class DraftEngine:
    """The draft model's paged slot cache, dense-equivalently provisioned.

    Mirrors the target engine's committed chain per slot: ``sync_to``
    replays chain tokens the draft has not seen (prompt tokens from the
    target's prompt buffer, committed generations from its output buffer
    — both device-resident, so catch-up never syncs the host) in
    power-of-two multi-token chunks; ``propose`` runs k+1 sequential
    draft steps; ``rollback`` moves positions back to the committed
    prefix after verification.
    """

    def __init__(self, cfg: T.ModelConfig, params, *, n_slots: int,
                 max_seq: int, block_size: int = 16, device=None):
        self.cfg = cfg
        self.max_seq = max_seq
        self.params = (params if device is None
                       else jax.device_put(params, device))
        cache = T.init_slot_cache_paged(cfg, n_slots, max_seq,
                                        block_size=block_size)
        bps = cache["block_tables"].shape[1]
        cache = dict(cache)
        cache["block_tables"] = jnp.asarray(
            np.arange(n_slots * bps, dtype=np.int32).reshape(n_slots, bps))
        if device is not None:
            cache = jax.device_put(cache, device)
        self.cache = cache
        # host view of each draft slot's position (== cache["pos"], kept
        # in lockstep so eligibility checks never pull the device)
        self.pos = np.zeros((n_slots,), np.int64)
        self._sync_fns: Dict[int, Callable] = {}
        self._propose_fns: Dict[int, Callable] = {}
        self._rollback_fn: Optional[Callable] = None

    def reset_slot(self, slot: int) -> None:
        self.cache = T.reset_slot_state(self.cfg, self.cache, slot)
        self.pos[slot] = 0

    def _sync_fn(self, m: int) -> Callable:
        fn = self._sync_fns.get(m)
        if fn is None:
            cfg, ms = self.cfg, self.max_seq

            def sync(params, cache, prompts, plens, out_buf, start, a):
                # committed chain: prompt tokens, then generated tokens
                # (out_buf[x] holds the token at absolute position
                # plen + x — see engine_loop._fused_step's scatter)
                cols = jnp.arange(prompts.shape[1])[None, :]
                gen_idx = jnp.clip(cols - plens[:, None], 0,
                                   out_buf.shape[1] - 1)
                chain = jnp.where(cols < plens[:, None], prompts,
                                  jnp.take_along_axis(out_buf, gen_idx,
                                                      axis=1))
                chunk = jax.lax.dynamic_slice(
                    chain, (0, start), (chain.shape[0], m))
                _, cache = T.decode_multi_step_slots_paged(
                    params, cfg, cache, chunk, a, max_seq=ms, advance=True)
                return cache

            fn = jax.jit(sync)
            self._sync_fns[m] = fn
        return fn

    def sync_to(self, slot: int, target_pos: int, *, prompts, plens,
                out_buf) -> None:
        """Feed the draft cache chain tokens [pos, target_pos) for one
        slot — initial enrollment (pos 0 -> plen) and catch-up after
        plain bursts advanced the target without the draft."""
        start = int(self.pos[slot])
        delta = int(target_pos) - start
        if delta <= 0:
            return
        onehot = np.zeros((self.pos.shape[0],), bool)
        onehot[slot] = True
        a = jnp.asarray(onehot)
        while delta > 0:
            m = 1 << (delta.bit_length() - 1)
            self.cache = self._sync_fn(m)(
                self.params, self.cache, prompts, plens, out_buf,
                jnp.int32(start), a)
            start += m
            delta -= m
        self.pos[slot] = int(target_pos)

    def _propose_fn(self, k: int) -> Callable:
        fn = self._propose_fns.get(k)
        if fn is None:
            cfg, ms = self.cfg, self.max_seq

            def propose(params, cache, last_tok, a):
                def body(carry, _):
                    c, tok = carry
                    logits, c = T.decode_step_slots_paged(
                        params, cfg, c, tok[:, None], a, max_seq=ms)
                    nxt = jnp.argmax(logits[:, -1], axis=-1).astype(
                        jnp.int32)
                    return (c, jnp.where(a, nxt, tok)), nxt

                # k+1 steps: the last feed writes draft KV for d_k, so a
                # fully-accepted round's rollback lands on a cache that
                # already holds the whole committed window
                (cache, _), toks = jax.lax.scan(
                    body, (cache, last_tok), None, length=k + 1)
                return cache, toks[:k].T           # proposals (B, k)

            fn = jax.jit(propose)
            self._propose_fns[k] = fn
        return fn

    def propose(self, k: int, last_tok, active) -> jax.Array:
        self.cache, toks = self._propose_fn(k)(
            self.params, self.cache, last_tok, active)
        return toks

    def rollback(self, k: int, commit, active) -> None:
        """After verify: active slots sit at pos0 + k + 1; move them back
        to the committed head pos0 + c (on-device — ``commit`` stays a
        device array, no host round-trip)."""
        if self._rollback_fn is None:
            def rb(cache, delta, a):
                cache = dict(cache)
                cache["pos"] = jnp.where(a, cache["pos"] + delta,
                                         cache["pos"])
                return cache

            self._rollback_fn = jax.jit(rb)
        self.cache = self._rollback_fn(self.cache, commit - (k + 1), active)


class SpeculativeDecoder:
    """One target SlotEngine's speculative decode state: the draft
    engine, the jitted verify step, per-run acceptance accounting, and
    the online veto (an `AcceptanceTracker` re-runs the trade-off
    decision as measured acceptance drifts; a negative decision disables
    speculation for the rest of the run and the loop re-prices admission
    back to plain decode).

    ``propose_override(round_index, proposals) -> proposals`` lets tests
    corrupt the draft's proposals deterministically (forcing rejection at
    a chosen window offset); it sees/returns host arrays, so it costs a
    sync and exists for tests only.
    """

    def __init__(self, engine, plan: SpecPlan, *,
                 propose_override: Optional[Callable] = None):
        if engine.kv_layout != "paged":
            raise ValueError("speculative decoding requires a paged engine")
        validate_speculation(engine.cfg, plan.draft_cfg,
                             kv_layout=engine.kv_layout,
                             prefix_sharing=engine.pool.prefix_sharing)
        self.eng = engine
        self.plan = plan
        self.draft = DraftEngine(
            plan.draft_cfg, plan.draft_params,
            n_slots=engine.pool.n_slots, max_seq=engine.pool.max_seq,
            block_size=engine.pool.block_size, device=engine.device)
        self.propose_override = propose_override
        self._verify_fns: Dict[int, Callable] = {}
        self.enabled = True
        self.disabled_midrun = False
        self._veto_handled = True
        self.n_rounds = 0
        self.n_proposed = 0
        self.n_accepted = 0
        self.n_committed = 0

    def reset_slot(self, slot: int) -> None:
        self.draft.reset_slot(slot)

    def sync_drafts(self, pos: np.ndarray, mask: np.ndarray) -> None:
        """Bring every masked slot's draft cache up to the target's
        committed position (no-op for already-synced slots)."""
        eng = self.eng
        for s in np.flatnonzero(mask):
            if self.draft.pos[s] != pos[s]:
                self.draft.sync_to(int(s), int(pos[s]),
                                   prompts=eng._prompts, plens=eng._plens,
                                   out_buf=eng._out_buf)

    def _verify_fn(self, k: int) -> Callable:
        fn = self._verify_fns.get(k)
        if fn is None:
            cfg, ms = self.eng.cfg, self.eng.pool.max_seq

            def verify(params, cache, draft_toks, last_tok, plens, out_buf,
                       a, rem):
                pos0 = cache["pos"]
                toks = jnp.concatenate([last_tok[:, None], draft_toks],
                                       axis=1)
                logits, cache = T.decode_multi_step_slots_paged(
                    params, cfg, cache, toks, a, max_seq=ms, advance=False)
                greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                # accepted = longest agreeing draft prefix; committed adds
                # the target's own next token, clamped to the steps owed
                match = (draft_toks == greedy[:, :k]).astype(jnp.int32)
                acc = jnp.sum(jnp.cumprod(match, axis=1), axis=1)
                commit = jnp.where(
                    a, jnp.minimum(acc + 1, jnp.maximum(rem, 1)), 0)
                # scatter committed tokens: greedy[:, j] is the chain
                # token at absolute position pos0 + j + 1, stored at
                # out_buf[pos0 + j + 1 - plen] (same layout as
                # engine_loop._fused_step)
                b, g = out_buf.shape
                j = jnp.arange(k + 1)[None, :]
                idx = pos0[:, None] + j - plens[:, None] + 1
                write = (a[:, None] & (j < commit[:, None])
                         & (idx >= 0) & (idx < g))
                safe = jnp.clip(idx, 0, g - 1)
                rows = out_buf[jnp.arange(b)[:, None], safe]
                out_buf = out_buf.at[jnp.arange(b)[:, None], safe].set(
                    jnp.where(write, greedy, rows))
                last = jnp.take_along_axis(
                    greedy, jnp.clip(commit - 1, 0, k)[:, None],
                    axis=1)[:, 0]
                last_tok = jnp.where(a, last, last_tok)
                cache = dict(cache)
                cache["pos"] = jnp.where(a, pos0 + commit, pos0)
                return cache, last_tok, out_buf, commit, acc

            fn = jax.jit(verify)
            self._verify_fns[k] = fn
        return fn

    def round(self, mask: np.ndarray, rem: np.ndarray) -> np.ndarray:
        """One speculative round over the masked slots (drafts must be
        synced).  Returns per-slot committed-token counts — the round's
        single host pull."""
        eng, k = self.eng, self.plan.k
        a = jnp.asarray(mask)
        proposals = self.draft.propose(k, eng._last_tok, a)
        if self.propose_override is not None:
            proposals = jnp.asarray(
                self.propose_override(self.n_rounds,
                                      np.asarray(proposals)),
                dtype=jnp.int32)
        remd = jnp.asarray(rem.astype(np.int32))
        (eng.cache, eng._last_tok, eng._out_buf, commit,
         acc) = self._verify_fn(k)(
            eng.params, eng.cache, proposals, eng._last_tok, eng._plens,
            eng._out_buf, a, remd)
        self.draft.rollback(k, commit, a)
        c = np.asarray(commit).astype(np.int64)
        acc_h = np.asarray(acc)
        self.draft.pos[mask] += c[mask]
        n = int(mask.sum())
        accepted = int(acc_h[mask].sum())
        self.n_rounds += 1
        self.n_proposed += k * n
        self.n_accepted += accepted
        self.n_committed += int(c[mask].sum())
        tracker = self.plan.tracker
        if tracker is not None and self.enabled:
            tracker.observe_round(k * n, accepted)
            if tracker.disabled:
                self.enabled = False
                self.disabled_midrun = True
                self._veto_handled = False
        return c

    def take_veto(self) -> bool:
        """True exactly once, when the tracker just vetoed speculation —
        the loop reacts by re-pricing admission back to plain decode."""
        if self._veto_handled:
            return False
        self._veto_handled = True
        return True

    @property
    def acceptance_rate(self) -> Optional[float]:
        if self.n_proposed <= 0:
            return None
        return self.n_accepted / self.n_proposed

    def stats(self) -> Dict:
        """JSON-safe per-run speculation accounting."""
        d = {"draft": self.plan.draft_name, "k": self.plan.k,
             "forced": self.plan.forced, "n_rounds": self.n_rounds,
             "n_proposed": self.n_proposed, "n_accepted": self.n_accepted,
             "n_committed": self.n_committed,
             "acceptance_rate": self.acceptance_rate,
             "enabled": self.enabled,
             "disabled_midrun": self.disabled_midrun}
        if self.plan.tracker is not None:
            d["tracker"] = self.plan.tracker.report()
        if self.plan.decision is not None:
            d["decision"] = self.plan.decision.summary()
        return d


def spec_dispatch(spec: SpeculativeDecoder, eng, pool, batcher, obs, *,
                  mask: np.ndarray, pos: np.ndarray, rem: np.ndarray,
                  budget: Optional[int]) -> int:
    """One speculative round under the serving loops' dispatch/telemetry
    contract (burst span, synced feedback/watchdog observation, pool
    write accounting).  Returns the step count credited to the driver:
    the maximum committed tokens across the round's slots."""
    if budget is not None and budget <= 0:
        return 0
    tracer, fb, wd = obs.tracer, obs.feedback, obs.watchdog
    spec.sync_drafts(pos, mask)
    n_active = int(mask.sum())
    timed = fb is not None or wd is not None
    # a round decodes only: every committed token is an output token
    with tracer.scope("burst", track=f"engine:{eng.name}", cat="engine",
                      args={"steps": spec.plan.k + 1, "n_active": n_active,
                            "speculative": True, "prefill_tokens": 0,
                            "prefill_steps": 0, "synced": timed}) as note:
        t0 = tracer.now() if timed else 0.0
        c = spec.round(mask, rem)
        committed = int(c[mask].sum())
        eng.steps_done[mask] += c[mask]
        for s in np.flatnonzero(mask):
            req = eng.slots[s]
            if req is not None and c[s] > 0:
                pool.note_write(req.rid, int(c[s]))
        if timed:
            eng.sync()
            dt = tracer.now() - t0
            # per-slot committed tokens this round, as fractional "steps":
            # the watchdog/feedback contract is wall time per step per
            # token
            steps = committed / max(n_active, 1)
            if fb is not None:
                fb.observe_burst(n_active, steps, dt)
            if wd is not None:
                wd.observe_burst(
                    eng.name, batcher.phase, n_tokens=n_active,
                    steps=steps, elapsed_s=dt,
                    priced_step_s=batcher.priced_step_s(n_active))
        note(committed=committed, decode_tokens=committed)
    obs.registry.counter("engine_decode_tokens").inc(committed)
    if spec.take_veto():
        # measured acceptance re-priced speculation worse than plain
        # decode: admission returns to the analytic plain-step model
        detail = batcher.reprice(batcher.analytic_step_s,
                                 source="speculation-disabled")
        if tracer.enabled:
            tracer.instant("speculation_disabled", track="server",
                           cat="watchdog", args=detail)
    return int(c[mask].max()) if n_active else 0


class SpeculativeEngineLoop(EngineLoop):
    """Colocated serving with draft-model speculation on the decode phase.

    Dispatch policy per driver iteration: when every burstable slot is
    decode-phase with ``rem >= k`` (the page-lease safety gate), run one
    speculative round — drafts are first synced to each slot's committed
    chain, which covers both initial enrollment at the phase flip and
    catch-up after plain bursts advanced the target alone.  Any other mix
    (prefilling slots, rem < k tails) falls back to the plain burst path
    unchanged, so scheduling stays simple and the identity contract rides
    entirely on the verify math.
    """

    def __init__(self, cfg, params, *, plan: SpecPlan,
                 propose_override: Optional[Callable] = None, **kwargs):
        super().__init__(cfg, params, **kwargs)
        self.spec = SpeculativeDecoder(self.engine, plan,
                                       propose_override=propose_override)

    def admit(self, queue, now, metrics):
        decision = super().admit(queue, now, metrics)
        for req in decision.admitted:
            self.spec.reset_slot(req.slot)
        return decision

    def dispatch(self, throttle: bool, budget: Optional[int]) -> int:
        eng = self.engine
        if self.spec.enabled:
            burstable = eng.active & (eng.steps_done < eng.steps_total)
            if burstable.any():
                plens = np.array([0 if r is None else r.prompt_len
                                  for r in eng.slots], np.int64)
                pos = eng.steps_done     # prefix sharing excluded: offset 0
                rem = eng.steps_total - eng.steps_done
                eligible = (burstable & (pos >= plens)
                            & (rem >= self.plan.k))
                if eligible[burstable].all():
                    return spec_dispatch(
                        self.spec, eng, self.pool, self.batcher, self.obs,
                        mask=burstable, pos=pos, rem=rem, budget=budget)
        return super().dispatch(throttle, budget)

    @property
    def plan(self) -> SpecPlan:
        return self.spec.plan

"""Serving runtime: KV-pool invariants, token-budgeted admission, and
continuous-vs-static greedy-token equivalence (bit-identical outputs)."""
import jax
import numpy as np
import pytest

from repro.models import transformer as T
from repro.serving import (ContinuousBatcher, EngineLoop, KVPool, Request,
                           RequestState, SlotEngine, decode_network_spec,
                           step_time_model, synthetic_workload,
                           token_budget_for_slo)

TINY = T.ModelConfig(
    name="serve-tiny", n_layers=3, d_model=32, n_heads=4, n_kv_heads=2,
    d_ff=64, vocab=64, attention_impl="dot", remat=False)


@pytest.fixture(scope="module")
def tiny_params():
    return T.init_params(jax.random.PRNGKey(0), TINY)


# ------------------------------------------------------------- KV pool
def test_pool_alloc_free_roundtrip():
    pool = KVPool(n_slots=4, max_seq=64, block_size=16)
    assert pool.total_blocks == 16
    slot = pool.alloc(rid=1, n_tokens=33)            # 3 blocks
    assert pool.free_slot_count == 3
    assert pool.free_block_count == 13
    assert pool.lease(1).slot == slot
    assert pool.free(1) == slot
    assert pool.free_slot_count == 4
    assert pool.free_block_count == 16


def test_pool_rejects_double_alloc_and_double_free():
    pool = KVPool(n_slots=2, max_seq=32, block_size=16)
    pool.alloc(rid=7, n_tokens=10)
    with pytest.raises(ValueError):
        pool.alloc(rid=7, n_tokens=10)
    pool.free(7)
    with pytest.raises(ValueError):
        pool.free(7)


def test_pool_admission_bounds():
    pool = KVPool(n_slots=2, max_seq=32, block_size=16, total_blocks=3)
    assert not pool.can_admit(33)                    # over slot row
    assert not pool.can_admit(3 * 16 + 1)            # over block budget
    assert pool.can_admit(32)
    pool.alloc(0, 32)                                # 2 blocks
    assert not pool.can_admit(17)                    # 1 block left
    assert pool.can_admit(16)
    pool.alloc(1, 16)
    assert not pool.can_admit(1)                     # no slots, no blocks


def test_pool_block_exclusivity_and_conservation():
    rng = np.random.default_rng(0)
    pool = KVPool(n_slots=8, max_seq=64, block_size=8)
    live = {}
    for step in range(200):
        if live and (len(live) == 8 or rng.random() < 0.4):
            rid = rng.choice(list(live))
            pool.free(rid)
            del live[rid]
        else:
            rid = step + 1000
            n = int(rng.integers(1, 65))
            if pool.can_admit(n):
                pool.alloc(rid, n)
                live[rid] = n
        # invariants
        owned = [b for r in live for b in pool.lease(r).blocks]
        assert len(owned) == len(set(owned))         # no block shared
        assert pool.free_block_count + len(owned) == pool.total_blocks
        assert 0.0 <= pool.utilization() <= 1.0
        assert 0.0 <= pool.occupancy() <= 1.0


def test_pool_utilization_tracks_writes():
    pool = KVPool(n_slots=2, max_seq=32, block_size=16)
    pool.alloc(1, 32)                                # 2 blocks = 32 tokens
    assert pool.utilization() == 0.0
    pool.note_write(1, 16)
    assert pool.utilization() == pytest.approx(0.5)
    with pytest.raises(ValueError):
        pool.note_write(1, 17)                       # past reservation


# ------------------------------------------------------------- batcher
def _req(rid, plen, glen, arrival=0.0, priority=0, deadline=None):
    return Request(rid=rid, prompt=np.zeros((plen,), np.int32),
                   max_new_tokens=glen, arrival=arrival, priority=priority,
                   deadline=deadline)


def test_batcher_respects_token_budget():
    pool = KVPool(n_slots=8, max_seq=64)
    b = ContinuousBatcher(TINY, pool, token_budget=3)
    queue = [_req(i, 8, 8, arrival=i) for i in range(6)]
    dec = b.admit(queue, n_active=0, now=0.0)
    assert [r.rid for r in dec.admitted] == [0, 1, 2]
    assert all(r.state is RequestState.PREFILL for r in dec.admitted)
    # with 2 already active only one more fits the budget
    queue2 = [_req(10 + i, 8, 8) for i in range(3)]
    dec2 = b.admit(queue2, n_active=2, now=0.0)
    assert len(dec2.admitted) == 1


def test_batcher_sheds_expired_and_unservable():
    pool = KVPool(n_slots=4, max_seq=32)
    b = ContinuousBatcher(TINY, pool)
    queue = [_req(0, 8, 8, deadline=1.0),            # expired at now=2
             _req(1, 30, 8),                         # 38 > max_seq: never fits
             _req(2, 8, 8)]
    dec = b.admit(queue, n_active=0, now=2.0)
    assert [r.rid for r in dec.dropped] == [0, 1]
    assert all(r.state is RequestState.DROPPED for r in dec.dropped)
    assert [r.rid for r in dec.admitted] == [2]


def test_batcher_backfills_past_blocked_request():
    pool = KVPool(n_slots=4, max_seq=64, block_size=16, total_blocks=5)
    b = ContinuousBatcher(TINY, pool)
    queue = [_req(0, 40, 20, arrival=0.0),           # 60 tokens = 4 blocks
             _req(1, 50, 14, arrival=1.0),           # 64 tokens: blocked
             _req(2, 8, 8, arrival=2.0)]             # 16 tokens: backfills
    dec = b.admit(queue, n_active=0, now=0.0)
    assert [r.rid for r in dec.admitted] == [0, 2]
    assert [r.rid for r in queue] == [1]


def test_batcher_priority_order():
    pool = KVPool(n_slots=2, max_seq=32)
    b = ContinuousBatcher(TINY, pool, token_budget=1)
    queue = [_req(0, 8, 8, arrival=0.0, priority=1),
             _req(1, 8, 8, arrival=5.0, priority=0)]
    dec = b.admit(queue, n_active=0, now=6.0)
    assert [r.rid for r in dec.admitted] == [1]      # lower priority value


def test_cost_model_admission_pricing():
    spec = decode_network_spec(TINY, kv_len=64)
    # one attention + one MLP tuple per layer
    assert len(spec) == 2 * TINY.n_layers
    t1 = step_time_model(TINY, 64, 1)
    t8 = step_time_model(TINY, 64, 8)
    assert 0 < t1 <= t8
    # generous SLO admits every slot; the tightest admits at least one
    assert token_budget_for_slo(TINY, 64, 8, step_slo_s=10.0) == 8
    assert token_budget_for_slo(TINY, 64, 8, step_slo_s=0.0) == 1


# ------------------------------------------------- engine loop end-to-end
def _virtual_clock():
    t = [0.0]

    def now():
        t[0] += 1e-3
        return t[0]

    return now


def _static_reference(params, requests, batch, max_len, cfg=TINY):
    """Per-request greedy tokens through the legacy static path — the same
    baseline construction the benchmark times (shared, so the bit-identity
    contract the test asserts is exactly what BENCH_serving.json reports)."""
    from benchmarks.bench_serving import run_static
    from repro.serving import ServeMetrics
    return run_static(cfg, params, requests, batch=batch, max_len=max_len,
                      metrics=ServeMetrics())


def test_continuous_matches_static_greedy_tokens(tiny_params):
    max_len = 8 + 12
    reqs = synthetic_workload(9, rate=1e9, vocab=TINY.vocab,
                              prompt_lens=(4, 8), gen_lens=(3, 6, 12),
                              seed=11)
    want = _static_reference(tiny_params, [
        Request(rid=r.rid, prompt=r.prompt.copy(),
                max_new_tokens=r.max_new_tokens, arrival=r.arrival)
        for r in reqs], batch=3, max_len=max_len)

    # 3 slots for 9 requests: slots recycle mid-stream, positions stagger
    engine = EngineLoop(TINY, tiny_params, n_slots=3, max_seq=max_len)
    metrics = engine.run(reqs, now_fn=_virtual_clock())
    assert metrics.n_done == 9
    got = {r.rid: r.output for r in reqs}
    assert got == want                               # bit-identical greedy


def test_engine_recycles_slots_and_accounts_pool(tiny_params):
    reqs = synthetic_workload(6, rate=1e9, vocab=TINY.vocab,
                              prompt_lens=(4,), gen_lens=(4,), seed=5)
    engine = EngineLoop(TINY, tiny_params, n_slots=2, max_seq=16)
    metrics = engine.run(reqs, now_fn=_virtual_clock())
    assert metrics.n_done == 6
    assert engine.pool.free_slot_count == 2          # everything released
    assert engine.pool.free_block_count == engine.pool.total_blocks
    assert all(r.state is RequestState.DONE for r in reqs)
    assert all(len(r.output) == 4 for r in reqs)
    s = metrics.summary()
    assert s["tokens_out"] == 24
    assert s["ttft_p50_s"] > 0 and s["latency_p99_s"] > 0


def test_dispatch_snapshots_the_active_mask(tiny_params):
    """A burst steps the slots that were active when it was dispatched,
    even when the caller changes its mask before the device runs the
    burst: on the CPU an aligned numpy array can become a device array
    without a copy."""
    pool = KVPool(n_slots=2, max_seq=8)
    eng = SlotEngine(TINY, tiny_params, pool)
    req = Request(rid=0, prompt=np.asarray([3], np.int32), max_new_tokens=2)
    req.slot = pool.alloc(0, 3)
    eng.bind(req, steps_total=2)
    buf = np.zeros(128, np.uint8)
    off = (-buf.ctypes.data) % 64
    mask = buf[off:off + 2].view(bool)
    mask[req.slot] = True
    seen = []
    burst_fn = eng._burst_fn

    def spy(k):
        fn = burst_fn(k)

        def run(*args):
            seen.append(args[-1])        # the device mask the burst reads
            return fn(*args)
        return run

    eng._burst_fn = spy
    eng.dispatch(1, mask)
    mask[:] = False
    assert np.asarray(seen[0]).tolist() == [True, False]
    assert int(eng.cache["pos"][req.slot]) == 1


def test_recycled_slot_does_not_leak_ssm_state():
    # hybrid arch: recurrent state carries no position, so slot recycling
    # must explicitly reset it (regression: second tenant of a slot used to
    # inherit the first tenant's RG-LRU/Mamba hidden state)
    cfg = T.ModelConfig(
        name="serve-rec", n_layers=4, d_model=32, n_heads=4, n_kv_heads=2,
        d_ff=64, vocab=64, block_pattern=("rec", "attn"),
        attention_impl="dot", remat=False)
    params = T.init_params(jax.random.PRNGKey(2), cfg)
    reqs = synthetic_workload(2, rate=1e9, vocab=cfg.vocab, prompt_lens=(4,),
                              gen_lens=(4,), seed=21)
    want = _static_reference(params, [
        Request(rid=r.rid, prompt=r.prompt.copy(),
                max_new_tokens=r.max_new_tokens, arrival=r.arrival)
        for r in reqs], batch=1, max_len=8, cfg=cfg)
    engine = EngineLoop(cfg, params, n_slots=1, max_seq=8)
    engine.run(reqs, now_fn=_virtual_clock())
    assert {r.rid: r.output for r in reqs} == want


def test_idle_engine_fast_forwards_to_next_arrival(tiny_params):
    # arrivals far apart vs service time: the clock must jump to each
    # arrival, never stamping TTFT/latency before the request arrived
    reqs = [_req(0, 4, 4, arrival=5.0), _req(1, 4, 4, arrival=50.0)]
    for r in reqs:
        r.prompt = np.arange(4, dtype=np.int32)
    engine = EngineLoop(TINY, tiny_params, n_slots=2, max_seq=16)
    metrics = engine.run(reqs, now_fn=_virtual_clock())
    assert metrics.n_done == 2
    assert all(t >= 0 for t in metrics.ttft_s)
    assert all(t >= 0 for t in metrics.latency_s)
    assert metrics.elapsed_s >= 50.0     # offered-load timeline, not wall


def test_engine_drops_expired_queued_requests(tiny_params):
    # one slot; the second request's deadline passes while it queues
    r0 = _req(0, 4, 8)
    r0.prompt = np.arange(4, dtype=np.int32)
    r1 = _req(1, 4, 4, arrival=0.0, deadline=1e-9)
    r1.prompt = np.arange(4, dtype=np.int32)
    engine = EngineLoop(TINY, tiny_params, n_slots=1, max_seq=16)
    metrics = engine.run([r0, r1], now_fn=_virtual_clock())
    assert metrics.n_done == 1
    assert metrics.n_dropped == 1
    assert r1.state is RequestState.DROPPED and r1.output == []

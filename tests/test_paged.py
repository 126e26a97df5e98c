"""Block-paged KV cache: allocator properties, bitwise paged-vs-dense
decode equivalence, block-granular export/import round-trips, and the
hardened regression-gate schema for the bench's ``paged`` section."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import check_regression as cr
from repro.models import transformer as T
from repro.serving import (DisaggregatedEngineLoop, EngineLoop, KVPool,
                           Request, SlotEngine, synthetic_workload)

TINY = T.ModelConfig(
    name="paged-tiny", n_layers=3, d_model=32, n_heads=4, n_kv_heads=2,
    d_ff=64, vocab=64, attention_impl="dot", remat=False)

# odd max_seq vs block_size: 21 % 8 != 0, so the last logical block
# overhangs the sequence axis — the boundary the gather must trim exactly
MAX_LEN = 21
BS = 8


@pytest.fixture(scope="module")
def tiny_params():
    return T.init_params(jax.random.PRNGKey(0), TINY)


def _virtual_clock():
    t = [0.0]

    def now():
        t[0] += 1e-3
        return t[0]

    return now


def _workload():
    return synthetic_workload(7, rate=1e9, vocab=TINY.vocab,
                              prompt_lens=(4, 7), gen_lens=(3, 6, 13),
                              seed=11)


@pytest.fixture(scope="module")
def dense_outputs(tiny_params):
    """Per-request greedy tokens through the dense-layout engine — the
    reference every paged run must match bit-for-bit."""
    reqs = _workload()
    engine = EngineLoop(TINY, tiny_params, n_slots=3, max_seq=MAX_LEN,
                        block_size=BS, kv_layout="dense")
    metrics = engine.run(reqs, now_fn=_virtual_clock())
    assert metrics.n_done == len(reqs)
    return {r.rid: r.output for r in reqs}


# --------------------------------------------------------------- allocator
def test_pool_block_table_order_and_padding():
    pool = KVPool(n_slots=2, max_seq=64, block_size=16)
    pool.alloc(rid=5, n_tokens=33)                   # 3 blocks
    lease = pool.lease(5)
    table = pool.block_table(5, pad_to=4)
    assert table.dtype == np.int32 and table.shape == (4,)
    assert table[:3].tolist() == lease.blocks        # lease order IS logical
    assert table[3] == 0                             # padding
    with pytest.raises(ValueError):
        pool.block_table(5, pad_to=2)                # pad below block count


def test_pool_churn_never_leaks_and_fragmentation_never_blocks():
    """Alloc/free churn: blocks are conserved, never shared, never double-
    freed — and because physical pages are interchangeable, an admit whose
    block count fits the free list NEVER fails (no external
    fragmentation)."""
    rng = np.random.default_rng(7)
    pool = KVPool(n_slots=6, max_seq=64, block_size=8, total_blocks=24)
    live = {}
    for step in range(300):
        if live and (rng.random() < 0.45 or len(live) == 6):
            rid = rng.choice(list(live))
            pool.free(rid)
            del live[rid]
            with pytest.raises(ValueError):
                pool.free(rid)                       # double free raises
        else:
            rid = 1000 + step
            n = int(rng.integers(1, 65))
            fits = (pool.free_slot_count > 0
                    and pool.blocks_needed(n) <= pool.free_block_count
                    and n <= pool.max_seq)
            assert pool.can_admit(n) == fits         # fit => admissible
            if fits:
                pool.alloc(rid, n)                   # never raises on a fit
                live[rid] = n
        owned = [b for r in live for b in pool.lease(r).blocks]
        assert len(owned) == len(set(owned))
        assert pool.free_block_count + len(owned) == pool.total_blocks
    for rid in list(live):
        pool.free(rid)
    assert pool.free_block_count == pool.total_blocks
    assert pool.free_slot_count == 6


def test_pool_shared_churn_conserves_refcounts():
    """The churn property under prefix sharing: blocks may now be held by
    several leases (plus pending COW source refs), so the conservation law
    becomes refcounted — free + distinct-referenced == total, and every
    block's refcount equals exactly the number of leases holding it plus
    the pending COW copies sourcing from it.  Admission still never fails
    on a fit (fresh blocks, not total blocks, are what an admit draws)."""
    rng = np.random.default_rng(3)
    pool = KVPool(n_slots=6, max_seq=64, block_size=8, total_blocks=32,
                  prefix_sharing=True)
    families = [tuple(int(t) for t in rng.integers(0, 997, size=(16,)))
                for _ in range(3)]
    live = {}
    for step in range(400):
        if live and (rng.random() < 0.45 or len(live) == 6):
            rid = rng.choice(list(live))
            pool.free(rid)
            del live[rid]
        else:
            rid = 1000 + step
            prefix = families[int(rng.integers(0, 3))]
            suffix = tuple(int(t) for t in rng.integers(
                1000, 2000, size=(int(rng.integers(1, 17)),)))
            prompt = prefix + suffix
            n = min(len(prompt) + int(rng.integers(0, 17)), pool.max_seq)
            fits = (pool.free_slot_count > 0
                    and pool.fresh_blocks_needed(n, prompt)
                    <= pool.free_block_count)
            assert pool.can_admit(n, prompt) == fits
            if fits:
                pool.alloc(rid, n, prompt=prompt)
                live[rid] = n
                if rng.random() < 0.5:
                    pool.consume_cow(rid)    # engine materialized the copy
                lease = pool.lease(rid)
                room = lease.reserved_tokens - lease.written_tokens
                pool.note_write(rid, int(rng.integers(0, room + 1)))
        held = {}
        for r in live:
            for b in pool.lease(r).blocks:
                held[b] = held.get(b, 0) + 1
        for ops in pool._pending_cow.values():
            for src, _ in ops:
                held[src] = held.get(src, 0) + 1
        assert held == pool._block_refs      # refcounts exactly account
        assert (pool.free_block_count + len(pool._block_refs)
                == pool.total_blocks)        # conservation, shared or not
    for rid in list(live):
        pool.free(rid)
    assert pool.free_block_count == pool.total_blocks
    assert pool.free_slot_count == 6
    assert pool._block_refs == {} and pool._prefix_index == {}


# ------------------------------------------- bitwise decode equivalence
# ------------------------------------------- which paged attention serves
@pytest.mark.parametrize("impl,backend,want", [
    ("auto", "cpu", "ref"), ("auto", "tpu", "pallas"), ("auto", "gpu", "ref"),
    ("ref", "tpu", "ref"), ("pallas", "cpu", "pallas")])
def test_paged_impl_resolves_by_backend(impl, backend, want):
    from repro.models.attention import resolve_paged_impl
    assert resolve_paged_impl(impl, backend) == want


def test_paged_impl_rejects_unknown_names():
    from repro.models.attention import resolve_paged_impl
    with pytest.raises(ValueError, match="unknown paged attention impl"):
        resolve_paged_impl("gather", "cpu")


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_engine_builds_its_bursts_with_the_backends_paged_attention(
        tiny_params, monkeypatch, backend):
    """The default configuration serves the reference on the CPU (what the
    bitwise paged == dense tests below test) and the kernel on a TPU; the
    engine records which (nothing is compiled here)."""
    assert TINY.paged_attention_impl == "auto"
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    eng = SlotEngine(TINY, tiny_params, KVPool(n_slots=2, max_seq=MAX_LEN,
                                               block_size=BS),
                     kv_layout="paged")
    assert eng.paged_kernel is (backend == "tpu")
    assert eng._step_cfg.paged_attention_impl == (
        "pallas" if backend == "tpu" else "ref")
    dense = SlotEngine(TINY, tiny_params, KVPool(n_slots=2, max_seq=MAX_LEN,
                                                 block_size=BS),
                       kv_layout="dense")
    assert dense.paged_kernel is False


def test_paged_decode_attention_routes_by_backend(rng, monkeypatch):
    """On a TPU backend the kernel runs, and an idle slot asks it for no
    page (position -1); on the CPU the reference runs, bitwise."""
    from repro.kernels import ref
    from repro.models import attention as attn_lib
    b, hq, hk, d, bs, nb = 3, 4, 2, 8, 4, 3
    q = jnp.asarray(rng.normal(size=(b, hq, 1, d)), jnp.float32)
    ka = jnp.asarray(rng.normal(size=(b * nb + 1, hk, bs, d)), jnp.float32)
    va = jnp.asarray(rng.normal(size=(b * nb + 1, hk, bs, d)), jnp.float32)
    bt = jnp.asarray(np.arange(1, b * nb + 1, dtype=np.int32).reshape(b, nb))
    pos = jnp.asarray(np.array([3, 9, 5], np.int32))
    active = jnp.asarray(np.array([True, False, True]))
    got = attn_lib.paged_decode_attention(q, ka, va, bt, pos, max_seq=10,
                                          active=active)
    want = ref.paged_attention_ref(q, ka, va, bt, pos, max_seq=10)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    seen = {}

    def kernel(q, ka, va, bt, pos, steps=None):
        seen["pos"] = np.asarray(pos)
        return jnp.zeros_like(q)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(attn_lib.kops, "paged_attention", kernel)
    attn_lib.paged_decode_attention(q, ka, va, bt, pos, max_seq=10,
                                    active=active)
    np.testing.assert_array_equal(seen["pos"], [3, -1, 5])


def test_paged_decode_step_bitwise_matches_dense(tiny_params):
    """decode_step_slots_paged == decode_step_slots bit-for-bit across
    steps that cross odd seq % block_size boundaries, with inactive slots
    mixed in.

    Active slots' logits and the whole persisted KV state must match
    bitwise every step.  (Inactive slots' *transient* step logits are not
    comparable by construction — the dense path attends against a write it
    then reverts, the paged path routes that write to the trash page — and
    the engine discards them either way.)"""
    from repro.kernels.ref import paged_gather

    n_slots = 3
    pool = KVPool(n_slots, MAX_LEN, block_size=BS)
    tables = []
    for rid in range(n_slots):
        pool.alloc(rid, MAX_LEN)
        tables.append(pool.block_table(rid, pad_to=pool.blocks_per_slot))
    tables = jnp.asarray(np.stack(tables))
    dense = T.init_slot_cache(TINY, n_slots, MAX_LEN)
    paged = T.init_slot_cache_paged(TINY, n_slots, MAX_LEN, block_size=BS)
    paged["block_tables"] = tables

    rng = np.random.default_rng(0)
    for step in range(12):
        toks = jnp.asarray(rng.integers(0, TINY.vocab, size=(n_slots, 1),
                                        dtype=np.int32))
        active = jnp.asarray(rng.random(n_slots) < 0.8)
        ld, dense = T.decode_step_slots(tiny_params, TINY, dense, toks,
                                        active)
        lp, paged = T.decode_step_slots_paged(tiny_params, TINY, paged,
                                              toks, active, max_seq=MAX_LEN)
        act = np.asarray(active)
        np.testing.assert_array_equal(np.asarray(ld)[act],
                                      np.asarray(lp)[act],
                                      err_msg=f"step {step}")
        np.testing.assert_array_equal(np.asarray(dense["pos"]),
                                      np.asarray(paged["pos"]))
        # persisted KV state identical for EVERY slot: the paged arenas,
        # gathered through the tables, equal the dense rows bit-for-bit
        (d_blocks, _), (p_blocks, _) = dense["layers"], paged["layers"]
        for dc, pc in zip(d_blocks, p_blocks):
            for key in ("k", "v"):
                for s in range(dc[key].shape[0]):        # super-block axis
                    rows = paged_gather(pc[key][s], tables, MAX_LEN)
                    np.testing.assert_array_equal(
                        np.asarray(dc[key][s]), np.asarray(rows),
                        err_msg=f"step {step} layer {s} {key}")


def test_paged_engine_matches_dense(tiny_params, dense_outputs):
    reqs = _workload()
    engine = EngineLoop(TINY, tiny_params, n_slots=3, max_seq=MAX_LEN,
                        block_size=BS, kv_layout="paged")
    engine.run(reqs, now_fn=_virtual_clock())
    assert {r.rid: r.output for r in reqs} == dense_outputs
    assert engine.pool.free_block_count == engine.pool.total_blocks


def test_paged_reduced_arena_matches_dense(tiny_params, dense_outputs):
    # tokens-in-flight provisioning: fewer physical pages than the dense
    # equivalent (9 blocks vs 3*3) — admission defers, outputs unchanged
    reqs = _workload()
    engine = EngineLoop(TINY, tiny_params, n_slots=3, max_seq=MAX_LEN,
                        block_size=BS, total_blocks=6, kv_layout="paged")
    engine.run(reqs, now_fn=_virtual_clock())
    assert {r.rid: r.output for r in reqs} == dense_outputs


def test_paged_disagg_matches_dense(tiny_params, dense_outputs):
    """Block-granular phase migration is exact: disaggregated paged
    serving produces the dense colocated tokens bit-for-bit."""
    reqs = _workload()
    loop = DisaggregatedEngineLoop(
        TINY, tiny_params, n_prefill_slots=2, n_decode_slots=3,
        max_seq=MAX_LEN, block_size=BS, kv_layout="paged")
    metrics = loop.run(reqs, now_fn=_virtual_clock())
    assert metrics.n_done == len(reqs)
    assert {r.rid: r.output for r in reqs} == dense_outputs
    assert loop.handoff.n_handoffs == len(reqs)


# ----------------------------------------------- export/import round-trip
def _bind_and_prefill(engine, pool, req, steps):
    req.slot = pool.alloc(req.rid, req.total_tokens)
    engine.bind(req, steps_total=steps)
    engine.dispatch(steps, engine.active.copy())


def test_paged_export_import_roundtrip_bit_identical(tiny_params):
    """A paged slot exported mid-flight and imported into a different
    engine (different physical pages) finishes with exactly the tokens an
    uninterrupted engine produces — and the snapshot ships only the pages
    holding written tokens."""
    prompt = np.arange(1, 8, dtype=np.int32)         # plen 7, gen 6
    mk = lambda: Request(rid=1, prompt=prompt.copy(), max_new_tokens=6)

    # uninterrupted reference
    pool_c = KVPool(2, MAX_LEN, block_size=BS)
    eng_c = SlotEngine(TINY, tiny_params, pool_c, kv_layout="paged")
    ref = mk()
    _bind_and_prefill(eng_c, pool_c, ref, 7 + 6 - 1)
    want = eng_c.pull_output(ref.slot)[:6]

    # prefill on A, migrate to B mid-flight
    pool_a = KVPool(2, MAX_LEN, block_size=BS)
    eng_a = SlotEngine(TINY, tiny_params, pool_a, kv_layout="paged")
    req = mk()
    _bind_and_prefill(eng_a, pool_a, req, 7)         # prefill phase only
    state = eng_a.export_slot(req.slot)
    assert state["layout"] == "paged" and state["kv_tokens"] == 7
    # only ceil(7/8) == 1 written page ships, not the 2-block reservation
    k_leaf = jax.tree.leaves(state["blocks"])[0]
    assert k_leaf.shape[1] == 1

    pool_b = KVPool(2, MAX_LEN, block_size=BS)
    pool_b.alloc(rid=99, n_tokens=10)                # shift physical ids
    eng_b = SlotEngine(TINY, tiny_params, pool_b, kv_layout="paged")
    eng_a.release(req)
    req.slot = pool_b.alloc(req.rid, req.total_tokens)
    eng_b.adopt(req, state, steps_total=6 - 1)
    eng_b.dispatch(5, eng_b.active.copy())
    got = eng_b.pull_output(req.slot)[:6]
    np.testing.assert_array_equal(got, want)


def test_import_rejects_layout_mismatch(tiny_params):
    pool_p = KVPool(1, MAX_LEN, block_size=BS)
    pool_d = KVPool(1, MAX_LEN, block_size=BS)
    eng_p = SlotEngine(TINY, tiny_params, pool_p, kv_layout="paged")
    eng_d = SlotEngine(TINY, tiny_params, pool_d, kv_layout="dense")
    req = Request(rid=1, prompt=np.arange(4, dtype=np.int32),
                  max_new_tokens=2)
    _bind_and_prefill(eng_p, pool_p, req, 4)
    state = eng_p.export_slot(req.slot)
    with pytest.raises(ValueError, match="layout"):
        eng_d.import_slot(0, state)
    with pytest.raises(ValueError, match="dest_blocks"):
        eng_p.import_slot(0, state)                  # paged needs a lease


def test_windowed_config_rejects_paged_layout():
    cfg = T.ModelConfig(name="swa", n_layers=2, d_model=32, n_heads=4,
                        n_kv_heads=2, d_ff=64, vocab=64, attn_window=8,
                        attention_impl="dot", remat=False)
    with pytest.raises(ValueError, match="sliding-window"):
        T.init_slot_cache_paged(cfg, 2, 32, block_size=8)


def test_reset_slot_state_preserves_block_tables():
    cache = T.init_slot_cache_paged(TINY, 2, MAX_LEN, block_size=BS)
    cache["block_tables"] = cache["block_tables"].at[1].set(7)
    out = T.reset_slot_state(TINY, cache, 1)
    assert "block_tables" in out                     # unknown keys survive
    np.testing.assert_array_equal(np.asarray(out["block_tables"]),
                                  np.asarray(cache["block_tables"]))


# ------------------------------------------------- regression-gate schema
def _good_paged_section():
    summ = {"tok_per_s": 100.0, "tokens_out": 10, "requests_done": 2}
    return {
        "block_size": 16, "blocks_per_slot": 5, "total_blocks": 24,
        "dense_equiv_blocks": 40, "kv_bytes_dense": 1000,
        "kv_bytes_paged": 600, "kv_bytes_ratio": 0.6,
        "achievable_n_slots_at_dense_budget": 13, "tok_per_s_ratio": 0.9,
        "dense": dict(summ), "paged": dict(summ),
        "bit_identical_colocated": True,
        "bit_identical_disaggregated": True, "all_identical": True,
    }


def test_validate_paged_accepts_well_formed_section():
    checks = cr.validate_paged({"paged": _good_paged_section()})
    assert checks and all(ok for _, ok, _ in checks)


@pytest.mark.parametrize("mutate,name", [
    (lambda s: s.clear(), "paged section schema"),
    (lambda s: s.pop("kv_bytes_paged"), "paged section schema"),
    (lambda s: s.pop("dense"), "paged section schema"),
    (lambda s: s.update(bit_identical_colocated=False),
     "paged outputs bit-identical to dense"),
    (lambda s: s.update(kv_bytes_paged=1000),
     "paged KV bytes resident strictly below dense"),
    (lambda s: s.update(kv_bytes_paged=2000),
     "paged KV bytes resident strictly below dense"),
])
def test_validate_paged_fails_malformed_or_regressed(mutate, name):
    section = _good_paged_section()
    mutate(section)
    checks = cr.validate_paged({"paged": section})
    failed = [n for n, ok, _ in checks if not ok]
    assert any(name in n for n in failed), (failed, name)


def test_validate_paged_missing_section_fails():
    checks = cr.validate_paged({})
    assert len(checks) == 1
    name, ok, _ = checks[0]
    assert name == "paged section present" and not ok


# ------------------------------------------------- absolute host baselines
def _fresh_bench():
    return {
        "loads": [{"offered_rate_req_s": 1e9, "bit_identical": True,
                   "speedup_tok_per_s": 2.0,
                   "continuous": {"tok_per_s": 500.0},
                   "static": {"tok_per_s": 250.0}}],
        "paged": {"paged": {"tok_per_s": 450.0}},
    }


def test_absolute_baseline_record_then_gate(tmp_path):
    d = str(tmp_path / "baselines")
    fresh = _fresh_bench()
    # first run records and passes
    checks = cr.check_absolute(fresh, threshold=0.2, baselines_dir=d,
                               record=True)
    assert all(ok for _, ok, _ in checks)
    path = tmp_path / "baselines" / f"{cr.host_key()}.json"
    assert path.exists()
    recorded = json.loads(path.read_text())
    assert recorded["metrics"]["continuous_tok_per_s"] == 500.0
    assert recorded["metrics"]["paged_tok_per_s"] == 450.0
    # same-host rerun within budget passes
    ok2 = cr.check_absolute(fresh, threshold=0.2, baselines_dir=d,
                            record=False)
    assert all(ok for _, ok, _ in ok2)
    # >20% regression on this host fails
    slow = _fresh_bench()
    slow["loads"][0]["continuous"]["tok_per_s"] = 300.0
    bad = cr.check_absolute(slow, threshold=0.2, baselines_dir=d,
                            record=False)
    assert any(not ok for _, ok, _ in bad)


def test_absolute_baseline_missing_without_record_skips(tmp_path):
    checks = cr.check_absolute(_fresh_bench(), threshold=0.2,
                               baselines_dir=str(tmp_path / "none"),
                               record=False)
    assert len(checks) == 1 and checks[0][1]
    assert "skipped" in checks[0][2]

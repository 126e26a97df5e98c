"""The traffic generator: deterministic per seed, its stated distributions
and clips, and the same work in every seed's window."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import spec  # noqa: E402

GEN = spec.load_module(ROOT / "bench/traffic/lognormal.py", "t_lognormal")
MIXES = {p.stem: json.loads(p.read_text())
         for p in (ROOT / "bench/traffic").glob("*.json")}
SEEDS = (1, 2**31 + 7, 2**33 + 3)


def _gen(mix, seed, window_s=30.0):
    return GEN.generate(MIXES[mix], seed=seed, vocab=1000, window_s=window_s)


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_same_seed_same_inputs(mix):
    a, b = _gen(mix, SEEDS[1]), _gen(mix, SEEDS[1])
    assert len(a) == len(b)
    for (ta, pa, oa), (tb, pb, ob) in zip(a, b):
        assert ta == tb and oa == ob and np.array_equal(pa, pb)
    c = _gen(mix, SEEDS[2])
    assert any(not np.array_equal(x[1][:8], y[1][:8]) for x, y in zip(a, c))


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_lengths_follow_the_stated_lognormal_and_clips(mix):
    """Every pair passes the mix's filter, and the kept lengths keep the
    stated median and log-sd (the filter drops only the far tails)."""
    m = MIXES[mix]
    keep = m["keep"]
    reqs = _gen(mix, SEEDS[0], window_s=600.0)
    plens = np.array([len(r[1]) for r in reqs])
    olens = np.array([r[2] for r in reqs])
    assert np.all(plens + olens <= keep["total_max"])
    for key, lens in (("prompt", plens), ("output", olens)):
        lo, hi = keep[key]
        assert lens.min() >= lo and lens.max() <= hi
        assert abs(np.median(lens) / m[key]["median"] - 1) < 0.15
        sigma = np.std(np.log(lens))
        assert 0.8 * m[key]["sigma"] < sigma < 1.1 * m[key]["sigma"]
    toks = np.concatenate([r[1] for r in reqs])
    assert toks.min() >= 0 and toks.max() < 1000


def test_filter_draws_again_instead_of_clipping():
    """A filter that keeps a narrow band leaves no pile at its edges."""
    mix = dict(MIXES["chat"], keep={"prompt": [50, 200], "output": [100, 400],
                                   "total_max": 500})
    reqs = GEN.generate(mix, seed=SEEDS[0], vocab=1000, window_s=600.0)
    plens = np.array([len(r[1]) for r in reqs])
    olens = np.array([r[2] for r in reqs])
    assert plens.min() >= 50 and plens.max() <= 200
    assert np.all(plens + olens <= 500)
    assert np.mean(plens == 200) < 0.02 and np.mean(olens == 100) < 0.02


def test_poisson_phases_hold_the_same_work_for_every_seed():
    m = MIXES["chat"]
    lead, window = m["lead_in_s"], 30.0
    sets = []
    for seed in SEEDS:
        reqs = _gen("chat", seed, window)
        t = np.array([r[0] for r in reqs])
        assert np.all(np.diff(t) >= 0)
        inw = [(len(r[1]), r[2]) for r in reqs
               if lead <= r[0] < lead + window]
        sets.append(sorted(inw))
        assert t.max() < lead + window + m["drain_cap_s"]
    assert sets[0] == sets[1] == sets[2]
    rate = m["arrivals"]["rate_per_s"]
    assert 0.5 * rate * window < len(sets[0]) < 1.5 * rate * window


def test_backlog_is_queued_at_zero():
    """Every seed queues the same sizes in the same order; only the token
    ids differ."""
    m = MIXES["sharegpt_offline"]
    reqs = _gen("sharegpt_offline", SEEDS[0])
    assert len(reqs) == m["arrivals"]["count"]
    assert all(r[0] == 0.0 for r in reqs)
    a = [(len(r[1]), r[2]) for r in reqs]
    other = _gen("sharegpt_offline", SEEDS[1])
    assert a == [(len(r[1]), r[2]) for r in other]
    assert any(not np.array_equal(x[1], y[1]) for x, y in zip(reqs, other))

"""The general traffic generator: lognormal prompt and output lengths kept
by a filter, open-loop Poisson arrivals or a backlog queued at t=0.

A mix file (``bench/traffic/<mix>.json``) gives the parameters:

* ``lengths_seed``: the seed of the lengths and the gaps between arrivals.
  They are drawn once from it, so every run offers the same multiset of
  request sizes and the same number of arrivals in each phase; the run's
  ``--seed`` permutes them inside each Poisson phase and draws the token
  ids.  A backlog keeps its order, so every seed's window holds the same
  requests.
* ``prompt`` / ``output``: ``median`` and ``sigma`` (of the log) in tokens,
  drawn independently and rounded.
* ``keep``: the filter a pair must pass, as a benchmark over a recorded
  set applies it: ``prompt`` and ``output`` ``[min, max]`` and
  ``total_max`` of the two.  A pair outside it is drawn again.
* ``arrivals``: ``{"kind": "poisson", "rate_per_s": r}`` — arrivals through
  the lead-in, the window and the drain, each phase drawn as its own
  segment — or ``{"kind": "backlog", "count": n}`` — n requests queued at
  t=0.
* ``lead_in_s`` and ``drain_cap_s``: the harness's phases around the
  measured window.

Returns plain ``(arrival_s, prompt_ids, max_new_tokens)`` triples; the
harness wraps them for the program.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np


def lengths(rng: np.random.Generator, mix: Dict,
            n: int) -> Tuple[np.ndarray, np.ndarray]:
    """n (prompt, output) length pairs that pass the mix's filter."""
    keep = mix["keep"]
    got_p: List[np.ndarray] = []
    got_o: List[np.ndarray] = []
    have = 0
    while have < n:
        m = 2 * (n - have) + 16
        p, o = (np.rint(rng.lognormal(np.log(mix[k]["median"]),
                                      mix[k]["sigma"], size=m)).astype(
                                          np.int64)
                for k in ("prompt", "output"))
        ok = ((p >= keep["prompt"][0]) & (p <= keep["prompt"][1])
              & (o >= keep["output"][0]) & (o <= keep["output"][1])
              & (p + o <= keep["total_max"]))
        got_p.append(p[ok])
        got_o.append(o[ok])
        have += int(ok.sum())
    return np.concatenate(got_p)[:n], np.concatenate(got_o)[:n]


def poisson_gaps(rng: np.random.Generator, rate: float,
                 duration: float) -> np.ndarray:
    """Gaps of a Poisson process at ``rate`` whose arrivals fall inside
    ``duration`` seconds (their sum is at most ``duration``)."""
    gaps: List[float] = []
    t = 0.0
    while True:
        g = float(rng.exponential(1.0 / rate))
        if t + g > duration:
            return np.asarray(gaps)
        t += g
        gaps.append(g)


def generate(mix: Dict, *, seed: int, vocab: int,
             window_s: float) -> List[Tuple[float, np.ndarray, int]]:
    base = np.random.default_rng(mix["lengths_seed"])
    run = np.random.default_rng(seed)
    arr = mix["arrivals"]
    if arr["kind"] == "poisson":
        phases = (mix["lead_in_s"], window_s, mix["drain_cap_s"])
        times = []
        start = 0.0
        for duration in phases:
            gaps = poisson_gaps(base, arr["rate_per_s"], duration)
            times.append(start + np.cumsum(run.permutation(gaps)))
            start += duration
        counts = [len(t) for t in times]
        arrivals = np.concatenate(times)
        permute = True
    elif arr["kind"] == "backlog":
        counts = [int(arr["count"])]
        arrivals = np.zeros(counts[0])
        permute = False
    else:
        raise ValueError(f"unknown arrival kind {arr['kind']!r}")
    # one multiset of sizes per phase, permuted by the run's seed
    plens, olens = [], []
    for n in counts:
        p, o = lengths(base, mix, n)
        perm = run.permutation(n) if permute else np.arange(n)
        plens.append(p[perm])
        olens.append(o[perm])
    plens = np.concatenate(plens)
    olens = np.concatenate(olens)
    return [(float(t), run.integers(0, vocab, size=int(p), dtype=np.int32),
             int(o)) for t, p, o in zip(arrivals, plens, olens)]

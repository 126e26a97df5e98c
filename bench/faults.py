"""Faults planted under a run, to show the check catches them (tests and
bench/calibrate.py only; a benchmark run plants none)."""
from __future__ import annotations

import numpy as np


def alter_tokens(loop, *, every: int = 5) -> None:
    """A token altered where it is produced: every ``every``-th sample of
    each slot's output row reads one id higher when the host pulls it."""
    eng = loop.engine
    vocab = loop.cfg.vocab
    pull_all, pull_one = eng.pull_outputs, eng.pull_output

    def bent(row):
        row = np.array(row)
        row[..., every - 1::every] = (row[..., every - 1::every] + 1) % vocab
        return row

    eng.pull_outputs = lambda: bent(pull_all())
    eng.pull_output = lambda slot: bent(pull_one(slot))


FAULTS = {"alter_tokens": alter_tokens}

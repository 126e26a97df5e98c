"""Test fixtures.  NOTE: no XLA_FLAGS here — smoke tests and benches must
see 1 device; only launch/dryrun.py forces 512 host devices."""
import os

import numpy as np
import pytest

# the persistent compilation cache stays off in the tests and in the CLI
# subprocesses they start (the entry points' main() would turn it on)
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"


@pytest.fixture
def rng():
    return np.random.default_rng(0)

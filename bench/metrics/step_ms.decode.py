"""`step_ms` for the decode cell, where it moves output_tok_per_s
(bench/readings.py)."""
from bench.readings import step_ms as read  # noqa: F401

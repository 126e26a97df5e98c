"""`mfu` for the decode cell, where it moves output_tok_per_s
(bench/readings.py)."""
from bench.readings import mfu as read  # noqa: F401

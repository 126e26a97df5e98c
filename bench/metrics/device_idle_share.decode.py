"""`device_idle_share` for the decode cell, where it moves output_tok_per_s
(bench/readings.py)."""
from bench.readings import device_idle_share as read  # noqa: F401

"""`step_ms` for the chat cell, where it moves tpot_p90_ms
(bench/readings.py)."""
from bench.readings import step_ms as read  # noqa: F401

"""`mfu` for the chat cell, where it moves tpot_p90_ms
(bench/readings.py)."""
from bench.readings import mfu as read  # noqa: F401

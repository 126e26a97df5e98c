"""`device_idle_share` for the chat cell, where it moves tpot_p90_ms
(bench/readings.py)."""
from bench.readings import device_idle_share as read  # noqa: F401

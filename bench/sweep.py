"""Find a chat cell's knee once, by a sweep of fixed rates in one process
(one chip):

    python3 bench/sweep.py --workload <name> --seconds <s> --seed <n> --rates 2,3,4

For each rate it serves the cell's traffic at that rate (no correctness
check) and prints one JSON line: tails, tokens per second, how many of
the window's requests finished within the drain cap, and whether the queue
grew across the window.  The knee is the highest rate at which the queue
does not grow and every request finishes; the cell's traffic file then
holds the knee and a fixed rate below it.
"""
import argparse
import copy
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    from bench import harness, spec
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    cell = spec.load_cell(args.workload)
    harness.configure_jax()
    devices = harness.chips_or_exit(cell.chips)
    peaks = spec.load_peaks(devices[0].device_kind)
    for rate in (float(r) for r in args.rates.split(",")):
        mix = copy.deepcopy(cell.traffic)
        mix["arrivals"]["rate_per_s"] = rate
        cfg, loop, mesh = harness.build(cell, seed=args.seed, peaks=peaks,
                                        annotate=False)
        run, _, _ = harness.serve(cell, cfg, loop, mesh, seed=args.seed,
                                  seconds=args.seconds, trace=False,
                                  t_start=time.perf_counter(), peaks=peaks,
                                  devices=devices, mix=mix)
        del loop
        harness.free_device_memory()
        counted = sorted(run.counted, key=lambda r: r.arrival)
        waits = [(r.admitted if r.admitted is not None else run.stop)
                 - r.arrival for r in counted]
        half = len(waits) // 2
        ttft = [(r.first if r.first is not None else run.stop) - r.arrival
                for r in counted]
        q = [n for t, n in run.queue_depth if run.in_window(t)]
        print(json.dumps({
            "rate": rate, "counted": len(counted),
            "done": sum(r.done is not None for r in counted),
            "ttft_p50_s": float(np.percentile(ttft, 50)),
            "ttft_p90_s": float(np.percentile(ttft, 90)),
            "wait_first_half_s": float(np.mean(waits[:half])),
            "wait_second_half_s": float(np.mean(waits[half:])),
            "queue_first_last_max": [q[0], q[-1], max(q)] if q else None,
            "delivered_tok_per_s": run.window_tokens / run.seconds,
            "metrics": {m.name: m.read(run) for m in cell.end_to_end}}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

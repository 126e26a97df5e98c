"""Readings that set a cell's correctness limit, several seeds in one
process (one chip):

    python3 bench/calibrate.py --workload <name> --seconds <s> --seeds 1,2,3 [--fault alter_tokens]

Each seed serves the cell's traffic with a short window and prints one JSON
line: the program's widest logit gap against the float32 reference and,
at the same positions, the gap of the tokens the float8 control puts
first.  The lower reading of the limit is the program's largest gap over
the seeds, the upper one the control's smallest.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    from bench import harness, spec
    from bench.faults import FAULTS
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--fault", choices=sorted(FAULTS))
    args = ap.parse_args()
    cell = spec.load_cell(args.workload)
    harness.configure_jax()
    devices = harness.chips_or_exit(cell.chips)
    peaks = spec.load_peaks(devices[0].device_kind)
    for seed in (int(s) for s in args.seeds.split(",")):
        out = harness.run_cell(
            cell, seed=seed, seconds=args.seconds, trace=False,
            t_start=time.perf_counter(), peaks=peaks, devices=devices,
            fault=FAULTS.get(args.fault), control=args.fault is None)
        c = out["checks"]
        print(json.dumps({
            "seed": seed, "fault": args.fault,
            "gap": c["widest_logit_gap"]["value"],
            "control_gap": c.get("control_widest_logit_gap",
                                 {}).get("value"),
            "compared": c["served_tokens_compared"]["value"],
            "metrics": {k: v["value"] for k, v in out["metrics"].items()},
            "memory_peak_bytes": out["device"]["memory_peak_bytes"]}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Readings that the limits of a cell's check are set from.

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds 5

For each seed, in one process (set-up is paid once for the programs that
stay compiled): run the cell for a short window, read every number the
check compares, then read the same numbers with the control in the
program's place (the reference one precision below what the
configuration states). Prints one JSON line per seed. The benchmark's own
runs never read the control. Runs only where JAX's first device is a TPU.
"""

import json
import sys
import time
from pathlib import Path


def main(argv) -> int:
    import argparse

    ap = argparse.ArgumentParser(prog="bench/control.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    root = Path(__file__).resolve().parents[1]
    sys.path.insert(0, str(root / "bench"))
    sys.path.insert(0, str(root / "src"))
    from benchlib.harness import BenchError, run_workload

    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        try:
            result, checks, control = run_workload(
                args.workload, seed, args.seconds, False, with_control=True)
        except BenchError as e:
            print(f"control: {e}", file=sys.stderr)
            return 1
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "seconds": time.perf_counter() - t,
            "program": {c.name: c.value for c in checks},
            "control": {c.name: c.value for c in control},
            "limits": {c.name: c.limit for c in checks},
            "correct": result["correct"],
            "control_correct": all(c.ok for c in control),
            "metrics": {k: v["value"] for k, v in result["metrics"].items()},
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

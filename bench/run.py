#!/usr/bin/env python3
"""The chip benchmark: one cell of ``BENCHMARK.json`` per call.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's configuration (``bench/configs/<config>.json``) and traffic
(``bench/traffic/<traffic>.json``), warms up, measures campaign after
campaign for ``--seconds``, checks what the timed path produced against a
plain reference, and prints one JSON line last on standard output. With
``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the metrics are the
cell's per-layer metrics, each read by ``bench/metrics/<name>.py``.

Runs only where JAX's first device is a TPU; exits non-zero, printing no
result, anywhere else.
"""

import sys
import time

T_START = time.perf_counter()

if __name__ == "__main__":
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parent))
    from benchlib.harness import main

    sys.exit(main(sys.argv[1:], t_start=T_START))

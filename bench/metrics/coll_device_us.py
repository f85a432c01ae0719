"""Mean device time of the collective program per call: the collectives'
programs' time in the traced window (averaged over the chips that ran
them), over the calls of them in it (the benchmark's ``bench:call``
spans, warm-up calls included, as each runs the program once)."""

import sys

from benchlib import trace as tr
from benchlib.systems.collectives import PROGRAM_KEY


def read(run):
    lo, hi = run.trace.window()
    calls = sum(1 for s, e, n in run.trace.spans
                if n == "call" and lo <= s < hi)
    ns = tr.program_ns(run.trace, PROGRAM_KEY, lo, hi)
    names = {n.split("(")[0] for evs in run.trace.modules.values()
             for _, _, n in evs}
    print(f"coll_device_us: {ns * 1e-9:.6f} s of {PROGRAM_KEY} programs "
          f"over {calls} calls; programs in the trace {sorted(names)}",
          file=sys.stderr)
    return ns * 1e-3 / calls if ns > 0 and calls else None

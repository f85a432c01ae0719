"""The least time the chip could take for one decode step, the larger of
FLOPs over peak FLOP/s and bytes over HBM bytes/s (both from the step's
shapes), over the step program's mean device time in the trace. The
program is found by the jitted function's name, ``serve_step``; the
binding bound goes to standard error."""

import sys


def read(run):
    lo, hi = run.trace.window()
    durs = [min(e, hi) - max(s, lo) for mods in run.trace.modules.values()
            for s, e, n in mods if "serve_step" in n and e > lo and s < hi]
    if not durs:
        return None
    c = run.cell.step_counts()
    t_flops = c["flops"] / run.peak("bf16_flops")
    t_bytes = c["bytes"] / run.peak("hbm_bytes_per_s")
    bound = "bytes" if t_bytes >= t_flops else "flops"
    mean_s = sum(durs) / len(durs) * 1e-9
    print(f"decode_roofline: {bound}-bound: flops {c['flops']:.6e} "
          f"({t_flops * 1e3:.6f} ms), bytes {c['bytes']:.6e} "
          f"({t_bytes * 1e3:.6f} ms); step device time {mean_s * 1e3:.6f} ms "
          f"over {len(durs)} steps", file=sys.stderr)
    return 100.0 * max(t_flops, t_bytes) / mean_s

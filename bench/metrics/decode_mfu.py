"""The FLOPs one decode step requires (from its shapes) times the timed
steps per second (the benchmark's host clock over every timed call of the
window), over the chip's bf16 peak."""


def read(run):
    d = run.cell.calls.durations
    if not d:
        return None
    flops = run.cell.step_counts()["flops"]
    return 100.0 * flops / (sum(d) / len(d)) / run.peak("bf16_flops")

"""The meter's timed calls on the trace, for cells with many short calls.

:func:`timed_calls` gives, for each of the meter's ``timed_call`` spans in
the completed campaigns, the span and the device time of the programs
named by ``key`` inside it: what :func:`benchlib.progspans.meter_gap_ms`
averages the difference of, taken by bisection over the sorted programs
instead of one pass over every program per call, since a window of KiB
collectives holds thousands of calls and as many programs on each chip.
"""

from __future__ import annotations

import bisect

from . import progspans
from .trace import merge


def per_span_ns(intervals, spans) -> list[float]:
    """The summed overlap of ``(s, e, ...)`` ``intervals`` with each
    ``(s, e)`` span: each interval clipped to the span, as
    :func:`benchlib.trace.program_ns` clips it."""
    ivs = sorted(intervals)
    starts = [iv[0] for iv in ivs]
    longest = max((iv[1] - iv[0] for iv in ivs), default=0.0)
    out = []
    for a, b in spans:
        i = bisect.bisect_left(starts, a - longest)
        j = bisect.bisect_left(starts, b)
        out.append(sum(min(e, b) - max(s, a) for s, e, *_ in ivs[i:j]
                       if min(e, b) > max(s, a)))
    return out


def device_ns(run, key: str, spans) -> list[float]:
    """Device time inside each span of the programs named by ``key``,
    averaged over the chips that ran one in it. A trace recorded on the
    CPU has no module line; there the union of its operations stands for
    it."""
    if run.device_kind == "cpu":
        ops = [iv for evs in run.trace.ops.values() for iv in evs]
        lo = min((a for a, _ in spans), default=0.0)
        hi = max((b for _, b in spans), default=0.0)
        return per_span_ns(merge(ops, lo, hi), spans)
    per = [per_span_ns([iv for iv in evs if key in iv[2]], spans)
           for evs in run.trace.modules.values()]
    out = []
    for k in range(len(spans)):
        ran = [p[k] for p in per if p[k] > 0]
        out.append(sum(ran) / len(ran) if ran else 0.0)
    return out


def timed_calls(run, key: str) -> list[tuple[float, float]] | None:
    """``(span, device time)`` in ns of each of the meter's ``timed_call``
    spans in the completed campaigns, in the order they ran; None where
    the trace holds no program named by ``key`` or the program kept no
    such span."""
    cpu = run.device_kind == "cpu"
    if not cpu and not any(key in n for evs in run.trace.modules.values()
                           for _, _, n in evs):
        return None
    program, _ = progspans._program_spans()
    done = sorted((c["start"] * 1e9, c["end"] * 1e9)
                  for c in run.completed())
    starts = [a for a, _ in done]
    calls = []
    for s in program or []:
        if s.name != "timed_call" or s.t1_ns is None:
            continue
        i = bisect.bisect_right(starts, s.t0_ns) - 1
        if i >= 0 and s.t1_ns <= done[i][1]:
            calls.append(s)
    off = progspans.offset_ns(run) if calls else None
    if off is None:
        return None
    spans = sorted((c.t0_ns + off, c.t1_ns + off) for c in calls)
    dev = device_ns(run, key, spans)
    return [(b - a, d) for (a, b), d in zip(spans, dev)]


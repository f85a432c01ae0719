"""The program's own spans (``repro.core.telemetry``) on the trace's clock.

The program keeps its spans on ``time.perf_counter_ns`` (and puts the same
spans in the profile as ``repro:`` events, which :mod:`benchlib.trace`
does not read). The benchmark's ``window`` span is on both clocks: its
start in the trace and in the host-clock spans gives the offset that maps
the program's spans onto the trace, beside the device's operations.

* :func:`idle_split`: the idle time of the first device inside the traced
  window, each idle instant given to the innermost program span that
  covers it, summed into ``sync`` (``clock_sync``), ``engine``
  (``sim_engine``, ``sim_wait``) and ``other`` (any other span, or none);
  each a share of the window in %, the three adding up to the idle share.
* :func:`meter_gap_ms`: the mean, over the meter's timed calls in the
  completed campaigns, of the ``timed_call`` span's length less the device
  time of the step programs inside it.

Every reader returns None, and does not raise, where the program keeps no
spans (a program without ``repro.core.telemetry``) or none lies in the
window; :func:`meter_gap_ms` also where a device's trace holds no step
program, as ``decode_roofline`` does. The buffer of spans stands in for
the profile's ``repro:`` events until :mod:`benchlib.trace` reads them.
"""

from __future__ import annotations

import sys

from .trace import merge, program_ns

SYNC = frozenset({"clock_sync"})
ENGINE = frozenset({"sim_engine", "sim_wait"})


def _program_spans():
    try:
        from repro.core import telemetry
    except ImportError:
        return None, 0
    return telemetry.spans(), telemetry.dropped()


def offset_ns(run) -> float | None:
    """Trace clock minus ``perf_counter`` clock, in ns, from the start of
    the benchmark's window span on both."""
    host = run.spans.of("window")
    if not host:
        return None
    lo, hi = run.trace.window()
    off = lo - host[0][0] * 1e9
    drift = (hi - host[0][1] * 1e9) - off
    print(f"progspans: trace clock - perf clock {off:.0f} ns; the window's "
          f"end reads {drift:.0f} ns from it", file=sys.stderr)
    return off


def on_trace(run):
    """The program's closed spans inside the traced window, as
    ``(start, end, name)`` on the trace's clock; None where there are
    none."""
    cache = run.__dict__.setdefault("_progspans", {})
    if "on_trace" not in cache:
        cache["on_trace"] = _on_trace(run)
    return cache["on_trace"]


def _on_trace(run):
    spans, dropped = _program_spans()
    if not spans:
        return None
    off = offset_ns(run)
    if off is None:
        return None
    lo, hi = run.trace.window()
    inside = [s for s in spans if s.t1_ns is not None
              and s.t1_ns + off > lo and s.t0_ns + off < hi]
    camps = sum(s.name == "campaign" for s in inside)
    print(f"progspans: {len(inside)} program spans in the window, over "
          f"{camps} campaigns; {dropped} dropped", file=sys.stderr)
    return [(s.t0_ns + off, s.t1_ns + off, s.name) for s in inside] or None


def innermost(spans, lo: float, hi: float) -> list[tuple[float, float, str]]:
    """``[lo, hi]`` cut into ``(s, e, name)`` pieces, each named by the
    innermost span that covers it (``""`` where none does). Spans nest as
    one thread opens them; one that outlasts the span it starts in is cut
    at that span's end."""
    out: list[tuple[float, float, str]] = []
    stack: list[tuple[float, str]] = []       # (end, name)
    t = lo

    def emit(until: float) -> None:
        nonlocal t
        until = min(until, hi)
        if until > t:
            out.append((t, until, stack[-1][1] if stack else ""))
            t = until

    for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            emit(stack[-1][0])
            stack.pop()
        emit(s)
        if stack:
            e = min(e, stack[-1][0])
        if e > s:
            stack.append((e, name))
    while stack:
        emit(stack[-1][0])
        stack.pop()
    emit(hi)
    return out


def _idle(run, lo: float, hi: float) -> list[tuple[float, float]] | None:
    """The stretches of ``[lo, hi]`` in which the first device ran no
    operation (as ``trace.idle_gaps`` takes them); None where it ran
    none."""
    if not run.trace.ops:
        return None
    dev = sorted(run.trace.ops)[0]
    busy = merge(run.trace.ops[dev], lo, hi)
    if not busy:
        return None
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def idle_split(run) -> dict | None:
    """``{"sync", "engine", "other"}``: the first device's idle time in the
    window under each kind of program span, in % of the window."""
    cache = run.__dict__.setdefault("_progspans", {})
    if "idle_split" not in cache:
        cache["idle_split"] = _idle_split(run)
    return cache["idle_split"]


def _idle_split(run):
    spans = on_trace(run)
    if spans is None:
        return None
    lo, hi = run.trace.window()
    gaps = _idle(run, lo, hi)
    if gaps is None:
        return None
    sums = {"sync": 0.0, "engine": 0.0}
    pieces = innermost(spans, lo, hi)
    i = 0
    for a, b in gaps:                       # both sorted and disjoint
        while i < len(pieces) and pieces[i][1] <= a:
            i += 1
        j = i
        while j < len(pieces) and pieces[j][0] < b:
            s, e, name = pieces[j]
            kind = ("sync" if name in SYNC else
                    "engine" if name in ENGINE else None)
            if kind is not None:
                sums[kind] += min(e, b) - max(s, a)
            j += 1
    idle = sum(b - a for a, b in gaps)
    sums["other"] = idle - sums["sync"] - sums["engine"]
    return {k: 100.0 * v / (hi - lo) for k, v in sums.items()}


def meter_gap_ms(run, key: str = "serve_step") -> float | None:
    """Mean host gap of the meter's timed calls in the completed campaigns:
    the ``timed_call`` span less the device time of the programs named by
    ``key`` inside it; None where the device's trace has no such program.
    A trace recorded on the CPU has no module line; there the union of the
    operations inside the span stands for it."""
    cpu = run.device_kind == "cpu"
    if not cpu and not any(key in n for evs in run.trace.modules.values()
                           for _, _, n in evs):
        return None
    spans, _ = _program_spans()
    done = [(c["start"] * 1e9, c["end"] * 1e9) for c in run.completed()]
    calls = [s for s in spans or [] if s.name == "timed_call"
             and s.t1_ns is not None
             and any(a <= s.t0_ns and s.t1_ns <= b for a, b in done)]
    off = offset_ns(run) if calls else None
    if off is None:
        return None
    gaps = []
    for c in calls:
        s, e = c.t0_ns + off, c.t1_ns + off
        if cpu:
            ops = [iv for evs in run.trace.ops.values() for iv in evs]
            dev = sum(b - a for a, b in merge(ops, s, e))
        else:
            dev = program_ns(run.trace, key, s, e)
        gaps.append((e - s) - dev)
    print(f"progspans: meter gap over {len(gaps)} timed calls",
          file=sys.stderr)
    return sum(gaps) / len(gaps) * 1e-6

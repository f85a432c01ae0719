"""From a profiler trace to the benchmark's device metrics.

:func:`load_xplane` reads the ``.xplane.pb`` that ``jax.profiler`` writes
into a :class:`Trace`: per device, the intervals of its operations and of
its programs (XLA modules), and the benchmark's own host spans
(``TraceAnnotation`` events named ``bench:<name>``). Everything after
that works on plain intervals in nanoseconds on the trace's clock:

* busy time: the union of a device's operation intervals inside a window,
  averaged over the devices; the idle share is 1 minus busy over window;
* program time: the summed durations of the modules whose name contains
  a key (the jitted function's name, such as ``window_fused``);
* the operations that took most device time, and the longest idle gaps,
  each labelled by the innermost benchmark span that covers it.

On a TPU the device planes are ``/device:TPU:<n>`` with the lines
``XLA Ops`` and ``XLA Modules``. A trace recorded on the CPU has no device
plane; ``platform="cpu"`` reads the XLA CPU client's threads instead, which
is how the reduction is tested without a chip.
"""

from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

BENCH_PREFIX = "bench:"


@dataclass
class Trace:
    ops: dict = field(default_factory=dict)      # device -> [(s, e, name)]
    modules: dict = field(default_factory=dict)  # device -> [(s, e, name)]
    spans: list = field(default_factory=list)    # [(s, e, name)]

    def window(self, name: str = "window") -> tuple[float, float]:
        """The (first) benchmark span of that name: the traced window."""
        for s, e, n in self.spans:
            if n == name:
                return s, e
        raise KeyError(f"no bench:{name} span in the trace")


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load_xplane(path: str, platform: str = "tpu") -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        pname = plane.name
        is_device = pname.startswith("/device:") and platform != "cpu"
        for line in plane.lines:
            lname = line.name
            cpu_xla = (platform == "cpu" and pname == "/host:CPU"
                       and lname.startswith("tf_XLA"))
            for ev in line.events:
                s = float(ev.start_ns)
                e = s + float(ev.duration_ns)
                name = ev.name
                if name.startswith(BENCH_PREFIX):
                    tr.spans.append((s, e, name[len(BENCH_PREFIX):]))
                elif is_device and lname == "XLA Ops":
                    # "%fusion.12 = f32[...] fusion(...)" -> "fusion.12"
                    name = name.split(" = ", 1)[0].lstrip("%")
                    tr.ops.setdefault(pname, []).append((s, e, name))
                elif is_device and lname == "XLA Modules":
                    tr.modules.setdefault(pname, []).append((s, e, name))
                elif cpu_xla and e > s and "::" not in name:
                    tr.ops.setdefault("cpu", []).append((s, e, name))
    for d in (tr.ops, tr.modules):
        for k in d:
            d[k].sort()
    tr.spans.sort()
    return tr


def merge(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The union of ``(s, e, ...)`` intervals clipped to ``[lo, hi]``, as
    sorted disjoint ``(s, e)`` pairs."""
    out: list[list[float]] = []
    for iv in sorted(intervals):
        s, e = max(iv[0], lo), min(iv[1], hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(trace: Trace, lo: float, hi: float) -> float:
    """Union of operation intervals in ``[lo, hi]``, averaged over the
    devices that ran any operation there."""
    per = [sum(e - s for s, e in merge(evs, lo, hi))
           for evs in trace.ops.values()]
    per = [b for b in per if b > 0]
    return sum(per) / len(per) if per else 0.0


def idle_share(trace: Trace, lo: float, hi: float) -> float:
    return 1.0 - busy_ns(trace, lo, hi) / (hi - lo)


def idle_percent(run):
    """The ``idle_share.<cell>`` readers: the share of a run's traced window,
    in %, in which the device ran no operation (none where it ran none)."""
    lo, hi = run.trace.window()
    if not busy_ns(run.trace, lo, hi):
        return None
    return 100.0 * idle_share(run.trace, lo, hi)


def _per_device_sum(table: dict, pred, lo: float, hi: float) -> float:
    per = []
    for evs in table.values():
        tot = sum(min(e, hi) - max(s, lo) for s, e, n in evs
                  if pred(n) and min(e, hi) > max(s, lo))
        per.append(tot)
    per = [t for t in per if t > 0]
    return sum(per) / len(per) if per else 0.0


def program_ns(trace: Trace, key: str, lo: float, hi: float) -> float:
    """Device time of the modules whose name contains ``key`` inside
    ``[lo, hi]``, averaged over the devices that ran them."""
    return _per_device_sum(trace.modules, lambda n: key in n, lo, hi)


def top_ops(trace: Trace, lo: float, hi: float, k: int = 10):
    """``[[name, seconds], ...]``: the operations that took most device
    time, summed by name and averaged over devices."""
    tot: dict[str, float] = {}
    ndev = max(1, len(trace.ops))
    for evs in trace.ops.values():
        for s, e, n in evs:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                tot[n] = tot.get(n, 0.0) + d
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[n, v / ndev * 1e-9] for n, v in best]


def label_at(trace: Trace, t: float, default: str = "outside") -> str:
    """The innermost (shortest) benchmark span that covers ``t``."""
    best = None
    for s, e, n in trace.spans:
        if s <= t <= e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, n)
    return best[2] if best is not None else default


def idle_gaps(trace: Trace, lo: float, hi: float, k: int = 10):
    """``[[label, seconds], ...]``: the longest stretches of ``[lo, hi]`` in
    which the first device ran no operation, each labelled by the
    innermost benchmark span at its middle."""
    if not trace.ops:
        return [["outside", (hi - lo) * 1e-9]]
    dev = sorted(trace.ops)[0]
    busy = merge(trace.ops[dev], lo, hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[label_at(trace, (a + b) / 2), (b - a) * 1e-9]
            for a, b in gaps[:k]]

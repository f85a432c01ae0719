"""Collectives on a chip mesh: ``JaxBackend``, timed by the repository's meter.

A campaign is the repository's ``Campaign`` over ``JaxBackend`` as the
configuration states it (its chips, payload dtype, ops, the meter's
epoch isolation and warm-up): every (op, size) case of the traffic, in
each launch epoch's shuffled order, ``nrep`` timed calls each. Each launch
epoch clears the jit caches, so the first (warm-up) call of a case in an
epoch traces and compiles its program again, as the program runs it.

The backend's programs are the program's own; only its payloads are the
benchmark's (``JaxBackend._input``, overridden): standard normal values
drawn from the run's seed, the case and the batch of its calls
(:func:`benchlib.ref_coll.payload`), so every case of every launch epoch
of every campaign gets payloads of its own, placed once for the epoch's
calls as the program places its own.

Set-up checks the backend it builds against the configuration, and that
JAX sees the configuration's chips, then warms every case once with a
campaign of one epoch and one call. Every campaign of the window uses the
traffic's design seed.

The time of a timed call is the benchmark's host clock from its start to
the start of the next call (or the return of its ``measure`` batch), kept
per case under the callable's ``op@msize`` name. A seeded sample of each
case's timed calls keeps what the call returned, with the batch it was
made in; once the window has closed, the check compares every kept
result with the plain reference (:mod:`benchlib.ref_coll`) on the
payloads that batch was due, and counts the cases that had no kept timed
call. A program that returned a result of other payloads, an earlier
epoch's, fails it.
"""

from __future__ import annotations

import contextlib
import sys

import numpy as np

from .. import ref_coll
from ..harness import BenchError, Check
from ..probes import BackendProxy, CallClock

# the name the chip's trace gives the collectives' programs: JAX lowers a
# pmap as a jit of a shard_map, whose module is ``jit_call_wrapped`` for
# every op and size
PROGRAM_KEY = "call_wrapped"


class CaseClock(CallClock):
    """A :class:`CallClock` that also keeps the case of every timed call
    (``order``, beside ``durations``): one ``measure`` batch is one case,
    named as its callable is. A kept output is ``(batch, out)``, ``batch``
    what ``batch_of()`` reads when the call returns."""

    def __init__(self, traced, rng, keep_prob, batch_of=lambda: None):
        super().__init__(traced, rng, keep_prob,
                         keep=lambda out: (batch_of(), out))
        self.order: list[str] = []
        self._case = None

    def wrap(self, name: str, fn):
        call = super().wrap(name, fn)

        def named():
            self._case = name
            return call()
        return named

    @contextlib.contextmanager
    def batch(self, nrep: int):
        n0 = len(self.durations)
        with super().batch(nrep):
            yield
        self.order.extend([self._case] * (len(self.durations) - n0))


def case_name(op: str, msize: int) -> str:
    return f"{op}@{int(msize)}"


def p95_geomean_us(durations: list[float], order: list[str]) -> float:
    """The geometric mean, over the cases, of each case's 95th percentile
    of its timed calls (``durations`` in seconds, ``order`` their cases;
    microseconds out)."""
    by_case: dict[str, list[float]] = {}
    for d, name in zip(durations, order):
        by_case.setdefault(name, []).append(d)
    if not by_case:
        raise RuntimeError("no timed call in the window")
    p95 = {k: float(np.percentile(d, 95)) * 1e6 for k, d in by_case.items()}
    print("collectives: p95 us " + ", ".join(
        f"{k} {v!r} ({len(by_case[k])} calls, median "
        f"{float(np.median(by_case[k])) * 1e6!r})"
        for k, v in sorted(p95.items())), file=sys.stderr)
    return float(np.exp(np.mean(np.log(list(p95.values())))))


def report_by_case(order: list[str], calls) -> None:
    """Standard error: per case, the mean ``timed_call`` span, the
    programs' device time in it and the meter's share of the span, from
    ``(span, device)`` pairs (ns) in the order the calls ran, which is
    the order of the case names ``order``."""
    if len(order) < len(calls):
        return
    sums: dict[str, list[float]] = {}
    for name, (span, dev) in zip(order, calls):
        acc = sums.setdefault(name, [0.0, 0.0, 0])
        acc[0] += span
        acc[1] += dev
        acc[2] += 1
    print("collectives: per timed call, span / device us, meter share: " +
          ", ".join(f"{k} {s / n * 1e-3:.3f} / {d / n * 1e-3:.3f}, "
                    f"{100 * (1 - d / s):.2f}%"
                    for k, (s, d, n) in sorted(sums.items())),
          file=sys.stderr)


class CollectivesCell:
    def __init__(self, config: dict, traffic: dict, run):
        self.config, self.traffic, self.run = config, traffic, run
        self.cases = [(op, int(m)) for op in traffic["ops"]
                      for m in traffic["msizes"]]
        # the batch of each case's calls now under way: 0 in set-up, then
        # 1, 2, ... for the window's ``measure`` calls of that case
        self.batches = {case_name(op, m): 0 for op, m in self.cases}
        self.current = None
        self.calls = CaseClock(run.traced, run.rng(11),
                               float(traffic["keep_prob"]),
                               batch_of=lambda: self.current)
        self.kept: list[tuple[str, int, np.ndarray]] = []
        self.control = False

    def payload_key(self, name: str, batch: int) -> tuple:
        pos = [case_name(op, m) for op, m in self.cases].index(name)
        return (self.run.seed, pos, batch)

    # -- the campaign ------------------------------------------------------
    def _backend(self):
        from repro.campaign import JaxBackend
        from repro.core.runtime_meter import MeterConfig

        cell = self

        class SeededJaxBackend(JaxBackend):
            """``JaxBackend`` with the benchmark's payloads: those due to the
            case's batch now under way."""

            def _input(self, op, msize, n):
                name = case_name(op, msize)
                return ref_coll.payload(
                    op, msize, n, cell.payload_key(name, cell.batches[name]),
                    self.dtype)

        c = self.config
        return SeededJaxBackend(
            ops=tuple(c["ops"]), n_devices=int(c["n_devices"]),
            meter=MeterConfig(epoch_isolation=c["epoch_isolation"]),
            dtype=c["dtype"])

    def _check_stated(self, backend):
        """What the configuration states, against the backend built and the
        chips JAX sees."""
        import jax

        c = self.config
        m = backend.meter
        program = next(k for k in type(backend).__mro__
                       if k.__module__.startswith("repro."))
        got = {"ops": list(backend.ops), "n_devices": backend.n_devices,
               "dtype": backend.dtype, "epoch_isolation": m.epoch_isolation,
               "warmup": m.warmup, "cold_buffers": m.cold_buffers,
               "backend": program.__name__}
        want = {k: c[k] for k in got}
        bad = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
        if bad:
            raise BenchError(f"backend differs from the configuration "
                             f"(got, stated): {bad}")
        if jax.device_count() < int(c["n_devices"]):
            raise BenchError(f"the configuration needs {c['n_devices']} "
                             f"chips, JAX sees {jax.device_count()}")
        unknown = set(self.traffic["ops"]) - set(backend.ops)
        if unknown:
            raise BenchError(f"traffic ops {sorted(unknown)} are not among "
                             f"the backend's {list(backend.ops)}")

    def _campaign(self, epochs: int, nrep: int, seed: int, backend):
        from repro.campaign import Campaign, CampaignSpec
        from repro.core import ExperimentDesign, TestCase

        design = ExperimentDesign(n_launch_epochs=epochs, nrep=nrep,
                                  seed=seed)
        cases = [TestCase(op, m) for op, m in self.cases]
        res = Campaign(CampaignSpec(cases, design), backend).run()
        with self.run.spans.span("analysis"):
            for case in cases:
                res.table.medians(case)
        return res

    def setup(self):
        self.backend = self._backend()
        self._check_stated(self.backend)
        self._campaign(1, 1, 0, self.backend)    # compile every case

    def campaign(self, k: int):
        proxy = _BatchProxy(self, self.backend, self.run.spans,
                            self.run.deadline, calls=self.calls)
        res = self._campaign(int(self.traffic["n_launch_epochs"]),
                             int(self.traffic["nrep"]),
                             int(self.traffic["design_seed"]), proxy)
        return {"records": len(res.records)}

    def end_to_end(self) -> dict:
        return {"coll_us_p95": p95_geomean_us(self.calls.durations,
                                              self.calls.order)}

    def release(self):
        lens = [round(c["end"] - c["start"], 3) for c in self.run.campaigns]
        print(f"collectives: campaigns {lens} s, "
              f"{len(self.run.completed())} completed", file=sys.stderr)
        self.kept = [(name, batch, np.asarray(out))
                     for name, (batch, out) in self.calls.kept]
        self.calls.kept = []
        self.backend = None

    # -- the check ---------------------------------------------------------
    def checks(self) -> list[Check]:
        """``coll_max_abs_err``: the largest absolute gap between a kept
        result and the reference's on the payloads its batch was due;
        ``coll_cases_missing``: the traffic's cases with no kept timed call
        in the window. In the control, the reference computed in bfloat16
        stands in the program's place."""
        lim = self.config["check_limits"]
        n = int(self.config["n_devices"])
        dtype = self.config["dtype"]
        want: dict[tuple, np.ndarray] = {}
        seen: dict[str, int] = {}
        err = 0.0
        for name, batch, got in self.kept:
            op, msize = name.split("@")
            key = self.payload_key(name, batch)
            if key not in want:
                want = {key: ref_coll.result(op, int(msize), n, key, dtype)}
            if self.control:
                got = ref_coll.result(op, int(msize), n, key, dtype,
                                      compute="bfloat16")
            err = max(err, ref_coll.max_abs_err(got, want[key]))
            seen[name] = seen.get(name, 0) + 1
        missing = [case_name(op, m) for op, m in self.cases
                   if case_name(op, m) not in seen]
        print(f"collectives check: {len(self.kept)} kept calls over "
              f"{len(seen)} cases; per case {seen}; missing {missing}",
              file=sys.stderr)
        if not self.kept:
            err = float("inf")
        return [Check("coll_max_abs_err", err, lim["coll_max_abs_err"]),
                Check("coll_cases_missing", float(len(missing)),
                      lim["coll_cases_missing"])]


class _BatchProxy(BackendProxy):
    """The backend seen through the benchmark's probes, counting each
    case's ``measure`` calls: the batch a call's payloads are due to."""

    def __init__(self, cell, *a, **kw):
        super().__init__(*a, **kw)
        self._cell = cell

    def measure(self, ctx, case, nrep: int):
        self._check_open()
        name = case_name(case.op, case.msize)
        self._cell.batches[name] += 1
        self._cell.current = self._cell.batches[name]
        return super().measure(ctx, case, nrep)


def make_cell(config, traffic, run):
    return CollectivesCell(config, traffic, run)

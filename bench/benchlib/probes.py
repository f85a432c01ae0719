"""Benchmark-owned probes around the program's measuring path.

* :class:`Spans` records host-clock spans (and, in a traced run, the same
  spans as ``jax.profiler.TraceAnnotation`` named ``bench:<name>``, so
  the trace reduction can label idle gaps by what the host was doing).
* :class:`CallClock` wraps each timed callable of a ``JaxEpochContext``
  and takes the host clock at the start of every call; the time of a call
  is the distance to the start of the next one, or to the end of its
  ``measure`` batch. It keeps the outputs of a seeded sample of calls for
  the reference check.
* :class:`BackendProxy` wraps a measurement backend: it times ``measure``
  and ``measure_epochs`` as backend-call spans, closes the window by
  raising :class:`WindowClosed`, and exposes ``measure_epochs`` only where
  the wrapped backend has it, because ``Campaign`` probes for it.
"""

from __future__ import annotations

import contextlib
import time

import numpy as np


class WindowClosed(Exception):
    """Raised at the next backend call once the measured window is over."""


class Spans:
    def __init__(self, traced: bool):
        self.traced = traced
        self.items: list[tuple[str, float, float]] = []

    @contextlib.contextmanager
    def span(self, name: str):
        ann = contextlib.nullcontext()
        if self.traced:
            import jax

            ann = jax.profiler.TraceAnnotation(f"bench:{name}")
        t0 = time.perf_counter()
        try:
            with ann:
                yield
        finally:
            self.items.append((name, t0, time.perf_counter()))

    def of(self, name: str) -> list[tuple[float, float]]:
        return [(a, b) for n, a, b in self.items if n == name]


class CallClock:
    """Start times of every call of the wrapped callables, grouped into the
    ``measure`` batches that made them; ``keep(out)`` selects what of a
    call's output is retained for the reference check."""

    def __init__(self, traced: bool, rng: np.random.Generator,
                 keep_prob: float, keep=lambda out: out):
        self.traced = traced
        self.rng = rng
        self.keep_prob = keep_prob
        self.keep = keep
        self.durations: list[float] = []     # every timed call, seconds
        self.kept: list[tuple[str, object]] = []
        self._starts: list[float] | None = None
        self._outs: list = []                # (index in batch, name, kept)

    def wrap(self, name: str, fn):
        def call():
            if self._starts is not None:
                self._starts.append(time.perf_counter())
            if self.traced:
                import jax

                with jax.profiler.TraceAnnotation("bench:call"):
                    out = fn()
            else:
                out = fn()
            if self._starts is not None and \
                    self.rng.random() < self.keep_prob:
                self._outs.append((len(self._starts) - 1, name,
                                   self.keep(out)))
            return out
        return call

    @contextlib.contextmanager
    def batch(self, nrep: int):
        """One ``measure`` call: its last ``nrep`` calls are the timed ones."""
        self._starts, self._outs = [], []
        try:
            yield
            t_end = time.perf_counter()
            first = len(self._starts) - nrep
            starts = self._starts[first:] if nrep > 0 else []
            self.durations.extend(np.diff(starts + [t_end]).tolist())
            self.kept.extend((name, out) for i, name, out in self._outs
                             if i >= first and nrep > 0)
        finally:
            self._starts, self._outs = None, []


class _WrappingDict(dict):
    def __init__(self, clock: CallClock, items: dict):
        super().__init__()
        self._clock = clock
        for k, v in items.items():
            self[k] = v

    def __setitem__(self, key, fn):
        super().__setitem__(key, self._clock.wrap(key, fn))


class BackendProxy:
    """A measurement backend seen through the benchmark's probes."""

    def __init__(self, backend, spans: Spans, deadline: float,
                 calls: CallClock | None = None, on_result=None):
        self._backend = backend
        self._spans = spans
        self._calls = calls
        self._on_result = on_result
        self.deadline = deadline

    def __getattr__(self, name):
        attr = getattr(self._backend, name)
        if name == "measure_epochs":
            return self._measure_epochs(attr)
        return attr

    def _check_open(self):
        if time.perf_counter() > self.deadline:
            raise WindowClosed

    def make_epoch(self, epoch: int):
        self._check_open()
        ctx = self._backend.make_epoch(epoch)
        if self._calls is not None:
            ctx.callables = _WrappingDict(self._calls, ctx.callables)
        return ctx

    def measure(self, ctx, case, nrep: int):
        self._check_open()
        with self._spans.span("backend_call"):
            if self._calls is not None:
                with self._calls.batch(nrep):
                    out = self._backend.measure(ctx, case, nrep)
            else:
                out = self._backend.measure(ctx, case, nrep)
        return out

    def _measure_epochs(self, fn):
        def measure_epochs(work, design):
            self._check_open()
            with self._spans.span("backend_call"):
                out = fn(work, design)
            if self._on_result is not None and out:
                self._on_result(out)
            return out
        return measure_epochs

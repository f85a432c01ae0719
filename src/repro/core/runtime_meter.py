"""Measuring *real* jitted JAX computations with the paper's method (§6).

This is the deployment path of the methodology: the object under test is a
compiled XLA executable (a collective, a ``train_step``, a ``serve_step``)
rather than the simulator's cost model. The same experimental design
applies:

  * a **launch epoch** = a fresh executable. ``epoch_isolation``:
      - ``"clear_caches"``: ``jax.clear_caches()`` + re-trace per epoch
        (in-process analogue of a fresh mpirun; captures compilation/layout
        nondeterminism),
      - ``"none"``: same executable reused (isolates pure run-time noise).
    On a real multi-host pod, epochs are separate launcher invocations and
    this module is driven once per process by ``launch/train.py``.
  * ``nrep`` timed calls per case, each fenced by ``block_until_ready``
    (the device-level "barrier"; host timestamps around a fenced dispatch
    are the §3.2.1 local-times scheme),
  * Tukey filtering + per-epoch averages downstream, via
    :mod:`repro.core.design`.
"""

from __future__ import annotations

import gc
import os
import time
from dataclasses import dataclass
from typing import Any, Callable

import numpy as np

from repro.core import telemetry

__all__ = ["timed_calls", "JaxEpochContext", "make_jax_measure", "MeterConfig",
           "use_compile_cache"]


def use_compile_cache(root: str) -> str:
    """Turn on JAX's persistent compilation cache for an entry point that
    measures on a device, and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is configured here. Otherwise the cache lives at the fixed path
    ``<root>/.jax_cache``: a directory that moved between runs would never
    hit. Launch epochs clear the in-memory jit cache
    (:class:`JaxEpochContext`), so each epoch's recompile of a large
    program is read back from this cache.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(os.path.abspath(root), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def _warm(fn: Callable[[], Any], n: int) -> None:
    import jax

    for _ in range(n):
        jax.block_until_ready(fn())


def timed_calls(fn: Callable[[], Any], nrep: int, warmup: int = 3) -> np.ndarray:
    """Time ``nrep`` calls of a nullary ``fn`` whose result supports
    ``block_until_ready`` (or is a pytree of such). Under a profiler trace
    each timed call, dispatch and block together, is a ``timed_call``
    span (:mod:`repro.core.telemetry`)."""
    import jax

    telemetry.watch_compiles()
    _block = jax.block_until_ready
    _warm(fn, warmup)
    out = np.empty(nrep)
    for i in range(nrep):
        with telemetry.span("timed_call"):
            t0 = time.perf_counter_ns()
            _block(fn())
            out[i] = (time.perf_counter_ns() - t0) * 1e-9
    return out


@dataclass
class MeterConfig:
    warmup: int = 3
    epoch_isolation: str = "clear_caches"   # or "none"
    cold_buffers: bool = False               # §5.8 cache factor: fresh inputs per call


class JaxEpochContext:
    """Per-epoch context: builds (and owns) freshly-jitted callables.

    Warm-up is paid once per callable per epoch: adaptive-``nrep`` stopping
    asks for a sample in growing chunks, and re-warming every chunk would
    both waste wall-clock and re-measure the §5.8 cold-cache factor the
    epoch already amortized.
    """

    @telemetry.spanned("epoch_build")
    def __init__(self, build: Callable[[int], dict[str, Callable[[], Any]]],
                 epoch: int, config: MeterConfig):
        # before the epoch's first jit, so that its compiles are counted
        telemetry.watch_compiles()
        self.epoch = epoch
        self.config = config
        if config.epoch_isolation == "clear_caches":
            import jax

            jax.clear_caches()
            gc.collect()
        self.callables = build(epoch)
        self._warmed: set[str] = set()

    def measure(self, name: str, nrep: int) -> np.ndarray:
        fn = self.callables[name]
        if name not in self._warmed:
            self._warmed.add(name)
            # the epoch's first calls: re-jit, then a compile or a read of
            # the persistent compilation cache
            with telemetry.span("warmup"):
                _warm(fn, self.config.warmup)
        return timed_calls(fn, nrep, warmup=0)


def make_jax_measure(build: Callable[[int], dict[str, Callable[[], Any]]],
                     config: MeterConfig | None = None):
    """Adapters for :func:`repro.core.design.run_design`.

    ``build(epoch)`` returns a dict mapping case names (``op@msize``) to
    nullary jitted callables. Returns ``(epoch_factory, measure)``.
    """
    cfg = config or MeterConfig()

    def epoch_factory(epoch: int) -> JaxEpochContext:
        return JaxEpochContext(build, epoch, cfg)

    def measure(ctx: JaxEpochContext, case, nrep: int) -> np.ndarray:
        name = f"{case.op}@{case.msize}"
        if name not in ctx.callables and case.op in ctx.callables:
            name = case.op
        return ctx.measure(name, nrep)

    return epoch_factory, measure

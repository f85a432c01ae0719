"""The program's own spans and counters.

Spans
    :func:`span` marks a stretch of host work. It records only while a JAX
    profiler trace is active (``jax.profiler.trace``, ``start_trace`` or a
    TensorBoard capture); otherwise it returns one shared null context, at
    the cost of one check. While recording it does two things:

    * it enters ``jax.profiler.TraceAnnotation("repro:<name>")``, so the
      span lies in the profile beside the device's operations, on the
      trace's clock;
    * it keeps ``(name, t0_ns, t1_ns)`` on ``time.perf_counter_ns`` in an
      in-memory buffer (:func:`spans`), for a reader that has the profile's
      device operations but not its ``repro:`` events. Spans nest as one
      thread opens them, so their times say which holds which.

    The buffer holds at most :data:`CAP` spans; what it cannot hold is
    counted by :func:`dropped`, and :func:`reset_spans` empties it.

Counters
    :func:`count` adds to a named process-global counter and
    :func:`counters` reads them all. Once :func:`watch_compiles` has run
    (the simulator engine and the meter call it before they first use JAX),
    ``jax.monitoring`` listeners keep three more: ``compiles`` and
    ``compile_s`` (every executable built, compiled or read from the
    persistent cache, and the seconds it took) and ``compile_cache_reads``
    (those read from the persistent cache, so that ``compiles`` less
    ``compile_cache_reads`` were compiled).

This module never imports JAX: a process that does not use JAX pays
nothing for it.
"""

from __future__ import annotations

import contextlib
import functools
import sys
import threading
import time
from typing import NamedTuple

__all__ = ["span", "spanned", "spans", "dropped", "reset_spans", "tracing",
           "Span", "count", "counters", "reset_counters", "watch_compiles",
           "CAP", "PREFIX"]

#: Name prefix of the spans' profiler events.
PREFIX = "repro:"
#: Most spans the buffer keeps.
CAP = 1 << 17

_NULL = contextlib.nullcontext()
_lock = threading.Lock()
_buf: list[list] = []           # [name, t0_ns, t1_ns]
_dropped = 0
_annotation = None              # jax.profiler.TraceAnnotation, once seen


class Span(NamedTuple):
    name: str
    t0_ns: int
    t1_ns: int | None           # None while the span is open


def tracing() -> bool:
    """Whether a JAX profiler trace is recording (False without JAX)."""
    global _annotation
    if _annotation is None:
        if "jax" not in sys.modules:
            return False
        import jax.profiler

        _annotation = jax.profiler.TraceAnnotation
    return _annotation.is_enabled()


def span(name: str):
    """A context manager over a stretch of host work named ``name``.
    Records only while tracing."""
    # tracing(), inlined once JAX has been seen: this runs for every span
    if _annotation is None and not tracing() \
            or not _annotation.is_enabled():
        return _NULL
    return _Recording(name)


def spanned(name: str):
    """Decorate a function so that each call is a :func:`span`."""
    def wrap(fn):
        @functools.wraps(fn)
        def call(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return call
    return wrap


class _Recording:
    __slots__ = ("ann", "row")

    def __init__(self, name: str):
        self.ann = _annotation(PREFIX + name)
        self.row = [name, 0, None]

    def __enter__(self):
        global _dropped
        self.row[1] = time.perf_counter_ns()
        self.ann.__enter__()
        with _lock:
            if len(_buf) < CAP:
                _buf.append(self.row)
            else:
                _dropped += 1
        return self

    def __exit__(self, *exc):
        self.row[2] = time.perf_counter_ns()
        self.ann.__exit__(*exc)
        return False


def spans() -> list[Span]:
    """Every span kept so far, in the order they were opened."""
    with _lock:
        return [Span(*r) for r in _buf]


def dropped() -> int:
    """Spans not kept because the buffer was full."""
    return _dropped


def reset_spans() -> None:
    global _dropped
    with _lock:
        _buf.clear()
        _dropped = 0


_counters: dict[str, float] = {}


def count(name: str, n=1) -> None:
    with _lock:
        _counters[name] = _counters.get(name, 0) + n


def counters() -> dict:
    """A copy of every counter."""
    with _lock:
        return dict(_counters)


def reset_counters(*names: str) -> None:
    with _lock:
        for name in names:
            _counters.pop(name, None)


_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_watching = False


def watch_compiles() -> None:
    """Register (once) the ``jax.monitoring`` listeners behind the
    ``compiles``, ``compile_s`` and ``compile_cache_reads`` counters."""
    global _watching
    if _watching:
        return
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)
    _watching = True


def _on_duration(event: str, duration_secs: float, **kw) -> None:
    if event == _COMPILE_EVENT:
        count("compiles")
        count("compile_s", float(duration_secs))


def _on_event(event: str, **kw) -> None:
    if event == _CACHE_HIT_EVENT:
        count("compile_cache_reads")

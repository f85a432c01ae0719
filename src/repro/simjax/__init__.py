"""Accelerator-resident (JAX) port of the simulation hot path.

``run_windowed_jax`` jit-compiles the whole windowed-measurement grid —
AR(1)-lognormal duration sampling with the bimodal-tail/spike/imbalance
mixture of :class:`~repro.core.mpi_ops.SimCollective`, the cross-call
entry recurrence (a prefix-sum + running-max, mapped to
``jax.lax.associative_scan`` / ``lax.cummax``), and every local↔global
clock conversion — over the full ``(nrep, p)`` array at once. It is
exposed as ``run_windowed(..., engine="jax")`` and
``SimBackend(engine="jax")`` with zero call-site changes.

The port is float64 end to end (inside ``jax.enable_x64(True)``, through
the engine's one :func:`~repro.simjax.engine.x64` scope), so
its absolute-time arithmetic carries the same resolution as the numpy
engine; draws use JAX's counter-based PRNG, so — like PR 1's batching —
campaigns are statistically, not bit-wise, identical to the numpy engines
(``tests/test_batch_equivalence.py``).
"""

from .engine import (FusedWindowRun, SimJaxUnavailable, engine_stats,
                     have_jax, reset_engine_stats, run_windowed_epochs_jax,
                     run_windowed_jax)

__all__ = [
    "SimJaxUnavailable",
    "have_jax",
    "run_windowed_jax",
    "run_windowed_epochs_jax",
    "FusedWindowRun",
    "engine_stats",
    "reset_engine_stats",
]

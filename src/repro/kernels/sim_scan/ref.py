"""The simulator's duration sampler (``repro.simjax``), in plain jnp.

There is no Pallas version: the simulator is float64 end to end, and the
TPU's kernel compiler (Mosaic) accepts no f64 operand, nor any kernel
traced inside an x64 scope. XLA compiles this one for the chip.

The exact math of :meth:`repro.core.mpi_ops.SimCollective.sample_durations`
on pre-drawn noise: the AR(1) recurrence ``s_i = coeff * s_{i-1} + eps_i``
expressed as a prefix composition of affine maps ``s -> a*s + b`` — the
composition rule ``(a1, b1) . (a2, b2) = (a1*a2, b1*a2 + b2)`` is
associative, so ``lax.associative_scan`` evaluates the whole chain in
O(log n) depth — followed by the lognormal/bimodal-tail/spike mixture.

Uniform draws replace the numpy engine's sequential coin flips: a tail
fires when ``u_tail < tail_prob`` with magnitude ``1 + tail_shift *
uniform(0.7, 1.3)`` (``u_mag`` rescaled), a spike when ``u_spike <
spike_prob`` — the same marginals, order-free.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

__all__ = ["sim_durations_ref"]


def sim_durations_ref(eps, u_tail, u_mag, u_spike, *, coeff, state, t0,
                      tail_prob, tail_shift, spike_prob, spike_scale):
    """Returns ``(durations, s)`` — the sampled common durations and the
    full AR(1) state sequence (the caller carries ``s[-1]`` across calls)."""
    a = jnp.full_like(eps, coeff)

    def combine(lhs, rhs):
        a1, b1 = lhs
        a2, b2 = rhs
        return a1 * a2, b1 * a2 + b2

    A, B = lax.associative_scan(combine, (a, eps))
    s = A * state + B
    t = t0 * jnp.exp(s)
    mag = 1.0 + tail_shift * (0.7 + 0.6 * u_mag)
    t = jnp.where(u_tail < tail_prob, t * mag, t)
    t = jnp.where(u_spike < spike_prob, t * spike_scale, t)
    return t, s

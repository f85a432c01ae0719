"""Pallas TPU kernel for the Mamba-2 SSD chunked scan.

TPU adaptation of the SSD algorithm [arXiv:2405.21060]:

  * Grid ``(batch, head_groups, num_chunks)`` — chunks innermost and
    sequential; the inter-chunk recurrent state ``(hg, p, n)`` lives in f32
    VMEM scratch carried across chunk iterations (the GPU version
    materializes per-chunk states in HBM and runs a separate scan kernel;
    on TPU the sequential grid + persistent scratch fuses both passes).
  * Heads lead the sequence axis (``x`` is laid out ``(b, h, s, p)`` and
    ``dt*A`` as ``(b, h, s)``), so every block's last two dimensions are
    ``(hg, chunk)``, ``(chunk, p)`` or ``(chunk, n)`` and obey the TPU's
    (8, 128) tiling at model widths (hg=8, chunk=256).
  * Within a chunk everything is 2-D matmul work for the MXU, one head at
    a time: ``G = C B^T`` (l x l, shared by the group's heads), the
    decay-masked intra-chunk product, and the state outer products. The
    in-chunk cumulative sum of ``dt*A`` is a matmul with a triangular
    ones matrix — the TPU lowering has no ``cumsum``.
  * B/C are shared across heads (single SSD group, as in mamba2), so their
    blocks are indexed by chunk only and never copied per head group.

Validated against ``ref.ssd_chunked_ref`` in interpret mode
(tests/test_kernels.py); compiled for a described v5e at mamba2-1.3b widths
in tests/test_v5e_compile.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssd_scan_fwd"]

_HI = lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))     # contract the last dim of both operands
_TN = (((0,), (0,)), ((), ()))     # contract the first dim of both operands


def _kernel(x_ref, a_ref, b_ref, c_ref, y_ref, state_scr, *, l, hg):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    a = a_ref[0].astype(jnp.float32)                 # (hg, l)
    B = b_ref[0]                                     # (l, n)
    C = c_ref[0]                                     # (l, n)
    row = lax.broadcasted_iota(jnp.int32, (l, l), 0)
    col = lax.broadcasted_iota(jnp.int32, (l, l), 1)
    tri = row >= col                                 # t >= s
    # inclusive cumsum over the chunk, in both orientations
    cs = jnp.dot(a, (row <= col).astype(jnp.float32), precision=_HI,
                 preferred_element_type=jnp.float32)           # (hg, l)
    cs_t = lax.dot_general(tri.astype(jnp.float32), a, _NT, precision=_HI,
                           preferred_element_type=jnp.float32)  # (l, hg)
    g = lax.dot_general(C, B, _NT, preferred_element_type=jnp.float32)
    C32 = C.astype(jnp.float32)

    for j in range(hg):
        x = x_ref[0, j]                              # (l, p)
        ct = cs_t[:, j:j + 1]                        # (l, 1)
        # the chunk total (a lane reduction: Mosaic cannot broadcast a
        # single element sliced from lane l-1 across a whole tile)
        last = jnp.sum(a[j:j + 1, :], axis=1, keepdims=True)   # (1, 1)
        # intra-chunk: decay(t, s) = exp(cs_t - cs_s) for s <= t
        dec = jnp.exp(jnp.where(tri, ct - cs[j:j + 1, :], -jnp.inf))
        m = (g * dec).astype(x.dtype)
        y = jnp.dot(m, x, preferred_element_type=jnp.float32)
        # inter-chunk: the state carried in from earlier chunks
        st = state_scr[j]                            # (p, n) f32
        y = y + lax.dot_general(C32, st, _NT,
                                preferred_element_type=jnp.float32) \
            * jnp.exp(ct)
        y_ref[0, j] = y.astype(y_ref.dtype)
        # state update
        xw = x * jnp.exp(last - ct).astype(x.dtype)  # (l, p)
        contrib = lax.dot_general(xw, B, _TN,
                                  preferred_element_type=jnp.float32)
        state_scr[j] = st * jnp.exp(last) + contrib


@functools.partial(jax.jit, static_argnames=("chunk", "head_group", "interpret"))
def ssd_scan_fwd(x, dta, B, C, *, chunk=256, head_group=8, interpret=False):
    """x: (b, s, h, p); dta: (b, s, h); B/C: (b, s, n). Returns y like x.

    Requirements: s % chunk == 0, h % head_group == 0 (``ops.py`` checks).
    """
    b, s, h, p = x.shape
    n = B.shape[-1]
    l = min(chunk, s)
    hg = min(head_group, h)

    y = pl.pallas_call(
        functools.partial(_kernel, l=l, hg=hg),
        grid=(b, h // hg, s // l),
        in_specs=[
            pl.BlockSpec((1, hg, l, p), lambda ib, ig, ic: (ib, ig, ic, 0)),
            pl.BlockSpec((1, hg, l), lambda ib, ig, ic: (ib, ig, ic)),
            pl.BlockSpec((1, l, n), lambda ib, ig, ic: (ib, ic, 0)),
            pl.BlockSpec((1, l, n), lambda ib, ig, ic: (ib, ic, 0)),
        ],
        out_specs=pl.BlockSpec((1, hg, l, p),
                               lambda ib, ig, ic: (ib, ig, ic, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((hg, p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.transpose(x, (0, 2, 1, 3)), jnp.transpose(dta, (0, 2, 1)), B, C)
    return jnp.transpose(y, (0, 2, 1, 3))

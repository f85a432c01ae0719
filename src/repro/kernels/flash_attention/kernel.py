"""Pallas TPU flash-attention kernel (forward).

Design (TPU-native, not a CUDA port):

  * Grid ``(batch, q_heads, num_q_blocks, num_kv_blocks)`` with the KV-block
    dimension innermost and *arbitrary* (sequential) — the online-softmax
    running state (row max ``m``, normalizer ``l``, accumulator ``acc``)
    lives in VMEM scratch that persists across KV iterations, so the
    ``S x T`` score matrix never exists in HBM (this is the whole point:
    the dry-run shows the jnp reference path is memory-bound on score
    traffic; see EXPERIMENTS.md §Perf).
  * Block shapes ``(block_q, head_dim)`` / ``(block_k, head_dim)`` are
    MXU-aligned (multiples of 128 by default) and sized so the working set
    (q, k, v blocks + f32 accumulator) fits VMEM:
    ``(bq + 2*bk) * d * 2B + bq * d * 4B + bq * bk * 4B`` ≈ 1.3 MiB at
    the default 512/512/128.
  * GQA folds into the index map: the KV block for query head ``h`` is
    ``h // group``; MQA (gemma-2b, granite) is ``group == n_heads``.
  * Sliding window / logit soft-capping / decode offset / KV-length mask
    are supported; the window is passed as a scalar *input* (VMEM) so one
    compiled kernel serves both local and global layers of gemma-2/3 under
    a scanned layer stack.

Validated against ``ref.flash_attention_ref`` in interpret mode on CPU
(tests/test_kernels.py) across shapes, dtypes, GQA ratios, windows and
soft-caps; compiled for a described v5e in tests/test_v5e_compile.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

__all__ = ["flash_attention_fwd"]


def _kernel(win_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale, causal, logit_cap, q_offset, kv_len, bq, bk, nk, use_window):
    ik = pl.program_id(3)
    iq = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0]                                    # (bq, d)
    k = k_ref[0, 0]                                    # (bk, d)
    v = v_ref[0, 0]                                    # (bk, d)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale     # (bq, bk) f32
    if logit_cap and logit_cap > 0:
        s = logit_cap * jnp.tanh(s / logit_cap)

    qpos = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + iq * bq + q_offset
    kpos = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + ik * bk
    mask = jnp.ones((bq, bk), dtype=jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    if use_window:
        w = win_ref[0, 0]
        mask &= (qpos - kpos) < w
    if kv_len is not None:
        mask &= kpos < kv_len
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                                # (bq, 1) f32
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                             # (bq, bk) f32
    l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ik == nk - 1)
    def _finish():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)                # fully-masked rows -> 0
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "logit_cap", "q_offset", "kv_len",
                     "block_q", "block_k", "interpret", "use_window"))
def flash_attention_fwd(q, k, v, window=None, *, causal=True, logit_cap=0.0,
                        q_offset=0, kv_len=None, block_q=512, block_k=512,
                        interpret=False, use_window=False):
    """q: (B, H, S, D); k/v: (B, Hkv, T, D); window: () int32 or None.

    Returns (B, H, S, D). Static shape requirements: S % block_q == 0,
    T % block_k == 0 (``ops.py`` pads).
    """
    b, h, s, d = q.shape
    hkv, t = k.shape[1], k.shape[2]
    g = h // hkv
    bq = min(block_q, s)
    bk = min(block_k, t)
    nq = s // bq
    nk = t // bk
    if window is None:
        window = jnp.full((1, 1), jnp.iinfo(jnp.int32).max, jnp.int32)
    else:
        window = jnp.asarray(window, jnp.int32).reshape(1, 1)

    kernel = functools.partial(
        _kernel, scale=1.0 / np.sqrt(d), causal=causal, logit_cap=logit_cap,
        q_offset=q_offset, kv_len=kv_len, bq=bq, bk=bk, nk=nk,
        use_window=use_window)

    return pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1), lambda ib, ih, iq, ik: (0, 0)),
            pl.BlockSpec((1, 1, bq, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda ib, ih, iq, ik: (ib, ih // g, ik, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda ib, ih, iq, ik: (ib, ih // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(window, q, k, v)

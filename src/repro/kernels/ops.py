"""Jitted public wrappers for the Pallas kernels.

``flash_attention`` / ``ssd_scan`` accept model-layout tensors and pick
blocks that divide the sequence; a sequence no block divides raises rather
than running the reference in the kernel's place. Traced windows under a
scanned layer stack reach the kernel (the window is a kernel input); only
decode with a traced position takes the reference (see
:func:`flash_attention`). On a TPU the kernels always compile; elsewhere
they run in Pallas interpret mode, the only way they run on a CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .flash_attention.kernel import flash_attention_fwd
from .flash_attention.ref import flash_attention_ref
from .ssd_scan.kernel import ssd_scan_fwd
from .ssd_scan.ref import ssd_chunked_ref

__all__ = ["flash_attention", "ssd_scan", "make_benchmark_op", "BENCHMARK_OPS"]


def _interpret(interpret):
    """``None`` picks from the platform: compiled on a TPU, interpret mode
    anywhere else."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _block(n: int, want: int, what: str) -> int:
    """The largest block of at most ``want`` rows that divides ``n`` and is
    ``n`` itself or a multiple of 8 (the TPU's sublane tile)."""
    if n <= want:
        return n
    for b in range(want - want % 8, 7, -8):
        if n % b == 0:
            return b
    raise ValueError(f"{what}={n}: no block of at most {want} rows that is "
                     "a multiple of 8 divides it")


def flash_attention(q, k, v, *, causal=True, window=None, logit_cap=0.0,
                    q_offset=0, kv_len=None, block_q=512, block_k=512,
                    interpret=None):
    """q: (B, S, H, D); k/v: (B, T, Hkv, D) — model layout. Returns like q."""
    if not isinstance(q_offset, int) or (kv_len is not None and not isinstance(kv_len, int)):
        # Decode with a traced position (``attn_decode_step`` under jit):
        # the kernel bakes q_offset / kv_len into its masks as static
        # values, so a position that moves every step would recompile it
        # per token. The reference takes them as traced scalars; with one
        # query row per step both are a read of the cache.
        return flash_attention_ref(q, k, v, causal=causal, window=window,
                                   logit_cap=logit_cap, q_offset=q_offset,
                                   kv_len=kv_len)
    bq = _block(q.shape[1], block_q, "query length")
    bk = _block(k.shape[1], block_k, "key length")
    use_window = window is not None
    kernel = functools.partial(
        flash_attention_fwd, causal=causal, logit_cap=logit_cap,
        q_offset=q_offset, kv_len=kv_len, block_q=bq, block_k=bk,
        interpret=_interpret(interpret), use_window=use_window)
    ref = functools.partial(flash_attention_ref, causal=causal,
                            logit_cap=logit_cap, q_offset=q_offset,
                            kv_len=kv_len)

    # The kernel is forward-only: a train step differentiates through the
    # reference's VJP (recomputed from the saved inputs).
    @jax.custom_vjp
    def attend(q, k, v, win):
        out = kernel(jnp.transpose(q, (0, 2, 1, 3)),
                     jnp.transpose(k, (0, 2, 1, 3)),
                     jnp.transpose(v, (0, 2, 1, 3)), win)
        return jnp.transpose(out, (0, 2, 1, 3))

    def attend_fwd(q, k, v, win):
        return attend(q, k, v, win), (q, k, v, win)

    def attend_bwd(res, g):
        q, k, v, win = res
        _, vjp = jax.vjp(lambda q, k, v: ref(q, k, v, window=win), q, k, v)
        return (*vjp(g), None)

    attend.defvjp(attend_fwd, attend_bwd)
    win = None if window is None else jnp.asarray(window, jnp.int32)
    return attend(q, k, v, win)


def ssd_scan(x, dta, B, C, *, chunk=256, head_group=8, interpret=None):
    """Chunked SSD scan; x: (b, s, h, p), dta: (b, s, h), B/C: (b, s, n)."""
    h = x.shape[2]
    hg = min(head_group, h)
    if h % hg:
        raise ValueError(f"heads={h} is not a multiple of head_group={hg}")
    return ssd_scan_fwd(x, dta, B, C,
                        chunk=_block(x.shape[1], chunk, "sequence length"),
                        head_group=hg, interpret=_interpret(interpret))


# ---------------------------------------------------------------------------
# Operations-under-test for the measurement campaign (repro.campaign)
# ---------------------------------------------------------------------------

BENCHMARK_OPS = ("flash_attention", "ssd_scan")


def make_benchmark_op(op: str, impl: str = "pallas", *, seq: int,
                      batch: int = 1, heads: int = 4, kv_heads: int | None = None,
                      head_dim: int = 32, state_dim: int = 16,
                      dtype=jnp.float32, seed: int = 0):
    """Build a nullary jitted callable running one forward of ``op`` at
    sequence length ``seq`` — the operation-under-test factory for
    :class:`repro.campaign.KernelBackend`.

    ``impl="pallas"`` times the Pallas kernel (compiled on a TPU, interpret
    mode elsewhere); ``impl="ref"`` times the pure-jnp oracle. The kernel
    never hands a shape to its reference — that would make the A-vs-B
    comparison measure the same code twice — so a ``seq`` no block divides
    raises. The callable is a ``functools.partial`` of the jitted function
    over its inputs, so ``op.func.lower(*op.args)`` gives the compiled
    program to inspect.
    """
    if op not in BENCHMARK_OPS:
        raise ValueError(f"unknown benchmark op {op!r}; one of {BENCHMARK_OPS}")
    if impl not in ("pallas", "ref"):
        raise ValueError(f"unknown impl {impl!r}; use 'pallas' or 'ref'")
    rng = np.random.default_rng(seed + 7919 * seq)
    kv_heads = heads if kv_heads is None else kv_heads

    def _t(*shape, scale=1.0):
        return jnp.asarray(rng.normal(0.0, scale, shape), dtype)

    if op == "flash_attention":
        q = _t(batch, seq, heads, head_dim)
        k = _t(batch, seq, kv_heads, head_dim)
        v = _t(batch, seq, kv_heads, head_dim)
        if impl == "pallas":
            fn = jax.jit(lambda q, k, v: flash_attention(
                q, k, v, causal=True, block_q=128, block_k=128))
        else:
            fn = jax.jit(lambda q, k, v: flash_attention_ref(q, k, v,
                                                             causal=True))
        return functools.partial(fn, q, k, v)

    chunk = _block(seq, 256, "seq")      # mamba2's chunk length
    x = _t(batch, seq, heads, head_dim)
    dta = -jnp.abs(_t(batch, seq, heads, scale=0.5)) - 0.05
    B = _t(batch, seq, state_dim)
    C = _t(batch, seq, state_dim)
    if impl == "pallas":
        fn = jax.jit(lambda x, dta, B, C: ssd_scan(x, dta, B, C,
                                                   chunk=chunk))
    else:
        fn = jax.jit(lambda x, dta, B, C: ssd_chunked_ref(x, dta, B, C,
                                                          chunk)[0])
    return functools.partial(fn, x, dta, B, C)

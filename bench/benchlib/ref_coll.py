"""The plain reference of the collectives cell: the payloads and what each
collective makes of them, in numpy.

The payloads are the benchmark's, drawn from a key (the run's seed, the
case and the batch of its calls): ``n`` chips, each holding ``count``
standard normal values in the payload's dtype, which use its whole
mantissa. ``count`` is the payload's bytes over the item size, at least
``n`` and rounded up to a multiple of ``n``, the layout
``repro.campaign.JaxBackend`` places; for ``all_to_all`` each chip's
payload is ``n`` blocks of ``count / n``, block ``k`` bound for chip
``k``. The reference computes in float64; ``compute`` names a lower
precision to compute in instead (the control).

Nothing here imports the program.
"""

from __future__ import annotations

import numpy as np

OPS = ("psum", "all_gather", "all_to_all")
STREAM = 23         # the payloads' stream of the run's seed


def count(msize: int, n: int, itemsize: int = 4) -> int:
    """Values per chip for a payload of ``msize`` bytes on ``n`` chips."""
    c = max(n, -(-int(msize) // itemsize))
    return -(-c // n) * n


def payload(op: str, msize: int, n: int, key, dtype="float32") -> np.ndarray:
    """Every chip's payload, chip first: ``(n, count)``, or ``(n, n,
    count / n)`` for ``all_to_all``; ``key`` is a sequence of whole
    numbers, the same key gives the same payload."""
    dt = np.dtype(dtype)
    c = count(msize, n, dt.itemsize)
    rng = np.random.default_rng([STREAM, *(int(k) for k in key)])
    vals = rng.standard_normal((n, c), dtype=np.float32).astype(dt)
    if op == "all_to_all":
        vals = vals.reshape(n, n, c // n)
    return vals


def _precision(name):
    if name == "bfloat16":
        import ml_dtypes

        return np.dtype(ml_dtypes.bfloat16)
    return np.dtype(name)


def result(op: str, msize: int, n: int, key, dtype="float32",
           compute="float64") -> np.ndarray:
    """What every chip holds after the collective, chip first, in float64:
    the sum of all payloads (``psum``, added in chip order, each partial
    sum rounded to ``compute``), every payload in chip order
    (``all_gather``), or block ``c`` of every chip's payload in chip order
    (``all_to_all``, on chip ``c``); the payloads are first rounded to
    ``compute``."""
    cd = _precision(compute)
    x = payload(op, msize, n, key, dtype).astype(cd)
    if op == "psum":
        acc = x[0]
        for k in range(1, n):
            acc = (acc.astype(np.float64) + x[k].astype(np.float64)
                   ).astype(cd)
        out = np.repeat(acc[None].astype(np.float64), n, axis=0)
    elif op == "all_gather":
        out = np.repeat(x[None].astype(np.float64), n, axis=0)
    elif op == "all_to_all":
        out = np.swapaxes(x, 0, 1).astype(np.float64)
    else:
        raise ValueError(f"unknown collective {op!r}; one of {OPS}")
    return out


def max_abs_err(got, want) -> float:
    """Largest absolute gap; inf where the shapes differ."""
    got = np.asarray(got, np.float64)
    if got.shape != want.shape:
        return float("inf")
    return float(np.max(np.abs(got - want))) if want.size else 0.0

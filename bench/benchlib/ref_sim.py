"""Plain reference of the simulator's device programs, in numpy.

Given the inputs that the program handed to its sample and window
programs (seeds or keys, cost-model terms, clock and sync coefficients,
start time, window size), recompute what they must return, float64 end to
end, by the textbook recurrences:

* sample: ``s_i = c s_{i-1} + eps_i`` (AR(1), exact IIR filter), duration
  ``t0 exp(s_i)``, times ``1 + tail_shift (0.7 + 0.6 u)`` where a uniform
  falls under ``tail_prob``, times ``spike_scale`` under ``spike_prob``;
* window (the paper's Alg. 2 with global-clock deadlines): the deadline of
  each rank in true time, per-rank spans ``d max(0.25, 1 + imbalance)``,
  the entry recurrence, start/end per rank, START_LATE where a deadline is
  not after the previous end, TOOK_TOO_LONG where a global end passes the
  next window, and ``max(end) - min(start)`` in global time;
* the HCA clock synchronization (the paper's Sec. 4.4, Algs. 2-4): from
  the exchanges' true times, the clocks' affine maps and the round-trip
  times, each fitpoint (the exchange of median offset), the least-squares
  drift model of every pair, their merge up the power-of-two tree and the
  remaining ranks, and each model's intercept re-anchored from a SKaMPI
  ping-pong offset (midpoint of the tightest bounds).

The random draws are JAX's own PRNG (threefry) on the host CPU from the
same keys: the per-seed stream is part of what the simulator guarantees
(the same seed gives the same records). The rank-imbalance quantiles are
this module's own table. Nothing here imports the program.

``dtype=np.float32`` is the control: the same computation one precision
below what the configuration states.
"""

from __future__ import annotations

import functools

import numpy as np

START_LATE = 1        # the window flags of the paper's Alg. 2
TOOK_TOO_LONG = 2


def _cpu():
    import jax

    return jax.devices("cpu")[0]


@functools.lru_cache(maxsize=1)
def normal_quantiles() -> np.ndarray:
    """2^16 normal quantiles at the midpoints of equal-probability bins."""
    from scipy.special import ndtri

    return ndtri((np.arange(65536, dtype=np.float64) + 0.5) / 65536.0)


def _draws(key, n):
    """eps ~ N(0, 1) and three U[0, 1) streams of length ``n`` (float64)."""
    import jax
    import jax.numpy as jnp

    with jax.enable_x64(True), jax.default_device(_cpu()):
        key = jnp.asarray(np.asarray(key))
        k_eps, k_tail, k_mag, k_spike = jax.random.split(key, 4)
        out = [jax.random.normal(k_eps, (n,), jnp.float64)]
        out += [jax.random.uniform(k, (n,), jnp.float64)
                for k in (k_tail, k_mag, k_spike)]
        return [np.asarray(a, np.float64) for a in out]


def fused_key(seed: int, term: int):
    import jax

    with jax.enable_x64(True), jax.default_device(_cpu()):
        return np.asarray(jax.random.fold_in(jax.random.PRNGKey(int(seed)),
                                             int(term)))


def sample(key, n, *, t0, ar_state, noise_sigma, autocorr, tail_prob,
           tail_shift, spike_prob, spike_scale, dtype=np.float64):
    """Durations ``(n,)`` and the AR(1) state sequence ``(n,)``."""
    from scipy.signal import lfilter

    eps, u_tail, u_mag, u_spike = (a.astype(dtype) for a in _draws(key, n))
    c = dtype(autocorr)
    eps = dtype(noise_sigma) * eps
    s = lfilter(np.array([1.0], dtype), np.array([1.0, -c], dtype), eps,
                zi=np.array([c * dtype(ar_state)], dtype))[0].astype(dtype)
    t = dtype(t0) * np.exp(s)
    mag = dtype(1.0) + dtype(tail_shift) * (dtype(0.7) + dtype(0.6) * u_mag)
    t = np.where(u_tail < tail_prob, t * mag, t)
    t = np.where(u_spike < spike_prob, t * dtype(spike_scale), t)
    return t.astype(dtype), s


def fused_imbalance(key, npad: int, ch: int, p: int) -> np.ndarray:
    """Standard-normal rank-imbalance factors ``(npad, p)`` drawn as the
    fused window draws them: 16 random bits per value, one key per chunk
    of ``ch`` rows, mapped through the quantile table."""
    import jax
    import jax.numpy as jnp

    k2 = (p + 1) // 2
    with jax.enable_x64(True), jax.default_device(_cpu()):
        keys = jax.random.split(jnp.asarray(np.asarray(key)), npad // ch)
        bits = jax.vmap(lambda k: jax.random.bits(k, (ch, k2), jnp.uint32))(
            keys)
        bits = np.asarray(bits).reshape(npad, k2)
    idx = np.concatenate([bits & 0xFFFF, bits >> 16], axis=1)[:, :p]
    return normal_quantiles()[idx]


def epoch_imbalance(key, n: int, p: int) -> np.ndarray:
    """The per-epoch window's draw: float32 normals ``(n, p)``."""
    import jax
    import jax.numpy as jnp

    with jax.enable_x64(True), jax.default_device(_cpu()):
        z = jax.random.normal(jnp.asarray(np.asarray(key)), (n, p),
                              jnp.float32)
        return np.asarray(z, np.float64)


def window(durations, imbalance, *, t0, off, skew, scale, slope, intercept,
           init_t, rank_imbalance, start_time, win_size, dtype=np.float64):
    """``(times, errors, end_true)`` of ``len(durations)`` windows."""
    f = lambda a: np.asarray(a, dtype)   # noqa: E731
    dur, t0, off, skew, scale = f(durations), f(t0), f(off), f(skew), \
        f(scale)
    slope, intercept, init_t = f(slope), f(intercept), f(init_t)
    one = dtype(1.0)
    n = dur.shape[0]
    ws = dtype(win_size)
    targets = dtype(start_time) + ws * np.arange(n, dtype=dtype)
    local = (targets[:, None] + intercept) / (one - slope) + init_t
    deadline = (local / (one + scale) - off) / (one + skew)
    imb = dtype(rank_imbalance) * f(imbalance)
    span = dur[:, None] * np.maximum(dtype(0.25), one + imb)
    e = span.max(axis=1)
    dmax = deadline.max(axis=1)
    C = np.concatenate([np.zeros(1, dtype), np.cumsum(e[:-1], dtype=dtype)])
    all_in = C + np.maximum(t0.max(), np.maximum.accumulate(dmax - C))
    end = all_in[:, None] + span
    prev_end = np.concatenate([t0[None, :], end[:-1]], axis=0)
    start = np.maximum(deadline, prev_end)
    late = (deadline <= prev_end).any(axis=1)

    def to_global(t):
        adj = (off + (one + skew) * t) * (one + scale) - init_t
        return adj - (adj * slope + intercept)

    sg, eg = to_global(start), to_global(end)
    took = (eg > (targets + ws)[:, None]).any(axis=1)
    errors = np.where(late, START_LATE, 0) | np.where(took, TOOK_TOO_LONG, 0)
    times = eg.max(axis=1) - sg.min(axis=1)
    return times.astype(np.float64), errors.astype(np.int64), end


def _fit(x, y, dtype):
    """Least-squares line ``y = slope x + intercept``."""
    x, y = np.asarray(x, dtype), np.asarray(y, dtype)
    xm, ym = x.mean(dtype=dtype), y.mean(dtype=dtype)
    slope = np.sum((x - xm) * (y - ym), dtype=dtype) / np.sum(
        (x - xm) ** 2, dtype=dtype)
    return slope, ym - slope * xm


def _local(clock, t, dtype):
    off, skew, scale = (dtype(v) for v in clock)
    return (off + (dtype(1.0) + skew) * np.asarray(t, dtype)) * (
        dtype(1.0) + scale)


def fitpoint_model(ex, dtype=np.float64):
    """(slope, intercept) of one pair's drift model from its exchanges:
    ``ex`` holds the true times of the server's stamps and of the
    client's receipts ``(n_fitpts, n_exchanges)``, both clocks' ``(offset,
    skew, scale_error)``, both initial local times and the round-trip
    time."""
    srv = _local(ex["ref_clock"], ex["srv_true"], dtype) - dtype(ex["init_ref"])
    loc = _local(ex["cli_clock"], ex["recv_true"], dtype) - dtype(ex["init_cli"])
    diffs = loc - srv - dtype(ex["rtt"]) / dtype(2.0)
    mid = np.argsort(diffs, axis=1, kind="stable")[:, diffs.shape[1] // 2]
    rows = np.arange(diffs.shape[0])
    return _fit(loc[rows, mid], diffs[rows, mid], dtype)


def pingpong_offset(pp, dtype=np.float64) -> float:
    """SKaMPI's offset estimate of the client's clock minus the
    reference's: the midpoint of the tightest lower and upper bounds."""
    send = np.asarray(pp["send"], dtype) - dtype(pp["init_ref"])
    recv = np.asarray(pp["recv"], dtype) - dtype(pp["init_ref"])
    srv = np.asarray(pp["srv"], dtype) - dtype(pp["init_cli"])
    return dtype(0.5) * (np.max(srv - recv) + np.min(srv - send))


def _merge(mid, child, dtype):
    """The child's model relative to the reference, from the middle
    process's model (relative to the reference) and the child's (relative
    to the middle): exact composition."""
    (s1, i1), (s2, i2) = mid, child
    return (dtype(s1) + dtype(s2) - dtype(s1) * dtype(s2),
            dtype(i1) + dtype(i2) - dtype(s1) * dtype(i2))


def hca_models(fits: dict, offsets: dict, p: int, hierarchical: bool,
               dtype=np.float64):
    """Every rank's ``(slope, intercept)`` relative to rank 0 (arrays of
    ``p``), from the pairs' exchanges ``fits[(ref, client)]`` and the
    re-anchoring ping-pongs ``offsets[(ref, client)]`` (each with the
    client's adjusted time ``ts`` at which the offset holds)."""
    one = (dtype(0.0), dtype(0.0))

    def model(ref, cli):
        m = fitpoint_model(fits[(ref, cli)], dtype)
        if hierarchical:
            m = anchor(m, offsets[(ref, cli)])
        return m

    def anchor(m, pp):
        return m[0], pingpong_offset(pp, dtype) - m[0] * dtype(pp["ts"])

    maxpower = 2 ** int(np.floor(np.log2(p))) if p > 1 else 1
    sub = {i: {i: one} for i in range(p)}
    step = 2
    while step <= maxpower:
        for r in range(0, maxpower, step):
            c = r + step // 2
            m = model(r, c)
            for k, lm in sub[c].items():
                sub[r][k] = _merge(m, lm, dtype)
        step *= 2
    for j in range(p - maxpower):
        sub[0][maxpower + j] = _merge(sub[0][j], model(j, maxpower + j),
                                      dtype)
    models = [sub[0].get(i, one) for i in range(p)]
    if not hierarchical:
        models = [models[0]] + [anchor(models[r], offsets[(0, r)])
                                for r in range(1, p)]
    return (np.array([m[0] for m in models], np.float64),
            np.array([m[1] for m in models], np.float64))


def rel_gap(got, want) -> float:
    """Largest ``|got - want| / |want|`` (inf where shapes differ)."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        return float("inf")
    if got.size == 0:
        return 0.0
    with np.errstate(divide="ignore", invalid="ignore"):
        r = np.abs(got - want) / np.abs(want)
    r = np.where(np.isfinite(r) | (got == want), r, np.inf)
    return float(np.nanmax(np.where(got == want, 0.0, r)))


# ---------------------------------------------------------------------------
# The analysis: the paper's Alg. 6 and the Wilcoxon rank-sum verdicts
# ---------------------------------------------------------------------------

def tukey_kept(x: np.ndarray, dtype=np.float64) -> np.ndarray:
    x = np.asarray(x, dtype)
    if x.size < 4:
        return x
    q1, q3 = np.percentile(x, [25.0, 75.0]).astype(dtype)
    k = dtype(1.5)
    lo, hi = q1 - k * (q3 - q1), q3 + k * (q3 - q1)
    kept = x[(x >= lo) & (x <= hi)]
    return kept if kept.size else x


def epoch_summary(times, dtype=np.float64) -> tuple[float, float]:
    """(mean, median) of the Tukey-filtered sample (Alg. 6)."""
    k = tukey_kept(times, dtype)
    return float(np.mean(k, dtype=dtype)), float(np.median(k))


def verdict(p_less: float, p_greater: float, alpha: float = 0.05) -> str:
    """The comparison's conclusion at level ``alpha``."""
    if p_less <= alpha:
        return "A<B"
    if p_greater <= alpha:
        return "A>B"
    return "indistinguishable"


def rank_sum_p(a, b, alternative: str) -> float:
    """Wilcoxon rank-sum (Mann-Whitney) p-value, normal approximation with
    tie and continuity correction."""
    from scipy.stats import mannwhitneyu

    return float(mannwhitneyu(a, b, alternative=alternative,
                              method="asymptotic", use_continuity=True
                              ).pvalue)


def holm(p) -> np.ndarray:
    p = np.asarray(p, np.float64)
    m = p.size
    order = np.argsort(p, kind="mergesort")
    adj = np.maximum.accumulate((m - np.arange(m)) * p[order])
    out = np.empty(m)
    out[order] = np.minimum(adj, 1.0)
    return out

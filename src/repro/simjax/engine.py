"""JIT-compiled windowed-measurement engine (the ``engine="jax"`` path).

One measurement call lowers to two jitted programs:

  * ``sample`` — per cost-model term: draw the AR(1) innovations and
    mixture uniforms, run the linear recurrence as a
    ``lax.associative_scan`` over affine maps ``(a, b)`` (composition
    ``(a1, b1) ∘ (a2, b2) = (a1 a2, b1 a2 + b2)`` is associative, so the
    scan is exact, not an approximation), and apply the
    lognormal/bimodal-tail/spike mixture
    (:mod:`repro.kernels.sim_scan.ref`, plain jnp: the TPU's kernel
    compiler takes no f64 operand);
  * ``window`` — deadline conversion, the cross-call entry recurrence
    ``all_in_i = C_i + max(max_r t0_r, cummax_i(dmax - C))``, per-rank
    finish imbalance, START_LATE / TOOK_TOO_LONG flags and global-time
    estimates, over the whole ``(nrep, p)`` grid.

Host-side work per call is O(p): clock/sync model coefficients, per-term
epoch biases (through the same :func:`~repro.core.clocks.derive_stream`
helper as the numpy engines), the AR(1) carry in/out, and the PRNG keys,
derived on the host by :func:`_fold_in` (bit-identical to
``jax.random.fold_in(jax.random.PRNGKey(seed), j)``). Between its device
programs the host issues no eager JAX operation: the terms' durations are
summed (and the fused engine's lanes padded and split) by one jitted
helper, and every device result of the call is read in one
``jax.device_get`` after all of the call's programs are dispatched — one
round-trip per call, unless a cost-model object appears in two terms of
the op, whose AR(1) carry must come back before the next term samples.

Small ``nrep`` are padded to a power-of-two bucket so adaptive campaigns
hit a handful of compiled shapes instead of recompiling per top-up; padded
windows are computed and discarded (the entry recurrence is forward-only,
so the first ``nrep`` windows are unaffected).

:func:`run_windowed_epochs_jax` is the campaign-resident variant: duration
sampling is vmapped over a per-epoch key axis (``fold_in`` of each epoch's
seed, so per-epoch draws stay bit-identical to the per-epoch engine) and
the window recurrence runs as a chunked ``lax.scan`` whose ``(chunk, p)``
working set stays cache-resident — one compiled trace per ``(op,
shape-bucket)`` serves every epoch and grid cell of a campaign. The fused
window computes its per-rank arithmetic in float32 on window-relative
times (the f64 absolute frame is carried by the O(nrep) chain only) and
draws the finish-imbalance factors from a 2^16-entry normal-quantile
table instead of per-value erfinv; its observations are therefore
statistically indistinguishable from the per-epoch engine's rather than
bit-identical (the sampled *durations* remain bit-identical).

Both engines meter themselves: :func:`engine_stats` counts compiled traces
and dispatches of the sample and window programs, so "one trace per
campaign" is a measured quantity, and the ``sim_host_reads`` counter
(:mod:`repro.core.telemetry`) counts the blocking host reads of device
results. Under a profiler trace, each engine call is a ``sim_engine`` span
and each such read a ``sim_wait`` span.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import numpy as np

from repro.core import telemetry
from repro.core.window import START_LATE, TOOK_TOO_LONG, WindowRun

__all__ = [
    "SimJaxUnavailable",
    "have_jax",
    "run_windowed_jax",
    "run_windowed_epochs_jax",
    "FusedWindowRun",
    "engine_stats",
    "reset_engine_stats",
]


class SimJaxUnavailable(RuntimeError):
    """The jax engine cannot run this request (no jax, or non-affine
    clocks). ``resolve_engine`` maps this to a numpy-engine fallback."""


@functools.lru_cache(maxsize=1)
def have_jax() -> bool:
    try:
        import jax  # noqa: F401
        return True
    except Exception:
        return False


def x64():
    """The engine's float64 scope (a context manager): every jitted program
    of this module is traced and called inside it, because the simulator
    is f64 end to end."""
    import jax

    return jax.enable_x64(True)


def _bucket(nrep: int) -> int:
    """Compiled-shape bucket: next power of two (>= 32) below 1024, exact
    above — campaigns reuse a few small shapes, benchmarks compile once."""
    if nrep >= 1024:
        return nrep
    n = 32
    while n < nrep:
        n *= 2
    return n


_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))


def _fold_in(seed, data) -> np.ndarray:
    """``jax.random.fold_in(jax.random.PRNGKey(seed), data)`` computed on
    the host, broadcast over ``seed`` and ``data`` (each in [0, 2^32)).

    ``PRNGKey(seed)`` is the raw key ``(0, seed)`` and ``fold_in`` hashes
    the counter ``(0, data)`` under it with Threefry-2x32 (20 rounds), so
    this is that hash in numpy ``uint32`` arithmetic, which wraps as the
    device's does. Returns raw keys, ``uint32`` of shape
    ``broadcast(seed, data).shape + (2,)``."""
    k1, x1 = np.broadcast_arrays(np.asarray(seed, dtype=np.uint32),
                                 np.asarray(data, dtype=np.uint32))
    shape = k1.shape
    # 1-D, so every wrap is array arithmetic (numpy warns on scalar wraps)
    k1, x1 = k1.reshape(-1), x1.reshape(-1)
    ks = (np.uint32(0), k1, k1 ^ np.uint32(0x1BD11BDA))
    x0 = np.zeros_like(x1)
    x1 = x1 + k1
    for i in range(5):
        for r in _ROTATIONS[i % 2]:
            x0 = x0 + x1
            x1 = ((x1 << r) | (x1 >> (32 - r))) ^ x0
        x0 = x0 + ks[(i + 1) % 3]
        x1 = x1 + ks[(i + 2) % 3] + np.uint32(i + 1)
    return np.stack([x0, x1], axis=-1).reshape(shape + (2,))


class _EngineStats:
    """Process-global jit telemetry, kept in :mod:`repro.core.telemetry`'s
    counters ``sim_dispatches`` and ``sim_traces``: every device dispatch
    is counted, and trace keys (jitted function x static/shape signature)
    are collected so ``sim_traces`` counts distinct compiled signatures.
    Monotone by design — like the jit cache it mirrors — so a
    snapshot-delta of the counts is the per-campaign telemetry."""

    __slots__ = ("trace_keys",)

    def __init__(self) -> None:
        self.trace_keys: set = set()

    def count(self, trace_key: tuple) -> None:
        telemetry.count("sim_dispatches")
        if trace_key not in self.trace_keys:
            self.trace_keys.add(trace_key)
            telemetry.count("sim_traces")


_STATS = _EngineStats()


def engine_stats() -> dict:
    """Cumulative jit telemetry: ``n_traces`` (distinct compiled
    signatures) and ``n_dispatches`` (device calls). Campaigns and the
    bench harness snapshot this before/after and report the delta."""
    c = telemetry.counters()
    return {"n_traces": c.get("sim_traces", 0),
            "n_dispatches": c.get("sim_dispatches", 0)}


def reset_engine_stats() -> None:
    telemetry.reset_counters("sim_dispatches", "sim_traces")
    _STATS.trace_keys.clear()


def _read(jax, tree):
    """One blocking host read of the device results in ``tree`` (a
    ``sim_wait`` span, counted by ``sim_host_reads``): every array's copy
    starts before the first is waited for."""
    with telemetry.span("sim_wait"):
        out = jax.device_get(tree)
    telemetry.count("sim_host_reads")
    return out


def _chained(term_subs) -> bool:
    """Whether a cost-model object appears in two terms (``term_subs[j]``
    lists term ``j``'s objects): its AR(1) carry out of the earlier term is
    the later term's carry in, so it must be read back in between."""
    ids = [{id(s) for s in subs} for subs in term_subs]
    return sum(map(len, ids)) > len(set().union(*ids))


def _chunk_for(p: int, n: int) -> int:
    """Rep-axis chunk of the fused window scan: sized so one ``(chunk, p)``
    float32 block is ~512 KB (cache-resident through the ~10 elementwise
    passes), never larger than the bucketed ``n`` itself."""
    ch = max(1, 131072 // max(1, p))
    ch = max(256, min(8192, 1 << (ch.bit_length() - 1)))
    return min(ch, n)


def _cumsum(x):
    """Inclusive prefix sum of a 1-D array as an associative scan. XLA:TPU
    lowers ``jnp.cumsum`` to a reduce-window, whose f64 emulation takes
    minutes to compile (207 s for 2048 doubles on a described v5e)."""
    import jax.numpy as jnp
    from jax import lax

    return lax.associative_scan(jnp.add, x)


def _cummax(x):
    """Inclusive running max, as an associative scan for the same reason."""
    import jax.numpy as jnp
    from jax import lax

    return lax.associative_scan(jnp.maximum, x)


@functools.lru_cache(maxsize=1)
def _cores():
    """The raw (un-jitted) sample/window math, built once. Shared by the
    per-epoch and the fused builders so the fused engine's vmapped duration
    sampling runs byte-for-byte the same program per epoch key. Raises
    :class:`SimJaxUnavailable` when jax is missing."""
    if not have_jax():
        raise SimJaxUnavailable("engine='jax' requires jax, which is not "
                                "importable in this environment")
    import jax
    import jax.numpy as jnp

    telemetry.watch_compiles()

    def sample(key, t0_op, ar_state, noise_sigma, autocorr, tail_prob,
               tail_shift, spike_prob, spike_scale, *, n):
        k_eps, k_tail, k_mag, k_spike = jax.random.split(key, 4)
        eps = noise_sigma * jax.random.normal(k_eps, (n,), jnp.float64)
        u_tail = jax.random.uniform(k_tail, (n,), jnp.float64)
        u_mag = jax.random.uniform(k_mag, (n,), jnp.float64)
        u_spike = jax.random.uniform(k_spike, (n,), jnp.float64)
        from repro.kernels.sim_scan.ref import sim_durations_ref

        return sim_durations_ref(eps, u_tail, u_mag, u_spike, coeff=autocorr,
                  state=ar_state, t0=t0_op, tail_prob=tail_prob,
                  tail_shift=tail_shift, spike_prob=spike_prob,
                  spike_scale=spike_scale)

    def window(durations, key, t0, off, skew, scale, slope, intercept,
               init_t, rank_imbalance, start_time, win_size):
        n = durations.shape[0]
        p = t0.shape[0]
        targets = start_time + win_size * jnp.arange(n, dtype=jnp.float64)
        # deadline: sync-model denormalize, then the affine clock inverse
        dl_local = (targets[:, None] + intercept[None, :]) \
            / (1.0 - slope[None, :]) + init_t[None, :]
        raw = dl_local / (1.0 + scale[None, :])
        deadline_true = (raw - off[None, :]) / (1.0 + skew[None, :])
        # f32 draw, f64 math: threefry bit generation is the hot spot and a
        # multiplicative spread factor needs ~1e-2 resolution, not 1e-16
        imb = rank_imbalance * jax.random.normal(
            key, (n, p), jnp.float32).astype(jnp.float64)
        span = durations[:, None] * jnp.maximum(0.25, 1.0 + imb)
        e = span.max(axis=1)
        dmax = deadline_true.max(axis=1)
        C = jnp.concatenate([jnp.zeros((1,), e.dtype), _cumsum(e[:-1])])
        all_in = C + jnp.maximum(jnp.max(t0), _cummax(dmax - C))
        end = all_in[:, None] + span
        prev_end = jnp.concatenate([t0[None, :], end[:-1]], axis=0)
        start = jnp.maximum(deadline_true, prev_end)
        late = (deadline_true <= prev_end).any(axis=1)

        def to_global(t_true):
            local = (off[None, :] + (1.0 + skew[None, :]) * t_true) \
                * (1.0 + scale[None, :])
            adj = local - init_t[None, :]
            return adj - (adj * slope[None, :] + intercept[None, :])

        sg = to_global(start)
        eg = to_global(end)
        took = (eg > (targets + win_size)[:, None]).any(axis=1)
        errors = jnp.where(late, START_LATE, 0) \
            | jnp.where(took, TOOK_TOO_LONG, 0)
        times = eg.max(axis=1) - sg.min(axis=1)
        return times, errors, sg, eg, start, end

    return jax, sample, window


@functools.lru_cache(maxsize=1)
def _jitted():
    """Build (once) the jitted per-epoch sample/window cores."""
    jax, sample, window = _cores()
    return (jax,
            jax.jit(sample, static_argnames=("n",)),
            jax.jit(window))


@functools.lru_cache(maxsize=1)
def _norm_lut():
    """2^16-entry float32 normal-quantile table (quantile midpoints, so
    the discretized draw is exactly stratified): the fused window's
    imbalance draw replaces per-value erfinv with 16 random bits + a
    cache-resident gather."""
    from scipy.special import ndtri

    q = (np.arange(65536, dtype=np.float64) + 0.5) / 65536.0
    return ndtri(q).astype(np.float32)


@functools.lru_cache(maxsize=1)
def _jitted_fused():
    """Build (once) the campaign-resident cores:

    * ``sample_epochs`` — the per-epoch :func:`_cores` ``sample`` vmapped
      over an epoch axis of keys derived per epoch seed (bit-identical per
      lane to the per-epoch engine);
    * ``window_fused``  — the window recurrence as a chunked ``lax.scan``:
      per-rank arithmetic in float32 on window-relative times, the
      sequential f64 chain (entry cumsum/cummax, previous-window rows)
      carried across chunks, LUT-quantile imbalance draw, and only the
      O(nrep) outputs materialized.
    """
    jax, sample, _ = _cores()
    import jax.numpy as jnp
    from jax import lax

    lut = jnp.asarray(_norm_lut())

    def sample_epochs(seeds, j, t0_op, ar_state, noise_sigma, autocorr,
                      tail_prob, tail_shift, spike_prob, spike_scale, nrep,
                      *, n):
        def one(seed, t0e, are):
            key = jax.random.fold_in(jax.random.PRNGKey(seed), j)
            dur, s = sample(key, t0e, are, noise_sigma, autocorr, tail_prob,
                            tail_shift, spike_prob, spike_scale, n=n)
            return dur, s[nrep - 1]
        return jax.vmap(one)(seeds, t0_op, ar_state)

    def window_fused(durations, key, t0, off, skew, scale, slope, intercept,
                     init_t, rank_imbalance, start_time, win_size, nrep,
                     *, ch):
        npad = durations.shape[0]
        nch = npad // ch
        p = t0.shape[0]
        # Per-rank affine constants: deadline_true and to_global are both
        # affine in the target time, so the (n, p) grids reduce to
        # slope/anchor pairs evaluated on window-relative f32 offsets.
        alpha = 1.0 / ((1.0 - slope) * (1.0 + scale) * (1.0 + skew))
        beta = ((intercept / (1.0 - slope) + init_t) / (1.0 + scale)
                - off) / (1.0 + skew)
        gamma = (1.0 - slope) * (1.0 + scale) * (1.0 + skew)
        delta = (off * (1.0 + scale) - init_t) * (1.0 - slope) - intercept
        T0 = start_time
        d0_32 = ((alpha - 1.0) * T0 + beta).astype(jnp.float32)
        g0_32 = ((gamma - 1.0) * T0 + delta).astype(jnp.float32)
        am1_32 = (alpha - 1.0).astype(jnp.float32)
        gm1_32 = (gamma - 1.0).astype(jnp.float32)
        gam32 = gamma.astype(jnp.float32)
        maxt0 = jnp.max(t0)
        ws32 = jnp.asarray(win_size, jnp.float32)
        ri32 = jnp.asarray(rank_imbalance, jnp.float32)
        t0rel32 = (t0 - T0).astype(jnp.float32)
        k2 = (p + 1) // 2
        keys = jax.random.split(key, nch)
        nrep1 = nrep - 1

        def step(carry, xs):
            Crun, cmax, prev_last, et_sel = carry
            dur_i, key_i, ic = xs
            tau = win_size * (ic * ch + jnp.arange(ch, dtype=jnp.float64))
            tau32 = tau.astype(jnp.float32)[:, None]
            bits = jax.random.bits(key_i, (ch, k2), jnp.uint32)
            idx = jnp.concatenate([bits & 0xFFFF, bits >> 16],
                                  axis=1)[:, :p]
            z = lut[idx]
            drel = am1_32[None, :] * tau32 + d0_32[None, :]
            dur32 = dur_i.astype(jnp.float32)[:, None]
            span = dur32 * jnp.maximum(jnp.float32(0.25), 1.0 + ri32 * z)
            e = span.max(axis=1).astype(jnp.float64)
            dmaxrel = drel.max(axis=1).astype(jnp.float64)
            T = T0 + tau
            C = Crun + jnp.concatenate(
                [jnp.zeros((1,), jnp.float64), _cumsum(e[:-1])])
            cm = _cummax(jnp.concatenate([cmax[None],
                                             T + dmaxrel - C]))[1:]
            all_in = C + jnp.maximum(maxt0, cm)
            A32 = (all_in - T).astype(jnp.float32)[:, None]
            endrel = A32 + span
            prevrel = jnp.concatenate([prev_last[None, :], endrel[:-1]],
                                      axis=0) - ws32
            startrel = jnp.maximum(drel, prevrel)
            late = (drel <= prevrel).any(axis=1)
            base = gm1_32[None, :] * tau32 + g0_32[None, :]
            egrel = base + gam32[None, :] * endrel
            sgrel = base + gam32[None, :] * startrel
            took = (egrel > ws32).any(axis=1)
            errors = jnp.where(late, START_LATE, 0) \
                | jnp.where(took, TOOK_TOO_LONG, 0)
            times = egrel.max(axis=1).astype(jnp.float64) \
                - sgrel.min(axis=1).astype(jnp.float64)
            # end_true row nrep-1 (the net.t carry-out) without
            # materializing the (n, p) grid: grab it in the chunk it lives
            local = nrep1 - ic * ch
            hit = (local >= 0) & (local < ch)
            row = lax.dynamic_slice_in_dim(
                endrel, jnp.clip(local, 0, ch - 1), 1, axis=0)[0]
            et_sel = jnp.where(hit, row, et_sel)
            return (C[-1] + e[-1], cm[-1], endrel[-1], et_sel), \
                (times, errors)

        init = (jnp.float64(0.0), jnp.float64(-jnp.inf), t0rel32 + ws32,
                jnp.zeros((p,), jnp.float32))
        (_, _, _, et_sel), (times, errors) = lax.scan(
            step, init, (durations.reshape(nch, ch), keys,
                         jnp.arange(nch)))
        et_last = et_sel.astype(jnp.float64) + (T0 + win_size * nrep1)
        return times.reshape(-1), errors.reshape(-1), et_last

    return (jax,
            jax.jit(sample_epochs, static_argnames=("n",)),
            jax.jit(window_fused, static_argnames=("ch",)))


@functools.lru_cache(maxsize=1)
def _jitted_lanes():
    """Build (once) the jitted helper that turns the terms' sampled
    durations into the window programs' input, so the host issues no eager
    op for it: ``lanes(durs, npad=)`` sums the tuple ``durs`` in term order;
    an ``(E, n)`` sum is padded to ``npad`` with each lane's last value
    (padded windows are computed and discarded) and returned as ``E``
    ``(npad,)`` lanes, an ``(n,)`` sum is returned as it is."""
    jax, _, _ = _cores()
    import jax.numpy as jnp

    def lanes(durs, *, npad):
        d = functools.reduce(jnp.add, durs)
        if d.ndim == 1:
            return d
        E, n = d.shape
        if npad > n:
            d = jnp.concatenate(
                [d, jnp.broadcast_to(d[:, n - 1:n], (E, npad - n))], axis=1)
        return tuple(d[e] for e in range(E))

    return jax.jit(lanes, static_argnames=("npad",))


@dataclass
class FusedWindowRun:
    """O(nrep) outputs of one fused epoch. The ``(nrep, p)`` global-time
    grids of :class:`WindowRun` are deliberately not materialized — the
    fused engine keeps only what campaign records consume."""

    times: np.ndarray
    errors: np.ndarray

    @property
    def valid_times(self) -> np.ndarray:
        return self.times[self.errors == 0]


def _rank_sharding(p: int):
    """NamedSharding splitting the rank axis across all visible devices
    (None when single-device, or when ``p`` does not divide evenly). The
    fused window's cross-rank reductions (max / min / any) are
    order-independent, so the sharded program is bitwise-identical to the
    single-device one — which is what the forced-host-device CI asserts."""
    if not have_jax():
        return None
    import jax

    devs = jax.devices()
    if len(devs) <= 1 or p % len(devs) != 0:
        return None
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.array(devs), ("ranks",))
    return NamedSharding(mesh, PartitionSpec("ranks"))


def _terms(op, p: int, msize: int):
    """Flatten an op into ``(term, term_p, term_msize)`` triples —
    composites sample each constituent at its own size/count and sum,
    exactly like ``SimCompositeOp.sample_durations``."""
    sub_terms = getattr(op, "terms", None)
    if not sub_terms:
        return [(op, p, msize)]
    out = []
    for sub, ms, ps in sub_terms:
        out.append((sub, op._term_p(p, ps), max(0, int(round(ms * msize)))))
    return out


@telemetry.spanned("sim_engine")
def run_windowed_jax(net, sync, op, msize, nrep, win_size,
                     ranks=None) -> WindowRun:
    """JAX port of ``run_windowed``'s batch engine (affine clocks only).

    Strict by design: raises :class:`SimJaxUnavailable` on random-walk
    clocks or a missing jax instead of silently degrading —
    ``resolve_engine`` is the sanctioned soft-fallback path.
    """
    ranks = list(range(net.p)) if ranks is None else ranks
    p = len(ranks)
    if not all(net.clocks[r].rw_sigma <= 0.0 for r in ranks):
        raise SimJaxUnavailable(
            "engine='jax' requires affine clocks (rw_sigma == 0); use "
            "engine='batch_rw' (or 'auto') for random-walk clocks")
    jax, sample, window = _jitted()
    if nrep <= 0:
        empty = np.empty((0, p))
        return WindowRun(times=np.empty(0),
                         errors=np.empty(0, dtype=np.int64),
                         start_global_est=empty, end_global_est=empty.copy(),
                         start_true=empty.copy(), end_true=empty.copy())

    g_now = max(sync.global_time(net, r) for r in ranks)
    start_time = g_now + win_size
    n = _bucket(nrep)
    seed = int(net.rng.integers(2**31))
    terms = _terms(op, p, msize)

    t0 = np.asarray(net.t[ranks], dtype=np.float64)
    off = np.array([net.clocks[r].offset for r in ranks])
    skew = np.array([net.clocks[r].skew for r in ranks])
    scale = np.array([net.clocks[r].scale_error for r in ranks])
    slope = np.array([sync.models[r].slope for r in ranks])
    intercept = np.array([sync.models[r].intercept for r in ranks])
    init_t = np.array([sync.initial_times[r] for r in ranks])

    # keys j < len(terms) sample the terms, key len(terms) the window
    keys = _fold_in(seed, np.arange(len(terms) + 1))
    chained = _chained([[sub] for sub, _, _ in terms])
    with x64():
        durs, carries = [], []
        for j, (sub, tp, tm) in enumerate(terms):
            t0_op = sub.base_time(tp, tm) * sub._bias_for(net)
            _STATS.count(("sample", n))
            dur, s = sample(keys[j], t0_op, sub._ar_state, sub.noise_sigma,
                            sub.autocorr, sub.tail_prob, sub.tail_shift,
                            sub.spike_prob, sub.spike_scale, n=n)
            durs.append(dur)
            if chained:
                sub._ar_state = float(_read(jax, s)[nrep - 1])
            else:
                carries.append(s)
        durations = durs[0] if len(durs) == 1 \
            else _jitted_lanes()(tuple(durs), npad=n)
        _STATS.count(("window", n, p))
        out = window(durations, keys[-1], t0, off, skew, scale, slope,
                     intercept, init_t, op.rank_imbalance, start_time,
                     win_size)
        carries, out = _read(jax, (carries, out))
    for (sub, _, _), s in zip(terms, carries):
        sub._ar_state = float(s[nrep - 1])
    times, errors, sg, eg, st, et = out
    run = WindowRun(times=np.asarray(times[:nrep], dtype=np.float64),
                    errors=np.asarray(errors[:nrep], dtype=np.int64),
                    start_global_est=np.asarray(sg[:nrep], dtype=np.float64),
                    end_global_est=np.asarray(eg[:nrep], dtype=np.float64),
                    start_true=np.asarray(st[:nrep], dtype=np.float64),
                    end_true=np.asarray(et[:nrep], dtype=np.float64))
    net.t[ranks] = run.end_true[nrep - 1]
    return run


@telemetry.spanned("sim_engine")
def run_windowed_epochs_jax(nets, syncs, ops, msize, nrep, win_size,
                            ranks=None) -> "list[FusedWindowRun]":
    """Measure one case across all launch epochs in fused device programs.

    ``nets[e] / syncs[e] / ops[e]`` are epoch ``e``'s simulator objects (one
    triple per launch epoch, exactly what the per-epoch engine would see).
    Duration sampling runs as ONE vmapped dispatch per cost-model term
    (bit-identical per epoch lane to :func:`run_windowed_jax`: the same
    ``_cores`` sample program under the same per-epoch ``fold_in`` keys);
    the window recurrence dispatches per epoch — start times differ — but
    every dispatch reuses one chunked-scan trace per ``(p, shape-bucket)``.
    Each epoch's window key is derived on the host (:func:`_fold_in` of
    the epoch seed and the number of terms); the terms' durations are
    summed, padded and split into per-epoch lanes by one jitted helper;
    all ``E`` windows are dispatched before the call's one host read, which
    takes every window's times, flags and end state and the terms' AR(1)
    carries together (a cost-model object in two terms has its carry read
    after its earlier term instead).
    Host-side RNG order per epoch (window seed, then per-term epoch biases)
    matches the per-epoch engine, and the AR(1) carry and ``net.t``
    writebacks land exactly as ``E`` sequential per-epoch calls would, so a
    campaign may interleave fused and per-epoch measurement of *different*
    cases freely.

    When several devices are visible and ``p`` divides evenly, the per-rank
    inputs are placed with a rank-axis :class:`~jax.sharding.NamedSharding`
    and GSPMD shards the window grid; cross-rank reductions are
    order-independent, so sharded results are bitwise-identical.

    Returns one :class:`FusedWindowRun` per epoch. Raises
    :class:`SimJaxUnavailable` under the same conditions as
    :func:`run_windowed_jax`.
    """
    E = len(nets)
    if E == 0:
        return []
    ranks = list(range(nets[0].p)) if ranks is None else list(ranks)
    p = len(ranks)
    for net in nets:
        if not all(net.clocks[r].rw_sigma <= 0.0 for r in ranks):
            raise SimJaxUnavailable(
                "engine='jax' requires affine clocks (rw_sigma == 0); use "
                "engine='batch_rw' (or 'auto') for random-walk clocks")
    jax, sample_epochs, window_fused = _jitted_fused()
    if nrep <= 0:
        return [FusedWindowRun(times=np.empty(0),
                               errors=np.empty(0, dtype=np.int64))
                for _ in range(E)]

    n = _bucket(nrep)
    ch = _chunk_for(p, n)
    npad = -(-n // ch) * ch

    # Host pass 1 — per-epoch seeds and window origins. Per-net RNG order
    # (seed before biases) matches the per-epoch engine; epochs own
    # independent nets, so interleaving across epochs is free.
    start_times = np.empty(E, dtype=np.float64)
    seeds = np.empty(E, dtype=np.int64)
    term_lists = []
    for e, (net, sync, op) in enumerate(zip(nets, syncs, ops)):
        start_times[e] = max(sync.global_time(net, r)
                             for r in ranks) + win_size
        seeds[e] = int(net.rng.integers(2**31))
        term_lists.append(_terms(op, p, msize))
    nterms = len(term_lists[0])

    # Host pass 2 — per-epoch clock/sync coefficient stacks, (E, p).
    def stack(fn):
        return np.stack([np.array([fn(e, r) for r in ranks])
                         for e in range(E)])

    t0 = stack(lambda e, r: nets[e].t[r])
    off = stack(lambda e, r: nets[e].clocks[r].offset)
    skew = stack(lambda e, r: nets[e].clocks[r].skew)
    scale = stack(lambda e, r: nets[e].clocks[r].scale_error)
    slope = stack(lambda e, r: syncs[e].models[r].slope)
    intercept = stack(lambda e, r: syncs[e].models[r].intercept)
    init_t = stack(lambda e, r: syncs[e].initial_times[r])

    sharding = _rank_sharding(p)

    def put(a):
        return jax.device_put(a, sharding) if sharding is not None else a

    term_subs = [[term_lists[e][j][0] for e in range(E)]
                 for j in range(nterms)]
    chained = _chained(term_subs)
    window_keys = _fold_in(seeds, nterms)
    with x64():
        durs, carries = [], []
        for j, subs in enumerate(term_subs):
            tp, tm = term_lists[0][j][1], term_lists[0][j][2]
            t0_op = np.array([sub.base_time(tp, tm) * sub._bias_for(net)
                              for sub, net in zip(subs, nets)])
            ar_state = np.array([sub._ar_state for sub in subs])
            s0 = subs[0]
            _STATS.count(("sample_epochs", E, n))
            dur, s_last = sample_epochs(
                seeds, j, t0_op, ar_state, s0.noise_sigma, s0.autocorr,
                s0.tail_prob, s0.tail_shift, s0.spike_prob, s0.spike_scale,
                nrep, n=n)
            durs.append(dur)
            if chained:
                s_last = _read(jax, s_last)
                for e, sub in enumerate(subs):
                    sub._ar_state = float(s_last[e])
            else:
                carries.append(s_last)

        lanes = _jitted_lanes()(tuple(durs), npad=npad)
        outs = []
        for e in range(E):
            _STATS.count(("window_fused", ch, npad, p))
            outs.append(window_fused(
                lanes[e], window_keys[e], put(t0[e]), put(off[e]),
                put(skew[e]), put(scale[e]), put(slope[e]),
                put(intercept[e]), put(init_t[e]), ops[e].rank_imbalance,
                float(start_times[e]), win_size, nrep, ch=ch))
        carries, outs = _read(jax, (carries, outs))
    for subs, s_last in zip(term_subs, carries):
        for e, sub in enumerate(subs):
            sub._ar_state = float(s_last[e])
    runs = []
    for e, (times, errors, et_last) in enumerate(outs):
        nets[e].t[ranks] = np.asarray(et_last, dtype=np.float64)
        runs.append(FusedWindowRun(
            times=np.asarray(times[:nrep], dtype=np.float64),
            errors=np.asarray(errors[:nrep], dtype=np.int64)))
    return runs

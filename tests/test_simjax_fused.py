"""Fused (campaign-resident) jax engine: equivalence with the per-epoch
engine, compile-shape bucket contracts, rank-axis sharding, the
`measure_epochs` campaign capability, jit telemetry, and the
once-per-sweep fallback warning.

The fused engine's contract mirrors the batch-engine contract one level
up: duration sampling is *bit-identical* per epoch to the per-epoch jax
engine (same `_cores` sample program under the same fold_in keys), while
the window recurrence — float32 relative-frame arithmetic and a
LUT-quantile imbalance draw — is a different draw of the same process and
must be statistically indistinguishable."""

import os
import warnings

import numpy as np
import pytest

from repro.campaign import (Campaign, CampaignSpec, ResultStore, SimBackend,
                            SweepScheduler, SweepSpec)
from repro.core import (ExperimentDesign, FactorAxis, FactorGrid,
                        SimCompositeOp, TestCase, compare_tables,
                        make_composite_op, make_op, make_sync, telemetry,
                        wilcoxon_rank_sum)

pytest.importorskip("jax")

from repro.simjax import engine_stats, run_windowed_epochs_jax  # noqa: E402
from repro.simjax.engine import _bucket, _chunk_for, run_windowed_jax  # noqa: E402

SYNC_KW = dict(n_fitpts=60, n_exchanges=20)
NOISE_FREE = dict(noise_sigma=0.0, tail_prob=0.0, spike_prob=0.0,
                  rank_imbalance=0.0, epoch_bias_sigma=0.0, autocorr=0.0)


def _epochs(E, p=8, seed0=7, op="allreduce", **op_kw):
    """Epoch ``e``'s net, sync and op; ``op`` is a name or a factory."""
    nets, syncs, ops = [], [], []
    for e in range(E):
        from repro.core import SimNet

        net = SimNet(p, seed=seed0 + 1000 * e)
        syncs.append(make_sync("hca", **SYNC_KW).synchronize(net))
        nets.append(net)
        ops.append(op() if callable(op) else make_op(op, **op_kw))
    return nets, syncs, ops


def _sim(**kw):
    kw.setdefault("p", 8)
    kw.setdefault("seed0", 5)
    kw.setdefault("sync_kw", dict(SYNC_KW))
    return SimBackend(**kw)


# ---------------------------------------------------------------------------
# Engine-level equivalence
# ---------------------------------------------------------------------------

def test_fused_matches_per_epoch_engine_statistically():
    """Per epoch: same sampled durations (pinned via the AR(1) carry-out),
    same simulator end state, Wilcoxon-indistinguishable times."""
    E, nrep = 3, 2000
    nets_u, syncs_u, ops_u = _epochs(E, seed0=7)
    nets_f, syncs_f, ops_f = _epochs(E, seed0=7)
    unfused = [run_windowed_jax(nets_u[e], syncs_u[e], ops_u[e], 4096, nrep,
                                400e-6) for e in range(E)]
    fused = run_windowed_epochs_jax(nets_f, syncs_f, ops_f, 4096, nrep,
                                    400e-6)
    for e in range(E):
        # durations bit-identical => identical AR(1) carry-out
        assert ops_u[e]._ar_state == ops_f[e]._ar_state
        res = wilcoxon_rank_sum(unfused[e].valid_times,
                                fused[e].valid_times)
        assert res.p_value > 0.01, (e, res.p_value)
        np.testing.assert_allclose(nets_u[e].t, nets_f[e].t, rtol=1e-5)


def test_fused_exact_when_noise_free():
    """No noise, no imbalance: the fused float32 relative-frame window must
    reproduce the per-epoch engine's f64 times to f32 resolution — this
    isolates the affine-decomposition algebra from the draw change."""
    E, nrep = 2, 128
    nets_u, syncs_u, ops_u = _epochs(E, seed0=11, **NOISE_FREE)
    nets_f, syncs_f, ops_f = _epochs(E, seed0=11, **NOISE_FREE)
    unfused = [run_windowed_jax(nets_u[e], syncs_u[e], ops_u[e], 4096, nrep,
                                400e-6) for e in range(E)]
    fused = run_windowed_epochs_jax(nets_f, syncs_f, ops_f, 4096, nrep,
                                    400e-6)
    for e in range(E):
        np.testing.assert_allclose(fused[e].times, unfused[e].times,
                                   rtol=1e-5)
        assert np.array_equal(fused[e].errors, unfused[e].errors)


def test_fused_strict_on_random_walk_clocks():
    from repro.core import SimNet
    from repro.simjax import SimJaxUnavailable

    net = SimNet(4, seed=3, clocks=None)
    net.clocks[0].rw_sigma = 1e-7
    sync = make_sync("hca", **SYNC_KW).synchronize(net)
    with pytest.raises(SimJaxUnavailable):
        run_windowed_epochs_jax([net], [sync], [make_op("bcast")], 256, 10,
                                400e-6)


# ---------------------------------------------------------------------------
# Host path: keys derived on the host, one read per call
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("j", range(4))
@pytest.mark.parametrize("seed", [0, 1, 40_503, 7_654_321, 2**30 - 3,
                                  1_234_567_891, 2**31 - 2, 2**31 - 1])
def test_host_key_matches_eager_fold_in(seed, j):
    import jax

    from repro.simjax.engine import _fold_in

    want = np.asarray(jax.random.fold_in(jax.random.PRNGKey(seed), j))
    got = _fold_in(seed, j)
    assert got.dtype == np.uint32 and np.array_equal(got, want)
    # broadcast over seeds and over terms, as the engines call it
    assert np.array_equal(_fold_in(np.array([seed, 5]), j)[0], want)
    assert np.array_equal(_fold_in(seed, np.arange(4))[j], want)


def _repeated():
    """A composite whose two terms are one cost-model object: its AR(1)
    carry runs from the first term into the second."""
    op = make_op("bcast")
    return SimCompositeOp(name="bcast+bcast*0.5",
                          terms=((op, 1.0, 1.0), (op, 0.5, 1.0)))


HOST_PATH_OPS = {
    "single": lambda: make_op("allreduce"),
    "composite": lambda: make_composite_op("scatter+allgather"),
    "repeated": _repeated,
}


def _coeffs(net, sync, ranks):
    """The window programs' per-rank inputs after the durations and key."""
    return [np.asarray(net.t[ranks], dtype=np.float64),
            np.array([net.clocks[r].offset for r in ranks]),
            np.array([net.clocks[r].skew for r in ranks]),
            np.array([net.clocks[r].scale_error for r in ranks]),
            np.array([sync.models[r].slope for r in ranks]),
            np.array([sync.models[r].intercept for r in ranks]),
            np.array([sync.initial_times[r] for r in ranks])]


def _eager_fused(nets, syncs, ops, msize, nrep, ws):
    """A fused call as the engine made it with eager device keys: the
    jitted programs called directly, keys from ``jax.random``, durations
    summed, padded and indexed eagerly. Returns (times, errors) per epoch
    and writes ``net.t`` and the AR(1) states back."""
    import jax
    import jax.numpy as jnp

    import repro.simjax.engine as eng

    _, sample_epochs, window_fused = eng._jitted_fused()
    E, p = len(nets), nets[0].p
    ranks = list(range(p))
    n = _bucket(nrep)
    ch = _chunk_for(p, n)
    npad = -(-n // ch) * ch
    starts, seeds, terms = [], [], []
    for net, sync, op in zip(nets, syncs, ops):
        starts.append(max(sync.global_time(net, r) for r in ranks) + ws)
        seeds.append(int(net.rng.integers(2**31)))
        terms.append(eng._terms(op, p, msize))
    seeds = np.array(seeds)
    coeffs = [_coeffs(net, sync, ranks) for net, sync in zip(nets, syncs)]
    runs = []
    with eng.x64():
        durations = None
        for j in range(len(terms[0])):
            subs = [terms[e][j][0] for e in range(E)]
            tp, tm = terms[0][j][1:]
            t0_op = np.array([s.base_time(tp, tm) * s._bias_for(net)
                              for s, net in zip(subs, nets)])
            s0 = subs[0]
            dur, s_last = sample_epochs(
                seeds, j, t0_op, np.array([s._ar_state for s in subs]),
                s0.noise_sigma, s0.autocorr, s0.tail_prob, s0.tail_shift,
                s0.spike_prob, s0.spike_scale, nrep, n=n)
            for e, s in enumerate(subs):
                s._ar_state = float(np.asarray(s_last)[e])
            durations = dur if durations is None else durations + dur
        if npad > n:
            durations = jnp.concatenate(
                [durations, jnp.broadcast_to(durations[:, n - 1:n],
                                             (E, npad - n))], axis=1)
        for e in range(E):
            key = jax.random.fold_in(jax.random.PRNGKey(int(seeds[e])),
                                     len(terms[0]))
            times, errors, et_last = window_fused(
                durations[e], key, *coeffs[e], ops[e].rank_imbalance,
                float(starts[e]), ws, nrep, ch=ch)
            nets[e].t[ranks] = np.asarray(et_last)
            runs.append((np.asarray(times)[:nrep],
                         np.asarray(errors)[:nrep]))
    return runs


def _eager_epoch(net, sync, op, msize, nrep, ws):
    """A per-epoch call as the engine made it with eager device keys;
    returns the six ``WindowRun`` arrays."""
    import jax

    import repro.simjax.engine as eng

    _, sample, window = eng._jitted()
    ranks = list(range(net.p))
    start = max(sync.global_time(net, r) for r in ranks) + ws
    n = _bucket(nrep)
    seed = int(net.rng.integers(2**31))
    terms = eng._terms(op, net.p, msize)
    coeffs = _coeffs(net, sync, ranks)
    with eng.x64():
        key = jax.random.PRNGKey(seed)
        durations = None
        for j, (sub, tp, tm) in enumerate(terms):
            dur, s = sample(jax.random.fold_in(key, j),
                            sub.base_time(tp, tm) * sub._bias_for(net),
                            sub._ar_state, sub.noise_sigma, sub.autocorr,
                            sub.tail_prob, sub.tail_shift, sub.spike_prob,
                            sub.spike_scale, n=n)
            sub._ar_state = float(s[nrep - 1])
            durations = dur if durations is None else durations + dur
        out = window(durations, jax.random.fold_in(key, len(terms)),
                     *coeffs, op.rank_imbalance, start, ws)
    out = [np.asarray(a)[:nrep] for a in out]
    net.t[ranks] = out[5][nrep - 1]
    return out


def _ar_states(ops):
    return [sub._ar_state for op in ops
            for sub, _, _ in getattr(op, "terms", ((op, 1.0, 1.0),))]


@pytest.mark.parametrize("op,nrep", [("single", 8200), ("composite", 300),
                                     ("repeated", 300)])
def test_fused_bit_identical_to_eager_keys(op, nrep):
    """Host keys, the jitted lanes and the single read change no bit of
    what the fused engine returns or writes back. ``single`` at nrep=8200,
    p=8 pads the window scan (n=8200 to npad=16384)."""
    if op == "single":
        n = _bucket(nrep)
        ch = _chunk_for(8, n)
        assert -(-n // ch) * ch > n
    want_nets, want_syncs, want_ops = _epochs(3, seed0=21,
                                              op=HOST_PATH_OPS[op])
    nets, syncs, ops = _epochs(3, seed0=21, op=HOST_PATH_OPS[op])
    want = _eager_fused(want_nets, want_syncs, want_ops, 4096, nrep, 400e-6)
    got = run_windowed_epochs_jax(nets, syncs, ops, 4096, nrep, 400e-6)
    for e in range(3):
        assert np.array_equal(got[e].times, want[e][0])
        assert np.array_equal(got[e].errors, want[e][1])
        assert np.array_equal(nets[e].t, want_nets[e].t)
    assert _ar_states(ops) == _ar_states(want_ops)


@pytest.mark.parametrize("op", list(HOST_PATH_OPS))
def test_per_epoch_bit_identical_to_eager_keys(op):
    (want_net,), (want_sync,), (want_op,) = _epochs(1, seed0=23,
                                                    op=HOST_PATH_OPS[op])
    (net,), (sync,), (op_,) = _epochs(1, seed0=23, op=HOST_PATH_OPS[op])
    for _ in range(2):           # the second call starts from the carries
        want = _eager_epoch(want_net, want_sync, want_op, 4096, 300, 400e-6)
        run = run_windowed_jax(net, sync, op_, 4096, 300, 400e-6)
        got = [run.times, run.errors, run.start_global_est,
               run.end_global_est, run.start_true, run.end_true]
        for g, w in zip(got, want):
            assert np.array_equal(g, w)
        assert np.array_equal(net.t, want_net.t)
        assert _ar_states([op_]) == _ar_states([want_op])


@pytest.mark.parametrize("op,E,order", [
    ("single", 1, ["sample", "window", "read"]),
    ("single", 4, ["sample"] + ["window"] * 4 + ["read"]),
    ("composite", 3, ["sample"] * 2 + ["window"] * 3 + ["read"]),
    # one object in two terms: its carry is read after each term
    ("repeated", 3, ["sample", "read"] * 2 + ["window"] * 3 + ["read"]),
])
def test_one_host_read_per_fused_call(monkeypatch, op, E, order):
    """Every window of the call is dispatched before the call's one read;
    ``sim_host_reads`` counts the reads."""
    import jax

    import repro.simjax.engine as eng

    nets, syncs, ops = _epochs(E, seed0=29, op=HOST_PATH_OPS[op])
    seen = []
    fused = eng._jitted_fused

    def recording():
        jx, sample, window = fused()

        def rec(name, fn):
            def call(*a, **kw):
                seen.append(name)
                return fn(*a, **kw)
            return call
        return jx, rec("sample", sample), rec("window", window)

    get = jax.device_get

    def device_get(tree):
        seen.append("read")
        return get(tree)

    monkeypatch.setattr(eng, "_jitted_fused", recording)
    monkeypatch.setattr(jax, "device_get", device_get)
    before = telemetry.counters().get("sim_host_reads", 0)
    run_windowed_epochs_jax(nets, syncs, ops, 4096, 200, 400e-6)
    assert seen == order
    assert telemetry.counters()["sim_host_reads"] - before \
        == order.count("read")


# ---------------------------------------------------------------------------
# Compile-shape buckets
# ---------------------------------------------------------------------------

def test_bucket_edges():
    assert _bucket(1) == 32 and _bucket(32) == 32        # at the edge
    assert _bucket(33) == 64                             # one past it
    assert _bucket(1023) == 1024 and _bucket(1024) == 1024
    assert _bucket(1025) == 1025                         # exact above 1024
    assert 256 <= _chunk_for(64, 10**5) <= 8192
    assert _chunk_for(64, 100) == 100                    # never above n


def test_bucketing_never_changes_values_within_a_bucket():
    """nrep at vs. past a pow2 edge, same bucket: identical draws, so the
    shorter run is a bitwise prefix of the longer — trace reuse is
    observationally free. (Crossing the edge changes the compiled shape
    and with it JAX's counter layout: a fresh draw of the same process,
    which is exactly what the statistical equivalence tests cover.)"""
    def run(nrep):
        nets, syncs, ops = _epochs(1, seed0=5)
        return run_windowed_jax(nets[0], syncs[0], ops[0], 4096, nrep,
                                400e-6)

    a, b = run(33), run(64)                  # both bucket 64
    assert np.array_equal(a.times, b.times[:33])
    assert np.array_equal(a.errors, b.errors[:33])
    c, d = run(20), run(32)                  # both bucket 32
    assert np.array_equal(c.times, d.times[:20])

    def fused(nrep):
        nets, syncs, ops = _epochs(2, seed0=5)
        return run_windowed_epochs_jax(nets, syncs, ops, 4096, nrep, 400e-6)

    fa, fb = fused(33), fused(64)
    for e in range(2):
        assert np.array_equal(fa[e].times, fb[e].times[:33])


def test_bucket_trace_reuse_and_edge_recompile():
    """Same bucket -> zero new traces; crossing the edge -> new traces.
    Measured through the engine's own telemetry, not inferred."""
    from repro.simjax import reset_engine_stats

    def fused(nrep, seed0):
        nets, syncs, ops = _epochs(2, seed0=seed0)
        return run_windowed_epochs_jax(nets, syncs, ops, 4096, nrep, 400e-6)

    reset_engine_stats()      # count relative to this test only
    fused(40, 21)                            # warm bucket 64
    s0 = engine_stats()
    fused(50, 31)                            # same bucket: reuse only
    s1 = engine_stats()
    assert s1["n_traces"] == s0["n_traces"]
    assert s1["n_dispatches"] > s0["n_dispatches"]
    fused(70, 41)                            # bucket 128: recompile
    s2 = engine_stats()
    assert s2["n_traces"] > s1["n_traces"]


def test_adaptive_topup_across_bucket_is_deterministic():
    """An adaptive campaign whose top-up chunks cross a bucket edge (24 ->
    bucket 32, later chunks -> bucket 64) must stay fully deterministic:
    two identical runs produce byte-identical stores, and the sample-size
    accounting survives the bucket crossings."""
    design = ExperimentDesign(n_launch_epochs=2, nrep_min=24, nrep_max=120,
                              rel_ci_target=1e-6, seed=3)
    cases = [TestCase("allreduce", 512)]

    def run(path):
        store = ResultStore(path)
        res = Campaign(CampaignSpec(cases, design),
                       _sim(engine="jax"), store=store).run()
        return res

    import tempfile
    with tempfile.TemporaryDirectory() as d:
        p1, p2 = os.path.join(d, "a.jsonl"), os.path.join(d, "b.jsonl")
        r1, r2 = run(p1), run(p2)
        assert open(p1, "rb").read() == open(p2, "rb").read()
    for r in r1.records:
        assert r.meta["nrep_used"] == r.times.size == 120
        assert r.meta["converged"] is False and "rel_ci" in r.meta


# ---------------------------------------------------------------------------
# Rank-axis sharding
# ---------------------------------------------------------------------------

@pytest.mark.jaxdevices(4)
def test_sharded_fused_bitwise_matches_unsharded(monkeypatch):
    """Under 4 forced host devices the (p,) inputs are placed with a
    rank-axis NamedSharding; all cross-rank reductions are
    order-independent, so the sharded program must be *bitwise* identical
    to the explicitly-unsharded one."""
    import repro.simjax.engine as eng

    assert eng._rank_sharding(8) is not None     # sharding actually active
    nets_s, syncs_s, ops_s = _epochs(2, seed0=13)
    sharded = run_windowed_epochs_jax(nets_s, syncs_s, ops_s, 4096, 300,
                                      400e-6)
    monkeypatch.setattr(eng, "_rank_sharding", lambda p: None)
    nets_u, syncs_u, ops_u = _epochs(2, seed0=13)
    unsharded = run_windowed_epochs_jax(nets_u, syncs_u, ops_u, 4096, 300,
                                        400e-6)
    for e in range(2):
        assert np.array_equal(sharded[e].times, unsharded[e].times)
        assert np.array_equal(sharded[e].errors, unsharded[e].errors)
        assert np.array_equal(nets_s[e].t, nets_u[e].t)


# ---------------------------------------------------------------------------
# Campaign capability: measure_epochs
# ---------------------------------------------------------------------------

def test_fused_campaign_equivalent_resumable_and_metered():
    """The tentpole, end to end: a fused campaign is compare_tables-
    equivalent to the per-cell-epoch one, resumes byte-compatibly at an
    epoch boundary, shares the unfused campaign's factor fingerprint
    (fuse_epochs is an execution knob, not a factor), and reports its jit
    telemetry in the campaign meta."""
    design = ExperimentDesign(n_launch_epochs=4, nrep=50, seed=5)
    cases = [TestCase("allreduce", 256), TestCase("allreduce", 4096),
             TestCase("bcast", 1024)]
    spec = CampaignSpec(cases, design)
    rf = Campaign(spec, _sim(engine="jax", fuse_epochs=True)).run()
    ru = Campaign(spec, _sim(engine="jax", fuse_epochs=False)).run()

    assert rf.factors.fingerprint() == ru.factors.fingerprint()
    for row in compare_tables(rf.table, ru.table):
        assert row.p_two_sided > 0.01, row
    assert all(r.meta["engine"] == "jax" and r.meta.get("fused")
               for r in rf.records)
    assert not any(r.meta.get("fused") for r in ru.records)
    assert all(r.meta["nrep_used"] == r.times.size == 50 for r in rf.records)

    jit = rf.meta["jit"]
    assert jit["n_dispatches"] > 0 and jit["n_compiles"] >= 0
    assert jit["compile_s"] >= 0.0 and "cache_hit_rate" not in jit
    assert rf.meta["jit"]["n_dispatches"] < ru.meta["jit"]["n_dispatches"]

    import tempfile
    with tempfile.TemporaryDirectory() as d:
        p1, p2 = os.path.join(d, "a.jsonl"), os.path.join(d, "b.jsonl")
        Campaign(spec, _sim(engine="jax"), store=ResultStore(p1)).run()
        Campaign(spec, _sim(engine="jax"),
                 store=ResultStore(p2)).run(epochs=[0, 1])
        Campaign(spec, _sim(engine="jax"), store=ResultStore(p2)).run()
        assert open(p1, "rb").read() == open(p2, "rb").read()


def test_fused_gating_falls_back_cleanly():
    """measure_epochs declines — and the campaign still runs identically
    through the per-epoch path — for every gate: non-jax engine, fusing
    disabled, shared-cluster epoch isolation."""
    design = ExperimentDesign(n_launch_epochs=2, nrep=10, seed=5)
    spec = CampaignSpec([TestCase("bcast", 256)], design)
    for backend in (_sim(engine="auto"),
                    _sim(engine="jax", fuse_epochs=False),
                    _sim(engine="jax", epoch_isolation="none")):
        assert backend.measure_epochs({0: spec.cases}, design) is None
        res = Campaign(spec, backend).run()
        assert len(res.records) == 2
        assert not any(r.meta.get("fused") for r in res.records)
    # auto resolves to the numpy engine: no jit telemetry in its meta
    assert "jit" not in Campaign(spec, _sim(engine="auto")).run().meta


def test_fused_no_factor_leak():
    """fuse_epochs must not appear anywhere in the factor set: flipping it
    cannot re-key stores, sweeps or audits."""
    design = ExperimentDesign(n_launch_epochs=2, nrep=5)
    a = _sim(engine="jax", fuse_epochs=True).factors(design)
    b = _sim(engine="jax", fuse_epochs=False).factors(design)
    assert a.fingerprint() == b.fingerprint()
    assert "fuse" not in repr(sorted(a.extra))


# ---------------------------------------------------------------------------
# Fallback warning: once per sweep
# ---------------------------------------------------------------------------

def test_engine_fallback_warns_once_per_sweep():
    """engine='jax' on random-walk clocks inside a sweep: the substitution
    RuntimeWarning fires once for the whole sweep (not once per cell), and
    the per-record `engine_fallback` provenance is untouched."""
    grid = FactorGrid((FactorAxis("dtype", ("float32", "float64")),))
    spec = SweepSpec(grid, [TestCase("bcast", 256)],
                     ExperimentDesign(n_launch_epochs=2, nrep=5, seed=1))
    backend = _sim(engine="jax", clock_kw=dict(rw_sigma=1e-7))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        res = SweepScheduler(spec, backend).run()
    fallback = [w for w in caught if issubclass(w.category, RuntimeWarning)
                and "engine" in str(w.message)]
    assert len(fallback) == 1, [str(w.message) for w in fallback]
    assert len(res.cells) == 2
    # per-record provenance: run one cell campaign directly
    r = Campaign(CampaignSpec(spec.cases, spec.design), backend).run()
    assert all(rec.meta["engine"] == "batch_rw" and
               "engine_fallback" in rec.meta for rec in r.records)


def test_engine_fallback_still_once_per_campaign_outside_sweep():
    backend = _sim(engine="jax", clock_kw=dict(rw_sigma=1e-7))
    spec = CampaignSpec([TestCase("bcast", 256)],
                        ExperimentDesign(n_launch_epochs=3, nrep=5, seed=1))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        Campaign(spec, backend).run()
    fallback = [w for w in caught if issubclass(w.category, RuntimeWarning)
                and "engine" in str(w.message)]
    assert len(fallback) == 1

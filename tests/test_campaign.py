"""Campaign subsystem: backend protocol, adaptive-nrep stopping, the
persistent JSONL store (append -> resume -> load), and the end-to-end
multi-backend comparison the architecture exists for."""

import numpy as np
import pytest

from repro.campaign import (Campaign, CampaignSpec, JaxBackend, KernelBackend,
                            MeasurementBackend, ResultStore, SimBackend)
from repro.core import (ExperimentDesign, TestCase, analyze_records,
                        compare_tables, measure_adaptive, run_design)

QUIET = dict(noise_sigma=0.004, tail_prob=0.0, spike_prob=0.0,
             autocorr=0.0, rank_imbalance=0.01, epoch_bias_sigma=0.0)
HEAVY = dict(noise_sigma=0.35, tail_prob=0.45, tail_shift=3.0,
             spike_prob=0.05, spike_scale=40.0)
FAST_SYNC = dict(n_fitpts=100, n_exchanges=20)


def _spec(cases, **design_kw):
    kw = dict(n_launch_epochs=3, nrep=20, seed=11)
    kw.update(design_kw)
    return CampaignSpec(cases=cases, design=ExperimentDesign(**kw))


def _sim(seed0=0, op_kw=None, **kw):
    kw.setdefault("sync_kw", dict(FAST_SYNC))
    return SimBackend(p=4, seed0=seed0, op_kw=op_kw or {}, **kw)


# ---------------------------------------------------------------------------
# Backend protocol & run_design integration
# ---------------------------------------------------------------------------

def test_backends_satisfy_protocol():
    for b in (SimBackend(), JaxBackend(), KernelBackend()):
        assert isinstance(b, MeasurementBackend)
        assert b.default_cases()
        fs = b.factors(ExperimentDesign(n_launch_epochs=2, nrep=5))
        assert fs.measurement_backend == b.name
        assert fs.fingerprint()


def test_sim_backend_factors_name_the_device_only_for_the_jit_engine(
        monkeypatch):
    """engine='jax' runs on JAX's default device, and its factor set says
    which; the numpy engines record the simulator and never query a device
    (a query would claim a TPU for this process)."""
    import jax

    design = ExperimentDesign(n_launch_epochs=2, nrep=5)
    fs = _sim(engine="jax").factors(design)
    assert (fs.backend, fs.device_kind) == (jax.default_backend(),
                                            jax.devices()[0].device_kind)

    def no_device(*a, **k):
        raise AssertionError("the numpy simulator queried a device")

    monkeypatch.setattr(jax, "devices", no_device)
    monkeypatch.setattr(jax, "default_backend", no_device)
    fs = _sim().factors(design)
    assert (fs.backend, fs.device_kind) == ("sim", "simnet")
    assert "capture_failure" not in dict(fs.extra)


def test_sim_backend_records_resolved_engine_meta():
    """Each record carries the engine that actually ran — ``auto`` on
    affine clocks resolves to ``batch``, and on random-walk clocks to
    ``batch_rw`` (never the scalar path)."""
    res = Campaign(_spec([TestCase("bcast", 256)], n_launch_epochs=2,
                         nrep=10), _sim(seed0=5)).run()
    assert all(r.meta["engine"] == "batch" for r in res.records)
    res_rw = Campaign(_spec([TestCase("bcast", 256)], n_launch_epochs=2,
                            nrep=10),
                      _sim(seed0=5, clock_kw=dict(rw_sigma=1e-7))).run()
    assert all(r.meta["engine"] == "batch_rw" for r in res_rw.records)


def test_sim_backend_jax_engine_fallback_warns_once_and_is_recorded():
    """engine='jax' on random-walk clocks: substituted (batch_rw), warned
    exactly once per campaign, and stamped on every record's meta."""
    import warnings

    backend = _sim(seed0=5, engine="jax", clock_kw=dict(rw_sigma=1e-7))
    spec = _spec([TestCase("bcast", 256)], n_launch_epochs=3, nrep=10)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        res = Campaign(spec, backend).run()
    fallback = [w for w in caught if issubclass(w.category, RuntimeWarning)
                and "resolved to" in str(w.message)]
    assert len(fallback) == 1, [str(w.message) for w in caught]
    assert all(r.meta["engine"] == "batch_rw" for r in res.records)
    assert all("engine_fallback" in r.meta for r in res.records)


def test_sim_backend_fallback_warning_points_at_caller():
    """The engine-fallback RuntimeWarning must be attributed to the code
    that asked for the engine (this file), not to a frame inside repro —
    ``warnings.filterwarnings(module=...)`` and editor jump-to-source
    both key off that location."""
    import warnings

    backend = _sim(seed0=5, engine="jax", clock_kw=dict(rw_sigma=1e-7))
    spec = _spec([TestCase("bcast", 256)], n_launch_epochs=2, nrep=10)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        Campaign(spec, backend).run()
    fallback = [w for w in caught if issubclass(w.category, RuntimeWarning)
                and "resolved to" in str(w.message)]
    assert len(fallback) == 1
    assert fallback[0].filename == __file__, (
        f"fallback warning attributed to {fallback[0].filename}, "
        f"expected {__file__}")


def test_sim_backend_jax_engine_end_to_end():
    """A campaign through the jit-compiled engine: right shapes, engine
    recorded, and means in the same ballpark as the numpy engine."""
    pytest.importorskip("jax")
    spec = _spec([TestCase("allreduce", 512)], n_launch_epochs=2, nrep=30)
    res_np = Campaign(spec, _sim(seed0=5)).run()
    res_jx = Campaign(spec, _sim(seed0=5, engine="jax")).run()
    assert all(r.meta["engine"] == "jax" for r in res_jx.records)
    case = res_jx.table.cases()[0]
    m_np = float(np.mean(res_np.table.means(case)))
    m_jx = float(np.mean(res_jx.table.means(case)))
    assert abs(m_np - m_jx) < 0.05 * m_np


def test_run_design_accepts_backend():
    """run_design consumes a backend directly (no ad-hoc pair) and falls
    back to the backend's default cases."""
    backend = _sim(seed0=3)
    design = ExperimentDesign(n_launch_epochs=3, nrep=15, seed=3)
    records = run_design(design, backend)
    cases = {c.key() for c in backend.default_cases()}
    assert {r.case.key() for r in records} == cases
    assert len(records) == 3 * len(cases)
    with pytest.raises(TypeError):
        run_design(design, lambda e: None)   # factory without measure


def test_sim_backend_tops_up_window_discards():
    """A tiny window discards many observations; the backend tops the
    valid sample back up toward the requested nrep."""
    backend = _sim(seed0=5, win_size=25e-6)
    ctx = backend.make_epoch(0)
    times = backend.measure(ctx, TestCase("alltoall", 8192), 50)
    assert times.size >= 25


# ---------------------------------------------------------------------------
# Adaptive nrep (sequential stopping)
# ---------------------------------------------------------------------------

def test_adaptive_nrep_converges_below_cap_on_quiet_case():
    backend = _sim(seed0=21, op_kw=QUIET)
    design = ExperimentDesign(n_launch_epochs=1, nrep_min=10, nrep_max=400,
                              rel_ci_target=0.02, seed=21)
    times, meta = measure_adaptive(backend.measure, backend.make_epoch(0),
                                   TestCase("allreduce", 1024), design)
    assert meta["converged"]
    assert meta["nrep_used"] < 400
    assert meta["rel_ci"] <= 0.02
    assert times.size == meta["nrep_used"]


def test_adaptive_nrep_hits_cap_on_heavy_tail_case():
    backend = _sim(seed0=22, op_kw=HEAVY)
    design = ExperimentDesign(n_launch_epochs=1, nrep_min=10, nrep_max=120,
                              rel_ci_target=0.02, seed=22)
    times, meta = measure_adaptive(backend.measure, backend.make_epoch(0),
                                   TestCase("allreduce", 1024), design)
    assert not meta["converged"]
    assert times.size >= 120
    assert meta["rel_ci"] > 0.02


def test_adaptive_records_carry_provenance():
    spec = _spec([TestCase("allreduce", 256)], n_launch_epochs=2, nrep_min=8,
                 nrep_max=30, rel_ci_target=0.05)
    res = Campaign(spec, _sim(seed0=9)).run()
    for rec in res.records:
        assert 8 <= rec.meta["nrep_used"] <= 30 + 8  # chunking may overshoot
        assert "rel_ci" in rec.meta
    assert res.factors.nrep_max == 30 and res.factors.nrep == 0


# ---------------------------------------------------------------------------
# Persistent store
# ---------------------------------------------------------------------------

def test_store_round_trip_matches_in_memory(tmp_path):
    """append -> load: analyze_records over store records reduces
    identically to the in-memory run."""
    spec = _spec([TestCase("allreduce", 256), TestCase("bcast", 1024)])
    store = ResultStore(tmp_path / "a.jsonl")
    res = Campaign(spec, _sim(seed0=31), store).run()
    assert res.n_measured == 6 and res.n_resumed == 0

    loaded = store.records(res.fingerprint)
    t_mem = res.table
    t_disk = analyze_records(loaded)
    for case in t_mem.cases():
        np.testing.assert_array_equal(t_mem.medians(case),
                                      t_disk.medians(case))
        np.testing.assert_array_equal(t_mem.means(case), t_disk.means(case))


def test_store_resume_skips_measurement(tmp_path):
    """Re-running the identical campaign against the same store loads every
    cell instead of re-measuring, and yields the same table."""
    spec = _spec([TestCase("allreduce", 256)])
    path = tmp_path / "a.jsonl"
    first = Campaign(spec, _sim(seed0=33), ResultStore(path)).run()

    calls = []
    backend = _sim(seed0=33)
    orig = backend.measure
    backend.measure = lambda *a, **k: calls.append(1) or orig(*a, **k)
    resumed = Campaign(spec, backend, ResultStore(path)).run()
    assert not calls
    assert resumed.n_resumed == 3 and resumed.n_measured == 0
    case = first.table.cases()[0]
    np.testing.assert_array_equal(first.table.medians(case),
                                  resumed.table.medians(case))


def test_store_partial_resume_measures_only_missing(tmp_path):
    """Truncating the store to the first epoch leaves later epochs to be
    measured; earlier cells come back verbatim."""
    spec = _spec([TestCase("allreduce", 256)], n_launch_epochs=4)
    path = tmp_path / "a.jsonl"
    full = Campaign(spec, _sim(seed0=35), ResultStore(path)).run()

    # first four lines: schema header, campaign declaration, two records
    lines = path.read_text().splitlines()
    cut = ResultStore(tmp_path / "cut.jsonl")
    (tmp_path / "cut.jsonl").write_text("\n".join(lines[:4]) + "\n")
    assert cut.completed(full.fingerprint) == {("allreduce", 256, 0),
                                               ("allreduce", 256, 1)}
    resumed = Campaign(spec, _sim(seed0=35), cut).run()
    assert resumed.n_resumed == 2 and resumed.n_measured == 2
    assert len(cut.completed(full.fingerprint)) == 4
    for rec, ref in zip(resumed.records[:2], full.records[:2]):
        np.testing.assert_array_equal(rec.times, ref.times)


def test_store_distinguishes_factor_sets(tmp_path):
    """One file, two campaigns with different factors: records stay keyed
    to their own fingerprint."""
    store = ResultStore(tmp_path / "multi.jsonl")
    spec = _spec([TestCase("allreduce", 256)], n_launch_epochs=2)
    ra = Campaign(spec, _sim(seed0=41), store).run()
    rb = Campaign(spec, _sim(seed0=41, op_kw=dict(alpha=9e-6)), store).run()
    assert ra.fingerprint != rb.fingerprint
    assert store.fingerprints() == [ra.fingerprint, rb.fingerprint]
    assert len(store.records(ra.fingerprint)) == 2
    a = store.to_table(ra.fingerprint).medians(TestCase("allreduce", 256))
    b = store.to_table(rb.fingerprint).medians(TestCase("allreduce", 256))
    assert np.mean(b) > np.mean(a)           # the slower library stayed slower


def test_design_identity_changes_fingerprint():
    """A different seed, randomization choice, or adaptive precision target
    is a different experiment: it must not resume another campaign's
    records from the store."""
    backend = _sim(seed0=47)
    base = dict(n_launch_epochs=2, nrep_min=5, nrep_max=20,
                rel_ci_target=0.05, seed=1)
    fp = backend.factors(ExperimentDesign(**base)).fingerprint()
    for change in (dict(seed=2), dict(shuffle=False),
                   dict(rel_ci_target=0.01), dict(nrep_max=40)):
        other = backend.factors(
            ExperimentDesign(**{**base, **change})).fingerprint()
        assert other != fp, change


def test_backend_identity_changes_fingerprint():
    """Backend configuration knobs that change what is measured must show
    up in the store fingerprint (no silent resume of a different
    experiment)."""
    d = ExperimentDesign(n_launch_epochs=2, nrep=5)
    assert (_sim(seed0=1).factors(d).fingerprint()
            != _sim(seed0=1, sync_kw=dict(n_fitpts=10, n_exchanges=2),
                    ).factors(d).fingerprint())
    assert (KernelBackend(kv_heads=2, seed0=0).factors(d).fingerprint()
            != KernelBackend(kv_heads=4, seed0=99).factors(d).fingerprint())


def test_store_redeclares_changed_spec(tmp_path):
    """Growing a campaign's case list resumes the same fingerprint but
    refreshes the declaration, so the last spec describes the data."""
    store = ResultStore(tmp_path / "a.jsonl")
    r1 = Campaign(_spec([TestCase("allreduce", 256)], n_launch_epochs=2),
                  _sim(seed0=61), store).run()
    r2 = Campaign(_spec([TestCase("allreduce", 256),
                         TestCase("allreduce", 4096)], n_launch_epochs=2),
                  _sim(seed0=61), store).run()
    assert r1.fingerprint == r2.fingerprint
    assert r2.n_resumed == 2 and r2.n_measured == 2
    assert store.fingerprints() == [r1.fingerprint]
    specs = [o for o in store._lines() if o["kind"] == "campaign"]
    assert len(specs) == 2 and len(specs[-1]["spec"]["cases"]) == 2


def test_store_warns_and_skips_truncated_tail_line(tmp_path):
    spec = _spec([TestCase("allreduce", 256)], n_launch_epochs=2)
    path = tmp_path / "a.jsonl"
    res = Campaign(spec, _sim(seed0=43), ResultStore(path)).run()
    with open(path, "a") as f:
        f.write('{"kind": "record", "fingerprint": "xyz", "op": "allre')
    with pytest.warns(RuntimeWarning,
                      match=r'undecodable "record" tail line'):
        assert len(ResultStore(path).records(res.fingerprint)) == 2


# ---------------------------------------------------------------------------
# End-to-end: one spec, two backends, two stores, one comparison
# ---------------------------------------------------------------------------

def test_end_to_end_sim_and_kernel_backends_compose(tmp_path):
    """The acceptance scenario: the *same* Campaign spec runs against
    SimBackend and KernelBackend (CPU interpret mode), both persist to
    stores, both reload, and compare_tables produces the report — proving
    the backend protocol, the store, and adaptive nrep compose."""
    spec = CampaignSpec(
        cases=[TestCase("flash_attention", 64)],
        design=ExperimentDesign(n_launch_epochs=2, nrep_min=3, nrep_max=6,
                                rel_ci_target=0.3, seed=17),
        name="e2e",
    )
    backends = {
        "sim": _sim(seed0=50),     # unknown op name -> generic cost model
        "kernel": KernelBackend(impl="pallas", batch=1, heads=2, head_dim=16),
    }
    stores = {}
    for label, backend in backends.items():
        store = ResultStore(tmp_path / f"{label}.jsonl")
        res = Campaign(spec, backend, store).run()
        assert res.n_measured == 2
        assert all(3 <= r.meta["nrep_used"] for r in res.records)
        assert store.factors()["measurement_backend"] == backend.name
        stores[label] = store

    rows = compare_tables(stores["sim"], stores["kernel"])
    assert len(rows) == 1
    row = rows[0]
    assert row.case.key() == ("flash_attention", 64)
    assert row.n_a == 2 and row.n_b == 2
    assert 0.0 <= row.p_two_sided <= 1.0
    assert np.isfinite(row.ratio)


@pytest.mark.jaxdevices(4)
def test_jax_backend_collectives_multi_device(tmp_path):
    """JaxBackend measures real jitted collectives over a >= 4-device host
    mesh and persists/reloads through the store."""
    spec = CampaignSpec(
        cases=[TestCase("psum", 1024), TestCase("all_to_all", 1024)],
        design=ExperimentDesign(n_launch_epochs=2, nrep_min=3, nrep_max=6,
                                rel_ci_target=0.5, seed=19),
        name="jax-collectives",
    )
    store = ResultStore(tmp_path / "jax.jsonl")
    res = Campaign(spec, JaxBackend(n_devices=4), store).run()
    assert res.factors.mesh_shape == (4,)
    table = store.to_table(res.fingerprint)
    for case in table.cases():
        med = table.medians(case)
        assert med.size == 2
        assert np.all(med > 0)


@pytest.mark.jaxdevices(4)
@pytest.mark.parametrize("op", ["psum", "all_gather", "all_to_all"])
def test_jax_backend_input_is_placed_per_device(op):
    """The collective's input is sharded over the mesh before the timed
    call — one payload on each device, none left on the default device for
    pmap to scatter — and the collective's output equals numpy's."""
    import jax

    backend = JaxBackend(n_devices=4)
    x = backend._place_input(op, 4096, 4)
    shards = x.addressable_shards
    assert [s.device for s in shards] == jax.devices()[:4]
    assert [s.index[0] for s in shards] == [slice(i, i + 1)
                                            for i in range(4)]
    assert all(s.data.shape == (1,) + x.shape[1:] for s in shards)
    assert backend.check(op, 4096) == 0.0

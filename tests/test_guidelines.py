"""Guideline-verification subsystem: the op-expression grammar, composite
mock-up execution on the backends, Holm correction, and the PGMPI verdict
engine — proven in both directions (an honest library passes, a seeded
mis-tuned collective is flagged) plus store resume of a killed
verification campaign."""

import numpy as np
import pytest

from repro.campaign import KernelBackend, ResultStore, SimBackend
from repro.core import (ExperimentDesign, SimNet, TestCase, compare_cases,
                        compare_tables, holm_bonferroni, is_composite,
                        make_composite_op, parse_opexpr)
from repro.core.design import analyze_records
from repro.guidelines import (SIM_GUIDELINES, Guideline, compile_cases,
                              format_report, format_violations,
                              verify_guidelines)

FAST_SYNC = dict(n_fitpts=100, n_exchanges=20)


def _sim(seed0=0, p=8, **kw):
    kw.setdefault("sync_kw", dict(FAST_SYNC))
    return SimBackend(p=p, seed0=seed0, **kw)


def _design(**kw):
    base = dict(n_launch_epochs=8, nrep=25, seed=5)
    base.update(kw)
    return ExperimentDesign(**base)


# ---------------------------------------------------------------------------
# Op-expression grammar
# ---------------------------------------------------------------------------

def test_parse_opexpr_terms_and_modifiers():
    t, = parse_opexpr("allreduce")
    assert (t.op, t.msize_scale, t.procs, t.impl) == ("allreduce", 1.0,
                                                      "all", None)
    terms = parse_opexpr("scatter + allgather*0.5")
    assert [x.op for x in terms] == ["scatter", "allgather"]
    assert terms[1].msize(1000) == 500
    t, = parse_opexpr("allreduce@half#ref")
    assert t.procs == "half" and t.impl == "ref"
    assert not is_composite("allreduce")
    for expr in ("allreduce*2", "a+b", "allreduce@half", "x#ref"):
        assert is_composite(expr), expr


def test_parse_opexpr_rejects_garbage():
    for bad in ("", "a-b", "a*", "a*0", "a@quarter", "1op", "a+"):
        with pytest.raises(ValueError):
            parse_opexpr(bad)


# ---------------------------------------------------------------------------
# Composite mock-up execution (SimBackend)
# ---------------------------------------------------------------------------

def test_composite_sim_op_sums_constituent_durations():
    net = SimNet(4, seed=7)
    comp = make_composite_op("reduce+bcast")
    lone = make_composite_op("reduce")
    d_comp = comp.sample_durations(net, 4, 4096, 200)
    net2 = SimNet(4, seed=7)
    d_lone = lone.sample_durations(net2, 4, 4096, 200)
    assert d_comp.mean() > d_lone.mean()
    # base_time is exactly additive (the stochastic parts are not)
    assert comp.base_time(4, 4096) == pytest.approx(
        make_composite_op("reduce").base_time(4, 4096)
        + make_composite_op("bcast").base_time(4, 4096))


def test_composite_half_term_uses_fewer_processes():
    # at zero message size the cost is pure latency: alpha * ceil(log2 p)
    comp = make_composite_op("allreduce@half+allreduce@half")
    full2 = make_composite_op("allreduce+allreduce")
    assert comp.base_time(8, 0) < full2.base_time(8, 0)


def test_composite_runs_through_windowed_campaign():
    backend = _sim(seed0=3, p=4)
    ctx = backend.make_epoch(0)
    times = backend.measure(ctx, TestCase("scatter+allgather", 2048), 40)
    ref = backend.measure(ctx, TestCase("bcast", 2048), 40)
    assert times.size >= 20 and np.all(times > 0)
    assert times.mean() > ref.mean()     # the mock-up costs more than bcast


def test_sim_rejects_impl_tags_and_per_op_kw_changes_fingerprint():
    backend = _sim(seed0=1)
    with pytest.raises(ValueError, match="implementation tags"):
        backend.make_epoch(0).op("allreduce#ref")
    d = ExperimentDesign(n_launch_epochs=2, nrep=5)
    honest = _sim(seed0=1).factors(d).fingerprint()
    seeded = _sim(seed0=1, per_op_kw={"alltoall": dict(alpha=9e-6)})
    assert seeded.factors(d).fingerprint() != honest


# ---------------------------------------------------------------------------
# Statistics: Holm correction, within-table comparison
# ---------------------------------------------------------------------------

def test_holm_bonferroni_adjustment():
    adj = holm_bonferroni([0.01, 0.04, 0.03, 0.9])
    np.testing.assert_allclose(adj, [0.04, 0.09, 0.09, 0.9])
    assert holm_bonferroni([]).size == 0
    np.testing.assert_allclose(holm_bonferroni([0.5]), [0.5])
    assert np.all(holm_bonferroni([0.4, 0.5, 0.6]) <= 1.0)
    with pytest.raises(ValueError):
        holm_bonferroni([0.1, 1.5])


def test_compare_cases_within_one_table():
    backend = _sim(seed0=13, p=4)
    cases = [TestCase("bcast", 1024), TestCase("alltoall", 1024)]
    from repro.core import run_design

    records = run_design(_design(), backend, cases=cases)
    table = analyze_records(records)
    row = compare_cases(table, cases[0], cases[1])
    assert row.case == cases[0]
    assert row.avg_a < row.avg_b          # bcast is cheaper than alltoall
    assert row.p_a_less <= 0.05
    with pytest.raises(ValueError, match="no data"):
        compare_cases(table, TestCase("nope", 1), cases[1])


def test_compare_tables_raises_without_common_cells():
    backend = _sim(seed0=17, p=4)
    from repro.core import run_design

    ta = analyze_records(run_design(_design(n_launch_epochs=2), backend,
                                    cases=[TestCase("bcast", 256)]))
    tb = analyze_records(run_design(_design(n_launch_epochs=2), backend,
                                    cases=[TestCase("bcast", 512)]))
    with pytest.raises(ValueError, match="no common"):
        compare_tables(ta, tb)


# ---------------------------------------------------------------------------
# Guideline engine
# ---------------------------------------------------------------------------

def test_compile_cases_dedups_shared_sides():
    gls = [
        Guideline("a", lhs="allgather", rhs="alltoall"),
        Guideline("b", lhs="allreduce", rhs="allreduce", rhs_msize_scale=2.0),
        Guideline("c", lhs="allreduce", rhs="reduce+bcast"),
    ]
    cases = compile_cases(gls, msizes=(1024, 2048))
    keys = [c.key() for c in cases]
    assert len(keys) == len(set(keys))
    # monotonicity rhs at 2x1024 coincides with the 2048 lhs cell
    assert ("allreduce", 2048) in keys
    assert sum(1 for k in keys if k == ("allreduce", 2048)) == 1


def test_honest_sim_library_passes_all_guidelines():
    report = verify_guidelines(SIM_GUIDELINES, _sim(seed0=2),
                               design=_design(), msizes=(1024, 8192))
    assert len(report.verdicts) == 10
    assert report.ok and not report.violations()
    # every family holds with positive evidence, not mere non-refutation
    assert all(v.verdict == "holds(<)" for v in report.verdicts)
    text = format_report(report)
    assert "all 10 cells hold" in text
    assert format_violations(report) == ""


def test_seeded_violation_inflated_alltoall_is_flagged():
    """The true-violation direction: a deliberately inflated alltoall
    base_time breaks the mock-up guideline that bounds alltoall from
    above, and only that guideline."""
    gls = list(SIM_GUIDELINES) + [
        # synthetic mock-up upper bound on alltoall (honest models satisfy
        # it comfortably: see the cost presets in repro.core.mpi_ops)
        Guideline("alltoall_mock_bound", lhs="alltoall",
                  rhs="allreduce*2+bcast*2",
                  description="mock-up bound: alltoall ⪯ allreduce(2m)+bcast(2m)"),
    ]
    honest = verify_guidelines(gls, _sim(seed0=4), design=_design(),
                               msizes=(1024,))
    assert honest.ok

    seeded = verify_guidelines(
        gls,
        _sim(seed0=4, per_op_kw={"alltoall": dict(alpha=12e-6, gamma=10e-6)}),
        design=_design(), msizes=(1024,))
    bad = seeded.violations()
    assert [v.guideline.name for v in bad] == ["alltoall_mock_bound"]
    v = bad[0]
    assert v.verdict == "VIOLATED" and v.ratio > 1.0
    assert v.p_violated <= v.p_holm <= 0.05
    assert "alltoall_mock_bound" in format_violations(seeded)


def test_seeded_violation_inflated_allgather_breaks_pattern_containment():
    report = verify_guidelines(
        SIM_GUIDELINES,
        _sim(seed0=6, per_op_kw={"allgather": dict(alpha=9e-6, gamma=8e-6)}),
        design=_design(), msizes=(1024,))
    names = {v.guideline.name for v in report.violations()}
    assert names == {"allgather_pat_alltoall"}


# ---------------------------------------------------------------------------
# Store: resumable verification campaigns
# ---------------------------------------------------------------------------

def test_guideline_campaign_resumes_from_store(tmp_path):
    store = ResultStore(tmp_path / "g.jsonl")
    first = verify_guidelines(SIM_GUIDELINES, _sim(seed0=8),
                              design=_design(), msizes=(1024,), store=store)
    assert first.n_measured > 0 and first.n_resumed == 0
    again = verify_guidelines(SIM_GUIDELINES, _sim(seed0=8),
                              design=_design(), msizes=(1024,), store=store)
    assert again.n_measured == 0
    assert again.n_resumed == first.n_measured
    assert [v.verdict for v in again.verdicts] == \
        [v.verdict for v in first.verdicts]
    for a, b in zip(first.verdicts, again.verdicts):
        assert a.lhs_us == pytest.approx(b.lhs_us)
        assert a.p_violated == pytest.approx(b.p_violated)


def test_killed_guideline_campaign_resumes_missing_cells_only(tmp_path):
    """Simulate a campaign killed mid-write: keep half the record lines
    plus a truncated tail. Resume warns about the torn line, re-measures
    only the missing cells, and still produces the full verdict table."""
    path = tmp_path / "g.jsonl"
    full = verify_guidelines(SIM_GUIDELINES, _sim(seed0=9),
                             design=_design(), msizes=(1024,),
                             store=ResultStore(path))
    lines = path.read_text().splitlines()
    # schema header + declaration + half the records
    n_keep = 2 + (len(lines) - 2) // 2
    killed = tmp_path / "killed.jsonl"
    killed.write_text("\n".join(lines[:n_keep]) + "\n"
                      + '{"kind": "record", "fingerprint": "'[:40])
    with pytest.warns(RuntimeWarning, match="undecodable"):
        resumed = verify_guidelines(SIM_GUIDELINES, _sim(seed0=9),
                                    design=_design(), msizes=(1024,),
                                    store=ResultStore(killed))
    assert resumed.n_resumed == n_keep - 2
    assert resumed.n_resumed + resumed.n_measured == full.n_measured
    assert len(resumed.verdicts) == len(full.verdicts)
    assert resumed.ok


# ---------------------------------------------------------------------------
# Kernel backend: impl tags (pallas vs ref inside one campaign)
# ---------------------------------------------------------------------------

def test_kernel_backend_impl_tags_and_composites():
    backend = KernelBackend(batch=1, heads=2, head_dim=16)
    ctx = backend.make_epoch(0)
    t_ref = backend.measure(ctx, TestCase("flash_attention#ref", 64), 2)
    assert t_ref.size == 2 and np.all(t_ref > 0)
    t_seq = backend.measure(
        ctx, TestCase("flash_attention#ref+flash_attention#ref", 64), 2)
    assert t_seq.size == 2 and np.all(t_seq > 0)
    with pytest.raises(ValueError, match="@half"):
        backend.measure(ctx, TestCase("flash_attention#ref@half", 64), 1)


@pytest.mark.jaxdevices(4)
def test_jax_backend_composite_collective(tmp_path):
    from repro.campaign import JaxBackend

    backend = JaxBackend(n_devices=4)
    ctx = backend.make_epoch(0)
    times = backend.measure(ctx, TestCase("psum+all_gather", 1024), 3)
    assert times.size == 3 and np.all(times > 0)
    half = backend.measure(ctx, TestCase("psum@half", 1024), 3)
    assert half.size == 3 and np.all(half > 0)
    with pytest.raises(ValueError, match="implementation tags"):
        backend.measure(ctx, TestCase("psum#x", 1024), 1)

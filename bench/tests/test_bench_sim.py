"""CPU rehearsals of the simulator cells: the last line, the control and
a planted fault, each through the harness's cell code at tiny sizes."""

import numpy as np
import pytest

import rehearsal

CELLS = ["sim-fused-p64", "sim-guidelines-p8"]


@pytest.mark.parametrize("trace", [False, True], ids=["e2e", "traced"])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_last_line(cell, trace):
    result, _, _ = rehearsal.run(cell, trace=trace)
    rehearsal.check_last_line(cell, result, trace)


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_where_the_program_passes(cell):
    _, checks, control = rehearsal.run(cell, control=True)
    assert all(c.ok for c in checks), checks
    assert not all(c.ok for c in control), control


@pytest.fixture
def altered_window(monkeypatch):
    """The window program's times altered where they are produced."""
    from repro.simjax import engine

    fused, epoch = engine._jitted_fused, engine._jitted

    def alter(fn, i):
        def call(*a, **kw):
            out = list(fn(*a, **kw))
            out[i] = out[i] * (1.0 + 1e-3)
            return tuple(out)
        return call

    monkeypatch.setattr(engine, "_jitted_fused", lambda: (
        fused()[0], fused()[1], alter(fused()[2], 0)))
    monkeypatch.setattr(engine, "_jitted", lambda: (
        epoch()[0], epoch()[1], alter(epoch()[2], 0)))


@pytest.fixture
def stale_state(monkeypatch):
    """The sample program hands back the AR(1) state it was given."""
    from repro.simjax import engine

    fused = engine._jitted_fused

    def stale(fn):
        def call(seeds, j, t0, ar_state, *a, **kw):
            dur, _ = fn(seeds, j, t0, ar_state, *a, **kw)
            return dur, np.asarray(ar_state)
        return call

    monkeypatch.setattr(engine, "_jitted_fused", lambda: (
        fused()[0], stale(fused()[1]), fused()[2]))


@pytest.fixture
def altered_sync(monkeypatch):
    """Every re-anchored clock model's intercept altered by 1 ns where it
    is produced."""
    from repro.core.clocks import LinearModel

    anchor = LinearModel.with_intercept_from_offset

    def altered(self, diff, ts):
        m = anchor(self, diff, ts)
        return LinearModel(m.slope, m.intercept + 1e-9)
    monkeypatch.setattr(LinearModel, "with_intercept_from_offset", altered)


@pytest.mark.parametrize("fault", ["altered_window", "stale_state",
                                   "altered_sync"])
def test_planted_fault_reads_not_correct(fault, request):
    request.getfixturevalue(fault)
    result, _, _ = rehearsal.run("sim-fused-p64")
    assert result["correct"] is False


def test_simulator_unlike_its_configuration_is_refused(monkeypatch):
    """A simulator that drops a stated synchronization size (and so runs
    its own default) does not run."""
    from repro.campaign import backends

    keep = backends._filter_sync_kw
    monkeypatch.setattr(backends, "_filter_sync_kw", lambda name, kw: {
        k: v for k, v in keep(name, kw).items() if k != "n_exchanges"})
    with pytest.raises(ValueError, match="sync_exchanges"):
        rehearsal.run("sim-fused-p64")


@pytest.mark.parametrize("sync", ["hca", "hca2"])
def test_hca_reference_reproduces_the_synchronization(sync):
    """On a small cluster whose size is no power of two, the reference's
    drift models from the captured exchanges are the program's."""
    from benchlib import ref_sim
    from benchlib.systems.simnet import _SyncCapture
    from repro.core.simnet import SimNet
    from repro.core.sync import make_sync

    cap = _SyncCapture(np.random.default_rng(0), keep=1)
    cap.install()
    try:
        cap.on = True
        make_sync(sync, n_fitpts=20, n_exchanges=9).synchronize(
            SimNet(5, seed=11))
    finally:
        cap.uninstall()
    (rec,) = cap.kept
    assert rec["hierarchical"] is (sync == "hca2")
    slope, icpt = ref_sim.hca_models(rec["fits"], rec["offsets"], rec["p"],
                                     rec["hierarchical"])
    np.testing.assert_allclose(slope, rec["slope"], rtol=1e-9, atol=1e-18)
    np.testing.assert_allclose(icpt, rec["intercept"], rtol=1e-9, atol=1e-15)
    assert np.any(rec["intercept"] != 0.0)

"""The readers of the program's own spans (``benchlib/progspans.py``): by
hand on synthetic runs, and on CPU rehearsals of the cells."""

import statistics
import time

import pytest

import rehearsal
from benchlib import harness, progspans
from benchlib import trace as tr
from benchlib.probes import Spans
from repro.core.telemetry import Span

SIM_CELLS = ["sim-fused-p64", "sim-guidelines-p8"]
SPLITS = ("idle_sync.sim", "idle_engine.sim", "idle_other.sim")


def _run(program, ops=(), modules=(), campaigns=(), kind="TPU v5 lite"):
    """A run whose window is [0, 100] ns on the host clock and [1000,
    1100] on the trace's; ``program`` spans are on the host clock."""
    run = harness.Run(workload="w", seed=0, seconds=1.0, traced=True,
                      device_kind=kind, spans=Spans(False))
    run.spans.items = [("window", 0.0, 100e-9)]
    run.trace = tr.Trace(
        ops={"/device:TPU:0": [(1000 + s, 1000 + e, "fusion.1")
                               for s, e in ops]},
        modules={"/device:TPU:0": [(1000 + s, 1000 + e, "jit_serve_step(1)")
                                   for s, e in modules]} if modules else {},
        spans=[(1000.0, 1100.0, "window")])
    run.campaigns = [dict(start=s * 1e-9, end=e * 1e-9, completed=c, info={})
                     for s, e, c in campaigns]
    run._program = [Span(n, s, e) for n, s, e in program]
    return run


@pytest.fixture
def program_spans(monkeypatch):
    """The readers take the program's spans from the run under test."""
    def use(run):
        monkeypatch.setattr(progspans, "_program_spans",
                            lambda: (run._program, 0))
        return run
    return use


def test_idle_split_by_hand(program_spans):
    run = program_spans(_run(
        [("campaign", 0, 90), ("epoch_build", 5, 40), ("clock_sync", 10, 30),
         ("sim_engine", 45, 80), ("sim_wait", 60, 70)],
        ops=[(20, 25), (62, 68), (85, 95)]))
    # idle [0, 20], [25, 62], [68, 85], [95, 100]: 79 of 100 ns. Under
    # clock_sync [10, 20] and [25, 30]; under sim_engine or sim_wait
    # [45, 62] and [68, 80]; the rest under campaign, epoch_build or none
    split = progspans.idle_split(run)
    assert split == pytest.approx({"sync": 15.0, "engine": 29.0,
                                   "other": 35.0})
    assert sum(split.values()) == pytest.approx(
        100.0 * tr.idle_share(run.trace, 1000.0, 1100.0))


def test_meter_gap_by_hand(program_spans):
    run = program_spans(_run(
        [("campaign", 5, 70), ("timed_call", 10, 30), ("timed_call", 40, 60),
         ("campaign", 80, 100), ("timed_call", 85, 99)],
        ops=[(12, 26), (41, 59), (86, 98)],
        modules=[(12, 26), (41, 59), (86, 98)],
        campaigns=[(5, 70, True), (80, 100, False)]))
    # the calls of the completed campaign: 20 - 14 and 20 - 18 ns
    assert progspans.meter_gap_ms(run) == pytest.approx(4e-6)


@pytest.mark.parametrize("kind,want", [("TPU v5 lite", None),
                                       ("cpu", 4e-6)])
def test_meter_gap_without_a_module_line(kind, want, program_spans):
    """Only a trace recorded on the CPU, which has no module line, takes
    the union of the operations for the step's device time: a device's
    trace without the step program reads None."""
    run = program_spans(_run(
        [("campaign", 5, 70), ("timed_call", 10, 30), ("timed_call", 40, 60)],
        ops=[(12, 26), (41, 59)], campaigns=[(5, 70, True)], kind=kind))
    got = progspans.meter_gap_ms(run)
    assert got == (None if want is None else pytest.approx(want))


def test_innermost_cuts_a_span_that_outlasts_its_parent():
    pieces = progspans.innermost([(0, 10, "a"), (5, 15, "b")], 0, 20)
    assert pieces == [(0, 5, "a"), (5, 10, "b"), (10, 20, "")]


@pytest.mark.parametrize("case", ["no_spans", "outside_window",
                                  "no_telemetry"])
def test_readers_give_none_without_program_spans(case, monkeypatch,
                                                 program_spans):
    import sys

    program = {"no_spans": [],
               "outside_window": [("campaign", 200, 300),
                                  ("clock_sync", 210, 220),
                                  ("timed_call", 230, 240)],
               "no_telemetry": [("clock_sync", 10, 30)]}[case]
    run = _run(program, ops=[(20, 25)], modules=[(20, 25)],
               campaigns=[(0, 100, True)])
    if case == "no_telemetry":       # a program without the module
        import repro.core

        monkeypatch.delattr(repro.core, "telemetry")
        monkeypatch.setitem(sys.modules, "repro.core.telemetry", None)
    else:
        program_spans(run)
    for name in SPLITS + ("meter_gap_ms.decode",):
        assert harness.load_reader(name)(run) is None, name


_TRACED: dict = {}


def _traced(cell):
    if cell not in _TRACED:
        _TRACED[cell] = rehearsal.run(cell, trace=True)[0]["metrics"]
    return _TRACED[cell]


@pytest.mark.parametrize("cell", SIM_CELLS)
def test_sim_splits_add_up_to_the_idle_share(cell):
    m = _traced(cell)
    parts = [m[k]["value"] for k in SPLITS]
    assert all(v >= 0.0 for v in parts), parts
    assert sum(parts) == pytest.approx(m["idle_share.sim"]["value"],
                                       abs=1e-6)


def test_meter_gap_lies_below_the_mean_timed_call(monkeypatch):
    runs = []
    load = harness.load_reader

    def spy(name, root=harness.ROOT):
        read = load(name, root)

        def read_and_keep(run):
            runs.append(run)
            return read(run)
        return read_and_keep
    monkeypatch.setattr(harness, "load_reader", spy)
    result, _, _ = rehearsal.run("mamba2-decode-b64", trace=True)
    gap = result["metrics"]["meter_gap_ms.decode"]["value"]
    mean_call_ms = statistics.fmean(runs[0].cell.calls.durations) * 1e3
    assert 0.0 <= gap < mean_call_ms


def test_a_slower_clock_sync_raises_only_its_split(monkeypatch):
    """50 ms of sleep planted inside every launch epoch's clock
    synchronization: ``idle_sync.sim`` rises, the other splits do not."""
    from repro.campaign import backends

    make_sync = backends.make_sync

    def slow_make_sync(name, **kw):
        sync = make_sync(name, **kw)
        synchronize = sync.synchronize

        def slow(*a, **k):
            time.sleep(0.05)
            return synchronize(*a, **k)
        sync.synchronize = slow
        return sync

    cell = "sim-fused-p64"
    sound = _traced(cell)
    monkeypatch.setattr(backends, "make_sync", slow_make_sync)
    slow = rehearsal.run(cell, trace=True)[0]["metrics"]
    assert slow["idle_sync.sim"]["value"] > \
        sound["idle_sync.sim"]["value"] + 10.0
    for k in ("idle_engine.sim", "idle_other.sim"):
        assert slow[k]["value"] <= sound[k]["value"] + 1.0, (k, slow, sound)

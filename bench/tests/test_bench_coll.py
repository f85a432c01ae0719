"""The collectives cell (``coll-small-4chip``) on four CPU devices: the
last line at tiny sizes, the control, planted faults, the reference
pinned to the program's payload layout and collectives, the end-to-end
metric by hand, and its readers on a synthetic four-chip trace."""

import math
from types import SimpleNamespace

import numpy as np
import pytest

import rehearsal
from benchlib import callgap, harness, progspans, ref_coll
from benchlib import trace as tr
from benchlib.probes import Spans
from benchlib.systems import collectives
from repro.core.telemetry import Span

CELL = "coll-small-4chip"
TINY = {"traffic": {"n_launch_epochs": 2, "nrep": 4, "msizes": [1024],
                    "keep_prob": 1.0}}
READERS = ("coll_device_us", "meter_gap_us.coll", "idle_share.coll")


def _run(trace=False, control=False, seed=4_000_000_321):
    import jax

    # bench/tests/conftest.py asks for four host devices; without them the
    # cell refuses to run, and this says why
    assert jax.device_count() >= 4, jax.devices()
    with rehearsal.jax_settings_restored():
        return harness.run_workload(
            CELL, seed, 3.0 if trace else 1.5, trace, require_tpu=False,
            overrides={k: dict(v) for k, v in TINY.items()},
            peaks=rehearsal.CPU_PEAKS, with_control=control,
            log=lambda msg: None)


@pytest.mark.parametrize("trace", [False, True], ids=["e2e", "traced"])
def test_rehearsal_last_line(trace):
    result, checks, _ = _run(trace=trace)
    rehearsal.check_last_line(CELL, result, trace)
    assert result["device"]["count"] == 4
    assert {c.name for c in checks} == {"coll_max_abs_err",
                                        "coll_cases_missing"}
    if trace:
        # a CPU trace has no module line, so coll_device_us reads nothing
        assert {"meter_gap_us.coll", "idle_share.coll"} <= set(
            result["metrics"])


def test_control_fails_where_the_program_passes():
    _, checks, control = _run(control=True)
    assert all(c.ok for c in checks), checks
    by = {c.name: c for c in control}
    assert not by["coll_max_abs_err"].ok and by["coll_max_abs_err"].value > 0
    # the program's psum rounds in float32, a hundred times under the limit
    err = {c.name: c for c in checks}["coll_max_abs_err"]
    assert 0 < err.value < err.limit / 100, err


def _wrap_lax(monkeypatch, name, fault):
    """Replace ``jax.lax.<name>`` by ``fault(original, *args)`` where the
    program's pmapped collectives trace it, in the timed path."""
    import jax

    orig = getattr(jax.lax, name)
    monkeypatch.setattr(jax.lax, name,
                        lambda *a, **kw: fault(orig, *a, **kw))


@pytest.fixture
def misrouted(monkeypatch):
    """all_to_all delivers the blocks of chips 0 and 1 to each other's
    place."""
    import jax.numpy as jnp

    _wrap_lax(monkeypatch, "all_to_all",
              lambda f, x, *a, **kw: f(x, *a, **kw)[jnp.array([1, 0, 2, 3])])


@pytest.fixture
def missing_chip(monkeypatch):
    """psum leaves out the last chip's payload."""
    import jax
    import jax.numpy as jnp

    def fault(f, x, axis_name, **kw):
        last = jax.lax.axis_index(axis_name) == 3
        return f(jnp.where(last, jnp.zeros_like(x), x), axis_name, **kw)
    _wrap_lax(monkeypatch, "psum", fault)


@pytest.fixture
def no_exchange(monkeypatch):
    """psum exchanges nothing: each chip keeps its own payload."""
    _wrap_lax(monkeypatch, "psum", lambda f, x, *a, **kw: x)


@pytest.fixture
def altered(monkeypatch):
    """all_gather's result altered in one value where it is produced."""
    _wrap_lax(monkeypatch, "all_gather",
              lambda f, *a, **kw: f(*a, **kw).at[0, 0].add(1.0))


@pytest.fixture
def stale(monkeypatch):
    """Each case's program and placed payload built once and reused by
    every later launch epoch: a result of earlier payloads."""
    from repro.campaign import JaxBackend

    build, memo = JaxBackend._build_collective, {}

    def once(self, op, msize, n=None):
        if (op, msize) not in memo:
            memo[op, msize] = build(self, op, msize, n)
        return memo[op, msize]
    monkeypatch.setattr(JaxBackend, "_build_collective", once)


@pytest.fixture
def untimed_case(monkeypatch):
    """The backend hands back times for all_gather without calling it."""
    from repro.campaign import JaxBackend

    measure = JaxBackend.measure

    def fake(self, ctx, case, nrep):
        if case.op == "all_gather":
            return np.full(nrep, 1e-4)
        return measure(self, ctx, case, nrep)
    monkeypatch.setattr(JaxBackend, "measure", fake)


@pytest.mark.parametrize("fault,number", [
    ("misrouted", "coll_max_abs_err"), ("missing_chip", "coll_max_abs_err"),
    ("no_exchange", "coll_max_abs_err"), ("altered", "coll_max_abs_err"),
    ("stale", "coll_max_abs_err"), ("untimed_case", "coll_cases_missing")])
def test_planted_fault_reads_not_correct(fault, number, request):
    request.getfixturevalue(fault)
    result, _, _ = _run()
    assert result["correct"] is False
    assert result["checks"][number]["value"] > 0, result["checks"]


def test_backend_differing_from_the_configuration_is_refused():
    with pytest.raises(harness.BenchError, match="warmup"):
        with rehearsal.jax_settings_restored():
            harness.run_workload(
                CELL, 1, 1.0, False, require_tpu=False, log=lambda m: None,
                overrides={"config": {"warmup": 5}, **TINY},
                peaks=rehearsal.CPU_PEAKS)


@pytest.mark.parametrize("msize", [1, 1024, 16384, 262144, 3000])
@pytest.mark.parametrize("op", ref_coll.OPS)
def test_reference_is_the_program_payload_and_result(op, msize):
    """``ref_coll``'s payloads in ``JaxBackend``'s own layout (shape and
    dtype), the program's own check still exact, and the program's
    collective on the cell's payloads equal to the reference's, but for
    the float32 rounding of a sum."""
    from repro.campaign import JaxBackend

    b = JaxBackend(n_devices=4)
    host = b._input(op, msize, 4)
    key = (4_000_000_123, 2, 7)
    mine = ref_coll.payload(op, msize, 4, key)
    assert host.dtype == mine.dtype and host.shape == mine.shape
    np.testing.assert_array_equal(mine, ref_coll.payload(op, msize, 4, key))
    assert b.check(op, msize) == 0.0
    b._input = lambda *a: mine
    got = np.asarray(b._build_collective(op, msize, 4)())
    want = ref_coll.result(op, msize, 4, key)
    err = ref_coll.max_abs_err(got, want)
    scale = float(np.max(np.abs(want)))
    if op == "psum":
        assert err <= 4 * scale * 2.0 ** -24
    else:
        assert err == 0.0
    lower = ref_coll.max_abs_err(
        ref_coll.result(op, msize, 4, key, compute="bfloat16"), want)
    assert lower > 1e-4 * scale


def test_payloads_are_new_for_every_case_and_batch():
    """No two batches of a run, and no two cases, share a payload value
    layout: a result of other payloads cannot pass for this one."""
    a = ref_coll.payload("psum", 1024, 4, (5, 0, 1))
    for key in [(5, 0, 2), (5, 1, 1), (6, 0, 1)]:
        assert np.max(np.abs(a - ref_coll.payload("psum", 1024, 4, key))) > 1
    bits = a.view(np.uint32) & 0xFFFF      # the low mantissa bits are used
    assert len(np.unique(bits)) > 200


def test_coll_us_p95_by_hand():
    order = ["psum@1024"] * 100 + ["all_gather@1024"] * 50
    durations = [i * 1e-6 for i in range(1, 101)] + [200e-6] * 50
    # p95 of 1..100 us by linear interpolation: 95.05 us, whatever the
    # order the calls ran in; a case without a timed call is the check's
    # to count, not the metric's
    want = math.sqrt(95.05 * 200.0)
    rng = np.random.default_rng(0)
    perm = rng.permutation(len(order))
    for idx in (range(len(order)), perm):
        got = collectives.p95_geomean_us([durations[i] for i in idx],
                                         [order[i] for i in idx])
        assert got == pytest.approx(want, rel=1e-12)
    with pytest.raises(RuntimeError):
        collectives.p95_geomean_us([], [])


def test_case_clock_keeps_the_case_of_every_timed_call(monkeypatch):
    """Warm-up calls open a batch and are not timed; each batch's timed
    calls go to its case."""
    from benchlib import probes

    clock = collectives.CaseClock(False, np.random.default_rng(0), 0.0)
    ticks = iter(float(t * t) for t in range(100))
    monkeypatch.setattr(probes, "time",
                        SimpleNamespace(perf_counter=lambda: next(ticks)))
    a, b = clock.wrap("psum@1024", lambda: 1), clock.wrap("psum@16", lambda: 2)
    with clock.batch(2):
        for _ in range(3):        # one warm-up, two timed
            a()
    with clock.batch(1):
        b()
    with clock.batch(0):          # a batch with no call
        pass
    with clock.batch(2):
        a()
        a()
    # clock t*t: starts 0, 1, 4 and end 9; start 16 and end 25; the empty
    # batch's end 36; starts 49, 64 and end 81
    assert clock.durations == [3.0, 5.0, 9.0, 15.0, 17.0]
    assert clock.order == ["psum@1024", "psum@1024", "psum@16",
                           "psum@1024", "psum@1024"]


# -- the readers on a synthetic four-chip trace -----------------------------

DEVS = [f"/device:TPU:{i}" for i in range(4)]


def _synthetic(program_spans):
    """Window [0, 1000] ns on the host clock, [5000, 6000] on the trace's.
    Two timed calls in a completed campaign [0, 600], each 100 ns long,
    at 100 and 300; a third at 700, in a campaign left open. Each chip runs
    one collective program in each call: 40 ns on chip 0, 20 ns on the
    others; an unrelated program runs 50 ns on chip 0 only."""
    run = harness.Run(workload=CELL, seed=0, seconds=1.0, traced=True,
                      device_kind="TPU v5 lite", spans=Spans(False))
    run.spans.items = [("window", 0.0, 1000e-9)]
    mods, ops = {}, {}
    for i, d in enumerate(DEVS):
        dur = 40 if i == 0 else 20
        mods[d] = [(5000 + t + 10, 5000 + t + 10 + dur,
                    "jit_call_wrapped(7)") for t in (100, 300, 700)]
        ops[d] = [(s, e, "all-reduce.1") for s, e, _ in mods[d]]
    mods[DEVS[0]].append((5850, 5900, "jit_other(2)"))
    ops[DEVS[0]].append((5850, 5900, "fusion.3"))
    calls = [(5000 + t + 1, 5000 + t + 5, "call") for t in (100, 300, 700)]
    run.trace = tr.Trace(ops=ops, modules=mods,
                         spans=[(5000.0, 6000.0, "window")] + calls)
    run.cell = SimpleNamespace(calls=SimpleNamespace(
        order=["psum@1024", "all_gather@1024", "psum@1024"]))
    run.campaigns = [dict(start=0.0, end=600e-9, completed=True, info={}),
                     dict(start=600e-9, end=1000e-9, completed=False,
                          info={})]
    return program_spans([Span("timed_call", t, t + 100)
                          for t in (100, 300, 700)]
                         + [Span("campaign", 0, 600)], run)


@pytest.fixture
def program_spans(monkeypatch):
    def use(spans, run):
        monkeypatch.setattr(progspans, "_program_spans",
                            lambda: (spans, 0))
        return run
    return use


def test_readers_on_a_synthetic_four_chip_trace(program_spans, capsys):
    run = _synthetic(program_spans)
    got = {name: harness.load_reader(name)(run) for name in READERS}
    # each timed call goes to its case, in the order the calls ran
    assert ("all_gather@1024 0.100 / 0.025, 75.00%, psum@1024 0.100 / "
            "0.025, 75.00%") in capsys.readouterr().err
    # per call (3 in the window), averaged over chips: (40 + 3 x 20) / 4 ns
    assert got["coll_device_us"] == pytest.approx(25e-3)
    # the completed campaign's two calls: 100 ns less 25 ns of program
    assert got["meter_gap_us.coll"] == pytest.approx(75e-3)
    # busy: chip 0 170 ns of 1000, the others 60 ns each
    assert got["idle_share.coll"] == pytest.approx(
        100.0 * (1 - (170 + 3 * 60) / 4 / 1000))


def test_meter_gap_sweep_agrees_with_progspans(program_spans):
    """The bisection gives the gap that ``progspans.meter_gap_ms`` gives
    one call at a time, on a trace with overlapping and long programs."""
    run = _synthetic(program_spans)
    rng = np.random.default_rng(3)
    for d in DEVS:
        run.trace.modules[d] += [
            (5000 + s, 5000 + s + w, "jit_call_wrapped(9)")
            for s, w in zip(rng.uniform(0, 900, 30), rng.uniform(1, 250, 30))]
        run.trace.modules[d].sort()
    want = progspans.meter_gap_ms(run, collectives.PROGRAM_KEY)
    calls = callgap.timed_calls(run, collectives.PROGRAM_KEY)
    assert len(calls) == 2
    assert sum(s - d for s, d in calls) / len(calls) * 1e-6 == \
        pytest.approx(want, rel=1e-12)
    assert callgap.timed_calls(run, "no_such_program") is None


def test_readers_read_nothing_without_the_program(program_spans):
    run = _synthetic(program_spans)
    run.trace.modules = {d: [(s, e, "jit_other(2)") for s, e, _ in evs]
                         for d, evs in run.trace.modules.items()}
    assert harness.load_reader("coll_device_us")(run) is None
    assert harness.load_reader("meter_gap_us.coll")(run) is None

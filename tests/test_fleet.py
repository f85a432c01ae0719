"""Fault-tolerant fleet execution: the retry policy, the hardened
parallel map, store corruption handling, the lease queue's exact
schedules, deterministic fault injection, shard federation, and the
chaos-fleet invariant — a fleet store under injected faults is
record-identical to a serial no-fault run, with quarantined cells
excluded *and reported*."""

import os
import tempfile
import time
import warnings
from pathlib import Path

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.campaign import (Campaign, CampaignSpec, ResultStore, SimBackend,
                            SweepScheduler)
from repro.core import RetryBudgetExceeded, RetryPolicy, retry_call
from repro.core.design import (ExperimentDesign, MeasurementRecord, TestCase,
                               map_parallel, run_design)
from repro.fleet import (CrashFault, FaultPlan, FaultyBackend, FleetConfig,
                         FleetScheduler, LeaseQueue, TransientFault,
                         merge_stores)
from repro.fleet.faults import TORN_LINE
from repro.fleet.queue import LEASED, PENDING, QUARANTINED
from repro.history import RunArchive
from repro.sweeps import default_sim_sweep

FAST_SYNC = dict(n_fitpts=60, n_exchanges=20)


def _tiny_sweep(seed=0, axes=("tuning",), n_launch_epochs=2, nrep=8):
    return default_sim_sweep(seed=seed, axes=axes, msizes=(512,),
                             n_launch_epochs=n_launch_epochs, nrep=nrep)


def _dump(store):
    """Every record of every campaign, exact times included — the
    bit-identity yardstick."""
    out = {}
    for fp in store.fingerprints():
        out[fp] = sorted(
            (r.case.op, r.case.msize, r.epoch,
             tuple(np.asarray(r.times, np.float64).tolist()))
            for r in store.records(fp))
    return out


class _FakeClock:
    """Deterministic clock for driving schedulers without real sleeps."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += max(float(s), 1e-4)


def _fast_fleet(**kw):
    clk = _FakeClock()
    kw.setdefault("n_workers", 1)
    kw.setdefault("clock", clk)
    kw.setdefault("sleep", clk.sleep)
    return FleetConfig(**kw)


# ---------------------------------------------------------------------------
# RetryPolicy / retry_call
# ---------------------------------------------------------------------------

def test_retry_policy_ceiling_grows_and_caps():
    p = RetryPolicy(base=0.1, factor=2.0, max_delay=0.5)
    assert [p.ceiling(k) for k in range(5)] == [0.1, 0.2, 0.4, 0.5, 0.5]


def test_retry_policy_seeded_delay_is_deterministic_and_jittered():
    p = RetryPolicy(base=0.1, seed=7)
    assert p.delay(2, key=3) == p.delay(2, key=3)
    assert p.delay(2, key=3) != p.delay(2, key=4)   # per-key streams
    assert p.delay(2, key=3) != RetryPolicy(base=0.1, seed=8).delay(2, key=3)
    for k in range(6):
        assert 0.0 <= p.delay(k) <= p.ceiling(k)


def test_retry_policy_deadline_caps_schedule():
    p = RetryPolicy(base=1.0, factor=2.0, max_delay=100.0, attempts=10,
                    deadline=2.0, seed=0)
    sched = list(p.delays())
    assert sum(sched) <= 2.0 and len(sched) < 9


def test_retry_policy_validation():
    with pytest.raises(ValueError, match="factor"):
        RetryPolicy(factor=0.5)
    with pytest.raises(ValueError, match="attempts"):
        RetryPolicy(attempts=0)
    with pytest.raises(ValueError, match=">= 0"):
        RetryPolicy(base=-1.0)


def test_retry_call_succeeds_after_transient_failures():
    calls = {"n": 0}
    slept = []

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError("transient")
        return "ok"

    p = RetryPolicy(base=0.01, attempts=5, seed=0)
    assert retry_call(flaky, p, sleep=slept.append) == "ok"
    assert calls["n"] == 3 and len(slept) == 2
    assert slept == [p.delay(0), p.delay(1)]   # the exact seeded schedule


def test_retry_call_exhaustion_chains_last_error():
    def boom():
        raise ValueError("always")

    with pytest.raises(RetryBudgetExceeded) as ei:
        retry_call(boom, RetryPolicy(base=0.0, attempts=3, seed=0),
                   sleep=lambda s: None)
    assert ei.value.attempts == 3
    assert isinstance(ei.value.__cause__, ValueError)


def test_retry_call_does_not_retry_unmatched_exceptions():
    calls = {"n": 0}

    def boom():
        calls["n"] += 1
        raise TypeError("programming error")

    with pytest.raises(TypeError):
        retry_call(boom, RetryPolicy(attempts=5, seed=0),
                   retry_on=(OSError,), sleep=lambda s: None)
    assert calls["n"] == 1


# ---------------------------------------------------------------------------
# map_parallel hardening: setup fallback vs crash restart vs stall
# ---------------------------------------------------------------------------

def _mp_ret(x):
    return x


def _mp_crash_once(flag, x):
    if x == 0 and not os.path.exists(flag):
        open(flag, "w").close()
        os._exit(1)           # worker killed mid-task: BrokenProcessPool
    return x * 10


def _mp_always_crash(x):
    os._exit(1)


def _mp_hang(x):
    time.sleep(60)


def test_map_parallel_empty_and_serial_fallback_on_unpicklable():
    assert map_parallel(_mp_ret, [], 2) == []
    with pytest.warns(RuntimeWarning, match="not picklable"):
        assert map_parallel(lambda x: x, [(1,)], 2) is None


def test_map_parallel_restarts_pool_after_worker_crash(tmp_path):
    """One worker dies mid-run: the pool is restarted, only unfinished
    tasks are resubmitted, and the warning names the crash — no silent
    serial fallback."""
    flag = str(tmp_path / "crashed-once")
    with pytest.warns(RuntimeWarning, match="worker process died"):
        out = map_parallel(_mp_crash_once, [(flag, i) for i in range(3)],
                           n_workers=2, what="crash-once tasks",
                           retry=RetryPolicy(base=0.0, seed=0))
    assert out == [0, 10, 20]


def test_map_parallel_reraises_when_pool_keeps_dying():
    import concurrent.futures as cf

    with pytest.warns(RuntimeWarning, match="worker process died"):
        with pytest.raises(cf.process.BrokenProcessPool,
                           match="unfinished"):
            map_parallel(_mp_always_crash, [(i,) for i in range(2)],
                         n_workers=2, max_restarts=1,
                         retry=RetryPolicy(base=0.0, seed=0))


def test_map_parallel_stall_raises_timeout_naming_in_flight():
    t0 = time.time()
    with pytest.raises(TimeoutError, match="in flight"):
        map_parallel(_mp_hang, [(1,), (2,)], n_workers=2, timeout=0.5)
    assert time.time() - t0 < 30   # the hung workers were actually killed


def test_workers_refused_when_this_process_holds_a_tpu(monkeypatch,
                                                       tmp_path):
    """A TPU belongs to one process: with the platform probe reporting a
    held chip, every path that starts worker processes raises before any
    worker exists — map_parallel, run_design's epoch fan-out and the
    fleet's multi-process mode — and the in-process paths still run."""
    import repro.core.design as design_mod

    assert design_mod.holds_tpu() is False      # the CPU test process
    monkeypatch.setattr(design_mod, "holds_tpu", lambda: True)
    with pytest.raises(RuntimeError, match="holds a TPU"):
        map_parallel(_mp_ret, [(1,), (2,)], n_workers=2)
    spec, backend = _tiny_sweep(axes=("tuning",), n_launch_epochs=2, nrep=8)
    with pytest.raises(RuntimeError, match="holds a TPU"):
        run_design(ExperimentDesign(n_launch_epochs=2, nrep=5), backend,
                   cases=[TestCase("allreduce", 512)], n_workers=2)
    with pytest.raises(RuntimeError, match="one process at a time"):
        FleetScheduler(spec, backend, ResultStore(tmp_path / "f.jsonl"),
                       FleetConfig(n_workers=2, poll_s=0.02)).run()
    res = FleetScheduler(spec, backend, ResultStore(tmp_path / "s.jsonl"),
                         _fast_fleet()).run()
    assert res.n_cells_measured == 2 and not res.quarantined


# ---------------------------------------------------------------------------
# Store hardening: mid-file corruption, torn-tail healing
# ---------------------------------------------------------------------------

def _store_with_records(path, n=3, fp="fp-test"):
    store = ResultStore(path)
    store._append(dict(kind="campaign", fingerprint=fp, factors={}, spec={}))
    for e in range(n):
        store.append_record(fp, MeasurementRecord(
            case=TestCase("allreduce", 512), epoch=e,
            times=np.array([1.0 + e, 2.0 + e])))
    return store, fp


def test_store_counts_and_names_midfile_corruption(tmp_path):
    store, fp = _store_with_records(tmp_path / "s.jsonl")
    lines = (tmp_path / "s.jsonl").read_text().splitlines()
    lines.insert(3, '{"kind": "record", "fingerprint": "torn-in-the-mi')
    (tmp_path / "s.jsonl").write_text("\n".join(lines) + "\n")
    with pytest.warns(RuntimeWarning,
                      match=r's\.jsonl:4: dropping undecodable "record" '
                            r'line mid-file'):
        recs = store.records(fp)
    assert len(recs) == 3                 # every intact record survives
    assert store.n_corrupt == 1
    assert store.snapshot().n_corrupt == 1


def test_store_tail_truncation_warns_differently(tmp_path):
    store, fp = _store_with_records(tmp_path / "t.jsonl")
    raw = (tmp_path / "t.jsonl").read_bytes()
    (tmp_path / "t.jsonl").write_bytes(raw[:-20])   # tear the last line
    with pytest.warns(RuntimeWarning, match="truncated write from a killed"):
        recs = store.records(fp)
    assert len(recs) == 2 and store.n_corrupt == 1


def test_append_heals_torn_tail_instead_of_gluing(tmp_path):
    """Appending to a file whose last line was torn mid-write must not
    merge the new line into the garbage — the torn residue is newline-
    terminated first, so only *it* is lost."""
    store, fp = _store_with_records(tmp_path / "h.jsonl")
    raw = (tmp_path / "h.jsonl").read_bytes()
    (tmp_path / "h.jsonl").write_bytes(raw[:-20])
    store.append_record(fp, MeasurementRecord(
        case=TestCase("allreduce", 512), epoch=9,
        times=np.array([9.0, 9.5])))
    with pytest.warns(RuntimeWarning):
        recs = store.records(fp)
    assert {r.epoch for r in recs} == {0, 1, 9}   # the new append survived
    assert store.n_corrupt == 1                   # only the torn line lost


# ---------------------------------------------------------------------------
# LeaseQueue: exact claim/heartbeat/expiry/backoff/quarantine schedules
# ---------------------------------------------------------------------------

def _queue(n=3, ttl=10.0, budget=3, seed=0):
    policy = RetryPolicy(base=1.0, factor=2.0, max_delay=8.0, seed=seed)
    return LeaseQueue([(i, f"fp{i}") for i in range(n)], lease_ttl=ttl,
                      policy=policy, retry_budget=budget), policy


def test_queue_validation():
    with pytest.raises(ValueError, match="lease_ttl"):
        LeaseQueue([(0, "a")], lease_ttl=0)
    with pytest.raises(ValueError, match="retry_budget"):
        LeaseQueue([(0, "a")], lease_ttl=1, retry_budget=0)


def test_queue_claims_lowest_index_first_and_exhausts():
    q, _ = _queue(n=2)
    a = q.claim("w0", now=0.0)
    b = q.claim("w1", now=0.0)
    assert (a.index, b.index) == (0, 1)
    assert a.state == LEASED and a.worker == "w0"
    assert q.claim("w2", now=0.0) is None
    assert not q.finished()


def test_queue_heartbeat_extends_lease_and_expiry_fires_without_it():
    q, _ = _queue(ttl=10.0)
    t = q.claim("w0", now=0.0)
    assert q.expired(now=9.9) == []
    q.heartbeat(t.index, now=8.0)          # lease now runs to 18.0
    assert q.expired(now=15.0) == []
    assert [x.index for x in q.expired(now=18.0)] == [t.index]


def test_queue_release_requeues_behind_exact_backoff_gate():
    q, policy = _queue(n=1)
    t = q.claim("w0", now=0.0)
    assert q.release(t.index, now=100.0, error="crash") == PENDING
    gate = 100.0 + policy.delay(0, key=t.index)   # seeded, reproducible
    assert t.not_before == gate and t.attempts == 1
    assert q.claim("w1", now=gate - 1e-6) is None or gate == 100.0
    assert q.next_wake(now=100.0) == gate
    got = q.claim("w1", now=gate)
    assert got is t and t.worker == "w1"


def test_queue_stale_heartbeat_after_revocation_is_ignored():
    q, _ = _queue()
    t = q.claim("w0", now=0.0)
    q.release(t.index, now=5.0, error="lease expired")
    q.heartbeat(t.index, now=6.0)          # zombie worker phones home
    assert t.state == PENDING and t.lease_expires <= 10.0


def test_queue_quarantines_after_retry_budget():
    q, _ = _queue(n=1, budget=2)
    for k in range(2):
        t = q.claim("w0", now=float(k * 100))
        state = q.release(t.index, now=float(k * 100 + 1), error=f"e{k}")
    assert state == QUARANTINED and t.errors == ["e0", "e1"]
    assert q.finished() and q.claim("w1", now=1e9) is None
    assert [x.index for x in q.quarantined()] == [0]
    s = q.stats()
    assert s["n_quarantined"] == 1 and s["n_failed_attempts"] == 2


def test_queue_finished_and_next_wake():
    q, _ = _queue(n=2, ttl=5.0)
    a = q.claim("w0", now=0.0)
    q.complete(a.index)
    b = q.claim("w0", now=1.0)
    assert q.next_wake(now=1.0) == 6.0     # only the live lease's expiry
    q.complete(b.index)
    assert q.finished() and q.next_wake(now=1.0) is None


# ---------------------------------------------------------------------------
# Fault injection: seeded, deterministic, fingerprint-transparent
# ---------------------------------------------------------------------------

def test_fault_plan_decides_deterministically_per_cell_attempt():
    plan = FaultPlan(seed=3, p_crash=0.5, p_raise=0.5)
    for cell in range(6):
        assert plan.decide(cell, 0) == plan.decide(cell, 0)
    assert any(plan.decide(c, 0) != FaultPlan(seed=4, p_crash=0.5,
                                              p_raise=0.5).decide(c, 0)
               for c in range(6))


def test_fault_plan_spares_attempts_past_the_faulty_budget():
    plan = FaultPlan(seed=0, p_crash=1.0, max_faulty_attempts=2)
    assert plan.decide(0, 0) and plan.decide(0, 1)
    assert plan.decide(0, 2) == [] and plan.decide(0, 99) == []


def test_fault_plan_validation_and_parse():
    with pytest.raises(ValueError, match="p_crash"):
        FaultPlan(p_crash=1.5)
    plan = FaultPlan.parse("crash=0.4,straggle=0.2,seed=7,within_calls=3,"
                           "torn_on_crash=false")
    assert plan == FaultPlan(seed=7, p_crash=0.4, p_straggle=0.2,
                             within_calls=3, torn_on_crash=False)
    with pytest.raises(ValueError, match="unknown key"):
        FaultPlan.parse("explode=1.0")
    with pytest.raises(ValueError, match="key=value"):
        FaultPlan.parse("crash")
    assert not FaultPlan().any_faults() and plan.any_faults()


def test_faulty_backend_is_fingerprint_transparent():
    design = ExperimentDesign(n_launch_epochs=2, nrep=5, seed=0)
    inner = SimBackend(p=4, seed0=1, sync_kw=dict(FAST_SYNC))
    fb = FaultyBackend(inner, FaultPlan(seed=0, p_crash=1.0), cell_index=0)
    assert fb.factors(design).fingerprint() == \
        inner.factors(design).fingerprint()
    assert fb.name == inner.name


def test_faulty_backend_injects_at_the_decided_call(tmp_path):
    design = ExperimentDesign(n_launch_epochs=1, nrep=4, seed=0)
    case = TestCase("allreduce", 512)

    def fresh(plan, attempt=0, shard=None):
        inner = SimBackend(p=4, seed0=1, sync_kw=dict(FAST_SYNC))
        fb = FaultyBackend(inner, plan, cell_index=0, attempt=attempt,
                           hard=False, shard_path=shard)
        return fb, fb.make_epoch(0)

    fb, ctx = fresh(FaultPlan(seed=0, p_crash=1.0, within_calls=1))
    with pytest.raises(CrashFault, match="cell 0, attempt 0, call 1"):
        fb.measure(ctx, case, 4)
    fb, ctx = fresh(FaultPlan(seed=0, p_raise=1.0, within_calls=1))
    with pytest.raises(TransientFault):
        fb.measure(ctx, case, 4)
    # past the faulty-attempt budget the same plan is a no-op, and the
    # measured values are the inner backend's exactly
    fb, ctx = fresh(FaultPlan(seed=0, p_crash=1.0, within_calls=1),
                    attempt=1)
    ref, rctx = fresh(FaultPlan(seed=0))
    np.testing.assert_array_equal(fb.measure(ctx, case, 4),
                                  ref.measure(rctx, case, 4))
    # torn writes land newline-terminated garbage in the shard
    shard = tmp_path / "shard.jsonl"
    fb, ctx = fresh(FaultPlan(seed=0, p_torn=1.0, within_calls=1),
                    shard=str(shard))
    fb.measure(ctx, case, 4)
    assert shard.read_text().startswith(TORN_LINE)
    assert shard.read_text().endswith("\n")


# ---------------------------------------------------------------------------
# Store federation
# ---------------------------------------------------------------------------

def _campaign_into(path, backend, design, cases, name):
    store = ResultStore(path)
    res = Campaign(CampaignSpec(list(cases), design, name=name),
                   backend, store).run()
    return store, res


def test_merge_stores_is_idempotent_and_complete(tmp_path):
    spec, backend = _tiny_sweep()
    compiled = SweepScheduler(spec, backend).compile()
    shards = []
    for cell, b, design, _, fp in compiled:
        store, _ = _campaign_into(tmp_path / f"shard{cell.index}.jsonl",
                                  b, design, spec.cases, f"cell{cell.index}")
        shards.append((store, fp))

    dest = ResultStore(tmp_path / "fed.jsonl")
    stats = merge_stores(dest, [s for s, _ in shards])
    assert stats.n_campaigns == len(shards)
    assert stats.n_records == sum(len(s.records(fp)) for s, fp in shards)
    assert stats.n_duplicates == 0
    for s, fp in shards:
        assert _dump(dest)[fp] == _dump(s)[fp]
    # replaying the merge (a crashed-compaction recovery) is a no-op
    again = merge_stores(dest, [s for s, _ in shards])
    assert again.merged_nothing()
    assert again.n_duplicates == stats.n_records


def test_merge_stores_rejects_self_merge_and_counts_corruption(tmp_path):
    store, fp = _store_with_records(tmp_path / "a.jsonl")
    with pytest.raises(ValueError, match="among its own shards"):
        merge_stores(store, [store])
    raw = (tmp_path / "a.jsonl").read_bytes()
    (tmp_path / "a.jsonl").write_bytes(raw[:-15])       # torn shard tail
    dest = ResultStore(tmp_path / "b.jsonl")
    with pytest.warns(RuntimeWarning, match="undecodable"):
        stats = merge_stores(dest, [store])
    assert stats.n_corrupt == 1
    assert len(dest.records(fp)) == 2                   # intact lines merged


def test_archive_records_corruption_and_resolves_merged_baselines(tmp_path):
    """RunEntry carries n_corrupt, and baseline_for resolves a federated
    (merged-shard) candidate against a plain single-campaign baseline via
    their shared factor fingerprint."""
    spec, backend = _tiny_sweep()
    (c0, b0, d0, _, fp0), (c1, b1, d1, _, fp1) = \
        SweepScheduler(spec, backend).compile()
    arch = RunArchive(tmp_path / "arch")
    arch.root.mkdir(parents=True)

    base_store, _ = _campaign_into(arch.root / "base.jsonl", b0, d0,
                                   spec.cases, "cellA")
    base = arch.register(base_store.path, tag="reference")
    assert base.n_corrupt == 0

    s0, _ = _campaign_into(tmp_path / "h0.jsonl", b0, d0, spec.cases, "cellA")
    s1, _ = _campaign_into(tmp_path / "h1.jsonl", b1, d1, spec.cases, "cellB")
    fed = ResultStore(arch.root / "fed.jsonl")
    merge_stores(fed, [s0, s1])
    # tear the federated store's tail: registration must record the damage
    raw = fed.path.read_bytes()
    fed.path.write_bytes(raw + b'{"kind": "record", "fin')
    with pytest.warns(RuntimeWarning, match="n_corrupt"):
        cand = arch.register(fed.path)
    assert cand.n_corrupt == 1
    assert arch.entry(cand.run_id).n_corrupt == 1       # manifest round-trip
    assert set(cand.fingerprints) == {fp0, fp1}
    resolved = arch.baseline_for(cand)
    assert resolved is not None and resolved.run_id == base.run_id


# ---------------------------------------------------------------------------
# FleetScheduler, in-process mode: equivalence, quarantine, recovery
# ---------------------------------------------------------------------------

def _serial_reference(tmp, spec, backend):
    store = ResultStore(tmp / "serial.jsonl")
    SweepScheduler(spec, backend, store, n_workers=1).run()
    return _dump(store)


def test_inprocess_fleet_matches_serial_without_faults(tmp_path):
    spec, backend = _tiny_sweep(axes=("tuning", "dtype"))
    ref = _serial_reference(tmp_path, spec, backend)
    store = ResultStore(tmp_path / "fleet.jsonl")
    res = FleetScheduler(spec, backend, store, _fast_fleet()).run()
    assert res.n_cells_measured == 4 and not res.quarantined
    assert _dump(store) == ref
    # and a re-run is a pure resume
    res2 = FleetScheduler(spec, backend, store, _fast_fleet()).run()
    assert res2.n_cells_measured == 0 and res2.n_cells_resumed == 4


def test_inprocess_fleet_matches_serial_under_soft_faults(tmp_path):
    """Every cell's first attempt crashes (soft) — the retries converge to
    records bit-identical to the serial no-fault run."""
    spec, backend = _tiny_sweep(axes=("tuning", "dtype"))
    ref = _serial_reference(tmp_path, spec, backend)
    store = ResultStore(tmp_path / "fleet.jsonl")
    plan = FaultPlan(seed=0, p_crash=1.0, within_calls=1)
    res = FleetScheduler(spec, backend, store,
                         _fast_fleet(faults=plan)).run()
    assert not res.quarantined
    assert res.fleet["n_failed_attempts"] == 4    # one crash per cell
    assert _dump(store) == ref


def test_inprocess_fleet_quarantines_and_reports_poisoned_cells(tmp_path):
    """Seed 26 crashes cells 0 and 2 on *every* attempt: they quarantine
    (durably, with attempts and error), the others complete, and the
    surviving records still match the serial run — partial but honest."""
    spec, backend = _tiny_sweep(axes=("tuning", "dtype"))
    ref = _serial_reference(tmp_path, spec, backend)
    compiled = SweepScheduler(spec, backend).compile()
    fps = {cell.index: fp for cell, *_, fp in compiled}

    store = ResultStore(tmp_path / "fleet.jsonl")
    plan = FaultPlan(seed=26, p_crash=0.5, within_calls=1,
                     max_faulty_attempts=99)
    with pytest.warns(RuntimeWarning, match="quarantining sweep cell"):
        res = FleetScheduler(spec, backend, store,
                             _fast_fleet(faults=plan)).run()
    assert set(res.quarantined) == {0, 2} and res.degraded()
    for idx, info in res.quarantined.items():
        assert info["fingerprint"] == fps[idx]
        assert info["attempts"] == 3 and "CrashFault" in info["error"]
    assert sorted(c.cell.index for c in res.cells) == [1, 3]
    # the quarantine is durable and survives a fresh parse
    assert set(store.sweep_cells_failed(res.sweep_id)) == {0, 2}
    # all-or-nothing attempts: a quarantined cell leaves NO partial records
    got = _dump(store)
    for idx in (0, 2):
        assert fps[idx] not in got
    for idx in (1, 3):
        assert got[fps[idx]] == ref[fps[idx]]

    # recovery: resume without faults — quarantined cells are re-attempted,
    # success supersedes the quarantine, and the store now matches serial
    res2 = FleetScheduler(spec, backend, store, _fast_fleet()).run()
    assert res2.n_cells_measured == 2 and res2.n_cells_resumed == 2
    assert not res2.quarantined
    assert store.sweep_cells_failed(res2.sweep_id) == {}
    assert _dump(store) == ref


def test_fleet_requires_a_store():
    spec, backend = _tiny_sweep()
    with pytest.raises(ValueError, match="store is required"):
        FleetScheduler(spec, backend, None, _fast_fleet())


# ---------------------------------------------------------------------------
# FleetScheduler, multi-process chaos mode: the headline invariant
# ---------------------------------------------------------------------------

def test_chaos_fleet_store_is_record_identical_to_serial(tmp_path):
    """Three workers under injected hard crashes (real SIGKILL-equivalent
    ``os._exit`` mid-cell, torn shard tails included) and transient
    raises: the merged fleet store must be record-identical to the serial
    no-fault run, with zero quarantines and no silent serial fallback."""
    spec, backend = _tiny_sweep(axes=("tuning", "dtype"), n_launch_epochs=2,
                                nrep=8)
    ref = _serial_reference(tmp_path, spec, backend)
    store = ResultStore(tmp_path / "chaos.jsonl")
    plan = FaultPlan(seed=7, p_crash=0.5, p_raise=0.3, within_calls=2)
    cfg = FleetConfig(n_workers=3, lease_ttl=5.0, poll_s=0.02, faults=plan)
    res = FleetScheduler(spec, backend, store, cfg).run()
    assert not res.quarantined
    assert res.n_cells_measured == 4
    assert res.fleet["n_failed_attempts"] >= 1    # chaos actually struck
    assert _dump(store) == ref
    shard_dir = store.path.parent / (store.path.stem + "-shards")
    assert not shard_dir.exists()                 # shards were compacted


def test_fleet_survivable_torn_shard_lines_are_counted(tmp_path):
    """A torn line written *into* a successful worker's shard is skipped
    (with a warning) at merge time and surfaces in the fleet stats, not in
    the merged data."""
    spec, backend = _tiny_sweep(axes=("tuning",), n_launch_epochs=2, nrep=8)
    ref = _serial_reference(tmp_path, spec, backend)
    store = ResultStore(tmp_path / "torn.jsonl")
    plan = FaultPlan(seed=1, p_torn=1.0, within_calls=2)
    cfg = FleetConfig(n_workers=2, lease_ttl=5.0, poll_s=0.02, faults=plan)
    with pytest.warns(RuntimeWarning, match="undecodable"):
        res = FleetScheduler(spec, backend, store, cfg).run()
    assert res.fleet["n_corrupt_shard_lines"] == 2   # one per cell
    assert _dump(store) == ref                       # data unharmed


def test_fleet_straggler_loses_lease_and_cell_is_rerun(tmp_path):
    """A worker stalled past the lease TTL is killed and its cell re-run:
    the sweep completes correctly without waiting out the stall."""
    spec, backend = _tiny_sweep(axes=("tuning",), n_launch_epochs=2, nrep=8)
    ref = _serial_reference(tmp_path, spec, backend)
    store = ResultStore(tmp_path / "straggle.jsonl")
    plan = FaultPlan(seed=3, p_straggle=1.0, straggle_s=30.0,
                     within_calls=2)
    cfg = FleetConfig(n_workers=2, lease_ttl=0.8, poll_s=0.05, faults=plan)
    t0 = time.time()
    res = FleetScheduler(spec, backend, store, cfg).run()
    assert time.time() - t0 < 20                  # did not wait out 30s
    assert not res.quarantined
    assert res.fleet["n_failed_attempts"] >= 1    # a lease actually expired
    assert _dump(store) == ref


# ---------------------------------------------------------------------------
# Property: any byte prefix of the sweep store resumes identically,
# even with an active fault plan
# ---------------------------------------------------------------------------

_PREFIX_REF: dict = {}


def _prefix_reference():
    if not _PREFIX_REF:
        d = Path(tempfile.mkdtemp())
        spec, backend = _tiny_sweep()
        store = ResultStore(d / "ref.jsonl")
        SweepScheduler(spec, backend, store, n_workers=1).run()
        _PREFIX_REF["raw"] = store.path.read_bytes()
        _PREFIX_REF["dump"] = _dump(store)
    return _PREFIX_REF["raw"], _PREFIX_REF["dump"]


def _check_prefix_resume(cut: int):
    raw, ref = _prefix_reference()
    with tempfile.TemporaryDirectory() as d:
        path = Path(d) / "cut.jsonl"
        path.write_bytes(raw[:cut])
        spec, backend = _tiny_sweep()
        plan = FaultPlan(seed=5, p_crash=1.0, within_calls=1)
        store = ResultStore(path)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")   # torn-tail warnings expected
            res = FleetScheduler(spec, backend, store,
                                 _fast_fleet(faults=plan)).run()
        assert not res.quarantined
        assert _dump(ResultStore(path)) == ref


def test_sampled_byte_prefixes_resume_identically_under_faults():
    """The deterministic always-runs slice of the property below: cut the
    sweep's JSONL at 0, mid-file bytes (mid-line included), one byte shy
    of the end, and the full length — every prefix, resumed through the
    fleet scheduler with crash faults active, converges to the identical
    serial store."""
    raw, _ = _prefix_reference()
    rng = np.random.default_rng(0)
    cuts = {0, len(raw), len(raw) - 1,
            *(int(c) for c in rng.integers(1, len(raw), size=5))}
    for cut in sorted(cuts):
        _check_prefix_resume(cut)


@settings(max_examples=6, deadline=None)
@given(st.integers(min_value=0, max_value=10 ** 6))
def test_any_byte_prefix_resumes_identically_under_faults(nonce):
    """Property form (hypothesis, when installed): an *arbitrary* byte
    prefix of the sweep store resumes identically under an active fault
    plan."""
    raw, _ = _prefix_reference()
    _check_prefix_resume(nonce % (len(raw) + 1))

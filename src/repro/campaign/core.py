"""The Campaign orchestrator: the paper's method end-to-end, resumable.

A :class:`CampaignSpec` (cases + :class:`~repro.core.design.ExperimentDesign`)
run by :class:`Campaign` against any
:class:`~repro.campaign.backends.MeasurementBackend` executes the full
pipeline —

  factor capture → launch-epoch replication → randomized case order →
  (adaptive-nrep) measurement → persistent store → Tukey + per-epoch
  averages (Alg. 6)

— and returns a :class:`CampaignResult`. With a
:class:`~repro.campaign.store.ResultStore` attached, every measured cell is
appended the moment it exists, and re-running the identical spec *resumes*:
cells already in the store are loaded instead of re-measured (the epoch
context is not even built unless a cell in that epoch is missing). Case
orders are drawn up front from the design seed exactly as
:func:`~repro.core.design.run_design` draws them, so a campaign resumed at
an epoch boundary yields records identical to an uninterrupted one. Inside
a partially measured epoch, the missing cells are measured fresh against a
rebuilt epoch context — valid observations of the same cell, but not
bit-identical to what the uninterrupted run would have drawn when the
backend's RNG state advances per measurement (the simulator's does).
"""

from __future__ import annotations

import platform
from dataclasses import dataclass, field

from repro.core import telemetry
from repro.core.design import (NREP_SPENT, ExperimentDesign,
                               MeasurementRecord, ResultTable, TestCase,
                               analyze_records, case_orders, measure_case)
from repro.core.factors import FactorSet

from .backends import MeasurementBackend
from .store import ResultStore, StoreSnapshot

__all__ = ["CampaignSpec", "CampaignResult", "Campaign"]


def _jit_counts() -> dict:
    """Cumulative jit telemetry: the simulation engine's dispatches and
    shape keys (`engine_stats`, which never imports jax) and the compiles
    and persistent-cache reads the telemetry counters saw (zero before
    anything used jax)."""
    from repro.simjax import engine_stats

    c = telemetry.counters()
    return dict(engine_stats(), n_compiles=c.get("compiles", 0),
                n_cache_reads=c.get("compile_cache_reads", 0),
                compile_s=c.get("compile_s", 0.0))


def _jit_delta(before: dict, after: dict) -> dict | None:
    """This campaign's share of the jit telemetry: simulator dispatches
    issued and shape keys newly traced (`n_traces`), and every executable
    compiled or read from the persistent cache while it ran
    (`n_compiles`, `compile_s`; this alone sees a meter's re-jits under
    `clear_caches`), of which `n_cache_reads` were read, so that
    `n_compiles - n_cache_reads` were compiled. None when the campaign
    neither dispatched to the simulator nor compiled — meta stays clean
    for other backends."""
    d = {k: after[k] - before[k] for k in after}
    if d["n_dispatches"] <= 0 and d["n_compiles"] <= 0:
        return None
    d["compile_s"] = round(d["compile_s"], 6)
    return d


@dataclass
class CampaignSpec:
    """What to measure, independent of how: the backend supplies the how."""

    cases: list[TestCase]
    design: ExperimentDesign
    name: str = "campaign"

    def meta(self) -> dict:
        d = self.design
        return dict(
            name=self.name,
            cases=[[c.op, int(c.msize)] for c in self.cases],
            n_launch_epochs=d.n_launch_epochs,
            nrep=d.nrep, nrep_min=d.nrep_min, nrep_max=d.nrep_max,
            rel_ci_target=d.rel_ci_target, shuffle=d.shuffle, seed=d.seed,
        )


@dataclass
class CampaignResult:
    records: list[MeasurementRecord]
    table: ResultTable
    factors: FactorSet
    fingerprint: str | None = None
    n_measured: int = 0               # cells executed this run
    n_resumed: int = 0                # cells loaded from the store
    meta: dict = field(default_factory=dict)


class Campaign:
    """Run a :class:`CampaignSpec` on a backend, optionally through a store.

    ``archive`` — a :class:`~repro.history.RunArchive` — auto-registers the
    store into the cross-run archive after the campaign finishes, so every
    persisted campaign is immediately addressable as an audit baseline or
    candidate; the registered run id lands in ``result.meta["archived_run"]``.
    """

    def __init__(self, spec: CampaignSpec, backend: MeasurementBackend,
                 store: ResultStore | None = None, archive=None):
        if archive is not None and store is None:
            raise ValueError("Campaign: an archive needs a store to "
                             "register (pass store= as well)")
        self.spec = spec
        self.backend = backend
        self.store = store
        self.archive = archive

    @telemetry.spanned("campaign")
    def run(self, snapshot: StoreSnapshot | None = None,
            on_record=None, epochs=None) -> CampaignResult:
        """Execute (or resume) the campaign. ``snapshot`` — a
        :meth:`~repro.campaign.ResultStore.snapshot` of the attached store
        — replaces the per-run full-file resume scan; a sweep runs many
        campaigns against one growing file and passes the one snapshot it
        took up front. ``on_record(record)`` fires after every *freshly
        measured* cell is (if a store is attached) durably appended — the
        progress heartbeat a fleet worker's lease is kept alive by.

        ``epochs`` — an iterable of launch-epoch indices — restricts the
        run to a *window* of the design's epochs (budgeted sweeps measure
        a cell round by round). The window must stay inside
        ``design.n_launch_epochs``: epoch count is part of the factor
        fingerprint, so widening the design itself would silently declare
        a different experiment. Case orders for *all* epochs are still
        drawn up front from the design seed, which is why measuring
        epochs ``[0,1)`` now and ``[1,3)`` later appends exactly the
        records an uninterrupted full run would have."""
        spec, backend, store = self.spec, self.backend, self.store
        design = spec.design
        cases = list(spec.cases) or backend.default_cases()
        factors = backend.factors(design)

        if epochs is None:
            epoch_window = None
        else:
            epoch_window = sorted({int(e) for e in epochs})
            bad = [e for e in epoch_window
                   if not 0 <= e < design.n_launch_epochs]
            if bad:
                raise ValueError(
                    f"Campaign: epochs {bad} outside the design's "
                    f"0..{design.n_launch_epochs - 1} range — the epoch "
                    "count is fingerprinted, so a wider window needs a "
                    "new design, not a bigger window")

        fingerprint = None
        done: dict[tuple[str, int, int], MeasurementRecord] = {}
        if store is not None:
            fingerprint = store.append_campaign(factors, spec.meta(),
                                                snapshot=snapshot)
            stored = (snapshot.records.get(fingerprint, [])
                      if snapshot is not None else store.records(fingerprint))
            done = {(r.case.op, r.case.msize, r.epoch): r for r in stored}

        records: list[MeasurementRecord] = []
        n_measured = n_resumed = 0
        orders = list(enumerate(case_orders(design, cases)))
        stats0 = _jit_counts()

        # Fused execution: a backend advertising `measure_epochs` gets the
        # whole window's pending work in one call and may batch epochs into
        # shared device programs. `None` (capability gated off for this
        # configuration) falls back to per-epoch measurement below.
        fused: dict = {}
        measure_epochs = getattr(backend, "measure_epochs", None)
        if measure_epochs is not None:
            work = {}
            for epoch, order in orders:
                if epoch_window is not None and epoch not in epoch_window:
                    continue
                pending = [c for c in order
                           if (c.op, c.msize, epoch) not in done]
                if pending:
                    work[epoch] = pending
            if work:
                fused = measure_epochs(work, design) or {}

        for epoch, order in orders:
            if epoch_window is not None and epoch not in epoch_window:
                continue
            missing = [c for c in order
                       if (c.op, c.msize, epoch) not in done
                       and (c.op, c.msize, epoch) not in fused]
            ctx = backend.make_epoch(epoch) if missing else None
            for case in order:
                key = (case.op, case.msize, epoch)
                if key in done:
                    records.append(done[key])
                    n_resumed += 1
                    continue
                if key in fused:
                    times, meta = fused.pop(key)
                    NREP_SPENT.add(times.size)
                else:
                    times, meta = measure_case(backend.measure, ctx, case,
                                               design)
                # `host` is deliberately NOT part of the fingerprint
                # (FactorSet excludes it), so a merged multi-host store
                # needs it stamped on every record to stay auditable.
                meta.setdefault("host", platform.node())
                # Backend-provided provenance (e.g. which window engine
                # actually ran after fallback resolution). Fused records
                # carry theirs already — their epoch context lives inside
                # the backend's fused call, not here.
                record_meta = getattr(backend, "record_meta", None)
                if record_meta is not None and ctx is not None:
                    for k, v in record_meta(ctx, case).items():
                        meta.setdefault(k, v)
                rec = MeasurementRecord(case=case, epoch=epoch, times=times,
                                        meta=meta)
                if store is not None:
                    store.append_record(fingerprint, rec)
                if on_record is not None:
                    on_record(rec)
                records.append(rec)
                n_measured += 1

        table = analyze_records(records, design.outlier_filter)
        meta = spec.meta()
        jit = _jit_delta(stats0, _jit_counts())
        if jit is not None:
            meta["jit"] = jit
        if self.archive is not None:
            entry = self.archive.register(store.path)
            meta["archived_run"] = entry.run_id
        return CampaignResult(records=records, table=table, factors=factors,
                              fingerprint=fingerprint, n_measured=n_measured,
                              n_resumed=n_resumed, meta=meta)

"""Benchmark harness entry point: ``python -m benchmarks.run <command>``.

Subcommands (``--help`` on each for its full flag set):

  run         run the benchmark suite (default when no command is given).
              One function per paper table/figure (``benchmarks.suite``);
              prints ``name,us_per_call,derived`` CSV, per-bench
              wall-clock *and total nrep spent* go to stderr / ``--json``.
  sweep       run a factor sweep on the sim backend and print the
              factor-impact report. ``--axes`` picks the swept axes,
              ``--store`` makes it resumable, ``--workers`` shards cells
              over a pool, ``--fleet N`` runs it on a lease-queue worker
              fleet (``--faults`` injects chaos), and ``--policy``
              switches to *budgeted* allocation: ``racing`` /
              ``successive_halving`` spend nrep only on axes whose
              MATTERS-or-null verdict is still undecided (``--budget``
              caps total nrep; ``--verdicts PATH`` writes the final
              per-axis verdicts as JSON for gating).
  guidelines  verify the PGMPI-style performance-guideline family
              (``--backend sim|kernel``); exit 1 on violation.
  audit       run the fixed sim audit campaign, register it into
              ``--archive``, and issue TOST equivalence verdicts against
              the baseline; exit 1 on DRIFTED.
  compare     Wilcoxon comparison of two stores' campaigns (Fig. 28).
  calibrate   fit SimNet's noise model to a measured target backend
              (``--target sim|jax``), certify the fit EQUIVALENT on
              held-out launch epochs via the TOST audit engine, and
              register the run in ``--archive`` under the
              ``calibrated`` tag; exit 1 on DRIFTED. Resumable: pass
              the same ``--store`` to replay persisted ``calib-round``
              search state and resume measurements mid-campaign.

The pre-subcommand flag spelling (``--sweep``, ``--guidelines``,
``--audit``, ``--compare``, or bare suite flags) still works through a
shim that rewrites the argv and emits a :class:`DeprecationWarning` —
update invocations to the subcommand form.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import warnings
from pathlib import Path

SUBCOMMANDS = ("run", "sweep", "guidelines", "audit", "compare", "calibrate")


def _legacy_argv(argv: list[str]) -> list[str]:
    """Map a legacy flag-style invocation onto the subcommand CLI.

    The returned argv is what the subcommand parser consumes; any
    rewriting (other than defaulting a bare no-argument call to ``run``)
    warns with the canonical spelling, so CI logs show exactly what to
    migrate to.
    """
    if not argv:
        return ["run"]            # documented no-args behavior, not legacy
    if argv[0] in SUBCOMMANDS or argv[0] in ("-h", "--help"):
        return list(argv)
    args = list(argv)
    if "--compare" in args:
        i = args.index("--compare")
        new = ["compare", *args[i + 1:i + 3]]
    elif "--audit" in args:
        args.remove("--audit")
        new = ["audit", *args]
    elif "--guidelines" in args:
        args.remove("--guidelines")
        if "--only" in args:       # --only picked the backend here
            args[args.index("--only")] = "--backend"
        new = ["guidelines", *args]
    elif "--sweep" in args:
        args.remove("--sweep")
        new = ["sweep", *args]
    else:
        new = ["run", *args]
    # stacklevel audited: warn(1) = this line, (2) = main's _legacy_argv
    # call, (3) = main's caller — the external invocation site. Pinned by
    # test_cli.test_legacy_warning_points_at_caller.
    warnings.warn(
        "flag-style invocation of benchmarks.run is deprecated; use the "
        f"subcommand form: python -m benchmarks.run {' '.join(new)}",
        DeprecationWarning, stacklevel=3)
    return new


def _compare_stores(ap, path_a: str, path_b: str) -> None:
    """Per-case Wilcoxon comparison (Fig. 28 style) of two stores' last
    campaigns; warns when the campaigns' factor fingerprints differ in more
    than the store identity (§5.9's comparability rule)."""
    import os

    from repro.campaign import ResultStore
    from repro.core import compare_tables, format_comparison

    for p in (path_a, path_b):
        if not os.path.exists(p):
            ap.error(f"compare: store not found: {p}")
    store_a, store_b = ResultStore(path_a), ResultStore(path_b)
    fps_a, fps_b = store_a.fingerprints(), store_b.fingerprints()
    if not fps_a or not fps_b:
        ap.error("compare: a store holds no campaigns")
    for path, fps in ((path_a, fps_a), (path_b, fps_b)):
        if len(fps) > 1:
            print(f"# note: {path} holds {len(fps)} campaigns; comparing "
                  f"the last one ({fps[-1]})", file=sys.stderr)
    fa, fb = store_a.factors(), store_b.factors()
    diffs = sorted(k for k in fa if k != "host" and fa.get(k) != fb.get(k))
    if diffs:
        print(f"# note: factor sets differ in {diffs} — treat these as the "
              "factors under test", file=sys.stderr)
    try:
        rows = compare_tables(store_a, store_b)
    except ValueError as e:   # no common (op, msize) cells
        ap.error(f"compare: {e}")
    print(format_comparison(rows, name_a=os.path.basename(path_a),
                            name_b=os.path.basename(path_b)))


def _run_guidelines(ap, args) -> None:
    """Guideline-verification mode: the repo auditing an implementation
    (here: the simulated MPI library, or the Pallas kernels vs. their jnp
    oracles) instead of benchmarking itself."""
    from repro.campaign import KernelBackend, ResultStore, SimBackend
    from repro.core import ExperimentDesign
    from repro.guidelines import (default_guidelines, format_report,
                                  format_violations, verify_guidelines)

    backend_name = args.backend
    if backend_name == "sim":
        backend = SimBackend(p=8, seed0=args.seed)
        design = ExperimentDesign(n_launch_epochs=10, nrep_min=20,
                                  nrep_max=150, rel_ci_target=0.05,
                                  seed=args.seed)
    else:
        # interpret mode off-TPU: the "pallas <= ref" guideline is expected
        # to fail there — the verdict names the emulation factor, which is
        # the point of carrying factors on every result. Lighter design:
        # a kernel launch epoch pays a real re-jit, unlike a simulated one.
        backend = KernelBackend(seed0=args.seed)
        design = ExperimentDesign(n_launch_epochs=6, nrep_min=10,
                                  nrep_max=40, rel_ci_target=0.10,
                                  seed=args.seed)
    guidelines = default_guidelines(backend_name)
    store = ResultStore(args.store) if args.store else None
    report = verify_guidelines(guidelines, backend, design=design,
                               store=store)
    print(format_report(report,
                        title=f"performance guidelines [{backend_name}]"))
    if store is not None:
        print(f"# store: {args.store} (resumable; "
              f"{report.n_resumed} cells loaded, "
              f"{report.n_measured} measured this run)", file=sys.stderr)
    if not report.ok:
        print(format_violations(report), file=sys.stderr)
        raise SystemExit(1)


def _run_sweep(ap, args) -> None:
    """Factor-sweep mode: enumerate a factor grid, run every cell as its
    own campaign (resumable through the store), and print the paper-style
    "which factors matter" table. With ``--policy``, allocation is
    budgeted: rounds of measurement with per-look axis verdicts."""
    from repro.campaign import ResultStore, SweepScheduler
    from repro.sweeps import (cells_from_result, default_sim_sweep,
                              format_factor_report, interaction_screen,
                              main_effects)

    axes = None
    if args.axes:
        axes = [a.strip() for a in args.axes.split(",") if a.strip()]
    try:
        spec, backend = default_sim_sweep(seed=args.seed, axes=axes)
    except ValueError as e:
        ap.error(f"--axes: {e}")
    store = ResultStore(args.store) if args.store else None
    policy = None
    if args.policy:
        if store is None:
            ap.error("--policy needs --store PATH: allocation rounds "
                     "persist their decisions as sweep-alloc lines")
        from repro.sweeps import make_policy
        policy = make_policy(args.policy, nrep_budget=args.budget)
    elif args.budget is not None:
        ap.error("--budget only makes sense with --policy")
    if args.fleet is not None:
        res = _run_fleet_sweep(ap, args, spec, backend, store, policy)
    else:
        res = SweepScheduler(spec, backend, store,
                             n_workers=args.workers or 1,
                             policy=policy).run()
    cells = cells_from_result(res)
    axis_names = ", ".join(ax.name for ax in spec.grid.axes)
    effects = None
    try:
        effects = main_effects(cells)
    except ValueError as e:
        # a quarantine-degraded fleet run can lose every cell of an axis
        # level; partial-but-honest results still exit 0, just without
        # the factor table the missing cells would have fed
        if not (args.fleet is not None and getattr(res, "degraded",
                                                   lambda: False)()):
            raise
        print(f"# factor analysis skipped on the degraded grid: {e}",
              file=sys.stderr)
    else:
        print(format_factor_report(effects, interaction_screen(cells),
                                   title=f"factor impact [{axis_names}]"))
    alloc = res.meta.get("alloc")
    if alloc:
        sv = (f"{alloc['savings']:.2f}x" if alloc.get("savings")
              else "n/a")
        print(f"# alloc: policy={alloc['policy']} "
              f"rounds={alloc['n_rounds']} "
              f"spent_nrep={alloc['spent_nrep']} "
              f"uniform_nrep={alloc['uniform_nrep']} savings={sv}",
              file=sys.stderr)
        print(f"# alloc decisions: {alloc['decisions']}"
              + (f" undecided: {alloc['undecided']}"
                 if alloc.get("undecided") else ""), file=sys.stderr)
    if args.verdicts:
        verdicts = {}
        if effects is not None:
            verdicts = {e.axis: ("MATTERS" if e.significant else "null")
                        for e in effects}
        if alloc:
            # the sequential verdicts are authoritative for the axes they
            # resolved; the one-shot report only fills in the leftovers
            verdicts.update(alloc["decisions"])
        with open(args.verdicts, "w") as f:
            json.dump(dict(axes=verdicts, alloc=alloc), f, indent=2,
                      sort_keys=True)
        print(f"# wrote {args.verdicts}", file=sys.stderr)
    if store is not None:
        print(f"# store: {args.store} (resumable; "
              f"{res.n_cells_resumed} cells resumed, "
              f"{res.n_cells_measured} cells measured this run)",
              file=sys.stderr)


def _run_fleet_sweep(ap, args, spec, backend, store, policy=None):
    """Fault-tolerant sweep execution (``--fleet N``): lease-queue
    scheduling over N worker processes, optionally under an injected
    :class:`~repro.fleet.FaultPlan` (``--faults``). Degradation semantics:
    quarantined cells are reported and the run still exits 0 — partial-
    but-honest results beat a wedged campaign — but a fleet that completes
    *nothing* exits 1."""
    from repro.fleet import FaultPlan, FleetConfig, FleetScheduler

    if store is None:
        ap.error("--fleet needs --store PATH: lease recovery and shard "
                 "federation are meaningless without durable results")
    plan = None
    if args.faults:
        try:
            plan = FaultPlan.parse(args.faults)
        except ValueError as e:
            ap.error(f"--faults: {e}")
    cfg = FleetConfig(n_workers=max(1, args.fleet), faults=plan)
    res = FleetScheduler(spec, backend, store, cfg, policy=policy).run()
    fl = res.fleet
    print(f"# fleet: {fl.get('n_workers')} workers, "
          f"{fl.get('n_done', 0)}/{fl.get('n_cells', 0)} cells done, "
          f"{fl.get('n_failed_attempts', 0)} failed attempts recovered, "
          f"{fl.get('n_quarantined', 0)} quarantined"
          + (f", faults: {args.faults}" if args.faults else ""),
          file=sys.stderr)
    for index, info in sorted(res.quarantined.items()):
        print(f"# QUARANTINED cell {index} "
              f"(fingerprint {info['fingerprint'][:12]}) after "
              f"{info['attempts']} attempts: {info['error']}",
              file=sys.stderr)
    if not res.cells:
        print("# fleet completed no cells: every cell exhausted its retry "
              "budget", file=sys.stderr)
        raise SystemExit(1)
    return res


def _run_audit(ap, args) -> None:
    """Reproducibility-audit mode: measure the fixed audit campaign,
    archive it, and certify it EQUIVALENT to (or DRIFTED from) the
    archived baseline — the paper's "reproducible" claim made executable."""
    from repro.campaign import Campaign, CampaignSpec, ResultStore, SimBackend
    from repro.core import ExperimentDesign, TestCase
    from repro.history import (CONTROL_TAG, RunArchive, audit_runs,
                               format_audit_report, format_drift)

    audit_ops = ("allreduce", "bcast", "alltoall")
    per_op_kw = {}
    if args.mistune:
        if args.mistune not in audit_ops:
            # per_op_kw overrides are looked up by op name, so a typo (or
            # an op the audit campaign never measures) would inject nothing
            # and the "positive control" would silently pass
            ap.error(f"--mistune: {args.mistune!r} is not an audited op "
                     f"(one of {', '.join(audit_ops)})")
        if args.tag:
            ap.error("--tag cannot be combined with --mistune: seeded-drift "
                     "runs are always tagged 'control' so they can never "
                     "become a pinned baseline")
        # the seeded-drift control: same defect shape as the sweep/guideline
        # layers' mis-tuned collective (4x latency term, 3x fixed overhead)
        per_op_kw = {args.mistune: dict(alpha=12e-6, gamma=6e-6)}
    backend = SimBackend(p=8, seed0=args.seed, per_op_kw=per_op_kw,
                         sync_kw=dict(n_fitpts=60, n_exchanges=20))
    cases = [TestCase(op, m) for op in audit_ops for m in (512, 4096)]
    design = ExperimentDesign(n_launch_epochs=12, nrep=40, seed=args.seed)
    archive = RunArchive(args.archive)

    store = ResultStore(archive.new_store_path())
    res = Campaign(CampaignSpec(cases, design, name="repro-audit"),
                   backend, store).run()
    # a seeded-drift run is a *control*: archived for the record, but never
    # eligible as a default baseline (a deliberately-bad run must not
    # become the yardstick a later bad run "passes" against)
    tag = args.tag or (CONTROL_TAG if args.mistune else None)
    entry = archive.register(store.path, tag=tag)
    print(f"# registered {store.path.name} as run {entry.run_id}"
          + (f" [{entry.tag}]" if entry.tag else ""), file=sys.stderr)

    try:
        report = audit_runs(archive, entry, baseline_tag=args.baseline)
    except (LookupError, KeyError) as e:
        if args.baseline:
            ap.error(f"--baseline: {e}")
        print(f"# first run in {args.archive}: registered as the initial "
              "reference, nothing to audit against yet", file=sys.stderr)
        return
    print(format_audit_report(
        report, title=f"reproducibility audit [sim seed={args.seed}]"))
    print(f"# archive: {args.archive} ({report.n_computed} cells computed, "
          f"{report.n_resumed} resumed; campaign measured "
          f"{res.n_measured} cells)", file=sys.stderr)
    if not report.ok:
        print(format_drift(report), file=sys.stderr)
        raise SystemExit(1)


def _run_calibrate(ap, args) -> None:
    """Sim-to-real calibration mode: fit SimNet's noise model to a
    measured target backend, certify the fit with the TOST audit engine
    on held-out launch epochs, and archive the run as ``calibrated``.
    Exit 1 only on DRIFTED (positive drift evidence on a held-out cell);
    INCONCLUSIVE cells report visibly but pass."""
    from repro.calibrate import calibrate, default_space
    from repro.campaign import ResultStore, SimBackend
    from repro.core import ExperimentDesign, TestCase
    from repro.history import RunArchive, format_audit_report, format_drift

    param_names = [s.strip() for s in args.params.split(",") if s.strip()]
    archive = RunArchive(args.archive)
    base = SimBackend(p=args.p, seed0=args.seed,
                      sync_kw=dict(n_fitpts=60, n_exchanges=20))
    # a real runtime's per-call dispatch cost (JaxBackend pmap on CPU:
    # hundreds of µs) dwarfs simulator-scale latencies; widen the
    # alpha/gamma bounds so the fit can reach it instead of railing
    latency_scale = 100.0 if args.target == "jax" else 1.0
    try:
        space = default_space(base=base, names=param_names or None,
                              latency_scale=latency_scale)
    except ValueError as e:
        ap.error(f"--params: {e}")

    if args.target == "sim":
        # sim-as-target smoke: a "truth" simulator with shifted noise
        # knobs and an offset seed0 (same seed would fit one noise
        # realization, which calibrate() rejects). What the fit should
        # recover is known, so CI can gate on the verdict.
        target = SimBackend(
            p=args.p, seed0=args.seed + 7919,
            op_kw=dict(alpha=6e-6, noise_sigma=0.09, tail_prob=0.16),
            sync_kw=dict(n_fitpts=60, n_exchanges=20))
        ops = ("allreduce", "bcast")
    else:
        # jax op names are unknown to make_op's preset table, so the sim
        # candidates start from the base noise model — which is the point:
        # the fit, not a preset, reproduces the measured latencies
        from repro.campaign import JaxBackend
        target = JaxBackend()
        ops = ("psum", "all_gather")
    cases = [TestCase(op, m) for op in ops for m in (512, 4096)]
    design = ExperimentDesign(n_launch_epochs=args.epochs, nrep=args.nrep,
                              seed=args.seed)
    store = ResultStore(args.store if args.store
                        else archive.new_store_path(stem="calib"))

    result = calibrate(space, target, cases=cases, design=design,
                       store=store, archive=archive, seed=args.seed,
                       budget=args.budget, max_rounds=args.rounds)

    fitted = ", ".join(f"{k}={v:.4g}" for k, v in result.params.items())
    print(f"# fitted: {fitted}", file=sys.stderr)
    print(f"# objective: {result.objective:.6f} after "
          f"{len(result.rounds)} rounds ({result.n_rounds_resumed} "
          f"replayed from the store), {result.spent_nrep} nrep spent",
          file=sys.stderr)
    print(format_audit_report(
        result.report,
        title=f"calibration certification [{args.target} -> sim, "
              f"{result.n_heldout_epochs} held-out epochs]"))
    print(f"# registered {store.path.name} as run "
          f"{result.run_entry.run_id} [{result.run_entry.tag}]"
          if result.run_entry else "# no archive entry", file=sys.stderr)
    print(f"# store: {store.path} (resumable: calib-round lines replay "
          "the search, records resume the measurements)", file=sys.stderr)
    if not result.ok:
        print(format_drift(result.report), file=sys.stderr)
        raise SystemExit(1)


def _run_suite(ap, args) -> None:
    """The default mode: run the benchmark suite and print CSV rows."""
    from repro.core.design import NREP_SPENT
    from repro.simjax import engine_stats

    from benchmarks import suite
    from benchmarks.suite import ALL_BENCHES

    if args.list:
        for bench in ALL_BENCHES:
            doc = (bench.__doc__ or "").strip().splitlines()[0]
            print(f"{bench.__name__}: {doc}")
        return

    if args.json:
        try:  # fail fast, not after minutes of benchmarking; append mode
            with open(args.json, "a"):  # so an existing file is untouched
                pass
        except OSError as e:
            ap.error(f"--json path not writable: {e}")

    suite.SEED_OFFSET = args.seed
    if args.workers is not None:
        suite.N_WORKERS = max(1, args.workers)
    suite.STORE_PATH = args.store

    report = {"seed_offset": args.seed, "workers": suite.N_WORKERS,
              "benches": []}
    print("name,us_per_call,derived")
    failures = 0
    t_suite = time.time()
    nrep_suite = NREP_SPENT.read()
    for bench in ALL_BENCHES:
        if args.only and args.only not in bench.__name__:
            continue
        t0 = time.time()
        nrep0 = NREP_SPENT.read()
        jit0 = engine_stats()
        try:
            rows = bench()
        except Exception as e:  # keep the suite running; report at the end
            print(f"{bench.__name__},NaN,ERROR:{e!r}", flush=True)
            report["benches"].append(
                dict(name=bench.__name__, seconds=time.time() - t0,
                     nrep_total=NREP_SPENT.read() - nrep0,
                     error=repr(e), rows=[]))
            failures += 1
            continue
        for name, us, derived in rows:
            print(f"{name},{us:.3f},{derived}", flush=True)
        dt = time.time() - t0
        nrep_total = NREP_SPENT.read() - nrep0
        # repetitions spent is the machine-independent cost: wall-clock
        # shows *when* a box is slow, nrep shows what the experiment *paid*
        print(f"# {bench.__name__} took {dt:.1f}s, spent {nrep_total} nrep",
              file=sys.stderr, flush=True)
        entry = dict(name=bench.__name__, seconds=round(dt, 3),
                     nrep_total=nrep_total,
                     rows=[dict(name=n, us_per_call=u, derived=d)
                           for n, u, d in rows])
        # jit telemetry delta: traces compiled / device dispatches this
        # bench issued through the simulation engine ("one trace per
        # campaign" as a measured quantity; absent for numpy-only benches)
        jit1 = engine_stats()
        nd = jit1["n_dispatches"] - jit0["n_dispatches"]
        if nd > 0:
            entry["jit"] = dict(n_traces=jit1["n_traces"] - jit0["n_traces"],
                                n_dispatches=nd)
        report["benches"].append(entry)
    report["total_seconds"] = round(time.time() - t_suite, 3)
    report["total_nrep"] = NREP_SPENT.read() - nrep_suite
    report["failures"] = failures
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=2)
        print(f"# wrote {args.json}", file=sys.stderr, flush=True)
    if failures:
        raise SystemExit(f"{failures} benchmarks failed")


def _add_seed(p) -> None:
    p.add_argument("--seed", type=int, default=0,
                   help="offset added to every simulator seed (>= 0)")


def _add_store(p, why: str) -> None:
    p.add_argument("--store", default=None, metavar="PATH", help=why)


def main(argv: list[str] | None = None) -> None:
    argv = _legacy_argv(sys.argv[1:] if argv is None else list(argv))

    ap = argparse.ArgumentParser(
        prog="benchmarks.run",
        description="MPI-benchmarking-revisited reproduction suite")
    sub = ap.add_subparsers(dest="cmd", required=True, metavar="COMMAND")

    p_run = sub.add_parser(
        "run", help="run the benchmark suite (the default command)")
    p_run.add_argument("--only", default=None,
                       help="substring filter on benchmark names")
    p_run.add_argument("--list", action="store_true",
                       help="list available benchmarks and exit")
    _add_seed(p_run)
    p_run.add_argument("--workers", type=int, default=None,
                       help="process-pool size for campaign launch epochs")
    p_run.add_argument("--json", default=None, metavar="PATH",
                       help="write per-bench wall-clock + nrep + rows as "
                            "JSON")
    _add_store(p_run, "persist campaign results to a JSONL ResultStore")

    p_sweep = sub.add_parser(
        "sweep", help="factor sweep + factor-impact report (sim backend)")
    p_sweep.add_argument("--axes", default=None, metavar="NAMES",
                         help="comma-separated subset of the stock factor "
                              "axes (default: tuning,sync_method,"
                              "window_us,dtype)")
    _add_seed(p_sweep)
    p_sweep.add_argument("--workers", type=int, default=None,
                         help="shard grid cells over a process pool")
    _add_store(p_sweep, "resumable sweep store (cell granularity; "
                        "required by --policy and --fleet)")
    p_sweep.add_argument("--policy", default=None,
                         choices=("uniform", "racing", "successive_halving"),
                         help="budgeted allocation policy: spend nrep in "
                              "rounds, only on axes whose verdict is still "
                              "undecided (requires --store)")
    p_sweep.add_argument("--budget", type=int, default=None, metavar="NREP",
                         help="total-nrep cap for --policy (a stop "
                              "criterion: raising it only extends the "
                              "allocation sequence)")
    p_sweep.add_argument("--verdicts", default=None, metavar="PATH",
                         help="write the final per-axis MATTERS/null "
                              "verdicts (+ allocation summary) as JSON")
    p_sweep.add_argument("--fleet", type=int, default=None, metavar="N",
                         help="run fault-tolerantly on N lease-queue "
                              "workers (requires --store; quarantined "
                              "cells are reported, exit 1 only if nothing "
                              "completes)")
    p_sweep.add_argument("--faults", default=None, metavar="SPEC",
                         help="inject seeded faults into a --fleet sweep, "
                              "e.g. crash=0.4,straggle=0.2,seed=7 (kinds: "
                              "crash, straggle, raise, torn)")

    p_guide = sub.add_parser(
        "guidelines", help="verify the performance-guideline family "
                           "(exit 1 on violation)")
    p_guide.add_argument("--backend", default="sim",
                         choices=("sim", "kernel"),
                         help="which implementation to audit")
    _add_seed(p_guide)
    _add_store(p_guide, "resumable verification store")

    p_audit = sub.add_parser(
        "audit", help="reproducibility audit vs the archived baseline "
                      "(exit 1 on DRIFTED)")
    p_audit.add_argument("--archive", required=True, metavar="DIR",
                         help="run-archive directory "
                              "(repro.history.RunArchive)")
    p_audit.add_argument("--baseline", default=None, metavar="TAG",
                         help="audit against the archived run tagged TAG")
    p_audit.add_argument("--tag", default=None, metavar="TAG",
                         help="register this audit run under TAG")
    p_audit.add_argument("--mistune", default=None, metavar="OP",
                         help="seed a drifted collective into the audit "
                              "run (positive control)")
    _add_seed(p_audit)

    p_cal = sub.add_parser(
        "calibrate", help="fit SimNet's noise model to a target backend, "
                          "certify on held-out epochs (exit 1 on DRIFTED)")
    p_cal.add_argument("--target", default="sim", choices=("sim", "jax"),
                       help="what to calibrate against: a shifted-truth "
                            "simulator (CI smoke) or the JAX backend's "
                            "measured collectives")
    p_cal.add_argument("--archive", required=True, metavar="DIR",
                       help="run-archive directory; the fitted run is "
                            "registered under the 'calibrated' tag and "
                            "the fit report logged to its manifest")
    _add_store(p_cal, "shared fit store (target + candidates + search "
                      "state; default: a fresh calib-NNN.jsonl in the "
                      "archive). Pass the same path to resume a killed "
                      "fit.")
    p_cal.add_argument("--budget", type=int, default=None, metavar="NREP",
                       help="total-repetition cap, checked at round "
                            "boundaries (a stop criterion)")
    p_cal.add_argument("--params", default="op.alpha,op.noise_sigma,"
                                           "op.tail_prob", metavar="NAMES",
                       help="comma-separated noise-model knobs to fit "
                            "(stock surface in repro.calibrate."
                            "default_space)")
    p_cal.add_argument("--rounds", type=int, default=8, metavar="N",
                       help="max coordinate-descent rounds")
    p_cal.add_argument("--epochs", type=int, default=12, metavar="N",
                       help="launch epochs per campaign (first two thirds "
                            "fit, the rest certify)")
    p_cal.add_argument("--nrep", type=int, default=30, metavar="N",
                       help="repetitions per (case, epoch)")
    p_cal.add_argument("--p", type=int, default=8, metavar="RANKS",
                       help="simulated cluster size")
    _add_seed(p_cal)

    p_cmp = sub.add_parser(
        "compare", help="Wilcoxon comparison of two stores' campaigns")
    p_cmp.add_argument("store_a", metavar="STOREA")
    p_cmp.add_argument("store_b", metavar="STOREB")

    args = ap.parse_args(argv)
    from repro.core.runtime_meter import use_compile_cache

    use_compile_cache(str(Path(__file__).resolve().parents[1]))
    if getattr(args, "seed", 0) < 0:
        ap.error("--seed must be >= 0 (it offsets non-negative RNG seeds)")
    if args.cmd == "sweep" and args.faults and args.fleet is None:
        ap.error("--faults only makes sense with --fleet")

    if args.cmd == "compare":
        _compare_stores(ap, args.store_a, args.store_b)
    elif args.cmd == "audit":
        _run_audit(ap, args)
    elif args.cmd == "calibrate":
        _run_calibrate(ap, args)
    elif args.cmd == "guidelines":
        _run_guidelines(ap, args)
    elif args.cmd == "sweep":
        _run_sweep(ap, args)
    else:
        _run_suite(ap, args)


if __name__ == "__main__":
    main()

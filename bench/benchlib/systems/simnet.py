"""The device-resident network simulator: ``SimBackend(engine="jax")``.

A campaign is the repository's ``Campaign`` (traffic ``kind: fixed``: a
case list at fixed ``nrep``, analysed by ``compare_cases``) or its
guideline verification (``kind: guidelines``: ``verify_guidelines`` over a
named family at its adaptive design). Every campaign runs the traffic's
design seed (so the same case orders and dispatches); each draws its own
simulated cluster from the run's seed.

The set-up runs the warm-up designs (every launch-epoch fan-in the fused
engine can form, with the traffic's sizes), so the window compiles
nothing.

The check: the calls of the simulator's jitted sample and window programs
are captured while the window is open (arguments and results); a seeded
sample of them is recomputed by the float64 reference
(:mod:`benchlib.ref_sim`), and every completed campaign's analysis is
recomputed from its records. A seeded sample of the launch epochs' HCA
clock synchronizations keeps its raw exchanges; the reference recomputes
every rank's drift model from them and compares it with the models the
program handed its window programs. The capture keys on the engine's
program factories ``_jitted_fused`` and ``_jitted`` and on the HCA
module's ``collect_fitpoints_batch`` and ``skampi_pingpong_adjusted``
(PERF.md lists the dependency).
"""

from __future__ import annotations

import time

import numpy as np

from .. import ref_sim
from ..harness import Check
from ..probes import BackendProxy


class _Capture:
    """Arguments and results of the engine's sample/window program calls,
    grouped as the engine issues them: the sample calls of one measurement
    (one per cost-model term), then its window calls (one per epoch)."""

    def __init__(self):
        self.groups: list[dict] = []
        self.on = False
        self._orig = None

    def install(self):
        from repro.simjax import engine

        self._orig = (engine._jitted_fused, engine._jitted)
        fused, epoch = self._orig

        def jitted_fused():
            jax, s, w = fused()
            return jax, self._sample("fused", s), self._window("fused", w)

        def jitted():
            jax, s, w = epoch()
            return jax, self._sample("epoch", s), self._window("epoch", w)

        engine._jitted_fused, engine._jitted = jitted_fused, jitted

    def uninstall(self):
        from repro.simjax import engine

        if self._orig is not None:
            engine._jitted_fused, engine._jitted = self._orig
            self._orig = None

    def _sample(self, kind, fn):
        def call(*a, **kw):
            out = fn(*a, **kw)
            if self.on:
                g = self.groups[-1] if self.groups else None
                if g is None or g["kind"] != kind or g["windows"]:
                    g = {"kind": kind, "samples": [], "windows": []}
                    self.groups.append(g)
                g["samples"].append((a, kw, out))
            return out
        return call

    def _window(self, kind, fn):
        def call(durations, *a, **kw):
            out = fn(durations, *a, **kw)
            if self.on and self.groups and self.groups[-1]["kind"] == kind:
                self.groups[-1]["windows"].append(
                    (a, kw, out, int(durations.shape[0])))
            return out
        return call


class _SyncCapture:
    """The raw exchanges of a seeded sample (a reservoir of ``keep``) of
    the HCA synchronizations run while ``on``: per pair, the fitpoint
    sweep's true times, both clocks and the round-trip time; per
    re-anchoring, the ping-pong stamps and the client's adjusted time; and
    what the synchronization returned."""

    def __init__(self, rng: np.random.Generator, keep: int):
        self.rng, self.keep = rng, keep
        self.on = False
        self.seen = 0
        self.kept: list[dict] = []
        self._cur = None
        self._orig = None

    def install(self):
        from repro.core.sync import hca, jk

        self._orig = (hca.collect_fitpoints_batch,
                      hca.skampi_pingpong_adjusted, hca.HCASync.synchronize)
        fitpoints, skampi, sync = self._orig
        cap = self

        def collect(net, client, ref, rtt, n_fitpts, n_exchanges, **kw):
            if cap._cur is None:
                return fitpoints(net, client, ref, rtt, n_fitpts,
                                 n_exchanges, **kw)
            sweep = jk._fitpoint_sweep_true
            got = {}

            def recorded(*a, **k):
                got["srv_true"], got["recv_true"] = sweep(*a, **k)
                return got["srv_true"], got["recv_true"]

            jk._fitpoint_sweep_true = recorded
            try:
                out = fitpoints(net, client, ref, rtt, n_fitpts, n_exchanges,
                                **kw)
            finally:
                jk._fitpoint_sweep_true = sweep
            init = kw["initial_times"]
            got.update(ref_clock=_clock(net, ref), cli_clock=_clock(net, client),
                       init_ref=init[ref], init_cli=init[client], rtt=rtt)
            cap._cur["fits"][(ref, client)] = got
            return out

        def pingpong(net, p1, p2, initial_times=None, n_pingpongs=100):
            if cap._cur is None:
                return skampi(net, p1, p2, initial_times, n_pingpongs)
            batch = net.pingpong_batch
            got = {}

            def recorded(*a, **k):
                got["send"], got["srv"], got["recv"] = batch(*a, **k)
                return got["send"], got["srv"], got["recv"]

            net.pingpong_batch = recorded
            try:
                out = skampi(net, p1, p2, initial_times, n_pingpongs)
            finally:
                del net.pingpong_batch
            got.update(init_ref=initial_times[p1], init_cli=initial_times[p2],
                       ts=net.local_time(p2) - initial_times[p2])
            cap._cur["offsets"][(p1, p2)] = got
            return out

        def synchronize(self_, net, ranks=None):
            slot = cap._slot() if cap.on and ranks is None else None
            if slot is None:
                return sync(self_, net, ranks)
            cap._cur = {"fits": {}, "offsets": {}, "p": net.p,
                        "clocks": np.array([_clock(net, r)
                                            for r in range(net.p)])}
            try:
                res = sync(self_, net, ranks)
            finally:
                rec, cap._cur = cap._cur, None
            rec.update(
                hierarchical=bool(res.params["hierarchical_intercepts"]),
                params=dict(res.params), init=np.asarray(res.initial_times),
                slope=np.array([m.slope for m in res.models]),
                intercept=np.array([m.intercept for m in res.models]))
            if slot < len(cap.kept):
                cap.kept[slot] = rec
            else:
                cap.kept.append(rec)
            return res

        hca.collect_fitpoints_batch = collect
        hca.skampi_pingpong_adjusted = pingpong
        hca.HCASync.synchronize = synchronize

    def _slot(self):
        """Reservoir sampling: where the next synchronization goes among
        the kept ones, or None."""
        i, self.seen = self.seen, self.seen + 1
        if i < self.keep:
            return i
        j = int(self.rng.integers(i + 1))
        return j if j < self.keep else None

    def uninstall(self):
        from repro.core.sync import hca

        if self._orig is not None:
            (hca.collect_fitpoints_batch, hca.skampi_pingpong_adjusted,
             hca.HCASync.synchronize) = self._orig
            self._orig = None


def _clock(net, r):
    c = net.clocks[r]
    return (c.offset, c.skew, c.scale_error)


# the per-epoch engine's compiled request sizes (``repro.simjax.engine``
# pads a request below 1024 to the next power of two from 32)
WARM_NREP = (32, 64, 128, 256, 512, 1024)


class SimCell:
    def __init__(self, config: dict, traffic: dict, run):
        self.config, self.traffic, self.run = config, traffic, run
        self.capture = _Capture()
        self.syncs = _SyncCapture(run.rng(19), int(traffic["check_syncs"]))
        self.verdicts: list[dict] = []   # per completed campaign
        self.dispatches: list[int] = []
        self.control = False             # reference in the program's place

    # -- the campaign ------------------------------------------------------
    def _backend(self, seed0: int, p: int):
        """The simulator as the configuration states it: engine, fused
        epochs, synchronization and its sizes, window; clocks and cost
        models at the program's defaults (affine clocks; checked in
        set-up)."""
        from repro.campaign import SimBackend

        c = self.config
        return SimBackend(
            p=p, seed0=seed0, engine=c["engine"],
            fuse_epochs=c["fused_epochs"], sync_name=c["sync"],
            sync_kw=dict(n_fitpts=c["sync_fitpoints"],
                         n_exchanges=c["sync_exchanges"]),
            win_size=c["window_us"] / 1e6,
            epoch_isolation=c["epoch_isolation"])

    def _check_stated(self, backend, ctx):
        """What the configuration states, against the simulator built."""
        c = self.config
        got = {"engine": ctx.engine, "sync": ctx.sync.algorithm,
               "sync_fitpoints": ctx.sync.params.get("n_fitpts"),
               "sync_exchanges": ctx.sync.params.get("n_exchanges"),
               "hierarchical_intercepts":
                   ctx.sync.params.get("hierarchical_intercepts"),
               "window_us": round(backend.win_size * 1e6, 6),
               "fused_epochs": backend.fuse_epochs,
               "epoch_isolation": backend.epoch_isolation,
               "clocks": "affine" if all(k.rw_sigma == 0.0
                                         for k in ctx.net.clocks) else "walk",
               "cost_models": "program defaults" if not (
                   backend.op_kw or backend.per_op_kw or backend.clock_kw)
               else "overridden"}
        want = {k: c[k] for k in got if k in c}
        want.update(hierarchical_intercepts=False,
                    cost_models="program defaults")
        bad = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
        if bad:
            raise ValueError(f"simulator differs from the configuration "
                             f"(got, stated): {bad}")

    def _design(self, design_seed: int, **over):
        from repro.core import ExperimentDesign

        t = self.traffic
        kw = dict(n_launch_epochs=t["n_launch_epochs"], seed=design_seed)
        if t["kind"] == "guidelines":
            kw.update(nrep_min=t["nrep_min"], nrep_max=t["nrep_max"],
                      rel_ci_target=t["rel_ci_target"])
        else:
            kw.update(nrep=t["nrep"])
        kw.update(over)
        return ExperimentDesign(**kw)

    def _one(self, design, seed0: int, deadline: float):
        from repro.campaign import Campaign, CampaignSpec
        from repro.core.compare import compare_cases

        t = self.traffic
        recs: dict = {}
        proxy = BackendProxy(self._backend(seed0, t["p"]), self.run.spans,
                             deadline, on_result=recs.update)
        if t["kind"] == "guidelines":
            from repro import guidelines as gl

            family = getattr(gl, t["family"])
            report = gl.verify_guidelines(family, proxy, design=design,
                                          msizes=tuple(t["msizes"]))
            return {"kind": "guidelines", "report": report, "records": recs,
                    "family": family, "n_records": len(recs)}
        cases = self._cases()
        res = Campaign(CampaignSpec(cases, design), proxy).run()
        with self.run.spans.span("analysis"):
            row = compare_cases(res.table, cases[0], cases[1])
        return {"kind": "fixed", "records": res.records, "table": res.table,
                "row": row, "cases": cases, "n_records": len(res.records)}

    def _cases(self):
        from repro.core import TestCase

        t = self.traffic
        if t["kind"] == "guidelines":
            from repro import guidelines as gl

            return gl.compile_cases(getattr(gl, t["family"]),
                                    tuple(t["msizes"]))
        return [TestCase(op, int(m)) for op, m in t["cases"]]

    def setup(self):
        self.capture.install()
        self.syncs.install()
        rng = self.run.rng(999)
        seed = lambda: int(rng.integers(2**31))   # noqa: E731
        # every fan-in the fused engine can form: E epochs sharing one case
        # order run as one dispatch of E lanes
        for e in range(1, self.traffic["n_launch_epochs"] + 1):
            self._one(self._design(seed(), n_launch_epochs=e,
                                   shuffle=False), seed(), float("inf"))
        # every shape a discard top-up or an adaptive chunk can take: the
        # per-epoch engine pads a request below 1024 to a power of two
        backend = self._backend(seed(), self.traffic["p"])
        ctx = backend.make_epoch(0)
        self._check_stated(backend, ctx)
        for case in self._cases():
            for n in WARM_NREP:
                backend.measure(ctx, case, n)

    def campaign(self, k: int):
        from repro.simjax import engine_stats

        # every campaign runs the traffic's design (the same case orders);
        # its simulated cluster is drawn from the run's seed
        design_seed = int(self.traffic["design_seed"])
        seed0 = int(self.run.rng(1000 + k).integers(2**31))
        self.capture.on = self.syncs.on = True
        d0 = engine_stats()["n_dispatches"]
        try:
            out = self._one(self._design(design_seed), seed0,
                            self.run.deadline)
        finally:
            self.capture.on = self.syncs.on = False
        if time.perf_counter() <= self.run.deadline:
            self.verdicts.append(out)
            self.dispatches.append(engine_stats()["n_dispatches"] - d0)
        return {"records": out["n_records"]}

    def end_to_end(self) -> dict:
        done = self.run.completed()
        if not done:
            raise RuntimeError("no campaign completed inside the window; "
                               "the window is shorter than one campaign")
        t0 = self.run.window[0]
        return {"verdict_s": (done[-1]["end"] - t0) / len(done)}

    def release(self):
        self.capture.uninstall()
        self.syncs.uninstall()

    # -- the check ---------------------------------------------------------
    def _sample_ref(self, kind, a, kw, lane=None, dtype=np.float64):
        """Reference durations and AR(1) state carried out of the call."""
        if kind == "fused":
            (seeds, j, t0_op, ar_state, sigma, autocorr, tail_prob,
             tail_shift, spike_prob, spike_scale, _nrep) = a
            key = ref_sim.fused_key(int(np.asarray(seeds)[lane]), int(j))
            t0 = float(np.asarray(t0_op)[lane])
            ar = float(np.asarray(ar_state)[lane])
        else:
            (key, t0, ar, sigma, autocorr, tail_prob, tail_shift,
             spike_prob, spike_scale) = a
            t0, ar = float(t0), float(ar)
        dur, st = ref_sim.sample(
            key, int(kw["n"]), t0=t0, ar_state=ar, noise_sigma=float(sigma),
            autocorr=float(autocorr), tail_prob=float(tail_prob),
            tail_shift=float(tail_shift), spike_prob=float(spike_prob),
            spike_scale=float(spike_scale), dtype=dtype)
        carry = st[int(_nrep) - 1] if kind == "fused" else st
        return dur, carry, float(sigma)

    def _check_group(self, g) -> tuple[float, float, int, int]:
        """(sample gap, times gap, carry gap, flag mismatches) of one
        group. The sample gap covers the durations and the AR(1) state the
        call carries out (relative to the noise scale); the carry gap is
        the window's end state in units of the window. In the control, the
        float32 reference stands in the program's place."""
        sgap = tgap = cgap = 0.0
        flags = 0
        fused = g["kind"] == "fused"
        lanes = range(len(g["windows"])) if fused else [None]
        for lane, (a, kw, out, npad) in zip(lanes, g["windows"]):
            dur = None
            for sa, skw, sout in g["samples"]:
                d, carry, sigma = self._sample_ref(g["kind"], sa, skw,
                                                   lane=lane)
                if self.control:
                    got, got_carry, _ = self._sample_ref(
                        g["kind"], sa, skw, lane=lane, dtype=np.float32)
                elif fused:
                    got = np.asarray(sout[0])[lane]
                    got_carry = np.asarray(sout[1])[lane]
                else:
                    got, got_carry = np.asarray(sout[0]), np.asarray(sout[1])
                sgap = max(sgap, ref_sim.rel_gap(got, d), float(np.max(
                    np.abs(np.asarray(got_carry, np.float64) - carry)))
                    / max(sigma, 1e-300))
                dur = d if dur is None else dur + d
            n = dur.shape[0]
            if fused:
                (key, t0, off, skew, scale, slope, intercept, init_t, ri,
                 start, ws, nrep) = a
                nrep = int(nrep)
                dpad = np.concatenate([dur, np.repeat(dur[n - 1:],
                                                      npad - n)])
                imb = ref_sim.fused_imbalance(key, npad, int(kw["ch"]),
                                              t0.shape[0])
            else:
                (key, t0, off, skew, scale, slope, intercept, init_t, ri,
                 start, ws) = a
                nrep = n
                dpad = dur
                imb = ref_sim.epoch_imbalance(key, n, t0.shape[0])
            args = dict(t0=t0, off=off, skew=skew, scale=scale, slope=slope,
                        intercept=intercept, init_t=init_t,
                        rank_imbalance=float(ri), start_time=float(start),
                        win_size=float(ws))
            times, errors, end = ref_sim.window(dpad[:nrep], imb[:nrep],
                                                **args)
            end = end[-1] if fused else end
            if self.control:
                got_t, got_e, got_end = ref_sim.window(
                    dpad[:nrep], imb[:nrep], dtype=np.float32, **args)
                got_end = got_end[-1] if fused else got_end
            else:
                if np.asarray(out[0]).dtype != np.float64:
                    return sgap, float("inf"), cgap, flags   # not float64
                got_t = np.asarray(out[0]).astype(np.float64)[:nrep]
                got_e = np.asarray(out[1]).astype(np.int64)[:nrep]
                got_end = np.asarray(out[2] if fused else out[5])
            tgap = max(tgap, ref_sim.rel_gap(got_t, times))
            cgap = max(cgap, float(np.max(np.abs(
                np.asarray(got_end, np.float64) - end))) / float(ws))
            flags += int(np.sum(got_e != errors))
        return sgap, tgap, cgap, flags

    def _sync_gap(self, rec, windows) -> float:
        """The widest gap, over ranks, between the global time that the
        program's drift models give and the reference's, at the end of
        the last window that used them, in windows. The models are read
        both as the synchronization returned them and as each window
        program got them (found by the initial clock readings, which the
        window programs get too); a window program that got other clocks
        or models than the synchronization had reads inf. In the control
        the float32 reference stands in the program's place."""
        args = dict(fits=rec["fits"], offsets=rec["offsets"], p=rec["p"],
                    hierarchical=rec["hierarchical"])
        slope, icpt = ref_sim.hca_models(**args)
        if self.control:
            gots = [ref_sim.hca_models(dtype=np.float32, **args)]
        else:
            gots = [(rec["slope"], rec["intercept"])]
        ws = float(self.config["window_us"]) / 1e6
        horizon = ws
        clocks = rec["clocks"].T
        for a, nrep in windows:
            if not np.array_equal(np.asarray(a[7]), rec["init"]):
                continue
            if not all(np.array_equal(np.asarray(a[i]), clocks[k])
                       for k, i in enumerate((2, 3, 4))):
                return float("inf")
            horizon = max(horizon, float(a[9]) + nrep * float(a[10]))
            if not self.control:
                gots.append((np.asarray(a[5]), np.asarray(a[6])))
        gap = 0.0
        for s_got, i_got in gots:
            d = (np.abs(np.asarray(s_got, np.float64) - slope) * horizon
                 + np.abs(np.asarray(i_got, np.float64) - icpt))
            gap = max(gap, float(np.max(d)) / ws)
        return gap

    def _summaries(self, times):
        """The reference's (mean, median), and what stands in the
        program's place: its own, or in the control the float32 ones."""
        want = ref_sim.epoch_summary(times)
        return want, (ref_sim.epoch_summary(times, np.float32)
                      if self.control else None)

    def _check_fixed(self, v) -> tuple[float, int]:
        """(table gap, verdicts that differ) of one campaign's analysis."""
        tgap = 0.0
        meds: dict = {}
        summaries = {(s.case.key(), s.epoch): s for s in v["table"].summaries}
        for r in v["records"]:
            want, ctl = self._summaries(r.times)
            s = summaries.get((r.case.key(), r.epoch))
            if s is None:
                return float("inf"), 1
            got = ctl or (s.mean, s.median)
            tgap = max(tgap, ref_sim.rel_gap(got, want))
            meds.setdefault(r.case.key(), []).append((r.epoch, want[1]))
        a, b = (np.array([m for _, m in sorted(meds[c.key()])])
                for c in v["cases"])
        row = v["row"]
        want = ref_sim.verdict(ref_sim.rank_sum_p(a, b, "less"),
                               ref_sim.rank_sum_p(a, b, "greater"))
        return tgap, int(row.verdict != want)

    def _check_guidelines(self, v) -> tuple[float, int]:
        """(table gap, verdicts that differ) of one verification."""
        meds: dict = {}
        for (op, m, e), (times, _meta) in v["records"].items():
            meds.setdefault((op, m), []).append(
                (e, ref_sim.epoch_summary(times)[1]))
        t = self.traffic
        cells = []
        for g in v["family"]:
            for m in (g.msizes or t["msizes"]):
                lhs, rhs = g.cases(m)
                cells.append((lhs, rhs))
        if any(c.key() not in meds for pair in cells for c in pair):
            return float("inf"), 1
        ab = [(np.array([x for _, x in sorted(meds[l.key()])]),
               np.array([x for _, x in sorted(meds[r.key()])]))
              for l, r in cells]
        p_viol = np.array([ref_sim.rank_sum_p(a, b, "greater")
                           for a, b in ab])
        p_conf = np.array([ref_sim.rank_sum_p(a, b, "less") for a, b in ab])
        p_holm = ref_sim.holm(p_viol)
        verd = v["report"].verdicts
        if len(verd) != len(cells):
            return float("inf"), 1
        tgap, flips = 0.0, 0
        for i, gv in enumerate(verd):
            a, b = ab[i]
            want = [a.mean() * 1e6, b.mean() * 1e6]
            got = ([np.float32(a).mean() * 1e6, np.float32(b).mean() * 1e6]
                   if self.control else [gv.lhs_us, gv.rhs_us])
            tgap = max(tgap, ref_sim.rel_gap(got, want))
            # the guideline's verdict: violated (Holm-adjusted p <= alpha),
            # confirmed (p of the other side <= alpha), or neither
            viol = bool(p_holm[i] <= gv.alpha)
            ref = (viol, not viol and bool(p_conf[i] <= gv.alpha))
            flips += int((gv.violated, gv.confirmed) != ref)
        return tgap, flips

    def checks(self) -> list[Check]:
        lim = self.config["check_limits"]
        groups = [g for g in self.capture.groups if g["windows"]]
        rng = self.run.rng(7)
        chosen = []
        for kind in ("fused", "epoch"):
            gs = [g for g in groups if g["kind"] == kind]
            k = min(len(gs), int(self.traffic["check_groups"]))
            chosen += [gs[i] for i in sorted(rng.choice(len(gs), k,
                                                        replace=False))]
        sgap = tgap = cgap = 0.0
        flags = 0
        for g in chosen:
            s, t, c, f = self._check_group(g)
            sgap, tgap, cgap = max(sgap, s), max(tgap, t), max(cgap, c)
            flags += f
        windows = [(a, int(a[11]) if g["kind"] == "fused" else npad)
                   for g in groups for a, _kw, _out, npad in g["windows"]]
        sync = max((self._sync_gap(r, windows) for r in self.syncs.kept),
                   default=float("inf"))
        table, flips = 0.0, 0
        for v in self.verdicts:
            tg, fl = (self._check_guidelines(v) if v["kind"] == "guidelines"
                      else self._check_fixed(v))
            table, flips = max(table, tg), flips + fl
        if not chosen or not self.verdicts:
            sgap = tgap = cgap = float("inf")     # nothing was checked
        return [Check("sample_gap", sgap, lim["sample_gap"]),
                Check("times_gap", tgap, lim["times_gap"]),
                Check("carry_gap", cgap, lim["carry_gap"]),
                Check("flag_flips", float(flags), lim["flag_flips"]),
                Check("sync_gap", sync, lim["sync_gap"]),
                Check("table_gap", table, lim["table_gap"]),
                Check("verdict_flips", float(flips), lim["verdict_flips"])]


def make_cell(config, traffic, run):
    return SimCell(config, traffic, run)

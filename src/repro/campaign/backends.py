"""Pluggable measurement backends for the paper's method.

The experimental design (Alg. 5/6) is engine-agnostic: it needs a fresh
context per *launch epoch*, a way to *measure* one test case, and the
:class:`~repro.core.factors.FactorSet` describing everything else that was
held fixed. A :class:`MeasurementBackend` packages exactly those three
capabilities, so the same :class:`~repro.campaign.Campaign` spec runs
against

  * :class:`SimBackend`    — the calibrated cluster simulator
    (:class:`~repro.core.simnet.SimNet` + window-based sync, §3.3/§4),
  * :class:`JaxBackend`    — real jitted JAX collectives (``psum`` /
    ``all_gather`` / ``all_to_all``) over a host-device mesh
    (``--xla_force_host_platform_device_count`` off-TPU),
  * :class:`KernelBackend` — Pallas kernels vs. their jnp references as
    the operations under test (compiled on a TPU, interpret mode
    elsewhere).

Backends are plain picklable dataclasses so
:func:`~repro.core.design.run_design` can fan their launch epochs over a
process pool, and deterministic per ``(seed0, epoch)`` so a resumed
campaign reproduces the original records bit-for-bit.
"""

from __future__ import annotations

from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Protocol, runtime_checkable

import numpy as np

from repro.core import telemetry
from repro.core.design import ExperimentDesign, TestCase
from repro.core.factors import FactorSet, capture_factors
from repro.core.mpi_ops import make_composite_op
from repro.core.opexpr import parse_opexpr
from repro.core.runtime_meter import JaxEpochContext, MeterConfig
from repro.core.simnet import ClockParams, SimNet
from repro.core.sync import make_sync
from repro.core.warnutil import warn_external
from repro.core.window import WindowRun, resolve_engine, run_windowed

__all__ = [
    "MeasurementBackend",
    "FunctionBackend",
    "SimBackend",
    "JaxBackend",
    "KernelBackend",
    "ensure_host_devices",
    "fallback_warning_scope",
]

_SYNC_KW = dict(n_fitpts=200, n_exchanges=40)

# Active fallback-warning dedup scopes (innermost last). A sweep pushes one
# scope around all of its cell campaigns so each distinct engine-fallback
# reason warns once per *sweep*, not once per cell.
_WARN_SCOPE: list = []


@contextmanager
def fallback_warning_scope():
    """Deduplicate engine-fallback ``RuntimeWarning``s across every campaign
    run inside the scope. Without an active scope each backend instance
    dedups on its own (once per campaign)."""
    _WARN_SCOPE.append(set())
    try:
        yield
    finally:
        _WARN_SCOPE.pop()


def _filter_sync_kw(sync_name: str, kw: dict) -> dict:
    """``sync_kw`` restricted to what the chosen algorithm's constructor
    accepts. Fitpoint knobs mean nothing to skampi/netgauge, and a sweep's
    ``sync_method`` axis must be able to swap algorithms under one backend
    configuration without the unused knobs turning into TypeErrors."""
    import inspect

    from repro.core.sync import SYNC_CLASSES

    cls = SYNC_CLASSES.get(sync_name)
    if cls is None:          # unknown name: let make_sync raise its error
        return dict(kw)
    params = inspect.signature(cls.__init__).parameters
    if any(p.kind == p.VAR_KEYWORD for p in params.values()):
        return dict(kw)
    return {k: v for k, v in kw.items() if k in params}


def _sequence_calls(fns):
    """One timed callable running ``fns`` back to back — the composite
    mock-up region. The epoch meter blocks on the *returned* value only,
    so return the last term's output (each prior dispatch is enqueued
    before it and completes under JAX's per-device program order)."""
    if len(fns) == 1:
        return fns[0]

    def composite():
        out = None
        for f in fns:
            out = f()
        return out

    return composite


@runtime_checkable
class MeasurementBackend(Protocol):
    """What a measurement engine must provide to run the paper's method."""

    name: str

    def make_epoch(self, epoch: int) -> Any:
        """Fresh launch-epoch context (the §5.2 blocking factor)."""
        ...

    def measure(self, ctx: Any, case: TestCase, nrep: int) -> np.ndarray:
        """``nrep`` run-times [s] of ``case`` inside an epoch context."""
        ...

    def factors(self, design: ExperimentDesign) -> FactorSet:
        """The Table-4 factor set a campaign on this backend must carry."""
        ...

    def default_cases(self) -> list[TestCase]:
        """Cases to run when the campaign spec does not name any."""
        ...


def _design_factor_kw(design: ExperimentDesign) -> dict:
    return dict(
        n_launch_epochs=design.n_launch_epochs,
        nrep=0 if design.adaptive else design.nrep,
        nrep_min=design.nrep_min if design.adaptive else 0,
        nrep_max=(design.nrep_max or 0) if design.adaptive else 0,
        rel_ci_target=design.rel_ci_target if design.adaptive else 0.0,
        design_seed=design.seed,
        shuffle=design.shuffle,
    )


# ---------------------------------------------------------------------------
# Simulator backend
# ---------------------------------------------------------------------------

def _apply_cold_buffers(op) -> None:
    """§5.8's cache factor for the simulator: cold buffers forfeit the
    cost model's own ``warm_cache_discount``, scaling every affine cost
    term by ``1 + discount`` (exactly what ``sample_duration(warm=False)``
    would do, applied once at op-construction time so both window engines
    and composites inherit it)."""
    if hasattr(op, "terms"):                 # SimCompositeOp
        for sub, _, _ in op.terms:
            _apply_cold_buffers(sub)
        return
    f = 1.0 + op.warm_cache_discount
    op.alpha *= f
    op.beta *= f
    op.gamma *= f


class _SimEpoch:
    """One simulated launch epoch: a fresh cluster, synchronized clocks,
    and a lazily-built cost model per op name."""

    @telemetry.spanned("epoch_build")
    def __init__(self, backend: "SimBackend", epoch: int):
        self.backend = backend
        self.net = SimNet(
            backend.p,
            clocks=ClockParams(**backend.clock_kw) if backend.clock_kw
            else None,
            seed=backend.seed0 + 1000 * epoch)
        sync_kw = _filter_sync_kw(backend.sync_name, backend.sync_kw)
        with telemetry.span("clock_sync"):
            self.sync = make_sync(backend.sync_name,
                                  **sync_kw).synchronize(self.net)
        # Resolve once per epoch: what will actually run. A substitution
        # (jax requested but unusable) is never silent — it is warned once
        # per campaign and recorded per record (`meta["engine"]`).
        self.engine, self.engine_note = resolve_engine(backend.engine,
                                                       self.net)
        if self.engine_note is not None:
            backend._warn_fallback(self.engine_note)
        self._ops: dict[str, Any] = {}

    def op(self, name: str):
        if name not in self._ops:
            # `name` may be a composite op expression (a guideline mock-up
            # such as "scatter+allgather" or "allreduce@half+allreduce@half")
            op = make_composite_op(
                name, per_op_kw=self.backend.per_op_kw, **self.backend.op_kw)
            if self.backend.buffer_policy == "cold":
                _apply_cold_buffers(op)
            self._ops[name] = op
        return self._ops[name]


@dataclass
class SimBackend:
    """Simulated cluster measured through window-based synchronization.

    ``case.op`` selects the collective's cost-model preset (unknown names
    get the generic model) — or a composite op *expression* (see
    :mod:`repro.core.opexpr`) sequencing several collectives inside one
    timed region, the mock-up side of a performance guideline. ``case.msize``
    is the message size; ``op_kw`` overrides apply to every case, which is
    how two "MPI libraries" with different latency terms are modeled, and
    ``per_op_kw`` overrides one named collective only (how a single
    mis-tuned collective — the thing guideline verification exists to catch
    — is seeded). Window discards (START_LATE / TOOK_TOO_LONG) are topped
    up so the returned sample has ~``nrep`` valid observations.

    Three Table-4 factors are sweepable knobs here so a
    :class:`~repro.core.factors.FactorGrid` can vary them:
    ``buffer_policy`` (``"cold"`` forfeits the cost model's warm-cache
    discount, §5.8), ``epoch_isolation`` (``"none"`` *reuses* one
    simulated cluster across every launch epoch — the §5.2 anti-pattern a
    sweep should expose as biased), and ``dtype`` (a pure label in the
    simulator: it must rank as a null factor, which is the negative
    control of the factor-impact analysis).
    """

    p: int = 8
    seed0: int = 0
    op_kw: dict = field(default_factory=dict)
    per_op_kw: dict = field(default_factory=dict)
    sync_name: str = "hca"
    sync_kw: dict = field(default_factory=lambda: dict(_SYNC_KW))
    win_size: float = 400e-6
    engine: str = "auto"
    clock_kw: dict = field(default_factory=dict)
    buffer_policy: str = "warm"        # warm | cold
    epoch_isolation: str = "process"   # process | none
    dtype: str = "float32"             # label-only (null factor by design)
    fuse_epochs: bool = True           # execution knob, not a factor
    name: str = "sim"
    _shared_epoch: Any = field(default=None, init=False, repr=False,
                               compare=False)
    _fallback_warned: set = field(default_factory=set, init=False,
                                  repr=False, compare=False)

    def _warn_fallback(self, note: str) -> None:
        """Warn once per campaign (per distinct reason) when the requested
        engine is substituted — the audit trail for the historic bug where
        ``engine="auto"`` silently dropped to the scalar path. Inside a
        :func:`fallback_warning_scope` (a sweep), dedup widens to the whole
        scope so the report is not drowned in per-cell repeats. The warning
        is attributed to the first frame *outside* ``repro`` — the call
        depth differs between a bare ``make_epoch`` and a full
        ``Campaign.run``, so no fixed ``stacklevel`` can point at the
        caller for both."""
        seen = _WARN_SCOPE[-1] if _WARN_SCOPE else self._fallback_warned
        if note in seen:
            return
        seen.add(note)
        warn_external(f"SimBackend(engine={self.engine!r}): {note}",
                      RuntimeWarning)

    def make_epoch(self, epoch: int) -> _SimEpoch:
        if self.buffer_policy not in ("warm", "cold"):
            raise ValueError(f"SimBackend: buffer_policy must be 'warm' or "
                             f"'cold', got {self.buffer_policy!r}")
        if self.epoch_isolation == "none":
            # the launch-epoch anti-pattern: every "epoch" shares one
            # cluster, so AR(1) state, epoch bias and clock drift carry
            # over (meaningful serially; workers each rebuild their own)
            if self._shared_epoch is None:
                self._shared_epoch = _SimEpoch(self, 0)
            return self._shared_epoch
        if self.epoch_isolation != "process":
            raise ValueError(f"SimBackend: epoch_isolation must be 'process' "
                             f"or 'none', got {self.epoch_isolation!r}")
        return _SimEpoch(self, epoch)

    def measure(self, ctx: _SimEpoch, case: TestCase, nrep: int) -> np.ndarray:
        op = ctx.op(case.op)
        runs = [run_windowed(ctx.net, ctx.sync, op, case.msize, nrep,
                             win_size=self.win_size, engine=ctx.engine)]
        # top up the window discards (bounded: at most 2 extra chunks)
        for _ in range(2):
            missing = nrep - sum(r.valid_times.size for r in runs)
            if missing <= 0:
                break
            runs.append(run_windowed(ctx.net, ctx.sync, op, case.msize,
                                     missing, win_size=self.win_size,
                                     engine=ctx.engine))
        wr = WindowRun.concat(runs)
        # Degenerate case (window far too small): nothing valid anywhere.
        # Return at most nrep raw observations rather than every top-up
        # draw, so adaptive stopping's sample-size accounting stays honest.
        return wr.valid_times if wr.valid_times.size else wr.times[:nrep]

    def record_meta(self, ctx: _SimEpoch, case: TestCase) -> dict:
        """Per-record provenance: the engine that *actually ran* (which can
        differ from the configured one — see :func:`resolve_engine`)."""
        meta = {"engine": ctx.engine}
        if ctx.engine_note is not None:
            meta["engine_fallback"] = ctx.engine_note
        return meta

    def measure_epochs(self, work: dict, design: ExperimentDesign):
        """Fused campaign execution (the optional backend capability
        :class:`~repro.campaign.Campaign` probes for).

        ``work`` maps ``epoch -> [TestCase, ...]`` in that epoch's shuffled
        case order. Epochs whose next pending case coincides are measured by
        ONE device program per cost-model term
        (:func:`repro.simjax.run_windowed_epochs_jax`); each epoch's own
        case order, host RNG stream, AR(1) carries and ``net.t`` writebacks
        are preserved exactly, so records match what sequential per-epoch
        measurement of the same pending work would produce (modulo the
        fused window's documented draw change). Window discards are topped
        up per epoch and adaptive nrep continues through the normal
        :func:`~repro.core.design.measure_adaptive` loop, both reusing the
        bucketed per-epoch traces.

        Returns ``{(op, msize, epoch): (times, meta)}`` covering every case
        in ``work``, or ``None`` when the fused path cannot run it (caller
        then measures per epoch as before): fusing disabled, shared-cluster
        epoch isolation, no jax, or an engine other than the jit one.
        """
        if not self.fuse_epochs or self.epoch_isolation != "process":
            return None
        # Only an explicit engine="jax" can resolve to the jit engine
        # (auto prefers the numpy batch path) — gate before building any
        # epoch context, so non-jax campaigns pay nothing for the probe.
        if self.engine != "jax":
            return None
        if not work or all(not cases for cases in work.values()):
            return None
        from repro.simjax import have_jax
        if not have_jax():
            return None
        ctxs = {e: self.make_epoch(e) for e in sorted(work)}
        if any(ctx.engine != "jax" for ctx in ctxs.values()):
            return None          # only the jit engine has a fused program
        from repro.core.design import measure_adaptive
        from repro.simjax import run_windowed_epochs_jax

        nrep0 = design.nrep_min if design.adaptive else design.nrep
        pos = {e: 0 for e in sorted(work)}
        out: dict = {}
        while True:
            by_case: dict = {}
            for e in sorted(work):
                if pos[e] < len(work[e]):
                    c = work[e][pos[e]]
                    by_case.setdefault((c.op, c.msize), []).append(e)
            if not by_case:
                return out
            # Most common next case first: maximal epoch fan-in per
            # dispatch without ever reordering within an epoch.
            (op_name, msize), epochs = max(
                by_case.items(), key=lambda kv: (len(kv[1]), kv[0]))
            ops = [ctxs[e].op(op_name) for e in epochs]
            runs = run_windowed_epochs_jax(
                [ctxs[e].net for e in epochs],
                [ctxs[e].sync for e in epochs],
                ops, msize, nrep0, self.win_size)
            for i, e in enumerate(epochs):
                ctx, case = ctxs[e], work[e][pos[e]]
                rs = [runs[i]]
                # top up the window discards (bounded, as measure() does)
                for _ in range(2):
                    miss = nrep0 - sum(r.valid_times.size for r in rs)
                    if miss <= 0:
                        break
                    rs.append(run_windowed(ctx.net, ctx.sync, ops[i],
                                           msize, miss,
                                           win_size=self.win_size,
                                           engine=ctx.engine))
                valid = np.concatenate([r.valid_times for r in rs])
                times = valid if valid.size else np.concatenate(
                    [r.times for r in rs])[:nrep0]
                if design.adaptive:
                    times, meta = measure_adaptive(self.measure, ctx, case,
                                                   design, initial=times)
                else:
                    meta = dict(nrep_used=int(times.size), converged=True)
                meta.update(self.record_meta(ctx, case))
                meta["fused"] = True
                out[(op_name, msize, e)] = (np.asarray(times, np.float64),
                                            meta)
                pos[e] += 1

    def factors(self, design: ExperimentDesign) -> FactorSet:
        # The jit engine runs on JAX's default device, which the factor set
        # then names; the numpy engines never touch a device.
        device = {} if self.engine == "jax" else dict(backend="sim",
                                                      device_kind="simnet")
        return capture_factors(
            measurement_backend=self.name,
            sync_method=self.sync_name,
            window_size_us=self.win_size * 1e6,
            epoch_isolation=self.epoch_isolation,
            buffer_policy=self.buffer_policy,
            dtype=self.dtype,
            extra=(("p", self.p), ("seed0", self.seed0),
                   ("op_kw", tuple(sorted(self.op_kw.items()))),
                   ("per_op_kw", tuple(sorted(
                       (op, tuple(sorted(kw.items())))
                       for op, kw in self.per_op_kw.items()))),
                   ("sync_kw", tuple(sorted(self.sync_kw.items()))),
                   ("clock_kw", tuple(sorted(self.clock_kw.items()))),
                   ("engine", self.engine)),
            **device,
            **_design_factor_kw(design),
        )

    def default_cases(self) -> list[TestCase]:
        return [TestCase("allreduce", m) for m in (256, 4096)]


# ---------------------------------------------------------------------------
# Real-JAX collective backend
# ---------------------------------------------------------------------------

def ensure_host_devices(n: int) -> int:
    """Request ``n`` host CPU devices via
    ``--xla_force_host_platform_device_count`` and return the count JAX
    actually provides. Only effective if called before JAX initializes its
    backends; afterwards it just reports the live device count."""
    import os

    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = \
            f"{flags} --xla_force_host_platform_device_count={n}".strip()
    import jax

    return jax.device_count()


@dataclass
class JaxBackend:
    """Real jitted JAX collectives on a host-device mesh.

    ``case.op`` is one of ``psum`` / ``all_gather`` / ``all_to_all`` —
    lowered through ``jax.pmap`` over ``n_devices`` devices so the timed
    executable contains a genuine cross-device collective even on a single
    host (``--xla_force_host_platform_device_count``). ``case.msize`` is
    the per-device payload in bytes. A launch epoch re-jits the collective
    (``epoch_isolation="clear_caches"``), the in-process analogue of a
    fresh mpirun.
    """

    ops: tuple = ("psum", "all_gather", "all_to_all")
    n_devices: int | None = None      # None = all available
    meter: MeterConfig = field(
        default_factory=lambda: MeterConfig(epoch_isolation="clear_caches"))
    dtype: str = "float32"
    name: str = "jax"

    def _ndev(self) -> int:
        import jax

        n = self.n_devices or jax.device_count()
        if n > jax.device_count():
            raise ValueError(
                f"JaxBackend: {n} devices requested, {jax.device_count()} "
                "available — set --xla_force_host_platform_device_count")
        return n

    def _input(self, op: str, msize: int, n: int) -> np.ndarray:
        """The host-side input of one collective: ``n`` per-device payloads
        of ``msize`` bytes (padded so all_to_all's split axis divides),
        small integers whose sums stay exact even in bfloat16, laid out
        differently on each device so a misrouted block changes the
        output."""
        import jax.numpy as jnp

        dtype = jnp.dtype(self.dtype)
        count = max(n, int(np.ceil(msize / dtype.itemsize)))
        count = int(np.ceil(count / n)) * n
        shape = (n, n, count // n) if op == "all_to_all" else (n, count)
        vals = (7 * np.arange(n)[:, None] + np.arange(count)[None, :]) % 16
        return vals.reshape(shape).astype(dtype)

    def _place_input(self, op: str, msize: int, n: int):
        """The input placed once, one payload per device, so the timed call
        moves no data before its collective starts."""
        import jax
        from jax.sharding import PmapSharding

        host = self._input(op, msize, n)
        return jax.device_put(host, PmapSharding.default(
            host.shape, 0, jax.devices()[:n]))

    def _build_collective(self, op: str, msize: int, n: int | None = None):
        import jax
        from jax import lax

        n = self._ndev() if n is None else n
        devices = jax.devices()[:n]
        if op == "psum":
            f = jax.pmap(lambda x: lax.psum(x, "i"), axis_name="i",
                         devices=devices)
        elif op == "all_gather":
            f = jax.pmap(lambda x: lax.all_gather(x, "i"), axis_name="i",
                         devices=devices)
        elif op == "all_to_all":
            # split axis must equal the mesh size: (n, count//n) per device
            f = jax.pmap(lambda x: lax.all_to_all(x, "i", 0, 0),
                         axis_name="i", devices=devices)
        else:
            raise ValueError(f"JaxBackend: unknown collective {op!r}; "
                             f"one of {self.ops}")
        x = self._place_input(op, msize, n)
        return lambda: f(x)

    def check(self, op: str, msize: int) -> float:
        """Run one collective on all ``n`` devices and return the largest
        absolute difference from its numpy result (0.0 when exact)."""
        n = self._ndev()
        host = self._input(op, msize, n)
        if op == "psum":
            want = np.broadcast_to(host.sum(axis=0), host.shape)
        elif op == "all_gather":
            want = np.broadcast_to(host[None], (n,) + host.shape)
        else:
            want = np.swapaxes(host, 0, 1)
        got = np.asarray(self._build_collective(op, msize, n)())
        return float(np.max(np.abs(got.astype(np.float64) - want)))

    def _build_case(self, opexpr: str, msize: int):
        """Build the timed callable for a case — a single collective, or a
        composite mock-up expression sequencing several collectives inside
        one timed region (``"reduce+bcast"``-style guideline sides;
        ``@half`` runs a term over half the mesh, the split-robustness
        mock-up)."""
        terms = parse_opexpr(opexpr)
        n = self._ndev()
        fns = []
        for t in terms:
            if t.impl is not None:
                raise ValueError(f"JaxBackend: '#{t.impl}' implementation "
                                 f"tags are not supported (case {opexpr!r})")
            tn = max(2, n // 2) if t.procs == "half" else n
            fns.append(self._build_collective(t.op, t.msize(msize), n=tn))
        return _sequence_calls(fns)

    def make_epoch(self, epoch: int) -> JaxEpochContext:
        def build(_epoch: int) -> dict:
            return {}  # callables are built lazily, one per case

        ctx = JaxEpochContext(build, epoch, self.meter)
        return ctx

    def measure(self, ctx: JaxEpochContext, case: TestCase,
                nrep: int) -> np.ndarray:
        key = f"{case.op}@{case.msize}"
        if key not in ctx.callables:
            ctx.callables[key] = self._build_case(case.op, case.msize)
        return ctx.measure(key, nrep)

    def factors(self, design: ExperimentDesign) -> FactorSet:
        return capture_factors(
            measurement_backend=self.name,
            sync_method="block_until_ready",
            mesh_shape=(self._ndev(),),
            mesh_axes=("i",),
            epoch_isolation=self.meter.epoch_isolation,
            buffer_policy="cold" if self.meter.cold_buffers else "warm",
            dtype=self.dtype,
            extra=(("ops", tuple(self.ops)), ("warmup", self.meter.warmup)),
            **_design_factor_kw(design),
        )

    def default_cases(self) -> list[TestCase]:
        return [TestCase(op, m) for op in self.ops for m in (1 << 10, 1 << 16)]


# ---------------------------------------------------------------------------
# Pallas-kernel backend
# ---------------------------------------------------------------------------

@dataclass
class KernelBackend:
    """Pallas kernels vs. their jnp references as operations under test.

    ``case.op`` names the kernel (``flash_attention`` / ``ssd_scan``),
    ``case.msize`` is the sequence length. ``impl`` selects which side of
    the A/B comparison this backend measures — run one campaign with
    ``impl="pallas"`` and one with ``impl="ref"``, then
    :func:`~repro.core.compare.compare_tables` answers "is the kernel
    faster?" the statistically sound way.

    A case may also be an op *expression* (:mod:`repro.core.opexpr`): a
    ``#impl`` tag overrides the backend-level ``impl`` for that term, so
    the guideline ``"flash_attention#pallas" <= "flash_attention#ref"``
    (the kernel must not lose to its own jnp oracle) runs both sides in
    the *same* campaign, and ``+`` sequences kernels inside one timed
    region. ``@half`` has no meaning for single-device kernels and is
    rejected.
    """

    impl: str = "pallas"              # pallas | ref
    batch: int = 1
    heads: int = 4
    kv_heads: int | None = None
    head_dim: int = 32
    state_dim: int = 16
    seed0: int = 0
    meter: MeterConfig = field(
        default_factory=lambda: MeterConfig(epoch_isolation="clear_caches",
                                            warmup=1))
    name: str = "kernel"

    def make_epoch(self, epoch: int) -> JaxEpochContext:
        def build(_epoch: int) -> dict:
            return {}

        return JaxEpochContext(build, epoch, self.meter)

    def _build_case(self, opexpr: str, msize: int, epoch: int):
        from repro.kernels.ops import make_benchmark_op

        fns = []
        for t in parse_opexpr(opexpr):
            if t.procs == "half":
                raise ValueError("KernelBackend: '@half' has no meaning for "
                                 f"single-device kernels (case {opexpr!r})")
            fns.append(make_benchmark_op(
                t.op, t.impl or self.impl, seq=t.msize(msize),
                batch=self.batch, heads=self.heads, kv_heads=self.kv_heads,
                head_dim=self.head_dim, state_dim=self.state_dim,
                seed=self.seed0 + epoch))
        return _sequence_calls(fns)

    def measure(self, ctx: JaxEpochContext, case: TestCase,
                nrep: int) -> np.ndarray:
        key = f"{case.op}@{case.msize}"
        if key not in ctx.callables:
            ctx.callables[key] = self._build_case(case.op, case.msize,
                                                  ctx.epoch)
        return ctx.measure(key, nrep)

    def factors(self, design: ExperimentDesign) -> FactorSet:
        return capture_factors(
            measurement_backend=self.name,
            sync_method="block_until_ready",
            epoch_isolation=self.meter.epoch_isolation,
            extra=(("impl", self.impl), ("batch", self.batch),
                   ("heads", self.heads), ("kv_heads", self.kv_heads),
                   ("head_dim", self.head_dim),
                   ("state_dim", self.state_dim), ("seed0", self.seed0)),
            **_design_factor_kw(design),
        )

    def default_cases(self) -> list[TestCase]:
        return [TestCase("flash_attention", s) for s in (64, 128)]


# ---------------------------------------------------------------------------
# Legacy-pair adapter
# ---------------------------------------------------------------------------

@dataclass
class FunctionBackend:
    """Lift a bare ``(epoch_factory, measure)`` pair into the
    :class:`MeasurementBackend` protocol.

    The migration path off the deprecated legacy form of
    :func:`~repro.core.design.run_design`: anything that could be
    expressed as the pair is expressible as this backend, and gains what
    the pair never had — a :class:`~repro.core.factors.FactorSet` (so
    results can live in stores, sweeps and audits) and a ``default_cases``
    hook. ``name`` lands in the factor set's ``measurement_backend``
    field: give two different measurement functions two different names,
    or their campaigns will collide on one fingerprint.
    """

    epoch_factory: Any                 # Callable[[int], Any]
    measure_fn: Any                    # Callable[[Any, TestCase, int], array]
    name: str = "function"
    cases: tuple = ()

    def make_epoch(self, epoch: int) -> Any:
        return self.epoch_factory(epoch)

    def measure(self, ctx: Any, case: TestCase, nrep: int) -> np.ndarray:
        return np.asarray(self.measure_fn(ctx, case, nrep), np.float64)

    def factors(self, design: ExperimentDesign) -> FactorSet:
        return capture_factors(
            measurement_backend=self.name,
            **_design_factor_kw(design),
        )

    def default_cases(self) -> list[TestCase]:
        return [TestCase(op, int(m)) for op, m in self.cases]

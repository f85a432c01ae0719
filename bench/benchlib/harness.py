"""The benchmark harness, driven by ``BENCHMARK.json`` and the files it names.

A cell (an entry of ``workloads``) names a configuration and a traffic mix.
The configuration file ``bench/configs/<config>.json`` names the system
module that drives it (``benchlib/systems/<system>.py``); the traffic file
``bench/traffic/<traffic>.json`` holds the mix's parameters. Each per-layer
metric is read by ``bench/metrics/<name>.py``. A new cell, configuration
or metric is therefore new files and new entries, never an edit.

One run: check the device, set up the cell (weights or data from the seed,
every shape warmed), measure campaign after campaign for ``seconds``
(under the profiler with ``trace``), read the peak memory, free the
program's state, check the window's output against the plain reference,
and report.
"""

from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import logging
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from .probes import Spans, WindowClosed

ROOT = Path(__file__).resolve().parents[2]


class BenchError(Exception):
    """A run that cannot produce a result (no chip, a bad cell, ...)."""


@dataclass
class Check:
    """One number compared with its limit: the run is correct when every
    value is at most its limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(np.isfinite(self.value) and self.value <= self.limit)


@dataclass
class Run:
    workload: str
    seed: int
    seconds: float
    traced: bool
    device_kind: str = ""
    spans: Spans = None
    deadline: float = 0.0
    window: tuple = (0.0, 0.0)           # host clock
    campaigns: list = field(default_factory=list)
    trace: object = None                 # benchlib.trace.Trace
    peaks: dict = field(default_factory=dict)
    cell: object = None

    def rng(self, stream: int) -> np.random.Generator:
        return np.random.default_rng([self.seed, stream])

    def peak(self, key: str) -> float:
        if self.device_kind not in self.peaks:
            raise BenchError(f"no peaks for device kind {self.device_kind!r} "
                             f"in bench/peaks.json")
        return float(self.peaks[self.device_kind][key])

    def completed(self) -> list[dict]:
        return [c for c in self.campaigns if c["completed"]]


def load_spec(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise BenchError(f"no {what} named {name!r} in BENCHMARK.json")


def applies(metric: dict, workload: str, spec: dict) -> bool:
    if "workloads" in metric:
        return workload in metric["workloads"]
    if metric in spec["end_to_end"]:
        return True
    moved = _by_name(spec["end_to_end"], metric["moves"], "metric")
    return applies(moved, workload, spec)


def load_system(name: str):
    return importlib.import_module(f"benchlib.systems.{name}")


def load_reader(name: str, root: Path = ROOT):
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _memory_peak(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class _CompileCounter(logging.Handler):
    """Compilations and persistent-cache reads, counted while the window
    is open, with the names of the functions lowered there. Set-up warms
    every shape; what still compiles in the window is the program's own
    launch epochs clearing its jit caches."""

    def __init__(self):
        import jax

        super().__init__(logging.WARNING)
        self._open = False
        self.misses = 0
        self.hits = 0
        self.names: dict[str, int] = {}
        jax.monitoring.register_event_listener(self._on_event)

    @property
    def open(self) -> bool:
        return self._open

    @open.setter
    def open(self, value: bool) -> None:
        import jax

        self._open = value
        jax.config.update("jax_log_compiles", value)
        log = logging.getLogger("jax")
        if value:       # count the compile messages instead of printing them
            self._saved = (log.handlers[:], log.propagate)
            log.handlers, log.propagate = [self], False
        elif hasattr(self, "_saved"):
            (log.handlers, log.propagate), self._saved = self._saved, None
            del self._saved

    def emit(self, record):
        msg = record.getMessage()
        if msg.startswith("Compiling "):
            name = msg.split()[1]
            self.names[name] = self.names.get(name, 0) + 1

    def _on_event(self, event, **kw):
        if not self._open:
            return
        if event == "/jax/compilation_cache/cache_misses":
            self.misses += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def run_workload(workload: str, seed: int, seconds: float, traced: bool, *,
                 t_start: float | None = None, root: Path = ROOT,
                 require_tpu: bool = True, overrides: dict | None = None,
                 peaks: dict | None = None, log=None,
                 with_control: bool = False):
    """Run one cell and return ``(result, checks, control)``. ``overrides``
    (keys ``config``, ``traffic``) replace entries of the cell's files: the
    CPU rehearsals use them for tiny sizes. ``with_control`` also reads the
    control (the reference one precision below, in the program's place)
    after the check. The command line uses neither."""
    t_start = time.perf_counter() if t_start is None else t_start
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    spec = load_spec(root)
    cell_e = _by_name(spec["workloads"], workload, "workload")
    conf_e = _by_name(spec["configs"], cell_e["config"], "config")
    config = json.loads((root / conf_e["file"]).read_text())
    traffic = json.loads((root / "bench" / "traffic"
                          / f"{cell_e['traffic']}.json").read_text())
    overrides = overrides or {}
    config.update(overrides.get("config", {}))
    traffic.update(overrides.get("traffic", {}))

    import jax

    from repro.core.runtime_meter import use_compile_cache

    # the program's own cache policy, as its entry points have it: a
    # program that compiles faster than JAX's threshold is compiled again
    # in every launch epoch that clears the jit caches, as users see it
    cache_dir = use_compile_cache(str(root))
    counter = _CompileCounter()

    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise BenchError(f"no TPU: JAX's first device is {dev.platform} "
                         f"({dev.device_kind})")
    if require_tpu and len(devices) < int(cell_e["chips"]):
        raise BenchError(f"cell {workload} needs {cell_e['chips']} chips, "
                         f"JAX sees {len(devices)}")
    if peaks is None:
        peaks = json.loads((root / "bench" / "peaks.json").read_text())
        peaks = peaks["devices"]
    run = Run(workload=workload, seed=int(seed), seconds=float(seconds),
              traced=bool(traced), device_kind=dev.device_kind,
              spans=Spans(bool(traced)), peaks=peaks)
    log(f"bench: {workload} seed={seed} seconds={seconds} trace={int(traced)} "
        f"on {dev.platform} {dev.device_kind} x{len(devices)}; jax "
        f"{jax.__version__}; compile cache {cache_dir}")

    system = load_system(config["system"])
    cell = system.make_cell(config, traffic, run)
    run.cell = cell
    trace_dir = None
    try:
        cell.setup()
        if traced:
            trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # host spans only, no Python
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        counter.open = True
        t0 = time.perf_counter()
        setup_s = t0 - t_start
        run.deadline = t0 + run.seconds
        k = 0
        with run.spans.span("window"):
            while time.perf_counter() < run.deadline:
                c0 = time.perf_counter()
                info, completed = {}, True
                try:
                    with run.spans.span("campaign"):
                        info = cell.campaign(k) or {}
                except WindowClosed:
                    completed = False
                c1 = time.perf_counter()
                run.campaigns.append(dict(start=c0, end=c1, info=info,
                                          completed=completed and
                                          c1 <= run.deadline))
                k += 1
        t1 = time.perf_counter()
        counter.open = False
        run.window = (t0, t1)
        if traced:
            jax.profiler.stop_trace()
        memory_peak = _memory_peak(devices[:int(cell_e["chips"])])
    finally:
        cell.release()
    log(f"bench: window {t1 - t0:.3f} s, {k} campaigns "
        f"({len(run.completed())} completed in it); compiles in window "
        f"{counter.misses}, cache reads {counter.hits}, programs lowered "
        f"{counter.names}; set-up "
        f"{setup_s:.3f} s; memory peak {memory_peak}")

    t_check = time.perf_counter()
    checks = cell.checks()
    log(f"bench: reference check took {time.perf_counter() - t_check:.3f} s")
    control = None
    if with_control:
        cell.control = True
        control = cell.checks()

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    metrics = {}
    result = {}
    if traced:
        from . import trace as tr

        run.trace = tr.load_xplane(tr.find_xplane(trace_dir),
                                   platform=dev.platform)
        shutil.rmtree(trace_dir, ignore_errors=True)
        lo, hi = run.trace.window()
        device["busy_s"] = tr.busy_ns(run.trace, lo, hi) * 1e-9
        device["window_s"] = (hi - lo) * 1e-9
        for m in spec["per_layer"]:
            if not applies(m, workload, spec):
                continue
            value = load_reader(m["name"], root)(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        result_breakdown = {"device_ops": tr.top_ops(run.trace, lo, hi),
                            "idle_gaps": tr.idle_gaps(run.trace, lo, hi)}
    else:
        e2e = cell.end_to_end()
        e2e["setup_s"] = setup_s
        for m in spec["end_to_end"]:
            if applies(m, workload, spec):
                if m["name"] not in e2e:
                    raise BenchError(f"cell {workload} did not measure "
                                     f"{m['name']}")
                metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                      "unit": m["unit"]}
    result.update(correct=all(c.ok for c in checks),
                  attempted=k, failed=0, metrics=metrics, device=device)
    if traced:
        result["breakdown"] = result_breakdown
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    return result, checks, control


def main(argv, t_start: float) -> int:
    ap = argparse.ArgumentParser(prog="bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: the program (src/repro) is not in {ROOT}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    try:
        result, checks, _ = run_workload(args.workload, args.seed, args.seconds,
                                      bool(args.trace), t_start=t_start)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    for c in checks:
        print(f"check {c.name} {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0

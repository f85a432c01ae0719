"""The program's spans and counters (``repro.core.telemetry``), on the CPU.

Spans record only under a JAX profiler trace: then each is a ``repro:``
event in the profile and an entry of ``telemetry.spans()`` on the
``perf_counter`` clock, and the two agree up to one offset."""

import glob
import os

import jax
import numpy as np
import pytest

from repro.campaign import (Campaign, CampaignSpec, FunctionBackend,
                            SimBackend)
from repro.core import ExperimentDesign, TestCase, telemetry
from repro.core.design import NREP_SPENT
from repro.core.runtime_meter import JaxEpochContext, MeterConfig
from repro.simjax import engine_stats, reset_engine_stats

SYNC_KW = dict(n_fitpts=30, n_exchanges=10)


def _sim_campaign(fuse=True, adaptive=False, epochs=3):
    kw = (dict(nrep_min=20, nrep_max=200, rel_ci_target=0.02) if adaptive
          else dict(nrep=40))
    design = ExperimentDesign(n_launch_epochs=epochs, seed=7, **kw)
    spec = CampaignSpec([TestCase("allreduce", 1024),
                         TestCase("bcast", 4096)], design)
    backend = SimBackend(engine="jax", p=8, seed0=11, fuse_epochs=fuse,
                         sync_kw=SYNC_KW)
    return Campaign(spec, backend)


def _profiled(tmp_path, fn):
    """Run ``fn`` under a profiler trace; return its spans and the
    ``repro:`` events of the profile as ``{name: [(start, end), ...]}``."""
    from jax.profiler import ProfileData

    telemetry.reset_spans()
    with jax.profiler.trace(str(tmp_path)):
        fn()
    spans = telemetry.spans()
    telemetry.reset_spans()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    events: dict = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(telemetry.PREFIX):
                    name = ev.name[len(telemetry.PREFIX):]
                    s = float(ev.start_ns)
                    events.setdefault(name, []).append(
                        (s, s + float(ev.duration_ns)))
    return spans, {k: sorted(v) for k, v in events.items()}


def test_without_a_profiler_span_records_nothing():
    assert not telemetry.tracing()
    telemetry.reset_spans()
    a, b = telemetry.span("campaign"), telemetry.span("clock_sync")
    assert a is b
    with a:
        with b:
            pass
    _sim_campaign(epochs=1).run()
    assert telemetry.spans() == [] and telemetry.dropped() == 0


@pytest.fixture(scope="module")
def profiled_campaign(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("trace")
    _sim_campaign(epochs=2).run()         # compile outside the trace
    return _profiled(tmp, lambda: _sim_campaign(epochs=2).run())


def _parents(spans):
    """Each span's enclosing span: the last one opened before it that
    holds it in time (None at the top)."""
    out = []
    for i, s in enumerate(spans):
        holders = [p for p in spans[:i]
                   if p.t0_ns <= s.t0_ns and s.t1_ns <= p.t1_ns]
        out.append(holders[-1].name if holders else None)
    return out


def test_campaign_records_its_span_tree(profiled_campaign):
    spans, events = profiled_campaign
    assert spans and all(s.t1_ns is not None and s.t1_ns >= s.t0_ns
                         for s in spans)
    tree = {(s.name, p) for s, p in zip(spans, _parents(spans))}
    assert ("campaign", None) in tree
    assert ("epoch_build", "campaign") in tree
    assert ("clock_sync", "epoch_build") in tree
    assert ("sim_engine", "campaign") in tree
    assert ("sim_wait", "sim_engine") in tree
    # one campaign, and every span inside it
    assert [s.name for s in spans].count("campaign") == 1
    assert spans[0].name == "campaign"
    assert all(p is not None for p in _parents(spans)[1:])
    # every kept span is also a repro: event in the profile
    for n in {s.name for s in spans}:
        assert len(events.get(n, [])) == sum(s.name == n for s in spans), n


def test_span_times_map_onto_the_trace_clock(profiled_campaign):
    spans, events = profiled_campaign
    anchor = spans[0]
    offset = events["campaign"][0][0] - anchor.t0_ns
    seen: dict = {}
    for s in spans:
        k = seen.get(s.name, 0)
        seen[s.name] = k + 1
        t0, t1 = events[s.name][k]
        assert abs(s.t0_ns + offset - t0) < 50_000, s
        assert abs(s.t1_ns + offset - t1) < 50_000, s


def test_meter_spans_each_timed_call_and_the_warmup(tmp_path):
    f = jax.jit(lambda x: x * 2.5 + 1.0)
    x = np.arange(8.0)

    def measure():
        ctx = JaxEpochContext(lambda e: {"f": lambda: f(x)}, 0,
                              MeterConfig(warmup=2))
        ctx.measure("f", 5)
        ctx.measure("f", 3)

    spans, events = _profiled(tmp_path, measure)
    names = [s.name for s in spans]
    assert names.count("epoch_build") == 1
    assert names.count("warmup") == 1
    assert names.count("timed_call") == 8
    assert all(n in events for n in ("epoch_build", "warmup", "timed_call"))


@pytest.mark.parametrize("fuse,adaptive,want", [
    (True, False, (2, 8, 240)),
    (True, True, (8, 38, 660)),
    (False, False, (2, 12, 240)),
    (False, True, (6, 44, 680)),
], ids=["fused", "fused-adaptive", "per-epoch", "per-epoch-adaptive"])
def test_counters_keep_their_values(fuse, adaptive, want):
    """The engine's and the repetition counters read through telemetry
    give what they gave when each kept its own count."""
    reset_engine_stats()
    n0 = NREP_SPENT.read()
    _sim_campaign(fuse=fuse, adaptive=adaptive).run()
    s = engine_stats()
    assert (s["n_traces"], s["n_dispatches"], NREP_SPENT.read() - n0) == want
    c = telemetry.counters()
    assert (c["sim_traces"], c["sim_dispatches"]) == want[:2]


def test_a_fresh_jit_counts_one_compile():
    telemetry.watch_compiles()
    f = jax.jit(lambda x: x * 3.25 - 0.5)
    x = np.arange(4.0)
    c0 = telemetry.counters()
    jax.block_until_ready(f(x))
    c1 = telemetry.counters()
    jax.block_until_ready(f(x))
    c2 = telemetry.counters()
    assert c1["compiles"] == c0.get("compiles", 0) + 1
    assert c1["compile_s"] > c0.get("compile_s", 0.0)
    assert c2["compiles"] == c1["compiles"]


@pytest.fixture
def unwatched():
    """The process as it was before anything registered the compile
    listeners, so that the code under test must register them itself."""
    if telemetry._watching:
        jax.monitoring.unregister_event_duration_listener(
            telemetry._on_duration)
        jax.monitoring.unregister_event_listener(telemetry._on_event)
        telemetry._watching = False
    yield
    telemetry.watch_compiles()


def test_campaign_meta_counts_compiles_without_sim_dispatches(unwatched):
    """A meter campaign re-jits its callable in each launch epoch: its
    ``meta["jit"]`` counts those compiles, the first epoch's too, though
    the simulator dispatched nothing."""
    def build(_epoch):
        f = jax.jit(lambda x: x * 1.75 + 2.0)
        return {"scale": lambda: f(np.arange(16.0))}

    cfg = MeterConfig(epoch_isolation="clear_caches", warmup=1)
    backend = FunctionBackend(lambda e: JaxEpochContext(build, e, cfg),
                              lambda ctx, case, n: ctx.measure(case.op, n))
    design = ExperimentDesign(n_launch_epochs=2, nrep=5, seed=1)
    res = Campaign(CampaignSpec([TestCase("scale", 16)], design),
                   backend).run()
    jit = res.meta["jit"]
    assert jit["n_dispatches"] == 0 and jit["n_traces"] == 0
    # one compile per epoch; the listener registered after the first
    # epoch's warm-up would see one
    assert jit["n_compiles"] >= 2 and jit["compile_s"] > 0.0
    # no persistent cache here, so nothing was read from one
    assert jit["n_cache_reads"] == 0
    assert "cache_hit_rate" not in jit


@pytest.fixture
def persistent_cache(tmp_path):
    """JAX's persistent compilation cache in a fresh directory, keeping
    every program; the process's settings put back after."""
    from jax._src import compilation_cache

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    jax.config.update(keys[0], str(tmp_path))
    jax.config.update(keys[1], 0.0)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


def test_a_persistent_cache_read_counts_as_a_compile_and_a_read(
        persistent_cache):
    telemetry.watch_compiles()
    x = np.arange(4.0)

    def fresh():
        jax.clear_caches()
        jax.block_until_ready(jax.jit(lambda v: v * 4.5 - 1.0)(x))
        return telemetry.counters()

    c0 = telemetry.counters()
    c1 = fresh()                      # compiled, and written to the cache
    c2 = fresh()                      # read back
    reads = [c.get("compile_cache_reads", 0) for c in (c0, c1, c2)]
    assert reads[1] == reads[0] and reads[2] == reads[1] + 1
    assert c1["compiles"] == c0.get("compiles", 0) + 1
    assert c2["compiles"] == c1["compiles"] + 1

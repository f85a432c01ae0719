"""The benchmark's trace reduction, operation counts and peak table.

The recorded trace (``data/cpu_trace.xplane.pb``) was taken on the CPU
around a jitted matmul chain, with the harness's own spans: a window of
two campaigns, each of three backend calls, then 4 ms of host work and a
6 ms analysis span.
"""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from benchlib import counts, harness  # noqa: E402
from benchlib import trace as tr  # noqa: E402

DATA = BENCH / "tests" / "data"


@pytest.fixture(scope="module")
def recorded():
    return tr.load_xplane(str(DATA / "cpu_trace.xplane.pb"), platform="cpu")


def _brute_busy(events, lo, hi, step=1000.0):
    """Busy time on a 1 us grid: an independent count of the union."""
    grid = np.arange(lo, hi, step)
    busy = np.zeros(grid.size, bool)
    for s, e, _ in events:
        busy |= (grid >= s) & (grid < e)
    return busy.sum() * step


def test_recorded_trace_has_the_harness_spans(recorded):
    names = [n for _, _, n in recorded.spans]
    assert names.count("window") == 1
    assert names.count("campaign") == 2
    assert names.count("backend_call") == 6
    assert names.count("analysis") == 2
    assert recorded.ops["cpu"], "no XLA operation read from the trace"


def test_busy_union_matches_a_brute_force_count(recorded):
    lo, hi = recorded.window()
    got = tr.busy_ns(recorded, lo, hi)
    want = _brute_busy(recorded.ops["cpu"], lo, hi)
    assert got == pytest.approx(want, rel=0.02, abs=20_000)
    assert 0.0 < got < hi - lo
    assert tr.idle_share(recorded, lo, hi) == pytest.approx(
        1.0 - got / (hi - lo))


def test_idle_gaps_are_labelled_by_the_covering_span(recorded):
    lo, hi = recorded.window()
    gaps = tr.idle_gaps(recorded, lo, hi, k=4)
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps), reverse=True)
    # the two longest gaps are the host work and analysis after each
    # campaign's backend calls (10 ms each); their middles lie in analysis
    assert [g[0] for g in gaps[:2]] == ["analysis", "analysis"]
    assert all(0.009 < g[1] < 0.02 for g in gaps[:2])
    idle_s = (hi - lo - tr.busy_ns(recorded, lo, hi)) * 1e-9
    assert sum(g[1] for g in tr.idle_gaps(recorded, lo, hi, k=10_000)) \
        == pytest.approx(idle_s, rel=1e-9)


def _synthetic():
    # two devices; times in ns; window [0, 100]
    return tr.Trace(
        ops={"/device:TPU:0": [(0, 10, "fusion.1"), (5, 20, "all-reduce.2"),
                               (50, 60, "fusion.1")],
             "/device:TPU:1": [(0, 30, "all-reduce.2"), (90, 110, "copy")]},
        modules={"/device:TPU:0": [(0, 20, "jit_window_fused(1)"),
                                   (50, 60, "jit_sample_epochs(2)")],
                 "/device:TPU:1": [(0, 30, "jit_window_fused(1)")]},
        spans=[(0, 100, "window"), (0, 60, "campaign"),
               (0, 25, "backend_call"), (70, 100, "analysis")])


def test_reduction_by_hand_counts():
    t = _synthetic()
    lo, hi = t.window()
    # device 0 busy [0, 20] + [50, 60] = 30; device 1 [0, 30] + [90, 100]
    assert tr.busy_ns(t, lo, hi) == 35.0
    assert tr.idle_share(t, lo, hi) == pytest.approx(0.65)
    # window_fused: 20 on device 0, 30 on device 1 -> mean 25
    assert tr.program_ns(t, "window_fused", lo, hi) == 25.0
    assert tr.program_ns(t, "sample", lo, hi) == 10.0
    assert tr.program_ns(t, "no_such_program", lo, hi) == 0.0
    top = tr.top_ops(t, lo, hi, k=2)
    assert top[0][0] == "all-reduce.2"
    assert top[0][1] == pytest.approx(45 / 2 * 1e-9)
    # device 0 idles [20, 50] (campaign) and [60, 100] (analysis at 80)
    assert tr.idle_gaps(t, lo, hi) == [["analysis", pytest.approx(40e-9)],
                                       ["campaign", pytest.approx(30e-9)]]


def test_mamba2_counts_by_hand_at_the_smoke_size():
    sys.path.insert(0, str(BENCH.parent / "src"))
    from repro.configs import get_smoke

    cfg = get_smoke("mamba2-1.3b")    # d 64, 4 layers, vocab 512, f32
    c = counts.mamba2_decode(cfg, batch=2)
    # d_inner 128, head_dim 16, state 16, 8 heads
    # projections 64 * (2*128 + 2*16 + 8) + 128 * 64 = 27136 per layer,
    # convs 4 * (128 + 2*16) = 640, state 8 * 16 * 16 = 2048
    # per layer 2 * (2*27136 + 2*640 + 6*2048) = 135680; head 2*2*64*512
    assert c["flops"] == 4 * 135680 + 131072
    # weights (27136 + 640 + 128 + 64) * 4 B + 3 * 8 * 4 B per layer,
    # embedding (512 * 64 + 64) * 4 B
    assert c["weight_bytes"] == 4 * (27968 * 4 + 96) + 32832 * 4
    # state and conv windows read and written: 2 * 4 layers * 2 rows *
    # (2048 + 3 * 160) * 4 B; logits 2 * 512 * 4 B
    assert c["state_bytes"] == 2 * 4 * 2 * 2528 * 4
    assert c["bytes"] == c["weight_bytes"] + c["state_bytes"] + 4096


def test_weight_bytes_match_the_parameter_tree():
    sys.path.insert(0, str(BENCH.parent / "src"))
    import functools

    import jax

    from repro.configs import get_smoke
    from repro.models import init_params

    cfg = get_smoke("mamba2-1.3b")
    shapes = jax.eval_shape(functools.partial(init_params, cfg),
                            jax.random.PRNGKey(0))
    nbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))
    assert counts.mamba2_decode(cfg, 1)["weight_bytes"] == nbytes


def test_benchmark_weights_have_the_models_layout():
    """The weights the benchmark makes are the tree, shapes and dtypes
    that the model's own initialiser makes."""
    sys.path.insert(0, str(BENCH.parent / "src"))
    import dataclasses
    import functools

    import jax

    from benchlib import ref_mamba2
    from repro.configs import get_smoke
    from repro.models import init_params

    cfg = dataclasses.replace(get_smoke("mamba2-1.3b"), dtype="bfloat16")
    want = jax.eval_shape(functools.partial(init_params, cfg),
                          jax.random.PRNGKey(0))
    got = ref_mamba2.init_params(
        3_000_000_007, d_model=cfg.d_model, n_layers=cfg.n_layers,
        vocab=cfg.vocab_size, d_state=cfg.ssm_state,
        head_dim=cfg.ssm_head_dim, expand=cfg.ssm_expand, dtype=cfg.dtype)
    shape = lambda t: jax.tree.map(  # noqa: E731
        lambda a: (a.shape, str(a.dtype)), t)
    assert shape(got) == shape(want)


def test_unknown_device_kind_is_an_error():
    peaks = json.loads((BENCH / "peaks.json").read_text())["devices"]
    run = harness.Run(workload="w", seed=0, seconds=1, traced=True,
                      device_kind="TPU v9 imaginary", peaks=peaks)
    with pytest.raises(harness.BenchError, match="no peaks"):
        run.peak("bf16_flops")
    run.device_kind = "TPU v5 lite"
    assert run.peak("bf16_flops") == 197e12
    assert run.peak("hbm_bytes_per_s") == 819e9

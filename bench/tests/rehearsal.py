"""Tiny sizes for every cell, and a runner that drives the harness's cell
code on the CPU (never the command line, which refuses to run without a
TPU). Imported by the rehearsal, control and fault tests."""

import contextlib
import re
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchlib import harness  # noqa: E402

TINY = {
    "sim-fused-p64": {"traffic": {"p": 8, "nrep": 3000,
                                  "n_launch_epochs": 2}},
    "sim-guidelines-p8": {"traffic": {"n_launch_epochs": 3,
                                      "msizes": [1024]}},
    # 16 layers of the smoke model: deep enough that the float8 control
    # lies above the limit set at the published depth
    "mamba2-decode-b64": {"config": {"smoke": True,
                                     "smoke_sizes": {"n_layers": 16}},
                          "traffic": {"batch": 4, "prompt_len": 16,
                                      "n_launch_epochs": 2, "nrep": 4,
                                      "keep_prob": 0.5, "ref_rows": 2}},
}
# a peak table entry for the CPU, so that readers that need peaks run
CPU_PEAKS = {"cpu": {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}}
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")

_JAX_SETTINGS = ("jax_compilation_cache_dir",)


@contextlib.contextmanager
def jax_settings_restored():
    """The harness turns the persistent compilation cache on; put the
    process's settings back for the tests after it."""
    import jax

    saved = {k: getattr(jax.config, k) for k in _JAX_SETTINGS}
    try:
        yield
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)


def overrides(cell: str) -> dict:
    return {k: dict(v) for k, v in TINY[cell].items()}


def run(cell: str, *, trace=False, seconds=2.0, seed=4_000_000_123,
        control=False, root=ROOT):
    with jax_settings_restored():
        return harness.run_workload(
            cell, seed, seconds, trace, require_tpu=False, root=root,
            overrides=overrides(cell), peaks=CPU_PEAKS, with_control=control,
            log=lambda msg: None)


def check_last_line(cell: str, result: dict, trace: bool,
                    root=ROOT) -> None:
    """The last line's keys, and every metric's name and unit, as the
    benchmark's contract has them."""
    import json

    spec = harness.load_spec(root)
    keys = ["correct", "attempted", "failed", "metrics", "device"]
    keys += ["breakdown"] if trace else []
    assert list(result) == keys + ["checks"], list(result)
    json.dumps(result)
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    dev = result["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    if trace:
        assert dev["window_s"] > 0 and dev["busy_s"] >= 0
        for k in ("device_ops", "idle_gaps"):
            assert len(result["breakdown"][k]) <= 10
    kind = "per_layer" if trace else "end_to_end"
    allowed = {m["name"]: m["unit"] for m in spec[kind]
               if harness.applies(m, cell, spec)}
    if not trace:
        assert set(result["metrics"]) == set(allowed)
    assert set(result["metrics"]) <= set(allowed)
    for name, m in result["metrics"].items():
        assert NAME.match(name), name
        assert UNIT.match(m["unit"]) and m["unit"] == allowed[name]
        assert isinstance(m["value"], float)
    for name, c in result["checks"].items():
        assert NAME.match(name), name
        assert c["value"] <= c["limit"], (name, c)

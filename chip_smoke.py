#!/usr/bin/env python3
"""Smoke run of the repository's measuring path on a TPU.

    python chip_smoke.py                # one chip, real sizes
    python chip_smoke.py --four-chips   # only the paths that span 4 chips
    python chip_smoke.py --rehearse     # every phase, tiny sizes, on the CPU

Everything runs in this one process, which holds the chip. The phases of
a one-chip run, in order:

  sim          a ``Campaign`` over ``SimBackend(engine="jax")`` on the fused
               path (4 launch epochs, nrep=1e5, p=64, allreduce at 256 B and
               4 KiB), then the same design on the numpy batch engine;
               ``compare_tables`` must find the two indistinguishable.
  kernel       ``KernelBackend`` Pallas vs reference for flash_attention at
               mixtral-8x22b's attention widths and ssd_scan at
               mamba2-1.3b's; each compiled kernel must hold a
               ``tpu_custom_call`` and match its reference.
  step         mamba2-1.3b at its published width and depth in bf16,
               weights from ``--seed``: prefill 4x2048 tokens and 16 decode
               steps, timed through ``make_jax_measure`` and
               ``FunctionBackend``; logits finite and close to a float32
               forward.
  collectives  the ``JaxBackend`` default cases, outputs checked.

``--four-chips`` runs instead the ``JaxBackend`` psum / all_gather /
all_to_all over four chips at 1 KiB to 64 MiB per device, checked against
numpy, and the fused simulator at p=1024 with its rank axis sharded over
the chips, compared with the numpy engine.

Each phase prints its numbers, with the device named. Any failure exits
non-zero; so does a run that finds no TPU. The last line of a passing chip
run is ``{"ok": true, "device": {...}}``; a rehearsal never prints it.

JAX's persistent compilation cache is where ``JAX_COMPILATION_CACHE_DIR``
says, or else ``.jax_cache`` next to this file; the run reports its hits.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent


@dataclasses.dataclass(frozen=True)
class Sizes:
    sim_p: int = 64
    sim_nrep: int = 100_000
    sim_epochs: int = 4
    # flash_attention at mixtral-8x22b's attention widths
    fa_heads: int = 48
    fa_kv_heads: int = 8
    fa_head_dim: int = 128
    fa_seq: int = 4096
    # ssd_scan at mamba2-1.3b's widths
    ssd_heads: int = 64
    ssd_head_dim: int = 64
    ssd_state: int = 128
    ssd_seq: int = 4096
    # the mamba2-1.3b step
    step_batch: int = 4
    step_prompt: int = 2048
    step_decode: int = 16
    step_smoke_model: bool = False
    # four chips
    coll_bytes: tuple = (1 << 10, 1 << 14, 1 << 18, 1 << 22, 1 << 26)
    sharded_p: int = 1024
    sharded_nrep: int = 10_000


REAL = Sizes()
TINY = Sizes(sim_p=8, sim_nrep=2000, fa_heads=4, fa_kv_heads=2,
             fa_head_dim=32, fa_seq=256, ssd_heads=8, ssd_head_dim=16,
             ssd_state=16, ssd_seq=256, step_batch=2, step_prompt=64,
             step_decode=4, step_smoke_model=True, coll_bytes=(1 << 10,
                                                                1 << 16),
             sharded_p=64, sharded_nrep=2000)

# Tolerances, fixed before any run. bf16 logits of a 48-layer model against
# an f32 forward at full matmul precision: the relative L2 error of the
# whole logit tensor, and the share of positions whose top token agrees.
STEP_REL_L2 = 0.1
STEP_TOP1 = 0.9
# A kernel against its reference in the same dtype (f32): on the TPU both
# take the MXU's default f32 precision, in different orders.
KERNEL_REL_L2 = 2e-2


class PhaseFailed(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise PhaseFailed(what)


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def rel_l2(got, want) -> float:
    import numpy as np

    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

def _sim_pair(phase, s, dev, *, p, nrep, seed):
    """The fused jit campaign and the numpy batch campaign of one design,
    checked and compared."""
    from repro.campaign import Campaign, CampaignSpec, SimBackend
    from repro.core import ExperimentDesign, TestCase, compare_tables

    design = ExperimentDesign(n_launch_epochs=s.sim_epochs, nrep=nrep,
                              seed=seed)
    spec = CampaignSpec([TestCase("allreduce", 256),
                         TestCase("allreduce", 4096)], design)
    t = time.perf_counter()
    rj = Campaign(spec, SimBackend(p=p, seed0=seed, engine="jax")).run()
    tj = time.perf_counter() - t
    check(all(r.meta.get("engine") == "jax" and r.meta.get("fused")
              for r in rj.records),
          "a jit-engine record was not measured on the fused path")
    check((rj.factors.backend, rj.factors.device_kind)
          == (dev.platform, dev.device_kind),
          f"factor set names {rj.factors.backend}/"
          f"{rj.factors.device_kind}, not the device")
    t = time.perf_counter()
    rn = Campaign(spec, SimBackend(p=p, seed0=seed, engine="batch")).run()
    tn = time.perf_counter() - t
    check(all(r.meta.get("engine") == "batch" for r in rn.records),
          "a numpy-engine record ran another engine")
    say(phase, f"p={p} nrep={nrep} epochs={s.sim_epochs}: jax fused "
               f"{len(rj.records)} records in {tj:.3f} s (compile "
               f"included; factors backend={rj.factors.backend} "
               f"device_kind={rj.factors.device_kind}, jit "
               f"{rj.meta.get('jit')}); numpy batch {len(rn.records)} "
               f"records in {tn:.3f} s")
    for row in compare_tables(rj.table, rn.table):
        say(phase, f"{row.case.op}@{row.case.msize}: jax {row.avg_a:.6e} s "
                   f"numpy {row.avg_b:.6e} s ratio {row.ratio:.6f} "
                   f"p2={row.p_two_sided:.4f} {row.verdict}")
        check(row.verdict == "indistinguishable",
              f"{row.case.op}@{row.case.msize}: jax and numpy engines "
              f"differ ({row.verdict}, p2={row.p_two_sided:.4f})")


def phase_sim(s, dev, seed):
    _sim_pair("sim", s, dev, p=s.sim_p, nrep=s.sim_nrep, seed=seed)


def phase_kernel(s, dev, seed, *, compiled):
    import numpy as np

    from repro.campaign import Campaign, CampaignSpec, KernelBackend
    from repro.core import ExperimentDesign, TestCase, compare_tables
    from repro.kernels.ops import make_benchmark_op

    widths = {
        "flash_attention": (s.fa_seq, dict(heads=s.fa_heads,
                                           kv_heads=s.fa_kv_heads,
                                           head_dim=s.fa_head_dim)),
        "ssd_scan": (s.ssd_seq, dict(heads=s.ssd_heads,
                                     head_dim=s.ssd_head_dim,
                                     state_dim=s.ssd_state)),
    }
    for op, (seq, kw) in widths.items():
        kernel = make_benchmark_op(op, "pallas", seq=seq, seed=seed, **kw)
        t = time.perf_counter()
        text = kernel.func.lower(*kernel.args).compile().as_text()
        t_compile = time.perf_counter() - t
        custom = "tpu_custom_call" in text
        if compiled:
            check(custom, f"{op}: no tpu_custom_call in the compiled program")
        got = np.asarray(kernel(), np.float32)
        want = np.asarray(make_benchmark_op(op, "ref", seq=seq, seed=seed,
                                            **kw)(), np.float32)
        err = rel_l2(got, want)
        say("kernel", f"{op} seq={seq} {kw}: compiled in {t_compile:.3f} s, "
                      f"tpu_custom_call={custom}, shape {got.shape}, "
                      f"rel L2 vs reference {err:.3e} "
                      f"(limit {KERNEL_REL_L2})")
        check(np.isfinite(got).all(), f"{op}: non-finite kernel output")
        check(err <= KERNEL_REL_L2, f"{op}: rel L2 {err:.3e} > "
                                    f"{KERNEL_REL_L2}")
        design = ExperimentDesign(n_launch_epochs=2, nrep=10, seed=seed)
        spec = CampaignSpec([TestCase(op, seq)], design)
        tables = {}
        for impl in ("pallas", "ref"):
            res = Campaign(spec, KernelBackend(impl=impl, seed0=seed,
                                               **kw)).run()
            check(res.factors.backend == dev.platform,
                  f"{op}/{impl}: factors name {res.factors.backend}")
            tables[impl] = res.table
        for row in compare_tables(tables["pallas"], tables["ref"]):
            say("kernel", f"{op} seq={seq}: per-epoch mean of medians "
                          f"pallas {row.avg_a:.6e} s ref {row.avg_b:.6e} s "
                          f"ratio {row.ratio:.4f} (observation, 2 epochs)")


def phase_step(s, dev, seed):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from repro.campaign import FunctionBackend
    from repro.configs import get_config, get_smoke
    from repro.core import ExperimentDesign, TestCase
    from repro.core.design import analyze_records, run_design
    from repro.core.runtime_meter import MeterConfig, make_jax_measure
    from repro.launch.steps import make_decode_step, make_prefill_step
    from repro.models import forward, init_params
    from repro.models.lm import prefill

    if s.step_smoke_model:
        cfg = dataclasses.replace(get_smoke("mamba2-1.3b"), dtype="bfloat16")
    else:
        cfg = get_config("mamba2-1.3b")
    b, t_prompt, n_dec = s.step_batch, s.step_prompt, s.step_decode
    t = time.perf_counter()
    params = jax.jit(functools.partial(init_params, cfg))(
        jax.random.PRNGKey(seed))
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                (b, t_prompt), 0, cfg.vocab_size)
    # The serving path primes the decode cache token by token.
    prompt_logits, cache = jax.jit(functools.partial(
        prefill, cfg, max_len=t_prompt + n_dec))(params, tokens)
    last_decode = np.asarray(prompt_logits[:, -1], np.float32)
    del prompt_logits
    first = jnp.argmax(last_decode, -1).astype(jnp.int32)[:, None]
    say("step", f"{cfg.name}: {cfg.n_layers} layers, d_model "
                f"{cfg.d_model}, {cfg.dtype}; weights (seed {seed}) and "
                f"{b}x{t_prompt} primed cache in "
                f"{time.perf_counter() - t:.3f} s")

    serve = make_decode_step(cfg)

    def decode_n(params, cache, tok):
        def body(carry, _):
            cache, tok = carry
            logits, cache = serve(params, cache, {"tokens": tok})
            nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
            return (cache, nxt), logits[:, -1]
        _, logits = lax.scan(body, (cache, tok), None, length=n_dec)
        return jnp.moveaxis(logits, 0, 1)

    batch = {"tokens": tokens}

    def build(epoch):
        pre = jax.jit(make_prefill_step(cfg))
        dec = jax.jit(decode_n)
        return {"prefill": lambda: pre(params, batch),
                "decode": lambda: dec(params, cache, first)}

    epoch_factory, measure = make_jax_measure(build, MeterConfig(warmup=1))
    design = ExperimentDesign(n_launch_epochs=2, nrep=5, seed=seed)
    cases = [TestCase("prefill", b * t_prompt), TestCase("decode", b * n_dec)]
    t = time.perf_counter()
    table = analyze_records(run_design(
        design, FunctionBackend(epoch_factory, measure, name="mamba2-step"),
        cases=cases))
    say("step", f"timed 2 launch epochs x 5 calls in "
                f"{time.perf_counter() - t:.3f} s (compile included)")
    for case in cases:
        med = table.medians(case)
        say("step", f"{case.op} ({case.msize} tokens per call): per-epoch "
                    f"medians {[float(m) for m in med]} s, "
                    f"{case.msize / float(np.mean(med)):.1f} tokens/s")

    logits = jax.jit(make_prefill_step(cfg))(params, batch)
    decoded = jax.jit(decode_n)(params, cache, first)
    check(logits.shape == (b, t_prompt, cfg.vocab_size),
          f"prefill logits shape {logits.shape}")
    check(decoded.shape == (b, n_dec, cfg.vocab_size),
          f"decode logits shape {decoded.shape}")
    check(bool(jnp.isfinite(logits).all()), "non-finite prefill logits")
    check(bool(jnp.isfinite(decoded).all()), "non-finite decode logits")
    got = np.asarray(logits[0], np.float32)
    del logits

    c32 = dataclasses.replace(cfg, dtype="float32")
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, x: forward(c32, p, x)[0])(p32,
                                                           tokens[:1])
    del p32
    want = np.asarray(want[0], np.float32)
    err = rel_l2(got, want)
    top1 = float(np.mean(got.argmax(-1) == want.argmax(-1)))
    err_dec = rel_l2(last_decode[0], want[-1])
    say("step", f"bf16 vs f32 forward (row 0, {t_prompt} positions): rel "
                f"L2 {err:.4e} (limit {STEP_REL_L2}), top-1 agreement "
                f"{top1:.4f} (limit {STEP_TOP1}); decode path's last "
                f"prompt position: rel L2 {err_dec:.4e}")
    check(err <= STEP_REL_L2, f"bf16 logits rel L2 {err:.4e}")
    check(top1 >= STEP_TOP1, f"top-1 agreement {top1:.4f}")
    check(err_dec <= STEP_REL_L2, f"decode-path rel L2 {err_dec:.4e}")


def _collectives(phase, backend, sizes, dev, seed):
    import numpy as np

    from repro.campaign import Campaign, CampaignSpec
    from repro.core import ExperimentDesign, TestCase

    cases = [TestCase(op, m) for op in backend.ops for m in sizes]
    for case in cases:
        err = backend.check(case.op, case.msize)
        check(err == 0.0, f"{case.op}@{case.msize}: max |error| {err}")
    res = Campaign(CampaignSpec(cases, ExperimentDesign(
        n_launch_epochs=2, nrep=10, seed=seed)), backend).run()
    check(res.factors.backend == dev.platform,
          f"factors name {res.factors.backend}")
    n = backend._ndev()
    for case in cases:
        med = res.table.medians(case)
        say(phase, f"{case.op} {case.msize} B/device on {n} device(s): "
                   f"exact vs numpy; per-epoch medians "
                   f"{[float(m) for m in med]} s")
    check(all(np.all(res.table.medians(c) > 0) for c in cases),
          "a collective timed at zero")


def phase_collectives(s, dev, seed):
    from repro.campaign import JaxBackend

    backend = JaxBackend()
    sizes = sorted({c.msize for c in backend.default_cases()})
    _collectives("collectives", backend, sizes, dev, seed)


def phase_collectives4(s, dev, seed):
    from repro.campaign import JaxBackend

    _collectives("collectives4", JaxBackend(n_devices=4), s.coll_bytes, dev,
                 seed)


def phase_sharded_sim(s, dev, seed):
    from repro.simjax.engine import _rank_sharding

    sharding = _rank_sharding(s.sharded_p)
    check(sharding is not None and sharding.mesh.size == 4,
          f"the rank axis of p={s.sharded_p} is not sharded over 4 devices")
    say("sharded_sim", f"rank axis sharded over mesh {dict(sharding.mesh.shape)}")
    _sim_pair("sharded_sim", s, dev, p=s.sharded_p, nrep=s.sharded_nrep,
              seed=seed)


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the paths that span four chips")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU, kernels in interpret mode; "
                         "never reports ok")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: the repro package is not next to this script "
              f"({ROOT / 'src' / 'repro'} missing)", file=sys.stderr)
        return 2
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.four_chips:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=4").strip()
    sys.path.insert(0, str(ROOT / "src"))

    import jax

    from repro.core.runtime_meter import use_compile_cache

    cache_dir = use_compile_cache(str(ROOT))
    cache = {"hits": 0, "writes": 0}

    def on_event(event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache["writes"] += 1

    jax.monitoring.register_event_listener(on_event)

    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    if not args.rehearse and dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's first device is "
              f"{dev.platform} {dev.device_kind})", file=sys.stderr)
        return 1
    if args.four_chips and len(devices) < 4:
        print(f"chip_smoke: --four-chips needs 4 devices, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 1
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"jax {jax.__version__}; compile cache {cache_dir}", flush=True)

    s = TINY if args.rehearse else REAL
    if args.four_chips:
        phases = [
            ("collectives4", phase_collectives4),
            ("sharded_sim", phase_sharded_sim),
        ]
    else:
        phases = [
            ("sim", phase_sim),
            ("kernel", functools.partial(phase_kernel,
                                         compiled=not args.rehearse)),
            ("step", phase_step),
            ("collectives", phase_collectives),
        ]
    failed = []
    for name, fn in phases:
        t = time.perf_counter()
        try:
            fn(s, dev, args.seed)
        except Exception:     # report every phase, then fail the run
            traceback.print_exc()
            failed.append(name)
            say(name, f"FAILED after {time.perf_counter() - t:.3f} s")
        else:
            say(name, f"passed in {time.perf_counter() - t:.3f} s")
    print(f"compile cache {cache_dir}: {cache['hits']} hits, "
          f"{cache['writes']} entries written", flush=True)
    if failed:
        print(f"chip_smoke: failed phases: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    if args.rehearse:
        print("rehearsal passed on the CPU; no device result", flush=True)
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

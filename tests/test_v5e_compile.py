"""The main path's device programs, compiled for a described TPU v5e.

Nothing runs here. The TPU compiler is installed with JAX and compiles for
a chip that is described, not attached; it refuses what the chip would
refuse — a block that breaks the (8, 128) tiling, a primitive the Pallas
TPU lowering lacks, an f64 operand in a kernel — which interpret-mode tests
cannot see. Widths are the real ones: flash_attention at mixtral-8x22b's
attention (48 query heads, 8 KV heads, head_dim 128, seq 4096), ssd_scan
at mamba2-1.3b's (64 heads, head_dim 64, state 128, chunk 256, seq 4096),
and the fused simulator at E=4 launch epochs, nrep=1e5, p=64.

The topology is described inside a module fixture, never while a module
is imported: only one process may load the TPU library, and test workers
import every test file.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e, with JAX's persistent compilation cache
    off for the module: a compile for a described chip is written to the
    cache but cannot be read back without the chip."""
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_compiles_for_v5e(one_chip, dtype):
    from repro.kernels.ops import flash_attention

    q = _spec(one_chip, (1, 4096, 48, 128), dtype)
    kv = _spec(one_chip, (1, 4096, 8, 128), dtype)
    fn = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=128, block_k=128, interpret=False))
    text = fn.lower(q, kv, kv).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_compiles_for_v5e(one_chip, dtype):
    from repro.kernels.ops import ssd_scan

    x = _spec(one_chip, (1, 4096, 64, 64), dtype)
    dta = _spec(one_chip, (1, 4096, 64), jnp.float32)
    bc = _spec(one_chip, (1, 4096, 128), dtype)
    fn = jax.jit(lambda x, dta, B, C: ssd_scan(x, dta, B, C, chunk=256,
                                               interpret=False))
    text = fn.lower(x, dta, bc, bc).compile().as_text()
    assert "tpu_custom_call" in text


_E, _NREP, _P = 4, 100_000, 64


def test_fused_sample_program_compiles_for_v5e(one_chip):
    from repro.simjax.engine import _bucket, _jitted_fused, x64

    with x64():
        _, sample_epochs, _ = _jitted_fused()
        f64 = _spec(one_chip, (), jnp.float64)
        per_epoch = _spec(one_chip, (_E,), jnp.float64)
        args = (_spec(one_chip, (_E,), jnp.int64),
                _spec(one_chip, (), jnp.int64), per_epoch, per_epoch,
                f64, f64, f64, f64, f64, f64, _spec(one_chip, (), jnp.int64))
        compiled = sample_epochs.lower(*args, n=_bucket(_NREP)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes > 0


def test_fused_window_program_compiles_for_v5e(one_chip):
    from repro.simjax.engine import _bucket, _chunk_for, _jitted_fused, x64

    n = _bucket(_NREP)
    ch = _chunk_for(_P, n)
    npad = -(-n // ch) * ch
    with x64():
        _, _, window_fused = _jitted_fused()
        f64 = _spec(one_chip, (), jnp.float64)
        ranks = _spec(one_chip, (_P,), jnp.float64)
        args = (_spec(one_chip, (npad,), jnp.float64),
                _spec(one_chip, (2,), jnp.uint32),
                ranks, ranks, ranks, ranks, ranks, ranks, ranks,
                f64, f64, f64, _spec(one_chip, (), jnp.int64))
        compiled = window_fused.lower(*args, ch=ch).compile()
    mem = compiled.memory_analysis()
    assert 0 < mem.temp_size_in_bytes < 16 * 2**30
    assert np.isfinite(mem.argument_size_in_bytes)


def test_fused_lanes_helper_compiles_for_v5e(one_chip):
    """The helper between the fused sample and window programs, for a
    two-term op at a padded size (n=1e5 to npad=100352)."""
    from repro.simjax.engine import _bucket, _chunk_for, _jitted_lanes, x64

    n = _bucket(_NREP)
    ch = _chunk_for(_P, n)
    npad = -(-n // ch) * ch
    assert npad > n
    with x64():
        dur = _spec(one_chip, (_E, n), jnp.float64)
        compiled = _jitted_lanes().lower((dur, dur), npad=npad).compile()
    assert len(compiled.out_info) == _E

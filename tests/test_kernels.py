"""Pallas kernel validation: shape/dtype sweeps vs. the pure-jnp oracles
(interpret mode on CPU) + hypothesis property tests (skipped when the
optional ``hypothesis`` dependency is absent)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.kernels.flash_attention.kernel import flash_attention_fwd
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.ops import flash_attention, ssd_scan
from repro.kernels.ssd_scan.ref import ssd_chunked_ref

RNG = np.random.default_rng(0)


def _t(*shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.normal(0, scale, shape), dtype)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("b,s,h,hkv,d", [
    (2, 256, 4, 2, 64),    # GQA
    (1, 512, 8, 1, 64),    # MQA (granite / gemma-2b pattern)
    (2, 128, 4, 4, 128),   # MHA
    (1, 256, 8, 2, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_shapes_dtypes(b, s, h, hkv, d, dtype):
    q, k, v = _t(b, s, h, d, dtype=dtype), _t(b, s, hkv, d, dtype=dtype), \
        _t(b, s, hkv, d, dtype=dtype)
    out = flash_attention(q, k, v, block_q=128, block_k=128, interpret=True)
    ref = flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("window", [32, 128])
def test_flash_attention_sliding_window(window):
    """Mixtral SWA / gemma local layers."""
    q, k, v = _t(2, 256, 4, 64), _t(2, 256, 2, 64), _t(2, 256, 2, 64)
    out = flash_attention(q, k, v, window=window, block_q=128, block_k=128,
                          interpret=True)
    ref = flash_attention_ref(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_softcap():
    """Gemma-2 logit soft-capping."""
    q, k, v = _t(1, 256, 4, 64, scale=3), _t(1, 256, 4, 64, scale=3), \
        _t(1, 256, 4, 64)
    out = flash_attention(q, k, v, logit_cap=30.0, block_q=128, block_k=128,
                          interpret=True)
    ref = flash_attention_ref(q, k, v, logit_cap=30.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)


def test_flash_attention_decode_offset_kvlen():
    """Static decode: 1 query at position 100 against a 256-slot cache with
    kv_len=101."""
    q = _t(2, 128, 4, 64)
    k, v = _t(2, 256, 2, 64), _t(2, 256, 2, 64)
    out = flash_attention_fwd(
        jnp.transpose(q, (0, 2, 1, 3)), jnp.transpose(k, (0, 2, 1, 3)),
        jnp.transpose(v, (0, 2, 1, 3)), None, causal=True, q_offset=100,
        kv_len=172, block_q=128, block_k=128, interpret=True)
    out = jnp.transpose(out, (0, 2, 1, 3))
    ref = flash_attention_ref(q, k, v, q_offset=100, kv_len=172)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window", [None, 64])
def test_flash_attention_grad_matches_reference(window):
    """The kernel is forward-only; its VJP is the reference's, so a train
    step through it gets the reference's gradients."""
    q, k, v = _t(1, 128, 4, 32), _t(1, 128, 2, 32), _t(1, 128, 2, 32)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    got = jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, window=window, block_q=64, block_k=64, interpret=True)),
        argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda q, k, v: flash_attention_ref(
        q, k, v, window=window)), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_flash_attention_block_must_divide_sequence():
    """A sequence no multiple-of-8 block divides raises: the kernel never
    hands a shape to its reference in silence."""
    q = _t(1, 1001, 2, 32)
    with pytest.raises(ValueError, match="divides"):
        flash_attention(q, q, q, block_q=512, block_k=512, interpret=True)


def test_flash_attention_block_size_invariance():
    q, k, v = _t(1, 512, 4, 64), _t(1, 512, 2, 64), _t(1, 512, 2, 64)
    outs = [flash_attention(q, k, v, block_q=bq, block_k=bk, interpret=True)
            for bq, bk in [(128, 128), (256, 512), (512, 256)]]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   rtol=1e-5, atol=1e-5)


@given(st.integers(1, 3), st.sampled_from([64, 128, 192, 256]),
       st.sampled_from([(4, 2), (8, 1), (4, 4)]),
       st.sampled_from([32, 64]), st.integers(0, 2**31 - 1))
@settings(max_examples=12, deadline=None)
def test_flash_attention_property(b, s, heads, d, seed):
    h, hkv = heads
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(0, 1, (b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (b, s, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (b, s, hkv, d)), jnp.float32)
    out = flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)
    ref = flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)
    # attention output is a convex combination of values
    assert float(jnp.max(jnp.abs(out))) <= float(jnp.max(jnp.abs(v))) + 1e-3


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,s,h,p,n,chunk,hg", [
    (2, 128, 8, 16, 32, 32, 4),
    (1, 256, 16, 32, 64, 64, 8),
    (2, 256, 8, 64, 128, 128, 8),   # mamba2-1.3b-like ratios
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_shapes_dtypes(b, s, h, p, n, chunk, hg, dtype):
    x = _t(b, s, h, p, dtype=dtype)
    dta = -jnp.abs(_t(b, s, h, dtype=jnp.float32)) * 0.1
    B, C = _t(b, s, n, dtype=dtype), _t(b, s, n, dtype=dtype)
    y = ssd_scan(x, dta, B, C, chunk=chunk, head_group=hg, interpret=True)
    yr, _ = ssd_chunked_ref(x, dta, B, C, chunk)
    scale = float(jnp.max(jnp.abs(yr))) + 1e-6
    err = float(jnp.max(jnp.abs(y.astype(jnp.float32) - yr.astype(jnp.float32)))) / scale
    assert err < (3e-2 if dtype == jnp.bfloat16 else 1e-5), err


def test_ssd_matches_sequential_recurrence():
    """The chunked algorithm equals the naive per-step recurrence."""
    b, s, h, p, n = 1, 64, 2, 8, 16
    x = _t(b, s, h, p)
    dta = -jnp.abs(_t(b, s, h)) * 0.2
    B, C = _t(b, s, n), _t(b, s, n)
    y, final = ssd_chunked_ref(x, dta, B, C, 16)
    state = np.zeros((b, h, p, n), np.float32)
    xs = np.asarray(x)
    dts = np.asarray(dta)
    Bs, Cs = np.asarray(B), np.asarray(C)
    y_naive = np.zeros((b, s, h, p), np.float32)
    for t in range(s):
        decay = np.exp(dts[:, t])[:, :, None, None]
        upd = np.einsum("bhp,bn->bhpn", xs[:, t], Bs[:, t])
        state = state * decay + upd
        y_naive[:, t] = np.einsum("bhpn,bn->bhp", state, Cs[:, t])
    np.testing.assert_allclose(np.asarray(y), y_naive, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(final), state, rtol=2e-4, atol=2e-4)


def test_ssm_block_decode_matches_train():
    """ssm_block over a sequence == repeated ssm_decode_step."""
    from repro.models import ModelConfig
    from repro.models.ssm import init_ssm_cache, init_ssm_params, ssm_block, ssm_decode_step

    cfg = ModelConfig(family="ssm", d_model=32, ssm_state=8, ssm_head_dim=8,
                      ssm_chunk=4, dtype="float32")
    params = init_ssm_params(cfg, jax.random.PRNGKey(0))
    x = _t(2, 16, 32) * 0.3
    y_train = ssm_block(cfg, params, x)
    cache = init_ssm_cache(cfg, 2, jnp.float32)
    outs = []
    for t in range(16):
        o, cache = ssm_decode_step(cfg, params, x[:, t:t + 1], cache)
        outs.append(o[:, 0])
    y_dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(y_train), np.asarray(y_dec),
                               rtol=5e-4, atol=5e-4)


# ---------------------------------------------------------------------------
# sim_scan: the simulator's duration sampler (repro.simjax hot path)
# ---------------------------------------------------------------------------

def test_sim_scan_ref_matches_numpy_ar1_filter():
    """The jnp sampler reproduces the numpy engine's _ar1_filter math."""
    from repro.core.mpi_ops import _ar1_filter
    from repro.kernels.sim_scan.ref import sim_durations_ref
    from repro.simjax.engine import x64

    rng = np.random.default_rng(7)
    eps = rng.normal(0.0, 0.04, size=500)
    with x64():
        zeros = jnp.zeros(500, jnp.float64)
        _, s = sim_durations_ref(jnp.asarray(eps), zeros, zeros, zeros,
                                 coeff=0.35, state=0.7, t0=1.0,
                                 tail_prob=0.0, tail_shift=0.0,
                                 spike_prob=0.0, spike_scale=1.0)
    np.testing.assert_allclose(np.asarray(s),
                               _ar1_filter(eps, 0.35, 0.7),
                               rtol=1e-10, atol=1e-14)

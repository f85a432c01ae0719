"""Attention blocks: GQA/MQA, sliding-window + global patterns, soft-capping,
RoPE, MLA (DeepSeek-V2 latent attention), and KV-cache decode paths.

The inner attention product routes through :func:`attention_op`, which
dispatches to the Pallas flash-attention kernel on TPU — a kernel that
fails there raises — and to the pure-jnp reference elsewhere (the dry-run
lowers the jnp path; kernels are validated separately in
``tests/test_kernels.py``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .common import ModelConfig, apply_rope, dense_init, rms_norm, rotary_embedding, softcap

__all__ = [
    "init_attn_params",
    "attention_op",
    "attn_block",
    "attn_decode_step",
    "init_mla_params",
    "mla_block",
    "mla_decode_step",
]


# ---------------------------------------------------------------------------
# Parameter construction
# ---------------------------------------------------------------------------

def init_attn_params(cfg: ModelConfig, key) -> dict:
    hd = cfg.hd
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    dt = cfg.jdtype
    return {
        "wq": dense_init(ks[0], (d, cfg.n_heads * hd), dt),
        "wk": dense_init(ks[1], (d, cfg.n_kv_heads * hd), dt),
        "wv": dense_init(ks[2], (d, cfg.n_kv_heads * hd), dt),
        "wo": dense_init(ks[3], (cfg.n_heads * hd, d), dt),
    }


def init_mla_params(cfg: ModelConfig, key) -> dict:
    """DeepSeek-V2 multi-head latent attention [arXiv:2405.04434]."""
    d, hd, r, rd = cfg.d_model, cfg.hd, cfg.kv_lora_rank, cfg.rope_head_dim
    qr = cfg.q_lora_rank or 0
    nh = cfg.n_heads
    ks = jax.random.split(key, 8)
    dt = cfg.jdtype
    p = {
        # KV path: compress to latent r (+ shared rope key), decompress per head
        "w_dkv": dense_init(ks[0], (d, r + rd), dt),
        "w_uk": dense_init(ks[1], (r, nh * hd), dt),
        "w_uv": dense_init(ks[2], (r, nh * hd), dt),
        "wo": dense_init(ks[3], (nh * hd, d), dt),
        "kv_norm": jnp.zeros((r,), dt),
    }
    if qr:
        p["w_dq"] = dense_init(ks[4], (d, qr), dt)
        p["w_uq"] = dense_init(ks[5], (qr, nh * (hd + rd)), dt)
        p["q_norm"] = jnp.zeros((qr,), dt)
    else:
        p["wq"] = dense_init(ks[6], (d, nh * (hd + rd)), dt)
    return p


# ---------------------------------------------------------------------------
# Core attention op (reference path; Pallas kernel plugs in on TPU)
# ---------------------------------------------------------------------------

def attention_op(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: jnp.ndarray | int | None = None,
    logit_cap: float = 0.0,
    q_offset: jnp.ndarray | int = 0,
    kv_len: jnp.ndarray | None = None,
    impl: str = "auto",
) -> jnp.ndarray:
    """Grouped-query attention.

    q: (B, S, H, Dh); k/v: (B, T, Hkv, Dh). ``window`` may be a traced
    scalar (per-layer local/global selection under scan). ``q_offset`` is
    the absolute position of q[0] (decode). ``kv_len`` masks a padded cache.
    """
    if impl == "auto" and jax.default_backend() == "tpu":
        from repro.kernels import ops as kops

        return kops.flash_attention(
            q, k, v, causal=causal, window=window,
            logit_cap=logit_cap, q_offset=q_offset, kv_len=kv_len,
        )
    return attention_reference(
        q, k, v, causal=causal, window=window, logit_cap=logit_cap,
        q_offset=q_offset, kv_len=kv_len,
    )


Q_CHUNK = 1024  # reference-path query blocking (memory control on long seqs)


def _attention_dense(q, k, v, *, causal, window, logit_cap, q_offset, kv_len):
    from .tuning import get_tuning

    tune = get_tuning()
    b, s, h, dh = q.shape
    t = k.shape[1]
    hkv = k.shape[2]
    g = h // hkv
    qg = q.reshape(b, s, hkv, g, dh)
    scale = 1.0 / np.sqrt(dh)
    logits = jnp.einsum("bskgd,btkd->bkgst", qg, k).astype(jnp.float32) * scale
    if logit_cap and logit_cap > 0:
        logits = softcap(logits, logit_cap)
    qpos = jnp.arange(s) + q_offset          # absolute positions of queries
    kpos = jnp.arange(t)
    mask = jnp.ones((s, t), dtype=bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        w = jnp.asarray(window)
        mask &= (qpos[:, None] - kpos[None, :]) < w
    if kv_len is not None:
        mask &= kpos[None, :] < kv_len
    if tune.attn_additive_mask:
        # additive bias fuses with the preceding scale (one fewer f32 pass)
        logits = logits + jnp.where(mask[None, None, None], 0.0, -1e30)
    else:
        logits = jnp.where(mask[None, None, None], logits, -1e30)
    if tune.attn_probs_bf16:
        m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
        p16 = jnp.exp((logits - m).astype(jnp.bfloat16).astype(jnp.float32))
        p16 = p16.astype(jnp.bfloat16)
        denom = jnp.sum(p16.astype(jnp.float32), axis=-1, keepdims=True)
        probs = (p16.astype(jnp.float32) / denom).astype(q.dtype)
    else:
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(b, s, h, dh)


def attention_reference(q, k, v, *, causal=True, window=None, logit_cap=0.0,
                        q_offset=0, kv_len=None) -> jnp.ndarray:
    """Reference attention, blocked over query chunks for long sequences.

    The score tensor is O(chunk * T) instead of O(S * T); each chunk body is
    checkpointed so the backward pass rematerializes probabilities chunk by
    chunk (the jnp analogue of the Pallas flash kernel's memory behavior).
    """
    b, s, h, dh = q.shape
    if s <= Q_CHUNK or s % Q_CHUNK != 0:
        return _attention_dense(q, k, v, causal=causal, window=window,
                                logit_cap=logit_cap, q_offset=q_offset,
                                kv_len=kv_len)
    nchunk = s // Q_CHUNK
    qc = q.reshape(b, nchunk, Q_CHUNK, h, dh)

    @jax.checkpoint
    def chunk(carry, inp):
        qi, i = inp
        out = _attention_dense(qi, k, v, causal=causal, window=window,
                               logit_cap=logit_cap,
                               q_offset=q_offset + i * Q_CHUNK, kv_len=kv_len)
        return carry, out

    _, out = jax.lax.scan(chunk, 0,
                          (jnp.moveaxis(qc, 1, 0), jnp.arange(nchunk)))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, dh)


# ---------------------------------------------------------------------------
# Full blocks (project -> rope -> attend -> output)
# ---------------------------------------------------------------------------

def _project_qkv(cfg: ModelConfig, p: dict, x: jnp.ndarray):
    b, s, d = x.shape
    hd = cfg.hd
    q = (x @ p["wq"]).reshape(b, s, cfg.n_heads, hd)
    k = (x @ p["wk"]).reshape(b, s, cfg.n_kv_heads, hd)
    v = (x @ p["wv"]).reshape(b, s, cfg.n_kv_heads, hd)
    return q, k, v


def attn_block(cfg: ModelConfig, p: dict, x: jnp.ndarray, *,
               is_global=None, positions=None, kv: jnp.ndarray | None = None,
               causal: bool = True) -> jnp.ndarray:
    """Self-attention (kv=None) or cross-attention (kv=encoder memory).

    ``is_global``: traced bool scalar choosing full vs sliding-window
    attention for this layer (the gemma-2/3 alternation under scan).
    """
    b, s, d = x.shape
    hd = cfg.hd
    if kv is None:
        q, k, v = _project_qkv(cfg, p, x)
        if positions is None:
            positions = jnp.arange(s)[None, :]
        cos, sin = rotary_embedding(positions, hd, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        window = None
        if cfg.window is not None:
            if is_global is None:
                window = cfg.window
            else:
                window = jnp.where(jnp.asarray(is_global), jnp.int32(2**30),
                                   jnp.int32(cfg.window))
        out = attention_op(q, k, v, causal=causal, window=window,
                           logit_cap=cfg.attn_softcap)
    else:
        t = kv.shape[1]
        q = (x @ p["wq"]).reshape(b, s, cfg.n_heads, hd)
        k = (kv @ p["wk"]).reshape(b, t, cfg.n_kv_heads, hd)
        v = (kv @ p["wv"]).reshape(b, t, cfg.n_kv_heads, hd)
        out = attention_op(q, k, v, causal=False, logit_cap=cfg.attn_softcap)
    return out.reshape(b, s, cfg.n_heads * hd) @ p["wo"]


def attn_decode_step(cfg: ModelConfig, p: dict, x: jnp.ndarray,
                     cache_k: jnp.ndarray, cache_v: jnp.ndarray,
                     pos: jnp.ndarray, *, is_global=None):
    """One-token decode with an in-place KV cache update.

    x: (B, 1, D); cache_k/v: (B, T, Hkv, Dh); pos: scalar current position.
    Returns (out (B,1,D), new_cache_k, new_cache_v).
    """
    b, s, d = x.shape
    hd = cfg.hd
    q, k, v = _project_qkv(cfg, p, x)
    positions = jnp.full((b, 1), pos)
    cos, sin = rotary_embedding(positions, hd, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    cache_k = jax.lax.dynamic_update_slice(cache_k, k.astype(cache_k.dtype),
                                           (0, pos, 0, 0))
    cache_v = jax.lax.dynamic_update_slice(cache_v, v.astype(cache_v.dtype),
                                           (0, pos, 0, 0))
    window = None
    if cfg.window is not None:
        if is_global is None:
            window = cfg.window
        else:
            window = jnp.where(jnp.asarray(is_global), jnp.int32(2**30),
                               jnp.int32(cfg.window))
    out = attention_op(q, cache_k, cache_v, causal=False, window=window,
                       logit_cap=cfg.attn_softcap, q_offset=pos,
                       kv_len=pos + 1)
    out = out.reshape(b, s, cfg.n_heads * hd) @ p["wo"]
    return out, cache_k, cache_v


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2)
# ---------------------------------------------------------------------------

def _mla_q(cfg: ModelConfig, p: dict, x: jnp.ndarray):
    b, s, _ = x.shape
    nh, hd, rd = cfg.n_heads, cfg.hd, cfg.rope_head_dim
    if "w_dq" in p:
        cq = rms_norm(x @ p["w_dq"], p["q_norm"])
        q = (cq @ p["w_uq"]).reshape(b, s, nh, hd + rd)
    else:
        q = (x @ p["wq"]).reshape(b, s, nh, hd + rd)
    return q[..., :hd], q[..., hd:]


def mla_block(cfg: ModelConfig, p: dict, x: jnp.ndarray,
              positions=None) -> jnp.ndarray:
    """Prefill/train path. The latent cache formulation is exercised in the
    decode path; here keys/values are decompressed in full (standard)."""
    b, s, d = x.shape
    nh, hd, r, rd = cfg.n_heads, cfg.hd, cfg.kv_lora_rank, cfg.rope_head_dim
    q_nope, q_rope = _mla_q(cfg, p, x)
    dkv = x @ p["w_dkv"]                       # (b, s, r + rd)
    c_kv = rms_norm(dkv[..., :r], p["kv_norm"])
    k_rope = dkv[..., r:].reshape(b, s, 1, rd)
    if positions is None:
        positions = jnp.arange(s)[None, :]
    cos, sin = rotary_embedding(positions, rd, cfg.rope_theta)
    q_rope = apply_rope(q_rope, cos, sin)
    k_rope = apply_rope(k_rope, cos, sin)
    k_nope = (c_kv @ p["w_uk"]).reshape(b, s, nh, hd)
    v = (c_kv @ p["w_uv"]).reshape(b, s, nh, hd)
    # Concatenate nope|rope components; rope key shared across heads (MQA-like)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (b, s, nh, rd))], axis=-1)
    # pad v to q's feature dim for the shared attention op, then slice back
    out = attention_op(q, k, jnp.concatenate(
        [v, jnp.zeros((b, s, nh, rd), v.dtype)], axis=-1), causal=True)
    out = out[..., :hd]
    return out.reshape(b, s, nh * hd) @ p["wo"]


def mla_decode_step(cfg: ModelConfig, p: dict, x: jnp.ndarray,
                    cache_ckv: jnp.ndarray, cache_krope: jnp.ndarray,
                    pos: jnp.ndarray):
    """Latent-cache decode: cache stores (c_kv, k_rope) only — the memory
    advantage of MLA. Keys/values are decompressed against the cache."""
    b, s, d = x.shape
    nh, hd, r, rd = cfg.n_heads, cfg.hd, cfg.kv_lora_rank, cfg.rope_head_dim
    q_nope, q_rope = _mla_q(cfg, p, x)
    dkv = x @ p["w_dkv"]
    c_kv = rms_norm(dkv[..., :r], p["kv_norm"])      # (b, 1, r)
    k_rope = dkv[..., r:].reshape(b, 1, 1, rd)
    positions = jnp.full((b, 1), pos)
    cos, sin = rotary_embedding(positions, rd, cfg.rope_theta)
    q_rope = apply_rope(q_rope, cos, sin)
    k_rope = apply_rope(k_rope, cos, sin)
    cache_ckv = jax.lax.dynamic_update_slice(
        cache_ckv, c_kv.astype(cache_ckv.dtype), (0, pos, 0))
    cache_krope = jax.lax.dynamic_update_slice(
        cache_krope, k_rope[:, :, 0].astype(cache_krope.dtype), (0, pos, 0))
    t = cache_ckv.shape[1]
    k_nope = (cache_ckv @ p["w_uk"]).reshape(b, t, nh, hd)
    v = (cache_ckv @ p["w_uv"]).reshape(b, t, nh, hd)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(cache_krope[:, :, None, :], (b, t, nh, rd))],
        axis=-1)
    out = attention_op(
        q, k, jnp.concatenate([v, jnp.zeros((b, t, nh, rd), v.dtype)], axis=-1),
        causal=False, q_offset=pos, kv_len=pos + 1)
    out = out[..., :hd]
    out = out.reshape(b, s, nh * hd) @ p["wo"]
    return out, cache_ckv, cache_krope

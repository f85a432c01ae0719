"""Operations and bytes that a step needs, computed from its shapes alone.

The count is the same whatever implements the step: it is what the
mathematics of the model requires, not what a compiled program happens to
execute. A multiply-add is two operations.
"""

from __future__ import annotations

CONV = 4


def mamba2_decode(cfg, batch: int) -> dict:
    """One decode step of a Mamba-2 language model at ``batch`` rows.

    FLOPs: the per-layer projections (``z``, ``x``, ``B``, ``C``, ``dt`` in,
    the output projection), the width-4 convolutions, the state update
    (``state * decay + dt x B``) and readout (``state . C``), and the tied
    output head. Norms, activations and the embedding gather are left
    out (under 0.1% here).

    Bytes: every weight read once (the embedding table once, as the
    head), the recurrent state and convolution windows read and written,
    the logits written, all in the served dtype.
    """
    d, L, V = cfg.d_model, cfg.n_layers, cfg.vocab_size
    di = cfg.ssm_expand * d
    hd, n = cfg.ssm_head_dim, cfg.ssm_state
    nh = di // hd
    itemsize = 2 if cfg.dtype == "bfloat16" else 4
    proj = d * (2 * di + 2 * n + nh) + di * d
    conv_w = CONV * (di + 2 * n)
    state = nh * hd * n
    flops_layer = batch * (2 * proj + 2 * conv_w + 6 * state)
    flops = L * flops_layer + 2 * batch * d * V
    # weights: projections and convs in the served dtype; per-head vectors
    # (a_log, d_skip, dt_bias) in float32; norms in the served dtype
    w_layer = (proj + conv_w + di + d) * itemsize + 3 * nh * 4
    weights = L * w_layer + (V * d + d) * itemsize
    state_rw = 2 * L * batch * (state + (CONV - 1) * (di + 2 * n)) * itemsize
    logits = batch * V * itemsize
    return {"flops": float(flops), "bytes": float(weights + state_rw + logits),
            "weight_bytes": float(weights), "state_bytes": float(state_rw)}

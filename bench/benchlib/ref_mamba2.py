"""Plain float32 reference of a Mamba-2 language model's forward pass,
and the random weights that both the program and the reference are fed.

Written from the published equations (Dao and Gu, arXiv:2405.21060) in
the parameterisation the model stores: separate projections for the gate
``z``, the input ``x``, ``B``, ``C`` and ``dt``; a width-4 causal depthwise
convolution with SiLU on ``x``, ``B`` and ``C``; ``dt = softplus(h W_dt +
dt_bias)``, ``A = -exp(a_log)`` per head; one group of ``B``/``C``. Per
head, the state-space output is the quadratic (attention-like) form of
the scan,

    y_t = sum_{s <= t} (C_t . B_s) exp(sum_{s < r <= t} dt_r A) dt_s x_s + D x_t,

then ``y * silu(z)`` through an RMS norm with scale ``1 + g`` and the
output projection. Blocks are pre-norm residual (RMS norm, eps 1e-6);
a final RMS norm and the tied embedding give the logits. Every matrix
product runs at ``precision="highest"``. After ``L`` tokens a layer's
recurrent state is ``S = sum_s exp(sum_{s < r <= L} dt_r A) dt_s x_s B_s^T``
per head, and its convolution windows hold the last three inputs of each
convolution.

``quant="fp8"`` is the control: every matrix product's operands rounded to
float8 (e4m3), one precision step below the model's bfloat16.

Nothing here imports the program; the parameters are a dictionary of
arrays in its layout (``segments[0]`` stacks the layers).
"""

from __future__ import annotations

import functools

import numpy as np

CONV = 4
EPS = 1e-6


def init_params(seed: int, *, d_model: int, n_layers: int, vocab: int,
                d_state: int, head_dim: int, expand: int, dtype: str):
    """Random weights in the model's layout, made on the device in one
    jitted call from ``seed``, in the served ``dtype`` (the per-head
    ``a_log``, ``d_skip``, ``dt_bias`` in float32, as the model keeps
    them). Projections are normal with variance 1 / fan-in, the embedding
    standard normal, convolutions normal with deviation 0.1; ``A`` per
    head uniform on [1, 16] (the published range), ``D = 1``,
    ``dt_bias = 0``, norm scales ``1 + 0``."""
    import jax
    import jax.numpy as jnp

    d, L, n = d_model, n_layers, d_state
    di = expand * d
    nh = di // head_dim
    dt = jnp.dtype(dtype)

    def make(key):
        ks = iter(jax.random.split(key, 16))

        def dense(shape):
            return (jax.random.normal(next(ks), (L,) + shape, jnp.float32)
                    / np.sqrt(shape[0])).astype(dt)

        def conv(dim):
            return (0.1 * jax.random.normal(next(ks), (L, CONV, dim),
                                            jnp.float32)).astype(dt)

        ssm = {"w_z": dense((d, di)), "w_x": dense((d, di)),
               "w_b": dense((d, n)), "w_c": dense((d, n)),
               "w_dt": dense((d, nh)), "conv_x": conv(di), "conv_b": conv(n),
               "conv_c": conv(n),
               "a_log": jnp.log(jax.random.uniform(next(ks), (L, nh),
                                                   jnp.float32, 1.0, 16.0)),
               "d_skip": jnp.ones((L, nh), jnp.float32),
               "dt_bias": jnp.zeros((L, nh), jnp.float32),
               "norm": jnp.zeros((L, di), dt), "out_proj": dense((di, d))}
        embed = jax.random.normal(next(ks), (vocab, d), jnp.float32)
        return {"embed": embed.astype(dt), "final_norm": jnp.zeros((d,), dt),
                "segments": [{"norm1": jnp.zeros((L, d), dt), "ssm": ssm}]}

    return jax.jit(make)(jax.random.PRNGKey(int(seed) % 2**31))


def _q(a, quant):
    import jax.numpy as jnp

    if quant == "fp8":
        return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return a


def _mm(a, b, quant):
    import jax.numpy as jnp

    return jnp.matmul(_q(a, quant), _q(b, quant), precision="highest")


def _rms(x, g):
    import jax.numpy as jnp

    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + EPS) * (1.0 + g.astype(jnp.float32))


def _conv_silu(x, w):
    """Causal depthwise conv over the sequence axis: out_t = sum_i
    w_i x_{t-3+i}, then SiLU."""
    import jax
    import jax.numpy as jnp

    L = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (CONV - 1, 0), (0, 0)))
    out = sum(xp[:, i:i + L] * w[i].astype(jnp.float32) for i in range(CONV))
    return jax.nn.silu(out)


def _layer(x, p, *, n_heads, head_dim, quant):
    """The layer's output, and its state and convolution windows after
    the last token."""
    import jax
    import jax.numpy as jnp

    f32 = lambda a: a.astype(jnp.float32)   # noqa: E731
    q = p["ssm"]
    h = _rms(x, p["norm1"])
    z = _mm(h, f32(q["w_z"]), quant)
    raw = {k: _mm(h, f32(q[f"w_{k}"]), quant) for k in ("x", "b", "c")}
    xs = _conv_silu(raw["x"], q["conv_x"])
    B = _conv_silu(raw["b"], q["conv_b"])
    C = _conv_silu(raw["c"], q["conv_c"])
    dt = jax.nn.softplus(_mm(h, f32(q["w_dt"]), quant) + f32(q["dt_bias"]))
    a = -jnp.exp(f32(q["a_log"]))                       # (H,)
    b, L, _ = x.shape
    xh = xs.reshape(b, L, n_heads, head_dim)
    cum = jnp.cumsum(dt * a, axis=1)                    # (b, L, H)
    seg = cum[:, :, None, :] - cum[:, None, :, :]       # (b, t, s, H)
    causal = jnp.tril(jnp.ones((L, L), bool))[None, :, :, None]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    G = _mm(C, jnp.swapaxes(B, 1, 2), quant)            # (b, t, s)
    M = G[..., None] * decay                            # (b, t, s, H)
    xdt = xh * dt[..., None]
    y = jnp.einsum("btsh,bshp->bthp", M, xdt, precision="highest")
    y = y + xh * f32(q["d_skip"])[None, None, :, None]
    y = y.reshape(b, L, n_heads * head_dim)
    y = _rms(y * jax.nn.silu(z), q["norm"])
    w = jnp.exp(cum[:, -1:] - cum) * dt                 # (b, L, H)
    state = jnp.einsum("bsh,bshp,bsn->bhpn", _q(w, quant), _q(xh, quant),
                       _q(B, quant), precision="highest")
    windows = {f"conv_{k}": v[:, -(CONV - 1):] for k, v in raw.items()}
    return x + _mm(y, f32(q["out_proj"]), quant), {"state": state, **windows}


@functools.lru_cache(maxsize=4)
def _forward_last(n_heads: int, head_dim: int, n_kept: int, quant):
    import jax
    import jax.numpy as jnp

    def layer(x, p):
        return _layer(x, p, n_heads=n_heads, head_dim=head_dim, quant=quant)

    def fwd(params, tokens, slot):
        """``slot[l]``: where layer ``l``'s state goes among the ``n_kept``
        kept layers (-1: not kept)."""
        embed = params["embed"].astype(jnp.float32)
        x = embed[tokens]
        layers = params["segments"][0]

        def body(carry, inp):
            x, kept = carry
            p, s = inp
            x, st = layer(x, p)
            i = jnp.maximum(s, 0)
            kept = jax.tree.map(lambda buf, new: buf.at[i].set(
                jnp.where(s >= 0, new, buf[i])), kept, st)
            return (x, kept), None

        one = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape[1:],
                                                          a.dtype), layers)
        shapes = jax.eval_shape(layer, jax.ShapeDtypeStruct(x.shape,
                                                            jnp.float32), one)
        kept = jax.tree.map(lambda a: jnp.zeros((n_kept,) + a.shape,
                                                jnp.float32), shapes[1])
        (x, kept), _ = jax.lax.scan(body, (x, kept), (layers, slot))
        x = _rms(x[:, -1], params["final_norm"])
        return _mm(x, embed.T, quant), kept

    return jax.jit(fwd)


def last_step(params, tokens, *, n_heads: int, head_dim: int, rows: int,
              layers, quant=None):
    """Logits at the last position of each row of ``tokens`` (B, L), and
    the state and convolution windows of the given ``layers`` after the
    last token (each ``(len(layers), B, ...)``), computed ``rows`` rows at
    a time."""
    import jax

    n_layers = jax.tree.leaves(params["segments"][0])[0].shape[0]
    slot = np.full(n_layers, -1, np.int32)
    slot[list(layers)] = np.arange(len(layers), dtype=np.int32)
    fwd = _forward_last(n_heads, head_dim, len(layers), quant)
    logits, kept = [], []
    for i in range(0, tokens.shape[0], rows):
        lg, kp = fwd(params, tokens[i:i + rows], slot)
        logits.append(np.asarray(lg, np.float32))
        kept.append({k: np.asarray(v, np.float32) for k, v in kp.items()})
    states = {k: np.concatenate([kp[k] for kp in kept], axis=1)
              for k in kept[0]}
    return np.concatenate(logits), states


def rel_l2(got, want) -> float:
    """Relative L2 distance of ``got`` from ``want`` (inf where the shapes
    differ)."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        return float("inf")
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def compare(got: np.ndarray, want: np.ndarray) -> dict:
    """``rel_l2``: relative L2 distance of all logits; ``top_gap``: the
    widest gap by which the token that ``got`` ranks first lies below the
    reference's best, in units of the reference's logit spread per row."""
    rel = rel_l2(got, want)
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    pick = got.argmax(axis=-1)
    best = want.max(axis=-1)
    chosen = np.take_along_axis(want, pick[:, None], axis=-1)[:, 0]
    gap = float(np.max((best - chosen) / want.std(axis=-1)))
    return {"rel_l2": rel, "top_gap": gap}

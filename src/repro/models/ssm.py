"""Mamba-2 SSD (state-space duality) mixer.

Used by ``mamba2-2.7b`` (every layer) and ``jamba-v0.1-52b`` (7 of 8 layers).
Training/prefill uses the chunked SSD algorithm (intra-chunk quadratic +
inter-chunk state recurrence); decode uses the O(1) recurrent update.
The intra-chunk matmuls route through the Pallas ``ssd_scan`` kernel on TPU
(pure-jnp reference elsewhere) via ``kernels.ops``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import _dense_init

SSM_HEAD_DIM = 64
CONV_WIDTH = 4


def ssm_dims(cfg: ModelConfig) -> tuple[int, int, int, int]:
    """(d_inner, nheads, d_state, conv_channels)."""
    d_inner = cfg.ssm_expand * cfg.d_model
    nheads = d_inner // SSM_HEAD_DIM
    d_state = cfg.ssm_state
    conv_ch = d_inner + 2 * d_state
    return d_inner, nheads, d_state, conv_ch


def ssm_init(rng, cfg: ModelConfig, dtype) -> tuple[dict, dict]:
    d = cfg.d_model
    i, h, n, conv_ch = ssm_dims(cfg)
    keys = jax.random.split(rng, 8)
    params = {
        "wz": _dense_init(keys[0], (d, i), dtype),
        "wx": _dense_init(keys[1], (d, i), dtype),
        "wB": _dense_init(keys[2], (d, n), dtype),
        "wC": _dense_init(keys[3], (d, n), dtype),
        "wdt": _dense_init(keys[4], (d, h), dtype),
        "dt_bias": jnp.log(jnp.expm1(jnp.full((h,), 0.01, jnp.float32))).astype(dtype),
        "A_log": jnp.log(
            jax.random.uniform(keys[5], (h,), jnp.float32, 1.0, 16.0)
        ).astype(dtype),
        "D": jnp.ones((h,), dtype),
        "conv_w": _dense_init(keys[6], (CONV_WIDTH, conv_ch), dtype),
        "conv_b": jnp.zeros((conv_ch,), dtype),
        "norm_scale": jnp.ones((i,), dtype),
        "wo": _dense_init(keys[7], (i, d), dtype),
    }
    axes = {
        "wz": ("embed", "inner"),
        "wx": ("embed", "inner"),
        "wB": ("embed", "state"),
        "wC": ("embed", "state"),
        "wdt": ("embed", "ssm_heads"),
        "dt_bias": ("ssm_heads",),
        "A_log": ("ssm_heads",),
        "D": ("ssm_heads",),
        "conv_w": ("conv_k", "inner"),
        "conv_b": ("inner",),
        "norm_scale": ("inner",),
        "wo": ("inner", "embed"),
    }
    return params, axes


def _causal_conv(xbc: jax.Array, w: jax.Array, b: jax.Array | None = None) -> jax.Array:
    """Depthwise causal conv, xbc: (b, s, c), w: (K, c); out[t] is the sum
    over taps i of w[i] * xbc[t - (K - 1) + i]."""
    K = w.shape[0]
    pad = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    out = jnp.zeros_like(xbc, dtype=jnp.float32)
    for t in range(K):
        out = out + pad[:, t : t + xbc.shape[1], :].astype(jnp.float32) * w[t].astype(
            jnp.float32
        )
    if b is not None:
        out = out + b.astype(jnp.float32)
    return out.astype(xbc.dtype)


def _gated_norm(y: jax.Array, z: jax.Array, scale: jax.Array, eps=1e-6) -> jax.Array:
    yf = (y * jax.nn.silu(z.astype(jnp.float32))).astype(jnp.float32)
    var = jnp.mean(jnp.square(yf), axis=-1, keepdims=True)
    return (yf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(y.dtype)


def _pre_ssd(params, cfg, x):
    """Shared projections+conv for forward/decode. x: (b,s,d)."""
    z = x @ params["wz"].astype(x.dtype)
    xi = x @ params["wx"].astype(x.dtype)
    Bssm = x @ params["wB"].astype(x.dtype)
    Cssm = x @ params["wC"].astype(x.dtype)
    dt_raw = x @ params["wdt"].astype(x.dtype)
    xbc = jnp.concatenate([xi, Bssm, Cssm], axis=-1)
    return z, xbc, dt_raw


def _post_conv_split(cfg, xbc):
    i, h, n, _ = ssm_dims(cfg)
    xbc = jax.nn.silu(xbc.astype(jnp.float32)).astype(xbc.dtype)
    xi, Bssm, Cssm = jnp.split(xbc, [i, i + n], axis=-1)
    return xi, Bssm, Cssm


def ssm_forward(
    params: dict,
    cfg: ModelConfig,
    x: jax.Array,
    return_state: bool = False,
    init_state: dict | None = None,
):
    """Full-sequence SSD forward. x: (b, s, d) -> (b, s, d).

    ``init_state`` ({"ssd", "conv"}) continues from a previous chunk
    (chunked prefill): the conv uses the cached raw history instead of zero
    padding and the SSD recurrence starts from the carried state.
    """
    from repro.kernels import ops

    b, s, d = x.shape
    i, h, n, _ = ssm_dims(cfg)
    p = SSM_HEAD_DIM
    z, xbc_raw, dt_raw = _pre_ssd(params, cfg, x)
    if init_state is not None:
        hist = init_state["conv"].astype(xbc_raw.dtype)
        full = jnp.concatenate([hist, xbc_raw], axis=1)
        xbc = _causal_conv(full, params["conv_w"], params["conv_b"])[
            :, CONV_WIDTH - 1 :
        ]
        xbc_hist_src = full
    else:
        xbc = _causal_conv(xbc_raw, params["conv_w"], params["conv_b"])
        xbc_hist_src = xbc_raw
    xi, Bssm, Cssm = _post_conv_split(cfg, xbc)

    dt = jax.nn.softplus(
        dt_raw.astype(jnp.float32) + params["dt_bias"].astype(jnp.float32)
    )  # (b,s,h)
    A = -jnp.exp(params["A_log"].astype(jnp.float32))  # (h,)
    xh = xi.reshape(b, s, h, p)
    y, final_state = ops.ssd_scan(
        xh,
        dt,
        A,
        Bssm.astype(jnp.float32),
        Cssm.astype(jnp.float32),
        cfg.ssm_chunk,
        init_state=init_state["ssd"].astype(jnp.float32) if init_state else None,
    )
    y = y + xh.astype(jnp.float32) * params["D"].astype(jnp.float32)[None, None, :, None]
    y = y.reshape(b, s, i).astype(x.dtype)
    y = _gated_norm(y, z, params["norm_scale"])
    out = y @ params["wo"].astype(x.dtype)
    if return_state:
        state = {
            "ssd": final_state,
            "conv": xbc_hist_src[:, -(CONV_WIDTH - 1) :, :].astype(jnp.float32),
        }
        return out, state
    return out


# ---------------------------------------------------------------------------
# Decode (recurrent, O(1) per token)
# ---------------------------------------------------------------------------


def ssm_init_state(cfg: ModelConfig, batch: int, dtype=jnp.float32):
    i, h, n, conv_ch = ssm_dims(cfg)
    return {
        "ssd": jnp.zeros((batch, h, SSM_HEAD_DIM, n), dtype),
        "conv": jnp.zeros((batch, CONV_WIDTH - 1, conv_ch), dtype),
    }


def ssm_decode(params: dict, cfg: ModelConfig, x: jax.Array, state: dict):
    """x: (b, 1, d). Returns (y, new_state)."""
    b = x.shape[0]
    i, h, n, conv_ch = ssm_dims(cfg)
    p = SSM_HEAD_DIM
    z, xbc, dt_raw = _pre_ssd(params, cfg, x)  # (b,1,*)
    # conv with cached history
    hist = jnp.concatenate([state["conv"], xbc.astype(state["conv"].dtype)], axis=1)
    w = params["conv_w"].astype(jnp.float32)
    conv_out = (hist.astype(jnp.float32) * w[None]).sum(axis=1, keepdims=True) + params[
        "conv_b"
    ].astype(jnp.float32)
    xi, Bssm, Cssm = _post_conv_split(cfg, conv_out.astype(x.dtype))
    new_conv = hist[:, 1:, :]

    dt = jax.nn.softplus(
        dt_raw[:, 0].astype(jnp.float32) + params["dt_bias"].astype(jnp.float32)
    )  # (b,h)
    A = -jnp.exp(params["A_log"].astype(jnp.float32))
    decay = jnp.exp(dt * A[None, :])  # (b,h)
    xh = xi[:, 0].reshape(b, h, p).astype(jnp.float32)
    Bv = Bssm[:, 0].astype(jnp.float32)  # (b,n)
    Cv = Cssm[:, 0].astype(jnp.float32)
    ssd = state["ssd"].astype(jnp.float32)
    ssd = decay[:, :, None, None] * ssd + (dt[:, :, None, None] * xh[..., None]) * Bv[
        :, None, None, :
    ]
    y = jnp.einsum("bhpn,bn->bhp", ssd, Cv) + xh * params["D"].astype(jnp.float32)[
        None, :, None
    ]
    y = y.reshape(b, 1, i).astype(x.dtype)
    y = _gated_norm(y, z, params["norm_scale"])
    out = y @ params["wo"].astype(x.dtype)
    return out, {"ssd": ssd.astype(state["ssd"].dtype), "conv": new_conv}

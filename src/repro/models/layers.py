"""Primitive layers: norms, RoPE, dense MLPs, embeddings.

Parameters are plain dicts; every constructor returns ``(params, axes)``
where ``axes`` mirrors the param tree with tuples of *logical axis names*
(consumed by distribution.sharding and by the Abstract Resource View).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig

# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def _dense_init(rng, shape, dtype, in_axis=0):
    fan_in = shape[in_axis]
    scale = 1.0 / np.sqrt(fan_in)
    return jax.random.normal(rng, shape, dtype) * jnp.asarray(scale, dtype)


def _embed_init(rng, shape, dtype):
    return jax.random.normal(rng, shape, dtype) * jnp.asarray(0.02, dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm_init(d: int, dtype) -> tuple[dict, dict]:
    return {"scale": jnp.ones((d,), dtype)}, {"scale": ("embed",)}


def rmsnorm_apply(params: dict, x: jax.Array, eps: float) -> jax.Array:
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(dtype)


def head_rmsnorm_apply(scale: jax.Array, x: jax.Array, eps: float) -> jax.Array:
    """qk-norm: normalize over the trailing head_dim."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: (..., seq) int32."""
    head_dim = x.shape[-1]
    freqs = rope_freqs(head_dim, theta)  # (half,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., seq, half)
    cos = jnp.cos(angles)[..., :, None, :]  # (..., seq, 1, half)
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GeGLU)
# ---------------------------------------------------------------------------


def mlp_init(rng, cfg: ModelConfig, dtype) -> tuple[dict, dict]:
    k1, k2, k3 = jax.random.split(rng, 3)
    d, f = cfg.d_model, cfg.d_ff
    params = {
        "wi_gate": _dense_init(k1, (d, f), dtype),
        "wi_up": _dense_init(k2, (d, f), dtype),
        "wo": _dense_init(k3, (f, d), dtype, in_axis=0),
    }
    axes = {
        "wi_gate": ("embed", "ffn"),
        "wi_up": ("embed", "ffn"),
        "wo": ("ffn", "embed"),
    }
    return params, axes


def _act(name: str, x: jax.Array) -> jax.Array:
    if name == "gelu":
        return jax.nn.gelu(x, approximate=True)
    return jax.nn.silu(x)


def mlp_apply(params: dict, x: jax.Array, act: str) -> jax.Array:
    gate = _act(act, x @ params["wi_gate"].astype(x.dtype))
    up = x @ params["wi_up"].astype(x.dtype)
    return (gate * up) @ params["wo"].astype(x.dtype)


# ---------------------------------------------------------------------------
# Embedding / LM head
# ---------------------------------------------------------------------------


def embed_init(rng, cfg: ModelConfig, dtype) -> tuple[dict, dict]:
    params = {"tok": _embed_init(rng, (cfg.vocab_size, cfg.d_model), dtype)}
    axes = {"tok": ("vocab", "embed")}
    return params, axes


def embed_apply(params: dict, tokens: jax.Array, dtype) -> jax.Array:
    return params["tok"].astype(dtype)[tokens]


def lm_head_init(rng, cfg: ModelConfig, dtype) -> tuple[dict, dict]:
    params = {"w": _dense_init(rng, (cfg.d_model, cfg.vocab_size), dtype)}
    axes = {"w": ("embed", "vocab")}
    return params, axes


def lm_head_apply(params: dict | None, embed_params: dict, x: jax.Array) -> jax.Array:
    if params is None:  # tied embeddings
        return x @ embed_params["tok"].astype(x.dtype).T
    return x @ params["w"].astype(x.dtype)

"""Gated short convolution mixer (LFM2's ``conv`` layers).

``B, C, x = split3(in_proj(h))``, then ``out_proj(C * conv(B * x))`` with
``conv`` a causal depthwise convolution of ``conv_kernel`` taps and no bias.
The three input projections are kept as separate matrices so that each
shards over its output channels like any other (the convolution is per
channel).
"""

from __future__ import annotations

import jax

from repro.configs.base import ModelConfig
from repro.models.layers import _dense_init
from repro.models.ssm import _causal_conv


def short_conv_init(rng, cfg: ModelConfig, dtype) -> tuple[dict, dict]:
    d, taps = cfg.d_model, cfg.conv_kernel
    keys = jax.random.split(rng, 5)
    params = {
        "wB": _dense_init(keys[0], (d, d), dtype),
        "wC": _dense_init(keys[1], (d, d), dtype),
        "wx": _dense_init(keys[2], (d, d), dtype),
        "conv_w": _dense_init(keys[3], (taps, d), dtype),
        "wo": _dense_init(keys[4], (d, d), dtype),
    }
    axes = {
        "wB": ("embed", "inner"),
        "wC": ("embed", "inner"),
        "wx": ("embed", "inner"),
        "conv_w": ("conv_k", "inner"),
        "wo": ("inner", "embed"),
    }
    return params, axes


def short_conv_apply(params: dict, x: jax.Array) -> jax.Array:
    """x: (b, s, d) -> (b, s, d); position t sees inputs t - taps + 1 .. t."""
    b_gate = x @ params["wB"].astype(x.dtype)
    c_gate = x @ params["wC"].astype(x.dtype)
    xx = x @ params["wx"].astype(x.dtype)
    conv = _causal_conv(b_gate * xx, params["conv_w"])
    return (c_gate * conv) @ params["wo"].astype(x.dtype)

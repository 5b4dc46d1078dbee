"""Decode-time caches: KV ring buffers (full / sliding-window attention),
SSD recurrent states (Mamba-2), and cross-attention KV for enc-dec.

Cache capacity: full attention => ``max_seq``; sliding window => ``min(max_seq,
window)`` (ring buffer, see attention.attn_decode). Cache leaves are stacked
over ``n_periods`` (leading axis) so the decode scan threads them as xs/ys.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import ssm as ssm_mod
from repro.models.transformer import block_program, n_periods, require_one_stack


def cache_capacity(cfg: ModelConfig, max_seq: int) -> int:
    if cfg.sliding_window > 0:
        return min(max_seq, cfg.sliding_window)
    return max_seq


def init_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype=jnp.bfloat16):
    """Zero cache pytree (real or under jax.eval_shape for abstract)."""
    require_one_stack(cfg, "the decode cache")
    prog = block_program(cfg)
    np_ = n_periods(cfg)
    kh, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    T = cache_capacity(cfg, max_seq)
    cache = {}
    for j, (mixer, _) in enumerate(prog):
        if mixer == "attn":
            cache[f"pos{j}"] = {
                "k": jnp.zeros((np_, batch, T, kh, hd), dtype),
                "v": jnp.zeros((np_, batch, T, kh, hd), dtype),
            }
        else:
            i, h, n, conv_ch = ssm_mod.ssm_dims(cfg)
            cache[f"pos{j}"] = {
                "ssd": jnp.zeros((np_, batch, h, ssm_mod.SSM_HEAD_DIM, n), jnp.float32),
                "conv": jnp.zeros((np_, batch, ssm_mod.CONV_WIDTH - 1, conv_ch), jnp.float32),
            }
    return cache


def init_cross_kv(cfg: ModelConfig, batch: int, enc_len: int, dtype=jnp.bfloat16):
    if cfg.family != "encdec":
        return None
    np_ = n_periods(cfg)
    kh, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    return {
        f"pos{j}": {
            "k": jnp.zeros((np_, batch, enc_len, kh, hd), dtype),
            "v": jnp.zeros((np_, batch, enc_len, kh, hd), dtype),
        }
        for j in range(len(block_program(cfg)))
    }


def cache_from_prefill(
    cfg: ModelConfig, collected: dict, cache_dtype=jnp.bfloat16, max_seq: int = 0
):
    """Convert stack_prefill's collected KV/states into decode-cache layout.

    Collected attention KV has shape (np_, b, s, kh, hd); for sliding-window
    models only the trailing ``window`` positions are retained (ring-aligned:
    slot = pos % window, exact when s % window == 0). When ``max_seq`` (the
    decode horizon) exceeds the prompt length the cache is padded to
    ``cache_capacity(cfg, max_seq)`` so subsequent decode steps have slots.
    """
    prog = block_program(cfg)
    out = {}
    for j, (mixer, _) in enumerate(prog):
        c = collected[f"pos{j}"]
        if mixer == "attn":
            k, v = c["k"], c["v"]
            if cfg.sliding_window > 0 and k.shape[2] > cfg.sliding_window:
                w = cfg.sliding_window
                assert k.shape[2] % w == 0, "prefill len must be multiple of window"
                k, v = k[:, :, -w:], v[:, :, -w:]
            cap = cache_capacity(cfg, max(max_seq, k.shape[2]))
            if cap > k.shape[2]:
                pad = ((0, 0), (0, 0), (0, cap - k.shape[2]), (0, 0), (0, 0))
                k, v = jnp.pad(k, pad), jnp.pad(v, pad)
            out[f"pos{j}"] = {"k": k.astype(cache_dtype), "v": v.astype(cache_dtype)}
        else:
            out[f"pos{j}"] = c
    return out

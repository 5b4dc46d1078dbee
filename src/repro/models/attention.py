"""GQA/MQA/MHA attention with RoPE, qk-norm, QKV bias and sliding windows.

Weights are stored 2-D flattened ``(d_model, heads*head_dim)`` so tensor-
parallel sharding over the fused head dimension is always divisible on the
production mesh (see DESIGN.md §4). The forward path optionally routes the
core attention product through the Pallas flash-attention kernel.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import shard_hints
from repro.models.layers import _dense_init, apply_rope, head_rmsnorm_apply


def attn_init(rng, cfg: ModelConfig, dtype, cross: bool = False) -> tuple[dict, dict]:
    d = cfg.d_model
    h, k, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    keys = jax.random.split(rng, 4)
    params = {
        "wq": _dense_init(keys[0], (d, h * hd), dtype),
        "wk": _dense_init(keys[1], (d, k * hd), dtype),
        "wv": _dense_init(keys[2], (d, k * hd), dtype),
        "wo": _dense_init(keys[3], (h * hd, d), dtype),
    }
    axes = {
        "wq": ("embed", "heads"),
        "wk": ("embed", "kv_heads"),
        "wv": ("embed", "kv_heads"),
        "wo": ("heads", "embed"),
    }
    if cfg.qkv_bias and not cross:
        params.update(
            bq=jnp.zeros((h * hd,), dtype),
            bk=jnp.zeros((k * hd,), dtype),
            bv=jnp.zeros((k * hd,), dtype),
        )
        axes.update(bq=("heads",), bk=("kv_heads",), bv=("kv_heads",))
    if cfg.qk_norm and not cross:
        params.update(q_norm=jnp.ones((hd,), dtype), k_norm=jnp.ones((hd,), dtype))
        axes.update(q_norm=("head_dim",), k_norm=("head_dim",))
    return params, axes


def _project_qkv(params, cfg: ModelConfig, x, positions, rope: bool = True):
    h, k, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    b, s, _ = x.shape
    q = x @ params["wq"].astype(x.dtype)
    kk = x @ params["wk"].astype(x.dtype)
    v = x @ params["wv"].astype(x.dtype)
    if cfg.qkv_bias and "bq" in params:
        q = q + params["bq"].astype(x.dtype)
        kk = kk + params["bk"].astype(x.dtype)
        v = v + params["bv"].astype(x.dtype)
    q = q.reshape(b, s, h, hd)
    kk = kk.reshape(b, s, k, hd)
    v = v.reshape(b, s, k, hd)
    if cfg.qk_norm and "q_norm" in params:
        q = head_rmsnorm_apply(params["q_norm"], q, cfg.norm_eps)
        kk = head_rmsnorm_apply(params["k_norm"], kk, cfg.norm_eps)
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        kk = apply_rope(kk, positions, cfg.rope_theta)
    q = shard_hints.constrain(q, "attn_qkv")
    return q, kk, v


def _sdpa(q, k, v, mask, scale):
    """Reference scaled-dot-product attention; q:(b,s,h,d) k/v:(b,t,kh,d)."""
    b, s, h, d = q.shape
    t, kh = k.shape[1], k.shape[2]
    rep = h // kh
    qf = q.astype(jnp.float32) * scale
    scores = jnp.einsum(
        "bshd,bthd->bhst",
        qf,
        jnp.repeat(k.astype(jnp.float32), rep, axis=2),
    )
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhst,bthd->bshd", probs, jnp.repeat(v.astype(jnp.float32), rep, axis=2))
    return out.astype(q.dtype)


def _causal_mask(s: int, t: int, window: int, q_offset: int = 0) -> jax.Array:
    qpos = jnp.arange(s)[:, None] + q_offset
    kpos = jnp.arange(t)[None, :]
    mask = kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    return mask[None, None]  # (1,1,s,t)


def attn_forward(
    params: dict,
    cfg: ModelConfig,
    x: jax.Array,
    positions: jax.Array,
    causal: bool = True,
    use_kernel: bool = True,
    return_kv: bool = False,
):
    """Full-sequence attention (training / prefill)."""
    b, s, _ = x.shape
    h, kh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q, k, v = _project_qkv(params, cfg, x, positions)
    scale = hd**-0.5
    if use_kernel:
        from repro.kernels import ops

        out = ops.flash_attention(
            q, k, v, causal=causal, window=cfg.sliding_window, scale=scale
        )
    else:
        if causal:
            mask = _causal_mask(s, s, cfg.sliding_window)
        else:
            mask = jnp.ones((1, 1, s, s), bool)
        out = _sdpa(q, k, v, mask, scale)
    out = out.reshape(b, s, h * hd)
    out = out @ params["wo"].astype(x.dtype)
    if return_kv:
        return out, k, v
    return out


def cross_attn_forward(
    params: dict,
    cfg: ModelConfig,
    x: jax.Array,
    kv_src: jax.Array | tuple[jax.Array, jax.Array],
) -> jax.Array:
    """Cross attention (enc-dec). ``kv_src`` is the encoder output (prefill)
    or a precomputed (k, v) cache tuple (decode)."""
    b, s, _ = x.shape
    h, kh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = (x @ params["wq"].astype(x.dtype)).reshape(b, s, h, hd)
    if isinstance(kv_src, tuple):
        k, v = kv_src
    else:
        k, v = cross_attn_kv(params, cfg, kv_src)
    t = k.shape[1]
    mask = jnp.ones((1, 1, s, t), bool)
    out = _sdpa(q, k, v, mask, hd**-0.5).reshape(b, s, h * hd)
    return out @ params["wo"].astype(x.dtype)


def cross_attn_kv(params: dict, cfg: ModelConfig, enc_out: jax.Array):
    kh, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    b, t, _ = enc_out.shape
    k = (enc_out @ params["wk"].astype(enc_out.dtype)).reshape(b, t, kh, hd)
    v = (enc_out @ params["wv"].astype(enc_out.dtype)).reshape(b, t, kh, hd)
    return k, v


def attn_chunk(
    params: dict,
    cfg: ModelConfig,
    x: jax.Array,  # (b, C, d) — chunk of the prompt
    cache_k: jax.Array,  # (b, T, kh, hd)
    cache_v: jax.Array,
    pos0: int,  # static: absolute position of the chunk's first token
):
    """Chunked-prefill attention: write the chunk's K/V into the cache and
    attend its queries against everything cached so far (ring-aware for
    sliding windows). Returns (out, new_k, new_v)."""
    b, C, _ = x.shape
    h, kh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    T = cache_k.shape[1]
    positions = jnp.broadcast_to(
        pos0 + jnp.arange(C, dtype=jnp.int32), (b, C)
    )
    q, k_new, v_new = _project_qkv(params, cfg, x, positions)
    qpos = pos0 + jnp.arange(C)
    ring = bool(cfg.sliding_window) and T < pos0 + C

    if ring:
        # Writing the chunk would evict ring entries the chunk's EARLY
        # queries still need (q at pos0 wants window ending at pos0, the
        # write installs up to pos0+C-1). Attend against the pre-write ring
        # ⊕ the fresh chunk, then commit the write.
        assert C <= T and T % C == 0, (C, T)
        idx = jnp.arange(T)
        prev = pos0 - 1
        abs_cache = prev - ((prev - idx) % T)  # ring contents BEFORE write
        k_ext = jnp.concatenate([cache_k.astype(q.dtype), k_new], axis=1)
        v_ext = jnp.concatenate([cache_v.astype(q.dtype), v_new], axis=1)
        abs_ext = jnp.concatenate([abs_cache, qpos])
        mask = (abs_ext[None, :] <= qpos[:, None]) & (abs_ext[None, :] >= 0)
        mask &= abs_ext[None, :] > qpos[:, None] - cfg.sliding_window
        out = _sdpa(q, k_ext, v_ext, mask[None, None], hd**-0.5)
        slot = pos0 % T
    else:
        slot = pos0
    cache_k = jax.lax.dynamic_update_slice_in_dim(
        cache_k, k_new.astype(cache_k.dtype), slot, axis=1
    )
    cache_v = jax.lax.dynamic_update_slice_in_dim(
        cache_v, v_new.astype(cache_v.dtype), slot, axis=1
    )
    if not ring:
        abs_pos = jnp.arange(T)
        mask = (abs_pos[None, :] <= qpos[:, None]) & (abs_pos[None, :] >= 0)
        if cfg.sliding_window:
            mask &= abs_pos[None, :] > qpos[:, None] - cfg.sliding_window
        out = _sdpa(
            q, cache_k.astype(q.dtype), cache_v.astype(q.dtype),
            mask[None, None], hd**-0.5,
        )
    out = out.reshape(b, C, h * hd)
    return out @ params["wo"].astype(x.dtype), cache_k, cache_v


# ---------------------------------------------------------------------------
# Decode path (single new token, KV cache)
# ---------------------------------------------------------------------------


def attn_decode(
    params: dict,
    cfg: ModelConfig,
    x: jax.Array,  # (b, 1, d)
    cache_k: jax.Array,  # (b, T, kh, hd)  T = cache capacity
    cache_v: jax.Array,
    pos: jax.Array,  # scalar int32: index of the new token
):
    """One decode step. Returns (out, new_k, new_v).

    For sliding-window models the cache is a ring buffer of capacity
    ``min(seq, window)``; positions are stored modulo capacity and masking
    uses absolute positions tracked via ``pos``.
    """
    b = x.shape[0]
    h, kh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    T = cache_k.shape[1]
    positions = jnp.full((b, 1), pos, dtype=jnp.int32)
    q, k_new, v_new = _project_qkv(params, cfg, x, positions)
    slot = pos % T if cfg.sliding_window else pos
    cache_k = jax.lax.dynamic_update_slice_in_dim(cache_k, k_new.astype(cache_k.dtype), slot, axis=1)
    cache_v = jax.lax.dynamic_update_slice_in_dim(cache_v, v_new.astype(cache_v.dtype), slot, axis=1)

    # absolute position of every cache slot
    idx = jnp.arange(T)
    if cfg.sliding_window:
        # slot i holds absolute position: the latest p <= pos with p % T == i
        abs_pos = pos - ((pos - idx) % T)
    else:
        abs_pos = idx
    valid = (abs_pos <= pos) & (abs_pos >= 0)
    if cfg.sliding_window:
        valid &= abs_pos > pos - cfg.sliding_window
    mask = valid[None, None, None, :]  # (1,1,1,T)

    from repro.kernels import ops

    out = ops.decode_attention(
        q, cache_k.astype(q.dtype), cache_v.astype(q.dtype), mask, hd**-0.5
    )
    out = out.reshape(b, 1, h * hd)
    return out @ params["wo"].astype(x.dtype), cache_k, cache_v

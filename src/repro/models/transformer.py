"""Transformer assembly: block program, scan-over-layers, decode scan.

Layers are grouped into *periods* (the LCM of the architecture's interleave
periods) so that heterogeneous stacks — Jamba's 1-attention-per-8 hybrid with
MoE every 2nd layer, Mixtral's uniform MoE, Mamba-2's MLP-free blocks — all
scan over ``n_periods`` with per-position stacked parameters. This keeps the
lowered HLO size independent of depth (critical for dry-run compile times)
and gives pipeline parallelism a natural stage boundary.
"""

from __future__ import annotations

import contextlib
import math
import os
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import mlp_apply, mlp_init, rmsnorm_apply, rmsnorm_init


# ---------------------------------------------------------------------------
# Block program
# ---------------------------------------------------------------------------


def _unroll() -> bool:
    """Dry-run cost probes set REPRO_SCAN_UNROLL=1 so XLA's cost analysis
    (which counts a while body exactly once) sees true trip counts."""
    return os.environ.get("REPRO_SCAN_UNROLL") == "1"


def block_program(cfg: ModelConfig) -> list[tuple[str, str]]:
    """[(mixer, mlp)] per position within one period.

    mixer in {"attn", "ssm"}; mlp in {"dense", "moe", "none"}.
    """
    period = 1
    if cfg.family == "hybrid" and cfg.attn_period > 0:
        period = math.lcm(cfg.attn_period, cfg.moe_period if cfg.num_experts else 1)
    elif cfg.num_experts > 0 and cfg.moe_period > 1:
        period = cfg.moe_period
    assert cfg.num_layers % period == 0, (cfg.name, cfg.num_layers, period)
    prog = []
    for j in range(period):
        mixer = cfg.layer_kind(j)
        if cfg.d_ff == 0:
            mlp = "none"
        elif cfg.is_moe_layer(j):
            mlp = "moe"
        else:
            mlp = "dense"
        prog.append((mixer, mlp))
    return prog


def n_periods(cfg: ModelConfig) -> int:
    return cfg.num_layers // len(block_program(cfg))


# ---------------------------------------------------------------------------
# Single block init/apply
# ---------------------------------------------------------------------------


def _block_init(rng, cfg: ModelConfig, mixer: str, mlp: str, dtype, cross: bool):
    keys = jax.random.split(rng, 4)
    params: dict[str, Any] = {}
    axes: dict[str, Any] = {}
    params["ln1"], axes["ln1"] = rmsnorm_init(cfg.d_model, dtype)
    if mixer == "attn":
        params["mixer"], axes["mixer"] = attn.attn_init(keys[0], cfg, dtype)
    else:
        params["mixer"], axes["mixer"] = ssm_mod.ssm_init(keys[0], cfg, dtype)
    if cross:
        params["ln_x"], axes["ln_x"] = rmsnorm_init(cfg.d_model, dtype)
        params["cross"], axes["cross"] = attn.attn_init(keys[2], cfg, dtype, cross=True)
    if mlp != "none":
        params["ln2"], axes["ln2"] = rmsnorm_init(cfg.d_model, dtype)
        if mlp == "moe":
            params["mlp"], axes["mlp"] = moe_mod.moe_init(keys[1], cfg, dtype)
        else:
            params["mlp"], axes["mlp"] = mlp_init(keys[1], cfg, dtype)
    return params, axes


def _block_apply_full(
    bp: dict,
    cfg: ModelConfig,
    mixer: str,
    mlp: str,
    x: jax.Array,
    positions: jax.Array,
    causal: bool,
    enc_out: jax.Array | None = None,
    collect_kv: bool = False,
):
    """Returns (x, aux_loss, kv) — kv is (k, v) when collect_kv and attn."""
    aux = jnp.zeros((), jnp.float32)
    kv = None
    # attention and MLP ops carry "attn" / "mlp" in their op_name, which
    # the benchmark's trace reader sums by layer
    with jax.named_scope("attn") if mixer == "attn" else contextlib.nullcontext():
        h = rmsnorm_apply(bp["ln1"], x)
        if mixer == "attn":
            if collect_kv:
                out, k, v = attn.attn_forward(
                    bp["mixer"], cfg, h, positions, causal=causal, return_kv=True
                )
                kv = (k, v)
            else:
                out = attn.attn_forward(bp["mixer"], cfg, h, positions, causal=causal)
            h = out
        else:
            if collect_kv:
                h, state = ssm_mod.ssm_forward(bp["mixer"], cfg, h, return_state=True)
                kv = state
            else:
                h = ssm_mod.ssm_forward(bp["mixer"], cfg, h)
    x = x + h
    if enc_out is not None and "cross" in bp:
        h = rmsnorm_apply(bp["ln_x"], x)
        x = x + attn.cross_attn_forward(bp["cross"], cfg, h, enc_out)
    if mlp != "none":
        with jax.named_scope("mlp"):
            h = rmsnorm_apply(bp["ln2"], x)
            if mlp == "moe":
                aux = aux + moe_mod.moe_aux_loss(bp["mlp"], cfg, h)
                h = moe_mod.moe_apply(bp["mlp"], cfg, h)
            else:
                h = mlp_apply(bp["mlp"], h, cfg.act)
        x = x + h
    # re-anchor the residual stream's sharding each block: without this
    # GSPMD resolves the FSDP-sharded contraction dims by ALL-REDUCING
    # activation-sized partial sums (see EXPERIMENTS.md §Perf)
    from repro.models import shard_hints

    x = shard_hints.constrain(x, "activation")
    return x, aux, kv


# ---------------------------------------------------------------------------
# Stack init (stacked over n_periods) and forward scan
# ---------------------------------------------------------------------------


def stack_init(rng, cfg: ModelConfig, dtype, cross: bool = False):
    prog = block_program(cfg)
    np_ = n_periods(cfg)
    params, axes = {}, {}
    rngs = jax.random.split(rng, len(prog))
    for j, (mixer, mlp) in enumerate(prog):
        keys = jax.random.split(rngs[j], np_)
        stacked = jax.vmap(
            lambda k: _block_init(k, cfg, mixer, mlp, dtype, cross)[0]
        )(keys)
        _, ax = _block_init(rngs[j], cfg, mixer, mlp, dtype, cross)
        params[f"pos{j}"] = stacked
        axes[f"pos{j}"] = jax.tree_util.tree_map(
            lambda a: ("layers",) + a, ax, is_leaf=lambda a: isinstance(a, tuple)
        )
    return params, axes


def stack_forward(
    params: dict,
    cfg: ModelConfig,
    x: jax.Array,
    positions: jax.Array,
    causal: bool = True,
    enc_out: jax.Array | None = None,
    remat: str = "full",
) -> tuple[jax.Array, jax.Array]:
    """Returns (x, total_moe_aux_loss)."""
    prog = block_program(cfg)

    def body(carry, period_params):
        h, aux = carry
        for j, (mixer, mlp) in enumerate(prog):
            h, a, _ = _block_apply_full(
                period_params[f"pos{j}"], cfg, mixer, mlp, h, positions, causal, enc_out
            )
            aux = aux + a
        return (h, aux), None

    if remat == "full":
        body = jax.checkpoint(body)
    elif remat == "dots":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        )
    (x, aux), _ = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)), params, unroll=_unroll()
    )
    return x, aux


def stack_prefill(
    params: dict,
    cfg: ModelConfig,
    x: jax.Array,
    positions: jax.Array,
    enc_out: jax.Array | None = None,
):
    """Forward pass that also materializes the decode cache.

    Returns (x, cache) where cache mirrors the per-position structure of
    :func:`repro.models.kvcache.init_cache` (stacked over n_periods).
    For sliding-window attention the collected KV is cropped to the ring
    window by the caller (kvcache.cache_from_prefill).
    """
    prog = block_program(cfg)

    def body(carry, period_params):
        h = carry
        ys = {}
        for j, (mixer, mlp) in enumerate(prog):
            h, _, kv = _block_apply_full(
                period_params[f"pos{j}"],
                cfg,
                mixer,
                mlp,
                h,
                positions,
                True,
                enc_out,
                collect_kv=True,
            )
            if mixer == "attn":
                ys[f"pos{j}"] = {"k": kv[0], "v": kv[1]}
            else:
                ys[f"pos{j}"] = kv  # ssm state dict
        return h, ys

    x, cache = jax.lax.scan(body, x, params, unroll=_unroll())
    return x, cache


def stack_chunk(
    params: dict,
    cfg: ModelConfig,
    x: jax.Array,  # (b, C, d) — prompt chunk
    cache: dict,
    pos0: int,  # static
):
    """Chunked-prefill step: like stack_decode but for C tokens at once —
    bounds prefill activation memory to O(C·context) instead of O(s²)
    (decoder-only archs; see model.prefill_chunked)."""
    prog = block_program(cfg)

    def body(carry, xs):
        h = carry
        period_params, period_cache = xs
        new_cache = {}
        for j, (mixer, mlp) in enumerate(prog):
            bp = period_params[f"pos{j}"]
            c = period_cache[f"pos{j}"]
            hin = rmsnorm_apply(bp["ln1"], h)
            if mixer == "attn":
                out, ck, cv = attn.attn_chunk(
                    bp["mixer"], cfg, hin, c["k"], c["v"], pos0
                )
                new_cache[f"pos{j}"] = {"k": ck, "v": cv}
            else:
                out, st = ssm_mod.ssm_forward(
                    bp["mixer"], cfg, hin, return_state=True, init_state=c
                )
                new_cache[f"pos{j}"] = {
                    "ssd": st["ssd"].astype(c["ssd"].dtype),
                    "conv": st["conv"].astype(c["conv"].dtype),
                }
            h = h + out
            if mlp != "none":
                h2 = rmsnorm_apply(bp["ln2"], h)
                if mlp == "moe":
                    h2 = moe_mod.moe_apply(bp["mlp"], cfg, h2)
                else:
                    h2 = mlp_apply(bp["mlp"], h2, cfg.act)
                h = h + h2
        return h, new_cache

    x, new_cache = jax.lax.scan(body, x, (params, cache), unroll=_unroll())
    return x, new_cache


# ---------------------------------------------------------------------------
# Decode scan (cache threaded as scan xs/ys)
# ---------------------------------------------------------------------------


def stack_decode(
    params: dict,
    cfg: ModelConfig,
    x: jax.Array,  # (b, 1, d)
    cache: dict,  # per-position stacked caches
    pos: jax.Array,  # scalar int32
    cross_kv: dict | None = None,  # per-position stacked (k, v) for enc-dec
):
    prog = block_program(cfg)

    def body(carry, xs):
        h = carry
        period_params, period_cache, period_cross = xs
        new_cache = {}
        for j, (mixer, mlp) in enumerate(prog):
            bp = period_params[f"pos{j}"]
            c = period_cache[f"pos{j}"]
            hin = rmsnorm_apply(bp["ln1"], h)
            if mixer == "attn":
                out, ck, cv = attn.attn_decode(bp["mixer"], cfg, hin, c["k"], c["v"], pos)
                new_cache[f"pos{j}"] = {"k": ck, "v": cv}
            else:
                out, st = ssm_mod.ssm_decode(bp["mixer"], cfg, hin, c)
                new_cache[f"pos{j}"] = st
            h = h + out
            if period_cross is not None and "cross" in bp:
                hx = rmsnorm_apply(bp["ln_x"], h)
                kv = (period_cross[f"pos{j}"]["k"], period_cross[f"pos{j}"]["v"])
                h = h + attn.cross_attn_forward(bp["cross"], cfg, hx, kv)
            if mlp != "none":
                h2 = rmsnorm_apply(bp["ln2"], h)
                if mlp == "moe":
                    h2 = moe_mod.moe_apply(bp["mlp"], cfg, h2)
                else:
                    h2 = mlp_apply(bp["mlp"], h2, cfg.act)
                h = h + h2
        return h, new_cache

    xs = (params, cache, cross_kv)
    x, new_cache = jax.lax.scan(body, x, xs, unroll=_unroll())
    return x, new_cache

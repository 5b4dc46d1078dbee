"""Transformer assembly: block program, scan-over-layers, decode scan.

Layers are grouped into *periods* (the LCM of the architecture's interleave
periods) so that heterogeneous stacks — Jamba's 1-attention-per-8 hybrid with
MoE every 2nd layer, Mixtral's uniform MoE, Mamba-2's MLP-free blocks — all
scan over ``n_periods`` with per-position stacked parameters. This keeps the
lowered HLO size independent of depth (critical for dry-run compile times)
and gives pipeline parallelism a natural stage boundary. Leading dense
layers before the MoE layers (LFM2's) are a stack of their own, scanned
first (:func:`segments`).
"""

from __future__ import annotations

import contextlib
import os
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import short_conv
from repro.models import ssm as ssm_mod
from repro.models.layers import mlp_apply, mlp_init, rmsnorm_apply, rmsnorm_init


# ---------------------------------------------------------------------------
# Block program
# ---------------------------------------------------------------------------


def _unroll() -> bool:
    """Dry-run cost probes set REPRO_SCAN_UNROLL=1 so XLA's cost analysis
    (which counts a while body exactly once) sees true trip counts."""
    return os.environ.get("REPRO_SCAN_UNROLL") == "1"


def layer_program(cfg: ModelConfig) -> list[tuple[str, str]]:
    """[(mixer, mlp)] of every decoder layer.

    mixer in {"attn", "ssm", "conv"}; mlp in {"dense", "moe", "none"}.
    """
    prog = []
    for layer in range(cfg.num_layers):
        if cfg.d_ff == 0:
            mlp = "none"
        elif cfg.is_moe_layer(layer):
            mlp = "moe"
        else:
            mlp = "dense"
        prog.append((cfg.layer_kind(layer), mlp))
    return prog


def _period(layers: list) -> int:
    """The least p such that ``layers`` is p-periodic over its whole length
    and p divides it."""
    n = len(layers)
    return next(p for p in range(1, n + 1)
                if n % p == 0 and layers == layers[:p] * (n // p))


def segments(cfg: ModelConfig) -> list[tuple[str, list[tuple[str, str]], int]]:
    """The stacks the layers are scanned in, in order: [(prefix, program,
    repeats)].

    ``lead``: the leading dense layers (``dense_layers``), if any; ``pos``:
    every later layer, as the least period that repeats to the end. A
    pattern that does not repeat (LFM2's attention every fourth layer, then
    every third) is one period of all those layers. Parameters of position
    j of a stack sit at ``blocks/<prefix><j>``, stacked over its repeats.
    """
    layers = layer_program(cfg)
    lead, rest = layers[: cfg.dense_layers], layers[cfg.dense_layers :]
    out = []
    if lead:
        p = _period(lead)
        out.append(("lead", lead[:p], len(lead) // p))
    p = _period(rest)
    return out + [("pos", rest[:p], len(rest) // p)]


def layer_stacks(cfg: ModelConfig) -> dict[str, tuple[int, int]]:
    """{prefix: (first layer, period)} of each stack of :func:`segments`:
    the global layer of position j in repeat r of a stack is
    first + r * period + j."""
    out, first = {}, 0
    for prefix, prog, repeats in segments(cfg):
        out[prefix] = (first, len(prog))
        first += len(prog) * repeats
    return out


def block_program(cfg: ModelConfig) -> list[tuple[str, str]]:
    """[(mixer, mlp)] per position of the main periodic stack (``pos``)."""
    return next(prog for prefix, prog, _ in segments(cfg) if prefix == "pos")


def n_periods(cfg: ModelConfig) -> int:
    """Repeats of the main periodic stack."""
    return next(n for prefix, _, n in segments(cfg) if prefix == "pos")


def require_one_stack(cfg: ModelConfig, what: str) -> None:
    """Raise ``NotImplementedError`` where ``what`` (serving, pipeline stages)
    only knows one periodic stack of attention and SSM layers."""
    if any(mixer == "conv" for mixer, _ in layer_program(cfg)):
        raise NotImplementedError(f"{cfg.name}: {what} has no short-conv ('conv') layers yet")
    if cfg.dense_layers:
        raise NotImplementedError(f"{cfg.name}: {what} runs one periodic layer stack, "
                                  "not a stack of leading dense layers before it")


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
    elif mixer == "conv":
        params["mixer"], axes["mixer"] = short_conv.short_conv_init(keys[0], cfg, dtype)
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


# the benchmark's trace reader sums device time by these scopes
_MIXER_SCOPE = {"attn": "attn", "conv": "short_conv"}


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
    """Returns (x, aux_loss, local_rows, kv): local_rows is the MoE layer's
    pairs routed to held experts (None for other layers); kv is (k, v) when
    collect_kv and attn."""
    aux = jnp.zeros((), jnp.float32)
    rows = None
    kv = None
    scope = _MIXER_SCOPE.get(mixer)
    with jax.named_scope(scope) if scope else contextlib.nullcontext():
        h = rmsnorm_apply(bp["ln1"], x, cfg.norm_eps)
        if mixer == "attn":
            if collect_kv:
                out, k, v = attn.attn_forward(
                    bp["mixer"], cfg, h, positions, causal=causal, return_kv=True
                )
                kv = (k, v)
            else:
                out = attn.attn_forward(bp["mixer"], cfg, h, positions, causal=causal)
            h = out
        elif mixer == "conv":
            h = short_conv.short_conv_apply(bp["mixer"], h)
        else:
            if collect_kv:
                h, state = ssm_mod.ssm_forward(bp["mixer"], cfg, h, return_state=True)
                kv = state
            else:
                h = ssm_mod.ssm_forward(bp["mixer"], cfg, h)
    x = x + h
    if enc_out is not None and "cross" in bp:
        h = rmsnorm_apply(bp["ln_x"], x, cfg.norm_eps)
        x = x + attn.cross_attn_forward(bp["cross"], cfg, h, enc_out)
    if mlp != "none":
        with jax.named_scope("mlp"):
            h = rmsnorm_apply(bp["ln2"], x, cfg.norm_eps)
            if mlp == "moe":
                h, a, rows = moe_mod.moe_apply(bp["mlp"], cfg, h)
                aux = aux + a
            else:
                h = mlp_apply(bp["mlp"], h, cfg.act)
        x = x + h
    # re-anchor the residual stream's sharding each block: without this
    # GSPMD resolves the FSDP-sharded contraction dims by ALL-REDUCING
    # activation-sized partial sums (see EXPERIMENTS.md §Perf)
    from repro.models import shard_hints

    x = shard_hints.constrain(x, "activation")
    return x, aux, rows, kv


# ---------------------------------------------------------------------------
# Stack init (each segment stacked over its repeats) and forward scan
# ---------------------------------------------------------------------------


def stack_init(rng, cfg: ModelConfig, dtype, cross: bool = False):
    params, axes = {}, {}
    segs = segments(cfg)
    seg_rngs = jax.random.split(rng, len(segs)) if len(segs) > 1 else [rng]
    for seg_rng, (prefix, prog, repeats) in zip(seg_rngs, segs):
        rngs = jax.random.split(seg_rng, len(prog))
        for j, (mixer, mlp) in enumerate(prog):
            keys = jax.random.split(rngs[j], repeats)
            stacked = jax.vmap(
                lambda k: _block_init(k, cfg, mixer, mlp, dtype, cross)[0]
            )(keys)
            _, ax = _block_init(rngs[j], cfg, mixer, mlp, dtype, cross)
            params[f"{prefix}{j}"] = stacked
            axes[f"{prefix}{j}"] = jax.tree_util.tree_map(
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
) -> tuple[jax.Array, dict]:
    """Returns (x, sums): ``moe_aux``, the MoE layers' auxiliary losses, and
    where the model has MoE layers ``moe_local_rows``, their pairs routed to
    held experts."""
    sums = {"moe_aux": jnp.zeros((), jnp.float32)}
    if any(mlp == "moe" for _, mlp in layer_program(cfg)):
        sums["moe_local_rows"] = jnp.zeros((), jnp.int32)
    for prefix, prog, _ in segments(cfg):

        def body(carry, period_params, prefix=prefix, prog=prog):
            h, sums = carry
            for j, (mixer, mlp) in enumerate(prog):
                h, a, rows, _ = _block_apply_full(
                    period_params[f"{prefix}{j}"], cfg, mixer, mlp, h, positions,
                    causal, enc_out,
                )
                sums = dict(sums, moe_aux=sums["moe_aux"] + a)
                if rows is not None:
                    sums["moe_local_rows"] = sums["moe_local_rows"] + rows
            return (h, sums), None

        if remat == "full":
            body = jax.checkpoint(body)
        elif remat == "dots":
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
            )
        stack = {f"{prefix}{j}": params[f"{prefix}{j}"] for j in range(len(prog))}
        (x, sums), _ = jax.lax.scan(body, (x, sums), stack, unroll=_unroll())
    return x, sums


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
    require_one_stack(cfg, "prefill")
    prog = block_program(cfg)

    def body(carry, period_params):
        h = carry
        ys = {}
        for j, (mixer, mlp) in enumerate(prog):
            h, _, _, kv = _block_apply_full(
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
    require_one_stack(cfg, "chunked prefill")
    prog = block_program(cfg)

    def body(carry, xs):
        h = carry
        period_params, period_cache = xs
        new_cache = {}
        for j, (mixer, mlp) in enumerate(prog):
            bp = period_params[f"pos{j}"]
            c = period_cache[f"pos{j}"]
            hin = rmsnorm_apply(bp["ln1"], h, cfg.norm_eps)
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
                h2 = rmsnorm_apply(bp["ln2"], h, cfg.norm_eps)
                if mlp == "moe":
                    h2, _, _ = moe_mod.moe_apply(bp["mlp"], cfg, h2)
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
    require_one_stack(cfg, "decode")
    prog = block_program(cfg)

    def body(carry, xs):
        h = carry
        period_params, period_cache, period_cross = xs
        new_cache = {}
        for j, (mixer, mlp) in enumerate(prog):
            bp = period_params[f"pos{j}"]
            c = period_cache[f"pos{j}"]
            hin = rmsnorm_apply(bp["ln1"], h, cfg.norm_eps)
            if mixer == "attn":
                out, ck, cv = attn.attn_decode(bp["mixer"], cfg, hin, c["k"], c["v"], pos)
                new_cache[f"pos{j}"] = {"k": ck, "v": cv}
            else:
                out, st = ssm_mod.ssm_decode(bp["mixer"], cfg, hin, c)
                new_cache[f"pos{j}"] = st
            h = h + out
            if period_cross is not None and "cross" in bp:
                hx = rmsnorm_apply(bp["ln_x"], h, cfg.norm_eps)
                kv = (period_cross[f"pos{j}"]["k"], period_cross[f"pos{j}"]["v"])
                h = h + attn.cross_attn_forward(bp["cross"], cfg, hx, kv)
            if mlp != "none":
                h2 = rmsnorm_apply(bp["ln2"], h, cfg.norm_eps)
                if mlp == "moe":
                    h2, _, _ = moe_mod.moe_apply(bp["mlp"], cfg, h2)
                else:
                    h2 = mlp_apply(bp["mlp"], h2, cfg.act)
                h = h + h2
        return h, new_cache

    xs = (params, cache, cross_kv)
    x, new_cache = jax.lax.scan(body, x, xs, unroll=_unroll())
    return x, new_cache

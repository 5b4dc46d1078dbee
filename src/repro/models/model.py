"""Public model API: init / forward / loss / prefill / decode / input specs.

All ten assigned architectures flow through these entry points; the
distribution layer wraps them into pjit'd train/prefill/decode steps and the
dry-run lowers them against the production mesh.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import kvcache
from repro.models import layers as L
from repro.models import shard_hints
from repro.models import transformer as T


def _dtype(name: str):
    # jnp.dtype resolves any registered dtype name; a literal two-entry map
    # here raised KeyError for e.g. float16 (see shadow.abstract_batch)
    return jnp.dtype(name)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _init_with_axes(cfg: ModelConfig, rng) -> tuple[dict, dict]:
    pdt = _dtype(cfg.param_dtype)
    keys = jax.random.split(rng, 4)
    params: dict[str, Any] = {}
    axes: dict[str, Any] = {}
    params["embed"], axes["embed"] = L.embed_init(keys[0], cfg, pdt)
    if cfg.family == "encdec":
        enc_cfg = _encoder_cfg(cfg)
        params["encoder"], axes["encoder"] = {}, {}
        params["encoder"]["blocks"], axes["encoder"]["blocks"] = T.stack_init(
            keys[3], enc_cfg, pdt
        )
        params["encoder"]["final_norm"], axes["encoder"]["final_norm"] = L.rmsnorm_init(
            cfg.d_model, pdt
        )
        params["blocks"], axes["blocks"] = T.stack_init(keys[1], cfg, pdt, cross=True)
    else:
        params["blocks"], axes["blocks"] = T.stack_init(keys[1], cfg, pdt)
    params["final_norm"], axes["final_norm"] = L.rmsnorm_init(cfg.d_model, pdt)
    if not cfg.tie_embeddings:
        params["lm_head"], axes["lm_head"] = L.lm_head_init(keys[2], cfg, pdt)
    return params, axes


def _encoder_cfg(cfg: ModelConfig) -> ModelConfig:
    import dataclasses

    return dataclasses.replace(
        cfg,
        family="dense",
        num_layers=cfg.encoder_layers,
        num_experts=0,
        attn_period=0,
        frontend=None,
    )


def init_params(cfg: ModelConfig, rng) -> dict:
    return _init_with_axes(cfg, rng)[0]


@functools.lru_cache(maxsize=64)
def _abstract_cached(cfg: ModelConfig):
    return jax.eval_shape(lambda: _init_with_axes(cfg, jax.random.key(0))[0])


def abstract_params(cfg: ModelConfig):
    """ShapeDtypeStruct pytree — no allocation (dry-run / planning)."""
    return _abstract_cached(cfg)


def param_logical_axes(cfg: ModelConfig) -> dict:
    """Logical-axis tuples mirroring the param tree (tuples are leaves)."""
    return _init_with_axes_axes(cfg)


@functools.lru_cache(maxsize=64)
def _init_with_axes_axes(cfg: ModelConfig) -> dict:
    # axes tree contains python tuples only; compute it via eval_shape to
    # avoid touching devices, then discard the abstract params.
    out = {}

    def capture():
        p, a = _init_with_axes(cfg, jax.random.key(0))
        out["axes"] = a
        return p

    jax.eval_shape(capture)
    return out["axes"]


def analytic_param_count(cfg: ModelConfig, active_only: bool = False) -> int:
    from repro.utils.pytree import axes_paths, tree_paths

    params = abstract_params(cfg)
    axes = param_logical_axes(cfg)
    pflat = tree_paths(params)
    aflat = axes_paths(axes)
    total = 0
    for path, leaf in pflat.items():
        n = int(np.prod(leaf.shape))
        if active_only and cfg.num_experts > 0:
            ax = aflat.get(path, ())
            if "expert" in ax:
                n = n * cfg.experts_per_token // cfg.num_experts
        total += n
    return total


# ---------------------------------------------------------------------------
# Forward / loss
# ---------------------------------------------------------------------------


def _embed_or_frames(cfg: ModelConfig, params, batch, dtype):
    if cfg.family == "encdec":
        return batch["frames"].astype(dtype)
    return L.embed_apply(params["embed"], batch["tokens"], dtype)


def encode(cfg: ModelConfig, params, frames: jax.Array, remat: str = "full"):
    """Encoder forward (enc-dec archs). frames: (b, s_enc, d_model)."""
    enc_cfg = _encoder_cfg(cfg)
    b, s, _ = frames.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    x, _ = T.stack_forward(
        params["encoder"]["blocks"],
        enc_cfg,
        frames.astype(_dtype(cfg.dtype)),
        positions,
        causal=False,
        remat=remat,
    )
    return L.rmsnorm_apply(params["encoder"]["final_norm"], x, cfg.norm_eps)


def _forward(cfg: ModelConfig, params: dict, batch: dict, remat: str):
    """(logits, the layer stack's sums: ``T.stack_forward``)."""
    adt = _dtype(cfg.dtype)
    tokens = batch["tokens"]
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    enc_out = None
    if cfg.family == "encdec":
        enc_out = encode(cfg, params, batch["frames"], remat=remat)
    with jax.named_scope("embed"):
        x = shard_hints.constrain(L.embed_apply(params["embed"], tokens, adt), "activation")
    x, sums = T.stack_forward(
        params["blocks"], cfg, x, positions, causal=True, enc_out=enc_out, remat=remat
    )
    with jax.named_scope("head"):
        x = L.rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
        logits = L.lm_head_apply(params.get("lm_head"), params["embed"], x)
        logits = shard_hints.constrain(logits, "logits")
    return logits, sums


def forward(
    cfg: ModelConfig, params: dict, batch: dict, remat: str = "full"
) -> tuple[jax.Array, jax.Array]:
    """Full-sequence forward. Returns (logits, moe_aux_loss).

    batch: {"tokens": (b,s) int32} for decoder-only;
           {"frames": (b,s_enc,d), "tokens": (b,s) int32} for enc-dec.
    """
    logits, sums = _forward(cfg, params, batch, remat)
    return logits, sums["moe_aux"]


def loss_fn(
    cfg: ModelConfig, params: dict, batch: dict, remat: str = "full",
    aux_weight: float = 0.01,
) -> tuple[jax.Array, dict]:
    """(loss, metrics): ``nll``, ``moe_aux`` and, for a model with MoE
    layers, ``moe_local_rows``: the (token, choice) pairs routed to the
    experts this world holds, summed over the layers."""
    logits, sums = _forward(cfg, params, batch, remat)
    aux = sums["moe_aux"]
    tokens = batch["tokens"]
    with jax.named_scope("head"):
        logits = logits[:, :-1].astype(jnp.float32)
        targets = tokens[:, 1:]
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        tgt_logit = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
        nll = (logz - tgt_logit).mean()
    loss = nll + aux_weight * aux
    return loss, {"nll": nll, **sums}


# ---------------------------------------------------------------------------
# Prefill / decode
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype=jnp.bfloat16):
    return kvcache.init_cache(cfg, batch, max_seq, dtype)


def abstract_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype=jnp.bfloat16):
    return jax.eval_shape(lambda: kvcache.init_cache(cfg, batch, max_seq, dtype))


def prefill(
    cfg: ModelConfig,
    params: dict,
    batch: dict,
    cache_dtype=jnp.bfloat16,
    max_seq: int = 0,
):
    """Process the prompt; returns (last_logits, cache, cross_kv).

    ``max_seq``: total decode horizon — the cache is sized for it.
    """
    adt = _dtype(cfg.dtype)
    tokens = batch["tokens"]
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    enc_out = None
    cross_kv = None
    if cfg.family == "encdec":
        enc_out = encode(cfg, params, batch["frames"])
        cross_kv = _build_cross_kv(cfg, params, enc_out)
    x = shard_hints.constrain(L.embed_apply(params["embed"], tokens, adt), "activation")
    x, collected = T.stack_prefill(params["blocks"], cfg, x, positions, enc_out=enc_out)
    x = L.rmsnorm_apply(params["final_norm"], x[:, -1:], cfg.norm_eps)
    logits = L.lm_head_apply(params.get("lm_head"), params["embed"], x)
    cache = kvcache.cache_from_prefill(cfg, collected, cache_dtype, max_seq=max_seq)
    return logits, cache, cross_kv


def prefill_chunked(
    cfg: ModelConfig,
    params: dict,
    batch: dict,
    chunk_len: int,
    max_seq: int = 0,
    cache_dtype=jnp.float32,
):
    """Chunked prefill (beyond-paper serving feature, EXPERIMENTS §Perf
    cell C): process the prompt ``chunk_len`` tokens at a time against the
    growing KV/SSD cache, bounding activation memory to O(chunk·context)
    instead of the O(s²) scores of whole-prompt prefill. Decoder-only archs.

    Returns (last_logits, cache) — same contract as :func:`prefill`.
    """
    assert cfg.family != "encdec", "chunked prefill is decoder-only"
    adt = _dtype(cfg.dtype)
    tokens = batch["tokens"]
    b, s = tokens.shape
    assert s % chunk_len == 0, (s, chunk_len)
    horizon = max(max_seq, s)
    cache = kvcache.init_cache(cfg, b, horizon, cache_dtype)
    x_last = None
    for i in range(s // chunk_len):
        pos0 = i * chunk_len
        chunk = jax.lax.dynamic_slice_in_dim(tokens, pos0, chunk_len, axis=1)
        x = L.embed_apply(params["embed"], chunk, adt)
        x, cache = T.stack_chunk(params["blocks"], cfg, x, cache, pos0)
        x_last = x
    h = L.rmsnorm_apply(params["final_norm"], x_last[:, -1:], cfg.norm_eps)
    logits = L.lm_head_apply(params.get("lm_head"), params["embed"], h)
    return logits, cache


def _build_cross_kv(cfg: ModelConfig, params, enc_out):
    from repro.models import attention as attn_mod

    prog = T.block_program(cfg)
    out = {}
    for j in range(len(prog)):
        bp = params["blocks"][f"pos{j}"]

        def per_layer(cross_params):
            k, v = attn_mod.cross_attn_kv(cross_params, cfg, enc_out)
            return {"k": k, "v": v}

        out[f"pos{j}"] = jax.vmap(per_layer)(bp["cross"])
    return out


def decode_step(
    cfg: ModelConfig,
    params: dict,
    cache: dict,
    tokens: jax.Array,  # (b, 1) int32
    pos: jax.Array,  # scalar int32 — absolute position of the new token
    cross_kv: dict | None = None,
):
    """One serving step: returns (logits (b,1,V), new_cache)."""
    adt = _dtype(cfg.dtype)
    x = L.embed_apply(params["embed"], tokens, adt)
    x, new_cache = T.stack_decode(params["blocks"], cfg, x, cache, pos, cross_kv)
    x = L.rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    logits = L.lm_head_apply(params.get("lm_head"), params["embed"], x)
    return logits, new_cache


# ---------------------------------------------------------------------------
# Input specs (dry-run stand-ins; ShapeDtypeStruct, no allocation)
# ---------------------------------------------------------------------------


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of this cell.

    train/prefill: token batch (+ frames for the audio-frontend stub).
    decode: single-token batch + abstract KV/state cache at seq_len capacity.
    """
    b, s = shape.global_batch, shape.seq_len
    sds = jax.ShapeDtypeStruct
    if shape.kind in ("train", "prefill"):
        specs = {"tokens": sds((b, s), jnp.int32)}
        if cfg.family == "encdec":
            specs["frames"] = sds((b, s, cfg.d_model), _dtype(cfg.dtype))
        return specs
    # decode: the cache is an input too
    specs = {
        "tokens": sds((b, 1), jnp.int32),
        "pos": sds((), jnp.int32),
        "cache": abstract_cache(cfg, b, s),
    }
    if cfg.family == "encdec":
        enc_len = min(s, 4096)
        specs["cross_kv"] = jax.eval_shape(
            lambda: kvcache.init_cross_kv(cfg, b, enc_len)
        )
    return specs

"""Resource view of the serving state (params + KV/SSD cache).

The serving analogue of ``core/resource_view.py``: every decode-cache leaf
(``models/kvcache.py`` layout) gets a :class:`TensorSpec` so cache state is
planned and moved by the SAME intersection-planner → ReshardEngine pipeline
as parameters — including delta classification, so a tp-preserving resize
adopts resident cache shards instead of re-streaming them.

Role assignment (the cache-migration invariant, DESIGN.md §16): the batch
(slot) axis carries role ``none`` — the cache is replicated across the
non-tp mesh factors, mirroring ``param_shardings(serving=True)`` which
replicates the embed dim. This is what makes residency reachable: with no
``dp`` role anywhere in the serving state, a resize that preserves the tp
degree classifies every cell resident (identical views on surviving
ranks), so the commit moves zero bytes. A ``dp``-split batch axis would
make full residency impossible for any world-size-changing resize.

Physical shardings are derived from the SAME roles (:func:`role_sharding`),
so the planner's classification and the device layout cannot disagree —
``LiveExecutor._adopt_resident`` then aliases buffers instead of copying.
"""

from __future__ import annotations

from typing import Any, Optional

import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ParallelConfig
from repro.core.intersection import TransferPlan, plan_transfer
from repro.core.resource_view import TensorSpec, build_tensor_specs
from repro.utils.pytree import tree_from_paths, tree_paths

__all__ = [
    "ROLE_AXIS",
    "cache_tensor_specs",
    "named_serve_leaves",
    "rebuild_serve_state",
    "role_sharding",
    "serve_plan",
    "serve_state_specs",
    "target_shardings_by_name",
]

# spec role -> mesh axis (make_elastic_mesh axis names)
ROLE_AXIS = {"pp": "pipe", "tp": "model", "dp": "data", "ep": "expert", "none": None}


def _dt(dtype) -> str:
    return np.dtype(dtype).name


def cache_tensor_specs(
    cfg: ModelConfig,
    batch: int,
    max_seq: int,
    cache_dtype="float32",
    cross_len: int = 0,
) -> list[TensorSpec]:
    """Specs for the decode cache pytree (+ cross-attention KV for encdec).

    Shapes mirror ``kvcache.init_cache``/``init_cross_kv`` exactly; names
    (``cache/pos{j}/k``, ``cross/pos{j}/k``) carry the ``/pos{j}/`` marker
    the planner's layer-granular streaming keys on, so cache cells land in
    the same global layer ids as the params of that block position.
    """
    from repro.models import ssm as ssm_mod
    from repro.models.kvcache import cache_capacity
    from repro.models.transformer import block_program, n_periods

    prog = block_program(cfg)
    np_ = n_periods(cfg)
    kh, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    T = cache_capacity(cfg, max_seq)
    specs: list[TensorSpec] = []
    for j, (mixer, _) in enumerate(prog):
        if mixer == "attn":
            for leaf in ("k", "v"):
                specs.append(
                    TensorSpec(
                        name=f"cache/pos{j}/{leaf}",
                        shape=(np_, batch, T, kh, hd),
                        dtype=_dt(cache_dtype),
                        roles=("pp", "none", "none", "tp", "none"),
                        stage_scope="stages",
                        collection="cache",
                    )
                )
        else:
            _, h, n, conv_ch = ssm_mod.ssm_dims(cfg)
            specs.append(
                TensorSpec(
                    name=f"cache/pos{j}/ssd",
                    shape=(np_, batch, h, ssm_mod.SSM_HEAD_DIM, n),
                    dtype="float32",
                    roles=("pp", "none", "tp", "none", "none"),
                    stage_scope="stages",
                    collection="cache",
                )
            )
            specs.append(
                TensorSpec(
                    name=f"cache/pos{j}/conv",
                    shape=(np_, batch, ssm_mod.CONV_WIDTH - 1, conv_ch),
                    dtype="float32",
                    roles=("pp", "none", "none", "tp"),
                    stage_scope="stages",
                    collection="cache",
                )
            )
    if cfg.family == "encdec":
        assert cross_len > 0, "encdec serve state needs the encoder length"
        for j in range(len(prog)):
            for leaf in ("k", "v"):
                specs.append(
                    TensorSpec(
                        name=f"cross/pos{j}/{leaf}",
                        shape=(np_, batch, cross_len, kh, hd),
                        dtype=_dt(cache_dtype),
                        roles=("pp", "none", "none", "tp", "none"),
                        stage_scope="stages",
                        collection="cross",
                    )
                )
    return specs


def serve_state_specs(
    cfg: ModelConfig,
    batch: int,
    max_seq: int,
    cache_dtype="float32",
    cross_len: int = 0,
) -> list[TensorSpec]:
    """Params + cache (+ cross-KV) — the full migratable serving state.

    Params come from the training resource view with ``include_optimizer=
    False``; serving param specs carry no ``dp`` role, so params are fully
    resident under any tp/pp-preserving resize, like the cache.
    """
    return build_tensor_specs(cfg, include_optimizer=False) + cache_tensor_specs(
        cfg, batch, max_seq, cache_dtype=cache_dtype, cross_len=cross_len
    )


def role_sharding(spec: TensorSpec, mesh: Mesh) -> NamedSharding:
    """Physical sharding derived from the spec's roles, with the standard
    divisibility fallback (mirroring ``_spec_for_axes``): a dim the mesh
    axis does not divide evenly is replicated — the planner still uses
    balanced splits there, and the executor operates on global arrays, so
    only the zero-copy fast path (not correctness) is at stake."""
    parts = []
    for d, role in enumerate(spec.roles):
        ax = ROLE_AXIS[role]
        if ax is not None and spec.shape[d] % mesh.shape[ax] != 0:
            ax = None
        parts.append(ax)
    return NamedSharding(mesh, P(*parts))


def target_shardings_by_name(
    specs: list[TensorSpec], mesh: Mesh
) -> dict[str, NamedSharding]:
    return {s.name: role_sharding(s, mesh) for s in specs}


def serve_plan(
    cfg: ModelConfig,
    specs: list[TensorSpec],
    cfg_src: ParallelConfig,
    cfg_dst: ParallelConfig,
    allowed_src=None,
) -> TransferPlan:
    """Intersection plan for a serving resize — one plan covers params and
    cache together, so both stream through one engine pass at commit."""
    from repro.models.transformer import layer_stacks

    return plan_transfer(
        specs,
        cfg_src,
        cfg_dst,
        source_policy="nearest",
        layer_granular=True,
        stacks=layer_stacks(cfg),
        allowed_src=allowed_src,
    )


def named_serve_leaves(
    params: Any, cache: Optional[Any] = None, cross_kv: Optional[Any] = None
) -> dict[str, Any]:
    """Flatten live serving state into the resource view's tensor names.

    ``cache=None`` covers wave-boundary commits: no generation in flight,
    so only params migrate."""
    named: dict[str, Any] = {}
    for path, leaf in tree_paths(params).items():
        named[f"params/{path}"] = leaf
    for path, leaf in tree_paths(cache or {}).items():
        named[f"cache/{path}"] = leaf
    if cross_kv is not None:
        for path, leaf in tree_paths(cross_kv).items():
            named[f"cross/{path}"] = leaf
    return named


def rebuild_serve_state(
    named: dict[str, Any], params_like: Any, cache_like: Any = None, cross_like: Any = None
):
    """Inverse of :func:`named_serve_leaves`. Returns (params, cache, cross)."""
    params = tree_from_paths(
        {p: named[f"params/{p}"] for p in tree_paths(params_like)}, params_like
    )
    cache = None
    if cache_like is not None:
        cache = tree_from_paths(
            {p: named[f"cache/{p}"] for p in tree_paths(cache_like)}, cache_like
        )
    cross = None
    if cross_like is not None:
        cross = tree_from_paths(
            {p: named[f"cross/{p}"] for p in tree_paths(cross_like)}, cross_like
        )
    return params, cache, cross

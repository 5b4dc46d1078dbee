"""Live-path resharder over jax.Arrays (paper §4.6.2 on the live worlds).

Both entry points execute a :class:`TransferPlan` through the shared
:class:`~repro.reshard.engine.ReshardEngine` + LiveExecutor — the same
protocol code the simulated-rank oracle runs, so chunking, staging bounds
and byte accounting cannot diverge between the two paths:

  * :func:`live_reshard_planned` — the controller's path: an intersection
    plan (core/intersection.py) computed from the model's resource view
    drives layer-ordered streaming of named state collections.
  * :func:`live_reshard` — plan-less pytree fallback (checkpoint resume,
    ad-hoc relayouts): synthesizes a one-task-per-leaf plan (each leaf its
    own streaming "layer") and runs the same engine, so oversized leaves
    are chunked by the shared chunker rather than a private loop.

Memory: the plan-less path with ``donate=True`` frees each source leaf as
its layer lands, so peak stays ~1x state + staging (invariant I2). The
plan-driven controller path keeps both worlds' storage resident until the
pointer swap — that is the paper's Active/Shadow coexistence, and the
destination storage is required for training regardless (Theorem 1,
item 2); the *transfer* overhead beyond it is still bounded by the
staging budget. On TPU pods the underlying ``device_put``/pack/unpack
lower to ICI DMA copies computed from exactly the kind of
shard-intersection the planner emits.
"""

from __future__ import annotations

import math
import time
from dataclasses import dataclass, field
from typing import Any, Optional

import jax
import numpy as np

from repro.configs.base import ModelConfig, ParallelConfig
from repro.core.intersection import TransferPlan, TransferTask, plan_transfer
from repro.core.resource_view import TensorSpec, build_tensor_specs
from repro.reshard.engine import (
    DEFAULT_STAGING_BYTES,
    ReshardEngine,
    StreamStats,
)
from repro.reshard.executors import LiveExecutor
from repro.utils.pytree import tree_from_paths, tree_paths

__all__ = [
    "DEFAULT_STAGING_BYTES",
    "ReshardReport",
    "live_reshard",
    "live_reshard_planned",
    "named_state_leaves",
    "plan_state_transfer",
    "rebuild_state",
]


@dataclass
class ReshardReport:
    leaves: int = 0
    chunked_leaves: int = 0
    moved_bytes: int = 0
    seconds: float = 0.0
    max_inflight_bytes: int = 0
    stats: Optional[StreamStats] = None


def _leaf_bytes(x) -> int:
    return int(math.prod(x.shape)) * np.dtype(x.dtype).itemsize


# ---------------------------------------------------------------------------
# Plan-less pytree path (fallback: checkpoint resume, ad-hoc relayout)
# ---------------------------------------------------------------------------


def live_reshard(
    state: Any,
    target_shardings: Any,
    staging_bytes: int = DEFAULT_STAGING_BYTES,
    donate: bool = True,
) -> tuple[Any, ReshardReport]:
    """Reshard a pytree of jax.Arrays to new shardings, leaf-streamed.

    Returns (new_state, report). Leaves already laid out as requested are
    passed through untouched (delta optimization). Each remaining leaf is
    a one-task streaming layer of a synthetic plan; the shared engine
    chunks oversized leaves to the staging budget. With ``donate=True``
    (default) each source leaf's device buffers are freed as soon as its
    layer lands — peak memory stays ~1x state + staging; the caller must
    not touch the input tree again. ``donate=False`` keeps sources intact
    (fallback safety: the Active World's storage must stay valid until
    commit — invariant I4).
    """
    flat, treedef = jax.tree_util.tree_flatten(state)
    flat_sh = treedef.flatten_up_to(target_shardings)
    report = ReshardReport()
    t0 = time.perf_counter()

    specs: list[TensorSpec] = []
    tasks: list[TransferTask] = []
    move_sh: dict[str, Any] = {}
    out_leaves: dict[int, Any] = {}
    for i, (leaf, sh) in enumerate(zip(flat, flat_sh)):
        report.leaves += 1
        if getattr(leaf, "sharding", None) == sh:
            out_leaves[i] = leaf  # delta optimization: zero-copy no-op
            continue
        name = f"leaf{i}"
        shape = tuple(int(d) for d in leaf.shape)
        nbytes = _leaf_bytes(leaf)
        specs.append(
            TensorSpec(
                name=name,
                shape=shape,
                dtype=str(leaf.dtype),
                roles=("none",) * len(shape),
                stage_scope="all",
                collection="state",
            )
        )
        # src 0 -> dst 1: fictitious ranks; "non-local" so the engine runs
        # the chunked staging path (rank identity is meaningless here)
        tasks.append(
            TransferTask(
                tensor=name,
                collection="state",
                src_rank=0,
                dst_rank=1,
                bounds=tuple((0, d) for d in shape),
                src_offset=(0,) * len(shape),
                dst_offset=(0,) * len(shape),
                nbytes=nbytes,
                layer=i,  # one streaming layer per leaf
            )
        )
        move_sh[name] = sh
        if nbytes > staging_bytes and leaf.ndim >= 1 and shape[0] > 1:
            report.chunked_leaves += 1

    if tasks:
        plan = TransferPlan(tasks=tasks, cfg_src=None, cfg_dst=None)
        spec_map = {s.name: s for s in specs}
        src = {t.tensor: flat[int(t.tensor[4:])] for t in tasks}
        executor = LiveExecutor(
            spec_map, src, move_sh, staging_bytes, free_sources=donate
        )
        engine = ReshardEngine(plan, executor, staging_bytes=staging_bytes)
        stats = engine.run()
        t1 = time.perf_counter()
        executor.block_until_ready()
        stats.drain_seconds += time.perf_counter() - t1
        for t in tasks:
            out_leaves[int(t.tensor[4:])] = executor.results()[t.tensor]
        report.moved_bytes += stats.network_bytes + stats.local_bytes
        report.max_inflight_bytes = stats.peak_staging_bytes
        report.stats = stats

    report.seconds = time.perf_counter() - t0
    out = [out_leaves[i] for i in range(len(flat))]
    return jax.tree_util.tree_unflatten(treedef, out), report


# ---------------------------------------------------------------------------
# Plan-driven path (the controller's live transfer)
# ---------------------------------------------------------------------------


def plan_state_transfer(
    cfg: ModelConfig,
    cfg_src: ParallelConfig,
    cfg_dst: ParallelConfig,
    source_policy: str = "nearest",
    allowed_src=None,
) -> tuple[list[TensorSpec], TransferPlan]:
    """Specs + intersection plan for the live training state.

    ``zero_sharding=False``: the live runtime shards optimizer moments like
    parameters (distribution/sharding.py), not ZeRO-split, so the plan's
    byte accounting matches what actually moves.

    ``allowed_src`` restricts sources to a survivor set (peer recovery,
    DESIGN.md §15); cells nobody in the set can donate come back as
    ``kind == "lost"``.
    """
    from repro.models.transformer import layer_stacks

    specs = build_tensor_specs(cfg, include_optimizer=True, zero_sharding=False)
    plan = plan_transfer(
        specs,
        cfg_src,
        cfg_dst,
        source_policy=source_policy,
        layer_granular=True,
        stacks=layer_stacks(cfg),
        allowed_src=allowed_src,
    )
    return specs, plan


def named_state_leaves(params: Any, opt_state: Any) -> tuple[dict[str, Any], dict]:
    """Flatten live training state into the resource view's tensor names.

    Returns (named leaves spanning params/mu/nu, leftovers) — leftovers
    (step count, error-feedback buffers, …) are not in the resource view
    and reshard through the plan-less fallback.
    """
    named: dict[str, Any] = {}
    for path, leaf in tree_paths(params).items():
        named[f"params/{path}"] = leaf
    extras: dict = {}
    for coll, sub in opt_state.items():
        if coll in ("mu", "nu"):
            for path, leaf in tree_paths(sub).items():
                named[f"{coll}/{path}"] = leaf
        else:
            extras[coll] = sub
    return named, extras


def rebuild_state(
    named: dict[str, Any], params_like: Any, opt_like: Any, extras: dict
) -> tuple[Any, Any]:
    """Inverse of named_state_leaves."""
    p_paths = {p: named[f"params/{p}"] for p in tree_paths(params_like)}
    params = tree_from_paths(p_paths, params_like)
    opt: dict[str, Any] = {}
    for coll, sub in opt_like.items():
        if coll in ("mu", "nu"):
            opt[coll] = tree_from_paths(
                {p: named[f"{coll}/{p}"] for p in tree_paths(sub)}, sub
            )
        else:
            opt[coll] = extras[coll]
    return params, opt


def live_reshard_planned(
    specs: list[TensorSpec],
    plan: TransferPlan,
    named_leaves: dict[str, Any],
    target_shardings: dict[str, Any],
    staging_bytes: int = DEFAULT_STAGING_BYTES,
    layers: Optional[list[int]] = None,
    wire_policy=None,
    wire_bw_bytes_s: float | None = None,
) -> tuple[dict[str, Any], StreamStats]:
    """Execute an intersection plan on live jax.Arrays via the shared
    engine. Returns (destination leaves by tensor name, stats).

    ``wire_policy`` (None = lossless) selects the per-collection wire
    format for remote chunks; ``wire_bw_bytes_s`` enables the executor's
    emulated-interconnect timing (benchmarks only)."""
    spec_map = {s.name: s for s in specs}
    executor = LiveExecutor(
        spec_map, named_leaves, target_shardings, staging_bytes,
        wire_policy=wire_policy, wire_bw_bytes_s=wire_bw_bytes_s,
    )
    engine = ReshardEngine(
        plan, executor, staging_bytes=staging_bytes, wire_policy=wire_policy
    )
    stats = engine.run(layers)
    t1 = time.perf_counter()
    executor.block_until_ready()
    stats.drain_seconds += time.perf_counter() - t1
    return executor.results(), stats

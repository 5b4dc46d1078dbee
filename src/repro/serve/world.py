"""Serving world construction — the decode analogue of ``core/shadow.py``'s
``build_train_world``, returning the same :class:`WorldHandle` so serving
worlds are first-class citizens of the warm :class:`WorldPool`:

  * ``step_fn``   — AOT-compiled batched decode step (one token per slot)
  * ``update_fn`` — AOT-compiled prefill (wave admission)
  * ``shardings`` — role-derived layouts for params/cache/cross, plus the
    by-name map the reshard executor targets at commit

Serving worlds are pp=1 (decode is a single-stage scan); tp/dp/ep vary
across resizes. Built inside a ShadowBuilder thread during Prepare, or
served warm from the pool.
"""

from __future__ import annotations

import time
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ParallelConfig
from repro.core.shadow import WorldHandle
from repro.kernels import ops
from repro.serve.cache_view import serve_state_specs, target_shardings_by_name
from repro.utils.pytree import tree_from_paths, tree_paths

__all__ = ["build_serve_world"]


def _sharding_tree(by_name: dict, prefix: str, like) -> dict:
    """Per-leaf sharding pytree for ``like`` from the by-name map."""
    return tree_from_paths(
        {p: by_name[f"{prefix}/{p}"] for p in tree_paths(like)}, like
    )


def build_serve_world(
    cfg: ModelConfig,
    parallel: ParallelConfig,
    n_slots: int,
    prompt_len: int,
    max_seq: int,
    devices=None,
    cache_dtype=jnp.float32,
    frames_len: int = 16,
    aot: bool = True,
) -> WorldHandle:
    """Synchronous serving-world construction (the shadow thread's body)."""
    from repro.distribution.sharding import make_elastic_mesh
    from repro.models import kvcache
    from repro.models import model as M

    assert parallel.pp == 1, "serving worlds are single-stage (pp=1)"
    timings: dict = {}
    t0 = time.perf_counter()
    mesh = make_elastic_mesh(parallel, devices=devices)
    timings["mesh_s"] = time.perf_counter() - t0

    cross_len = frames_len if cfg.family == "encdec" else 0
    specs = serve_state_specs(
        cfg, n_slots, max_seq, cache_dtype=cache_dtype, cross_len=cross_len
    )
    by_name = target_shardings_by_name(specs, mesh)
    rep = NamedSharding(mesh, P())

    aparams = M.abstract_params(cfg)
    acache = M.abstract_cache(cfg, n_slots, max_seq, dtype=cache_dtype)
    psh = _sharding_tree(by_name, "params", aparams)
    csh = _sharding_tree(by_name, "cache", acache)
    xsh = None
    across = None
    if cfg.family == "encdec":
        across = jax.eval_shape(
            lambda: kvcache.init_cross_kv(cfg, n_slots, cross_len, cache_dtype)
        )
        xsh = _sharding_tree(by_name, "cross", across)

    if cfg.family == "encdec":
        decode_fn = jax.jit(
            lambda p, c, t, pos, x: M.decode_step(cfg, p, c, t, pos, x),
            in_shardings=(psh, csh, rep, rep, xsh),
            out_shardings=(rep, csh),
        )
    else:
        decode_fn = jax.jit(
            lambda p, c, t, pos: M.decode_step(cfg, p, c, t, pos),
            in_shardings=(psh, csh, rep, rep),
            out_shardings=(rep, csh),
        )
    prefill_fn = jax.jit(
        ops.with_kernel_mesh(
            lambda p, b: M.prefill(cfg, p, b, cache_dtype=cache_dtype, max_seq=max_seq),
            mesh,
        ),
        in_shardings=(psh, rep),
        out_shardings=(rep, csh, xsh),
    )

    step_fn, update_fn = decode_fn, prefill_fn
    if aot:
        atok = jax.ShapeDtypeStruct((n_slots, 1), jnp.int32)
        apos = jax.ShapeDtypeStruct((), jnp.int32)
        dargs = (aparams, acache, atok, apos) + (
            (across,) if cfg.family == "encdec" else ()
        )
        abatch = {"tokens": jax.ShapeDtypeStruct((n_slots, prompt_len), jnp.int32)}
        if cfg.family == "encdec":
            abatch["frames"] = jax.ShapeDtypeStruct(
                (n_slots, frames_len, cfg.d_model), jnp.dtype(cfg.dtype)
            )
        t0 = time.perf_counter()
        lowered_d = decode_fn.lower(*dargs)  # mock-warmup analogue
        lowered_p = prefill_fn.lower(aparams, abatch)
        timings["lower_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        step_fn = lowered_d.compile()  # communicator-setup analogue
        update_fn = lowered_p.compile()
        timings["compile_s"] = time.perf_counter() - t0

    return WorldHandle(
        parallel=parallel,
        mesh=mesh,
        step_fn=step_fn,
        shardings={
            "by_name": by_name,
            "params": psh,
            "cache": csh,
            "cross": xsh,
            "replicated": rep,
        },
        timings=timings,
        update_fn=update_fn,
        plan_bundle=specs,
    )

"""Shadow World construction (paper §4.4 'Parallel Worlds').

While the Active World keeps training, a background thread (the Companion
Manager's worker) builds the target world. The JAX mapping of the paper's
Prepare phase:

  1. mesh construction over the target device set   (process-group analogue)
  2. ``lower()`` — trace + StableHLO + sharding inference. Device-free: this
     IS the mock-process-group warmup (local work, no coordination); the
     standalone abstract-mesh variant lives in core/mock_groups.py.
  3. ``compile()`` — XLA compilation + executable load onto the target
     devices (the NCCL-communicator-setup + JIT-warmup analogue).

All three run off the critical path; §6.3's steady-state-interference
experiment is reproduced in benchmarks/bench_interference.py.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import jax
from jax.sharding import Mesh

from repro.configs.base import ModelConfig, ParallelConfig
from repro.utils.spans import span


@dataclass
class WorldHandle:
    """Everything the training loop needs from a world: the communicator
    analogue (mesh) + pre-compiled executable + shardings."""

    parallel: ParallelConfig
    mesh: Mesh
    step_fn: Callable  # compiled train step (jax.stages.Compiled)
    shardings: Any  # (param_sh, opt_sh, batch_sh)
    gen_id: int = -1
    timings: dict = field(default_factory=dict)
    # split-step commit executables (overlapped reconfiguration): the
    # optimizer-only step for THIS world (compiled in the shadow thread so
    # it never touches the critical path), and a grads-only step compiled
    # on demand for the world being left.
    update_fn: Optional[Callable] = None
    grad_fn: Optional[Callable] = None
    # (src ParallelConfig, specs, TransferPlan) computed during Prepare so
    # the commit pause never pays the planning cost
    plan_bundle: Any = None
    released: bool = False

    def release(self) -> None:
        """Drop the executables/mesh/sharding references so device memory
        (compiled programs and their embedded constants) is reclaimable
        immediately instead of whenever GC finds the handle. Idempotent;
        a released handle must never be trained on or pooled again."""
        self.step_fn = None
        self.update_fn = None
        self.grad_fn = None
        self.shardings = None
        self.mesh = None
        self.plan_bundle = None
        self.released = True


class ShadowBuilder:
    """Builds a WorldHandle in a background thread; poll ``ready`` — the
    Companion Manager thread of the paper's §4.5.1.

    ``on_discard`` is invoked exactly once with the completed handle when
    the builder was abandoned — from the worker thread if the abandon
    preceded completion, from ``abandon()`` itself otherwise. The default
    releases the world's device memory (an orphaned build used to pin its
    mesh + executables until GC); the controller overrides it to deposit
    the world into the warm :class:`~repro.core.world_pool.WorldPool`.
    """

    def __init__(
        self,
        build_fn: Callable[[], WorldHandle],
        gen_id: int,
        on_discard: Optional[Callable[[WorldHandle], None]] = None,
    ):
        self._build_fn = build_fn
        self.gen_id = gen_id
        self._result: Optional[WorldHandle] = None
        self._error: Optional[BaseException] = None
        self._done = threading.Event()
        # non-daemon: a daemon thread killed inside an XLA compile at
        # interpreter exit segfaults/aborts the process; Python joins
        # non-daemon threads cleanly (exit waits out an in-flight build
        # instead of crashing)
        self._thread = threading.Thread(target=self._run, daemon=False)
        # stamped when the worker thread starts, NOT at construction:
        # callers (the warm pool above all) routinely construct builders
        # well before starting them, and stamping in __init__ silently
        # inflated prepare_total_s by the construction→start gap
        self.started_at: Optional[float] = None
        self.abandoned = False
        self._on_discard = on_discard
        self._discard_lock = threading.Lock()
        self._discarded = False

    def start(self) -> "ShadowBuilder":
        self._thread.start()
        return self

    def _run(self) -> None:
        try:
            with span("liver/prepare", gen=self.gen_id) as prepare:
                self.started_at = prepare.t0
                handle = self._build_fn()
                handle.gen_id = self.gen_id
            handle.timings["prepare_total_s"] = prepare.seconds
            self._result = handle
        except BaseException as e:  # surfaced on result()
            self._error = e
        finally:
            self._done.set()
        self._maybe_discard()

    @property
    def ready(self) -> bool:
        return self._done.is_set()

    def _maybe_discard(self) -> None:
        with self._discard_lock:
            if not self.abandoned or self._discarded or self._result is None:
                return
            self._discarded = True
            handle = self._result
        if self._on_discard is not None:
            self._on_discard(handle)
        else:
            handle.release()

    def abandon(self) -> None:
        """Retarget/cancel semantics (paper §7 'Concurrent reconfiguration
        events'): the worker thread cannot be killed mid-``compile()``, so
        the builder is marked abandoned and its world discarded on
        completion (``on_discard`` — release or pool deposit; it no longer
        lingers until GC). The controller may start a fresh builder
        immediately — the stale thread only ever writes into this object."""
        self.abandoned = True
        if self._done.is_set():
            self._maybe_discard()

    def result(self, timeout: Optional[float] = None) -> WorldHandle:
        if not self._done.wait(timeout):
            raise TimeoutError("shadow world not ready")
        if self._error is not None:
            raise self._error
        assert self._result is not None
        return self._result


def abstract_batch(cfg: ModelConfig, global_batch: int, seq_len: int) -> dict:
    """Abstract batch pytree for AOT lowering (and its compile-time shape
    contract). ``frames`` resolves the configured compute dtype through
    ``jnp.dtype`` — the old two-entry ``{"bfloat16","float32"}`` literal
    map raised KeyError for every other configured dtype (float16, fp8
    experiments, ...)."""
    import jax.numpy as jnp

    abatch = {"tokens": jax.ShapeDtypeStruct((global_batch, seq_len), jnp.int32)}
    if cfg.family == "encdec":
        abatch["frames"] = jax.ShapeDtypeStruct(
            (global_batch, seq_len, cfg.d_model), jnp.dtype(cfg.dtype)
        )
    return abatch


def _abstract_opt(cfg: ModelConfig, aparams, compression: str):
    """Abstract optimizer state matching ``adamw_init`` (+ error-feedback
    buffers under int8_ef compression)."""
    import jax.numpy as jnp

    from repro.optim import adamw_init

    aopt = jax.eval_shape(lambda: adamw_init(aparams))
    if compression == "int8_ef":
        aopt = dict(aopt)
        aopt["ef"] = jax.tree_util.tree_map(
            lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32), aparams
        )
    return aopt


def build_update_world_fn(
    cfg: ModelConfig,
    mesh: Mesh,
    parallel: ParallelConfig,
    opt_cfg,
    compression: str = "none",
    aot: bool = True,
):
    """Optimizer-only executable for the split-step commit of ``mesh``'s
    world. Factored out of :func:`build_train_world` so a warm pool hit
    whose cached handle predates split-step mode can backfill ``update_fn``
    without re-running the full Prepare."""
    from repro.distribution.step import jit_update_step
    from repro.models.model import abstract_params

    jitted_u, _ = jit_update_step(
        cfg, mesh, opt_cfg, compression=compression, parallel=parallel
    )
    if not aot:
        return jitted_u
    aparams = abstract_params(cfg)
    aopt = _abstract_opt(cfg, aparams, compression)
    agrads = jax.tree_util.tree_map(
        lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype), aparams
    )
    return jitted_u.lower(agrads, aopt, aparams).compile()


def build_train_world(
    cfg: ModelConfig,
    parallel: ParallelConfig,
    opt_cfg,
    global_batch: int,
    seq_len: int,
    microbatches: int = 1,
    devices=None,
    compression: str = "none",
    aot: bool = True,
    hint_version: str | None = None,
    split_step: bool = False,
) -> WorldHandle:
    """Synchronous world construction (the shadow thread's body)."""
    from repro.distribution.sharding import make_elastic_mesh
    from repro.distribution.step import jit_train_step
    from repro.models.model import abstract_params

    timings: dict = {}
    with span("liver/world/mesh") as ph:
        mesh = make_elastic_mesh(parallel, devices=devices)
    timings["mesh_s"] = ph.seconds

    if parallel.pp > 1:
        from repro.distribution.pipeline import jit_pipeline_train_step

        jitted, shardings = jit_pipeline_train_step(
            cfg, mesh, parallel, opt_cfg, global_batch, max(microbatches, parallel.pp)
        )
    else:
        jitted, shardings = jit_train_step(
            cfg,
            mesh,
            opt_cfg,
            global_batch,
            microbatches=microbatches,
            compression=compression,
            hint_version=hint_version,
        )

    step_fn = jitted
    if aot:
        aparams = abstract_params(cfg)
        aopt = _abstract_opt(cfg, aparams, compression)
        abatch = abstract_batch(cfg, global_batch, seq_len)
        with span("liver/world/lower") as ph:
            lowered = jitted.lower(aparams, aopt, abatch)  # mock-warmup analogue
        timings["lower_s"] = ph.seconds
        with span("liver/world/compile") as ph:
            step_fn = lowered.compile()  # communicator-setup analogue
        timings["compile_s"] = ph.seconds

    update_fn = None
    if split_step:
        # optimizer-only executable for the split-step commit: compiled
        # here, in the shadow thread, so the commit pause never pays it
        with span("liver/world/update_compile") as ph:
            update_fn = build_update_world_fn(
                cfg, mesh, parallel, opt_cfg, compression=compression, aot=aot
            )
        timings["update_compile_s"] = ph.seconds

    return WorldHandle(
        parallel=parallel,
        mesh=mesh,
        step_fn=step_fn,
        shardings=shardings,
        timings=timings,
        update_fn=update_fn,
    )

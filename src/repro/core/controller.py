"""LiveR controller (paper §4.3 end-to-end workflow, §4.7 switch).

Orchestrates the full reconfiguration lifecycle on live JAX state:

  trigger → Prepare (shadow thread: mesh + AOT compile)  [overlapped, I1]
          → Ready   (await iteration boundary)           [deterministic, I3]
          → Switch  (drain → live reshard → pointer swap) [the only pause]
          → Cleanup (free old world asynchronously)
          → Stable

plus the fail-stop fallback to durable checkpoints (invariant I4), the
stop-and-restart / checkpoint-reshape (UCP) baselines used by the
benchmarks, and the event-stream verbs the deadline scheduler drives
(DESIGN.md §10): per-request transfer-mode override, ``retarget_resize``
(supersede the in-flight reconfiguration, adopting its streamed state)
and ``escalate_commit`` (deadline-pressure stop-copy).
"""

from __future__ import annotations

import os
import threading
import time
import traceback
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import AsyncCheckpointer, load_checkpoint, save_checkpoint
from repro.configs.base import ModelConfig, ParallelConfig
from repro.core.downtime import GoodputLedger
from repro.core.generations import GenerationMachine, GenState
from repro.core.reshard import (
    DEFAULT_STAGING_BYTES,
    live_reshard,
    live_reshard_planned,
    named_state_leaves,
    plan_state_transfer,
    rebuild_state,
)
from repro.core.shadow import (
    ShadowBuilder,
    WorldHandle,
    abstract_batch,
    build_train_world,
    build_update_world_fn,
)
from repro.core.records import ReuseRecordMixin
from repro.core.world_pool import WorldPool
from repro.data import SyntheticLM
from repro.optim import AdamWConfig
from repro.reshard import OverlapSession
from repro.utils.pytree import tree_paths


@dataclass
class ReconfigRecord(ReuseRecordMixin):
    # reused_layers / resident_layers / skipped_bytes come from the shared
    # ReuseRecordMixin (classified plan IR, DESIGN.md §13)
    gen_id: int
    src: str
    dst: str
    prepare_s: float = 0.0
    drain_s: float = 0.0
    transfer_s: float = 0.0
    switch_s: float = 0.0
    total_pause_s: float = 0.0
    moved_bytes: int = 0
    # live | live_overlap | restart | ucp_restart | peer_recover | fallback
    mode: str = "live"
    # per-event disposition (DESIGN.md §10 fallback lattice):
    #   committed  — the reconfiguration completed via its requested path
    #   retargeted — superseded by a newer event before commit (its partial
    #                streamed state may have been adopted by the successor)
    #   fell_back  — completed, but via a downgraded path (stop-copy under
    #                deadline pressure, or checkpoint restore)
    #   aborted    — abandoned without completing
    outcome: str = "committed"
    # Prepare served from the warm world pool (or residual shadow work):
    # lower+compile skipped entirely. The DeadlineEstimator keeps separate
    # warm/cold prepare estimates keyed on this flag.
    warm_hit: bool = False
    # how Prepare was served: "cold" (full build) | "pool" | "residual" |
    # "speculative_join" (joined an in-flight prefetch — measures neither a
    # warm nor a cold Prepare, so both estimators exclude it)
    prepare_source: str = "cold"
    # plan-vs-live agreement (both sides from the one ReshardEngine path)
    plan_network_bytes: int = 0
    plan_local_bytes: int = 0
    executed_bytes: int = 0
    plan_s: float = 0.0  # planning time (0.0 when planned in the shadow thread)
    # overlapped-streaming phases (zero under stop-copy)
    precopy_s: float = 0.0
    precopy_bytes: int = 0
    resync_s: float = 0.0
    resync_bytes: int = 0
    update_s: float = 0.0
    dirty_layers: int = 0
    layers_total: int = 0
    # async data-plane attribution: host time issuing device programs vs
    # blocking for them, and cells that fell off the row-merge fast path
    # (a growing generic_cells count flags a slow-path regression)
    stream_dispatch_s: float = 0.0
    stream_drain_s: float = 0.0
    generic_cells: int = 0
    # resident_cells / skipped_bytes / wire_bytes / logical_bytes come from
    # the mixin; the tuned data-plane parameters this reconfig ran with
    # (None = the hand-set fallback constants, DESIGN.md §14)
    operating_point: Optional[dict] = None
    # peer recovery (DESIGN.md §15): how a fail-stop was sourced
    donors: int = 0  # distinct surviving ranks that donated cells
    lost_devices: int = 0  # ranks lost to the failure
    parity_bytes: int = 0  # bytes reconstructed from the XOR parity word


class LiveRController:
    def __init__(
        self,
        cfg: ModelConfig,
        parallel: ParallelConfig,
        opt_cfg: AdamWConfig,
        seq_len: int,
        global_batch: int,
        data: Optional[SyntheticLM] = None,
        ckpt_dir: Optional[str] = None,
        ckpt_interval: int = 50,
        staging_bytes: int = DEFAULT_STAGING_BYTES,
        devices=None,
        microbatches: int = 1,
        compression: str = "none",
        hint_version: str | None = None,
        seed: int = 0,
        overlap: str = "stop_copy",  # "stop_copy" | "stream"
        stream_k: int = 4,
        source_policy: str = "nearest",
        sync_compile: bool = False,
        world_pool: Optional[WorldPool] = None,
        max_spec_builds: int = 1,
        wire_policy=None,
        wire_bw_bytes_s: float | None = None,
        parity_every: int = 0,
    ):
        self.cfg = cfg
        self.opt_cfg = opt_cfg
        self.seq_len = seq_len
        self.global_batch = global_batch
        self.staging_bytes = staging_bytes
        self.devices = devices if devices is not None else jax.devices()
        self.microbatches = microbatches
        self.compression = compression
        self.hint_version = hint_version
        assert overlap in ("stop_copy", "stream"), overlap
        self.overlap = overlap
        # per-reconfiguration override (request_resize(..., overlap=...));
        # resets to the constructor default when the reconfig retires
        self._overlap_mode = overlap
        self.stream_k = stream_k
        self.source_policy = source_policy
        # compressed wire format (DESIGN.md §14): None = fully lossless.
        # Distinct from ``compression`` (gradient all-reduce int8+EF): the
        # wire policy shapes what the RESHARD stream sends, per collection.
        self.wire_policy = wire_policy
        # emulated interconnect bandwidth for the live executors (benchmarks
        # only; None on real hardware)
        self.wire_bw_bytes_s = wire_bw_bytes_s
        # per-reconfiguration tuned operating point (reshard.autotune),
        # installed by request_resize/retarget_resize; None = fallbacks
        self._operating_point = None
        # deterministic mode for parity tests / --check benchmark gates:
        # compile the split-step grad executable inline instead of in a
        # background thread, so the commit step index is reproducible
        self.sync_compile = sync_compile
        # streamed state captured from a superseded session at retarget,
        # consumed by the next _start_overlap_session
        self._reuse: Optional[tuple] = None
        self._session: Optional[OverlapSession] = None
        self._session_specs = None
        self._session_plan = None
        self._session_targets = None
        self._pending_rec: Optional[ReconfigRecord] = None
        self._commit_armed = False
        self._grad_builder = None
        self.machine = GenerationMachine()
        self.ledger = GoodputLedger()
        self.records: list[ReconfigRecord] = []
        self.iteration_times: list[float] = []
        self.step = 0
        self.data = data or SyntheticLM(cfg.vocab_size, seq_len, global_batch, seed)
        self.ckpt_dir = ckpt_dir
        self.ckpt_interval = ckpt_interval
        self._ckpt = AsyncCheckpointer(ckpt_dir) if ckpt_dir else None
        self._builder: Optional[ShadowBuilder] = None
        # speculative warm world pool (DESIGN.md §12): retired/abandoned/
        # prefetched worlds keyed by pool_key; warm hits skip lower+compile
        self.world_pool = world_pool
        self.max_spec_builds = max_spec_builds
        self._spec_builders: dict[tuple, ShadowBuilder] = {}
        # transfer-executable prewarm (DESIGN.md §15): (src, dst) pairs
        # whose reshard compiles already ran off the critical path
        self._prewarmed_pairs: set = set()
        self._prewarm_thread: Optional[threading.Thread] = None
        self._prewarm_pair: Optional[tuple] = None
        self._inflight_target: Optional[ParallelConfig] = None
        # spare-shard scheme (DESIGN.md §15): refresh the XOR parity words
        # every N idle step boundaries so dp=1 worlds can reconstruct a
        # shard whose only owner died; 0 disables
        self.parity_every = parity_every
        self._parity = None
        # errors that speculation, prewarm and warm-pool paths swallow by
        # design (training must go on); one line each, so a caller that
        # must know — a smoke run, a benchmark gate — can see them
        self.swallowed_errors: list[str] = []
        # verification hook: called at every live commit as
        # fn((params, opt_state) at the cut on the old world,
        #    (params, opt_state) moved onto the new world, before any update)
        self.commit_observer: Optional[Callable[[tuple, tuple], None]] = None

        # Active World (generation 0). With a pool, every world is built
        # split-step so its update_fn is already warm if it later serves a
        # streamed resize out of the pool.
        world = self._build_world(parallel, split_step=world_pool is not None)
        world.gen_id = 0
        self.machine.active.payload = world
        from repro.distribution.step import init_train_state

        self.params, self.opt_state = init_train_state(
            cfg, world.mesh, seed=seed, compression=compression
        )

    # ------------------------------------------------------------------
    @property
    def world(self) -> WorldHandle:
        return self.machine.active.payload

    def _device_subset(self, parallel: ParallelConfig):
        return self.devices[: parallel.world_size]

    def _build_world(self, target: ParallelConfig, split_step: bool) -> WorldHandle:
        return build_train_world(
            self.cfg,
            target,
            self.opt_cfg,
            self.global_batch,
            self.seq_len,
            microbatches=self.microbatches,
            devices=self._device_subset(target),
            compression=self.compression,
            hint_version=self.hint_version,
            split_step=split_step,
        )

    # ------------------------------------------------------------------
    # Warm world pool (DESIGN.md §12)
    # ------------------------------------------------------------------
    def pool_key(self, target: ParallelConfig) -> tuple:
        """Pool identity of the world this controller would build for
        ``target``: everything that shapes the compiled executables, plus
        the device-set fingerprint — a world is warm only for the exact
        devices its executables were loaded onto."""
        fingerprint = tuple(
            getattr(d, "id", i) for i, d in enumerate(self._device_subset(target))
        )
        return (
            self.cfg,
            target,
            fingerprint,
            self.global_batch,
            self.seq_len,
            self.microbatches,
            self.compression,
            self.hint_version,
        )

    def _refresh_pooled(
        self, handle: WorldHandle, mode: str, source: str = "pool"
    ) -> WorldHandle:
        """Revalidate a warm world for use as the pending shadow: backfill
        the split-step executable if this reconfiguration streams and the
        cached build predates split-step mode (pool-bound builds always
        split-step, so this is the rare path), and tag the timings so the
        ReconfigRecord/DeadlineEstimator can tell warm from cold."""
        assert not handle.released, "warm world was released while pooled"
        handle.timings = dict(handle.timings)
        handle.timings["warm_hit"] = source == "pool"
        handle.timings["prepare_source"] = source
        handle.plan_bundle = None  # src-dependent: always replanned below
        if mode == "stream" and handle.update_fn is None:
            t0 = time.perf_counter()
            handle.update_fn = build_update_world_fn(
                self.cfg, handle.mesh, handle.parallel, self.opt_cfg,
                compression=self.compression,
            )
            handle.timings["update_compile_s"] = time.perf_counter() - t0
        return handle

    def _discard_world(self, handle: WorldHandle) -> None:
        """An abandoned builder's completed world: keep it warm when a pool
        exists (bounded — LRU eviction releases it), release its device
        memory immediately otherwise. Runs on the orphaned build thread
        when the abandon preceded completion; the pool is thread-safe."""
        if self.world_pool is not None and not handle.released:
            handle.gen_id = -1
            handle.plan_bundle = None
            self.world_pool.put(self.pool_key(handle.parallel), handle)
        else:
            handle.release()

    def _retire_world(self, old_gen) -> None:
        """Post-switch cleanup of the outgoing generation. With a pool the
        old world stays warm — resizing back to a recently-left
        configuration is the dominant elasticity pattern (walk-down then
        walk-up) — otherwise the reference simply drops."""
        world, old_gen.payload = old_gen.payload, None
        if world is None or self.world_pool is None or world.released:
            return
        world.gen_id = -1
        world.plan_bundle = None
        self.world_pool.put(self.pool_key(world.parallel), world)

    def _harvest_spec_builders(self) -> None:
        """Deposit completed speculative builds into the pool. Build errors
        are swallowed: speculation must never take down training (the same
        target requested for real will rebuild — and re-raise — on the
        normal path)."""
        for key in [k for k, b in self._spec_builders.items() if b.ready]:
            builder = self._spec_builders.pop(key)
            try:
                handle = builder.result(0)
            except BaseException as e:
                self.swallowed_errors.append(f"speculative build: {e!r}")
                continue
            self.world_pool.put(key, handle)

    def prefetch_world(self, target: ParallelConfig) -> bool:
        """Speculatively build ``target``'s world into the warm pool, off
        the critical path (daemon thread, same interference profile as a
        real Prepare). Never runs concurrently with a real reconfiguration
        — the one-live-shadow invariant I2 is about *generations*, which
        speculative builds never touch, but stacking compiles multiplies
        steady-state interference for no deadline benefit. Returns True
        when a build was started."""
        if self.world_pool is None or self.reconfig_pending:
            return False
        if target == self.world.parallel:
            return False
        key = self.pool_key(target)
        self._harvest_spec_builders()
        if self.world_pool.contains(key) or key in self._spec_builders:
            return False
        if len(self._spec_builders) >= self.max_spec_builds:
            return False
        self._spec_builders[key] = ShadowBuilder(
            lambda: self._build_world(target, split_step=True), gen_id=-1
        ).start()
        return True

    @staticmethod
    def _speculation_trace(msg: str) -> None:
        """Append one line to the file named by REPRO_PREWARM_TRACE (unset:
        no-op). Speculative threads swallow their failures by design — this
        is the only way to see what the speculation layer actually did."""
        path = os.environ.get("REPRO_PREWARM_TRACE")
        if not path:
            return
        try:
            with open(path, "a") as f:
                f.write(f"{time.perf_counter():.3f} {msg}\n")
        except OSError:
            pass

    def _derived_named_shardings(self, parallel: ParallelConfig) -> Optional[dict]:
        """Named state shardings a world under ``parallel`` WILL carry,
        derived from mesh + rules alone — no build, no compile (~ms).
        Lets the stream-ahead prewarm (§15) start at resize-request time
        instead of waiting for the shadow world. None when the layout
        can't be derived cheaply (pipeline worlds shard via the pipeline
        step builder)."""
        if parallel.pp > 1:
            return None
        from repro.distribution.sharding import make_elastic_mesh
        from repro.distribution.step import train_state_shardings

        try:
            mesh = make_elastic_mesh(parallel, devices=self.devices)
            ps, os_ = train_state_shardings(self.cfg, mesh)
        except BaseException:
            return None
        named = {}
        for p, sh in tree_paths(ps).items():
            named[f"params/{p}"] = sh
        for coll in ("mu", "nu"):
            for p, sh in tree_paths(os_[coll]).items():
                named[f"{coll}/{p}"] = sh
        return named

    def prewarm_failover_ahead(self) -> int:
        """During a resize, prewarm the transfer executables for
        (incoming world → pooled world) pairs — the incoming world's
        failover paths (§15). A window-0 event landing right after the
        commit otherwise pays the pair's cold compiles inside its pause:
        the pair is only knowable once the incoming world is, and that is
        knowable the moment the resize is requested — the state shardings
        it will carry are pure metadata (mesh + rules), no build needed.
        Returns prewarm threads started (≤1; one pair per tick)."""
        target = self._inflight_target
        if target is None or self.world_pool is None:
            return 0
        # same policy as the idle-tick loop: non-growing pairs only,
        # nearest size first (same-size retopology is the likeliest
        # window-0 target; grows come with windows and stream)
        needed = sorted(
            (
                key[1]
                for key in self.world_pool.keys()
                if key[1] != target
                and key[1].world_size <= target.world_size
                and (target, key[1]) not in self._prewarmed_pairs
            ),
            key=lambda p: target.world_size - p.world_size,
        )
        if not needed:
            return 0
        if self._prewarm_thread is not None and self._prewarm_thread.is_alive():
            return 0
        src_sh = self._derived_named_shardings(target)
        if src_sh is None:
            self._speculation_trace(f"ahead: no derived shardings for {target}")
            return 0
        started = 0
        for tgt in needed:
            if self.prewarm_transfer(
                tgt, src_parallel=target, src_shardings=src_sh
            ):
                started += 1
        return started

    def prewarm_transfer(
        self,
        target: ParallelConfig,
        src_parallel: Optional[ParallelConfig] = None,
        src_shardings: Optional[dict] = None,
    ) -> bool:
        """Compile the reshard executables for (current world → target)
        off the critical path, against a pooled world's shardings.

        A fail-stop recovery (§15) pays its transfer inside the pause, and
        a first-time (src, dst) pair spends most of that transfer in
        one-time pack/scatter/staging compiles — measured ~5× the warm
        transfer on the smoke workload. jax's jit cache is keyed on
        avals + shardings, so executing one throwaway transfer of the same
        plan against the same shardings warms every executable the real
        recovery will use (the recovery path is lossless, so the prewarm
        runs lossless too). Sources are throwaway zero arrays with the
        live leaves' avals + shardings — the train step donates the real
        buffers, so reading them from a background thread would race with
        training — and the results are discarded.

        With ``src_parallel``/``src_shardings`` the pair is
        (src world → target) instead of (current → target): the
        stream-ahead path (:meth:`prewarm_failover_ahead`) warms the
        incoming world's failover pairs while the resize toward it is
        still preparing/streaming. The live state will carry exactly
        those shardings after the commit; global shapes/dtypes are
        world-invariant. Returns True when a prewarm thread was started."""
        if self.world_pool is None:
            return False
        ahead = src_parallel is not None
        if not ahead and self.reconfig_pending:
            return False
        if src_parallel is None:
            src_parallel = self.world.parallel
        if target == src_parallel:
            return False
        pair = (src_parallel, target)
        if pair in self._prewarmed_pairs:
            return False
        if self._prewarm_thread is not None and self._prewarm_thread.is_alive():
            return False
        handle = self.world_pool.peek(self.pool_key(target))
        if handle is None or handle.released:
            return False
        self._speculation_trace(
            f"prewarm start {src_parallel.describe()}->{target.describe()} "
            f"ahead={ahead}"
        )
        self._prewarmed_pairs.add(pair)
        self._prewarm_pair = pair
        targets = self._named_target_shardings(handle)
        extra_sh = self._extra_shardings(handle)
        # Metadata-only snapshot: (name, shape, dtype, sharding) per leaf.
        # The arrays themselves must not escape to the thread — train steps
        # donate them, and a donated buffer read off-thread is a race.
        named, extras = named_state_leaves(self.params, self.opt_state)
        src_sh = (
            src_shardings
            if ahead
            else {n: a.sharding for n, a in named.items()}
        )
        named_meta = {
            n: (a.shape, a.dtype, src_sh[n]) for n, a in named.items()
        }
        if not ahead:
            extra_leaves, extra_treedef = jax.tree_util.tree_flatten(extras)
            extra_meta = [
                (a.shape, a.dtype, a.sharding) if hasattr(a, "sharding") else a
                for a in extra_leaves
            ]
        else:
            # extras (step count, error-feedback buffers) reshard through
            # the plan-less fallback whose programs are per-leaf trivial;
            # skip them rather than reconstruct their future shardings
            extra_leaves, extra_treedef, extra_meta = [], None, []

        def _zeros(shape, dtype, sharding):
            return jax.jit(
                lambda: jnp.zeros(shape, dtype), out_shardings=sharding
            )()

        def _warm() -> None:
            try:
                from repro.elastic.redundancy import (
                    balance_donors,
                    heal_plan,
                    survivors_for,
                )

                dummy_named = {
                    n: _zeros(*m) for n, m in named_meta.items()
                }
                # mirror fail_stop_recover's plan EXACTLY — the warned-rung
                # geometry (prefix complement of the target lost, sources
                # survivor-constrained, donors balanced). The jit cache is
                # keyed on the programs the plan's cells produce, so a
                # prewarm against any other plan warms nothing the
                # recovery pause will run.
                lost = tuple(
                    range(target.world_size, src_parallel.world_size)
                )
                survivors = survivors_for(
                    src_parallel, lost, target=target, devices_failed=False
                )
                specs, plan = plan_state_transfer(
                    self.cfg, src_parallel, target,
                    source_policy=self.source_policy,
                    allowed_src=survivors,
                )
                if plan.lost_tasks():
                    plan, _ = heal_plan(plan, specs)
                plan = balance_donors(plan, specs, survivors)
                live_reshard_planned(
                    specs, plan, dummy_named, targets,
                    staging_bytes=self.staging_bytes,
                    wire_policy=None,
                    wire_bw_bytes_s=self.wire_bw_bytes_s,
                )
                if extra_treedef is not None:
                    dummy_extras = jax.tree_util.tree_unflatten(
                        extra_treedef,
                        [
                            _zeros(*m) if isinstance(m, tuple) else m
                            for m in extra_meta
                        ],
                    )
                    live_reshard(
                        dummy_extras, extra_sh, staging_bytes=self.staging_bytes
                    )
                self._speculation_trace(
                    f"prewarm done {src_parallel.describe()}"
                    f"->{target.describe()} ahead={ahead}"
                )
            except BaseException as e:
                # speculation must never take down training; the real
                # transfer will compile (and surface errors) on its own
                self.swallowed_errors.append(f"transfer prewarm: {e!r}")
                self._speculation_trace(
                    f"prewarm FAILED {src_parallel.describe()}"
                    f"->{target.describe()} ahead={ahead}\n"
                    + traceback.format_exc()
                )

        # Non-daemon: a daemon thread killed inside an XLA compile at
        # interpreter exit aborts the process ("terminate called without
        # an active exception"); Python joins non-daemon threads cleanly.
        self._prewarm_thread = threading.Thread(
            target=_warm, name="transfer-prewarm", daemon=False
        )
        self._prewarm_thread.start()
        return True

    # ------------------------------------------------------------------
    # Prepare (background)
    # ------------------------------------------------------------------
    def request_resize(
        self,
        target: ParallelConfig,
        overlap: Optional[str] = None,
        operating_point=None,
    ) -> int:
        """Trigger: spawn Shadow World preparation. Non-blocking.

        ``overlap`` overrides the constructor's transfer mode for THIS
        reconfiguration only — the deadline scheduler uses it to downgrade
        a single event to stop-copy without flipping the whole controller.
        ``operating_point`` (reshard.autotune.OperatingPoint) likewise
        overrides ``stream_k``/``staging_bytes`` for this reconfiguration;
        None keeps the documented fallback constants.

        Consults the warm world pool first: a hit (or an in-flight
        speculative build for the same key, which the Prepare thread joins)
        skips lower+compile entirely and goes straight to transfer
        planning.
        """
        if overlap is not None:
            assert overlap in ("stop_copy", "stream"), overlap
            self._overlap_mode = overlap
        if operating_point is not None:
            self._operating_point = operating_point
        mode = self._overlap_mode
        gen = self.machine.begin_prepare(description=target.describe())

        src_parallel = self.world.parallel
        warm = None
        join = None
        if self.world_pool is not None:
            # take BEFORE any harvest: a harvest here could LRU-evict the
            # very entry the deadline estimator just priced as warm. A
            # ready-but-unharvested speculative builder is still caught by
            # the join path below (its result() returns immediately).
            warm = self.world_pool.take(self.pool_key(target))
            if warm is None:
                # a speculative build for this exact key is in flight:
                # join it instead of duplicating the compile
                join = self._spec_builders.pop(self.pool_key(target), None)

        def build():
            handle = None
            try:
                if warm is not None:
                    handle = self._refresh_pooled(warm, mode)
                elif join is not None:
                    handle = self._refresh_pooled(
                        join.result(), mode, source="speculative_join"
                    )
            except BaseException as e:
                # speculation must never fail the real resize: a broken
                # warm/joined world falls back to a fresh cold build (the
                # taken handle is released, not left pinned until GC)
                self.swallowed_errors.append(f"warm world refresh: {e!r}")
                if warm is not None:
                    warm.release()
                handle = None
            if handle is None:
                handle = self._build_world(
                    target,
                    split_step=mode == "stream" or self.world_pool is not None,
                )
            # transfer planning is metadata-only — do it here, in the
            # Prepare thread, so the commit pause never pays it (paper:
            # planning runs during Prepare)
            try:
                t0 = time.perf_counter()
                specs, plan = plan_state_transfer(
                    self.cfg, src_parallel, target,
                    source_policy=self.source_policy,
                )
                handle.timings["plan_s"] = time.perf_counter() - t0
                handle.plan_bundle = (src_parallel, specs, plan)
            except BaseException:
                # the resize fails either way; re-pool (or release) the
                # completed world rather than leaking it to GC
                self._discard_world(handle)
                raise
            return handle

        self._builder = ShadowBuilder(
            build, gen.gen_id, on_discard=self._discard_world
        ).start()
        # knowable-now metadata for the stream-ahead prewarm (§15)
        self._inflight_target = target
        return gen.gen_id

    def cancel_resize(self, outcome: Optional[str] = None) -> None:
        """Target became stale before commit (paper §7): abandon shadow.

        ``outcome`` (``retargeted`` | ``aborted``) retires the pending
        reconfiguration with a ReconfigRecord so event-stream accounting
        (DESIGN.md §10) sees every disposition; None keeps the classic
        silent cancel."""
        if outcome is not None and self._builder is not None:
            rec = self._pending_rec or ReconfigRecord(
                gen_id=self._builder.gen_id,
                src=self.world.parallel.describe(),
                dst=self.machine.shadow.description if self.machine.shadow else "?",
                mode="live_overlap" if self._overlap_mode == "stream" else "live",
            )
            rec.outcome = outcome
            self.records.append(rec)
        if self._builder is not None:
            self._builder.abandon()
        self.machine.cancel()
        self._reset_reconfig_state()

    @property
    def reconfig_pending(self) -> bool:
        """A resize is in flight (Prepare/Ready/streaming, not committed)."""
        return self._builder is not None

    def wait_shadow_ready(self, timeout: Optional[float] = None) -> None:
        """Block until the in-flight shadow world finishes building.

        Deterministic-replay hook (parity tests, ``--check`` benchmark
        gates): removes XLA-compile wall-clock from the commit-step
        alignment. Never used on the autonomous path — there the training
        loop simply keeps stepping until ``_poll_boundary`` sees readiness.
        """
        if self._builder is not None:
            self._builder.result(timeout)

    def retarget_resize(
        self,
        target: ParallelConfig,
        overlap: Optional[str] = None,
        operating_point=None,
    ) -> int:
        """A newer elasticity event supersedes the in-flight reconfiguration
        (paper §7 'Concurrent reconfiguration events').

        The pending shadow is abandoned (its build thread cannot be killed,
        only orphaned) and a fresh Prepare starts for ``target``. Any state
        the superseded session already streamed is captured first — after a
        full drain, so no in-flight scatter writes into a re-homed carry —
        and the successor session adopts it (:meth:`OverlapSession.adopt`):
        the stream continues where it left off instead of restarting from
        scratch. The superseded event retires with a ``retargeted``
        ReconfigRecord carrying whatever pre-copy work it had done.
        """
        if self._builder is None:
            return self.request_resize(
                target, overlap=overlap, operating_point=operating_point
            )

        reuse = None
        rec = self._pending_rec
        if self._session is not None:
            # drain before capture: adopted carries must hold fully-landed
            # rows, and the old session's staging must not alias sources
            self._session.drain()
            reuse = (
                self._session_targets,
                dict(self._session.executor.dst),
                dict(self._session.streamed_at),
            )
            rep = self._session.report
            if rec is not None:
                rec.precopy_s = rep.precopy_seconds
                rec.precopy_bytes = rep.precopy_bytes
        if rec is None:
            dst = self.machine.shadow.description if self.machine.shadow else "?"
            rec = ReconfigRecord(
                gen_id=self._builder.gen_id,
                src=self.world.parallel.describe(),
                dst=dst,
                mode="live_overlap" if self._overlap_mode == "stream" else "live",
            )
        rec.outcome = "retargeted"
        self.records.append(rec)

        self._builder.abandon()
        # the grads-only executable targets the OLD world, which a retarget
        # does not change — keep the compile (or compiled fn) across resets
        grad_builder = self._grad_builder
        if self.machine.state in (GenState.PREPARE, GenState.READY):
            self.machine.cancel()
        self._reset_reconfig_state()
        self._grad_builder = grad_builder
        gen_id = self.request_resize(
            target, overlap=overlap, operating_point=operating_point
        )
        self._reuse = reuse
        return gen_id

    def escalate_commit(self) -> Optional[ReconfigRecord]:
        """Deadline pressure mid-stream: commit NOW via stop-copy.

        The scheduler calls this when the warning window no longer covers
        the remaining pre-copy rounds. If the shadow world is ready the
        whole (remaining) transfer executes inside one stop-copy pause —
        the middle rung of the fallback lattice. Returns the commit record,
        or None when nothing was ready to commit (caller falls through to
        the checkpoint rung)."""
        if self._builder is None or not self._builder.ready:
            return None
        if self.machine.state == GenState.PREPARE:
            self.machine.mark_ready(self._builder.gen_id, payload=self._builder.result())
        if self.machine.state != GenState.READY:
            return None
        rep = None
        reused = self._pending_rec.reused_layers if self._pending_rec else 0
        if self._session is not None:
            # retire the streaming session: its scatters must land before
            # its carries are dropped; the stop-copy below re-moves
            # everything from the current cut
            self._session.drain()
            rep = self._session.report
        self._commit_switch()
        rec = self.records[-1]
        rec.outcome = "fell_back"
        if rep is not None:
            # keep the abandoned rounds' accounting: the escalation's cost
            # IS the pre-copy work it wasted
            rec.precopy_s = rep.precopy_seconds
            rec.precopy_bytes = rep.precopy_bytes
            # max, not overwrite: the stop-copy commit already counted the
            # plan's resident layers; the session's figure additionally
            # includes layers adopted at retarget
            rec.reused_layers = max(rec.reused_layers, reused)
        return rec

    # ------------------------------------------------------------------
    # Training loop with boundary polling
    # ------------------------------------------------------------------
    def train_steps(self, n: int, collect: Optional[Callable] = None) -> list[float]:
        losses = []
        for _ in range(n):
            t0 = time.perf_counter()
            batch = self._batch()
            if self._commit_armed:
                # overlapped mode: this step runs split (grads on the old
                # world overlapped with the dirty re-sync; optimizer update
                # on the new world) and commits the switch at its end
                metrics = self._split_step_commit(batch)
            else:
                self.params, self.opt_state, metrics = self.world.step_fn(
                    self.params, self.opt_state, batch
                )
            jax.block_until_ready(metrics["loss"])
            dt = time.perf_counter() - t0
            self.iteration_times.append(dt)
            self.ledger.record(t0, t0 + dt, "train", self.world.parallel.world_size)
            losses.append(float(metrics["loss"]))
            self.step += 1
            if collect:
                collect(self.step, metrics)
            if self._ckpt and self.step % self.ckpt_interval == 0:
                self._ckpt.save(self.step, {"params": self.params, "opt": self.opt_state})
            if self.parity_every and self.step % self.parity_every == 0:
                self._refresh_parity()
            self._poll_boundary()
        return losses

    def _refresh_parity(self) -> None:
        """Idle-boundary XOR parity snapshot (spare-shard scheme, §15)."""
        from repro.core.resource_view import build_tensor_specs
        from repro.elastic.redundancy import ParityStore

        if self._parity is None or self._parity.cfg != self.world.parallel:
            specs = build_tensor_specs(
                self.cfg, include_optimizer=True, zero_sharding=False
            )
            self._parity = ParityStore(specs, self.world.parallel)
        named, _ = named_state_leaves(self.params, self.opt_state)
        self._parity.refresh(named, self.step)

    def _batch(self):
        tokens = jnp.asarray(self.data.global_batch_at(self.step))
        batch = {"tokens": tokens}
        if self.cfg.family == "encdec":
            # dtype must match the AOT lowering's abstract batch (see
            # shadow.abstract_batch) or the compiled step rejects the input
            batch["frames"] = jnp.zeros(
                (self.global_batch, self.seq_len, self.cfg.d_model),
                jnp.dtype(self.cfg.dtype),
            )
        return batch

    def _poll_boundary(self) -> None:
        """Iteration boundary = the consistent cut (invariant I3)."""
        if self._spec_builders:
            self._harvest_spec_builders()
        if self._builder is None or not self._builder.ready:
            return
        if self.machine.state == GenState.PREPARE:
            handle = self._builder.result()
            self.machine.mark_ready(self._builder.gen_id, payload=handle)
        if self.machine.state != GenState.READY:
            return
        if self._overlap_mode == "stop_copy":
            self._commit_switch()
            return
        # overlapped streaming: pre-copy K layers per boundary while the
        # Active World keeps training; once the plan is fully streamed,
        # arm the split-step commit for the NEXT train step
        if self._session is None:
            self._start_overlap_session()
        t0 = time.perf_counter()
        named, _ = named_state_leaves(self.params, self.opt_state)
        self._session.stream_next(named, self.step)
        dt = time.perf_counter() - t0
        self.ledger.record(t0, t0 + dt, "reshard_overlap",
                           self.world.parallel.world_size)
        if not self._session.done_precopy:
            return
        ready = self._grad_fn_ready()
        if ready:
            self._commit_armed = True
        elif ready is None:
            # split-step executables unavailable (compile failed): the
            # reconfiguration still completes — degraded to stop-copy
            self._commit_switch()
            self.records[-1].outcome = "fell_back"

    def _grad_fn_ready(self):
        """True = armed, False = still compiling, None = compile failed."""
        if self.world.grad_fn is not None:
            return True
        if self._grad_builder is None:
            return False
        th, holder = self._grad_builder
        if th.is_alive():
            return False
        self._grad_builder = None
        if "err" in holder:
            import warnings

            self.swallowed_errors.append(f"split-step grad compile: {holder['err']!r}")
            warnings.warn(
                "split-step grad compile failed; falling back to stop-copy "
                f"commit: {holder['err']!r}"
            )
            return None
        self.world.grad_fn = holder["fn"]
        return True

    # ------------------------------------------------------------------
    # Plan + target-sharding bookkeeping (computed once, at READY)
    # ------------------------------------------------------------------
    def _named_target_shardings(self, world: WorldHandle) -> dict:
        ps, os_, _ = world.shardings
        named = {}
        for p, sh in tree_paths(ps).items():
            named[f"params/{p}"] = sh
        for coll in ("mu", "nu"):
            for p, sh in tree_paths(os_[coll]).items():
                named[f"{coll}/{p}"] = sh
        return named

    def _extra_shardings(self, world: WorldHandle) -> dict:
        """Shardings for opt-state leaves outside the resource view
        (step count, error-feedback buffers, ...)."""
        _, os_, _ = world.shardings
        return {k: v for k, v in os_.items() if k not in ("mu", "nu")}

    def _ensure_plan(self, new_world: WorldHandle) -> None:
        """Intersection plan for this reconfiguration. Normally precomputed
        by the Prepare thread (request_resize); recomputed here — timed into
        the record — only if the source layout changed since the request."""
        if self._session_plan is not None:
            return
        bundle = new_world.plan_bundle
        if bundle is not None and bundle[0] == self.world.parallel:
            _, specs, plan = bundle
            self._plan_seconds = 0.0
        else:
            t0 = time.perf_counter()
            specs, plan = plan_state_transfer(
                self.cfg,
                self.world.parallel,
                new_world.parallel,
                source_policy=self.source_policy,
            )
            self._plan_seconds = time.perf_counter() - t0
        self._session_specs = specs
        self._session_plan = plan
        self._session_targets = self._named_target_shardings(new_world)

    def _op_params(self) -> tuple[int, int]:
        """(stream_k, staging_bytes) for the current reconfiguration: the
        tuned operating point when the scheduler installed one, else the
        documented fallback constants."""
        op = self._operating_point
        if op is None:
            return self.stream_k, self.staging_bytes
        return op.stream_k, op.staging_bytes

    def _start_overlap_session(self) -> None:
        new_world: WorldHandle = self.machine.shadow.payload
        self._ensure_plan(new_world)
        stream_k, staging_bytes = self._op_params()
        self._session = OverlapSession(
            self._session_specs,
            self._session_plan,
            {},  # sources provided per streaming round
            self._session_targets,
            staging_bytes,
            stream_k=stream_k,
            wire_policy=self.wire_policy,
            wire_bw_bytes_s=self.wire_bw_bytes_s,
        )
        self._pending_rec = ReconfigRecord(
            gen_id=self._builder.gen_id,
            src=self.world.parallel.describe(),
            dst=new_world.parallel.describe(),
            prepare_s=new_world.timings.get("prepare_total_s", 0.0),
            mode="live_overlap",
            plan_s=self._plan_seconds,
            warm_hit=bool(new_world.timings.get("warm_hit", False)),
            prepare_source=new_world.timings.get("prepare_source", "cold"),
        )
        if self._operating_point is not None:
            self._pending_rec.operating_point = self._operating_point.to_dict()
        # retarget reuse: continue from the superseded session's streamed
        # state instead of restarting the stream from scratch
        if self._reuse is not None:
            old_targets, old_carries, old_streamed_at = self._reuse
            self._reuse = None
            self._session.adopt(old_carries, old_targets, old_streamed_at)
        # the session's figure already counts the plan's resident layers
        # (never streamed) plus anything adopted above
        self._pending_rec.reused_layers = self._session.report.reused_layers
        self._pending_rec.resident_layers = self._session.report.resident_layers
        if self.sync_compile and self.world.grad_fn is None:
            self.world.grad_fn = self._compile_grad_fn(self.world)
        # grads-only executable for the OLD world: compiled in a background
        # thread so the training loop never stalls on XLA (the commit is
        # simply not armed until it lands)
        if self.world.grad_fn is None and self._grad_builder is None:
            import threading

            world = self.world
            holder: dict = {}

            def compile_grad():
                try:
                    holder["fn"] = self._compile_grad_fn(world)
                except BaseException as e:  # surfaced at arm time
                    holder["err"] = e

            # non-daemon for the same reason as the prewarm thread: a
            # daemon thread killed mid-XLA-compile at exit crashes
            th = threading.Thread(target=compile_grad, daemon=False)
            th.start()
            self._grad_builder = (th, holder)

    def _compile_grad_fn(self, world: WorldHandle):
        from repro.distribution.step import jit_grad_step
        from repro.models.model import abstract_params

        jitted, _ = jit_grad_step(
            self.cfg,
            world.mesh,
            self.global_batch,
            microbatches=self.microbatches,
            hint_version=self.hint_version,
            parallel=world.parallel,
        )
        aparams = abstract_params(self.cfg)
        abatch = abstract_batch(self.cfg, self.global_batch, self.seq_len)
        return jitted.lower(aparams, abatch).compile()

    # ------------------------------------------------------------------
    # Switch — stop-copy: the whole transfer inside the pause
    # ------------------------------------------------------------------
    def _commit_switch(self) -> None:
        gen_id = self._builder.gen_id
        new_world: WorldHandle = self.machine.shadow.payload
        self._ensure_plan(new_world)
        plan = self._session_plan
        rec = ReconfigRecord(
            gen_id=gen_id,
            src=self.world.parallel.describe(),
            dst=new_world.parallel.describe(),
            prepare_s=new_world.timings.get("prepare_total_s", 0.0),
            plan_network_bytes=plan.network_bytes,
            plan_local_bytes=plan.local_bytes,
            layers_total=len(plan.layers()),
            reused_layers=len(plan.resident_layers()),
            resident_layers=len(plan.resident_layers()),
            plan_s=self._plan_seconds,
            warm_hit=bool(new_world.timings.get("warm_hit", False)),
            prepare_source=new_world.timings.get("prepare_source", "cold"),
        )
        pause_start = time.perf_counter()
        self.machine.begin_switch(gen_id)

        # 1. drain: all in-flight device work completes (1F1B boundary)
        t0 = time.perf_counter()
        jax.block_until_ready((self.params, self.opt_state))
        rec.drain_s = time.perf_counter() - t0

        # 2. streaming transfer: the plan executed on live arrays through
        # the shared engine (same protocol code as the sim oracle)
        t0 = time.perf_counter()
        named, extras = named_state_leaves(self.params, self.opt_state)
        _, op_staging = self._op_params()
        moved, stats = live_reshard_planned(
            self._session_specs,
            plan,
            named,
            self._session_targets,
            staging_bytes=op_staging,
            wire_policy=self.wire_policy,
            wire_bw_bytes_s=self.wire_bw_bytes_s,
        )
        new_extras, rep_x = live_reshard(
            extras, self._extra_shardings(new_world),
            staging_bytes=op_staging,
        )
        moved_state = rebuild_state(moved, self.params, self.opt_state, new_extras)
        if self.commit_observer is not None:
            self.commit_observer((self.params, self.opt_state), moved_state)
        self.params, self.opt_state = moved_state
        rec.transfer_s = time.perf_counter() - t0
        rec.moved_bytes = (
            stats.network_bytes + stats.local_bytes + rep_x.moved_bytes
        )
        rec.skipped_bytes = stats.resident_bytes
        rec.resident_cells = stats.resident_cells
        rec.wire_bytes = stats.wire_bytes
        rec.logical_bytes = stats.logical_bytes
        rec.executed_bytes = stats.executed_bytes + rep_x.moved_bytes
        rec.stream_dispatch_s = stats.dispatch_seconds
        rec.stream_drain_s = stats.drain_seconds
        rec.generic_cells = stats.generic_cells
        if self._operating_point is not None:
            rec.operating_point = self._operating_point.to_dict()

        # 3. atomic switch: pointer swap of world references
        t0 = time.perf_counter()
        old = self.machine.commit_switch(gen_id)
        rec.switch_s = time.perf_counter() - t0

        rec.total_pause_s = time.perf_counter() - pause_start
        self.ledger.record(
            pause_start,
            pause_start + rec.total_pause_s,
            "pause",
            max(self.world.parallel.world_size, new_world.parallel.world_size),
        )
        self.records.append(rec)
        self._reset_reconfig_state()

        # 4. cleanup (old world retires into the warm pool when one exists,
        # else its resources release; source arrays freed as the last
        # references drop with the old generation)
        self._retire_world(old)
        self.machine.finish_cleanup()

    # ------------------------------------------------------------------
    # Switch — overlapped: grads on the old world hide the dirty re-sync;
    # the optimizer update lands directly on the new world
    # ------------------------------------------------------------------
    def _split_step_commit(self, batch) -> dict:
        gen_id = self._builder.gen_id
        new_world: WorldHandle = self.machine.shadow.payload
        session = self._session
        rec = self._pending_rec
        plan = self._session_plan

        # dispatch the final gradient computation on the OLD world (params
        # are not donated: they are simultaneously the re-sync source)
        t0 = time.perf_counter()
        loss, grads = self.world.grad_fn(self.params, batch)

        # overlapped with it: re-sync every dirty layer from this
        # boundary's consistent cut, plus the non-resource-view leftovers.
        # drain=False: only the dispatch (and the staging sync) happens
        # here — the scatters keep landing underneath the grad computation,
        # and the single blocking drain moves inside the pause where it is
        # a residual tail rather than a full re-stream wait
        named, extras = named_state_leaves(self.params, self.opt_state)
        session.resync(named, self.step, drain=False)
        _, op_staging = self._op_params()
        new_extras, _ = live_reshard(
            extras, self._extra_shardings(new_world),
            staging_bytes=op_staging,
        )
        t1 = time.perf_counter()
        jax.block_until_ready((loss, grads))
        grad_tail_s = time.perf_counter() - t1  # residual wait past overlap

        # ---- the commit pause: re-sync tail + grad reshard + update +
        # pointer swap. session.drain() is the ONLY blocking wait on the
        # streamed state (per-round barriers were retired with the async
        # data plane); it must land before update_fn may donate the
        # destination carries ----
        pause_start = time.perf_counter()
        self.machine.begin_switch(gen_id)
        commit_drain_s = session.drain()
        t0 = time.perf_counter()
        p_specs = [s for s in self._session_specs if s.collection == "params"]
        from repro.core.intersection import TransferPlan

        p_plan = TransferPlan(
            tasks=[t for t in plan.tasks if t.collection == "params"],
            cfg_src=plan.cfg_src,
            cfg_dst=plan.cfg_dst,
        )
        g_named = {
            f"params/{p}": leaf for p, leaf in tree_paths(grads).items()
        }
        g_targets = {
            k: v for k, v in self._session_targets.items()
            if k.startswith("params/")
        }
        g_moved, g_stats = live_reshard_planned(
            p_specs, p_plan, g_named, g_targets,
            staging_bytes=op_staging,
            wire_policy=self.wire_policy,
            wire_bw_bytes_s=self.wire_bw_bytes_s,
        )
        from repro.utils.pytree import tree_from_paths

        grads_new = tree_from_paths(
            {p: g_moved[f"params/{p}"] for p in tree_paths(grads)}, grads
        )
        rec.transfer_s = time.perf_counter() - t0

        t0 = time.perf_counter()
        params_new, opt_new = rebuild_state(
            session.results(), self.params, self.opt_state, new_extras
        )
        if self.commit_observer is not None:
            self.commit_observer((self.params, self.opt_state), (params_new, opt_new))
        self.params, self.opt_state, om = new_world.update_fn(
            grads_new, opt_new, params_new
        )
        jax.block_until_ready(self.params)
        rec.update_s = time.perf_counter() - t0

        t0 = time.perf_counter()
        old = self.machine.commit_switch(gen_id)
        rec.switch_s = time.perf_counter() - t0
        rec.total_pause_s = time.perf_counter() - pause_start

        rep = session.report
        # drain_s = residual waits: grad tail outside the pause + re-sync
        # tail inside it (commit_drain_s appears here and on the drain-side
        # axis below, nowhere else — the phase columns stay additive)
        rec.drain_s = grad_tail_s + commit_drain_s
        rec.precopy_s = rep.precopy_seconds
        rec.precopy_bytes = rep.precopy_bytes
        rec.resync_s = rep.resync_seconds
        rec.resync_bytes = rep.resync_bytes
        rec.stream_dispatch_s = rep.dispatch_seconds + g_stats.dispatch_seconds
        rec.stream_drain_s = (
            rep.drain_seconds + commit_drain_s + g_stats.drain_seconds
        )
        rec.generic_cells = session.stats.generic_cells + g_stats.generic_cells
        rec.dirty_layers = rep.resync_layers
        rec.layers_total = len(plan.layers())
        rec.reused_layers = rep.reused_layers
        rec.resident_layers = rep.resident_layers
        rec.skipped_bytes = rep.skipped_bytes + g_stats.resident_bytes
        rec.resident_cells = rep.resident_cells + g_stats.resident_cells
        rec.wire_bytes = rep.wire_bytes + g_stats.wire_bytes
        rec.logical_bytes = rep.logical_bytes + g_stats.logical_bytes
        rec.plan_network_bytes = plan.network_bytes
        rec.plan_local_bytes = plan.local_bytes
        rec.moved_bytes = rep.total_bytes + g_stats.network_bytes + g_stats.local_bytes
        rec.executed_bytes = session.stats.executed_bytes + g_stats.executed_bytes
        self.ledger.record(
            pause_start, pause_start + rec.total_pause_s, "pause",
            max(self.world.parallel.world_size, new_world.parallel.world_size),
        )
        self.records.append(rec)
        self._reset_reconfig_state()

        self._retire_world(old)
        self.machine.finish_cleanup()
        return {"loss": loss, **om}

    def _reset_reconfig_state(self) -> None:
        self._builder = None
        self._inflight_target = None
        self._session = None
        self._session_specs = None
        self._session_plan = None
        self._session_targets = None
        self._pending_rec = None
        self._commit_armed = False
        self._grad_builder = None
        self._plan_seconds = 0.0
        self._reuse = None
        self._overlap_mode = self.overlap
        self._operating_point = None

    # ------------------------------------------------------------------
    # Fail-stop fallback (invariant I4) and restart baselines
    # ------------------------------------------------------------------
    def checkpoint_now(self) -> None:
        """Durable snapshot of the current step, synchronously.

        The scheduler's checkpoint rung: a warned event whose window cannot
        fit any live path saves NOW (inside the window) so the follow-up
        restore loses no progress."""
        if self._ckpt is not None:
            self._ckpt.save(
                self.step, {"params": self.params, "opt": self.opt_state}
            )
            self._ckpt.wait()

    def peer_coverage(
        self,
        target: ParallelConfig,
        lost_ranks: tuple = (),
        devices_failed: bool = True,
    ):
        """(survivor-constrained plan covers the state?, donor wire bytes).

        Metadata-only (one intersection plan), used by the deadline
        estimator to price the ``peer_recover`` rung. Counts state the
        fresh parity word could repair as covered."""
        from repro.elastic.redundancy import survivors_for

        src = self.world.parallel
        survivors = survivors_for(
            src, lost_ranks, target=target, devices_failed=devices_failed
        )
        _, plan = plan_state_transfer(
            self.cfg, src, target,
            source_policy=self.source_policy, allowed_src=survivors,
        )
        lost_bytes = plan.lost_bytes
        parity_ok = self._parity is not None and self._parity.covers(self.step)
        covered = lost_bytes == 0 or parity_ok
        return covered, plan.network_bytes + (lost_bytes if parity_ok else 0)

    def fail_stop_recover(
        self,
        target: ParallelConfig,
        devices_failed: bool = True,
        lost_ranks: tuple = (),
    ) -> ReconfigRecord:
        """Recover a fail-stop from surviving peers, in memory (§15).

        The recovery rungs, in order:

        1. **peer_recover** — plan the state transfer with sources
           restricted to the survivor set; DP/EP replicas donate the cells
           the dead ranks held (donor-balanced), cells whose whole replica
           group died are reconstructed from the XOR parity word when it
           is fresh. The survivor world comes warm-pool-first, then the
           stream runs over the same engine as a live resize — losslessly:
           recovery is correctness-critical, so the compressed wire format
           never applies. No step rollback: the survivors' state IS the
           current step.
        2. **checkpoint** (demoted, last resort) — only when survivors +
           parity cannot cover the state and a ckpt_dir exists.
        3. Neither → typed :class:`RecoveryError` (never a bare assert).

        ``devices_failed`` distinguishes an unannounced failure (devices
        in the old world are suspect: the outgoing world is NOT pooled and
        pooled worlds overlapping the lost device prefix are invalidated)
        from the scheduler's past-deadline rung for a *warned* event
        (devices are fine, only the window was too short — everyone
        survives and warm worlds stay valid). ``lost_ranks`` names the
        dead ranks explicitly; empty means the prefix-allocation default
        (the ranks beyond ``target``'s world died)."""
        from repro.elastic.redundancy import (
            balance_donors,
            heal_plan,
            survivors_for,
        )

        src_parallel = self.world.parallel
        survivors = survivors_for(
            src_parallel, lost_ranks, target=target,
            devices_failed=devices_failed,
        )
        lost = frozenset(range(src_parallel.world_size)) - survivors
        if devices_failed and self.world_pool is not None and lost:
            # under prefix allocation a pooled world of size W runs on
            # devices[:W] — it overlaps the dead set iff W exceeds the
            # lowest lost device id
            min_lost = min(lost)
            self.world_pool.invalidate(
                lambda key, h: h.parallel.world_size > min_lost
            )

        rec = ReconfigRecord(
            gen_id=-1, src=src_parallel.describe(), dst=target.describe(),
            mode="peer_recover", outcome="committed",
        )
        rec.lost_devices = len(lost)
        pause_start = time.perf_counter()

        # residual shadow work (paper §4.1 graceful degradation): a ready
        # shadow for the same target skips re-initialization — even one
        # caught mid-stream or mid-commit; its partially streamed state is
        # dropped (it may predate this boundary's cut) and re-streamed
        residual = None
        if (
            self._builder is not None
            and self._builder.ready
            and self.machine.shadow is not None
        ):
            cand: WorldHandle = self._builder.result()
            if cand.parallel == target:
                residual = cand
        if self._builder is not None and residual is None:
            self._builder.abandon()
        if self.machine.state in (GenState.PREPARE, GenState.READY):
            self.machine.cancel()
        self._reset_reconfig_state()

        # if a prewarm for exactly this pair is mid-compile, wait for it:
        # its cache insert is strictly cheaper than compiling the same
        # programs a second time in parallel with it
        if (
            self._prewarm_thread is not None
            and self._prewarm_thread.is_alive()
            and self._prewarm_pair == (src_parallel, target)
        ):
            self._prewarm_thread.join(timeout=60.0)
        self._speculation_trace(
            f"recover {src_parallel.describe()}->{target.describe()} "
            f"prewarmed={(src_parallel, target) in self._prewarmed_pairs}"
        )

        # survivor-constrained plan (metadata only)
        t0 = time.perf_counter()
        specs, plan = plan_state_transfer(
            self.cfg, src_parallel, target,
            source_policy=self.source_policy, allowed_src=survivors,
        )
        rec.plan_s = time.perf_counter() - t0

        lost_tasks = plan.lost_tasks()
        parity_fresh = (
            self._parity is not None
            and self._parity.cfg == src_parallel
            and self._parity.covers(self.step)
        )
        if lost_tasks and not parity_fresh:
            # peers cannot cover the state: demote to the checkpoint rung
            return self._checkpoint_restore(
                target, devices_failed, pause_start, rec.lost_devices,
                reason=f"{plan.lost_bytes} bytes have no surviving replica "
                "and no fresh parity",
            )

        # consistent cut: all in-flight device work lands before we read
        # survivor bytes (and before parity mixes them into a repair)
        t0 = time.perf_counter()
        jax.block_until_ready((self.params, self.opt_state))
        rec.drain_s = time.perf_counter() - t0

        named, extras = named_state_leaves(self.params, self.opt_state)
        if lost_tasks:
            named, rec.parity_bytes = self._parity.repair(
                named, lost, self.step
            )
            plan, _ = heal_plan(plan, specs)
        plan = balance_donors(plan, specs, survivors)
        rec.plan_network_bytes = plan.network_bytes
        rec.plan_local_bytes = plan.local_bytes
        rec.donors = len(
            {t.src_rank for t in plan.tasks if t.kind == "remote"}
        )

        # survivor world: residual shadow, warm pool, an in-flight
        # speculative build (joined), then cold
        t0 = time.perf_counter()
        world = residual
        rec.prepare_source = "residual" if residual is not None else "cold"
        if world is None and self.world_pool is not None:
            world = self.world_pool.take(self.pool_key(target))
            if world is not None:
                rec.prepare_source = "pool"
            else:
                join = self._spec_builders.pop(self.pool_key(target), None)
                if join is not None:
                    try:
                        world = self._refresh_pooled(
                            join.result(), self._overlap_mode,
                            source="speculative_join",
                        )
                        rec.prepare_source = "speculative_join"
                    except BaseException:
                        world = None
        rec.warm_hit = world is not None and rec.prepare_source == "pool"
        if world is None:
            world = self._build_world(
                target, split_step=self.world_pool is not None
            )
        rec.prepare_s = time.perf_counter() - t0

        # donor stream over the shared engine — always lossless: a lossy
        # wire would make the recovered state diverge from the survivors'
        t0 = time.perf_counter()
        targets = self._named_target_shardings(world)
        moved, stats = live_reshard_planned(
            specs, plan, named, targets,
            staging_bytes=self.staging_bytes,
            wire_policy=None,
            wire_bw_bytes_s=self.wire_bw_bytes_s,
        )
        new_extras, rep_x = live_reshard(
            extras, self._extra_shardings(world),
            staging_bytes=self.staging_bytes,
        )
        self.params, self.opt_state = rebuild_state(
            moved, self.params, self.opt_state, new_extras
        )
        rec.transfer_s = time.perf_counter() - t0
        rec.moved_bytes = (
            stats.network_bytes + stats.local_bytes + rep_x.moved_bytes
        )
        rec.skipped_bytes = stats.resident_bytes
        rec.resident_cells = stats.resident_cells
        rec.wire_bytes = stats.wire_bytes
        rec.logical_bytes = stats.logical_bytes
        rec.executed_bytes = stats.executed_bytes + rep_x.moved_bytes
        # NO step rollback: survivors carry the current step's state

        t0 = time.perf_counter()
        gen = self.machine.begin_prepare("failstop-" + target.describe())
        self.machine.mark_ready(gen.gen_id, payload=world)
        self.machine.begin_switch(gen.gen_id)
        old = self.machine.commit_switch(gen.gen_id)
        rec.switch_s = time.perf_counter() - t0
        if devices_failed:
            # the outgoing world ran on the (partially) failed device set:
            # never pool it — a later walk-up would compute the same
            # fingerprint from the static device list and serve executables
            # loaded onto a dead device
            old.payload = None
        else:
            self._retire_world(old)
        self.machine.finish_cleanup()
        # the parity word XORs per-rank images of the OLD layout
        self._parity = None

        rec.total_pause_s = time.perf_counter() - pause_start
        self.ledger.record(
            pause_start, pause_start + rec.total_pause_s, "pause",
            target.world_size,
        )
        self.records.append(rec)
        return rec

    def _checkpoint_restore(
        self,
        target: ParallelConfig,
        devices_failed: bool,
        pause_start: float,
        lost_devices: int,
        reason: str = "",
    ) -> ReconfigRecord:
        """The demoted last-resort rung: rebuild from the latest durable
        checkpoint (rolls the step back to it). Only reached when the
        survivor set plus parity cannot cover the state."""
        from repro.core.errors import RecoveryError

        if not self.ckpt_dir:
            raise RecoveryError(
                f"fail-stop to {target.describe()} is unrecoverable: "
                f"{reason or 'peers cannot cover the state'}, and no "
                "checkpoint directory is configured"
            )
        if self._ckpt:
            try:
                self._ckpt.wait()
            except Exception:
                # a failed background write surfaces here (satellite:
                # AsyncCheckpointer error propagation); an older durable
                # step may still exist — let load_checkpoint decide
                pass
        rec = ReconfigRecord(
            gen_id=-1, src=self.world.parallel.describe(),
            dst=target.describe(), mode="fallback", outcome="fell_back",
        )
        rec.lost_devices = lost_devices
        # residual shadow work (paper §4.1 graceful degradation): a ready
        # shadow for the same target skips re-initialization
        residual = None
        if (
            self._builder is not None
            and self._builder.ready
            and self.machine.shadow is not None
        ):
            cand: WorldHandle = self._builder.result()
            if cand.parallel == target:
                residual = cand
        if self._builder is not None and residual is None:
            self._builder.abandon()
        if self.machine.state in (GenState.PREPARE, GenState.READY):
            self.machine.cancel()
        self._reset_reconfig_state()

        t0 = time.perf_counter()
        world = residual
        rec.prepare_source = "residual" if residual is not None else "cold"
        if world is None and self.world_pool is not None:
            # warm pool: same graceful degradation as residual shadow work
            world = self.world_pool.take(self.pool_key(target))
            if world is not None:
                rec.prepare_source = "pool"
        rec.warm_hit = world is not None
        if world is None:
            world = self._build_world(
                target, split_step=self.world_pool is not None
            )
        init_s = time.perf_counter() - t0

        t0 = time.perf_counter()
        ps, os_, _ = world.shardings
        try:
            state, step, load_s = load_checkpoint(
                self.ckpt_dir,
                like={"params": self.params, "opt": self.opt_state},
                target_shardings={"params": ps, "opt": os_},
            )
        except Exception as e:
            raise RecoveryError(
                f"fail-stop to {target.describe()} is unrecoverable: "
                f"{reason or 'peers cannot cover the state'}, and no "
                f"durable checkpoint could be loaded from {self.ckpt_dir}"
            ) from e
        self.params, self.opt_state = state["params"], state["opt"]
        self.step = step

        gen = self.machine.begin_prepare("failstop-" + target.describe())
        self.machine.mark_ready(gen.gen_id, payload=world)
        self.machine.begin_switch(gen.gen_id)
        old = self.machine.commit_switch(gen.gen_id)
        if devices_failed:
            old.payload = None
        else:
            self._retire_world(old)
        self.machine.finish_cleanup()
        self._parity = None

        rec.transfer_s = load_s
        rec.prepare_s = init_s
        rec.total_pause_s = time.perf_counter() - pause_start
        self.ledger.record(
            pause_start, pause_start + rec.total_pause_s, "pause",
            target.world_size,
        )
        self.records.append(rec)
        return rec

    def gathered_params(self) -> Any:
        """Fully-replicated host copy (verification only — never on the
        live path)."""
        return jax.tree_util.tree_map(
            lambda x: np.asarray(jax.device_get(x)), self.params
        )

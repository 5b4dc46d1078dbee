"""Overlapped layer streaming for live reconfiguration (DESIGN.md §9).

Stop-copy moves the entire state inside the commit pause. An
:class:`OverlapSession` instead streams the plan's layers *between*
training steps while the Active World keeps stepping (pre-copy rounds),
tracks which layers the optimizer dirtied afterwards (a layer streamed at
step ``s`` is stale once the optimizer has stepped past ``s``), and
re-syncs only the dirty set at commit time — ideally overlapped with the
final gradient computation, so the blocking pause shrinks to the residual
tail plus the pointer swap.

Rounds are **asynchronous and double-buffered**: ``stream_next`` only
dispatches a round's pack/put/scatter programs, then (a) waits for the
round's *staging* buffers to materialize — the point after which the round
no longer reads its source leaves, so the next train step may donate them
— and (b) drains the round-before-last's destination writes, keeping at
most one round's scatters in flight. The full barrier exists only at
``resync``/``drain`` (commit). The invariant that makes this safe: a
staging buffer is reusable (and its sources donatable) only after the
scatter consuming it has been *dispatched* — which ``stream_next``
guarantees by ordering the scatter dispatch before ``sync_staging``.

Note the honest limit: under a dense optimizer (AdamW updates every
element every step) a pre-copied layer is always dirty by commit, so
pre-copy rounds cannot reduce commit *bytes* — what shrinks the pause is
re-syncing those bytes concurrently with the last step's gradient
computation (split-step commit, LiveRController._split_step_commit) while
destination storage and copy executables are already warm. With sparse or
infrequent updates (embedding rows, frozen adapters, accumulation
windows) the dirty set genuinely shrinks and pre-copy pays off directly;
the per-round byte accounting below reports both regimes truthfully.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Optional

from repro.core.intersection import TransferPlan
from repro.core.records import ReuseRecordMixin
from repro.core.resource_view import TensorSpec
from repro.reshard.engine import ReshardEngine, StreamStats
from repro.reshard.executors import LiveExecutor
from repro.utils.spans import span


def _layout_agrees(sh_old, sh_new, shape: tuple) -> bool:
    """True when two shardings lay the same logical shape out identically
    on the same devices — carries transfer between them zero-copy. Sharding
    equality is sufficient; otherwise compare the device→index maps (two
    NamedShardings over differently-factored meshes can still place every
    byte identically, e.g. fully-replicated tensors on the same device
    set)."""
    if sh_old is sh_new or sh_old == sh_new:
        return True
    try:
        return sh_old.devices_indices_map(shape) == sh_new.devices_indices_map(shape)
    except Exception:
        return False


def _carry_alive(leaf) -> bool:
    """True when every buffer backing ``leaf`` is still readable.
    ``is_deleted()`` alone is not enough: a zero-copy alias shares
    buffers with the leaf it aliases, and a donating train step deletes
    those buffers without marking the alias object itself deleted."""
    try:
        if getattr(leaf, "is_deleted", None) and leaf.is_deleted():
            return False
        for s in getattr(leaf, "addressable_shards", ()):
            data = s.data
            if data is None:
                return False
            if hasattr(data, "is_deleted") and data.is_deleted():
                return False
    except Exception:
        return False
    return True


@dataclass
class OverlapReport(ReuseRecordMixin):
    # reused_layers / resident_layers / skipped_bytes come from the shared
    # ReuseRecordMixin: resident layers never stream; adopt() adds layers
    # inherited from a superseded session at retarget
    precopy_rounds: int = 0
    precopy_bytes: int = 0
    precopy_seconds: float = 0.0
    resync_layers: int = 0
    resync_bytes: int = 0
    resync_seconds: float = 0.0
    # dispatch-vs-drain attribution across all rounds (pre-copy + re-sync):
    # dispatch = host time issuing device programs, drain = blocking waits
    # (staging syncs, double-buffer backpressure, final commit drain)
    dispatch_seconds: float = 0.0
    drain_seconds: float = 0.0

    @property
    def total_bytes(self) -> int:
        return self.precopy_bytes + self.resync_bytes


class OverlapSession:
    """Drives one live reconfiguration's streaming across iteration
    boundaries. The controller owns the schedule (when boundaries happen);
    the session owns what moves at each one."""

    def __init__(
        self,
        specs: list[TensorSpec],
        plan: TransferPlan,
        src_leaves: dict[str, Any],
        target_shardings: dict[str, Any],
        staging_bytes: int,
        stream_k: int = 4,  # documented fallback; autotune picks per-window
        max_inflight_rounds: int = 2,
        wire_policy=None,
        wire_bw_bytes_s: float | None = None,
        gen_id: int = -1,
    ):
        self.gen_id = gen_id  # the reconfiguration's id on its spans
        self.spec_map = {s.name: s for s in specs}
        self.plan = plan
        self.executor = LiveExecutor(
            self.spec_map, src_leaves, target_shardings, staging_bytes,
            wire_policy=wire_policy, wire_bw_bytes_s=wire_bw_bytes_s,
        )
        self.engine = ReshardEngine(
            plan, self.executor, staging_bytes, wire_policy=wire_policy
        )
        self.stream_k = max(1, stream_k)
        self.max_inflight_rounds = max(1, max_inflight_rounds)
        # fully-resident layers never enter the pre-copy schedule: their
        # bytes are already in place and the commit-time resync refreshes
        # them from the final cut with a near-free aliasing pass-through
        # (re-classification, not a re-stream — DESIGN.md §13)
        resident = set(plan.resident_layers())
        self.resident_layers: list[int] = sorted(
            l for l in self.engine.layers() if l in resident
        )
        self.pending: list[int] = [
            l for l in self.engine.layers() if l not in resident
        ]
        self.streamed_at: dict[int, int] = {}
        self.stats = StreamStats()
        self.report = OverlapReport()
        self.report.resident_layers = len(self.resident_layers)
        self.report.reused_layers = len(self.resident_layers)
        # rounds whose destination writes may still be in flight: each
        # entry is the set of tensor names the round touched
        self._inflight: list[set[str]] = []

    @property
    def done_precopy(self) -> bool:
        return not self.pending

    # ------------------------------------------------------------------
    def adopt(
        self,
        carries: dict[str, Any],
        old_targets: dict[str, Any],
        streamed_at: dict[int, int],
    ) -> int:
        """Retarget reuse (DESIGN.md §10): seed this session from a
        superseded session's already-streamed intersection state instead of
        restarting the stream from scratch.

        A destination carry is a *global* array — its streamed rows hold the
        step-``s`` values of the logical tensor regardless of which plan
        decomposition wrote them — so carries transfer between targets:
        zero-copy where the old and new target shardings agree
        (:func:`_layout_agrees`), via a single device-side relayout
        (``device_put``) where they do not; both are cheaper than re-pulling
        the bytes from the source through the engine. A layer counts as
        already streamed iff the old session streamed it, and keeps its
        original ``streamed_at`` step so the commit-time dirty re-sync still
        refreshes anything the optimizer has since touched (reuse shortens
        the pre-copy schedule — time-to-commit under a deadline — never the
        re-sync correctness). Returns the number of reused layers.

        Must be called before the first ``stream_next``; the caller is
        responsible for having drained the old session first (its scatters
        must have landed before its carries are re-homed)."""
        import jax

        assert not self.streamed_at, "adopt() must precede streaming"
        adopted: set[str] = set()
        relayout: list[tuple[str, Any, Any]] = []  # (name, leaf, sh_new)
        for name, sh_new in self.executor.target_shardings.items():
            leaf = carries.get(name)
            sh_old = old_targets.get(name)
            if leaf is None or sh_old is None:
                continue
            spec = self.spec_map.get(name)
            if spec is None or tuple(leaf.shape) != tuple(spec.shape):
                continue
            if not _carry_alive(leaf):
                # a superseded carry can be a zero-copy alias of a live
                # leaf (resident pass-through) that a donating train step
                # has since consumed — unadoptable, so its layers simply
                # re-stream
                continue
            if _layout_agrees(sh_old, sh_new, tuple(leaf.shape)):
                self.executor.dst[name] = leaf
            else:
                relayout.append((name, leaf, sh_new))
            adopted.add(name)
        if relayout:
            # one batched relayout: device_put takes a pytree of arrays and
            # a matching pytree of shardings, so every mismatched carry
            # moves in a single dispatch instead of one host round-trip
            # per leaf
            try:
                moved = jax.device_put(
                    [leaf for _, leaf, _ in relayout],
                    [sh for _, _, sh in relayout],
                )
                for (name, _, _), leaf in zip(relayout, moved):
                    self.executor.dst[name] = leaf
            except RuntimeError:
                # a carry died between the liveness probe and the dispatch
                # (an alias whose shared buffers a train step donated);
                # retry per-leaf so one dead carry doesn't void the batch
                for name, leaf, sh in relayout:
                    try:
                        self.executor.dst[name] = jax.device_put(leaf, sh)
                    except RuntimeError:
                        adopted.discard(name)
        # a layer is reused iff the old session streamed it AND every
        # tensor its tasks touch has an adopted carry
        reused = [
            l
            for l in self.pending
            if l in streamed_at
            and {t.tensor for t in self.plan.by_layer(l)} <= adopted
        ]
        for l in reused:
            self.pending.remove(l)
            self.streamed_at[l] = streamed_at[l]
        # += : resident layers were already counted as reused at __init__
        self.report.reused_layers += len(reused)
        return len(reused)

    def dirty_layers(self, step: int) -> list[int]:
        """Layers whose stream predates the optimizer's latest update."""
        return sorted(l for l, s in self.streamed_at.items() if s < step)

    # ------------------------------------------------------------------
    def _drain_rounds(self, keep: int) -> None:
        """Block until all but the newest ``keep`` rounds' destination
        writes have landed. Later rounds donate earlier carries, so
        round-granular handles cannot be kept; tensors a newer in-flight
        round re-touched are skipped — their current dst leaf is the newer
        round's output, and waiting on it would degenerate double buffering
        into a full per-round barrier for stacked tensors that span every
        round. Those tensors' backpressure comes from the executor's
        bounded staging instead (per-device program order retires their
        scatters before anything newer)."""
        while len(self._inflight) > keep:
            names = self._inflight.pop(0)
            for newer in self._inflight:
                names -= newer
            for n in names:
                leaf = self.executor.dst.get(n)
                if leaf is not None and hasattr(leaf, "block_until_ready"):
                    leaf.block_until_ready()

    def drain(self) -> None:
        """Full barrier: every dispatched round has landed. The only sync
        points are here and in ``resync`` — commit-time calls."""
        self._drain_rounds(0)
        self.executor.block_until_ready()

    # ------------------------------------------------------------------
    def stream_next(self, src_leaves: dict[str, Any], step: int) -> int:
        """One pre-copy round at an iteration boundary: dispatch the next K
        pending layers from the current state, wait only until the round's
        staging is materialized (sources safe to donate) and the
        round-before-last has drained (double buffering). Returns layers
        streamed."""
        if not self.pending:
            return 0
        batch, self.pending = self.pending[: self.stream_k], self.pending[self.stream_k :]
        self.executor.update_sources(src_leaves)
        self.executor.begin_round()
        with span("liver/stream/dispatch", gen=self.gen_id) as dispatch:
            s = self.engine.run(batch)
        dispatch_dt = dispatch.seconds
        self._inflight.append(self.executor.round_touched())
        with span("liver/stream/drain", gen=self.gen_id) as drain:
            self.executor.sync_staging()
            self._drain_rounds(self.max_inflight_rounds - 1)
        drain_dt = drain.seconds
        s.drain_seconds += drain_dt
        self.stats.merge(s)
        for l in batch:
            self.streamed_at[l] = step
        self.report.precopy_rounds += 1
        self.report.precopy_bytes += s.network_bytes + s.local_bytes
        # skipped bytes accrue per resident CELL — partially-resident layers
        # contribute here without counting in resident_layers (the
        # skipped_bytes ⟺ resident_cells identity, core/records.py)
        self.report.skipped_bytes += s.resident_bytes
        self.report.resident_cells += s.resident_cells
        self.report.logical_bytes += s.logical_bytes
        self.report.wire_bytes += s.wire_bytes
        self.report.precopy_seconds += dispatch_dt + drain_dt
        # the engine self-reports pure dispatch; staging backpressure hit
        # inside its loop belongs on the drain side
        self.report.dispatch_seconds += s.dispatch_seconds
        self.report.drain_seconds += drain_dt + max(
            0.0, dispatch_dt - s.dispatch_seconds
        )
        return len(batch)

    def resync(
        self, src_leaves: dict[str, Any], step: int, drain: bool = True
    ) -> StreamStats:
        """Re-stream every dirty layer (plus any remaining pending tail)
        from the boundary-consistent state at ``step``. After this, the
        destination holds a byte-exact copy of the step-``step`` cut.
        With ``drain=False`` only the dispatch (and the staging sync that
        frees the sources) happens — the caller overlaps the scatter drain
        with other work and must call :meth:`drain` before consuming
        :meth:`results`."""
        # resident layers join every resync: their refresh is a re-classify
        # (an aliasing pass-through from the step-``step`` cut), never a
        # byte re-stream — even when the optimizer dirtied them
        layers = sorted(
            set(self.dirty_layers(step))
            | set(self.pending)
            | set(self.resident_layers)
        )
        self.pending = []
        self.executor.update_sources(src_leaves)
        self.executor.reset_round()
        self.executor.begin_round()
        with span("liver/resync/dispatch", gen=self.gen_id) as dispatch:
            s = self.engine.run(layers)
        dispatch_dt = dispatch.seconds
        self._inflight.append(self.executor.round_touched())
        with span("liver/resync/drain", gen=self.gen_id) as drain_span:
            self.executor.sync_staging()
            if drain:
                self.drain()
        drain_dt = drain_span.seconds
        s.drain_seconds += drain_dt
        self.stats.merge(s)
        for l in layers:
            self.streamed_at[l] = step
        self.report.resync_layers += len(layers)
        self.report.resync_bytes += s.network_bytes + s.local_bytes
        self.report.skipped_bytes += s.resident_bytes
        self.report.resident_cells += s.resident_cells
        self.report.logical_bytes += s.logical_bytes
        self.report.wire_bytes += s.wire_bytes
        self.report.resync_seconds += dispatch_dt + drain_dt
        self.report.dispatch_seconds += s.dispatch_seconds
        self.report.drain_seconds += drain_dt + max(
            0.0, dispatch_dt - s.dispatch_seconds
        )
        return s

    def results(self) -> dict[str, Any]:
        return self.executor.results()

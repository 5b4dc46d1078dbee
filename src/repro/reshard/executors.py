"""Execution backends for the ReshardEngine.

SimExecutor — the byte-level oracle: simulated ranks own numpy shards
(``RankStore``); every planned chunk is copied shard-to-shard exactly as a
real send/recv would. This is the semantics reference the property tests
exercise and the live path is validated against.

LiveExecutor — the live path over global ``jax.Array``s. Plan cells are
per-(tensor, destination-rank); on live arrays the same bytes exist once,
so the executor deduplicates replica fan-out, merges each layer's cells
into row-range groups on the stacked dim, and moves them:

  * scattered rows  -> ONE compiled program chain per staging batch:
    Pallas ``pack_rows`` gather into a contiguous staging buffer, one
    staged ``device_put`` onto the target mesh, one overwrite-semantics
    ``scatter_rows`` into the (donated) destination carry. Overwrite makes
    re-streaming a dirty layer idempotent; the fused form replaces the
    per-run dynamic-update-slice chain that used to cost O(runs) host
    dispatches per batch.
  * contiguous runs -> slice + ``device_put`` + donated
    dynamic-update-slice (already a 3-dispatch path; also used for cells
    that do not decompose into full-width rows).

Destination carries are allocated device-side under the target sharding
(jitted sharded ``jnp.zeros`` — no host materialization or host->device
round trip of the full buffer). All jit helpers live in module-level
caches keyed by destination sharding, so retraces are cached per shape
family across executor instances and streaming rounds.

Everything the executor emits is an *async dispatch*: nothing here waits
on destination writes. The only waits are staging backpressure — at most
two staged buffers stay pinned (double buffering; ``_stage`` waits on the
oldest beyond that, whose consumer is already dispatched, so a full plan's
staging can never accumulate on device) — and the explicit round hooks:
callers that pipeline rounds use ``begin_round``/``sync_staging``/
``round_touched``, where ``sync_staging`` waits only until this round's
staged buffers are materialized (after which the round no longer reads its
source leaves and they are safe to donate to the next train step), while
the scatters into the destination carries keep draining in the background.

Staging is bounded by the engine's budget. On TPU backends
``ops.pack_rows``/``scatter_rows`` run the Pallas kernels natively; on CPU
they run the jnp reference (or interpret mode under
``REPRO_FORCE_PALLAS_INTERPRET=1``).
"""

from __future__ import annotations

import math
from typing import Any

from repro.core.intersection import TransferTask
from repro.core.resource_view import TensorSpec
from repro.reshard.chunking import rows_per_budget
from repro.reshard.wire import wire_nbytes


# ---------------------------------------------------------------------------
# Sim backend
# ---------------------------------------------------------------------------


class SimExecutor:
    """Copy planned chunks between per-rank numpy shard stores.

    The sim always copies losslessly (it is the byte-level semantics
    oracle), but it *prices* wire bytes under the given policy: its
    ``wire_bytes`` counter reports what a compressed wire would have
    carried for the same plan, so sim↔live accounting comparisons hold
    with or without quantization.
    """

    def __init__(
        self,
        src_stores: dict[int, Any],
        dst_stores: dict[int, Any],
        wire_policy=None,
    ):
        self.src_stores = src_stores
        self.dst_stores = dst_stores
        self.wire_policy = wire_policy
        self.executed_bytes = 0
        self.wire_bytes = 0

    def begin_layer(self, layer: int) -> None:
        pass

    def apply(self, task: TransferTask) -> None:
        src = self.src_stores[task.src_rank]
        dst = self.dst_stores[task.dst_rank]
        shape = task.shape()
        ssl = tuple(slice(o, o + s) for o, s in zip(task.src_offset, shape))
        dsl = tuple(slice(o, o + s) for o, s in zip(task.dst_offset, shape))
        dst.shards[task.tensor][dsl] = src.shards[task.tensor][ssl]
        # resident cells are already in place on the real device — the sim
        # still performs the copy (its per-rank stores are distinct buffers,
        # and the oracle must produce complete destination shards) but the
        # byte oracle counts them as zero moved bytes (DESIGN.md §13)
        if not task.resident:
            self.executed_bytes += task.nbytes
            self.wire_bytes += wire_nbytes(self.wire_policy, task)

    def end_layer(self, layer: int) -> None:
        pass


# ---------------------------------------------------------------------------
# Live backend
# ---------------------------------------------------------------------------

# Module-level jit caches: shared across executor instances and streaming
# rounds so every round after the first hits warm executables. _DUS0/_DUS_ND
# rely on jax.jit's own per-shape cache; zeros/scatter need explicit
# out_shardings (a trace-time constant), so they are additionally keyed by
# the destination sharding. Bounded: an elastic job cycles through many
# world configurations, and an unbounded cache would pin every historical
# mesh (and its executables) for process lifetime.
_ZEROS_CACHE: dict = {}
_SCATTER_CACHE: dict = {}
_RELAYOUT_CACHE: dict = {}
_RELAYOUT_ND_CACHE: dict = {}
_DEQ_SCATTER_CACHE: dict = {}
_PACK_CACHE: dict = {}
_PACKQ_CACHE: dict = {}
_JIT_CACHE_MAX = 64


def _cache_put(cache: dict, key, fn):
    if len(cache) >= _JIT_CACHE_MAX:
        cache.pop(next(iter(cache)))  # FIFO: oldest shape family retraces
    cache[key] = fn
    return fn


def _await_staged(buf) -> float:
    """Wait for a staged buffer unless it was already deleted: a staged
    device_put with a matching layout returns its input array, which the
    plan-less path's ``release`` may legitimately delete — only ever after
    the consuming destination drained, so a deleted buffer means 'done'.
    Returns the seconds spent blocked (drain-side time, not dispatch)."""
    import time

    if hasattr(buf, "block_until_ready") and not (
        hasattr(buf, "is_deleted") and buf.is_deleted()
    ):
        t0 = time.perf_counter()
        buf.block_until_ready()
        return time.perf_counter() - t0
    return 0.0


def _jit_helpers():
    """Module-level jitted copy helpers (cached across executor instances)."""
    global _DUS0, _DUS_ND
    if "_DUS0" in globals():
        return
    import jax

    _DUS0 = jax.jit(
        lambda carry, chunk, start: jax.lax.dynamic_update_slice_in_dim(
            carry, chunk, start, axis=0
        ),
        donate_argnums=(0,),
    )
    # starts is a traced 1-D index array; carry.ndim is static per trace,
    # so this caches per (carry shape, chunk shape) pair
    _DUS_ND = jax.jit(
        lambda carry, chunk, starts: jax.lax.dynamic_update_slice(
            carry, chunk, tuple(starts[i] for i in range(carry.ndim))
        ),
        donate_argnums=(0,),
    )


def _rows(x):
    """Rows on dim 0: a 1-D leaf moves as (R, 1) rows."""
    return x.reshape(x.shape[0], 1) if x.ndim == 1 else x


def _flat_sharding(sharding, ndim: int):
    """Sharding descriptor of a rank-``ndim`` leaf's (R, C) view: dim 0 as
    the leaf's, every tail mesh axis merged onto the row. The kernels only
    ask whether rows are split or whole, which this preserves."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    if not isinstance(sharding, NamedSharding):
        return sharding
    spec = tuple(sharding.spec) + (None,) * (ndim - len(sharding.spec))
    tail = tuple(
        a for ax in spec[1:] if ax is not None
        for a in ((ax,) if isinstance(ax, str) else ax)
    )
    return NamedSharding(sharding.mesh, P(spec[0] if spec else None, tail or None))


def _rows_sharding(sharding, ndim: int):
    """The sharding of ``_rows`` of a rank-``ndim`` leaf."""
    return sharding if ndim > 1 else _flat_sharding(sharding, 1)


def _pack_fn(sharding):
    """Jitted row gather into a staging buffer on the SOURCE mesh, run per
    device (the buffer keeps the leaf's tail layout)."""
    fn = _PACK_CACHE.get(sharding)
    if fn is None:
        import jax

        def f(leaf, starts):
            from repro.kernels import ops

            return ops.pack_rows(
                _rows(leaf), starts, 1, sharding=_rows_sharding(sharding, leaf.ndim)
            )

        fn = _cache_put(_PACK_CACHE, sharding, jax.jit(f))
    return fn


def _packq_fn(sharding, fmt: str):
    """Jitted compressed-wire pack on the source mesh: gather + per-row
    quantize of the leaf's (R, C) view, returning (int8/fp8 payload,
    float32 sidecar scales)."""
    key = (sharding, fmt)
    fn = _PACKQ_CACHE.get(key)
    if fn is None:
        import jax

        def f(leaf, starts):
            from repro.kernels import ops

            return ops.pack_quant_rows(
                leaf.reshape(leaf.shape[0], -1), starts, 1, fmt,
                sharding=_flat_sharding(sharding, leaf.ndim),
            )

        fn = _cache_put(_PACKQ_CACHE, key, jax.jit(f))
    return fn


def _zeros_fn(shape: tuple, dtype: str, sharding):
    """Jitted device-side allocation of a zeroed carry directly under the
    target sharding — the old host-side ``jnp.zeros`` + ``device_put``
    double-materialized every destination tensor."""
    key = (shape, dtype, sharding)
    fn = _ZEROS_CACHE.get(key)
    if fn is None:
        import jax
        import jax.numpy as jnp

        fn = _cache_put(
            _ZEROS_CACHE,
            key,
            jax.jit(lambda: jnp.zeros(shape, dtype), out_shardings=sharding),
        )
    return fn


def _scatter_fn(sharding):
    """Jitted fused overwrite-scatter of the packed row buffer into the
    carry at the given offsets, run per device on the destination mesh.
    The carry is donated and the output pinned to the destination sharding.
    jax.jit caches traces per (carry, buf, starts) shape family underneath
    the per-sharding entry."""
    fn = _SCATTER_CACHE.get(sharding)
    if fn is None:
        import jax

        def f(carry, buf, starts):
            from repro.kernels import ops

            out = ops.scatter_rows(
                _rows(carry), buf, starts, 1,
                sharding=_rows_sharding(sharding, carry.ndim),
            )
            return out.reshape(carry.shape)

        fn = _cache_put(
            _SCATTER_CACHE,
            sharding,
            jax.jit(f, donate_argnums=(0,), out_shardings=sharding),
        )
    return fn


def _dequant_scatter_fn(sharding):
    """Jitted fused dequant + overwrite-scatter for the compressed wire
    path: collapse the donated carry to 2-D, dequantize each staged tile
    with its sidecar scale and scatter it at the given row offsets, restore
    the carry shape. Same overwrite/idempotence semantics as
    ``_scatter_fn`` — dequant is a deterministic elementwise map, so
    re-applying the same payload lands bitwise-identical bytes."""
    fn = _DEQ_SCATTER_CACHE.get(sharding)
    if fn is None:
        import jax

        def f(carry, buf, scales, starts):
            from repro.kernels import ops

            c2 = carry.reshape(carry.shape[0], -1)
            c2 = ops.dequant_scatter_rows(
                c2, buf, scales, starts, 1,
                sharding=_flat_sharding(sharding, carry.ndim),
            )
            return c2.reshape(carry.shape)

        fn = _cache_put(
            _DEQ_SCATTER_CACHE,
            sharding,
            jax.jit(f, donate_argnums=(0,), out_shardings=sharding),
        )
    return fn


def _relayout_fn(sharding):
    """Jitted fused on-device relayout for "local" plan cells: gather the
    named rows from the SOURCE leaf and overwrite-scatter them into the
    donated destination carry at the same global offsets — one compiled
    program, no staging buffer, no cross-mesh device_put hop. Legal only
    when source and target meshes flatten to the same device assignment
    (the caller guards via ``_same_device_assignment``)."""
    fn = _RELAYOUT_CACHE.get(sharding)
    if fn is None:
        import jax

        def f(carry, leaf, starts):
            from repro.kernels import ops

            out = ops.relayout_rows(
                _rows(carry), _rows(leaf), starts, 1,
                sharding=_rows_sharding(sharding, carry.ndim),
            )
            return out.reshape(carry.shape)

        fn = _cache_put(
            _RELAYOUT_CACHE,
            sharding,
            jax.jit(f, donate_argnums=(0,), out_shardings=sharding),
        )
    return fn


def _relayout_nd_fn(sharding, chunk_shape: tuple[int, ...]):
    """Jitted fused slice+update for a "local" cell that does not decompose
    into full-width rows: dynamic_slice the SOURCE leaf at the cell's global
    origin and dynamic_update_slice it into the donated carry at the same
    origin — one program instead of the slice/device_put/DUS chain."""
    key = (sharding, chunk_shape)
    fn = _RELAYOUT_ND_CACHE.get(key)
    if fn is None:
        import jax

        def f(carry, leaf, starts):
            idx = tuple(starts[i] for i in range(carry.ndim))
            chunk = jax.lax.dynamic_slice(leaf, idx, chunk_shape)
            return jax.lax.dynamic_update_slice(carry, chunk, idx)

        fn = _cache_put(
            _RELAYOUT_ND_CACHE,
            key,
            jax.jit(f, donate_argnums=(0,), out_shardings=sharding),
        )
    return fn


def _same_device_assignment(sh_a, sh_b) -> bool:
    """True when two NamedShardings flatten to the identical ordered device
    list — the precondition for putting both arrays through one jitted
    program (jax rejects mixed device assignments)."""
    from jax.sharding import NamedSharding

    if not isinstance(sh_a, NamedSharding) or not isinstance(sh_b, NamedSharding):
        return False
    a = sh_a.mesh.devices.ravel().tolist()
    b = sh_b.mesh.devices.ravel().tolist()
    return len(a) == len(b) and all(x is y for x, y in zip(a, b))


class LiveExecutor:
    """Execute plan regions on live jax.Arrays.

    src: {tensor name: global jax.Array on the source mesh}
    target_shardings: {tensor name: Sharding on the target mesh}
    fused: route scattered-row batches through the pack -> staged put ->
        overwrite-scatter program chain (default); ``False`` keeps the
        legacy per-run dynamic-update-slice chain (benchmark baseline).
    """

    def __init__(
        self,
        specs: dict[str, TensorSpec],
        src: dict[str, Any],
        target_shardings: dict[str, Any],
        staging_bytes: int,
        free_sources: bool = False,
        fused: bool = True,
        wire_policy=None,
        wire_bw_bytes_s: float | None = None,
    ):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        _jit_helpers()
        self.specs = specs
        self.src = src
        self.target_shardings = target_shardings
        self.staging_bytes = staging_bytes
        self.free_sources = free_sources
        self.fused = fused
        # per-kind wire policy: None = fully lossless (the byte-oracle
        # default). With a policy, remote row batches of quantized
        # collections go through the fused pack-quant -> staged put ->
        # dequant-scatter chain; the generic per-cell fallback and the
        # legacy (fused=False) baseline stay lossless.
        self.wire_policy = wire_policy
        # emulated interconnect: when set, every staged wire transfer
        # blocks for wire_bytes / wire_bw_bytes_s. This container's host
        # "transfers" are memcpys, so without an emulated wire the payload
        # size cannot show up in wall time; benches set this to measure
        # compression as effective bandwidth (documented deviation,
        # DESIGN.md §14).
        self.wire_bw_bytes_s = wire_bw_bytes_s
        self.dst: dict[str, Any] = {}
        self.executed_bytes = 0
        # bytes that physically crossed the (possibly emulated) wire:
        # quantized payload + sidecar for compressed batches, raw bytes for
        # lossless ones; on-device relayouts cross no wire and count zero
        self.wire_bytes = 0
        self.generic_cells = 0  # cells that fell off the row-merge fast path
        # blocking time spent in staging backpressure — drain-side wall
        # clock; the engine subtracts its delta from the loop time so
        # dispatch_seconds stays pure dispatch
        self.stage_wait_seconds = 0.0
        # count of resident pass-through refreshes (tests/benchmarks)
        self.resident_passthroughs = 0
        # replica-dedupe: region key -> strongest kind seen ("resident" is
        # upgraded in place if another dst rank genuinely needs the bytes)
        self._seen: dict[tuple, str] = {}
        self._cells: dict[str, dict[tuple, TransferTask]] = {}
        # tensors already refreshed via the resident pass-through this round
        self._resident_done: set[str] = set()
        # async round tracking: staged buffers whose readiness implies this
        # round's source reads completed, and the dst names it touched
        self._round_staged: list[Any] = []
        self._round_touched: set[str] = set()
        # destinations produced by a bare device_put may ALIAS source
        # buffers on devices common to both meshes — deleting such sources
        # would poison the destination (these are scalars; skip the free)
        self._no_release: set[str] = set()
        # last-resort staging layout: replicated on the target mesh (used
        # for the quantized payload, whose collapsed (R, C) rows defeat the
        # spec, and for offset tables); packed buffers and sliced chunks
        # stage in the target's own non-dim0 layout instead
        any_sh = next(iter(target_shardings.values()))
        self._replicated_sh = NamedSharding(any_sh.mesh, P())
        self._jnp = jnp
        self._jax = jax

    def _stage_sharding(self, name: str, chunk_shape: tuple[int, ...]):
        """Staging layout for a chunk of ``name``: the destination's own
        sharding with dim 0 unsharded (chunks are row-slices smaller than a
        dim-0 partition in general) and non-dividing axes dropped — so each
        target device only receives its slice of the chunk, not the whole
        chunk replicated."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        sh = self.target_shardings[name]
        if not isinstance(sh, NamedSharding):
            return self._replicated_sh
        spec = list(sh.spec) + [None] * (len(chunk_shape) - len(sh.spec))
        spec = spec[: len(chunk_shape)]
        if spec:
            spec[0] = None
        sizes = dict(zip(sh.mesh.axis_names, sh.mesh.devices.shape))
        for d, ax in enumerate(spec):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            factor = 1
            for a in axes:
                factor *= sizes.get(a, 1)
            if factor == 0 or chunk_shape[d] % factor != 0:
                spec[d] = None
        while spec and spec[-1] is None:
            spec.pop()
        return NamedSharding(sh.mesh, P(*spec))

    def release(self, name: str) -> None:
        """Engine hook: this tensor's sources are no longer needed by the
        current run. Only frees device buffers when the caller opted in
        (``free_sources`` — donation semantics: the source tree must not be
        used again)."""
        if not self.free_sources or name in self._no_release:
            return
        leaf = self.src.pop(name, None)
        if leaf is not None and hasattr(leaf, "delete"):
            # drain the consumers first: deleting a buffer with dispatched
            # reads still in flight poisons the destination arrays
            dst = self.dst.get(name)
            if dst is not None and hasattr(dst, "block_until_ready"):
                dst.block_until_ready()
            leaf.delete()

    def update_sources(self, src: dict[str, Any]) -> None:
        """Swap in fresh source leaves (the previous generation's arrays are
        invalidated by step-function donation between streaming rounds).
        Resident destinations must re-alias the NEW leaves, so their
        pass-through marks reset too."""
        self.src = src
        self._resident_done = set()

    def reset_round(self) -> None:
        """Start a new streaming round: layers streamed before may be
        re-streamed (dirty re-sync), so the replica-dedupe set resets and
        resident tensors are refreshed from the new cut."""
        self._seen = {}
        self._resident_done = set()

    # -- async round protocol -------------------------------------------
    def begin_round(self) -> None:
        """Open a dispatch round: forget the previous round's staged-buffer
        and touched-destination bookkeeping (NOT the replica-dedupe set —
        see ``reset_round``)."""
        self._round_staged = []
        self._round_touched = set()

    def round_touched(self) -> set[str]:
        """Destination tensors this round dispatched writes into."""
        return set(self._round_touched)

    def _emulate_wire(self, nbytes: int) -> None:
        """Account a wire crossing; block for its emulated transfer time."""
        self.wire_bytes += nbytes
        if self.wire_bw_bytes_s:
            import time

            time.sleep(nbytes / self.wire_bw_bytes_s)

    def _stage(self, buf):
        """Track a staged buffer, keeping at most two pinned (double
        buffering). Beyond that the oldest is waited on and dereferenced;
        per-device program order then frees it as soon as its (already
        dispatched) consumer retires. Live staging is therefore bounded by
        a small constant multiple of the budget — ~3 chunks: two pinned
        here plus at most one whose consumer is still retiring — not by
        the plan size; callers that never round-sync (the stop-copy paths)
        cannot accumulate a whole plan's staging on device. (The engine's
        ``peak_staging_bytes`` accounts the logical per-flush bound; this
        constant factor is the pipelining price on top.)"""
        self._round_staged.append(buf)
        if len(self._round_staged) > 2:
            self.stage_wait_seconds += _await_staged(self._round_staged.pop(0))
        return buf

    def sync_staging(self) -> None:
        """Block until this round's staged buffers are materialized. A
        staged buffer being ready implies the pack/slice that produced it
        — i.e. every read of this round's SOURCE leaves — has completed,
        so the caller may let the training step donate those sources while
        the scatters into the destination carries keep draining."""
        for buf in self._round_staged:
            self.stage_wait_seconds += _await_staged(buf)
        self._round_staged = []

    # -- engine protocol ------------------------------------------------
    def begin_layer(self, layer: int) -> None:
        self._cells = {}

    def apply(self, chunk: TransferTask) -> None:
        key = (chunk.tensor, chunk.bounds)
        prev = self._seen.get(key)
        if prev is not None:  # replica fan-out: same bytes, other dst rank
            if prev == "resident" and chunk.kind != "resident":
                # the region first showed up as resident, but this replica
                # lands on a device that does NOT already hold it — one
                # move on the global array covers every destination device
                # (including the resident one), so upgrade in place
                self._seen[key] = chunk.kind
                self._cells[chunk.tensor][chunk.bounds] = chunk
            return
        self._seen[key] = chunk.kind
        self._cells.setdefault(chunk.tensor, {})[chunk.bounds] = chunk

    def end_layer(self, layer: int) -> None:
        for name, regions in self._cells.items():
            cells = list(regions.values())
            if all(c.resident for c in cells):
                # every byte of this tensor's layer is already on the right
                # device: refresh the destination by aliasing the live
                # source instead of streaming (DESIGN.md §13)
                self._adopt_resident(name)
            else:
                self._move_tensor(name, cells)
        self._cells = {}

    def _adopt_resident(self, name: str) -> None:
        self._round_touched.add(name)
        if name in self._resident_done:
            return
        self._resident_done.add(name)
        # a same-layout device_put aliases per-device buffers where the
        # target already holds the bytes — near-free, and exactly why the
        # sources of a resident destination must never be force-freed
        self.dst[name] = self._jax.device_put(
            self.src[name], self.target_shardings[name]
        )
        self._no_release.add(name)
        self._stage(self.dst[name])
        self.resident_passthroughs += 1

    # -- movement -------------------------------------------------------
    def _dst_carry(self, name: str):
        if name not in self.dst:
            spec = self.specs[name]
            # allocated directly under the target sharding inside jit: no
            # host-side zeros buffer, no host->device transfer of the full
            # tensor, and the executable is cached per shape family
            self.dst[name] = _zeros_fn(
                spec.shape, spec.dtype, self.target_shardings[name]
            )()
        return self.dst[name]

    def _move_tensor(self, name: str, cells: list[TransferTask]) -> None:
        spec = self.specs[name]
        leaf = self.src[name]
        self._round_touched.add(name)
        if leaf.ndim == 0:
            self.dst[name] = self._jax.device_put(
                leaf, self.target_shardings[name]
            )
            self._stage(self.dst[name])
            self._no_release.add(name)
            self.executed_bytes += spec.nbytes
            self._emulate_wire(spec.nbytes)  # scalars are always lossless
            return
        # classified routing: same-rank cells ("local" relayouts, plus the
        # rare resident cell sharing a layer with moved regions) can take
        # the fused on-device relayout — one program, no staging hop —
        # when both meshes flatten to the same device assignment (a jitted
        # program cannot span two device sets) AND splitting them off does
        # not break the row-merge fast path for either partition.
        here = [c for c in cells if c.kind in ("local", "resident")]
        if here and self._relayout_ok(name):
            rest = [c for c in cells if c.kind == "remote"]
            rows_here = _full_rows(spec, here)
            rows_rest = _full_rows(spec, rest) if rest else []
            if rows_here is not None and rows_rest is not None:
                self._relayout_rows(name, rows_here)
                if not rest:
                    return
                cells = rest
            elif _full_rows(spec, cells) is None:
                # everything is generic either way: at least fuse the
                # same-device cells into single-program relayouts
                self.generic_cells += len(cells)
                for c in here:
                    self._relayout_cell(name, c)
                for c in rest:
                    self._move_cell(name, c)
                return
            # else: local+remote jointly tile full rows — the combined
            # staged row path beats two per-partition generic paths
        # row-merge: do this layer's cells tile full-width rows of dim 0?
        rows = _full_rows(spec, cells)
        if rows is not None:
            self._move_rows(name, rows)
        else:
            # partial-width cells (no full-row union): per-cell fallback
            self.generic_cells += len(cells)
            for c in cells:
                self._move_cell(name, c)

    # -- fused on-device relayout (classified "local" cells) ------------
    def _relayout_ok(self, name: str) -> bool:
        sh_src = getattr(self.src[name], "sharding", None)
        return _same_device_assignment(sh_src, self.target_shardings[name])

    def _relayout_rows(self, name: str, rows: list[int]) -> None:
        jnp = self._jnp
        spec = self.specs[name]
        leaf = self.src[name]
        per_row = spec.nbytes // spec.shape[0]
        carry = self._dst_carry(name)
        fn = _relayout_fn(self.target_shardings[name])
        max_rows = rows_per_budget(per_row, self.staging_bytes)
        for i in range(0, len(rows), max_rows):
            batch = rows[i : i + max_rows]
            starts = self._jax.device_put(
                jnp.asarray(batch, jnp.int32), self._replicated_sh
            )
            carry = fn(carry, leaf, starts)
            self.executed_bytes += per_row * len(batch)
        self.dst[name] = carry
        # the carry's readiness implies every source read of the relayout
        # chain retired — that is what sync_staging promises callers
        self._stage(carry)

    def _relayout_cell(self, name: str, cell: TransferTask) -> None:
        carry = self._dst_carry(name)
        starts = self._jax.device_put(
            self._jnp.asarray([lo for lo, _ in cell.bounds], self._jnp.int32),
            self._replicated_sh,
        )
        fn = _relayout_nd_fn(self.target_shardings[name], cell.shape())
        self.dst[name] = fn(carry, self.src[name], starts)
        self._stage(self.dst[name])
        self.executed_bytes += cell.nbytes

    def _wire_format(self, name: str) -> str:
        if self.wire_policy is None or not self.fused:
            return "none"
        return self.wire_policy.format_for(self.specs[name].collection)

    def _move_rows(self, name: str, rows: list[int]) -> None:
        jnp, jax = self._jnp, self._jax
        spec = self.specs[name]
        leaf = self.src[name]
        tail = spec.shape[1:]
        per_row = spec.nbytes // spec.shape[0]
        fmt = self._wire_format(name)
        if fmt != "none":
            # one sidecar float32 scale per row-tile rides with the payload
            row_elems = int(math.prod(tail)) if tail else 1
            wire_per_row = row_elems + 4
        else:
            wire_per_row = per_row
        carry = self._dst_carry(name)
        # the staging budget bounds wire bytes — what is physically staged —
        # so a quantized tensor packs ~4x more logical rows per batch
        max_rows = rows_per_budget(wire_per_row, self.staging_bytes)
        for i in range(0, len(rows), max_rows):
            batch = rows[i : i + max_rows]
            runs = _runs(batch)
            if fmt != "none":
                # compressed wire path: pack-quantize on the source mesh
                # (payload + sidecar scales), stage the small buffers, then
                # one fused dequant + overwrite-scatter into the donated
                # carry. Used for contiguous runs too — the wire transfer,
                # not the dispatch count, is what compression shrinks.
                starts = jnp.asarray(batch, jnp.int32)
                qbuf, scales = _packq_fn(leaf.sharding, fmt)(leaf, starts)
                qbuf = jax.device_put(qbuf, self._replicated_sh)
                scales = jax.device_put(scales, self._replicated_sh)
                starts_dev = jax.device_put(starts, self._replicated_sh)
                carry = _dequant_scatter_fn(self.target_shardings[name])(
                    carry, qbuf, scales, starts_dev
                )
                self._stage(qbuf)
                self._emulate_wire(wire_per_row * len(batch))
            elif len(runs) == 1:
                lo, hi = runs[0]
                chunk_shape = (hi - lo,) + tail
                chunk = jax.device_put(
                    leaf[lo:hi], self._stage_sharding(name, chunk_shape)
                )
                carry = _DUS0(carry, chunk, lo)
                self._stage(chunk)
            elif self.fused:
                # scattered rows (dirty-layer re-sync): one pack on the
                # source mesh, one staged put, one overwrite scatter into
                # the donated carry — 3 dispatches per batch instead of
                # O(runs). (An accumulate scatter would be cheaper on TPU
                # but is NOT idempotent: re-streaming a dirty layer would
                # compound onto the stale pre-copied value.)
                starts = jnp.asarray(batch, jnp.int32)
                buf = _pack_fn(leaf.sharding)(leaf, starts)
                buf = jax.device_put(buf, self._stage_sharding(name, buf.shape))
                starts_dev = jax.device_put(starts, self._replicated_sh)
                carry = _scatter_fn(self.target_shardings[name])(
                    carry, buf, starts_dev
                )
                self._stage(buf)
            else:
                # legacy baseline (bench_dataplane's "per-run DUS" path):
                # pack once, then per-run slice + dynamic-update-slice
                starts = jnp.asarray(batch, jnp.int32)
                buf = _pack_fn(leaf.sharding)(leaf, starts)
                buf = jax.device_put(buf, self._stage_sharding(name, buf.shape))
                self._stage(buf)
                off = 0
                for lo, hi in runs:
                    k = hi - lo
                    chunk = buf[off : off + k].reshape((k,) + tail)
                    carry = _DUS0(carry, chunk, lo)
                    off += k
            self.executed_bytes += per_row * len(batch)
            if fmt == "none":
                self._emulate_wire(per_row * len(batch))
        self.dst[name] = carry

    def _move_cell(self, name: str, cell: TransferTask) -> None:
        jax = self._jax
        carry = self._dst_carry(name)
        sl = tuple(slice(lo, hi) for lo, hi in cell.bounds)
        chunk_shape = cell.shape()
        chunk = jax.device_put(
            self.src[name][sl], self._stage_sharding(name, chunk_shape)
        )
        starts = self._jnp.asarray([lo for lo, _ in cell.bounds], self._jnp.int32)
        self.dst[name] = _DUS_ND(carry, chunk, starts)
        self._stage(chunk)
        self.executed_bytes += cell.nbytes
        # the generic fallback stays lossless regardless of policy
        self._emulate_wire(cell.nbytes)

    # -- results --------------------------------------------------------
    def results(self) -> dict[str, Any]:
        """Destination leaves (tensors never planned keep no entry)."""
        return self.dst

    def block_until_ready(self) -> None:
        self._round_staged = []
        for v in self.dst.values():
            v.block_until_ready()


def _full_rows(spec, cells: list[TransferTask]) -> list[int] | None:
    """The sorted dim-0 rows these cells tile at full width, or None if the
    union does not decompose into complete rows (the generic-cell case)."""
    rows: set[int] = set()
    for c in cells:
        rows.update(range(c.bounds[0][0], c.bounds[0][1]))
    per_row = spec.nbytes // spec.shape[0]
    covered = sum(c.nbytes for c in cells)
    if covered == per_row * len(rows):
        return sorted(rows)
    return None


def _runs(sorted_rows: list[int]) -> list[tuple[int, int]]:
    """Collapse a sorted unique row list into contiguous [lo, hi) runs."""
    runs: list[tuple[int, int]] = []
    for r in sorted_rows:
        if runs and runs[-1][1] == r:
            runs[-1] = (runs[-1][0], r + 1)
        else:
            runs.append((r, r + 1))
    return runs

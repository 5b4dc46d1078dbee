"""Pallas TPU kernels for the streaming resharder's staging-buffer assembly.

The hot loop of LiveR's layer-streaming protocol (paper Algorithm 1, lines
13–17) gathers the planned rows of a source shard into the contiguous
staging buffer (pack) and overwrites received buffer rows into the new
parameter storage (scatter). A row is one index of dim 0; the row offsets
arrive as a scalar-prefetched table (``PrefetchScalarGridSpec``), so the
copy schedule is data-dependent without host round trips.

A TPU moves HBM in (8, 128) tiles (32-bit; 16 and 32 sublanes for 16- and
8-bit types), so how a row is copied depends on where the row lives:

* rank >= 3 (stacked layers ``(L, d, f)``): a row is a whole slab of tiles,
  and every copy is one HBM->HBM DMA of ``ref.at[pl.ds(start, rows)]`` —
  no VMEM, any row count.
* rank 2 (``(R, C)``, 32-bit): a row is one sublane of a tile row, which a
  DMA cannot address. Gathers DMA the aligned 8-row tile holding each row
  into VMEM and pick the row out at a dynamic sublane; scatters
  read-modify-write that tile, selecting the new row in by sublane index.
  The HBM reads are 8 rows per moved row; the bytes a plan stages and
  sends are unchanged.

``scatter_rows`` is the overwrite counterpart of ``pack_rows``: it writes
into an existing destination carried through ``input_output_aliases`` (the
destination is donated; untouched rows keep their bytes). Overwrite makes
re-streaming a dirty layer idempotent — the invariant the live re-sync path
depends on. Copies into the destination run strictly in offset-table
order, so duplicate starts resolve last-wins like the oracle's fori_loop.

Which shapes the chip's compiler takes is decided by :func:`tpu_layout`;
``ops.py`` sends everything else to the oracles and counts it.

Oracles: :func:`repro.kernels.ref.pack_rows_ref` / ``unpack_rows_ref`` /
``scatter_rows_ref`` / ``relayout_rows_ref``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# rows gathered per grid step of the rank-2 kernels (one 32-bit tile row)
GROUP = 8
# HBM->HBM copies kept in flight by the rank>=3 gather
DMA_WINDOW = 8
# VMEM the rank-2 kernels may stage per grid step; wider rows are split
# into lane chunks (a multiple of 128) that fit it
VMEM_BUDGET = 4 << 20

_ANY = pl.BlockSpec(memory_space=pl.ANY)  # left in HBM; the kernel DMAs it


def sublane_tile(dtype) -> int:
    """Rows in one HBM tile of ``dtype`` (8 for 32-bit, 16 / 32 packed)."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def tpu_layout(shape: tuple[int, ...], dtype) -> str | None:
    """How the chip can move rows of an array of this (per-device) shape:
    ``"dma"`` (rank >= 3: whole-slab DMAs), ``"tile"`` (rank-2 32-bit
    arrays: VMEM tile read-modify-write), or None when the TPU's tiling
    rules refuse both (the caller takes the oracle and counts it)."""
    if len(shape) >= 3:
        if shape[-1] % 128 == 0 and shape[-2] % sublane_tile(dtype) == 0:
            return "dma"
        return None
    if (
        len(shape) == 2
        and jnp.dtype(dtype).itemsize == 4
        and shape[0] % 8 == 0
        and shape[1] % 128 == 0
    ):
        return "tile"
    return None


def lane_chunk(C: int, itemsize: int, rows: int) -> int:
    """Widest lane chunk (a divisor of ``C``, multiple of 128 when it
    splits) whose ``rows`` x chunk staging fits ``VMEM_BUDGET``."""
    if rows * C * itemsize <= VMEM_BUDGET or C % 128:
        return C
    lanes = C // 128
    best = 1
    for d in range(1, lanes + 1):
        if lanes % d == 0 and rows * 128 * d * itemsize <= VMEM_BUDGET:
            best = d
    return 128 * best


def _row(starts_ref, k, block_rows: int):
    """Source/destination row of the k-th moved row."""
    return starts_ref[k // block_rows] + k % block_rows


def _tile_rows(R: int) -> int:
    """Rows in the VMEM tile a rank-2 kernel stages around a moved row."""
    return 8 if R % 8 == 0 else R


def _tile_base(r, t: int):
    return pl.multiple_of((r // t) * t, t) if t == 8 else 0


# ---------------------------------------------------------------------------
# rank >= 3: whole-slab HBM->HBM DMAs
# ---------------------------------------------------------------------------


def _dma_kernel(starts_ref, *refs, nb, block_rows, mode):
    """One DMA per offset-table entry. ``pack`` reads ``src[start]`` into
    buffer block i; ``scatter`` writes buffer block i to ``dst[start]``;
    ``relayout`` copies ``src[start]`` to ``dst[start]``. Gathers keep
    ``DMA_WINDOW`` copies in flight (their destinations are disjoint);
    scatters run one at a time so duplicate starts land in table order."""
    if mode == "pack":
        src, out, sem = refs
    else:  # scatter reads the buffer, relayout the source; dst is aliased
        src, _, out, sem = refs
    br = block_rows

    def copy(i):
        s = pl.ds(starts_ref[i], br)
        b = pl.ds(i * br, br)
        if mode == "pack":
            return pltpu.make_async_copy(src.at[s], out.at[b], sem)
        if mode == "scatter":
            return pltpu.make_async_copy(src.at[b], out.at[s], sem)
        return pltpu.make_async_copy(src.at[s], out.at[s], sem)

    if mode == "scatter":

        def serial(i, c):
            cp = copy(i)
            cp.start()
            cp.wait()
            return c

        jax.lax.fori_loop(0, nb, serial, 0)
        return

    # every copy has the same size, so any descriptor waits for one of them
    def issue(i, c):
        @pl.when(i >= DMA_WINDOW)
        def _():
            copy(0).wait()

        copy(i).start()
        return c

    jax.lax.fori_loop(0, nb, issue, 0)

    def drain(i, c):
        copy(0).wait()
        return c

    jax.lax.fori_loop(0, min(nb, DMA_WINDOW), drain, 0)


def _dma_call(mode, starts, args, out_shape, aliases, block_rows, interpret):
    nb = starts.shape[0]
    return pl.pallas_call(
        functools.partial(_dma_kernel, nb=nb, block_rows=block_rows, mode=mode),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1,),
            in_specs=[_ANY] * len(args),
            out_specs=_ANY,
            scratch_shapes=[pltpu.SemaphoreType.DMA(())],
        ),
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
    )(starts, *args)


# ---------------------------------------------------------------------------
# rank 2: rows are sublanes — stage the aligned tile in VMEM
# ---------------------------------------------------------------------------


def _gather_tile_kernel(starts_ref, src, o_ref, scr, sem, *, n, block_rows, t, cb):
    """Output rows [g*GROUP, (g+1)*GROUP) x lane chunk c: DMA each row's
    aligned tile into VMEM, then copy the row out at its sublane."""
    g, c = pl.program_id(0), pl.program_id(1)
    lanes = pl.ds(pl.multiple_of(c * cb, cb), cb)
    pending = []
    for j in range(GROUP):
        k = jnp.minimum(g * GROUP + j, n - 1)  # rows past n are masked out
        r = _row(starts_ref, k, block_rows)
        base = _tile_base(r, t)
        cp = pltpu.make_async_copy(src.at[pl.ds(base, t), lanes], scr.at[j], sem.at[j])
        cp.start()
        pending.append((cp, r - base))
    for j, (cp, off) in enumerate(pending):
        cp.wait()
        o_ref[pl.ds(j, 1), :] = scr[j, pl.ds(off, 1), :]


def _rmw_tile_kernel(starts_ref, *refs, n, block_rows, t, cb, mode):
    """Overwrite rows of the aliased destination through its VMEM tile:
    ``scatter`` takes row j of this step's buffer block, ``relayout`` takes
    the same row of ``src``. One row at a time, in table order."""
    if mode == "scatter":
        buf_ref, _, out, tile, src_tile, sem = refs
    else:
        src, _, out, tile, src_tile, sem = refs
    g, c = pl.program_id(0), pl.program_id(1)
    lanes = pl.ds(pl.multiple_of(c * cb, cb), cb)
    sub = jax.lax.broadcasted_iota(jnp.int32, (t, cb), 0)
    for j in range(GROUP):
        k = g * GROUP + j

        @pl.when(k < n)
        def _():
            r = _row(starts_ref, k, block_rows)
            base = _tile_base(r, t)
            window = out.at[pl.ds(base, t), lanes]
            rd = pltpu.make_async_copy(window, tile, sem.at[0])
            rd.start()
            if mode == "scatter":
                new = buf_ref[pl.ds(j, 1), :]
            else:
                rs = pltpu.make_async_copy(
                    src.at[pl.ds(base, t), lanes], src_tile, sem.at[1]
                )
                rs.start()
                rs.wait()
                new = src_tile[...]
            rd.wait()
            tile[...] = jnp.where(sub == r - base, new, tile[...])
            wr = pltpu.make_async_copy(tile, window, sem.at[0])
            wr.start()
            wr.wait()


def _group_block(cb: int):
    """GROUP rows x lane chunk cb of an (n, C) buffer, per grid step."""
    return pl.BlockSpec((GROUP, cb), lambda g, c, s: (g, c))


def _tile_call(kernel, starts, args, n, R, C, dtype, *, specs, scratch,
               out_shape, aliases, interpret, **kw):
    """Launch a rank-2 kernel on a (row groups, lane chunks) grid;
    ``specs(cb)`` gives (in_specs, out_spec), ``scratch(t, cb)`` the VMEM
    and semaphores. Every step runs in order ("arbitrary"): scatters must
    land in table order."""
    t = _tile_rows(R)
    cb = lane_chunk(C, jnp.dtype(dtype).itemsize, GROUP * t)
    in_specs, out_spec = specs(cb)
    return pl.pallas_call(
        functools.partial(kernel, n=n, t=t, cb=cb, **kw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(pl.cdiv(n, GROUP), C // cb),
            in_specs=in_specs,
            out_specs=out_spec,
            scratch_shapes=scratch(t, cb),
        ),
        out_shape=out_shape,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")
        ),
        interpret=interpret,
    )(starts, *args)


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------


def pack_rows_pallas(
    src: jax.Array,  # (R, *tail)
    row_starts: jax.Array,  # (nb,) int32 block starts
    block_rows: int,
    interpret: bool = False,
) -> jax.Array:
    """Gather nb blocks of ``block_rows`` rows into (nb*block_rows, *tail)."""
    nb = row_starts.shape[0]
    n = nb * block_rows
    out_shape = jax.ShapeDtypeStruct((n,) + src.shape[1:], src.dtype)
    if src.ndim >= 3:
        return _dma_call("pack", row_starts, (src,), out_shape, {}, block_rows, interpret)
    R, C = src.shape
    return _tile_call(
        _gather_tile_kernel, row_starts, (src,), n, R, C, src.dtype,
        specs=lambda cb: ([_ANY], _group_block(cb)),
        out_shape=out_shape, aliases={}, interpret=interpret, block_rows=block_rows,
        scratch=lambda t, cb: [
            pltpu.VMEM((GROUP, t, cb), src.dtype),
            pltpu.SemaphoreType.DMA((GROUP,)),
        ],
    )


def scatter_rows_pallas(
    dst: jax.Array,  # (R, *tail) — donated; aliased into the output
    buf: jax.Array,  # (nb*block_rows, *tail)
    row_starts: jax.Array,  # (nb,) int32
    block_rows: int,
    interpret: bool = False,
) -> jax.Array:
    """Overwrite-scatter buffer blocks into ``dst`` at the given row offsets.

    ``dst`` is aliased to the output (``input_output_aliases``), so rows
    not named by ``row_starts`` keep their existing bytes — no zero base,
    no full-destination rewrite. Duplicate starts resolve last-wins. The
    caller must treat ``dst`` as donated.
    """
    n = row_starts.shape[0] * block_rows
    out_shape = jax.ShapeDtypeStruct(dst.shape, dst.dtype)
    # flattened input index 2 (starts, buf, dst) -> output 0
    if dst.ndim >= 3:
        return _dma_call("scatter", row_starts, (buf, dst), out_shape, {2: 0},
                         block_rows, interpret)
    R, C = dst.shape
    return _tile_call(
        _rmw_tile_kernel, row_starts, (buf, dst), n, R, C, dst.dtype,
        specs=lambda cb: ([_group_block(cb), _ANY], _ANY),
        out_shape=out_shape, aliases={2: 0}, interpret=interpret,
        block_rows=block_rows, mode="scatter",
        scratch=lambda t, cb: [
            pltpu.VMEM((t, cb), dst.dtype),
            pltpu.VMEM((1, 128), dst.dtype),  # unused by scatter
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )


def relayout_rows_pallas(
    dst: jax.Array,  # (R, *tail) — donated; aliased into the output
    src: jax.Array,  # (R, *tail) — same global shape, different layout
    row_starts: jax.Array,  # (nb,) int32
    block_rows: int,
    interpret: bool = False,
) -> jax.Array:
    """Fused gather→scatter for the classified plan IR's "local" cells: copy
    blocks of ``src`` into ``dst`` at the same row offsets in ONE kernel,
    with no intermediate staging buffer. ``dst`` is aliased to the output,
    so untouched rows keep their bytes and re-applying is idempotent,
    exactly like ``scatter_rows``."""
    n = row_starts.shape[0] * block_rows
    out_shape = jax.ShapeDtypeStruct(dst.shape, dst.dtype)
    # flattened input index 2 (starts, src, dst) -> output 0
    if dst.ndim >= 3:
        return _dma_call("relayout", row_starts, (src, dst), out_shape, {2: 0},
                         block_rows, interpret)
    R, C = dst.shape
    return _tile_call(
        _rmw_tile_kernel, row_starts, (src, dst), n, R, C, dst.dtype,
        specs=lambda cb: ([_ANY, _ANY], _ANY),
        out_shape=out_shape, aliases={2: 0}, interpret=interpret,
        block_rows=block_rows, mode="relayout",
        scratch=lambda t, cb: [
            pltpu.VMEM((t, cb), dst.dtype),
            pltpu.VMEM((t, cb), dst.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )


def unpack_rows_pallas(
    buf: jax.Array,  # (nb*block_rows, *tail)
    row_starts: jax.Array,  # (nb,) int32
    block_rows: int,
    out_rows: int,
    interpret: bool = False,
) -> jax.Array:
    """Scatter buffer blocks into a zeroed (out_rows, *tail) array."""
    zeros = jnp.zeros((out_rows,) + buf.shape[1:], buf.dtype)
    return scatter_rows_pallas(zeros, buf, row_starts, block_rows, interpret)

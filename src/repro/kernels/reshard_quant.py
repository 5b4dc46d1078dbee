"""Pallas TPU kernels for the compressed wire format of the streaming resharder.

The reshard data plane (paper Algorithm 1; ``reshard_pack.py``) moves raw
bytes: pack gathers planned row-blocks into the staging buffer, scatter
overwrites them into the destination shard. After the delta planner (PR 6)
the bytes that still cross the wire are dominated by optimizer moments,
which tolerate aggressive formats — so these kernels fuse symmetric
quantization into the pack (bf16/fp32 → int8 or fp8-e4m3, one per-tile
scale per row-block carried in a float32 sidecar array) and the matching
dequantization into the overwrite-scatter. A tile is one ``block_rows``
row-block, i.e. one grid step of the pack kernel; the sidecar has one
scale per tile.

Quantization is symmetric around zero with a per-tile scale::

    scale = max(absmax(tile), eps) / qmax        # eps floor: all-zero tiles
    int8:      q = clip(round(x / scale), -127, 127)
    fp8-e4m3:  q = cast_fp8(x / scale)           # |x/scale| <= 448 by construction

and dequant is ``q * scale`` cast back to the destination dtype. Both
directions are deterministic elementwise maps, so streaming the same tile
twice produces bitwise-identical destination bytes — the idempotence
invariant the dirty-layer re-stream path depends on survives compression.

``dequant_scatter_rows`` composes with ``scatter_rows``'s overwrite
semantics: the destination is donated and aliased into the output
(``input_output_aliases``), untouched rows keep their bytes, duplicate
starts resolve last-wins on the sequential grid.

This module is also the home of the int8 symmetric-quant math that used to
live in ``distribution/compress.py`` (per-tensor :func:`quantize_int8` /
:func:`dequantize_int8` and the error-feedback round trip
:func:`compress_decompress_with_ef`): the gradient-compression path and the
wire format now share one quantizer definition, and the per-tensor
functions double as the scalar oracle the kernel tests check against.

Oracles: :func:`repro.kernels.ref.pack_quant_rows_ref` /
``dequant_scatter_rows_ref``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Smallest representable scale floor: keeps all-zero (and fully denormal)
# tiles from dividing by zero; such tiles quantize to 0 and dequantize to 0.
QUANT_EPS = 1e-12

# np.finfo(float8_e4m3fn) raises on some numpy versions — hardcode the max.
FP8_E4M3_MAX = 448.0

WIRE_QMAX = {"int8": 127.0, "fp8_e4m3": FP8_E4M3_MAX}
WIRE_QDTYPE = {"int8": jnp.int8, "fp8_e4m3": jnp.float8_e4m3fn}
# float32 per-tile scale carried alongside the quantized payload
SIDECAR_BYTES_PER_TILE = 4


def wire_itemsize(fmt: str) -> int:
    """Bytes per element of the quantized payload (both formats are 1B)."""
    return jnp.dtype(WIRE_QDTYPE[fmt]).itemsize


def _quantize(xf: jax.Array, scale: jax.Array, fmt: str) -> jax.Array:
    """``xf / scale`` in the wire format (``scale`` broadcasts against
    ``xf``). Shared by the kernel bodies, the per-tensor quantizer and the
    oracle so every path is the same arithmetic."""
    y = xf / scale
    if fmt == "int8":
        qmax = WIRE_QMAX[fmt]
        return jnp.clip(jnp.round(y), -qmax, qmax).astype(jnp.int8)
    return y.astype(jnp.float8_e4m3fn)


def _tile_scale(absmax: jax.Array, fmt: str) -> jax.Array:
    """Per-tile scale ``absmax * (1/qmax)`` with the reciprocal folded to a
    float32 constant, NOT ``absmax / qmax``: XLA strength-reduces division
    by a constant to a reciprocal multiply only in some fusion contexts, so
    the divide form computes 1-ULP-different scales between compilation
    contexts. Multiply form is bitwise-stable."""
    return jnp.maximum(absmax, QUANT_EPS) * jnp.float32(1.0 / WIRE_QMAX[fmt])


def _quantize_tile(x: jax.Array, fmt: str) -> tuple[jax.Array, jax.Array]:
    """Quantize one tile (any shape) → (q, scale ()-float32)."""
    xf = x.astype(jnp.float32)
    scale = _tile_scale(jnp.max(jnp.abs(xf)), fmt)
    return _quantize(xf, scale, fmt), scale


def _dequantize_tile(q: jax.Array, scale: jax.Array, out_dtype) -> jax.Array:
    return (q.astype(jnp.float32) * scale).astype(out_dtype)


# ---------------------------------------------------------------------------
# Pallas kernels (rank-2 ``(R, C)`` leaves; rows staged as in reshard_pack)
# ---------------------------------------------------------------------------


def quant_group(block_rows: int) -> int:
    """Rows per grid step: whole 8-bit payload tiles (32 rows) holding a
    multiple of 8 quantization tiles, so the (tiles, 1) sidecar block is
    8-row aligned."""
    return math.lcm(32, 8 * block_rows)


def quant_vmem_bytes(C: int, block_rows: int) -> int:
    """VMEM one grid step of the quant kernels stages for a width-C row."""
    g = quant_group(block_rows)
    return g * 8 * C * 4 + g * C * 4 + 2 * g * C


def _pack_quant_kernel(starts_ref, src, q_ref, s_ref, scr, rows, sem, *,
                       n, block_rows, t, fmt):
    """One step = ``G`` payload rows: DMA each row's aligned source tile
    into VMEM, collect the rows, then quantize the whole block at once with
    one scale per ``block_rows``-row tile."""
    G = quant_group(block_rows)
    g = pl.program_id(0)
    pending = []
    for j in range(G):
        k = jnp.minimum(g * G + j, n - 1)  # rows past n are masked out
        r = starts_ref[k // block_rows] + k % block_rows
        base = pl.multiple_of((r // t) * t, t) if t == 8 else 0
        cp = pltpu.make_async_copy(src.at[pl.ds(base, t)], scr.at[j], sem.at[j])
        cp.start()
        pending.append((cp, r - base))
    for j, (cp, off) in enumerate(pending):
        cp.wait()
        rows[pl.ds(j, 1), :] = scr[j, pl.ds(off, 1), :].astype(jnp.float32)
    x = rows[...]
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)  # (G, 1)
    if block_rows == 1:
        scale = _tile_scale(absmax, fmt)
        s_ref[...] = scale
    else:
        ntiles = G // block_rows
        row_tile = jax.lax.broadcasted_iota(jnp.int32, (G, 1), 0) // block_rows
        tile_id = jax.lax.broadcasted_iota(jnp.int32, (ntiles, 1), 0)
        scale = jnp.zeros((G, 1), jnp.float32)
        scales = jnp.zeros((ntiles, 1), jnp.float32)
        for m in range(ntiles):
            s_m = _tile_scale(
                jnp.max(absmax[m * block_rows : (m + 1) * block_rows]), fmt
            )
            scale = jnp.where(row_tile == m, s_m, scale)
            scales = jnp.where(tile_id == m, s_m, scales)
        s_ref[...] = scales
    q_ref[...] = _quantize(x, scale, fmt)


def pack_quant_rows_pallas(
    src: jax.Array,  # (R, C)
    row_starts: jax.Array,  # (nb,) int32
    block_rows: int,
    fmt: str,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Gather + quantize nb row-blocks: ((nb*block_rows, C) q, (nb, 1) f32).

    The rows are gathered through the scalar-prefetched offset table into
    VMEM, their absmax reduced in-register, and the quantized payload plus
    sidecar scales written in the same pass — no second HBM round trip over
    the staged bytes to compute scales.
    """
    nb = row_starts.shape[0]
    n = nb * block_rows
    R, C = src.shape
    G = quant_group(block_rows)
    t = 8 if R % 8 == 0 else R
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(pl.cdiv(n, G),),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[
            pl.BlockSpec((G, C), lambda g, s: (g, 0)),
            pl.BlockSpec((G // block_rows, 1), lambda g, s: (g, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((G, t, C), src.dtype),
            pltpu.VMEM((G, C), jnp.float32),
            pltpu.SemaphoreType.DMA((G,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _pack_quant_kernel, n=n, block_rows=block_rows, t=t, fmt=fmt
        ),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n, C), WIRE_QDTYPE[fmt]),
            jax.ShapeDtypeStruct((nb, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(row_starts, src)


def _dequant_scatter_kernel(starts_ref, scales_ref, buf_ref, dst, out, deq, tile,
                            sem, *, n, block_rows, t):
    """One step = ``G`` payload rows: dequantize the block, then overwrite
    each row into the aliased destination through its VMEM tile, one row at
    a time in table order (duplicate starts last-wins)."""
    del dst  # aliased into ``out``
    G = quant_group(block_rows)
    g = pl.program_id(0)
    deq[...] = buf_ref[...].astype(jnp.float32)
    sub = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 0)
    for j in range(G):
        k = g * G + j

        @pl.when(k < n)
        def _():
            r = starts_ref[k // block_rows] + k % block_rows
            base = pl.multiple_of((r // t) * t, t) if t == 8 else 0
            window = out.at[pl.ds(base, t)]
            rd = pltpu.make_async_copy(window, tile, sem)
            rd.start()
            row = (deq[pl.ds(j, 1), :] * scales_ref[k // block_rows]).astype(
                tile.dtype
            )
            rd.wait()
            tile[...] = jnp.where(sub == r - base, row, tile[...])
            wr = pltpu.make_async_copy(tile, window, sem)
            wr.start()
            wr.wait()


def dequant_scatter_rows_pallas(
    dst: jax.Array,  # (R, C) — donated; aliased into the output
    buf: jax.Array,  # (nb*block_rows, C) quantized payload
    scales: jax.Array,  # (nb, 1) float32 sidecar
    row_starts: jax.Array,  # (nb,) int32
    block_rows: int,
    interpret: bool = False,
) -> jax.Array:
    """Dequantize + overwrite-scatter tiles into ``dst`` at the row offsets.

    The compressed-wire counterpart of ``scatter_rows_pallas``: same
    aliased-destination overwrite semantics (untouched rows keep their
    bytes, duplicate starts last-wins), with the per-tile dequant fused in
    front of the store instead of materializing a dequantized staging
    buffer first. The sidecar rides in SMEM next to the offset table.
    """
    nb = row_starts.shape[0]
    n = nb * block_rows
    R, C = dst.shape
    G = quant_group(block_rows)
    t = 8 if R % 8 == 0 else R
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(pl.cdiv(n, G),),
        in_specs=[
            pl.BlockSpec((G, C), lambda g, s, sc: (g, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((G, C), jnp.float32),
            pltpu.VMEM((t, C), dst.dtype),
            pltpu.SemaphoreType.DMA(()),
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _dequant_scatter_kernel, n=n, block_rows=block_rows, t=t
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(dst.shape, dst.dtype),
        # flattened input index 3 (starts, scales, buf, dst) -> output 0
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(row_starts, scales.reshape(nb), buf, dst)


# ---------------------------------------------------------------------------
# Per-tensor int8 quantization + error feedback (ex distribution/compress.py)
# ---------------------------------------------------------------------------


def quantize_int8(g: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric per-tensor int8 quantization: (q int8, scale ()-f32).

    The whole-tensor special case of the wire format's per-tile quantizer
    (one tile = the tensor); kept as the gradient-compression entry point.
    """
    return _quantize_tile(g, "int8")


def dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return _dequantize_tile(q, scale, jnp.float32)


def compress_decompress_with_ef(grads, opt_state):
    """Int8 round trip with error feedback carried in ``opt_state['ef']``.

    Each leaf adds its residual from the previous step before quantizing
    and stores the new residual, so the quantization error is re-injected
    instead of lost (beyond-paper extension, DESIGN.md §8).
    """
    ef = opt_state["ef"]

    def leaf(g, e):
        corrected = g.astype(jnp.float32) + e
        q, scale = quantize_int8(corrected)
        deq = dequantize_int8(q, scale)
        return deq, corrected - deq

    flat_g, treedef = jax.tree_util.tree_flatten(grads)
    flat_e = treedef.flatten_up_to(ef)
    outs = [leaf(g, e) for g, e in zip(flat_g, flat_e)]
    new_g = jax.tree_util.tree_unflatten(treedef, [o[0] for o in outs])
    new_e = jax.tree_util.tree_unflatten(treedef, [o[1] for o in outs])
    new_opt = dict(opt_state)
    new_opt["ef"] = new_e
    return new_g, new_opt

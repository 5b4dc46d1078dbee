"""Pallas TPU fused RMSNorm.

Grid over row blocks; each step normalizes a ``(block_rows, d)`` tile held in
VMEM (one pass: square-reduce + rsqrt + scale — avoids the extra HBM round
trip of the unfused mean/var + mul sequence). d is the model dimension
(always a multiple of 128 for the assigned archs).

Oracle: :func:`repro.kernels.ref.rmsnorm_ref`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rmsnorm_kernel(x_ref, scale_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)  # (rows, d)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps) * scale_ref[...].astype(jnp.float32)[None, :]
    o_ref[...] = y.astype(o_ref.dtype)


def rmsnorm_pallas(
    x: jax.Array,  # (..., d)
    scale: jax.Array,  # (d,)
    eps: float = 1e-6,
    block_rows: int = 256,
    interpret: bool = False,
) -> jax.Array:
    orig_shape = x.shape
    d = orig_shape[-1]
    rows = 1
    for dim in orig_shape[:-1]:
        rows *= dim
    x2 = x.reshape(rows, d)
    block_rows = min(block_rows, rows)
    # pad rows to a multiple of block_rows
    pad = (-rows) % block_rows
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    grid = (x2.shape[0] // block_rows,)

    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x2, scale)
    if pad:
        out = out[:rows]
    return out.reshape(orig_shape)

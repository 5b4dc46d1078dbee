"""Pallas TPU kernel for the Mamba-2 SSD *intra-chunk* block.

The SSD chunked algorithm splits into (a) a quadratic-within-chunk part —
``(C·Bᵀ ∘ L) · X`` plus the per-chunk state contribution — which dominates
FLOPs and is what this kernel computes, and (b) a cheap O(num_chunks)
inter-chunk recurrence handled in plain JAX by the wrapper in ``ops.py``.

Grid: ``(batch, heads, num_chunks)``, one (chunk × head_dim) tile per step.
The kernel runs heads-major so every block's last two dims are legal TPU
tiles: x/y as ``(1, 1, chunk, p)`` blocks of ``(b, h, s, p)``, and the
per-step scalars dt and cum both as a row ``(1, chunk)`` of
``(b, h, nc, 1, chunk)`` and as a column ``(chunk, 1)`` of ``(b, h, s, 1)`` (the decay matrix needs
cum along both axes; a column/row pair avoids an in-kernel transpose).

Oracle: :func:`repro.kernels.ref.ssd_scan_ref` (intra-chunk terms).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_chunk_kernel(
    x_ref,  # (1, 1, chunk, p)
    dt_row_ref,  # (1, 1, 1, 1, chunk)
    cum_row_ref,  # (1, 1, 1, 1, chunk)   cumsum(dt*A) within chunk
    dt_col_ref,  # (1, 1, chunk, 1)
    cum_col_ref,  # (1, 1, chunk, 1)
    b_ref,  # (1, chunk, n)
    c_ref,  # (1, chunk, n)
    y_ref,  # (1, 1, chunk, p)  intra-chunk output
    s_ref,  # (1, 1, 1, p, n)   chunk state contribution
    *,
    chunk: int,
):
    x = x_ref[0, 0].astype(jnp.float32)  # (q, p)
    dt_row = dt_row_ref[0, 0, 0]  # (1, q)
    cum_row = cum_row_ref[0, 0, 0]  # (1, q)
    dt_col = dt_col_ref[0, 0]  # (q, 1)
    cum_col = cum_col_ref[0, 0]  # (q, 1)
    B = b_ref[0].astype(jnp.float32)  # (q, n)
    C = c_ref[0].astype(jnp.float32)  # (q, n)

    # decay matrix L[t,s] = exp(cum_t - cum_s) for s <= t
    ti = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    L = jnp.where(si <= ti, jnp.exp(cum_col - cum_row), 0.0)

    CB = jax.lax.dot_general(
        C, B, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (q, q)
    M = CB * L * dt_row
    y = jax.lax.dot_general(
        M, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (q, p)
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # state contribution: S = sum_s exp(cum_last - cum_s) dt_s x_s ⊗ B_s
    w = jnp.exp(cum_row[:, chunk - 1 :] - cum_col) * dt_col  # (q, 1)
    S = jax.lax.dot_general(
        x * w, B, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (p, n)
    s_ref[0, 0, 0] = S.astype(s_ref.dtype)


def ssd_intra_chunk_pallas(
    x: jax.Array,  # (b, s, h, p)
    dt: jax.Array,  # (b, s, h) float32
    cum: jax.Array,  # (b, s, h) float32 within-chunk cumsum of dt*A
    B: jax.Array,  # (b, s, n) float32
    C: jax.Array,  # (b, s, n) float32
    chunk: int,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Returns (y_intra (b,s,h,p) f32, S (b,nc,h,p,n) f32)."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    assert s % chunk == 0
    nc = s // chunk
    grid = (b, h, nc)

    xh = x.transpose(0, 2, 1, 3)  # (b, h, s, p)
    dth = dt.astype(jnp.float32).transpose(0, 2, 1)  # (b, h, s)
    cumh = cum.astype(jnp.float32).transpose(0, 2, 1)
    row = lambda a: a.reshape(b, h, nc, 1, chunk)
    col = lambda a: a[:, :, :, None]  # (b, h, s, 1)
    row_spec = pl.BlockSpec(
        (1, 1, 1, 1, chunk), lambda bi, hi, ci: (bi, hi, ci, 0, 0)
    )
    col_spec = pl.BlockSpec((1, 1, chunk, 1), lambda bi, hi, ci: (bi, hi, ci, 0))
    x_spec = pl.BlockSpec((1, 1, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0))
    bc_spec = pl.BlockSpec((1, chunk, n), lambda bi, hi, ci: (bi, ci, 0))

    kernel = functools.partial(_ssd_chunk_kernel, chunk=chunk)
    y, S = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[x_spec, row_spec, row_spec, col_spec, col_spec, bc_spec, bc_spec],
        out_specs=[
            x_spec,
            pl.BlockSpec((1, 1, 1, p, n), lambda bi, hi, ci: (bi, ci, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, p), jnp.float32),
            jax.ShapeDtypeStruct((b, nc, h, p, n), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
        ),
        interpret=interpret,
    )(xh, row(dth), row(cumh), col(dth), col(cumh), B, C)
    return y.transpose(0, 2, 1, 3), S

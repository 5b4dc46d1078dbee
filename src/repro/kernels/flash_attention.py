"""Pallas TPU flash attention with causal/sliding-window masking and GQA
head mapping, differentiable through ``jax.custom_vjp``.

Layout: the kernel runs heads-major — q ``(b, h, s, d)``, k/v
``(b, kh, t, d)`` — so every block is ``(1, 1, block, d)`` and its last two
dims are a full ``(block, head_dim)`` tile, which the TPU's (8, 128) block
rule accepts for any head_dim. The public entry point keeps the model's
``(b, s, h, d)`` layout and transposes around the kernel.

Grid: ``(batch, q_heads, num_q_blocks, num_k_blocks)`` with the k-block
dimension innermost ("arbitrary" semantics) so the VMEM scratch
accumulators (running max / denominator / output block) persist across the
online-softmax reduction — the canonical TPU flash pattern. k blocks that
the causal/window mask removes entirely are skipped.

Backward: the VJP of the jnp oracle, recomputed from (q, k, v) — no Pallas
backward kernel yet. :data:`BACKWARD` names what runs, so a caller can
report it.

The oracle is :func:`repro.kernels.ref.flash_attention_ref`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref as _ref

NEG_INF = -1e30
BACKWARD = "reference VJP (jnp oracle, recomputed)"


def _fa_kernel(
    q_ref,  # (1, 1, block_q, d)
    k_ref,  # (1, 1, block_k, d)
    v_ref,  # (1, 1, block_k, d)
    o_ref,  # (1, 1, block_q, d)
    m_scr,  # (block_q, 1) f32 scratch
    l_scr,  # (block_q, 1) f32 scratch
    acc_scr,  # (block_q, d) f32 scratch
    *,
    scale: float,
    causal: bool,
    window: int,
    block_q: int,
    block_k: int,
    q_offset: int,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_first = qi * block_q + q_offset
    k_first = ki * block_k
    live = True
    if causal:
        live = k_first <= q_first + block_q - 1
    if window > 0:
        live = jnp.logical_and(live, k_first + block_k - 1 > q_first - window)

    @pl.when(live)
    def _step():
        q = q_ref[0, 0]  # (bq, d)
        k = k_ref[0, 0]  # (bk, d)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (bq, bk)
        qpos = q_first + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_first + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window > 0:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]  # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)  # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)  # (bq, 1)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _flush():
        denom = jnp.where(l_scr[...] == 0.0, 1.0, l_scr[...])
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def _fa_forward(q, k, v, causal, window, scale, block_q, block_k, interpret):
    """Heads-major kernel call: q (b, h, s, d), k/v (b, kh, t, d)."""
    b, h, s, d = q.shape
    kh, t = k.shape[1], k.shape[2]
    rep = h // kh
    grid = (b, h, s // block_q, t // block_k)
    kernel = functools.partial(
        _fa_kernel,
        scale=scale,
        causal=causal,
        window=window,
        block_q=block_q,
        block_k=block_k,
        q_offset=t - s,  # right-aligned queries (prefill continuation)
    )
    kv_spec = pl.BlockSpec(
        (1, 1, block_k, d), lambda bi, hi, qi, ki: (bi, hi // rep, ki, 0)
    )
    q_spec = pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, window, scale, block_q, block_k, interpret):
    heads_major = lambda x: x.transpose(0, 2, 1, 3)
    out = _fa_forward(
        heads_major(q), heads_major(k), heads_major(v),
        causal, window, scale, block_q, block_k, interpret,
    )
    return heads_major(out)


def _flash_fwd(q, k, v, causal, window, scale, block_q, block_k, interpret):
    out = _flash(q, k, v, causal, window, scale, block_q, block_k, interpret)
    return out, (q, k, v)


def _flash_bwd(causal, window, scale, block_q, block_k, interpret, res, g):
    q, k, v = res
    with jax.named_scope("flash_bwd"):
        _, vjp = jax.vjp(
            lambda q, k, v: _ref.flash_attention_ref(
                q, k, v, causal=causal, window=window, scale=scale
            ),
            q, k, v,
        )
        return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention_pallas(
    q: jax.Array,  # (b, s, h, d)
    k: jax.Array,  # (b, t, kh, d)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    s, t, d = q.shape[1], k.shape[1], q.shape[-1]
    if scale is None:
        scale = d**-0.5
    block_q = min(block_q, s)
    block_k = min(block_k, t)
    assert s % block_q == 0 and t % block_k == 0, (s, t, block_q, block_k)
    return _flash(q, k, v, causal, window, float(scale), block_q, block_k, interpret)

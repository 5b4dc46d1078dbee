"""Pallas TPU flash attention with causal/sliding-window masking and GQA
head mapping, differentiable through ``jax.custom_vjp``.

Layout: the kernels run heads-major — q ``(b, h, s, d)``, k/v
``(b, kh, t, d)`` — so every block is ``(1, 1, block, d)`` and its last two
dims are a full ``(block, head_dim)`` tile, which the TPU's (8, 128) block
rule accepts for any head_dim. The public entry point keeps the model's
``(b, s, h, d)`` layout and transposes around the kernels.

Tiling, shared by the forward and the backward: blocks follow the shape
(:func:`_block`: the largest candidate that divides the length, up to 1024
rows in the forward and 512 in the backward), a dead
(q block, k block) pair skips its compute and its index map clamps to a
live neighbour, so it costs no DMA, and only pairs that straddle the causal
diagonal or the window's edge build a mask.

Forward grid: ``(batch, q_heads, q blocks, k blocks)`` with the k-block
dimension innermost ("arbitrary" semantics) so the VMEM scratch
accumulators (running max / denominator / output block) persist across the
online-softmax reduction — the canonical TPU flash pattern. A q block's k/v
index map clamps into the span of k blocks its queries see
(:func:`_k_span`): a causally dead or out-of-window step re-addresses the
block already in VMEM. Its one output, of q's shape and dtype, is the
signature by which the benchmark finds the forward.

Backward (:data:`BACKWARD`), all under the ``flash_bwd`` scope, from the
residuals (q, k, v, o):

- an lse pass over the live (q block, k block) pairs writes each query's
  log-sum-exp as f32 rows ``(b, h, 1, s)``;
- ``D = rowsum(dO ∘ O)`` in f32, laid out the same way;
- a dK/dV kernel, grid ``(b, kh, k blocks, rep × q blocks)``, sums the GQA
  group's query heads in f32 VMEM accumulators;
- a dQ kernel, grid ``(b, h, q blocks, k blocks)``.

Both kernels recompute ``p = exp(s − lse)`` a block at a time and work in
the transposed orientation ``(k, q)``, so lse and D broadcast as rows.
Matmul operands keep the input dtype, p and dS are cast to it, and every
product accumulates in f32. dQ is written in f32 and dK/dV as one
two-output call: no backward call has a single bf16 output of q's shape.

The oracle is :func:`repro.kernels.ref.flash_attention_ref`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
BACKWARD = "Pallas flash backward (lse pass, dK/dV and dQ kernels)"


# ---------------------------------------------------------------------------
# tiling shared by the forward and the backward
# ---------------------------------------------------------------------------

_BLOCKS = (1024, 512, 256, 128)
# the largest block of each direction, chosen by timing on a TPU v5e at the
# benchmark cells' shapes: the forward gains up to 1024, the backward keeps
# the 512 it was measured at
_FWD_BLOCK, _BWD_BLOCK = 1024, 512
_NT = (((1,), (1,)), ((), ()))  # a · bᵀ


def _block(n: int, largest: int) -> int:
    """The largest candidate block up to ``largest`` that divides ``n``;
    ``n`` itself (one full-length block) when none does."""
    return next((c for c in _BLOCKS if c <= largest and n % c == 0), n)


def _k_span(q_first, bq, bk, nk, causal, window):
    """First and last k block holding a key that some query of the q block
    starting at position ``q_first`` attends to."""
    lo, hi = 0, nk - 1
    if causal:
        hi = jnp.minimum(hi, (q_first + bq - 1) // bk)
    if window > 0:
        lo = jnp.maximum(lo, (q_first - window + 1) // bk)
    return lo, hi


def _q_span(k_first, bq, bk, nq, q_offset, causal, window):
    """First and last q block with a query that attends to some key of the
    k block starting at ``k_first`` (empty, lo > hi, where none does)."""
    lo, hi = 0, nq - 1
    if causal:
        lo = jnp.maximum(lo, (k_first - q_offset) // bq)
    if window > 0:
        hi = jnp.minimum(hi, (k_first + bk - 2 + window - q_offset) // bq)
    return lo, hi


def _clamp(i, lo, hi, n):
    """Block index ``i`` moved into [lo, hi]: a dead step fetches the block
    a live neighbour fetches, so Pallas issues no DMA for it."""
    return jnp.clip(jnp.clip(i, lo, hi), 0, n - 1)


def _needs_mask(q_first, k_first, bq, bk, causal, window):
    """Whether some (query, key) pair of the block pair is masked out."""
    cut = False
    if causal:
        cut = k_first + bk - 1 > q_first
    if window > 0:
        cut = jnp.logical_or(cut, k_first <= q_first + bq - 1 - window)
    return cut


def _masked(x, q_first, k_first, q_axis, *, causal, window):
    """Scores ``x`` of a block pair, queries along ``q_axis``, with the pairs
    the causal/window mask removes set to NEG_INF."""
    qpos = q_first + jax.lax.broadcasted_iota(jnp.int32, x.shape, q_axis)
    kpos = k_first + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1 - q_axis)
    keep = jnp.ones(x.shape, jnp.bool_)
    if causal:
        keep &= kpos <= qpos
    if window > 0:
        keep &= kpos > qpos - window
    return jnp.where(keep, x, NEG_INF)


def _live_blocks(live, cut, block):
    """Run ``block(masked)`` on a live block pair, masking only where the
    pair straddles the causal diagonal or the window's edge."""
    pl.when(jnp.logical_and(live, cut))(functools.partial(block, True))
    pl.when(jnp.logical_and(live, jnp.logical_not(cut)))(functools.partial(block, False))


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
)


def _k_inner_specs(q, k, causal, window, block_q, block_k):
    """Block specs of a ``(b, h, q blocks, k blocks)`` grid: q-shaped, k-shaped
    (a q block's dead k blocks clamped to its span) and lse-row blocks."""
    d, rep = q.shape[3], q.shape[1] // k.shape[1]
    nk, q_offset = k.shape[2] // block_k, k.shape[2] - q.shape[2]

    def k_block(bi, hi, qi, ki):
        lo, top = _k_span(qi * block_q + q_offset, block_q, block_k, nk, causal, window)
        return (bi, hi // rep, _clamp(ki, lo, top, nk), 0)

    return (
        pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        pl.BlockSpec((1, 1, block_k, d), k_block),
        pl.BlockSpec((1, 1, 1, block_q), lambda bi, hi, qi, ki: (bi, hi, 0, qi)),
    )


def _static(q, k, causal, window, scale, block_q, block_k):
    # q_offset: queries are right-aligned to the keys (chunked prefill: t > s)
    return dict(scale=scale, causal=causal, window=window, bq=block_q, bk=block_k,
                q_offset=k.shape[2] - q.shape[2])


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *, scale, causal,
               window, bq, bk, q_offset):
    qi, ki, nk = pl.program_id(2), pl.program_id(3), pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_first, k_first = qi * bq + q_offset, ki * bk
    lo, hi = _k_span(q_first, bq, bk, nk, causal, window)

    def block(masked):
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q_ref[0, 0], k_ref[0, 0], _NT, preferred_element_type=jnp.float32
        ) * scale  # (bq, bk)
        if masked:
            s = _masked(s, q_first, k_first, 0, causal=causal, window=window)
        m_prev = m_scr[...]  # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    live = jnp.logical_and(ki >= lo, ki <= hi)
    _live_blocks(live, _needs_mask(q_first, k_first, bq, bk, causal, window), block)

    @pl.when(ki == nk - 1)
    def _flush():
        denom = jnp.where(l_scr[...] == 0.0, 1.0, l_scr[...])
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def _fa_forward(q, k, v, causal, window, scale, block_q, block_k, interpret):
    """Heads-major kernel call: q (b, h, s, d), k/v (b, kh, t, d) -> the
    output in q's shape and dtype."""
    b, h, s, d = q.shape
    q_spec, kv_spec, _ = _k_inner_specs(q, k, causal, window, block_q, block_k)
    f32 = jnp.float32
    return pl.pallas_call(
        functools.partial(_fa_kernel, **_static(q, k, causal, window, scale, block_q, block_k)),
        grid=(b, h, s // block_q, k.shape[2] // block_k),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), f32),
            pltpu.VMEM((block_q, 1), f32),
            pltpu.VMEM((block_q, d), f32),
        ],
        compiler_params=_PARAMS,
        interpret=interpret,
    )(q, k, v)


# ---------------------------------------------------------------------------
# backward: an lse pass, then dK/dV and dQ kernels that recompute p from it
# ---------------------------------------------------------------------------


def _scores_t(q, k, q_first, k_first, masked, *, scale, causal, window):
    """sᵀ = k qᵀ · scale, (bk, bq) in f32, masked entries at NEG_INF."""
    st = jax.lax.dot_general(k, q, _NT, preferred_element_type=jnp.float32) * scale
    if masked:
        st = _masked(st, q_first, k_first, 1, causal=causal, window=window)
    return st


def _lse_kernel(q_ref, k_ref, lse_ref, m_scr, l_scr, *, scale, causal, window,
                bq, bk, q_offset):
    qi, ki, nk = pl.program_id(2), pl.program_id(3), pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    q_first, k_first = qi * bq + q_offset, ki * bk
    lo, hi = _k_span(q_first, bq, bk, nk, causal, window)

    def block(masked):
        st = _scores_t(q_ref[0, 0], k_ref[0, 0], q_first, k_first, masked,
                       scale=scale, causal=causal, window=window)
        m_prev = m_scr[...]  # (1, bq)
        m_new = jnp.maximum(m_prev, jnp.max(st, axis=0, keepdims=True))
        l_scr[...] = jnp.exp(m_prev - m_new) * l_scr[...] + jnp.sum(
            jnp.exp(st - m_new), axis=0, keepdims=True
        )
        m_scr[...] = m_new

    live = jnp.logical_and(ki >= lo, ki <= hi)
    _live_blocks(live, _needs_mask(q_first, k_first, bq, bk, causal, window), block)

    @pl.when(ki == nk - 1)
    def _flush():
        lse_ref[0, 0] = m_scr[...] + jnp.log(l_scr[...])


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
                dk_scr, dv_scr, *, scale, causal, window, bq, bk, q_offset, nq):
    ki, j, nj = pl.program_id(2), pl.program_id(3), pl.num_programs(3)
    qi = j % nq  # j runs over (query head of the group, q block)

    @pl.when(j == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q_first, k_first = qi * bq + q_offset, ki * bk
    lo, hi = _q_span(k_first, bq, bk, nq, q_offset, causal, window)

    def block(masked):
        q, do = q_ref[0, 0], do_ref[0, 0]
        pt = jnp.exp(
            _scores_t(q, k_ref[0, 0], q_first, k_first, masked,
                      scale=scale, causal=causal, window=window)
            - lse_ref[0, 0]
        )  # (bk, bq)
        dv_scr[...] += jnp.dot(pt.astype(do.dtype), do, preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(v_ref[0, 0], do, _NT, preferred_element_type=jnp.float32)
        dst = pt * (dpt - di_ref[0, 0])
        dk_scr[...] += jnp.dot(dst.astype(q.dtype), q, preferred_element_type=jnp.float32)

    live = jnp.logical_and(qi >= lo, qi <= hi)
    _live_blocks(live, _needs_mask(q_first, k_first, bq, bk, causal, window), block)

    @pl.when(j == nj - 1)
    def _flush():
        dk_ref[0, 0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref, dq_scr, *,
               scale, causal, window, bq, bk, q_offset):
    qi, ki, nk = pl.program_id(2), pl.program_id(3), pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    q_first, k_first = qi * bq + q_offset, ki * bk
    lo, hi = _k_span(q_first, bq, bk, nk, causal, window)

    def block(masked):
        k = k_ref[0, 0]
        pt = jnp.exp(
            _scores_t(q_ref[0, 0], k, q_first, k_first, masked,
                      scale=scale, causal=causal, window=window)
            - lse_ref[0, 0]
        )
        dpt = jax.lax.dot_general(v_ref[0, 0], do_ref[0, 0], _NT,
                                  preferred_element_type=jnp.float32)
        dst = pt * (dpt - di_ref[0, 0])  # (bk, bq)
        dq_scr[...] += jnp.dot(dst.T.astype(k.dtype), k, preferred_element_type=jnp.float32)

    live = jnp.logical_and(ki >= lo, ki <= hi)
    _live_blocks(live, _needs_mask(q_first, k_first, bq, bk, causal, window), block)

    @pl.when(ki == nk - 1)
    def _flush():
        dq_ref[0, 0] = dq_scr[...] * scale


def _lse_pass(q, k, causal, window, scale, block_q, block_k, interpret):
    """Heads-major q (b, h, s, d), k (b, kh, t, d) -> the log-sum-exp of each
    query's masked scores, f32 rows (b, h, 1, s)."""
    b, h, s, _ = q.shape
    q_spec, k_spec, lse_spec = _k_inner_specs(q, k, causal, window, block_q, block_k)
    f32 = jnp.float32
    return pl.pallas_call(
        functools.partial(_lse_kernel, **_static(q, k, causal, window, scale, block_q, block_k)),
        grid=(b, h, s // block_q, k.shape[2] // block_k),
        in_specs=[q_spec, k_spec],
        out_specs=lse_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, 1, s), f32),
        scratch_shapes=[pltpu.VMEM((1, block_q), f32), pltpu.VMEM((1, block_q), f32)],
        compiler_params=_PARAMS,
        interpret=interpret,
    )(q, k)


def _fa_backward(q, k, v, do, di, causal, window, scale, block_q, block_k, interpret):
    """Heads-major: q, do (b, h, s, d), k/v (b, kh, t, d), di (b, h, 1, s)
    f32. Returns dq (f32), dk, dv (k's dtype)."""
    b, h, s, d = q.shape
    kh, t = k.shape[1], k.shape[2]
    rep, nq, nk = h // kh, s // block_q, t // block_k
    q_offset = t - s
    static = _static(q, k, causal, window, scale, block_q, block_k)
    f32 = jnp.float32
    lse = _lse_pass(q, k, causal, window, scale, block_q, block_k, interpret)

    q_spec, kv_spec, lse_spec = _k_inner_specs(q, k, causal, window, block_q, block_k)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **static),
        grid=(b, h, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, lse_spec, lse_spec],
        out_specs=q_spec,
        # f32: a bf16 output of q's shape would read as the forward kernel
        out_shape=jax.ShapeDtypeStruct(q.shape, f32),
        scratch_shapes=[pltpu.VMEM((block_q, d), f32)],
        compiler_params=_PARAMS,
        interpret=interpret,
    )(q, k, v, do, lse, di)

    # (b, kh, k blocks, rep × q blocks): j runs over the group's query heads
    # and q blocks; a k block's dead q blocks clamp to its span
    def q_head_block(bi, hi, ki, j):
        lo, top = _q_span(ki * block_k, block_q, block_k, nq, q_offset, causal, window)
        return bi, hi * rep + j // nq, _clamp(j % nq, lo, top, nq)

    def q_block(*idx):
        bi, head, qi = q_head_block(*idx)
        return (bi, head, qi, 0)

    def lse_block(*idx):
        bi, head, qi = q_head_block(*idx)
        return (bi, head, 0, qi)

    k_spec = pl.BlockSpec((1, 1, block_k, d), lambda bi, hi, ki, j: (bi, hi, ki, 0))
    q_spec = pl.BlockSpec((1, 1, block_q, d), q_block)
    lse_spec = pl.BlockSpec((1, 1, 1, block_q), lse_block)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, nq=nq, **static),
        grid=(b, kh, nk, rep * nq),
        in_specs=[q_spec, k_spec, k_spec, q_spec, lse_spec, lse_spec],
        out_specs=[k_spec, k_spec],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype)] * 2,
        scratch_shapes=[pltpu.VMEM((block_k, d), f32), pltpu.VMEM((block_k, d), f32)],
        compiler_params=_PARAMS,
        interpret=interpret,
    )(q, k, v, do, lse, di)
    return dq, dk, dv


def _heads_major(x):
    """(b, s, h, d) <-> (b, h, s, d)."""
    return x.transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, window, scale, interpret):
    out = _fa_forward(
        _heads_major(q), _heads_major(k), _heads_major(v), causal, window, scale,
        _block(q.shape[1], _FWD_BLOCK), _block(k.shape[1], _FWD_BLOCK), interpret,
    )
    return _heads_major(out)


def _flash_fwd(q, k, v, causal, window, scale, interpret):
    out = _flash(q, k, v, causal, window, scale, interpret)
    return out, (q, k, v, out)


def _flash_bwd(causal, window, scale, interpret, res, g):
    q, k, v, o = res
    with jax.named_scope("flash_bwd"):
        # D = rowsum(dO ∘ O), f32, laid out as the kernels' lse rows
        di = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
        di = di.transpose(0, 2, 1)[:, :, None, :]
        dq, dk, dv = _fa_backward(
            _heads_major(q), _heads_major(k), _heads_major(v), _heads_major(g), di,
            causal, window, scale, _block(q.shape[1], _BWD_BLOCK), _block(k.shape[1], _BWD_BLOCK),
            interpret,
        )
        return (
            _heads_major(dq).astype(q.dtype), _heads_major(dk), _heads_major(dv),
        )


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention_pallas(
    q: jax.Array,  # (b, s, h, d)
    k: jax.Array,  # (b, t, kh, d)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    if scale is None:
        scale = q.shape[-1] ** -0.5
    return _flash(q, k, v, causal, window, float(scale), interpret)

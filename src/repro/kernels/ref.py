"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth for kernel tests (interpret=True vs ref) and the
CPU execution path of ``ops.py`` (this container has no TPU).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def flash_attention_ref(
    q: jax.Array,  # (b, s, h, d)
    k: jax.Array,  # (b, t, kh, d)
    v: jax.Array,  # (b, t, kh, d)
    causal: bool = True,
    window: int = 0,
    scale: float | None = None,
) -> jax.Array:
    b, s, h, d = q.shape
    t, kh = k.shape[1], k.shape[2]
    rep = h // kh
    if scale is None:
        scale = d**-0.5
    kf = jnp.repeat(k.astype(jnp.float32), rep, axis=2)
    vf = jnp.repeat(v.astype(jnp.float32), rep, axis=2)
    scores = jnp.einsum("bshd,bthd->bhst", q.astype(jnp.float32) * scale, kf)
    qpos = jnp.arange(s)[:, None] + (t - s)  # right-aligned when t != s
    kpos = jnp.arange(t)[None, :]
    if causal:
        mask = kpos <= qpos
    else:
        mask = jnp.ones((s, t), bool)
    if window > 0:
        mask &= kpos > qpos - window
    scores = jnp.where(mask[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhst,bthd->bshd", probs, vf)
    return out.astype(q.dtype)


def decode_attention_ref(
    q: jax.Array,  # (b, 1, h, d)
    k: jax.Array,  # (b, T, kh, d)
    v: jax.Array,
    mask: jax.Array,  # broadcastable to (b, 1, 1, T)
    scale: float,
) -> jax.Array:
    b, _, h, d = q.shape
    kh = k.shape[2]
    rep = h // kh
    kf = jnp.repeat(k.astype(jnp.float32), rep, axis=2)
    vf = jnp.repeat(v.astype(jnp.float32), rep, axis=2)
    scores = jnp.einsum("bshd,bthd->bhst", q.astype(jnp.float32) * scale, kf)
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhst,bthd->bshd", probs, vf)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Mamba-2 SSD chunked scan
# ---------------------------------------------------------------------------


def ssd_scan_ref(
    x: jax.Array,  # (b, s, h, p) float
    dt: jax.Array,  # (b, s, h)  float32, post-softplus
    A: jax.Array,  # (h,)       float32, negative
    B: jax.Array,  # (b, s, n)  float32
    C: jax.Array,  # (b, s, n)  float32
    chunk: int,
    init_state: jax.Array | None = None,  # (b, h, p, n)
) -> tuple[jax.Array, jax.Array]:
    """Returns (y (b,s,h,p) float32, final_state (b,h,p,n) float32)."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    assert s % chunk == 0, (s, chunk)
    nc, q = s // chunk, chunk
    xf = x.astype(jnp.float32).reshape(b, nc, q, h, p)
    dtc = dt.reshape(b, nc, q, h)
    Bc = B.reshape(b, nc, q, n)
    Cc = C.reshape(b, nc, q, n)

    a = dtc * A[None, None, None, :]  # (b,nc,q,h) <= 0
    cum = jnp.cumsum(a, axis=2)  # inclusive cumsum

    # --- intra-chunk (quadratic within chunk) -----------------------------
    diff = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # (b,nc,t,s,h)
    tri = jnp.tril(jnp.ones((q, q), bool))
    L = jnp.where(tri[None, None, :, :, None], jnp.exp(diff), 0.0)
    CB = jnp.einsum("bcin,bcjn->bcij", Cc, Bc)  # (b,nc,t,s)
    M = CB[..., None] * L * dtc[:, :, None, :, :]  # weight at source step s
    y_intra = jnp.einsum("bctsh,bcshp->bcthp", M, xf)

    # --- chunk state contributions ----------------------------------------
    decay_to_end = jnp.exp(cum[:, :, -1:, :] - cum)  # (b,nc,q,h)
    S = jnp.einsum("bcqh,bcqn,bcqhp->bchpn", decay_to_end * dtc, Bc, xf)
    chunk_decay = jnp.exp(cum[:, :, -1, :])  # (b,nc,h)

    # --- inter-chunk recurrence --------------------------------------------
    h0 = (
        jnp.zeros((b, h, p, n), jnp.float32)
        if init_state is None
        else init_state.astype(jnp.float32)
    )

    def step(carry, inputs):
        S_c, dec_c = inputs  # (b,h,p,n), (b,h)
        h_prev = carry
        h_new = dec_c[:, :, None, None] * h_prev + S_c
        return h_new, h_prev  # emit the *incoming* state for this chunk

    final, h_prevs = jax.lax.scan(
        step, h0, (S.swapaxes(0, 1), chunk_decay.swapaxes(0, 1))
    )
    h_prevs = h_prevs.swapaxes(0, 1)  # (b,nc,h,p,n)

    state_decay_in = jnp.exp(cum)  # decay from chunk start to step t
    y_inter = jnp.einsum("bcqn,bcqh,bchpn->bcqhp", Cc, state_decay_in, h_prevs)

    y = (y_intra + y_inter).reshape(b, s, h, p)
    return y, final


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------


def rmsnorm_ref(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Reshard pack/unpack (staging-buffer assembly)
# ---------------------------------------------------------------------------


def pack_rows_ref(src: jax.Array, row_starts: jax.Array, block_rows: int) -> jax.Array:
    """Gather ``len(row_starts)`` blocks of ``block_rows`` contiguous rows of
    ``src`` into a dense output (the paper's staging-buffer assemble loop).

    src: (R, *tail); row_starts: (nb,) int32; out: (nb*block_rows, *tail).
    """
    nb = row_starts.shape[0]

    def take(start):
        return jax.lax.dynamic_slice_in_dim(src, start, block_rows, axis=0)

    blocks = jax.vmap(take)(row_starts)  # (nb, block_rows, *tail)
    return blocks.reshape((nb * block_rows,) + src.shape[1:])


def unpack_rows_ref(
    buf: jax.Array, row_starts: jax.Array, block_rows: int, out_rows: int
) -> jax.Array:
    """Inverse of pack_rows: scatter buffer blocks into a (out_rows, *tail)
    zero array at the given row offsets."""
    nb = row_starts.shape[0]
    out = jnp.zeros((out_rows,) + buf.shape[1:], buf.dtype)
    blocks = buf.reshape((nb, block_rows) + buf.shape[1:])

    def body(i, acc):
        return jax.lax.dynamic_update_slice_in_dim(
            acc, blocks[i], row_starts[i], axis=0
        )

    return jax.lax.fori_loop(0, nb, body, out)


def relayout_rows_ref(
    dst: jax.Array, src: jax.Array, row_starts: jax.Array, block_rows: int
) -> jax.Array:
    """On-device relayout: gather blocks of ``src`` at ``row_starts`` and
    overwrite-scatter them into ``dst`` at the SAME row offsets (both arrays
    are global views of one tensor; "local" plan cells move bytes between
    two layouts of the same global coordinates). Composition of
    ``pack_rows_ref`` and ``scatter_rows_ref`` with a shared offset table;
    duplicate starts resolve last-wins like the scatter."""
    nb = row_starts.shape[0]

    def take(start):
        return jax.lax.dynamic_slice_in_dim(src, start, block_rows, axis=0)

    blocks = jax.vmap(take)(row_starts)  # (nb, block_rows, *tail)

    def body(i, acc):
        return jax.lax.dynamic_update_slice_in_dim(
            acc, blocks[i], row_starts[i], axis=0
        )

    return jax.lax.fori_loop(0, nb, body, dst)


def pack_quant_rows_ref(
    src: jax.Array,
    row_starts: jax.Array,
    block_rows: int,
    fmt: str,
) -> tuple[jax.Array, jax.Array]:
    """Oracle for ``pack_quant_rows_pallas``: gather ``len(row_starts)``
    row-blocks and quantize each tile symmetrically around zero with its own
    scale. Returns ((nb*block_rows, C) quantized, (nb, 1) float32 scales).

    The arithmetic is written out independently of the kernel body so
    interpret-vs-ref parity is a real check: ``scale = max(absmax, eps) *
    (1/qmax)`` (reciprocal folded to a float32 constant — the divide form
    is not bitwise-stable across compilation contexts); int8
    rounds-to-nearest then clips, fp8-e4m3 casts (|x/scale| <= 448 by
    construction). All-zero tiles hit the eps floor and quantize to exact
    zeros.
    """
    from repro.kernels.reshard_quant import QUANT_EPS, WIRE_QMAX

    nb = row_starts.shape[0]
    qmax = WIRE_QMAX[fmt]

    def take(start):
        return jax.lax.dynamic_slice_in_dim(src, start, block_rows, axis=0)

    blocks = jax.vmap(take)(row_starts).astype(jnp.float32)  # (nb, br, C)
    absmax = jnp.max(jnp.abs(blocks), axis=(1, 2))  # (nb,)
    scales = jnp.maximum(absmax, QUANT_EPS) * jnp.float32(1.0 / qmax)
    y = blocks / scales[:, None, None]
    if fmt == "int8":
        q = jnp.clip(jnp.round(y), -qmax, qmax).astype(jnp.int8)
    else:
        q = y.astype(jnp.float8_e4m3fn)
    return q.reshape(nb * block_rows, src.shape[1]), scales[:, None]


def dequant_scatter_rows_ref(
    dst: jax.Array,
    buf: jax.Array,
    scales: jax.Array,
    row_starts: jax.Array,
    block_rows: int,
) -> jax.Array:
    """Oracle for ``dequant_scatter_rows_pallas``: dequantize each tile with
    its sidecar scale and overwrite-scatter into ``dst`` (rows not named by
    ``row_starts`` keep their values; duplicate starts last-wins via the
    sequential fori_loop, matching the kernel's sequential grid)."""
    nb = row_starts.shape[0]
    blocks = buf.reshape(nb, block_rows, buf.shape[1]).astype(jnp.float32)
    deq = (blocks * scales.reshape(nb)[:, None, None]).astype(dst.dtype)

    def body(i, acc):
        return jax.lax.dynamic_update_slice_in_dim(
            acc, deq[i], row_starts[i], axis=0
        )

    return jax.lax.fori_loop(0, nb, body, dst)


def scatter_rows_ref(
    dst: jax.Array, buf: jax.Array, row_starts: jax.Array, block_rows: int
) -> jax.Array:
    """Overwrite-scatter buffer blocks into an existing destination.

    Unlike ``unpack_rows_ref`` the base is the caller's ``dst``, so rows not
    named by ``row_starts`` keep their current values and re-applying the
    same scatter is idempotent (the dirty-layer re-stream invariant).
    Duplicate starts resolve last-wins (sequential fori_loop), matching the
    Pallas kernel's sequential grid.
    """
    nb = row_starts.shape[0]
    blocks = buf.reshape((nb, block_rows) + buf.shape[1:])

    def body(i, acc):
        return jax.lax.dynamic_update_slice_in_dim(
            acc, blocks[i], row_starts[i], axis=0
        )

    return jax.lax.fori_loop(0, nb, body, dst)

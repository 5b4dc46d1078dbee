"""Pallas kernel validation: interpret=True vs pure-jnp oracle, with
shape/dtype sweeps (assignment requirement)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.reshard_pack import (
    pack_rows_pallas,
    relayout_rows_pallas,
    scatter_rows_pallas,
    unpack_rows_pallas,
)
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.kernels.ssd_scan import ssd_intra_chunk_pallas

RNG = np.random.default_rng(0)


def _rand(shape, dtype=jnp.float32):
    return jnp.asarray(RNG.normal(size=shape), dtype)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,s,h,kh,d,causal,window",
    [
        (1, 128, 2, 2, 64, True, 0),
        (2, 256, 4, 2, 64, True, 0),
        (2, 256, 4, 1, 32, True, 128),  # MQA + sliding window
        (1, 128, 2, 2, 128, False, 0),
        (1, 384, 6, 3, 64, True, 0),  # GQA rep=2, 3 blocks
        # blocks over 128, several live and dead (the forward's are 1024 here)
        (1, 2048, 8, 2, 64, True, 0),  # GQA 4:1, d 64
        (1, 3072, 2, 1, 64, True, 300),  # window edge inside a block pair
        (1, 2048, 4, 4, 128, False, 0),
    ],
)
def test_flash_attention_sweep(b, s, h, kh, d, causal, window, dtype):
    q, k, v = _rand((b, s, h, d), dtype), _rand((b, s, kh, d), dtype), _rand((b, s, kh, d), dtype)
    out = flash_attention_pallas(q, k, v, causal=causal, window=window, interpret=True)
    exp = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(exp, np.float32), atol=tol, rtol=tol
    )


@pytest.mark.parametrize(
    "s,t",
    [
        (128, 256),
        (1024, 1536),  # t - s = 512: not a multiple of the 1024-row q block
    ],
)
def test_flash_attention_cross_block_q_offset(s, t):
    """t > s: right-aligned queries (continuation chunk)."""
    q = _rand((1, s, 2, 64))
    k = _rand((1, t, 2, 64))
    v = _rand((1, t, 2, 64))
    out = flash_attention_pallas(q, k, v, causal=True, interpret=True)
    exp = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=2e-6)


@pytest.mark.parametrize(
    "s,t,bq,bk,causal,window",
    [
        (4096, 4096, 1024, 1024, True, 0),
        (2048, 2048, 512, 256, True, 700),  # window edge inside a block pair
        (2048, 2048, 256, 512, False, 1024),
        (512, 1152, 256, 128, True, 0),  # q_offset 640
        (256, 2048, 128, 256, True, 300),  # q_offset 1792 and a window
    ],
)
def test_flash_forward_dead_blocks_fetch_a_live_neighbour(s, t, bq, bk, causal, window):
    """The forward's k/v index map: a live (q block, k block) step fetches its
    own k block; a dead one re-addresses a live block of its q block, the one
    its neighbouring step fetches, so a q block's k/v DMAs are as many as its
    live k blocks."""
    from repro.kernels.flash_attention import _k_inner_specs

    q = jax.ShapeDtypeStruct((1, 2, s, 64), jnp.float32)
    k = jax.ShapeDtypeStruct((1, 1, t, 64), jnp.float32)
    kv_map = _k_inner_specs(q, k, causal, window, bq, bk)[1].index_map
    qpos, kpos = np.arange(s)[:, None] + (t - s), np.arange(t)[None, :]
    keep = kpos <= qpos if causal else np.ones((s, t), bool)
    if window > 0:
        keep &= kpos > qpos - window
    nq, nk = s // bq, t // bk
    live = keep.reshape(nq, bq, nk, bk).any(axis=(1, 3))
    assert live.any(axis=1).all() and not live.all()
    for qi in range(nq):
        fetched = [int(kv_map(0, 1, qi, ki)[2]) for ki in range(nk)]
        own = np.flatnonzero(live[qi])
        for ki, got in enumerate(fetched):
            if live[qi, ki]:
                assert got == ki
            else:
                assert got == own[0 if ki < own[0] else -1], (qi, ki, got)
        dmas = 1 + sum(a != b for a, b in zip(fetched, fetched[1:]))
        assert dmas == len(own)


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "b,s,h,p,n,chunk",
    [
        (1, 64, 2, 16, 32, 16),
        (2, 128, 3, 32, 64, 32),
        (1, 96, 4, 64, 128, 16),  # jamba/mamba2-ish dims
    ],
)
def test_ssd_intra_chunk_sweep(b, s, h, p, n, chunk):
    x = _rand((b, s, h, p))
    dt = jnp.asarray(RNG.uniform(0.01, 0.3, (b, s, h)), jnp.float32)
    A = -jnp.asarray(RNG.uniform(0.5, 2.0, (h,)), jnp.float32)
    B = _rand((b, s, n))
    C = _rand((b, s, n))

    import os

    os.environ["REPRO_FORCE_PALLAS_INTERPRET"] = "1"
    try:
        from repro.kernels import ops

        y1, f1 = ops.ssd_scan(x, dt, A, B, C, chunk)
    finally:
        os.environ.pop("REPRO_FORCE_PALLAS_INTERPRET", None)
    y2, f2 = ref.ssd_scan_ref(x, dt, A, B, C, chunk)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(f1), np.asarray(f2), atol=1e-5, rtol=1e-5)


def test_ssd_matches_sequential_recurrence():
    """Chunked SSD == naive per-token recurrence (ground truth)."""
    b, s, h, p, n, chunk = 1, 32, 2, 8, 16, 8
    x = np.asarray(_rand((b, s, h, p)))
    dt = RNG.uniform(0.01, 0.3, (b, s, h)).astype(np.float32)
    A = -RNG.uniform(0.5, 2.0, (h,)).astype(np.float32)
    B = np.asarray(_rand((b, s, n)))
    C = np.asarray(_rand((b, s, n)))

    state = np.zeros((b, h, p, n), np.float32)
    ys = np.zeros((b, s, h, p), np.float32)
    for t in range(s):
        decay = np.exp(dt[:, t] * A[None, :])  # (b,h)
        state = decay[:, :, None, None] * state + (
            dt[:, t][:, :, None, None]
            * x[:, t][:, :, :, None]
            * B[:, t][:, None, None, :]
        )
        ys[:, t] = np.einsum("bhpn,bn->bhp", state, C[:, t])

    y, final = ref.ssd_scan_ref(
        jnp.asarray(x), jnp.asarray(dt), jnp.asarray(A), jnp.asarray(B),
        jnp.asarray(C), chunk,
    )
    np.testing.assert_allclose(np.asarray(y), ys, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(final), state, atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# rmsnorm / pack / unpack
# ---------------------------------------------------------------------------


@settings(max_examples=10, deadline=None)
@given(
    rows=st.integers(1, 300),
    d=st.sampled_from([128, 256]),
)
def test_rmsnorm_property(rows, d):
    x = _rand((rows, d))
    sc = _rand((d,))
    out = rmsnorm_pallas(x, sc, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref.rmsnorm_ref(x, sc)), atol=1e-6
    )


@settings(max_examples=10, deadline=None)
@given(data=st.data())
def test_pack_unpack_roundtrip(data):
    nb = data.draw(st.integers(1, 6))
    block = data.draw(st.sampled_from([8, 16]))
    R = block * data.draw(st.integers(nb, 12))
    starts = data.draw(
        st.lists(
            st.integers(0, R // block - 1), min_size=nb, max_size=nb, unique=True
        )
    )
    starts = jnp.asarray(sorted(s * block for s in starts), jnp.int32)
    src = _rand((R, 128))
    packed = pack_rows_pallas(src, starts, block, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(packed), np.asarray(ref.pack_rows_ref(src, starts, block))
    )
    un = unpack_rows_pallas(packed, starts, block, R, interpret=True)
    for st_ in np.asarray(starts):
        np.testing.assert_array_equal(
            np.asarray(un[st_ : st_ + block]), np.asarray(src[st_ : st_ + block])
        )


# ---------------------------------------------------------------------------
# scatter_rows: overwrite-semantics scatter (the live re-sync fast path)
# ---------------------------------------------------------------------------


@settings(max_examples=10, deadline=None)
@given(data=st.data())
def test_scatter_rows_property(data):
    """Pallas (interpret) == jnp oracle == manual numpy overwrite, including
    preservation of every destination row NOT named by the offset table
    (the input_output_aliases carry-through)."""
    nb = data.draw(st.integers(1, 6))
    block = data.draw(st.sampled_from([1, 8]))
    R = block * data.draw(st.integers(max(nb, 2), 12))
    starts = data.draw(
        st.lists(
            st.integers(0, R // block - 1), min_size=nb, max_size=nb, unique=True
        )
    )
    starts = jnp.asarray([s * block for s in starts], jnp.int32)
    dst = _rand((R, 128))
    buf = _rand((nb * block, 128))
    out_p = scatter_rows_pallas(dst, buf, starts, block, interpret=True)
    out_r = ref.scatter_rows_ref(dst, buf, starts, block)
    exp = np.asarray(dst).copy()
    for i, s in enumerate(np.asarray(starts)):
        exp[s : s + block] = np.asarray(buf)[i * block : (i + 1) * block]
    np.testing.assert_array_equal(np.asarray(out_r), exp)
    np.testing.assert_array_equal(np.asarray(out_p), exp)


def test_scatter_rows_duplicate_starts_last_wins():
    """Both paths resolve duplicate offsets sequentially (last block wins) —
    the deterministic tie-break the oracle's fori_loop defines."""
    dst = _rand((16, 128))
    buf = _rand((3, 128))
    starts = jnp.asarray([4, 4, 9], jnp.int32)
    exp = np.asarray(dst).copy()
    exp[4] = np.asarray(buf)[1]
    exp[9] = np.asarray(buf)[2]
    np.testing.assert_array_equal(
        np.asarray(ref.scatter_rows_ref(dst, buf, starts, 1)), exp
    )
    np.testing.assert_array_equal(
        np.asarray(scatter_rows_pallas(dst, buf, starts, 1, interpret=True)), exp
    )


def test_scatter_rows_idempotent():
    """Overwrite semantics: re-applying the same scatter is a no-op (the
    dirty-layer re-stream invariant; an accumulate scatter would fail this)."""
    dst = _rand((24, 128))
    buf = _rand((4, 128))
    starts = jnp.asarray([2, 7, 11, 21], jnp.int32)
    once = ref.scatter_rows_ref(dst, buf, starts, 1)
    twice = ref.scatter_rows_ref(once, buf, starts, 1)
    np.testing.assert_array_equal(np.asarray(once), np.asarray(twice))
    once_p = scatter_rows_pallas(dst, buf, starts, 1, interpret=True)
    twice_p = scatter_rows_pallas(once_p, buf, starts, 1, interpret=True)
    np.testing.assert_array_equal(np.asarray(once_p), np.asarray(twice_p))


# ---------------------------------------------------------------------------
# relayout_rows: fused gather->scatter for the classified "local" cells
# ---------------------------------------------------------------------------


@settings(max_examples=10, deadline=None)
@given(data=st.data())
def test_relayout_rows_property(data):
    """Pallas (interpret) == jnp oracle == manual numpy copy: named row
    blocks of src overwrite the same offsets of dst; every other dst row
    keeps its bytes (the input_output_aliases carry-through)."""
    nb = data.draw(st.integers(1, 6))
    block = data.draw(st.sampled_from([1, 8]))
    R = block * data.draw(st.integers(max(nb, 2), 12))
    starts = data.draw(
        st.lists(
            st.integers(0, R // block - 1), min_size=nb, max_size=nb, unique=True
        )
    )
    starts = jnp.asarray([s * block for s in starts], jnp.int32)
    dst = _rand((R, 128))
    src = _rand((R, 128))
    out_p = relayout_rows_pallas(dst, src, starts, block, interpret=True)
    out_r = ref.relayout_rows_ref(dst, src, starts, block)
    exp = np.asarray(dst).copy()
    for s in np.asarray(starts):
        exp[s : s + block] = np.asarray(src)[s : s + block]
    np.testing.assert_array_equal(np.asarray(out_r), exp)
    np.testing.assert_array_equal(np.asarray(out_p), exp)


def test_relayout_rows_idempotent_and_matches_pack_scatter():
    """relayout == pack o scatter composed (same bytes, one program), and
    re-applying it is a no-op — the resident/dirty re-classify invariant."""
    from repro.kernels import ops

    src = _rand((32, 128))
    dst = _rand((32, 128))
    rows = jnp.asarray([0, 3, 4, 11, 30], jnp.int32)
    via_pack = ops.scatter_rows(dst, ops.pack_rows(src, rows, 1), rows, 1)
    once = ops.relayout_rows(dst, src, rows, 1)
    np.testing.assert_array_equal(np.asarray(once), np.asarray(via_pack))
    twice = ops.relayout_rows(once, src, rows, 1)
    np.testing.assert_array_equal(np.asarray(once), np.asarray(twice))
    once_p = relayout_rows_pallas(dst, src, rows, 1, interpret=True)
    np.testing.assert_array_equal(np.asarray(once_p), np.asarray(via_pack))


def test_pack_then_scatter_roundtrip():
    """ops-level dispatch: pack_rows o scatter_rows restores the gathered
    rows into a different destination exactly (the executor's fused path)."""
    from repro.kernels import ops

    src = _rand((32, 128))
    dst = _rand((32, 128))
    rows = jnp.asarray([1, 4, 5, 9, 30], jnp.int32)
    buf = ops.pack_rows(src, rows, 1)
    out = ops.scatter_rows(dst, buf, rows, 1)
    exp = np.asarray(dst).copy()
    for r in np.asarray(rows):
        exp[r] = np.asarray(src)[r]
    np.testing.assert_array_equal(np.asarray(out), exp)


# ---------------------------------------------------------------------------
# flash attention VJP, rank-3 row kernels, counted fallbacks
# ---------------------------------------------------------------------------


# bf16 gradients: the kernels feed p and dS to the MXU in bf16 and the
# oracle keeps them in f32, so the gap is bf16 rounding, relative to the
# largest entry (measured up to ~5e-3)
BF16_GRAD_TOL = 2e-2


@pytest.mark.parametrize(
    "b,s,t,h,kh,d,causal,window,dtype",
    [
        # GQA rep 2, 2 blocks; the ids of the two cases this test began with
        pytest.param(1, 256, 256, 4, 2, 32, True, 0, jnp.float32, id="0"),
        pytest.param(1, 256, 256, 4, 2, 32, True, 64, jnp.float32, id="64"),
        (1, 384, 384, 4, 4, 64, True, 0, jnp.float32),  # MHA, 3 blocks
        (1, 384, 384, 4, 1, 64, True, 100, jnp.float32),  # MQA, window < block
        (2, 256, 256, 8, 2, 128, False, 0, jnp.float32),  # GQA rep 4, non-causal
        (1, 768, 768, 4, 1, 32, False, 200, jnp.float32),  # 3 blocks of 256
        (1, 1536, 1536, 2, 1, 32, True, 0, jnp.float32),  # 3 blocks of 512
        (1, 256, 640, 4, 2, 64, True, 0, jnp.float32),  # t > s: right-aligned
        (1, 256, 768, 4, 2, 32, True, 128, jnp.float32),  # a k block no query sees
        (1, 384, 384, 4, 2, 128, True, 0, jnp.bfloat16),
        (1, 256, 512, 8, 2, 64, True, 96, jnp.bfloat16),
        # forward blocks of 1024, backward of 512, the window's edge inside them
        (1, 2048, 2048, 2, 1, 64, True, 700, jnp.float32),
    ],
)
def test_flash_attention_vjp_matches_reference_grad(b, s, t, h, kh, d, causal, window, dtype):
    """jax.grad through the kernel (custom_vjp: the Pallas backward) ==
    jax.grad of the oracle."""
    q, w = _rand((b, s, h, d), dtype), _rand((b, s, h, d))
    k, v = _rand((b, t, kh, d), dtype), _rand((b, t, kh, d), dtype)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v).astype(jnp.float32) * w).sum()

    kern = loss(lambda q, k, v: flash_attention_pallas(
        q, k, v, causal=causal, window=window, interpret=True))
    orac = loss(lambda q, k, v: ref.flash_attention_ref(q, k, v, causal=causal, window=window))
    got = jax.grad(kern, argnums=(0, 1, 2))(q, k, v)
    exp = jax.grad(orac, argnums=(0, 1, 2))(q, k, v)
    for g, e in zip(got, exp):
        g, e = np.asarray(g, np.float32), np.asarray(e, np.float32)
        if dtype == jnp.float32:
            np.testing.assert_allclose(g, e, atol=1e-5, rtol=1e-5)
        else:
            tol = BF16_GRAD_TOL
            np.testing.assert_allclose(g, e, atol=tol * np.abs(e).max(), rtol=tol)


@pytest.mark.parametrize(
    "s,t,causal,window",
    [(256, 256, True, 0), (384, 384, True, 100), (256, 768, True, 128), (256, 256, False, 64)],
)
def test_flash_lse_pass_matches_logsumexp(s, t, causal, window):
    """The backward's lse pass == logsumexp of the oracle's masked scores,
    rows a window leaves partly empty included."""
    from repro.kernels.flash_attention import _BWD_BLOCK, _block, _lse_pass

    b, h, kh, d = 1, 4, 2, 32
    q, k = _rand((b, h, s, d)), _rand((b, kh, t, d))
    scale = d**-0.5
    got = _lse_pass(q, k, causal, window, scale, _block(s, _BWD_BLOCK), _block(t, _BWD_BLOCK), True)
    scores = jnp.einsum("bhsd,bhtd->bhst", q * scale, jnp.repeat(k, h // kh, axis=1))
    qpos, kpos = jnp.arange(s)[:, None] + (t - s), jnp.arange(t)[None, :]
    keep = kpos <= qpos if causal else jnp.ones((s, t), bool)
    if window > 0:
        keep &= kpos > qpos - window
    exp = jax.nn.logsumexp(jnp.where(keep, scores, -jnp.inf), axis=-1)
    assert got.shape == (b, h, 1, s)
    np.testing.assert_allclose(np.asarray(got[:, :, 0]), np.asarray(exp), atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("kernel", ["pack", "scatter", "relayout"])
def test_row_kernels_rank3_match_reference(kernel):
    """Stacked-layer leaves (rows = whole (d, f) slabs) take the HBM->HBM
    DMA path; bytes equal the oracle, duplicate starts last-wins."""
    src = _rand((6, 16, 128))
    dst = _rand((6, 16, 128))
    starts = jnp.asarray([4, 0, 4, 2], jnp.int32)
    if kernel == "pack":
        got = pack_rows_pallas(src, starts, 1, interpret=True)
        exp = ref.pack_rows_ref(src, starts, 1)
    elif kernel == "scatter":
        buf = _rand((4, 16, 128))
        got = scatter_rows_pallas(dst, buf, starts, 1, interpret=True)
        exp = ref.scatter_rows_ref(dst, buf, starts, 1)
    else:
        got = relayout_rows_pallas(dst, src, starts, 1, interpret=True)
        exp = ref.relayout_rows_ref(dst, src, starts, 1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))


def test_unfit_shapes_fall_back_counted(monkeypatch):
    """Where the kernels are in use, a call they cannot take runs on the
    reference and is counted — never silently."""
    from repro.kernels import ops

    monkeypatch.setenv("REPRO_FORCE_PALLAS_INTERPRET", "1")
    ops.FALLBACKS.clear()
    q = _rand((1, 96, 2, 32))
    out = ops.flash_attention(q, q, q)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref.flash_attention_ref(q, q, q)), atol=1e-6
    )
    assert ops.FALLBACKS == {"flash_attention: seq 96/96 not a multiple of 128": 1}
    ops.FALLBACKS.clear()

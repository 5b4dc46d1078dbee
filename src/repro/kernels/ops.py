"""Jit-ready kernel entry points used by the model code and the data plane.

Dispatch policy: on TPU backends the Pallas kernels run natively; on CPU
the mathematically identical pure-jnp references execute instead — Pallas
interpret mode is reserved for the kernel unit tests (it is a Python-level
interpreter, far too slow for full models). Set
``REPRO_FORCE_PALLAS_INTERPRET=1`` to force the Pallas path in interpret
mode (used by integration tests to exercise kernel plumbing).

Where the kernels are in use, a call they cannot take — a shape the chip's
tiling rules refuse, rows split across devices, a tile larger than VMEM —
still runs, on the reference, and is counted in :data:`FALLBACKS` under
``"<kernel>: <reason>"``. The count is per traced dispatch (a jitted
program that retraces counts again), so zero means every kernel call of
every program traced so far ran its kernel.

Sharded programs: a ``pallas_call`` inside a GSPMD program cannot be
partitioned, so XLA would all-gather its operands. The kernels therefore
run per device under ``jax.shard_map``: attention on the mesh a step
builder names with :func:`kernel_mesh`, the row kernels on the mesh of the
``sharding`` the data plane passes.
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import math
import os

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.kernels import ref as _ref
from repro.kernels import reshard_pack as _rp
from repro.kernels import reshard_quant as _rq
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.ssd_scan import ssd_intra_chunk_pallas

FALLBACKS: collections.Counter = collections.Counter()

_MESH: contextvars.ContextVar = contextvars.ContextVar("kernel_mesh", default=None)


def _use_pallas() -> tuple[bool, bool]:
    """(use_pallas, interpret)."""
    if os.environ.get("REPRO_FORCE_PALLAS_INTERPRET") == "1":
        return True, True
    return jax.default_backend() == "tpu", False


def _fallback(kernel: str, reason: str) -> None:
    FALLBACKS[f"{kernel}: {reason}"] += 1


@contextlib.contextmanager
def kernel_mesh(mesh):
    """Name the mesh the program being traced runs on, so kernels called
    deep inside the model can shard_map themselves onto it."""
    tok = _MESH.set(mesh)
    try:
        yield
    finally:
        _MESH.reset(tok)


def with_kernel_mesh(fn, mesh):
    """``fn`` traced under :func:`kernel_mesh` (step builders wrap the
    function they jit with this)."""

    def wrapped(*args, **kwargs):
        with kernel_mesh(mesh):
            return fn(*args, **kwargs)

    return wrapped


def _multi(mesh) -> bool:
    return mesh is not None and mesh.devices.size > 1


def _axis_sizes(mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def _on_shards(fn, mesh, in_specs, out_specs):
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def _attn_spec(mesh, b: int, h: int, kh: int):
    """(b, s, heads, d) spec running attention per device: batch over the
    data axes, heads over ``model``; None when an axis would not divide
    (the kernel would then need the whole operand on every device) or
    when pipeline stages stack activations along ``pipe``."""
    sizes = _axis_sizes(mesh)
    if sizes.get("pipe", 1) > 1:
        return None
    batch = tuple(a for a in ("pod", "data") if sizes.get(a, 1) > 1)
    if b % math.prod(sizes[a] for a in batch):
        return None
    tp = sizes.get("model", 1)
    if h % tp or kh % tp:
        return None
    return P(batch or None, None, "model" if tp > 1 else None, None)


def flash_attention(q, k, v, *, causal=True, window=0, scale=None):
    use, interp = _use_pallas()
    s, t = q.shape[1], k.shape[1]
    if use and s % 128 == 0 and t % 128 == 0:
        call = lambda q, k, v: flash_attention_pallas(
            q, k, v, causal=causal, window=window, scale=scale, interpret=interp
        )
        mesh = _MESH.get()
        if not _multi(mesh):
            return call(q, k, v)
        spec = _attn_spec(mesh, q.shape[0], q.shape[2], k.shape[2])
        if spec is not None:
            return _on_shards(call, mesh, (spec, spec, spec), spec)(q, k, v)
        _fallback("flash_attention", "batch, heads or stages do not fit the mesh")
    elif use:
        _fallback("flash_attention", f"seq {s}/{t} not a multiple of 128")
    return _ref.flash_attention_ref(q, k, v, causal=causal, window=window, scale=scale)


def decode_attention(q, k, v, mask, scale):
    """Single-token attention against a KV cache (matvec-shaped; XLA's fused
    path is already bandwidth-optimal, no kernel needed)."""
    return _ref.decode_attention_ref(q, k, v, mask, scale)


# ---------------------------------------------------------------------------
# SSD scan: pallas intra-chunk + jnp inter-chunk recurrence
# ---------------------------------------------------------------------------


def _ssd_inter(cum, Cc, S, chunk_decay, init_state):
    """Inter-chunk recurrence shared by kernel and ref paths.

    cum: (b,nc,q,h); Cc: (b,nc,q,n); S: (b,nc,h,p,n); chunk_decay: (b,nc,h).
    Returns (y_inter (b,nc,q,h,p), final_state (b,h,p,n)).
    """

    def step(carry, inputs):
        S_c, dec_c = inputs
        h_new = dec_c[:, :, None, None] * carry + S_c
        return h_new, carry

    final, h_prevs = jax.lax.scan(
        step, init_state, (S.swapaxes(0, 1), chunk_decay.swapaxes(0, 1))
    )
    h_prevs = h_prevs.swapaxes(0, 1)  # (b,nc,h,p,n)
    state_decay_in = jnp.exp(cum)
    y_inter = jnp.einsum("bcqn,bcqh,bchpn->bcqhp", Cc, state_decay_in, h_prevs)
    return y_inter, final


def ssd_scan(x, dt, A, B, C, chunk, init_state=None):
    """Chunked SSD scan. Shapes as in ref.ssd_scan_ref; returns (y, final).

    Pads the sequence up to a chunk multiple (dt=0 padding is a no-op:
    zero contribution, unit decay) and crops the output.
    """
    use, interp = _use_pallas()
    b, s, h, p = x.shape
    n = B.shape[-1]
    if init_state is None:
        init_state = jnp.zeros((b, h, p, n), jnp.float32)
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    if use and (chunk % 8 or _multi(_MESH.get())):
        _fallback("ssd_scan", "chunk not a multiple of 8 or sharded program")
        use = False
    if not use:
        y, final = _ref.ssd_scan_ref(x, dt, A, B, C, chunk, init_state)
        return (y[:, :s] if pad else y), final

    sp = s + pad
    nc, q = sp // chunk, chunk
    a = dt.reshape(b, nc, q, h) * A[None, None, None, :]
    cum = jnp.cumsum(a, axis=2)  # within-chunk inclusive cumsum
    cum_flat = cum.reshape(b, sp, h)

    y_intra, S = ssd_intra_chunk_pallas(
        x, dt, cum_flat, B, C, chunk, interpret=interp
    )
    chunk_decay = jnp.exp(cum[:, :, -1, :])
    Cc = C.reshape(b, nc, q, n).astype(jnp.float32)
    y_inter, final = _ssd_inter(cum, Cc, S, chunk_decay, init_state)
    y = y_intra.reshape(b, nc, q, h, p) + y_inter
    y = y.reshape(b, sp, h, p)
    return (y[:, :s] if pad else y), final


# ---------------------------------------------------------------------------
# Reshard data plane: row gather / scatter / relayout (+ quantized wire)
# ---------------------------------------------------------------------------


def _row_plan(sharding, ndim: int):
    """Where a row kernel runs for an operand with this sharding: None
    (one device — call the kernel directly), a (mesh, spec) pair (run per
    device under shard_map; rows are whole on every device), or "split"
    (dim 0 is divided across devices, which a per-device row kernel with
    global offsets cannot serve)."""
    if not isinstance(sharding, NamedSharding) or sharding.mesh.devices.size == 1:
        return None
    spec = tuple(sharding.spec) + (None,) * (ndim - len(sharding.spec))
    if spec[0] is not None:
        return "split"
    return sharding.mesh, P(*spec)


def _row_op(name, kernel, reference, local_ok, arrays, row_starts, sharding,
            n_out=1, whole_rows=False):
    """Run ``kernel(*arrays, starts, interpret)`` on every device's block,
    or the counted reference. ``arrays[0]`` carries the sharding (and the
    row dim); the other arrays follow the same spec; ``local_ok(shape,
    dtype)`` gives the reason the chip cannot take a per-device block, or
    None. ``whole_rows``: the kernel needs each row on one device."""
    use, interp = _use_pallas()
    starts = jnp.asarray(row_starts, jnp.int32)
    if not use:
        return reference(*arrays, starts)
    plan = _row_plan(sharding, arrays[0].ndim)
    split = plan == "split" or (
        whole_rows and plan is not None and any(a is not None for a in plan[1])
    )
    if split:
        _fallback(name, "rows split across devices")
        return reference(*arrays, starts)
    if plan is None:
        local = arrays[0].shape
        mesh = spec = None
    else:
        mesh, spec = plan
        sizes = _axis_sizes(mesh)
        local = tuple(
            d // math.prod(sizes[a] for a in ((ax,) if isinstance(ax, str) else ax))
            if ax is not None else d
            for d, ax in zip(arrays[0].shape, spec)
        )
    why = None if interp else local_ok(local, arrays[0].dtype)
    if why:
        _fallback(name, why)
        return reference(*arrays, starts)
    call = lambda *a: kernel(*a, interp)
    if plan is None:
        return call(*arrays, starts)
    out_specs = spec if n_out == 1 else (spec, P())
    return _on_shards(
        call, mesh, tuple(spec for _ in arrays) + (P(),), out_specs
    )(*arrays, starts)


def _move_ok(shape, dtype):
    if _rp.tpu_layout(shape, dtype) is None:
        return f"{len(shape)}-D {jnp.dtype(dtype).name} rows off the TPU tiling"
    return None


def _quant_ok(block_rows: int):
    def ok(shape, dtype):
        if _rp.tpu_layout(shape, dtype) != "tile":
            return f"{len(shape)}-D {jnp.dtype(dtype).name} rows off the TPU tiling"
        if _rq.quant_vmem_bytes(shape[1], block_rows) > 3 * _rp.VMEM_BUDGET:
            return "row too wide for one VMEM tile"
        return None

    return ok


def pack_rows(src, row_starts, block_rows: int, sharding=None):
    """Gather row blocks of ``src`` (any rank, rows on dim 0) into a
    (nb*block_rows, *tail) staging buffer laid out like ``src``."""
    return _row_op(
        "pack_rows",
        lambda s, st, i: _rp.pack_rows_pallas(s, st, block_rows, interpret=i),
        lambda s, st: _ref.pack_rows_ref(s, st, block_rows),
        _move_ok, (src,), row_starts, sharding,
    )


def relayout_rows(dst, src, row_starts, block_rows: int, sharding=None):
    """On-device relayout for the classified plan IR's "local" cells: copy
    row blocks of ``src`` into ``dst`` (treated as donated) at the same
    global offsets, in one fused gather→scatter with no staging buffer.
    Rows not named by ``row_starts`` keep their existing bytes; duplicate
    starts resolve last-wins on both paths. ``sharding`` is ``dst``'s."""
    return _row_op(
        "relayout_rows",
        lambda d, s, st, i: _rp.relayout_rows_pallas(d, s, st, block_rows, interpret=i),
        lambda d, s, st: _ref.relayout_rows_ref(d, s, st, block_rows),
        _move_ok, (dst, src), row_starts, sharding,
    )


def scatter_rows(dst, buf, row_starts, block_rows: int, sharding=None):
    """Overwrite-scatter buffer blocks into ``dst`` (treated as donated).

    The idempotent counterpart of ``pack_rows``: rows not named by
    ``row_starts`` keep their existing bytes, and re-applying the same
    scatter is a no-op — the property the dirty-layer re-stream depends on.
    Duplicate starts resolve last-wins on both paths. ``sharding`` is
    ``dst``'s; ``buf`` must be laid out the same way.
    """
    return _row_op(
        "scatter_rows",
        lambda d, b, st, i: _rp.scatter_rows_pallas(d, b, st, block_rows, interpret=i),
        lambda d, b, st: _ref.scatter_rows_ref(d, b, st, block_rows),
        _move_ok, (dst, buf), row_starts, sharding,
    )


def pack_quant_rows(src, row_starts, block_rows: int, fmt: str, sharding=None):
    """Gather + per-tile quantize row blocks of a rank-2 ``src`` for the
    compressed wire format.

    Returns ``(qbuf (nb*block_rows, C), scales (nb, 1) float32)``. One tile
    = one row-block; the sidecar carries one symmetric scale per tile.
    Deterministic: the same source rows always produce the same payload and
    scales, so a dirty-layer re-stream lands bitwise-identical bytes. A
    tile's scale spans its whole row, so on a mesh the row must not be
    split across devices.
    """
    return _row_op(
        "pack_quant_rows",
        lambda s, st, i: _rq.pack_quant_rows_pallas(s, st, block_rows, fmt, interpret=i),
        lambda s, st: _ref.pack_quant_rows_ref(s, st, block_rows, fmt),
        _quant_ok(block_rows), (src,), row_starts, sharding,
        n_out=2, whole_rows=True,
    )


def dequant_scatter_rows(dst, buf, scales, row_starts, block_rows: int,
                         sharding=None):
    """Dequantize + overwrite-scatter quantized tiles into a rank-2 ``dst``
    (donated).

    The compressed-wire counterpart of ``scatter_rows``: rows not named by
    ``row_starts`` keep their bytes, duplicate starts last-wins, and because
    dequant is a deterministic elementwise map, re-applying the same payload
    is idempotent.
    """
    return _row_op(
        "dequant_scatter_rows",
        lambda d, b, sc, st, i: _rq.dequant_scatter_rows_pallas(
            d, b, sc, st, block_rows, interpret=i
        ),
        lambda d, b, sc, st: _ref.dequant_scatter_rows_ref(d, b, sc, st, block_rows),
        _quant_ok(block_rows), (dst, buf, scales), row_starts, sharding,
        whole_rows=True,
    )


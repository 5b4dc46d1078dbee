"""Compile the main path's Pallas kernels for a described TPU v5e at real
widths — what interpret mode cannot check: block shapes against the chip's
(8, 128) tiling, VMEM use, and whether a kernel inside a sharded program
gets its operands all-gathered. Nothing runs; each test asserts that the
compiled program still holds the kernel (``tpu_custom_call``).

The topology is described inside a fixture: only one process at a time
may load the TPU library, so nothing here touches it at import time.
"""

from __future__ import annotations

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.reshard_pack import (
    pack_rows_pallas,
    relayout_rows_pallas,
    scatter_rows_pallas,
)
from repro.kernels.reshard_quant import (
    WIRE_QDTYPE,
    dequant_scatter_rows_pallas,
    pack_quant_rows_pallas,
)
from repro.kernels.ssd_scan import ssd_intra_chunk_pallas


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    # compiles for a described chip are written to the persistent cache but
    # can never be read back here; keep the cache out of this module
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    """dp1 x tp4 over the 2x2 host, axes as the elastic trainer names them."""
    import numpy as np

    return Mesh(
        np.asarray(topo.devices).reshape(1, 1, 1, 4), ("data", "pipe", "expert", "model")
    )


@pytest.fixture
def chip_dispatch(monkeypatch):
    """Steer ops' dispatch to the native kernels, as on a TPU backend."""
    monkeypatch.setattr(ops, "_use_pallas", lambda: (True, False))
    ops.FALLBACKS.clear()
    yield
    ops.FALLBACKS.clear()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, **jit_kw) -> str:
    text = jax.jit(fn, **jit_kw).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


_PASS_THROUGH = ("bitcast", "copy", "get-tuple-element", "reshape", "transpose")


def _gathered_kernel_operands(hlo: str) -> list[str]:
    """tpu_custom_call operands that come from an all-gather, directly or
    through layout-only ops."""
    defs = {}
    calls = []
    for line in hlo.splitlines():
        if " = " not in line:
            continue
        lhs, rhs = line.split(" = ", 1)
        name = lhs.strip().removeprefix("ROOT ").lstrip("%")
        m = re.search(r"(?:^|\s)([a-z][\w\-]*)\((.*)", rhs)
        if not m:
            continue
        op, rest = m.group(1), m.group(2)
        depth, end = 1, 0
        for end, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
        operands = re.findall(r"%([\w.\-]+)", rest[:end])
        defs[name] = (op, operands)
        if op == "custom-call" and "tpu_custom_call" in rest:
            calls.append(operands)

    def from_gather(name, seen=()):
        op, operands = defs.get(name, ("", []))
        if op.startswith("all-gather"):
            return True
        if op.startswith(_PASS_THROUGH) and name not in seen:
            return any(from_gather(o, seen + (name,)) for o in operands)
        return False

    return [o for operands in calls for o in operands if from_gather(o)]


# ---------------------------------------------------------------------------
# attention: qwen3-1.7b (16 q / 8 kv heads, d=128) and gpt-1.7b (24, d=96)
# ---------------------------------------------------------------------------

ATTN = {"qwen3-1.7b": (16, 8, 128), "gpt-1.7b": (24, 24, 96)}


def _qkv(one_chip, heads, kv, d, b=4, s=1024):
    return (
        _sds((b, s, heads, d), jnp.bfloat16, one_chip),
        _sds((b, s, kv, d), jnp.bfloat16, one_chip),
        _sds((b, s, kv, d), jnp.bfloat16, one_chip),
    )


@pytest.mark.parametrize("arch", sorted(ATTN))
def test_flash_attention_forward_compiles(one_chip, arch):
    _compile(lambda q, k, v: flash_attention_pallas(q, k, v), *_qkv(one_chip, *ATTN[arch]))


@pytest.mark.parametrize("arch", sorted(ATTN))
def test_flash_attention_grad_compiles(one_chip, arch):
    def loss(q, k, v):
        return flash_attention_pallas(q, k, v).astype(jnp.float32).sum()

    _compile(
        jax.value_and_grad(loss, argnums=(0, 1, 2)), *_qkv(one_chip, *ATTN[arch])
    )


# ---------------------------------------------------------------------------
# reshard data plane at the executors' granularity (block_rows=1)
# ---------------------------------------------------------------------------

# (leaf shape, rows moved): a 2048x6144 MLP weight, and a stack of them
LEAVES = {"2d": ((2048, 6144), 500), "stacked": ((4, 2048, 6144), 3)}


@pytest.mark.parametrize("leaf", sorted(LEAVES))
@pytest.mark.parametrize("kernel", ["pack", "scatter", "relayout"])
def test_row_kernel_compiles(one_chip, kernel, leaf):
    shape, nb = LEAVES[leaf]
    x = _sds(shape, jnp.float32, one_chip)
    buf = _sds((nb,) + shape[1:], jnp.float32, one_chip)
    starts = _sds((nb,), jnp.int32, one_chip)
    if kernel == "pack":
        _compile(lambda s, st: pack_rows_pallas(s, st, 1), x, starts)
    elif kernel == "scatter":
        _compile(lambda d, b, st: scatter_rows_pallas(d, b, st, 1), x, buf, starts,
                 donate_argnums=(0,))
    else:
        _compile(lambda d, s, st: relayout_rows_pallas(d, s, st, 1), x, x, starts,
                 donate_argnums=(0,))


@pytest.mark.parametrize("fmt", sorted(WIRE_QDTYPE))
def test_quant_pair_compiles(one_chip, fmt):
    R, C, nb = 2048, 6144, 500
    src = _sds((R, C), jnp.float32, one_chip)
    starts = _sds((nb,), jnp.int32, one_chip)
    _compile(lambda s, st: pack_quant_rows_pallas(s, st, 1, fmt), src, starts)
    q = _sds((nb, C), WIRE_QDTYPE[fmt], one_chip)
    scales = _sds((nb, 1), jnp.float32, one_chip)
    _compile(
        lambda d, b, sc, st: dequant_scatter_rows_pallas(d, b, sc, st, 1),
        src, q, scales, starts, donate_argnums=(0,),
    )


def test_ssd_intra_chunk_compiles(one_chip):
    """mamba2-2.7b: 80 heads of 64, d_state 128, chunk 64."""
    b, s, h, p, n, chunk = 1, 1024, 80, 64, 128, 64
    f32 = jnp.float32
    _compile(
        lambda x, dt, cum, B, C: ssd_intra_chunk_pallas(x, dt, cum, B, C, chunk),
        _sds((b, s, h, p), jnp.bfloat16, one_chip),
        _sds((b, s, h), f32, one_chip),
        _sds((b, s, h), f32, one_chip),
        _sds((b, s, n), f32, one_chip),
        _sds((b, s, n), f32, one_chip),
    )


# ---------------------------------------------------------------------------
# kernels inside sharded programs: per device, never fed by an all-gather
# ---------------------------------------------------------------------------


def test_sharded_row_kernels_stay_local(mesh4, chip_dispatch):
    """The executors' gather/scatter on a tp-sharded stacked leaf."""
    sh = NamedSharding(mesh4, P(None, None, "model"))
    leaf = _sds((4, 2048, 6144), jnp.float32, sh)
    buf = _sds((3, 2048, 6144), jnp.float32, sh)
    starts = _sds((3,), jnp.int32, NamedSharding(mesh4, P()))
    for fn, args, kw in [
        (lambda s, st: ops.pack_rows(s, st, 1, sharding=sh), (leaf, starts), {}),
        (lambda d, b, st: ops.scatter_rows(d, b, st, 1, sharding=sh),
         (leaf, buf, starts), {"donate_argnums": (0,)}),
    ]:
        text = _compile(fn, *args, out_shardings=sh, **kw)
        assert "all-gather" not in text
    assert not ops.FALLBACKS


def test_tp4_train_step_attention_stays_local(mesh4, chip_dispatch):
    """qwen3-1.7b at published widths (one layer) on dp1 x tp4: the flash
    kernel runs per device in forward and backward, fed by no all-gather."""
    from repro.configs import get_config
    from repro.distribution.step import jit_train_step
    from repro.models.model import abstract_params
    from repro.optim import AdamWConfig
    from repro.utils.pytree import tree_from_paths, tree_paths

    cfg = dataclasses.replace(get_config("qwen3-1.7b"), num_layers=1)
    step, (ps, os_, bs) = jit_train_step(cfg, mesh4, AdamWConfig(), global_batch=4)

    def shaped(tree, shardings):
        shs = tree_paths(shardings)
        return tree_from_paths(
            {p: _sds(a.shape, a.dtype, shs[p]) for p, a in tree_paths(tree).items()},
            tree,
        )

    aparams = abstract_params(cfg)
    params = shaped(aparams, ps)
    opt = {
        "mu": params,
        "nu": params,
        "count": _sds((), jnp.int32, os_["count"]),
    }
    batch = {"tokens": _sds((4, 256), jnp.int32, bs["tokens"])}
    text = step.lower(params, opt, batch).compile().as_text()
    assert "tpu_custom_call" in text
    assert _gathered_kernel_operands(text) == []
    assert not ops.FALLBACKS

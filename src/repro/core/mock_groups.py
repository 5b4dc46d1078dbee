"""Mock Process Groups (paper §4.5) — JAX adaptation.

The paper intercepts NCCL collectives so cold ranks can finish heavyweight
*local* initialization (model construction, JIT compilation, autotuning)
without blocking hot ranks. The JAX analogue: trace + lower the target-world
step functions against an ``AbstractMesh`` — the entire Python-side pipeline
(model construction, jaxpr tracing, StableHLO lowering, sharding inference)
executes with *zero* device participation; only the final ``compile()``
(the communicator-construction analogue) binds concrete devices, and that
runs in the Shadow World's background thread (core/shadow.py).

The symmetry break is identical to the paper's: local work is decoupled from
global coordination, so active devices never wait on cold-start latency.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Callable

import jax
from jax.sharding import AbstractMesh, Mesh, NamedSharding


@dataclass
class MockWarmupResult:
    lowered: Any  # jax.stages.Lowered against the abstract mesh
    lower_seconds: float
    hlo_bytes: int


def abstract_of(mesh: Mesh) -> AbstractMesh:
    return AbstractMesh(tuple(mesh.devices.shape), tuple(mesh.axis_names))


def _retarget(sharding_tree: Any, amesh: AbstractMesh) -> Any:
    """Rebuild a NamedSharding tree onto the abstract mesh."""
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(amesh, s.spec) if isinstance(s, NamedSharding) else s,
        sharding_tree,
        is_leaf=lambda s: isinstance(s, NamedSharding),
    )


def mock_warmup(
    fn: Callable,
    mesh: Mesh,
    in_shardings: Any,
    abstract_args: tuple,
    out_shardings: Any = None,
    donate_argnums: tuple = (),
    static_argnums: tuple = (),
) -> MockWarmupResult:
    """Run the 'mock process group' warmup: full trace+lower on an abstract
    stand-in of the target mesh. No device is touched.
    """
    amesh = abstract_of(mesh)
    t0 = time.perf_counter()
    jitted = jax.jit(
        fn,
        in_shardings=_retarget(in_shardings, amesh),
        out_shardings=(
            _retarget(out_shardings, amesh) if out_shardings is not None else None
        ),
        donate_argnums=donate_argnums,
        static_argnums=static_argnums,
    )
    # device-less lowering must name its target platform explicitly
    lowered = jitted.trace(*abstract_args).lower(
        lowering_platforms=(jax.default_backend(),)
    )
    dt = time.perf_counter() - t0
    return MockWarmupResult(
        lowered=lowered, lower_seconds=dt, hlo_bytes=len(lowered.as_text())
    )

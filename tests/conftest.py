"""Shared test helpers.

NOTE: XLA_FLAGS / host-device-count is deliberately NOT set here — in-process
tests see the real single CPU device. Tests that need a multi-device mesh
spawn a subprocess via ``run_with_devices`` so the 512-device dry-run
environment never leaks into the default test session.
"""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")

# Parity tolerance for cross-mesh training comparisons (test_elastic_e2e,
# bench_parity). The reshard byte-movement itself is exactly lossless —
# property-tested BIT-EXACT in test_reshard_engine/test_streaming, and
# the subtle one-step-stale-layer class (divergence ~lr, which a loose
# float tolerance could miss) is guarded bit-exactly by
# test_dirty_resync_is_byte_exact. What this tolerance covers is training
# *after* the switch: a different mesh factorization changes XLA's
# reduction order in matmul/collective lowerings, giving ~1-ulp gradient
# differences, and Adam's m̂/(√v̂+ε) normalization amplifies any
# sign-flip of a tiny-magnitude update to a full ±lr step. Observed drift
# is ≈2·lr·steps in the worst case (lr=1e-3, ~10 steps → ~2e-2); gross
# resharding bugs (wrong bytes) show up at O(0.1–1) or NaN, so 1e-2
# separates reduction-order noise from movement failures while the
# bit-exact tests above cover everything smaller.
RESHAPE_PARITY_TOL = 1e-2


def run_with_devices(code: str, n_devices: int = 8, timeout: int = 900) -> str:
    """Run a python snippet in a subprocess with N host platform devices
    (held to the CPU: a child never contends for an accelerator)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True,
        text=True,
        env=env,
        timeout=timeout,
    )
    if r.returncode != 0:
        raise AssertionError(
            f"subprocess failed (rc={r.returncode})\nstdout:\n{r.stdout[-3000:]}"
            f"\nstderr:\n{r.stderr[-3000:]}"
        )
    return r.stdout


@pytest.fixture(scope="session")
def subproc():
    return run_with_devices

"""Shared benchmark helpers: CSV emission, the ``BENCH_*.json`` artifact
envelope, and the multi-device subprocess runner.

Output contract (documented for trajectory tooling in results/README.md):
``emit`` prints one ``name,us_per_call,derived`` CSV row per metric;
``write_results`` persists a benchmark's structured payload under
``results/BENCH_<name>.json`` with a standard envelope so artifacts are
self-describing across runs.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")
RESULTS = os.path.join(REPO, "results")

# bump when the envelope fields below change shape
RESULTS_SCHEMA = 1


def emit(name: str, us_per_call: float, derived: str) -> None:
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)


def write_results(name: str, payload: dict, mode: str | None = None) -> str:
    """Persist ``results/BENCH_<name>.json`` with the standard envelope
    (schema in results/README.md) and return the path. ``mode`` tags the
    run variant (e.g. "smoke" vs "full")."""
    doc = {
        "bench": name,
        "schema": RESULTS_SCHEMA,
        "unix_time": time.time(),
    }
    if mode is not None:
        doc["mode"] = mode
    doc.update(payload)
    os.makedirs(RESULTS, exist_ok=True)
    path = os.path.join(RESULTS, f"BENCH_{name}.json")
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, default=str)
    return path


def run_with_devices(code: str, n_devices: int = 8, timeout: int = 1200) -> str:
    """Run a python snippet in a child with N CPU host devices (the child
    is held to the CPU, so it never contends for a chip the parent holds)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=timeout,
    )
    if r.returncode != 0:
        raise RuntimeError(f"bench subprocess failed:\n{r.stderr[-3000:]}")
    return r.stdout


class Timed:
    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *a):
        self.us = (time.perf_counter() - self.t0) * 1e6
        return False

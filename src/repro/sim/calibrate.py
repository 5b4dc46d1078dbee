"""Host calibration for the simulator (paper §5 'Simulator Calibration').

Measures on THIS machine: process spawn, jax import+init, XLA compile-time
scaling with model size, and host memcpy/device_put bandwidth. Constants are
cached to JSON; the Fig. 10-style validation benchmark
(benchmarks/bench_simvalidate.py) compares simulator predictions against
live LiveR reconfigurations measured by the controller on host devices.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

CACHE = "results/calibration.json"


def measure(force: bool = False) -> dict:
    if not force and os.path.exists(CACHE):
        with open(CACHE) as f:
            return json.load(f)

    out: dict = {}

    # process spawn + interpreter boot
    t0 = time.perf_counter()
    subprocess.run([sys.executable, "-c", "pass"], check=True)
    out["proc_spawn_s"] = time.perf_counter() - t0

    # jax import + backend init in a fresh process
    t0 = time.perf_counter()
    subprocess.run(
        [sys.executable, "-c", "import jax; jax.devices()"],
        check=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},  # never contend for a chip
    )
    out["jax_init_s"] = time.perf_counter() - t0

    # host memcpy bandwidth (the staging-buffer assemble cost)
    buf = np.random.default_rng(0).random(64 * 1024 * 1024 // 8)
    t0 = time.perf_counter()
    for _ in range(4):
        _ = buf.copy()
    dt = (time.perf_counter() - t0) / 4
    out["memcpy_gbps"] = buf.nbytes / dt / 1e9 * 8

    # compile-time scaling: lower+compile a 2-layer block at two widths
    import jax

    import jax.numpy as jnp

    def compile_probe(d):
        def f(x, w1, w2):
            def body(c, _):
                return jnp.tanh(c @ w1) @ w2, None
            c, _ = jax.lax.scan(body, x, None, length=2)
            return c.sum()
        args = [jax.ShapeDtypeStruct(s, jnp.float32) for s in ((8, d), (d, d), (d, d))]
        t0 = time.perf_counter()
        jax.jit(jax.grad(f, argnums=(1, 2))).lower(*args).compile()
        return time.perf_counter() - t0

    t_small, t_big = compile_probe(256), compile_probe(1024)
    out["compile_base_s"] = t_small
    out["compile_scale"] = max(t_big - t_small, 1e-3)

    os.makedirs(os.path.dirname(CACHE), exist_ok=True)
    with open(CACHE, "w") as f:
        json.dump(out, f, indent=2)
    return out

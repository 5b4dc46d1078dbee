"""One run of one cell: set-up, the timed window, the comparison with the
plain reference, and the result line.

Everything a cell is made of is found by name: ``workloads/<cell>.json``
names its configuration (``configs/<config>.json``), its traffic driver
(``drivers/<driver>.py``) and its limits; the configuration names its model
family (``families/<family>.py``) and its plain reference (``<reference>.py``,
trained by ``reference.py``); the per-layer metrics it reports are the
``per_layer`` entries of ``BENCHMARK.json`` that list it (or list no cells),
each read by ``metrics/<metric>.py``.
"""

from __future__ import annotations

import argparse
import importlib
import json
import math
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

from bench import model
from bench.model import BENCH, BenchError

ROOT = BENCH.parent
TRACE_DIR = ROOT / ".bench_trace"


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def peaks_for(kind: str, bench_dir: Path = BENCH) -> dict:
    table = json.loads((bench_dir / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise BenchError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def cell_metrics(bench: dict, cell: str, section: str) -> list[dict]:
    return [m for m in bench[section] if cell in m.get("workloads", [cell])]


@dataclass
class RunContext:
    """What a driver gets: the cell, the seed, the devices and the window's
    bracket (compile counting and, with ``trace``, the profiler)."""

    name: str
    workload: dict
    conf: dict
    cfg: object
    seed: int
    seconds: float
    trace: bool
    devices: list
    chips: int
    t0: float
    counter: object = None
    state_shardings: object = None  # (params, opt_state) shardings of the controller
    state_abstract: object = None  # and their shapes
    _span: object = field(default=None, repr=False)

    def mark(self, phase: str) -> None:
        """Log how far into set-up ``phase`` ended."""
        log(f"{phase} at {time.perf_counter() - self.t0:.2f}s")

    def open_window(self) -> None:
        import jax

        self.counter.active = True
        if self.trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation("window")
        self._span.__enter__()

    def close_window(self, out) -> None:
        import jax

        self._span.__exit__(None, None, None)
        if self.trace:
            jax.profiler.stop_trace()
        self.counter.active = False
        out.compiles_in_window = self.counter.compiles
        out.gc_in_window_s = self.counter.gc_s


@dataclass
class Run:
    """What a per-layer metric reader gets."""

    ctx: RunContext
    out: object  # drivers.common.Window
    trace: object  # trace.TraceSummary, or None in an untraced run
    peaks: dict | None


def read_per_layer(bench: dict, run: Run) -> dict:
    metrics = {}
    for m in cell_metrics(bench, run.ctx.name, "per_layer"):
        reader = importlib.import_module(f"bench.metrics.{m['name']}")
        value = reader.read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": reader.UNIT}
    return metrics


def run_cell(name: str, seed: int, seconds: float, trace: bool, t0: float,
             root: Path = BENCH, require_tpu: bool = True) -> dict:
    """Run the cell once and return its result line (a dict).

    ``require_tpu=False`` skips the look for a chip: tests drive the rest of
    a run on the CPU with it."""
    import jax

    from bench import reference
    from bench import trace as trace_mod
    from bench.drivers.common import WindowCounters

    wl = model.load("workloads", name, root)
    conf = model.load("configs", wl["config"], root)
    devices = jax.devices()
    dev = devices[0]
    peaks = None
    if require_tpu:
        if dev.platform != "tpu":
            raise BenchError(f"no TPU: JAX platform is {dev.platform!r}")
        if len(devices) < wl["chips"]:
            raise BenchError(f"the cell needs {wl['chips']} chips, JAX sees {len(devices)}")
        peaks = peaks_for(dev.device_kind)
        # every program of the run, small ones too, so that a second run of
        # the cell in this checkout compiles nothing
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    ctx = RunContext(name=name, workload=wl, conf=conf, cfg=model.family(conf).model_config(conf),
                     seed=seed, seconds=seconds, trace=trace, devices=devices,
                     chips=wl["chips"], t0=t0, counter=WindowCounters())
    driver = importlib.import_module(f"bench.drivers.{wl['driver']}")
    out = driver.run(ctx)
    slow = sorted(range(len(out.step_s)), key=lambda i: -out.step_s[i])[:3]
    log(f"set-up {out.setup_s:.2f}s; window {out.window_s:.2f}s, {len(out.step_s)} steps, "
        f"{out.compiles_in_window} compiles and {out.gc_in_window_s:.3f}s of gc inside it; "
        f"slowest steps " + ", ".join(f"#{i} {1e3 * out.step_s[i]:.1f}ms" for i in slow))

    held = (devices[0].memory_stats() or {}).get("bytes_in_use")
    log(f"device 0 holds {held} bytes before the reference")
    t_ref = time.perf_counter()
    ref = reference.train_readings(conf, wl["optimizer"], seed, out.batches, device=devices[0])
    checks = reference.compare(out.readings, ref)
    limits = wl["limits"]
    correct = set(checks) == set(limits) and all(
        math.isfinite(checks[k]) and checks[k] <= limits[k] for k in checks)
    log(f"reference: {len(out.batches)} steps in {time.perf_counter() - t_ref:.2f}s; "
        f"losses {out.readings.losses} vs {ref.losses}")

    bench = load_benchmark(root.parent)
    result = {"correct": correct, "attempted": out.attempted, "failed": out.failed}
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
              "memory_peak_bytes": out.memory_peak_bytes}
    summary = None
    if trace:
        summary = trace_mod.load(str(TRACE_DIR))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        used = [summary.devices[i].busy_s for i in range(ctx.chips) if i in summary.devices]
        device["busy_s"] = sum(used) / len(used)
        device["window_s"] = summary.window_s
        result["metrics"] = read_per_layer(bench, Run(ctx, out, summary, peaks))
    else:
        values = dict(out.e2e, setup_s=out.setup_s)
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell_metrics(bench, name, "end_to_end") if m["name"] in values}
    result["device"] = device
    if summary is not None:
        result["breakdown"] = summary.breakdown(0)
    result["checks"] = {k: {"value": checks[k], "limit": limits.get(k)} for k in checks}
    return result


def main(argv: list[str], t0: float) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace), t0)
    except BenchError as e:
        log(f"error: {e}")
        return 2
    for k, c in result["checks"].items():
        log(f"check {k} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0

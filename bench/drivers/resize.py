"""Live resizes under training: the window opens on the workload's
``parallel`` layout and requests ``grow_to`` and back, alternately, at
t = (i + 1/2) * P for P = seconds / resizes_per_window, each request waiting
for the one before it to commit. The controller streams both directions
(``overlap``) and keeps both worlds warm in a ``WorldPool``.

Set-up runs one step and then one full grow + shrink cycle, so that every
program the window runs is compiled and every resize in the window is a
warm-pool hit; those steps are the ones ``correct`` compares.
"""

from __future__ import annotations

import gc
import statistics
import time

import jax

from bench.drivers import common
from bench.reference import Readings

MAX_STEPS_TO_COMMIT = 32


def _until_commit(ctrl, losses: list) -> None:
    n = len(ctrl.records)
    while len(ctrl.records) == n:
        if len(losses) > MAX_STEPS_TO_COMMIT:
            raise RuntimeError("set-up: a resize did not commit")
        losses += ctrl.train_steps(1)


def build(ctx):
    from repro.core.world_pool import WorldPool

    wl = ctx.workload
    return common.build_controller(
        ctx, wl["parallel"], ctx.devices[: ctx.chips], overlap=wl["overlap"],
        stream_k=wl["stream_k"], world_pool=WorldPool(wl["pool_capacity"]),
        sync_compile=True)


def readings(ctrl, ctx) -> tuple[Readings, list]:
    """One step, then a grow and a shrink, each driven to its commit and one
    step past it; read those steps."""
    from repro.configs.base import ParallelConfig

    wl = ctx.workload
    first = len(ctrl.records)
    losses = ctrl.train_steps(1)
    grads = common.first_grad_norms(ctrl)
    for target in (ParallelConfig(**wl["grow_to"]), ParallelConfig(**wl["parallel"])):
        ctrl.request_resize(target)
        ctrl.wait_shadow_ready()
        _until_commit(ctrl, losses)
        losses += ctrl.train_steps(1)
    bad = [(r.src, r.dst, r.mode, r.outcome) for r in ctrl.records[first:]
           if (r.mode, r.outcome) != ("live_overlap", "committed")]
    if bad or ctrl.swallowed_errors:
        raise RuntimeError(f"set-up resizes: {bad} {ctrl.swallowed_errors}")
    batches = [ctrl.data.global_batch_at(i) for i in range(len(losses))]
    return Readings(losses, grads, common.update_norms(ctrl, ctx)), batches


def run(ctx) -> common.Window:
    from repro.configs.base import ParallelConfig

    wl, out = ctx.workload, common.Window()
    devices = ctx.devices[: ctx.chips]
    ctrl = build(ctx)
    ctx.mark("controller built")
    small, big = ParallelConfig(**wl["parallel"]), ParallelConfig(**wl["grow_to"])
    out.readings, out.batches = readings(ctrl, ctx)
    out.setup_s = time.perf_counter() - ctx.t0

    n_req = wl["resizes_per_window"]
    period = ctx.seconds / n_req
    due = [(i + 0.5) * period for i in range(n_req)]
    tokens_per_step = ctrl.global_batch * ctrl.seq_len
    resize_s, pending, issued = [], None, 0
    ctx.open_window()
    start = time.perf_counter()
    while time.perf_counter() - start < ctx.seconds:
        if pending is None and issued < n_req and time.perf_counter() - start >= due[issued]:
            with jax.profiler.TraceAnnotation("request_resize"):
                t_req = time.perf_counter()
                ctrl.request_resize(big if issued % 2 == 0 else small)
            pending = {"t": t_req, "records": len(ctrl.records), "committed": False}
            issued += 1
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("train_step"):
            ctrl.train_steps(1)
        t1 = time.perf_counter()
        out.step_s.append(t1 - t0)
        out.tokens += tokens_per_step
        if pending is None:
            continue
        if pending["committed"]:  # the first step wholly on the new world
            resize_s.append(t1 - pending["t"])
            pending = None
        elif len(ctrl.records) > pending["records"]:
            rec = ctrl.records[-1]
            if rec.outcome == "committed":
                out.records.append(rec)
                pending["committed"] = True
            else:
                out.failed += 1
                pending = None
    out.window_s = time.perf_counter() - start
    ctx.close_window(out)

    if pending is not None and not pending["committed"]:
        out.failed += 1
        ctrl.cancel_resize()
    out.attempted = len(out.step_s) + n_req
    out.failed += n_req - issued  # never requested: the one before had not committed
    common.rate_and_tail(out)
    if out.records:
        out.e2e["resize_pause_ms"] = 1e3 * statistics.fmean(r.total_pause_s for r in out.records)
    if resize_s:
        out.e2e["resize_s"] = statistics.fmean(resize_s)
    out.memory_peak_bytes = common.peak_bytes(devices)
    del ctrl
    gc.collect()
    return out

"""Steady training: a closed loop of ``train_steps(1)`` on one layout."""

from __future__ import annotations

import gc
import time

from bench.drivers import common
from bench.reference import Readings


def build(ctx):
    return common.build_controller(ctx, ctx.workload["parallel"], ctx.devices[: ctx.chips])


def readings(ctrl, ctx) -> tuple[Readings, list]:
    """Drive the first ``check_steps`` steps from the seed and read them."""
    n = ctx.workload["check_steps"]
    batches = [ctrl.data.global_batch_at(i) for i in range(n)]
    losses = ctrl.train_steps(1)
    grads = common.first_grad_norms(ctrl)
    losses += ctrl.train_steps(n - 1)
    return Readings(losses, grads, common.update_norms(ctrl, ctx)), batches


def run(ctx) -> common.Window:
    out = common.Window()
    ctrl = build(ctx)
    ctx.mark("controller built")
    out.readings, out.batches = readings(ctrl, ctx)
    out.setup_s = time.perf_counter() - ctx.t0

    ctx.open_window()
    common.timed_steps(ctrl, ctx.seconds, out)
    ctx.close_window(out)
    out.memory_peak_bytes = common.peak_bytes(ctx.devices[: ctx.chips])
    del ctrl
    gc.collect()
    return out

"""Set-up and readings shared by the training drivers.

One controller is built per run. Set-up places the seed's weights in it,
drives its first steps through ``LiveRController.train_steps`` (the window's
own call and feed) and reads what ``correct`` compares; the same controller
then runs the window.
"""

from __future__ import annotations

import functools
import gc
import json
import statistics
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from bench import model
from bench.reference import Readings


@dataclass
class Window:
    """What a driver measured, for the harness to report."""

    step_s: list[float] = field(default_factory=list)  # every step of the window
    window_s: float = 0.0
    tokens: int = 0
    setup_s: float = 0.0
    attempted: int = 0
    failed: int = 0
    e2e: dict = field(default_factory=dict)  # end-to-end values by metric name
    records: list = field(default_factory=list)  # ReconfigRecords committed in the window
    memory_peak_bytes: int = 0
    compiles_in_window: int = 0
    gc_in_window_s: float = 0.0
    readings: Readings | None = None
    batches: list = field(default_factory=list)  # tokens of the compared steps


class WindowCounters:
    """XLA backend compiles (``jax.monitoring`` events) and Python garbage
    collection pauses, counted while ``active``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.compiles = 0
        self.gc_s = 0.0
        self.active = False
        self._gc_t0 = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        gc.callbacks.append(self._on_gc)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if self.active and event == self.EVENT:
            self.compiles += 1

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self.active:
            self.gc_s += time.perf_counter() - self._gc_t0


def build_controller(ctx, parallel: dict, devices, **kw):
    """A ``LiveRController`` for the cell, its weights replaced by the seed's."""
    from repro.configs.base import ParallelConfig
    from repro.core.controller import LiveRController
    from repro.optim import AdamWConfig

    wl = ctx.workload
    ctrl = LiveRController(
        ctx.cfg, ParallelConfig(**parallel), AdamWConfig(**wl["optimizer"]),
        seq_len=wl["seq_len"], global_batch=wl["global_batch"], data=feed(ctx),
        devices=devices, seed=model.seed_words(ctx.seed)[0], **kw,
    )
    state = (ctrl.params, ctrl.opt_state)
    ctx.state_shardings = jax.tree_util.tree_map(lambda a: a.sharding, state)
    ctx.state_abstract = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), state)
    del state
    reseed(ctrl, ctx)
    return ctrl


def feed(ctx) -> model.SeededTokens:
    wl = ctx.workload
    return model.SeededTokens(ctx.seed, ctx.conf["vocab_size"], wl["global_batch"], wl["seq_len"])


def reseed(ctrl, ctx) -> None:
    """Start the controller's training over from ``ctx.seed``: the seed's
    weights, zero AdamW state, step 0 and the seed's batches."""
    p_sh, o_sh = ctx.state_shardings
    ctrl.params = ctrl.opt_state = None
    init_params = model.family(ctx.conf).init_params
    params = jax.jit(functools.partial(init_params, ctx.conf), out_shardings=p_sh)(
        model.params_key(ctx.seed))
    if jax.tree_util.tree_structure(params) != jax.tree_util.tree_structure(p_sh):
        raise model.BenchError("the program's parameter layout differs from the benchmark's")
    ctrl.params = params
    ctrl.opt_state = jax.jit(
        lambda: jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), ctx.state_abstract[1]),
        out_shardings=o_sh)()
    ctrl.step = 0
    ctrl.data = feed(ctx)


@functools.lru_cache(maxsize=None)
def _grad_norms_fn(b1: float):
    return jax.jit(lambda mu: model.leaf_norms(
        jax.tree_util.tree_map(lambda m: m / (1 - b1), mu)))


@functools.lru_cache(maxsize=None)
def _delta_norms_fn(conf_key: str):
    conf = json.loads(conf_key)
    init_params = model.family(conf).init_params
    return jax.jit(lambda p, k: model.leaf_norms(
        jax.tree_util.tree_map(jnp.subtract, p, init_params(conf, k))))


def first_grad_norms(ctrl) -> dict[str, float]:
    """Norms of the first clipped gradient as the optimizer got it: AdamW's
    first moment after one step from zero is (1 - b1) * g."""
    return model.flat_norms(jax.device_get(_grad_norms_fn(ctrl.opt_cfg.b1)(ctrl.opt_state["mu"])))


def update_norms(ctrl, ctx) -> dict[str, float]:
    """Norms of the change of the weights since the seed's."""
    fn = _delta_norms_fn(json.dumps(ctx.conf, sort_keys=True))
    return model.flat_norms(jax.device_get(fn(ctrl.params, model.params_key(ctx.seed))))


def peak_bytes(devices) -> int:
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)


def timed_steps(ctrl, seconds: float, out: Window) -> None:
    """Closed loop of ``train_steps(1)`` for ``seconds``; each step timed on
    the host clock, closed by the loss's ``block_until_ready`` inside
    ``train_steps``."""
    tokens_per_step = ctrl.global_batch * ctrl.seq_len
    start = time.perf_counter()
    while time.perf_counter() - start < seconds:
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("train_step"):
            ctrl.train_steps(1)
        out.step_s.append(time.perf_counter() - t0)
        out.tokens += tokens_per_step
    out.window_s = time.perf_counter() - start
    out.attempted += len(out.step_s)
    rate_and_tail(out)


def rate_and_tail(out: Window) -> None:
    """The window's ``train_tokens_per_s`` and ``step_ms_p95``."""
    out.e2e["train_tokens_per_s"] = out.tokens / out.window_s
    out.e2e["step_ms_p95"] = 1e3 * statistics.quantiles(out.step_s, n=20)[-1]

"""Readings that a cell's limits are set from, at the cell's own size, on the
chip. Not part of a benchmark run.

    python3 bench/calibrate.py --workload <cell> --seeds 1-12 --control-seeds 1-3 [--out f.json]

For every seed of ``--seeds`` the program (one controller, restarted from
each seed) is compared with the plain reference that the configuration's
``reference`` key names: the lower readings. For
every seed of ``--control-seeds`` the control (the reference in fp8 put in
the program's place), half of the batch left out (the reference with
``fault="half_batch"`` in the program's place) and, in a cell whose driver
resizes, the program with every cross-chip move of the resize left out are
compared with it too: the upper readings. A state left unchanged reads 1 on
``update_norm_gap`` by construction and is not run.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _seeds(text: str) -> list[int]:
    lo, _, hi = text.partition("-")
    return list(range(int(lo), int(hi or lo) + 1))


@contextlib.contextmanager
def cross_chip_moves_dropped():
    """The resize's exchange between chips left out: every cell of a moved
    tensor whose source and destination ranks differ is skipped, so its
    destination keeps the zeros it was allocated with."""
    from repro.reshard.executors import LiveExecutor

    move = LiveExecutor._move_tensor

    def local_only(self, name, cells):
        self._dst_carry(name)
        kept = [c for c in cells if c.kind != "remote"]
        if kept:
            move(self, name, kept)

    LiveExecutor._move_tensor = local_only
    try:
        yield
    finally:
        LiveExecutor._move_tensor = move


def table(ctx, seeds: list[int], control: list[int]) -> tuple[list[dict], dict]:
    """The readings of the program on ``seeds`` and of the control and the
    faults on ``control``, each against the reference; and their summary:
    the largest program reading and the smallest of each other kind."""
    import importlib

    from bench import reference
    from bench.drivers import common

    wl, conf, devices = ctx.workload, ctx.conf, ctx.devices
    driver = importlib.import_module(f"bench.drivers.{wl['driver']}")
    ctrl = driver.build(ctx)
    opt = wl["optimizer"]
    rows = []

    def program(seed):
        ctx.seed = seed
        common.reseed(ctrl, ctx)
        got, batches = driver.readings(ctrl, ctx)
        ctrl.params = ctrl.opt_state = None  # free the program's state for the reference
        return got, batches

    def row(kind, seed, got, ref):
        gaps = reference.compare(got, ref)
        rows.append({"kind": kind, "seed": seed, **gaps})
        print(json.dumps(rows[-1]), flush=True)

    refs = {}
    for seed in seeds:
        t0 = time.perf_counter()
        got, batches = program(seed)
        ref = reference.train_readings(conf, opt, seed, batches, device=devices[0])
        refs[seed] = (ref, batches)
        row("program", seed, got, ref)
        print(f"seed {seed}: {time.perf_counter() - t0:.1f}s", file=sys.stderr, flush=True)
    for seed in control:
        ref, batches = refs.get(seed) or (None, None)
        if ref is None:
            got, batches = program(seed)
            ref = reference.train_readings(conf, opt, seed, batches, device=devices[0])
            refs[seed] = (ref, batches)
        for kind, kw in (("control_fp8", {"matmul": "fp8"}), ("half_batch", {"fault": "half_batch"})):
            got = reference.train_readings(conf, opt, seed, batches, device=devices[0], **kw)
            row(kind, seed, got, ref)
    if wl.get("grow_to"):
        with cross_chip_moves_dropped():
            for seed in control:
                got, _ = program(seed)
                row("no_exchange", seed, got, refs[seed][0])
    summary = {}
    for kind in sorted({r["kind"] for r in rows}):
        sel = [r for r in rows if r["kind"] == kind]
        agg = max if kind == "program" else min
        summary[kind] = {k: agg(r[k] for r in sel) for k in ("loss_gap", "grad_norm_gap", "update_norm_gap")}
    return rows, summary


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1-12")
    ap.add_argument("--control-seeds", default="1-3")
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(CHECKOUT, ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [CHECKOUT, os.path.join(CHECKOUT, "src")]
    import jax

    from bench import harness, model
    from bench.drivers import common

    jax.config.update("jax_compilation_cache_dir", os.path.join(CHECKOUT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    wl = model.load("workloads", args.workload)
    conf = model.load("configs", wl["config"])
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < wl["chips"]:
        raise SystemExit(f"needs {wl['chips']} TPU chips")
    seeds, control = _seeds(args.seeds), _seeds(args.control_seeds)
    ctx = harness.RunContext(name=args.workload, workload=wl, conf=conf,
                             cfg=model.family(conf).model_config(conf), seed=seeds[0], seconds=0,
                             trace=False, devices=devices, chips=wl["chips"],
                             t0=time.perf_counter(), counter=common.WindowCounters())
    rows, summary = table(ctx, seeds, control)
    print(json.dumps({"workload": args.workload, "summary": summary}), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "rows": rows, "summary": summary}, f, indent=1)


if __name__ == "__main__":
    main()

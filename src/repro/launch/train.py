"""Elastic training launcher.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
    PYTHONPATH=src python -m repro.launch.train \\
        --arch qwen3-1.7b --reduced --dp 2 --tp 2 --steps 60 \\
        --resize 20:dp2,tp4 --resize 40:dp1,tp4

Each ``--resize STEP:SPEC`` schedules a live reconfiguration request at that
step; the switch lands at the first iteration boundary after the shadow
world is ready (invariant I3). ``--failstop STEP:SPEC`` injects an
unannounced failure handled via checkpoint fallback (invariant I4).
"""

from __future__ import annotations

import argparse
import json
import time


def parse_parallel(spec: str):
    """'dp2,tp4' -> ParallelConfig; 'auto8' -> 8 (device count; the
    topology search picks the layout — paper §2.3(D) integration)."""
    from repro.configs.base import ParallelConfig

    if spec.startswith("auto"):
        return int(spec[4:])
    kv = {}
    for part in spec.split(","):
        k = part.rstrip("0123456789")
        v = int(part[len(k):])
        kv[k] = v
    return ParallelConfig(**kv)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--pp", type=int, default=1)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--compression", default="none", choices=["none", "int8_ef"])
    ap.add_argument("--overlap", default="stop_copy", choices=["stop_copy", "stream"],
                    help="reconfiguration transfer mode: stop-copy pause or "
                    "overlapped layer streaming with split-step commit")
    ap.add_argument("--stream-k", type=int, default=4,
                    help="layers pre-copied per iteration boundary (overlap=stream)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-interval", type=int, default=25)
    ap.add_argument("--resize", action="append", default=[], metavar="STEP:SPEC")
    ap.add_argument("--failstop", default=None, metavar="STEP:SPEC")
    ap.add_argument("--out", default=None, help="write run record JSON here")
    args = ap.parse_args()

    from repro.configs import get_config
    from repro.configs.base import ParallelConfig
    from repro.core.controller import LiveRController
    from repro.optim import AdamWConfig
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    parallel = ParallelConfig(dp=args.dp, pp=args.pp, tp=args.tp)
    opt = AdamWConfig(
        learning_rate=args.lr, warmup_steps=max(args.steps // 10, 1),
        total_steps=args.steps,
    )
    print(f"[train] {cfg.name} {parallel.describe()} seq={args.seq} "
          f"batch={args.batch} steps={args.steps}", flush=True)
    ctrl = LiveRController(
        cfg, parallel, opt, seq_len=args.seq, global_batch=args.batch,
        ckpt_dir=args.ckpt_dir, ckpt_interval=args.ckpt_interval,
        microbatches=args.microbatches, compression=args.compression,
        overlap=args.overlap, stream_k=args.stream_k,
    )
    resizes = sorted(
        (int(s.split(":")[0]), parse_parallel(s.split(":")[1])) for s in args.resize
    )
    failstop = None
    if args.failstop:
        st, spec = args.failstop.split(":")
        failstop = (int(st), parse_parallel(spec))

    losses = []
    t0 = time.perf_counter()
    while ctrl.step < args.steps:
        while resizes and resizes[0][0] <= ctrl.step:
            _, target = resizes.pop(0)
            if isinstance(target, int):  # auto<N>: search picks the layout
                from repro.core.topology_search import best_target

                target = best_target(
                    cfg, target, args.batch, args.seq,
                    current=ctrl.world.parallel, transition_weight=1e-9,
                )
                print(f"[search] chose {target.describe()} for the new world",
                      flush=True)
            print(f"[event] step {ctrl.step}: resize -> {target.describe()} "
                  "(shadow prepare in background)", flush=True)
            ctrl.request_resize(target)
        if failstop and failstop[0] == ctrl.step:
            print(f"[event] step {ctrl.step}: FAIL-STOP -> "
                  f"{failstop[1].describe()}", flush=True)
            rec = ctrl.fail_stop_recover(failstop[1])
            print(f"[event] recovered via {rec.mode} at step {ctrl.step} "
                  f"in {rec.total_pause_s:.2f}s", flush=True)
            failstop = None
        before = len(ctrl.records)
        losses += ctrl.train_steps(1)
        if len(ctrl.records) > before:
            r = ctrl.records[-1]
            print(f"[switch] step {ctrl.step}: {r.src} -> {r.dst} "
                  f"pause={r.total_pause_s*1e3:.1f}ms "
                  f"(prepare {r.prepare_s:.1f}s overlapped, "
                  f"moved {r.moved_bytes/1e6:.1f}MB)", flush=True)
        if ctrl.step % 10 == 0:
            print(f"  step {ctrl.step:5d} loss={losses[-1]:.4f} "
                  f"world={ctrl.world.parallel.describe()}", flush=True)

    wall = time.perf_counter() - t0
    print(f"[done] {args.steps} steps in {wall:.1f}s; "
          f"goodput={ctrl.ledger.goodput*100:.2f}% "
          f"pause_total={ctrl.ledger.pause_seconds:.3f}s "
          f"reconfigs={len(ctrl.records)}", flush=True)
    if args.out:
        rec = {
            "arch": cfg.name,
            "losses": losses,
            "goodput": ctrl.ledger.goodput,
            "pause_seconds": ctrl.ledger.pause_seconds,
            "reconfigs": [
                {
                    "src": r.src, "dst": r.dst, "mode": r.mode,
                    "prepare_s": r.prepare_s, "pause_s": r.total_pause_s,
                    "moved_bytes": r.moved_bytes,
                }
                for r in ctrl.records
            ],
            "iteration_times": ctrl.iteration_times,
        }
        with open(args.out, "w") as f:
            json.dump(rec, f, indent=2)


if __name__ == "__main__":
    main()

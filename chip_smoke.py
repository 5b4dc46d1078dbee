"""Smoke run of the elastic trainer on a TPU, in one process.

    python chip_smoke.py             # one chip: train steps + data plane
    python chip_smoke.py --chips 4   # four chips: a live 2<->4-chip resize

One chip (default):
  A. qwen3-1.7b at its published widths (d_model 2048, d_ff 6144, 16 q /
     8 kv heads of 128, vocab 151936, qk_norm), depth cut from 28 to 4
     layers; f32 parameters and AdamW state, bf16 compute; dp1 x tp1,
     batch 4 x seq 1024, one warm-up step then 5 timed steps, driven
     through ``LiveRController`` exactly as ``repro.launch.train`` does.
     Losses must be finite and the first near ln(vocab).
  B. The reshard data plane on that run's MLP weight ``wi_up`` and its
     Adam moments: the executors' pack -> scatter and relayout programs
     byte-equal to ``kernels/ref.py``, on the stacked (4, 2048, 6144)
     leaves and on their 2048 x 6144 layer-0 slices; the int8 and fp8
     wire pairs equal to the oracle.

Four chips (``--chips 4``): the same widths at 8 of 28 layers. A
controller starts on dp1 x tp2 (chips 0-1), grows to dp1 x tp4 with a live
streamed resize (lossless wire), trains, and shrinks back to dp1 x tp2
with stop-copy. At each commit the state moved onto the new world must
equal, byte for byte on the host, the state it was cut from; each resize
must commit on the live path and move bytes; and the losses must stay
within ``LOSS_TOL`` of a controller that never resized.

Every phase also asserts that no kernel fell back to its reference
(``ops.FALLBACKS``) and that the compiled programs hold the Pallas kernels
(``tpu_custom_call``). The last line of output is the JSON verdict; any
failure exits non-zero before printing it. No accelerator: exit non-zero.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 0
LOSS_TOL = 5e-2  # |loss - loss without resize| after a switch (bf16 compute)


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SystemExit(f"[smoke] FAIL: {msg}")


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def _no_fallbacks(phase: str) -> None:
    from repro.kernels import ops

    check(not ops.FALLBACKS, f"{phase}: kernel fallbacks {dict(ops.FALLBACKS)}")
    log(f"{phase}: kernel fallbacks 0")


def qwen3(layers: int):
    from repro.configs import get_config

    return dataclasses.replace(get_config("qwen3-1.7b"), num_layers=layers)


def make_controller(cfg, tp: int, seq: int, batch: int, devices, **kw):
    from repro.configs.base import ParallelConfig
    from repro.core.controller import LiveRController
    from repro.optim import AdamWConfig

    opt = AdamWConfig(learning_rate=3e-4, warmup_steps=2, total_steps=100)
    return LiveRController(
        cfg, ParallelConfig(dp=1, tp=tp), opt, seq_len=seq, global_batch=batch,
        devices=devices, seed=SEED, **kw,
    )


def peak_hbm(devices) -> int:
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)


# ---------------------------------------------------------------------------
# A. training on one chip
# ---------------------------------------------------------------------------


def phase_train(cfg, seq: int, batch: int, steps: int, devices):
    from repro.kernels import flash_attention

    t0 = time.perf_counter()
    ctrl = make_controller(cfg, 1, seq, batch, devices[:1])
    timings = ctrl.world.timings
    log(
        f"A: {cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
        f"d_ff={cfg.d_ff} heads={cfg.num_heads}/{cfg.num_kv_heads} "
        f"vocab={cfg.vocab_size} dp1xtp1 batch={batch}x{seq}"
    )
    log(
        f"A: world built in {time.perf_counter() - t0:.2f}s "
        f"(lower {timings.get('lower_s', 0):.2f}s, compile "
        f"{timings.get('compile_s', 0):.2f}s)"
    )
    check(_has_kernel(ctrl.world.step_fn), "A: no tpu_custom_call in the train step")
    losses = ctrl.train_steps(1)  # warm-up
    losses += ctrl.train_steps(steps)
    times = ctrl.iteration_times[1:]
    log(f"A: losses {[round(x, 4) for x in losses]}")
    log(
        f"A: step time after block_until_ready: mean {sum(times) / len(times):.4f}s "
        f"min {min(times):.4f}s max {max(times):.4f}s over {len(times)} steps "
        f"(warm-up {ctrl.iteration_times[0]:.3f}s)"
    )
    log(f"A: peak_bytes_in_use {peak_hbm(devices[:1]) / 2**30:.2f} GiB")
    log(f"A: attention forward: Pallas flash kernel; backward: {flash_attention.BACKWARD}")
    check(all(math.isfinite(x) for x in losses), "A: non-finite loss")
    ln_v = math.log(cfg.vocab_size)
    check(abs(losses[0] - ln_v) < 1.5, f"A: first loss {losses[0]} far from ln(V)={ln_v:.3f}")
    check(not ctrl.swallowed_errors, f"A: swallowed errors {ctrl.swallowed_errors}")
    _no_fallbacks("A")
    return ctrl


# ---------------------------------------------------------------------------
# B. data plane on one chip
# ---------------------------------------------------------------------------


def _same_bytes(a, b) -> bool:
    import numpy as np

    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and a.dtype == b.dtype and a.tobytes() == b.tobytes()


def _leaf_and_moments(ctrl):
    from repro.utils.pytree import tree_paths

    path = next(p for p in tree_paths(ctrl.params) if p.endswith("mlp/wi_up"))
    return path, {
        "param": tree_paths(ctrl.params)[path],
        "mu": tree_paths(ctrl.opt_state["mu"])[path],
        "nu": tree_paths(ctrl.opt_state["nu"])[path],
    }


def phase_dataplane(ctrl, rows_2d: int):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ref
    from repro.reshard import executors as ex

    path, leaves = _leaf_and_moments(ctrl)
    rng = np.random.default_rng(SEED)
    for kind, leaf in leaves.items():
        for view, x in (("stacked", leaf), ("layer0", leaf[0])):
            R = x.shape[0]
            n = 3 if view == "stacked" else rows_2d
            if n > R:
                n = R
            starts = jnp.asarray(np.sort(rng.choice(R, n, replace=False)), jnp.int32)
            sh = x.sharding
            dst = jnp.zeros_like(x) + 1.0  # a destination unlike the source
            pack = ex._pack_fn(sh)
            check(_has_kernel(pack.lower(x, starts).compile()), f"B: pack {view} has no kernel")
            buf = pack(x, starts)
            scat = ex._scatter_fn(sh)
            check(
                _has_kernel(scat.lower(dst, buf, starts).compile()),
                f"B: scatter {view} has no kernel",
            )
            got = scat(jnp.copy(dst), buf, starts)
            want = ref.scatter_rows_ref(dst, ref.pack_rows_ref(x, starts, 1), starts, 1)
            check(_same_bytes(buf, ref.pack_rows_ref(x, starts, 1)), f"B: pack {kind}/{view}")
            check(_same_bytes(got, want), f"B: pack->scatter {kind}/{view}")
            rel = ex._relayout_fn(sh)
            check(
                _has_kernel(rel.lower(dst, x, starts).compile()),
                f"B: relayout {view} has no kernel",
            )
            got = rel(jnp.copy(dst), x, starts)
            check(
                _same_bytes(got, ref.relayout_rows_ref(dst, x, starts, 1)),
                f"B: relayout {kind}/{view}",
            )
            log(f"B: {path} {kind} {view} {tuple(x.shape)}: {n} rows pack->scatter "
                "and relayout byte-equal to ref")
            if view != "layer0":
                continue
            for fmt in ("int8", "fp8_e4m3"):
                packq = ex._packq_fn(sh, fmt)
                check(_has_kernel(packq.lower(x, starts).compile()), f"B: {fmt} pack has no kernel")
                q, scales = packq(x, starts)
                q_r, s_r = ref.pack_quant_rows_ref(x, starts, 1, fmt)
                check(_same_bytes(q, q_r), f"B: {fmt} payload {kind}")
                check(_same_bytes(scales, s_r), f"B: {fmt} scales {kind}")
                deq = ex._dequant_scatter_fn(sh)
                check(
                    _has_kernel(deq.lower(dst, q, scales, starts).compile()),
                    f"B: {fmt} dequant-scatter has no kernel",
                )
                got = deq(jnp.copy(dst), q, scales, starts)
                want = ref.dequant_scatter_rows_ref(dst, q_r, s_r, starts, 1)
                check(_same_bytes(got, want), f"B: {fmt} dequant-scatter {kind}")
                log(f"B: {kind} layer0 {fmt} pack/dequant-scatter equal to the oracle")
    jax.block_until_ready(leaves)
    _no_fallbacks("B")


# ---------------------------------------------------------------------------
# four chips: live grow (stream) and shrink (stop-copy)
# ---------------------------------------------------------------------------


class StateCheck:
    """commit observer: the moved state equals the cut it came from."""

    def __init__(self):
        self.commits = []

    def __call__(self, before, after):
        import jax
        import numpy as np

        t0 = time.perf_counter()
        b_leaves = jax.tree_util.tree_leaves(before)
        a_leaves = jax.tree_util.tree_leaves(after)
        check(len(b_leaves) == len(a_leaves), "resize: state trees differ")
        nbytes = 0
        for b, a in zip(b_leaves, a_leaves):
            hb, ha = np.asarray(jax.device_get(b)), np.asarray(jax.device_get(a))
            check(_same_bytes(hb, ha), f"resize: a {hb.shape} leaf changed across the switch")
            nbytes += hb.nbytes
        self.commits.append(nbytes)
        log(f"resize: {len(b_leaves)} leaves, {nbytes / 2**30:.2f} GiB byte-equal "
            f"across the switch ({time.perf_counter() - t0:.1f}s host check)")


def _until_commit(ctrl, n_records: int, max_steps: int) -> list[float]:
    losses = []
    while len(ctrl.records) < n_records:
        check(len(losses) < max_steps, "resize: no commit within the step budget")
        losses += ctrl.train_steps(1)
    return losses


def phase_resize(cfg, seq: int, batch: int, devices, steps_between: int = 2):
    from repro.configs.base import ParallelConfig

    log(f"R: {cfg.name} layers={cfg.num_layers} batch={batch}x{seq} on {len(devices)} chips")
    ctrl = make_controller(cfg, 2, seq, batch, devices, overlap="stream", stream_k=4,
                           sync_compile=True)
    check(_has_kernel(ctrl.world.step_fn), "R: no tpu_custom_call in the tp2 step")
    observer = StateCheck()
    ctrl.commit_observer = observer
    losses = ctrl.train_steps(1)
    ctrl.request_resize(ParallelConfig(dp=1, tp=4))
    ctrl.wait_shadow_ready()
    losses += _until_commit(ctrl, 1, max_steps=12)
    grow_at = len(losses)
    check(_has_kernel(ctrl.world.step_fn), "R: no tpu_custom_call in the tp4 step")
    losses += ctrl.train_steps(steps_between)
    ctrl.request_resize(ParallelConfig(dp=1, tp=2), overlap="stop_copy")
    ctrl.wait_shadow_ready()
    losses += _until_commit(ctrl, 2, max_steps=4)
    shrink_at = len(losses)
    losses += ctrl.train_steps(steps_between)
    for rec in ctrl.records:
        log(f"R: {rec.src} -> {rec.dst} mode={rec.mode} outcome={rec.outcome} "
            f"moved={rec.moved_bytes / 2**20:.1f}MiB prepare={rec.prepare_s:.1f}s")
        check(rec.outcome == "committed", f"R: resize ended {rec.outcome}")
        check(rec.moved_bytes > 0, "R: resize moved no bytes")
    check(ctrl.records[0].mode == "live_overlap", "R: grow did not stream")
    check(ctrl.records[1].mode == "live", "R: shrink was not a live stop-copy")
    check(len(observer.commits) == 2, "R: a commit skipped the state check")
    check(not ctrl.swallowed_errors, f"R: swallowed errors {ctrl.swallowed_errors}")
    check(ctrl.world.parallel == ParallelConfig(dp=1, tp=2), "R: did not end on tp2")
    log(f"R: peak_bytes_in_use {peak_hbm(devices) / 2**30:.2f} GiB (max over chips)")
    n = len(losses)
    del ctrl
    gc.collect()

    base = make_controller(cfg, 2, seq, batch, devices)
    base_losses = base.train_steps(n)
    del base
    gc.collect()
    log(f"R: losses       {[round(x, 4) for x in losses]}")
    log(f"R: never-resized {[round(x, 4) for x in base_losses]}")
    check(all(math.isfinite(x) for x in losses), "R: non-finite loss")
    for i in range(grow_at - 1, n):
        d = abs(losses[i] - base_losses[i])
        check(d <= LOSS_TOL, f"R: step {i} loss off by {d:.4f} (> {LOSS_TOL}) "
              f"after a switch (grow at {grow_at}, shrink at {shrink_at})")
    worst = max(abs(a - b) for a, b in zip(losses, base_losses))
    log(f"R: max |loss - never-resized| = {worst:.5f} (tolerance {LOSS_TOL})")
    _no_fallbacks("R")


# ---------------------------------------------------------------------------


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args()
    if os.environ.get("REPRO_FORCE_PALLAS_INTERPRET"):
        raise SystemExit("[smoke] FAIL: REPRO_FORCE_PALLAS_INTERPRET is set; "
                         "the smoke measures the compiled kernels only")
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"[smoke] FAIL: no TPU (JAX platform {dev.platform!r})")
    check(len(devices) >= args.chips, f"need {args.chips} chips, JAX sees {len(devices)}")
    sys.path.insert(0, os.path.join(HERE, "src"))
    from repro.utils.compile_cache import enable_compile_cache

    log(f"device {dev.device_kind} x{len(devices)}; compile cache {enable_compile_cache()}")
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_resize(qwen3(8), seq=1024, batch=4, devices=devices[:4])
    else:
        ctrl = phase_train(qwen3(4), seq=1024, batch=4, steps=5, devices=devices)
        phase_dataplane(ctrl, rows_2d=512)
    log(f"total {time.perf_counter() - t0:.1f}s")
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices)},
    }))


if __name__ == "__main__":
    main()

"""The phase readers (bench/phases.py and the five metrics that use it) on a
small synthetic trace: chip 0's time by the program's named scopes, the
busy-time identity, the host spans of the window's steps, and the four
metrics that were there before reading the same on a trace of a program
with and without the scopes and spans."""

import re
import sys
import threading
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
from bench import harness, model, phases, trace  # noqa: E402
from bench.drivers.common import Window  # noqa: E402
from bench.metrics import (  # noqa: E402
    attn_bwd_ms, bwd_ms, device_idle_pct, feed_ms, flash_fwd_roofline, fwd_ms,
    kernel_fallbacks, opt_ms, step_mfu_pct,
)

CELL = "qwen3-1.7b.steady-4k"
STEP = "jit(train_step)/jit(main)"
FWD = f"{STEP}/jvp(liver/fwd)/while/body/closed_call"
BWD = f"{STEP}/transpose(jvp(liver/fwd))"
# (HLO text, op_name, [(start ns, duration ns)]): a step's ops on chip 0
OPS = [
    ("%while.3 = (s32[]) while((s32[]) %t), body=%b", f"{STEP}/jvp(liver/fwd)/while",
     [(100, 300)]),
    ("%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kLoop", f"{FWD}/mlp/dot_general",
     [(120, 80), (950, 100)]),
    ('%closed_call.2 = bf16[1,16,4096,128]{3,2,1,0} custom-call(bf16[1,16,4096,128]{3,2,1,0} %q), '
     'custom_call_target="tpu_custom_call"', f"{FWD}/attn/pallas_call", [(250, 100)]),
    ("%fusion.4 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kOutput",
     f"{BWD}/while/body/closed_call/checkpoint/rematted_computation/mlp/dot_general", [(400, 50)]),
    ("%fusion.5 = f32[8]{0} fusion(f32[8]{0} %p), kind=kOutput",
     f"{BWD}/while/body/closed_call/checkpoint/attn/flash_bwd/transpose(jvp())/dot_general",
     [(450, 70)]),
    ("%fusion.6 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", f"{BWD}/head/dot_general",
     [(520, 40)]),
    ("%fusion.7 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", f"{STEP}/liver/opt/mul",
     [(600, 100)]),
    ("%copy.8 = f32[8]{0} copy(f32[8]{0} %p)", None, [(700, 20)]),
]
# window 0..1000 ns: busy 100..560, 600..720 and 950..1000 (the last op is cut)
BUSY_NS = 630
# self ns: the loop 300 - 80 - 100, the rest as they run
SELF_NS = {"fwd": 120 + 80 + 50 + 100, "remat": 50, "bwd": 70 + 40, "opt": 100,
           "unattributed": 20}
ATTN_BWD_NS = 70


def _event(meta: int, start_ns: int, dur_ns: int) -> str:
    return f"events {{ metadata_id: {meta} offset_ps: {start_ns * 1000} duration_ps: {dur_ns * 1000} }}"


def _line(lid: int, name: str, events: list[tuple[str, int, int]], names: dict) -> str:
    for n, _, _ in events:
        names.setdefault(n, len(names) + 1)
    body = " ".join(_event(names[n], a, d) for n, a, d in events)
    return f'lines {{ id: {lid} name: "{name}" timestamp_ns: 0 {body} }}'


def _plane(pid: int, name: str, lines: list[tuple[str, list]]) -> str:
    names: dict[str, int] = {}
    text = " ".join(_line(pid * 10 + i, ln, evs, names) for i, (ln, evs) in enumerate(lines))
    meta = " ".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
        for n, i in ((n.replace('"', '\\"'), i) for n, i in names.items()))
    return f'planes {{ id: {pid} name: "{name}" {text} {meta} }}'


def _profile(scoped: bool):
    """The benchmark's host spans and chip 0's ops; with ``scoped``, as the
    program with named scopes and ``liver/*`` spans traces: op names carry
    their ``op_name`` and the host has step spans on the main thread and a
    world build on another."""
    from jax.profiler import ProfileData

    main = [("window", 0, 1000), ("train_step", 100, 460), ("train_step", 560, 420)]
    other = []
    if scoped:
        for t, dur in ((100, 460), (560, 420)):
            main += [("liver/step", t, dur), ("liver/batch", t, 5), ("liver/dispatch", t + 5, 20),
                     ("liver/wait", t + 25, dur - 30), ("liver/boundary", t + dur - 5, 5)]
        other = [("liver/prepare", 50, 900), ("liver/world/compile", 60, 800)]
    main.sort(key=lambda e: (e[1], -e[2]))
    dev = []
    for hlo, op_name, runs in OPS:
        name = f'{hlo}, metadata={{op_name="{op_name}"}}' if scoped and op_name else hlo
        dev += [(name, a, d) for a, d in runs]
    dev.sort(key=lambda e: (e[1], -e[2]))
    host = _plane(1, "/host:CPU", [("python3", main)] + ([("shadow", other)] if other else []))
    chip = _plane(2, "/device:TPU:0", [("XLA Ops", dev)])
    return ProfileData.from_text_proto("\n".join([host, chip]))


def _run(summary, steps: int = 2):
    wl = model.load("workloads", CELL)
    conf = model.load("configs", wl["config"])
    ctx = harness.RunContext(name=CELL, workload=wl, conf=conf, cfg=None, seed=1, seconds=1.0,
                             trace=True, devices=[], chips=1, t0=0.0)
    out = Window(step_s=[0.4] * steps, window_s=1.0, tokens=4096 * steps)
    return harness.Run(ctx, out, summary, harness.peaks_for("TPU v5 lite"))


def test_the_four_metrics_read_the_same_with_and_without_the_scopes():
    before, after = (trace.summarize(_profile(s)) for s in (False, True))
    assert after.devices[0].busy_s == before.devices[0].busy_s == pytest.approx(BUSY_NS * 1e-9)
    # idle gaps are still named by the benchmark's own spans
    assert after.devices[0].gaps == before.devices[0].gaps
    for reader in (device_idle_pct, step_mfu_pct, flash_fwd_roofline, kernel_fallbacks):
        a, b = reader.read(_run(after)), reader.read(_run(before))
        assert a is not None and a == b, reader.__name__
    assert device_idle_pct.read(_run(after)) == pytest.approx(37.0)


def test_chip_time_by_phase_adds_up_to_busy_time():
    run = _run(trace.summarize(_profile(True)))
    split = phases.device_split(run)
    assert split.steps == 2
    assert split.seconds == pytest.approx({p: ns * 1e-9 for p, ns in SELF_NS.items()})
    assert sum(split.seconds.values()) == pytest.approx(split.busy_s)
    assert split.unattributed_ops == [("copy.8", pytest.approx(20e-9))]
    per_step = lambda ns: 1e3 * ns * 1e-9 / 2  # noqa: E731
    assert fwd_ms.read(run) == pytest.approx(per_step(SELF_NS["fwd"] + SELF_NS["remat"]))
    assert bwd_ms.read(run) == pytest.approx(per_step(SELF_NS["bwd"]))
    assert opt_ms.read(run) == pytest.approx(per_step(SELF_NS["opt"]))
    assert attn_bwd_ms.read(run) == pytest.approx(per_step(ATTN_BWD_NS))
    # the identity, per step: forward (with remat) + backward + optimizer + unattributed
    unattributed = split.per_step_ms("unattributed")
    total = fwd_ms.read(run) + bwd_ms.read(run) + opt_ms.read(run) + unattributed
    assert total == pytest.approx(1e3 * split.busy_s / 2)


def _module(ops=OPS) -> str:
    """The compiled module's text of ``ops``, as ``Compiled.as_text`` prints
    it: operands without their shapes, and each op's ``op_name``."""
    lines = []
    for hlo, op_name, _ in ops:
        line = re.sub(r"[a-z0-9]+\[[\d,]*\](\{[^}]*\})? (?=%)", "", hlo)
        lines.append(f'  {line}, metadata={{op_name="{op_name}" stack_frame_id=3}}'
                     if op_name else f"  {line}")
    lines[-1] = lines[-1].replace("  %", "  ROOT %", 1)
    return "ENTRY %main.9 (p: f32[8]) -> f32[8] {\n" + "\n".join(lines) + "\n}\n"


def test_op_names_come_from_the_program_where_the_trace_has_none(monkeypatch):
    run = _run(trace.summarize(_profile(False)))
    monkeypatch.setattr(phases, "program_op_names", lambda r: phases.module_ops(_module()))
    split = phases.device_split(run)
    assert split.seconds == pytest.approx({p: ns * 1e-9 for p, ns in SELF_NS.items()})


@pytest.mark.parametrize("change", [
    ("bf16[8]{0} fusion(bf16[8]{0} %p)", "bf16[16]{0} fusion(bf16[16]{0} %p)"),  # shape
    ("bf16[8]{0} fusion(bf16[8]{0} %p)", "bf16[8]{0} fusion(bf16[8]{0} %q)"),  # operand
    ("bf16[8]{0} fusion(bf16[8]{0} %p)", "bf16[8]{0} copy(bf16[8]{0} %p)"),  # opcode
])
def test_a_rebuilt_step_that_differs_from_the_traced_one_reads_nothing(monkeypatch, change):
    """Where the rebuilt program is not the traced one, its instruction
    names belong to other ops: nothing is read rather than misattributed."""
    run = _run(trace.summarize(_profile(False)))
    rebuilt = [(hlo.replace(*change) if hlo.startswith("%fusion.1 ") else hlo, op, runs)
               for hlo, op, runs in OPS]
    monkeypatch.setattr(phases, "program_op_names", lambda r: phases.module_ops(_module(rebuilt)))
    for reader in (fwd_ms, bwd_ms, opt_ms, attn_bwd_ms):
        assert reader.read(run) is None


def test_a_traced_instruction_and_its_module_line_have_one_signature():
    traced = ("%fusion.476 = f32[1,4096,16,128]{3,1,2,0:T(8,128)S(1)} fusion(bf16[4096,16,128]"
              "{2,0,1:T(8,128)(2,1)S(1)} %get-tuple-element.1381, f32[128]{0:T(128)S(1)} "
              "%fusion.3), kind=kOutput, calls=%fused_computation.400")
    module = ("  ROOT %fusion.476 = f32[1,4096,16,128]{3,1,2,0:T(8,128)S(1)} "
              "fusion(%get-tuple-element.1381, /*index=1*/%fusion.3), kind=kOutput, "
              'calls=%fused_computation.400, metadata={op_name="jit(f)/liver/fwd/attn/dot"}')
    assert phases.module_ops(module) == {"fusion.476": (phases.signature(traced),
                                                         "jit(f)/liver/fwd/attn/dot")}
    assert phases.signature(traced) == ("fusion.476", "f32[1,4096,16,128]", "fusion",
                                        ("get-tuple-element.1381", "fusion.3"))
    tup = ("%split.1 = (bf16[2]{0}, bf16[2]{0}) fusion((s32[]{:T(128)}, f32[4]{0}) %t), "
           "kind=kLoop")
    assert phases.signature(tup) == ("split.1", "(bf16[2], bf16[2])", "fusion", ("t",))
    assert phases.signature("%f.2 = f32[64]{0:T(128)} fusion(), kind=kLoop")[3] == ("",)


def test_chip_time_by_model_layer():
    run = _run(trace.summarize(_profile(True)))
    split = phases.device_split(run)
    per_layer = {layer: sum(by.values()) for layer, by in split.layers.items()}
    assert per_layer == pytest.approx({"mlp": (80 + 50 + 50) * 1e-9, "attn": (100 + 70) * 1e-9,
                                       "head": 40e-9, "other": (120 + 100 + 20) * 1e-9})
    assert split.layers["mlp"]["remat"] == pytest.approx(50e-9)
    assert split.layers["attn"]["bwd"] == pytest.approx(70e-9)
    assert sum(per_layer.values()) == pytest.approx(split.busy_s)


def test_a_program_without_the_scopes_reads_nothing(monkeypatch):
    run = _run(trace.summarize(_profile(False)))
    monkeypatch.setattr(phases, "program_op_names", lambda r: phases.module_ops(
        _module([(hlo, "jit(f)/dot" if op else None, runs) for hlo, op, runs in OPS])))
    for reader in (fwd_ms, bwd_ms, opt_ms, attn_bwd_ms):
        assert reader.read(run) is None
    monkeypatch.setattr(phases, "program_op_names", lambda r: 1 / 0)
    assert fwd_ms.read(_run(trace.summarize(_profile(False)))) is None


def test_feed_reads_the_window_steps_of_the_readers_thread():
    from repro.utils.spans import span

    def steps(n, feed_s):
        out = []
        for i in range(n):
            with span("liver/step", step=i):
                with span("liver/batch", step=i) as b:
                    time.sleep(feed_s(i))
                with span("liver/wait", step=i):
                    pass
            out.append(b.seconds)
        return out

    steps(2, lambda i: 0.02)  # set-up steps before the window
    mine = steps(3, lambda i: 0.001 * (i + 1))
    th = threading.Thread(target=steps, args=(2, lambda i: 0.05))  # another thread's
    th.start()
    th.join()
    run = SimpleNamespace(out=SimpleNamespace(step_s=[0.1] * 3))
    assert feed_ms.read(run) == pytest.approx(1e3 * sorted(mine)[1])
    assert feed_ms.read(SimpleNamespace(out=SimpleNamespace(step_s=[0.1] * 9999))) is None

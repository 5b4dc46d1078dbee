"""The train step's phases, as the program names them.

Device side. The program names the halves of its train step with
``jax.named_scope``: ``liver/fwd`` around the loss, whose gradient's ops
carry ``transpose(jvp(liver/fwd))`` and whose remat recompute carries
``transpose(jvp(liver/fwd))/.../rematted_computation``; ``liver/opt``
around the optimizer update; ``flash_bwd`` inside the flash kernel's
backward rule. XLA keeps each op's scope path as its ``op_name``. A trace
event is named by its HLO instruction's text; where that text holds no
``op_name``, the instruction's name is looked up in the compiled step's
HLO text, which the program builds again for the cell's layout (a read of
the compilation cache: the same program compiles to the same names). Each
traced instruction must match the rebuilt one in its result shape, opcode
and operands, else nothing is read: a rebuilt program that differs from the
traced one would give its names to other ops. :func:`device_split` sums
chip 0's self time in the window by phase, and logs it by model layer
(the model's ``embed``, ``attn``, ``mlp`` and ``head`` scopes).

Host side. The program's ``liver/*`` spans (``repro.utils.spans``) time
each step's batch feed, dispatch, wait and boundary work, and the world's
build; :func:`window_steps` finds the window's steps among them.

A program without the scopes or the spans (an older commit) reads as
None: no metric, no error.
"""

from __future__ import annotations

import inspect
import re
import statistics
import threading
from dataclasses import dataclass, field

from bench.harness import log

FWD = "liver/fwd"
BWD = f"transpose(jvp({FWD}))"
OPT = "liver/opt"
REMAT = "rematted_computation"
ATTN_BWD = "flash_bwd"
PHASES = ("fwd", "remat", "bwd", "opt", "unattributed")
LAYERS = ("embed", "attn", "mlp", "head")  # the model's scopes
OP_NAME = re.compile(r'op_name="([^"]*)"')
# one instruction of an HLO module's text
HLO_LINE = re.compile(r"^\s*(?:ROOT )?(%?[\w.-]+ = .*)$", re.M)
LAYOUT = re.compile(r"\{[^{}]*\}")
COMMENT = re.compile(r"/\*.*?\*/")  # the module text's /*index=5*/ marks
WORLD_PHASES = ("liver/world/mesh", "liver/world/lower", "liver/world/compile",
                "liver/world/update_compile")


def phase_of(op_name: str) -> str:
    """``fwd``, ``remat`` (forward recomputed in the backward), ``bwd``,
    ``opt`` or ``unattributed``."""
    if OPT in op_name:
        return "opt"
    if BWD in op_name:
        return "remat" if REMAT in op_name else "bwd"
    if FWD in op_name:
        return "fwd"
    return "unattributed"


def layer_of(op_name: str) -> str:
    """The model scope (``LAYERS``) on ``op_name``'s path, else ``other``."""
    path = op_name.split("/")
    return next((scope for scope in LAYERS if scope in path), "other")


@dataclass
class DeviceSplit:
    """Chip 0's self seconds in the window, by phase."""

    steps: int
    busy_s: float
    seconds: dict[str, float] = field(default_factory=dict)  # phase -> s
    attn_bwd_s: float = 0.0
    unattributed_ops: list[tuple[str, float]] = field(default_factory=list)  # longest first
    layers: dict[str, dict[str, float]] = field(default_factory=dict)  # layer -> phase -> s

    def per_step_ms(self, *phases: str) -> float:
        return 1e3 * sum(self.seconds[p] for p in phases) / self.steps


_last: tuple = (None, None)  # (run, split): the readers of one run share one split


def device_split(run) -> DeviceSplit | None:
    global _last
    if _last[0] is run:
        return _last[1]
    split = None
    try:
        split = _device_split(run)
    except Exception as e:  # noqa: BLE001 - a metric that cannot be read is left out
        log(f"phases: no device split: {e!r}")
    _last = (run, split)
    return split


def _device_split(run) -> DeviceSplit | None:
    if run.trace is None or 0 not in run.trace.devices or not run.out.step_s:
        return None
    ops = run.trace.devices[0].ops
    names = op_names(ops, lambda: program_op_names(run))
    if not any(FWD in n or OPT in n for n in names.values()):
        return None
    split = DeviceSplit(steps=len(run.out.step_s), busy_s=run.trace.devices[0].busy_s,
                        seconds=dict.fromkeys(PHASES, 0.0))
    loose: dict[str, float] = {}
    for key, (secs, _) in ops.items():
        name = names.get(key, "")
        phase = phase_of(name)
        split.seconds[phase] += secs
        by_phase = split.layers.setdefault(layer_of(name), dict.fromkeys(PHASES, 0.0))
        by_phase[phase] += secs
        if ATTN_BWD in name:
            split.attn_bwd_s += secs
        if phase == "unattributed":
            short = instruction(key)
            loose[short] = loose.get(short, 0.0) + secs
    split.unattributed_ops = sorted(loose.items(), key=lambda kv: -kv[1])[:5]
    total = sum(split.seconds.values())
    log("phases: chip 0 per step (ms): " + ", ".join(
        f"{p} {split.per_step_ms(p):.3f}" for p in split.seconds)
        + f"; attention backward {1e3 * split.attn_bwd_s / split.steps:.3f}"
        + f"; self time {1e3 * total / split.steps:.3f} against busy "
        f"{1e3 * split.busy_s / split.steps:.3f}; unattributed "
        f"{100 * split.seconds['unattributed'] / max(total, 1e-30):.2f}% of self time: "
        + ", ".join(f"{n} {1e3 * s / split.steps:.3f}" for n, s in split.unattributed_ops))
    ms = lambda s: f"{1e3 * s / split.steps:.3f}"  # noqa: E731
    log("phases: chip 0 per step by layer (ms): " + "; ".join(
        f"{layer} {ms(sum(by.values()))} ("
        + ", ".join(f"{p} {ms(s)}" for p, s in by.items() if s) + ")"
        for layer in (*LAYERS, "other") if (by := split.layers.get(layer))))
    return split


def instruction(hlo: str) -> str:
    """``%fusion.12 = ...`` -> ``fusion.12``."""
    return hlo.partition(" = ")[0].strip().lstrip("%")


def op_names(ops: dict, program) -> dict[str, str]:
    """Trace op key -> ``op_name``: from the key's own text where it holds
    one, else from ``program()`` (instruction name -> (its
    :func:`signature`, its ``op_name``)). Raises ``ValueError`` where a
    traced instruction differs from the program's of that name."""
    found = {k: m.group(1) for k in ops if (m := OP_NAME.search(k))}
    if found:
        return found
    lines = program()
    names, differ = {}, []
    for k in ops:
        line = lines.get(instruction(k))
        if line is None:
            continue  # not the step's: left unattributed
        if line[0] != signature(k):
            differ.append(f"{line[0]} traced as {signature(k)}")
        names[k] = line[1]
    if differ:
        raise ValueError(f"{len(differ)} of {len(ops)} traced instructions differ from the "
                         "rebuilt step's: " + "; ".join(differ[:3]))
    return names


def signature(hlo: str) -> tuple:
    """An instruction's name, result shape (without layout), opcode and
    operand names: what the trace's text and the module's text both print,
    whatever else each prints of it."""
    name, _, rest = hlo.strip().partition(" = ")
    rest = COMMENT.sub("", rest).replace("%", "")
    while (bare := LAYOUT.sub("", rest)) != rest:
        rest = bare
    depth, start = 0, None
    for i, c in enumerate(rest):
        if c == "(" and depth == 0 and i and (rest[i - 1].isalnum() or rest[i - 1] in "-_"):
            start = i
            break
        depth += (c in "([") - (c in ")]")
    if start is None:
        return (name.lstrip("%"), rest)
    opcode_at = rest.rfind(" ", 0, start) + 1
    depth, items, item = 0, [], ""
    for c in rest[start + 1:]:
        if depth == 0 and c in ",)":
            items.append(item.split()[-1] if item.split() else "")
            item = ""
            if c == ")":
                break
            continue
        depth += (c in "([") - (c in ")]")
        item += c
    return (name.lstrip("%"), rest[:opcode_at].strip(), rest[opcode_at:start], tuple(items))


def program_op_names(run) -> dict[str, tuple[tuple, str]]:
    """Instruction name -> (:func:`signature`, ``op_name``) of the train
    step the cell runs, compiled again through the program's
    ``build_train_world`` with the arguments ``LiveRController`` gives it
    for the cell's layout (its defaults, as the steady driver leaves them)."""
    from repro.configs.base import ParallelConfig
    from repro.core.controller import LiveRController
    from repro.core.shadow import build_train_world
    from repro.optim import AdamWConfig

    ctx, wl = run.ctx, run.ctx.workload
    if "parallel" not in wl:
        return {}
    default = {k: p.default for k, p in inspect.signature(LiveRController).parameters.items()}
    world = build_train_world(ctx.cfg, ParallelConfig(**wl["parallel"]),
                              AdamWConfig(**wl["optimizer"]), wl["global_batch"],
                              wl["seq_len"], microbatches=default["microbatches"],
                              devices=ctx.devices[: ctx.chips],
                              compression=default["compression"],
                              hint_version=default["hint_version"],
                              split_step=default["world_pool"] is not None)
    text = world.step_fn.as_text()
    world.release()
    return module_ops(text)


def module_ops(text: str) -> dict[str, tuple[tuple, str]]:
    """Instruction name -> (:func:`signature`, ``op_name``) of an HLO
    module's text."""
    out = {}
    for line in HLO_LINE.findall(text):
        op_name = OP_NAME.search(line)
        out[instruction(line)] = (signature(line), op_name.group(1) if op_name else "")
    return out


# ---------------------------------------------------------------------------
# host spans
# ---------------------------------------------------------------------------


def recent_spans() -> list[tuple]:
    try:
        from repro.utils import spans
    except ImportError:
        return []
    return spans.recent()


def window_steps(run, recent: list[tuple]) -> list[tuple]:
    """The ``liver/step`` spans of the window: the last as many steps as the
    window timed, on the thread that held the window (the readers' own)."""
    n = len(run.out.step_s)
    thread = threading.get_ident()
    steps = [s for s in recent if s[0] == "liver/step" and s[4] == thread]
    return steps[-n:] if n and len(steps) >= n else []


def step_phases(step: tuple, recent: list[tuple]) -> dict[str, float]:
    """Seconds of each ``liver/*`` child span inside ``step``."""
    out: dict[str, float] = {}
    for name, t0, t1, _, thread in recent:
        if thread == step[4] and step[1] <= t0 and t1 <= step[2] and name != "liver/step":
            out[name] = out.get(name, 0.0) + t1 - t0
    return out


def feed_ms(run) -> float | None:
    """Median over the window's steps of the ``liver/batch`` time."""
    recent = recent_spans()
    steps = window_steps(run, recent)
    if not steps:
        return None
    feeds = [step_phases(s, recent).get("liver/batch", 0.0) for s in steps]
    log_setup(recent, steps[0][1])
    return 1e3 * statistics.median(feeds)


def log_setup(recent: list[tuple], first: float) -> None:
    """Log set-up's split: the world's build and the steps before ``first``,
    the window's first step. (A slow step's split is the program's own
    warning, which untraced runs log too.)"""
    world = {p: sum(t1 - t0 for n, t0, t1, _, _ in recent if n == p and t1 <= first)
             for p in WORLD_PHASES}
    before = [s for s in recent if s[0] == "liver/step" and s[2] <= first]
    log("phases: set-up (s): " + ", ".join(f"{p} {v:.3f}" for p, v in world.items())
        + f"; {len(before)} steps before the window in "
        f"{sum(t1 - t0 for _, t0, t1, _, _ in before):.3f}")

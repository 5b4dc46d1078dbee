"""Reduction from a profiler trace (``.xplane.pb``) to device busy time,
per-op device time and the idle gaps, each gap named by the host span the
benchmark had open at the time.

Device planes are ``/device:<kind>:<n>``; their op line is ``XLA Ops``,
whose events are named by the HLO instruction's text and nest (a ``while``
event spans its body's ops), so an op's time is its self time: its span
less the spans of the ops inside it. The window is the host span
``window`` that the harness opens around the timed steps; only device time
inside it counts.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:(\d+)$")
OPCODE = re.compile(r"[})\]] ([a-z][\w-]*)\(")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "window"
# the benchmark's own host spans (drivers and harness), by which idle gaps are named
HOST_SPANS = ("train_step", "request_resize", WINDOW_SPAN)
TOP = 10


@dataclass
class DeviceTrace:
    busy_s: float
    ops: dict[str, list[float]]  # HLO text -> [self seconds inside the window, calls]
    gaps: list[tuple[str, float]]  # (host span open at the gap, seconds), longest first


@dataclass
class TraceSummary:
    window_s: float
    devices: dict[int, DeviceTrace] = field(default_factory=dict)

    def op_time(self, device: int, *patterns: str) -> tuple[float, int]:
        """Summed self seconds and calls of the ops on ``device`` whose HLO
        text holds every one of ``patterns``."""
        secs, calls = 0.0, 0
        for name, (s, n) in self.devices[device].ops.items():
            if all(p in name for p in patterns):
                secs, calls = secs + s, calls + int(n)
        return secs, calls

    def breakdown(self, device: int = 0) -> dict:
        dev = self.devices[device]
        ops = sorted(dev.ops.items(), key=lambda kv: -kv[1][0])[:TOP]
        return {"device_ops": [[short_name(name), s] for name, (s, _) in ops],
                "idle_gaps": [[label, s] for label, s in dev.gaps[:TOP]]}


def short_name(hlo: str) -> str:
    """``%fusion.12 = bf16[..] fusion(...)`` -> ``fusion.12 fusion``."""
    head, _, rest = hlo.partition(" = ")
    m = OPCODE.search(rest)
    return f"{head.lstrip('%')} {m.group(1)}" if m else head.lstrip("%")


def _self_times(events: list[tuple[float, float, str]]):
    """(name, self ns) of nested events sorted by start: a parent's span
    less the spans of the events it contains."""
    stack: list[list] = []  # [end, name, self]
    for a, b, name in events:
        while stack and stack[-1][0] <= a:
            _, n, own = stack.pop()
            yield n, own
        if stack:
            stack[-1][2] -= b - a
        stack.append([b, name, b - a])
    while stack:
        _, n, own = stack.pop()
        yield n, own


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _label(spans: list[tuple[float, float, str]], t: float) -> str:
    """The innermost benchmark span open at time ``t``."""
    best = None
    for a, b, name in spans:
        if a <= t <= b and (best is None or b - a < best[1] - best[0]):
            best = (a, b, name)
    return best[2] if best else "outside spans"


def summarize(profile) -> TraceSummary:
    """``profile``: a ``jax.profiler.ProfileData``."""
    spans, window = [], None
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in HOST_SPANS:
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name))
                    if ev.name == WINDOW_SPAN:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    w0, w1 = window
    summary = TraceSummary(window_s=(w1 - w0) * 1e-9)
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        ops: dict[str, list[float]] = {}
        events = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                a, b = max(ev.start_ns, w0), min(ev.start_ns + ev.duration_ns, w1)
                if b > a:
                    events.append((a, b, ev.name))
        events.sort(key=lambda e: (e[0], -e[1]))
        for name, own in _self_times(events):
            acc = ops.setdefault(name, [0.0, 0])
            acc[0] += own * 1e-9
            acc[1] += 1
        busy = _union([(a, b) for a, b, _ in events])
        edges = [w0] + [x for ab in busy for x in ab] + [w1]
        gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        gaps.sort(key=lambda ab: ab[0] - ab[1])
        summary.devices[int(m.group(1))] = DeviceTrace(
            busy_s=sum(b - a for a, b in busy) * 1e-9,
            ops=ops,
            gaps=[(_label(spans, (a + b) / 2), (b - a) * 1e-9) for a, b in gaps[:TOP]],
        )
    if not summary.devices:
        raise ValueError("no device plane in the trace")
    return summary


def load(trace_dir: str) -> TraceSummary:
    import glob

    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise ValueError(f"no .xplane.pb under {trace_dir}")
    return summarize(ProfileData.from_file(paths[-1]))

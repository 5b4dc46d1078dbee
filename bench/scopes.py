"""Chip 0's self time under the model's own scopes, and the MoE layers'
routed-rows counter, for the per-layer metrics of the layers that
``bench/phases.py`` does not split out (``moe``, ``short_conv``).

Each traced op is named as ``phases.op_names`` names it (its ``op_name``,
looked up in the rebuilt step); an op is under a scope when the scope's
names follow one another in its path, in the forward, in the remat
recompute and in the backward alike. XLA expands ``jax.lax.ragged_dot``
into instructions named ``ragged-dot-*`` that carry no ``op_name``; the MoE
layer's expert products are the program's only grouped products, so those
count under ``moe/experts``.

The counter is read from the program's ``liver/boundary`` spans, which
carry each step's ``moe_local_rows``. A program without the scopes, the
spans or the counter reads None.
"""

from __future__ import annotations

from bench import phases
from bench.harness import log

GROUPED = "ragged-dot"  # instruction names of XLA's expanded grouped products

_last: tuple = (None, None)  # (run, {op key: op_name}): one rebuild a run


def _names(run) -> dict[str, str] | None:
    global _last
    if _last[0] is run:
        return _last[1]
    names = None
    if run.trace is not None and 0 in run.trace.devices and run.out.step_s:
        try:
            names = phases.op_names(run.trace.devices[0].ops,
                                    lambda: phases.program_op_names(run))
        except ValueError as e:
            log(f"scopes: no op names: {e!r}")
    _last = (run, names)
    return names


def under(op_name: str, instruction: str, scope: tuple[str, ...]) -> bool:
    path = op_name.split("/")
    n = len(scope)
    if any(tuple(path[i:i + n]) == scope for i in range(len(path) - n + 1)):
        return True
    return scope[0] == "moe" and scope[1:] in ((), ("experts",)) and \
        instruction.startswith(GROUPED)


def seconds(run, *scope: str) -> float | None:
    """Chip 0's self seconds in the window under ``scope``, or None where
    nothing ran there."""
    names = _names(run)
    if not names:
        return None
    total = sum(secs for key, (secs, _) in run.trace.devices[0].ops.items()
                if under(names.get(key, ""), phases.instruction(key), scope))
    return total or None


def per_step_ms(run, *scope: str) -> float | None:
    secs = seconds(run, *scope)
    return None if secs is None else 1e3 * secs / len(run.out.step_s)


def window_rows(run) -> int | None:
    """``moe_local_rows`` summed over the window's steps."""
    recent = phases.recent_spans()
    steps = {s[3].get("step") for s in phases.window_steps(run, recent)}
    rows = [ids["moe_local_rows"] for name, _, _, ids, _ in recent
            if name == "liver/boundary" and "moe_local_rows" in ids and ids.get("step") in steps]
    return sum(rows) if steps and len(rows) == len(steps) else None

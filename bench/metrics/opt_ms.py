"""Chip 0's self time per step of the optimizer update: ops under the
program's ``liver/opt`` scope."""

from bench import phases

LAYER = "model step"
UNIT = "ms"
MOVES = "train_tokens_per_s"


def read(run):
    split = phases.device_split(run)
    return split.per_step_ms("opt") if split else None

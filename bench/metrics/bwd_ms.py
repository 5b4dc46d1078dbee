"""Chip 0's self time per step of the backward pass: ops under
``transpose(jvp(liver/fwd))`` other than the remat recompute."""

from bench import phases

LAYER = "model step"
UNIT = "ms"
MOVES = "train_tokens_per_s"


def read(run):
    split = phases.device_split(run)
    return split.per_step_ms("bwd") if split else None

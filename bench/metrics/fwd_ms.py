"""Chip 0's self time per step of the ops under the program's ``liver/fwd``
scope that are not transposed: the forward pass, and the forward that the
backward recomputes under remat (logged apart by ``bench.phases``)."""

from bench import phases

LAYER = "model step"
UNIT = "ms"
MOVES = "train_tokens_per_s"


def read(run):
    split = phases.device_split(run)
    return split.per_step_ms("fwd", "remat") if split else None

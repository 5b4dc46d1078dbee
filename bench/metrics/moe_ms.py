"""Chip 0's self time per step of the ops under the MoE layers' ``moe``
scope: routing, dispatch, the expert products and the combine, in the
forward, the remat recompute and the backward (``bench/scopes.py``)."""

from bench import scopes

LAYER = "model step"
UNIT = "ms"
MOVES = "train_tokens_per_s"


def read(run):
    return scopes.per_step_ms(run, "moe")

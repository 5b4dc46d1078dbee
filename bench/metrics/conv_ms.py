"""Chip 0's self time per step of the ops under the ``short_conv`` scope: the
gated short-convolution layers' norm, projections and convolution, in the
forward, the remat recompute and the backward (``bench/scopes.py``)."""

from bench import scopes

LAYER = "model step"
UNIT = "ms"
MOVES = "train_tokens_per_s"


def read(run):
    return scopes.per_step_ms(run, "short_conv")

"""Chip 0's self time per step of the flash-attention backward: ops under
the ``flash_bwd`` scope of the kernel's backward rule, the Pallas backward
(the lse pass, the dK/dV and dQ kernels, D and the layout transposes;
``flash_attention.BACKWARD``)."""

from bench import phases

LAYER = "kernels"
UNIT = "ms"
MOVES = "train_tokens_per_s"


def read(run):
    split = phases.device_split(run)
    return 1e3 * split.attn_bwd_s / split.steps if split else None

"""Kernel calls that ran their reference instead (``ops.FALLBACKS``) in
every program traced so far in the run."""

LAYER = "kernels"
UNIT = "calls"
MOVES = "train_tokens_per_s"


def read(run):
    from repro.kernels import ops

    return float(sum(ops.FALLBACKS.values()))

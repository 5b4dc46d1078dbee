"""Share of the traced window in which no operation ran on chip 0 (chip 0
belongs to every layout a cell runs)."""

LAYER = "device"
UNIT = "%"
MOVES = "train_tokens_per_s"


def read(run):
    if run.trace is None or 0 not in run.trace.devices:
        return None
    return 100.0 * (1.0 - run.trace.devices[0].busy_s / run.trace.window_s)

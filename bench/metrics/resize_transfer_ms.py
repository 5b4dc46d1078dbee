"""Mean in-pause reshard time (``ReconfigRecord.transfer_s``: the gradient
reshard of the split-step commit) of the window's committed resizes."""

import statistics

LAYER = "data plane"
UNIT = "ms"
MOVES = "resize_pause_ms"


def read(run):
    recs = run.out.records
    return 1e3 * statistics.fmean(r.transfer_s for r in recs) if recs else None

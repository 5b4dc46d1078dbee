"""Mean Prepare time (``ReconfigRecord.prepare_s``) of the window's
committed resizes: shadow world from the warm pool, plus transfer planning."""

import statistics

LAYER = "controller + shadow build"
UNIT = "ms"
MOVES = "resize_s"


def read(run):
    recs = run.out.records
    return 1e3 * statistics.fmean(r.prepare_s for r in recs) if recs else None

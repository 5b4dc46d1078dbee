"""Mean bytes moved per committed resize of the window
(``ReconfigRecord.moved_bytes``: pre-copy, dirty re-sync and gradients)."""

import statistics

LAYER = "plan + stream"
UNIT = "GiB"
MOVES = "resize_s"


def read(run):
    recs = run.out.records
    return statistics.fmean(r.moved_bytes for r in recs) / 2**30 if recs else None

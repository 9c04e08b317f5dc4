"""Median of ``QueryReport.lock_wait_s`` over the answered queries due in
the window: the seconds each query's thread waited to take the request
pipeline's and the scheduler's locks.  Those locks guard their
bookkeeping only and are not held across an engine call, so this reads
waits on shared state; a query that waits for the engine's slots waits in
the batcher, which this does not see.  A program whose reports lack it
reads nothing."""
import numpy as np


def read(run):
    waits = [getattr(r.ticket.report, "lock_wait_s", None)
             for r in run.due() if r.ok and r.ticket.report is not None]
    waits = [w for w in waits if w is not None]
    return float(np.percentile(waits, 50)) if waits else None

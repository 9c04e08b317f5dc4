"""Median of ``QueryReport.lock_wait_s`` over the answered queries due in
the window: the seconds each query's thread waited on the request
pipeline's and the scheduler's dispatch locks, queued behind other
queries' engine batches.  A program whose reports lack it reads
nothing."""
import numpy as np


def read(run):
    waits = [getattr(r.ticket.report, "lock_wait_s", None)
             for r in run.due() if r.ok and r.ticket.report is not None]
    waits = [w for w in waits if w is not None]
    return float(np.percentile(waits, 50)) if waits else None

"""95th percentile of ``QueryTicket.queue_wait_s`` (submit to execution
start, as the serving engine times it) over the queries due in the
window."""
import numpy as np


def read(run):
    waits = [r.ticket.queue_wait_s for r in run.due() if r.ok]
    return float(np.percentile(waits, 95)) if waits else None

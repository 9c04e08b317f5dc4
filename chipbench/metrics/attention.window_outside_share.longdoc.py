"""Share of the KV blocks gathered for sliding-window layers that lay
wholly before the row's window: the continuous batcher's counters
``window_blocks_outside`` over ``window_blocks`` over the window.  The
paged path gathers a row's whole cache and masks the positions outside
the window; a batcher that gathered only the window would read 0.  None
where the program keeps no such counters."""


def read(run):
    if "window_blocks" not in run.backend[1]:
        return None
    gathered = run.delta("backend", "window_blocks")
    if not gathered:
        return None
    return 100.0 * run.delta("backend", "window_blocks_outside") / gathered

"""Host milliseconds per engine step: the wall seconds inside the
continuous batcher's serve loop less those blocked in its readbacks, over
its prefill and decode steps, in the window (engine counters loop_s,
readback_s, prefill_steps, decode_steps).  Where a step reads back before
the next is dispatched, as decode does every step, this is device idle
time.  A program without the counters reads nothing."""


def read(run):
    if "loop_s" not in run.backend[1]:
        return None
    steps = (run.delta("backend", "prefill_steps")
             + run.delta("backend", "decode_steps"))
    if not steps:
        return None
    host = run.delta("backend", "loop_s") - run.delta("backend", "readback_s")
    return 1e3 * host / steps

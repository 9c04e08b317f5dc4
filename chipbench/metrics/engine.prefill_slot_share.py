"""Share of the chunked-prefill steps' slots that held a prefilling
sequence, over the window: engine counters prefill_rows over
prefill_steps x slots.  ``engine.prefill_fill`` over this share is the
share of an occupied slot's chunk that held prompt tokens, so the two
split a step's empty token slots into empty slots and ragged last
chunks.  A program without the counter reads nothing."""


def read(run):
    if "prefill_rows" not in run.backend[1]:
        return None
    steps = run.delta("backend", "prefill_steps")
    if not steps:
        return None
    return 100.0 * run.delta("backend", "prefill_rows") / (steps * run.slots)

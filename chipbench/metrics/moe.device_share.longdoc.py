"""Share of the traced ``_prefill_fn`` device time spent in the routed
experts' grouped matmuls (the Mosaic calls of ``jax.lax.ragged_dot``,
``%ragged-dot-*``), over the step program's device time.  The router's
and the shared expert's operations fuse into ones the trace cannot tie to
them: a TPU trace's operations carry no scope metadata.  None where the
trace holds no grouped matmul."""

STEP = "_prefill_fn"
GROUPED = r"^%ragged-dot[-\w.]* = "


def read(run):
    if run.trace is None:
        return None
    _, total = run.trace.module(STEP)
    seconds = run.trace.op_seconds(GROUPED, STEP)
    if not total or not seconds:
        return None
    return 100.0 * seconds / total

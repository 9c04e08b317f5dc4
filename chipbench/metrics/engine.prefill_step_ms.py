"""Device time of one chunked-prefill step: the traced executions of the
engine's ``_prefill_fn`` program, their device seconds over their count."""


def read(run):
    return run.step_ms("_prefill_fn")

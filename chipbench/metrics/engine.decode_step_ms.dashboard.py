"""Device time of one decode step: the traced executions of the engine's
``_decode_fn`` program, their device seconds over their count."""


def read(run):
    return run.step_ms("_decode_fn")

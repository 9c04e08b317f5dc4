"""Share of each chunked-prefill step's token capacity that held prompt
tokens, over the window: engine counters prefill_tokens over
prefill_steps x slots x prefill chunk."""


def read(run):
    steps = run.delta("backend", "prefill_steps")
    if not steps:
        return None
    tokens = run.delta("backend", "prefill_tokens")
    return 100.0 * tokens / (steps * run.slots * run.prefill_chunk)

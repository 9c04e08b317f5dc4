"""Share of the requests entering the shared pipeline in the window that
were answered by its result cache or by an identical request in flight:
``PipelineStats`` (cache_hits + inflight_hits) over submitted."""


def read(run):
    submitted = run.delta("pipeline", "submitted")
    if not submitted:
        return None
    hits = (run.delta("pipeline", "cache_hits")
            + run.delta("pipeline", "inflight_hits"))
    return 100.0 * hits / submitted

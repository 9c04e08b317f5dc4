"""LLM requests each query dispatched after dedup and cache hits: the
``QueryReport.ai_calls`` of the queries due in the window, summed, over
their count."""


def read(run):
    reports = [r.ticket.report for r in run.due()
               if r.ok and r.ticket.report is not None]
    if not reports:
        return None
    return sum(rep.ai_calls for rep in reports) / len(reports)

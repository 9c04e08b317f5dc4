"""Share of the sequences admitted into the continuous batcher's slots in
the window that joined a step loop beside another caller's sequences:
engine counters joined over admitted.  Here, the queries that rode a
running loop instead of waiting for it to drain.  A program without the
counter reads nothing."""


def read(run):
    if "joined" not in run.backend[1]:
        return None
    admitted = run.delta("backend", "admitted")
    if not admitted:
        return None
    return 100.0 * run.delta("backend", "joined") / admitted

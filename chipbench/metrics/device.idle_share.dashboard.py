"""Share of the traced window in which no operation ran on the device: one
minus the union of the device-op intervals over the window."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share

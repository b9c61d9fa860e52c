"""1 - (union of device-operation intervals / the traced window), from
the profiler trace (``devtrace.reduce``), averaged over the cell's
chips."""


def read(run):
    if run.trace is None:
        return None
    return run.trace["idle_share"]

"""p90 over the window's requests of the time each waited before its
prefill started (admission less due time), in ms, from the program's own
per-request record (``repro.obs.RECORDER.requests``, written where
``Worker.queue_times`` is). A request never admitted waited until the
run's cut, which is its TTFT (it has no token). None for a program
without that record."""

from benchmarks.chip import stats


def read(run):
    try:
        from repro import obs
    except ImportError:
        return None
    admitted = obs.RECORDER.requests
    waits = [admitted[o.rid].wait if o.rid in admitted else o.ttft
             for o in run.outcomes]
    if not waits:
        return None
    return 1e3 * stats.percentile(waits, 90)

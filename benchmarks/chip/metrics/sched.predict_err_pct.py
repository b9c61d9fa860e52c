"""Median over the window's iterations of |measured - predicted| /
measured, in %: how far the cost model that prices the scheduler's
decisions (``Worker.plan_duration``) is from the executor's measured
iteration time. Read from the program's own iteration records
(``repro.obs.RECORDER``), one per iteration in ``run.iterations``. None
for a program without them."""

import statistics


def read(run):
    try:
        from repro import obs
    except ImportError:
        return None
    errs = [100.0 * abs(r.measured - r.predicted) / r.measured
            for r in obs.RECORDER.match(run.iterations) if r.measured > 0]
    if not errs:
        return None
    return statistics.median(errs)

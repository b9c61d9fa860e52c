"""Mean over the window's iterations of the executor's host phases
(prepare + dispatch + readback: everything in ``run_plan`` but the wait
for the device), in ms, host clock. Read from the program's own
iteration records (``repro.obs.RECORDER``), one per iteration in
``run.iterations``. None for a program without them."""


def read(run):
    try:
        from repro import obs
    except ImportError:
        return None
    host = [r.phases.host for r in obs.RECORDER.match(run.iterations)
            if r.phases is not None]
    if not host:
        return None
    return 1e3 * sum(host) / len(host)

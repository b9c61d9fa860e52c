"""Scheduler host time per iteration, in ms: the time inside
``ClusterScheduler.handle`` during the window less the time inside the
backend's ``run_iteration`` and ``on_migrate``, over the iterations that
started in the window. Host clock."""


def read(run):
    if not run.iterations:
        return None
    return 1e3 * (run.handle_s - run.backend_s) / len(run.iterations)

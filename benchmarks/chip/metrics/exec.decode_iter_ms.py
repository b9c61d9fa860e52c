"""Mean host-clock time of ``run_iteration`` for the window's iterations
with no prefill part, in ms. Each ends in ``block_until_ready``."""


def read(run):
    times = [it.seconds for it in run.iterations if it.kind == "decode"]
    if not times:
        return None
    return 1e3 * sum(times) / len(times)

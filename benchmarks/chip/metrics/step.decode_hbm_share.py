"""Useful bytes of the window's pure-decode iterations (weights once,
plus each request's live KV: ``flops.decode_bytes``) over their summed
host-clock time times the chip's peak HBM bandwidth, in %. Empty slots
that the decode step also reads do not count."""

from benchmarks.chip import flops


def read(run):
    its = [it for it in run.iterations if it.kind == "decode"]
    busy = sum(it.seconds for it in its)
    if busy <= 0:
        return None
    moved = sum(flops.decode_bytes(run.spec, it.decode_ctx) for it in its)
    return 100.0 * moved / (busy * run.peaks["hbm_bytes_per_s"])

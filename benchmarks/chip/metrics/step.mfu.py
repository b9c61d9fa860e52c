"""Useful model FLOPs of the window's iterations over their summed
host-clock time times the chip's peak bf16 rate, in %. Useful counts
prompt and output tokens only, attention over the live causal context
(``flops.iteration_flops``); padding and recompute do not count."""

from benchmarks.chip import flops


def read(run):
    busy = sum(it.seconds for it in run.iterations)
    if busy <= 0:
        return None
    work = sum(flops.iteration_flops(run.spec, it.prefill, it.decode_ctx)
               for it in run.iterations)
    return 100.0 * work / (busy * run.peaks["bf16_flops_per_s"])

"""host loop: the program's `metrics/sample` and `health/check` spans (the
phase sampler and the health probe, each every 200 iterations by default)
over the iterations traced. The sampler first waits for the steps still
queued, a whole block in the block cells: that wait is the `metrics/drain`
span, device time of the steps, and is left out here."""

from chipbench import loopspans


def read(ctx):
    return loopspans.ms_per_step(ctx, ("metrics/sample", "health/check"))

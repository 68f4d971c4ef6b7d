"""host loop: the program's `handler/write` spans (a file handler's HDF5
write) over the iterations traced."""

from chipbench import loopspans


def read(ctx):
    return loopspans.ms_per_step(ctx, ("handler/write",))

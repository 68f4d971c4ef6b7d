"""host loop: the program's `handler/pull` spans (`np.asarray` of a due
handler's results) over the iterations traced. Host time: the pull blocks
until the device has produced the results, so it is not idle time of the
device."""

from chipbench import loopspans


def read(ctx):
    return loopspans.ms_per_step(ctx, ("handler/pull",))

"""host loop: the program's `handler/eval` spans (gathering a due handler's
task-program inputs, which scatters the state on the first read after a
step, and launching it; or the op-by-op eager walk after a fallback) over
the iterations traced."""

from chipbench import loopspans


def read(ctx):
    return loopspans.ms_per_step(ctx, ("handler/eval",))

"""host loop: the program's `step/factor` spans, one per LHS
refactorization, per 100 iterations of the window. The inside of
`dt_changes_per_100`, which infers the same from the dt values."""

from chipbench import loopspans


def read(ctx):
    return loopspans.per_100_steps(ctx, "step/factor")

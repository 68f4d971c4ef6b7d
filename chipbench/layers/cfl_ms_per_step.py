"""host loop: the program's `cfl` spans (`CFL.compute_timestep` when its
cadence falls due: the pull of the velocity's grid data and the NumPy
reduction) over the iterations traced. The inside of what the harness's
`chipbench/compute_timestep` span times from outside."""

from chipbench import loopspans


def read(ctx):
    return loopspans.ms_per_step(ctx, ("cfl",))

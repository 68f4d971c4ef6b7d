"""host loop: what launching the step program costs the host. Self time of
the program's `step` and `step_many` spans (the host side of an iteration,
less its `step/factor`, `step/handlers`, `metrics/drain`,
`metrics/sample` and `health/check` children) over the iterations traced.
Host clock around host work; the launch is asynchronous, so this is not
device time."""

from chipbench import loopspans


def read(ctx):
    return loopspans.ms_per_step(ctx, ("step", "step_many"), self_time=True)

"""host loop: how often the dt handed to solver.step differed from the one
before it, per 100 iterations of the window. Each change refactors the
LHS. Counted by the harness from the values compute_timestep returned;
exists only where dt comes from CFL."""


def read(ctx):
    dts = ctx.get("dts") or []
    if ctx.get("dt_mode") != "cfl" or len(dts) < 2:
        return None
    changes = sum(1 for a, b in zip(dts, dts[1:]) if a != b)
    return 100.0 * changes / (len(dts) - 1)

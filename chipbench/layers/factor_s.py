"""pencil solve: the solver's own build-phase clock for `factor`
(`solver.build_phases.record()["factor_sec"]`): the upload of M and L and
the run's first factorization, waited for — chunk by chunk where it is
incremental. Host clock around device work that is blocked on."""


def read(ctx):
    phases = ctx.get("build_phases") or {}
    return phases.get("factor_sec")

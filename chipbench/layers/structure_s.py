"""host assembly: the solver's own build-phase clock for `structure`
(`solver.build_phases.record()["structure_sec"]`): the banded structural
analysis (pattern union, matching, closures); 0 on an assembly-cache hit
and for dense pencils."""


def read(ctx):
    phases = ctx.get("build_phases") or {}
    return phases.get("structure_sec")

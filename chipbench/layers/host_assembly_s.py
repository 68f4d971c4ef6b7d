"""host assembly: the solver's own build-phase clock
(`solver.build_phases.record()["host_assembly_sec"]`), host clock around
host work."""


def read(ctx):
    phases = ctx.get("build_phases") or {}
    return phases.get("host_assembly_sec")

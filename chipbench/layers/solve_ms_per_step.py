"""pencil solve: self time of the device ops whose scope path holds
`dedalus/matsolve/` (matvecs, solves and, where dt moves, factorizations),
over the iterations traced."""

from chipbench.tracered import scope_seconds


def read(ctx):
    r, n = ctx.get("reduced"), ctx.get("iterations")
    if not r or not n:
        return None
    return 1e3 * scope_seconds(r, "dedalus/matsolve/") / n

"""pencil solve, banded: self time of the band matvecs —
`dedalus/matsolve/banded.matvec`, `banded.matvec_pair` (M @ X0, L @ Xi and
the refinement sweep's residual) and `banded.refine` (that sweep's own
arithmetic) — over the iterations traced: the streaming part."""

from chipbench.tracered import scope_seconds


def read(ctx):
    r, n = ctx.get("reduced"), ctx.get("iterations")
    if not r or not n:
        return None
    # "banded.matvec" is also the start of "banded.matvec_pair"
    matvecs = scope_seconds(r, "dedalus/matsolve/banded.matvec") \
        + scope_seconds(r, "dedalus/matsolve/banded.refine")
    return 1e3 * matvecs / n if matvecs > 0 else None

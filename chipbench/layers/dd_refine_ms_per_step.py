"""pencil solve, float64 route: self time of the refined solve less its
products — the residual subtraction and the correction under
`dedalus/matsolve/dd.refine`, and the float32 solves inside it, which keep
their own scopes (`dense.solve`, `<solver class>.solve`) — over the
iterations traced. Left out where the program has no `dd.refine` scope: in
a float32 cell the same solve scopes are `solve_ms_per_step`'s."""

from chipbench.tracered import scope_seconds


def read(ctx):
    r, n = ctx.get("reduced"), ctx.get("iterations")
    if not r or not n:
        return None
    refine = scope_seconds(r, "dedalus/matsolve/dd.refine")
    if refine <= 0:
        return None
    solves = sum(v for k, v in r["scopes"].items()
                 if k.startswith("dedalus/matsolve/")
                 and k.endswith(".solve"))
    return 1e3 * (refine + solves) / n

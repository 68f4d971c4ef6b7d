"""evaluator, float64 route: self time of the double-double interpreter
of the right-hand side's expression tree — pointwise products and sums in
software float64, and the operators' matrices as int8 plane products — the
device ops under `dedalus/evaluator/dd.rhs`, over the iterations traced.
The grid <-> coefficient transforms inside it are
`dd_transform_ms_per_step`'s. Left out where the program has no such
scope."""

from chipbench.tracered import scope_seconds


def read(ctx):
    r, n = ctx.get("reduced"), ctx.get("iterations")
    if not r or not n:
        return None
    rhs = scope_seconds(r, "dedalus/evaluator/dd.rhs")
    return 1e3 * rhs / n if rhs > 0 else None

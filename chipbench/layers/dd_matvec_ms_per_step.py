"""pencil solve, float64 route: self time of the pencil products in
emulated float64 — M X and L X under `dedalus/matsolve/dd.matvec`, the
A x of the refinement sweeps under `dedalus/matsolve/dd.residual` (Ozaki
int8 plane products and their recombination) — over the iterations traced.
Left out where the program has no such scope (a tree before PR 35, or a
float64 route that is not the double-double one)."""

from chipbench.costs_dd import PRODUCT_SCOPES
from chipbench.tracered import scope_seconds


def read(ctx):
    r, n = ctx.get("reduced"), ctx.get("iterations")
    if not r or not n:
        return None
    products = sum(scope_seconds(r, scope) for scope in PRODUCT_SCOPES)
    return 1e3 * products / n if products > 0 else None

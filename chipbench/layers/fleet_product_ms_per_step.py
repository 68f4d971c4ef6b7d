"""pencil solve, ensemble: self time of the fleet's dense pencil products
— M X0 and L Xi under `dedalus/matsolve/dense.fleet_matvec`, the stored
inverse's solves under `dense.fleet_solve`, each ONE contraction over S for
every member (matsolvers.fleet_matvec) — over the fleet steps traced. Left
out where the program has no such scope (an older tree, or a fleet the
rule keeps on the vmapped multiply-reduce)."""

from chipbench.costs_fleet import PRODUCT_SCOPES
from chipbench.tracered import scope_seconds


def read(ctx):
    r, n = ctx.get("reduced"), ctx.get("iterations")
    if not r or not n:
        return None
    products = sum(scope_seconds(r, scope) for scope in PRODUCT_SCOPES)
    return 1e3 * products / n if products > 0 else None

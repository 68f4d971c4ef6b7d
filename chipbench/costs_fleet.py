"""
Operations and bytes of a fleet's dense pencil products from shapes: the
numerator of `fleet_product_roofline`. Computed, not measured; what the
work has to do whatever implements it (costs.py says the same of the
single-vector pencil layer).

An ensemble (core/ensemble.py) shares M, L and the stored inverse among
its N members, so each product of a step meets N vectors: 2 N G S^2
operations, and the (G, S, S) stack read once with the N members' vectors
read and written once. One IMEX Runge-Kutta step makes 1 + 2 stages such
products (M X0 once, then L Xi and the stored-inverse solve per stage), as
`costs.rk_dense_step` counts them for one member. N is the configuration's
member count (`sizes.members` of its file), G, S, the stages and the item
size the run's (`chipbench built:`).
"""

# the scopes whose self time these are divided by: the fleet's products
# as matsolvers.fleet_matvec makes them (M X and L X; the inverse's solve)
PRODUCT_SCOPES = ("dedalus/matsolve/dense.fleet_matvec",
                  "dedalus/matsolve/dense.fleet_solve")


def fleet_product(G, S, members, itemsize):
    """(G, S, S) . (G, S, N): 2 N G S^2 operations; the stack once, the N
    vectors in and out once."""
    return {"flops": 2 * members * G * S * S,
            "bytes": (G * S * S + 2 * members * G * S) * itemsize}


def rk_fleet_step(G, S, stages, members, itemsize):
    """One fleet step of an IMEX Runge-Kutta scheme on dense pencils."""
    one = fleet_product(G, S, members, itemsize)
    products = 1 + 2 * stages
    return {"flops": products * one["flops"],
            "bytes": products * one["bytes"], "products": products}

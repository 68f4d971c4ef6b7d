"""numerics: rel. L2 distance of the compared coefficients from the float64
CPU reference at the comparison step. What a precision change pays."""


def read(ctx):
    return ctx.get("ref_rel_l2")

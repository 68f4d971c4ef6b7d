"""
Bytes of the float64 route's pencil products from shapes: the numerator of
`dd_matvec_roofline`. Computed, not measured; what the work has to read
WHATEVER implements it, not what a program happens to read (costs.py says
the same of the float32 pencil layer).

A float64 problem's pencil products (M X, L X, and the A x of every
refinement sweep) each meet one column, so they are bound by bandwidth:
one multiply-add per matrix entry read. A float64-grade entry is 8 bytes
however it is kept — a double, a float32 pair, or 8 int8 planes of 7 bits
— so one product has to read 8 G S^2 bytes, and nothing that keeps the
guarantee can read less. A program that reads each of 8 planes once reads
exactly that; the Ozaki product as first written issues 36 plane products
over those 8 planes and reads 4.5 times as much. The vectors are a
five-hundredth of a matrix and left out.

How many products one Runge-Kutta step makes is the scheme's, stated in
the configuration's file under `dd_shapes` (M X once a step, L X for
every stage a later stage reads, A x once a stage and sweep) and checked against the lowered step in
tests/test_config_rb256x64_f64.py: not read from the program at run time,
so that a later change to how the products are done is judged on the same
work. G, S and the stages come from the run (`chipbench built:`).
"""

# the scopes whose self time those bytes are divided by: M X and L X, and
# the A x of the refinement sweeps
PRODUCT_SCOPES = ("dedalus/matsolve/dd.matvec", "dedalus/matsolve/dd.residual")


def products_per_step(shapes, stages):
    """Pencil products of one IMEX Runge-Kutta step under a
    configuration's `dd_shapes`."""
    p = shapes["products"]
    return int(p["M_per_step"] + p["L_per_step"]
               + stages * shapes["sweeps"] * p["A_per_stage_per_sweep"])


def rk_dd_step(shapes, G, S, stages):
    """One step: every product reads its (G, S, S) matrix once at
    `entry_bytes` a number."""
    products = products_per_step(shapes, stages)
    one = int(shapes["entry_bytes"]) * G * S * S
    return {"bytes": products * one, "products": products,
            "bytes_per_product": one}

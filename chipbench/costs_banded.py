"""
Bytes of the banded pencil layer from shapes: the numerator of
`banded_solve_roofline`. Computed, not measured; what the algorithm has to
read, not what a program happens to read (costs.py says the same of the
dense layer). The layer is bound by bandwidth: per number read it does one
or two multiply-adds.

The shapes of a pencil (q, the diagonals of M and L, the pinned rows) do
not depend on the resolution; the configuration's file states them under
`banded_shapes`, and G and S come from the run (`chipbench built:`).
"""


def shapes(spec, G, S):
    """The banded layer's shapes for G pencils of S unknowns under a
    configuration's `banded_shapes`: n_pad is S rounded up to whole block
    rows of q, NB their number."""
    b = spec["banded_shapes"]
    q = int(b["q"])
    NB = -(-int(S) // q)
    return {"G": int(G), "q": q, "NB": NB, "n_pad": NB * q,
            "diagonals_M": int(b["diagonals_M"]),
            "diagonals_L": int(b["diagonals_L"]),
            "pin_rows_M": int(b["pin_rows_M"]),
            "pin_rows_L": int(b["pin_rows_L"]), "pins": int(b["pins"])}


def band_matvec(G, n_pad, diagonals, pin_rows, itemsize):
    """One banded + pinned-row matvec: every stored diagonal and every
    pinned row read once. (The vectors, G * n_pad numbers each, are under
    a fiftieth of that and left out.)"""
    return G * (diagonals + pin_rows) * n_pad * itemsize


def packed_factors(G, NB, q, itemsize):
    """The blocked banded LU as LAPACK packs it: per block row but the
    last a (2q x q) panel and a (q x 2q) fill block, 4 q^2 numbers, and 2q
    pivot indices of four bytes; a (q x q) LU and q pivots for the last.
    The least a substitution has to read, whatever form the program keeps
    (its precomposed operators are 7 q^2 per block row)."""
    return G * ((NB - 1) * (4 * q * q * itemsize + 2 * q * 4)
                + q * q * itemsize + q * 4)


def woodbury_blocks(G, n_pad, pins, itemsize):
    """The low-rank correction of a solve: the pinned rows V^T and the
    solved pin columns Y^T, (pins x n_pad) each, and the capacitance
    matrix."""
    return G * (2 * pins * n_pad + pins * pins) * itemsize


def rk_banded_step(shape, stages, itemsize):
    """One IMEX Runge-Kutta step as core/timesteppers.RungeKuttaIMEX does
    it on banded pencils: M @ X0 once; per stage L @ Xi and one solve,
    which reads the factors and the Woodbury blocks once. A refinement
    sweep (a second solve and a residual matvec) is the implementation's
    choice and is not counted."""
    G, n_pad = shape["G"], shape["n_pad"]
    parts = {
        "M": band_matvec(G, n_pad, shape["diagonals_M"],
                         shape["pin_rows_M"], itemsize),
        "L": stages * band_matvec(G, n_pad, shape["diagonals_L"],
                                  shape["pin_rows_L"], itemsize),
        "factors": stages * packed_factors(G, shape["NB"], shape["q"],
                                           itemsize),
        "woodbury": stages * woodbury_blocks(G, n_pad, shape["pins"],
                                             itemsize),
    }
    return {"bytes": sum(parts.values()), "parts": parts}

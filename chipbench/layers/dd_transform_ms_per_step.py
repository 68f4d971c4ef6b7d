"""transforms, float64 route: self time of the grid <-> coefficient
transforms as Ozaki int8 plane products against each basis's transform
matrix (no FFT, no DCT) — the device ops under
`dedalus/transform/<Basis>.dd.{fwd,bwd}` — over the iterations traced.
Left out where the program has no such scope."""


def read(ctx):
    r, n = ctx.get("reduced"), ctx.get("iterations")
    if not r or not n:
        return None
    transforms = sum(v for k, v in r["scopes"].items()
                     if k.startswith("dedalus/transform/")
                     and k.endswith((".dd.fwd", ".dd.bwd")))
    return 1e3 * transforms / n if transforms > 0 else None

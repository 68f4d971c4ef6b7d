"""The banded pencil product on the chip, outside every timed window
(PR 38): `BandedOps.matvec` and `matvec_pair` at the north star's shapes
(RB 2048x1024: 1,024 pencils of 8,206, 54 stored diagonals each of M and
L, 16 pinned rows of L; only the pencil's STRUCTURE is assembled, from
Nx = 8, and the stores are random numbers made on the device) and
`_band_mv` alone at sw_ell255's (256 pencils, 11 diagonals of 1,540).
Each product as the tree has it (`tiled`, at the tile size the shapes
give and at others: `_BAND_TILE_BYTES` is the one number varied) against
the loop over whole diagonals it replaced (`loop`: the reference
tests/test_banded.py keeps), with the largest difference between the two
results, which
must be 0: the same float operations in the same order. One process,
which holds the chip:

    chiprun -- python3 benchmarks/band_product.py

Here, `JAX_PLATFORMS=cpu python3 benchmarks/band_product.py --nz 64
--groups 8 --reps 2` rehearses it small. Prints one JSON line per
(shape, product, form, tile bytes): milliseconds a call over `--reps`
calls in a row, the tiling, and GB/s of the bytes the product has to
read (the stores once). A diagnosis, not a metric: the cell's
`banded_matvec_ms_per_step` is the measurement."""

import argparse
import json
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "tests")]


def timed(fn, args, reps):
    import jax
    out = jax.block_until_ready(fn(*args))     # compile + first call
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return out, (time.perf_counter() - t0) / reps * 1e3


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nz", type=int, default=1024)
    ap.add_argument("--groups", type=int, default=1024)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--tile-mb", type=float, nargs="*",
                    default=[8, 16, 32, 128, 256])
    args = ap.parse_args()
    import copy
    import jax
    import jax.numpy as jnp
    import dedalus_tpu.public as d3  # noqa: F401
    from dedalus_tpu.extras.bench_problems import build_rb_solver
    from dedalus_tpu.libraries.pencilops import BandedMatrix
    from test_banded import _with_loop_band_mv

    dev = jax.devices()[0]
    say = lambda **kw: print(json.dumps(kw), flush=True)    # noqa: E731
    say(device=dev.device_kind, platform=dev.platform)
    solver, _ = build_rb_solver(8, args.nz, np.float32, matsolver="banded")
    ops = solver.ops
    G, S = args.groups, solver.pencil_shape[1]
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 8))

    def grow(a):
        return None if a is None else jax.random.normal(
            next(keys), (G,) + a.shape[1:], a.dtype)
    M, L = (BandedMatrix(grow(A.bands), grow(A.Vt), A.dsel)
            for A in (solver.M_mat, solver.L_mat))
    X = jax.random.normal(next(keys), (G, S), jnp.float32)

    def compare(shape, name, make, args_, stores, ops, tile_mb):
        """One product: the loop, the tiling the shapes give, then the
        tilings `tile_mb` forces. `stores`: the arrays it has to read."""
        nbytes = sum(a.nbytes for a in stores)

        def run(form, o):
            out, ms = timed(make(o), args_, args.reps)
            line = dict(shape=shape, product=name, form=form, ms=ms,
                        GBps=nbytes / ms / 1e6)
            if form == "tiled":
                rows, tiles = o._band_tiling
                line.update(tile_MB=o._BAND_TILE_BYTES / 2 ** 20, rows=rows,
                            tiles=tiles, max_abs_diff_to_loop=max(
                                float(jnp.max(jnp.abs(a - b))) for a, b in
                                zip(jax.tree.leaves(out),
                                    jax.tree.leaves(wanted))))
            say(**line)
            return out
        wanted = run("loop", _with_loop_band_mv(ops))
        run("tiled", ops)
        for mb in tile_mb:
            o = copy.copy(ops)
            o._BAND_TILE_BYTES = int(mb * 2 ** 20)
            run("tiled", o)

    stores = lambda *As: [a for A in As for a in (A.bands, A.Vt)  # noqa: E731
                          if a is not None]
    compare("rb", "M@X", lambda o: jax.jit(o.matvec), (M, X), stores(M),
            ops, args.tile_mb)
    compare("rb", "L@X", lambda o: jax.jit(o.matvec), (L, X), stores(L),
            ops, args.tile_mb)
    compare("rb", "pair", lambda o: jax.jit(o.matvec_pair), (M, L, X),
            stores(M, L), ops, args.tile_mb)

    # sw_ell255's stores: one tile, the body once and no loop around it;
    # forced into 9, 5 and 3 tiles, a scan
    sw = copy.copy(ops)
    sw.kl = sw.ku = 5
    Gs, Ds, Ws = (256, 11, 1540) if dev.platform == "tpu" else (8, 11, 140)
    bands = jax.random.normal(next(keys), (Gs, Ds, Ws), jnp.float32)
    x = jax.random.normal(next(keys), (Gs, Ws), jnp.float32)
    dsel = tuple(range(Ds))
    compare("sw", "_band_mv", lambda o: jax.jit(
        lambda b, x: o._band_mv([(b, dsel)], x)), (bands, x), [bands], sw,
        (2, 4, 8))


if __name__ == "__main__":
    main()

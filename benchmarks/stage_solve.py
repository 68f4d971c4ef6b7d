"""The banded stage solve on the chip, outside every timed window: the
program an RK222 step runs once a stage (`timestepper._stage_solve`: the
right-hand side, the refinement residual's band-product pair and two
`BandedOps._solve_once`, each a loop over the pencil chunks whose block-row
sweeps read the packed factors) at the two banded cells' one-chip shapes:

    rb2048  RB 2048x1024: 1,024 pencils of 8,206 unknowns (q = 32, 257
            block rows), factored in 8 chunks of 128 pencils
    rb3d    one chip's share of 3-D RB 128^2x128: 1,024 pencils of 2,596
            unknowns (q = 80, 33 block rows), 8 chunks of 128; the cell's
            four chips each run this program inside their shard_map

Only the pencil's STRUCTURE is assembled, from a small build (Nx = 8, and
Ny = 8 for rb3d); M and L are its pencils repeated to 1,024 and factored by
the incremental path (`factor_lincomb_incremental`) at the cells' dt. One
process, which holds the chip:

    python3 benchmarks/stage_solve.py  # on one TPU chip

On a CPU, `JAX_PLATFORMS=cpu python3 benchmarks/stage_solve.py --nz 64 --nz3
16 --groups 6 --chunk-mb 0.05 --reps 2` rehearses it small. Prints one JSON
line per shape: milliseconds a stage solve over `--reps` calls in a row,
the chunking, and a digest of the solution's bytes, so that two trees'
runs compare bit for bit. A diagnosis, not a metric: the cells'
`solve_ms_per_step` and `banded_sweep_ms_per_step` are the measurement."""

import argparse
import hashlib
import json
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT)]


def small_build(shape, nz):
    """A float32 banded solver of the shape's pencil structure."""
    if shape == "rb2048":
        from dedalus_tpu.extras.bench_problems import build_rb_solver
        solver, _ = build_rb_solver(8, nz, np.float32, matsolver="banded")
        return solver
    from chipbench.manifest import load_module
    rb = load_module(ROOT / "chipbench" / "configs" / "rb3d128x128x128.py")
    size = {"Nx": 8, "Ny": 8, "Nz": nz, "matsolver": "banded"}
    return rb.build(0, size=size).solver


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nz", type=int, default=1024)
    ap.add_argument("--nz3", type=int, default=128)
    ap.add_argument("--groups", type=int, default=1024)
    ap.add_argument("--dt", type=float, default=1e-3)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--chunk-mb", default=None,
                    help="[linear algebra] BANDED_CHUNK_MB (a rehearsal "
                         "forces chunks with it)")
    ap.add_argument("--shapes", nargs="*", default=["rb2048", "rb3d"])
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import dedalus_tpu.public as d3  # noqa: F401
    from dedalus_tpu.libraries.pencilops import BandedMatrix
    from dedalus_tpu.tools.config import config
    if args.chunk_mb is not None:
        config["linear algebra"]["BANDED_CHUNK_MB"] = args.chunk_mb

    dev = jax.devices()[0]
    say = lambda **kw: print(json.dumps(kw), flush=True)    # noqa: E731
    say(device=dev.device_kind, platform=dev.platform, tree=str(ROOT))
    for shape in args.shapes:
        solver = small_build(shape, args.nz if shape == "rb2048"
                             else args.nz3)
        ops, ts = solver.ops, solver.timestepper
        G, S = args.groups, solver.pencil_shape[1]

        def grow(a):
            return None if a is None else jnp.asarray(
                np.resize(np.asarray(a), (G,) + a.shape[1:]))
        M, L = (BandedMatrix(grow(A.bands), grow(A.Vt), A.dsel)
                for A in (solver.M_mat, solver.L_mat))
        X = jax.random.normal(jax.random.PRNGKey(0), (G, S), jnp.float32)
        dt = jnp.asarray(args.dt, jnp.float32)
        t0 = time.perf_counter()
        aux = ops.factor_lincomb_incremental(
            1.0, M, L, b_scale=args.dt * ts.uniq_H_diag[0])
        factor_s = time.perf_counter() - t0
        call = (2, X, [X, X], [X, X], dt, aux, M, L)
        t0 = time.perf_counter()
        out = jax.block_until_ready(ts._stage_solve(*call))
        first_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(args.reps):
            out = ts._stage_solve(*call)
        out = jax.block_until_ready(out)
        ms = (time.perf_counter() - t0) / args.reps * 1e3
        host = np.asarray(out)
        say(shape=shape, groups=G, S=S, q=ops.q, block_rows=ops.NB,
            chunks=ops._g_chunks, chunk_width=ops._chunk_width,
            packed="interior" in aux, factor_s=factor_s,
            first_call_s=first_s, ms_per_stage_solve=ms, reps=args.reps,
            finite=bool(np.isfinite(host).all()),
            max_abs=float(np.abs(host).max()),
            sha256=hashlib.sha256(host.tobytes()).hexdigest()[:16])
        del solver, ops, ts, M, L, X, aux, out, call


if __name__ == "__main__":
    main()

"""
Banded + pinned-Woodbury pencil solve vs the dense reference path
(reference test pattern: dual-implementation oracle,
/root/reference/dedalus/tests/test_transforms.py — here the oracle is the
dense (G,S,S) batched solve).
"""

import numpy as np
import pytest
import jax.numpy as jnp

import dedalus_tpu.public as d3


def build_rb(Nx, Nz, matsolver=None, timestepper=None, dtype=np.float64):
    Lx, Lz = 4.0, 1.0
    coords = d3.CartesianCoordinates("x", "z")
    dist = d3.Distributor(coords, dtype=dtype)
    xbasis = d3.RealFourier(coords["x"], size=Nx, bounds=(0, Lx), dealias=3/2)
    zbasis = d3.ChebyshevT(coords["z"], size=Nz, bounds=(0, Lz), dealias=3/2)
    p = dist.Field(name="p", bases=(xbasis, zbasis))
    b = dist.Field(name="b", bases=(xbasis, zbasis))
    u = dist.VectorField(coords, name="u", bases=(xbasis, zbasis))
    tau_p = dist.Field(name="tau_p")
    tau_b1 = dist.Field(name="tau_b1", bases=xbasis)
    tau_b2 = dist.Field(name="tau_b2", bases=xbasis)
    tau_u1 = dist.VectorField(coords, name="tau_u1", bases=xbasis)
    tau_u2 = dist.VectorField(coords, name="tau_u2", bases=xbasis)
    kappa = nu = 2.0e-6 ** 0.5
    x, z = dist.local_grids(xbasis, zbasis)
    ex, ez = coords.unit_vector_fields(dist)
    lift_basis = zbasis.derivative_basis(1)
    lift = lambda A: d3.Lift(A, lift_basis, -1)
    grad_u = d3.grad(u) + ez*lift(tau_u1)
    grad_b = d3.grad(b) + ez*lift(tau_b1)
    problem = d3.IVP([p, b, u, tau_p, tau_b1, tau_b2, tau_u1, tau_u2],
                     namespace=locals())
    problem.add_equation("trace(grad_u) + tau_p = 0")
    problem.add_equation("dt(b) - kappa*div(grad_b) + lift(tau_b2) = - u@grad(b)")
    problem.add_equation("dt(u) - nu*div(grad_u) + grad(p) - b*ez + lift(tau_u2) = - u@grad(u)")
    problem.add_equation("b(z=0) = Lz")
    problem.add_equation("u(z=0) = 0")
    problem.add_equation("b(z=Lz) = 0")
    problem.add_equation("u(z=Lz) = 0")
    problem.add_equation("integ(p) = 0")
    solver = problem.build_solver(timestepper or d3.RK222, matsolver=matsolver)
    b.fill_random("g", seed=42, distribution="normal", scale=1e-3)
    b["g"] += (Lz - z)
    return solver


@pytest.mark.parametrize("timestepper", [d3.RK222, d3.SBDF2])
def test_rb_banded_matches_dense(timestepper):
    sd = build_rb(16, 64, timestepper=timestepper)
    sb = build_rb(16, 64, matsolver="banded", timestepper=timestepper)
    assert sd.ops.kind == "dense"
    assert sb.ops.kind == "banded"
    for _ in range(5):
        sd.step(0.01)
        sb.step(0.01)
    Xd, Xb = np.asarray(sd.X), np.asarray(sb.X)
    assert np.isfinite(Xd).all()
    assert np.abs(Xd - Xb).max() < 1e-11


def test_rb_banded_structure_scales():
    """Pins and bandwidth must be resolution-independent: storage is
    O(G * S * band), enabling the RB 2048x1024 target (VERDICT item 2)."""
    stats = []
    for Nz in (64, 256):
        s = build_rb(8, Nz, matsolver="banded")
        st = s.structure
        stats.append((st.t_pins, st.kl, st.ku))
    assert stats[0] == stats[1]
    # storage for M+L at Nz=256 stays far below dense G*S^2
    s = build_rb(8, 256, matsolver="banded")
    nbytes = sum(a.nbytes for n in ("M", "L") for a in s._matrices[n].values()
                 if hasattr(a, "nbytes"))
    G, S = s.pencil_shape
    assert nbytes < 0.1 * (2 * G * S * S * 8)


def test_rb_banded_matvec_matches_densified():
    s = build_rb(8, 32, matsolver="banded")
    G, S = s.pencil_shape
    rng = np.random.default_rng(1)
    x = rng.standard_normal((G, S))
    for name, mat in (("M", s.M_mat), ("L", s.L_mat)):
        y = np.asarray(s.ops.matvec(mat, jnp.asarray(x)))
        for g in range(G):
            A = s.ops.densify_host(s._matrices[name], g)
            assert np.abs(y[g] - A @ x[g]).max() < 1e-10


def build_poisson(matsolver=None):
    coords = d3.CartesianCoordinates("x", "z")
    dist = d3.Distributor(coords, dtype=np.float64)
    xb = d3.RealFourier(coords["x"], size=16, bounds=(0, 2*np.pi))
    zb = d3.ChebyshevT(coords["z"], size=64, bounds=(0, 1))
    u = dist.Field(name="u", bases=(xb, zb))
    tau1 = dist.Field(name="tau1", bases=xb)
    tau2 = dist.Field(name="tau2", bases=xb)
    f = dist.Field(name="f", bases=(xb, zb))
    x, z = dist.local_grids(xb, zb)
    f["g"] = np.sin(2*x)*np.cos(np.pi*z)
    lift_basis = zb.derivative_basis(1)
    lift = lambda A, n: d3.Lift(A, lift_basis, n)
    problem = d3.LBVP([u, tau1, tau2], namespace=locals())
    problem.add_equation("lap(u) + lift(tau1,-1) + lift(tau2,-2) = f")
    problem.add_equation("u(z=0) = 0")
    problem.add_equation("u(z=1) = 0")
    solver = problem.build_solver(matsolver=matsolver)
    solver.solve()
    return np.asarray(u["g"])


def test_lbvp_banded_matches_dense():
    """The pure-elliptic LBVP is the hard case: a boundary-row Schur
    complement is exponentially ill-conditioned here; the pinned Woodbury
    form must still solve it to near machine precision."""
    ud = build_poisson()
    ub = build_poisson(matsolver="banded")
    assert np.abs(ud).max() > 1e-3
    assert np.abs(ud - ub).max() < 1e-12


def build_ball(matsolver=None):
    coords = d3.SphericalCoordinates("phi", "theta", "r")
    dist = d3.Distributor(coords, dtype=np.float64)
    ball = d3.BallBasis(coords, shape=(8, 4, 16), radius=1.0)
    u = dist.Field(name="u", bases=ball)
    tau = dist.Field(name="tau", bases=ball.surface)
    lift = lambda A: d3.Lift(A, ball, -1)
    problem = d3.IVP([u, tau], namespace=locals())
    problem.add_equation("dt(u) - lap(u) + lift(tau) = 0")
    problem.add_equation("u(r=1) = 0")
    solver = problem.build_solver(d3.SBDF2, matsolver=matsolver)
    u.fill_random("g", seed=7, scale=1e-2)
    for _ in range(5):
        solver.step(1e-3)
    return np.asarray(solver.X)


def test_ball_banded_matches_dense():
    """Curvilinear (per-ell coupled radial) pencils on the banded path."""
    Xd = build_ball()
    Xb = build_ball(matsolver="banded")
    assert np.isfinite(Xd).all()
    assert np.abs(Xd).max() > 1e-6
    assert np.abs(Xd - Xb).max() < 1e-12


def test_auto_selects_dense_for_small():
    s = build_rb(8, 16)
    assert s.ops.kind == "dense"


@pytest.mark.parametrize("timestepper", [d3.RK222, d3.SBDF2])
def test_rb_banded_chunked_matches_dense(timestepper):
    """G-chunked factorization/solve (lax.map over pencil-batch chunks,
    the HBM-bounding path for RB 2048x1024) must reproduce the dense
    answer exactly like the unchunked banded path."""
    from dedalus_tpu.tools.config import config
    sd = build_rb(16, 64, timestepper=timestepper)
    old = config["linear algebra"].get("BANDED_CHUNK_MB")
    config["linear algebra"]["BANDED_CHUNK_MB"] = "0.01"
    try:
        sb = build_rb(16, 64, matsolver="banded", timestepper=timestepper)
        assert sb.ops.kind == "banded"
        for _ in range(5):
            sd.step(0.01)
            sb.step(0.01)
        assert sb.ops._g_chunks > 1
    finally:
        config["linear algebra"]["BANDED_CHUNK_MB"] = old
    Xd, Xb = np.asarray(sd.X), np.asarray(sb.X)
    assert np.isfinite(Xd).all()
    assert np.abs(Xd - Xb).max() < 1e-11


def test_rb_banded_chunk_padding_matches_dense():
    """Group counts with no convenient divisor edge-pad the chunked batch
    (C*Gc > G) instead of degenerating to size-1 sequential chunks."""
    from dedalus_tpu.tools.config import config
    sd = build_rb(14, 64)
    sb0 = build_rb(14, 64, matsolver="banded")
    ops = sb0.ops
    G = sb0.pencil_shape[0]
    assert G % 2 == 1, "want an odd group count to force padding"
    # target exactly two groups per chunk -> C = ceil(G/2), G_pad = C*2 > G
    per_g = ops.NB * 2 * ops.q * ops.q * 2 * np.dtype(sb0.pencil_dtype).itemsize
    old = config["linear algebra"].get("BANDED_CHUNK_MB")
    # 2.05x margin: the /1e6 str round-trip must not land below 2*per_g
    config["linear algebra"]["BANDED_CHUNK_MB"] = str(2.05 * per_g / 1e6)
    try:
        sb = build_rb(14, 64, matsolver="banded")
        for _ in range(5):
            sd.step(0.01)
            sb.step(0.01)
        C = sb.ops._g_chunks
        assert C > 1 and G % C != 0, f"padding path not engaged (G={G}, C={C})"
    finally:
        config["linear algebra"]["BANDED_CHUNK_MB"] = old
    Xd, Xb = np.asarray(sd.X), np.asarray(sb.X)
    assert np.isfinite(Xd).all()
    assert np.abs(Xd - Xb).max() < 1e-11


@pytest.mark.parametrize("timestepper", [d3.RK222, d3.SBDF2])
def test_rb_banded_incremental_factor_matches_dense(timestepper):
    """Incremental (per-chunk dispatch, donated-store) factorization — the
    HBM-peak-capping mode for RB 2048x1024 — must reproduce the dense
    answer exactly like the fused factor."""
    from dedalus_tpu.tools.config import config
    sd = build_rb(16, 64, timestepper=timestepper)
    la = config["linear algebra"]
    old = (la.get("BANDED_CHUNK_MB"), la.get("BANDED_FACTOR_MODE", "auto"))
    la["BANDED_CHUNK_MB"] = "0.01"
    la["BANDED_FACTOR_MODE"] = "incremental"
    try:
        sb = build_rb(16, 64, matsolver="banded", timestepper=timestepper)
        assert sb.ops.kind == "banded"
        for _ in range(5):
            sd.step(0.01)
            sb.step(0.01)
        assert sb.ops._g_chunks > 1
    finally:
        la["BANDED_CHUNK_MB"] = old[0]
        la["BANDED_FACTOR_MODE"] = old[1]
    Xd, Xb = np.asarray(sd.X), np.asarray(sb.X)
    assert np.isfinite(Xd).all()
    assert np.abs(Xd - Xb).max() < 1e-11


def test_lbvp_banded_chunked_matches_dense():
    """factor()/solve() (LBVP path) under forced chunking."""
    from dedalus_tpu.tools.config import config
    ud = build_poisson()
    old = config["linear algebra"].get("BANDED_CHUNK_MB")
    config["linear algebra"]["BANDED_CHUNK_MB"] = "0.01"
    try:
        ub = build_poisson(matsolver="banded")
    finally:
        config["linear algebra"]["BANDED_CHUNK_MB"] = old
    assert np.abs(ud - ub).max() < 1e-12


# The chunk loop of `BandedOps._solve_once` hands the block-row sweeps the
# WHOLE chunked stores and the chunk index, and each sweep body slices its
# block row (c, i) where it lies (`BandedOps._block_rows`). Five distinct
# pencils forced into three chunks of two (the third edge-padded) against
# the same pencils in one chunk: the packed factors at RB 2048x1024's
# q = 32, the polished ones from `_POLISHED_MIN_Q` (3-D RB's regime), and
# the chunked FUSED_SOLVE operators; float32, each solve with its
# refinement sweep, four block rows a pencil, so that each sweep runs
# three bodies in every chunk, the backward one in reverse.

CHUNK_CASES = {"packed": (32, False), "polished": (64, False),
               "fused": (32, True)}


@pytest.mark.parametrize("case", list(CHUNK_CASES))
def test_banded_chunk_loop_slices_blocks_in_place(case):
    from dedalus_tpu.tools.config import config
    q, fused = CHUNK_CASES[case]
    G = 5
    ops, A, dense = _packed_case(q, 4, 3, G=G, dtype=np.float32)
    assert ops._polished == (q >= ops._POLISHED_MIN_Q)
    per_g = ops.NB * 2 * q * q * 2 * 4

    def factor():
        if fused:    # a*M + b*L == A exactly: the IVP's factor path
            return ops.factor_lincomb(0.5, A, 0.5, A), (A, A)
        return ops.factor(A), None

    la = config["linear algebra"]
    old = la.get("BANDED_CHUNK_MB")
    la["BANDED_CHUNK_MB"] = str(2.05 * per_g / 1e6)    # two pencils a chunk
    try:
        aux_c, mats = factor()
        assert (ops._g_chunks, ops._chunk_width) == (3, 2)
    finally:
        la["BANDED_CHUNK_MB"] = old
    aux_1, _ = factor()
    assert ops._g_chunks == 1
    stores = (aux_c["fsub"]["FwdOp"] if fused
              else aux_c["interior"][1])
    assert stores.shape[:2] == (3, ops.NB - 1)
    assert ("fsub" in aux_c) == fused

    rhs = np.random.default_rng(q).standard_normal((G, ops.n))
    rhs = jnp.asarray(rhs, dtype=jnp.float32)
    x_c = np.asarray(ops.solve(aux_c, rhs, mats=mats), np.float64)
    x_1 = np.asarray(ops.solve(aux_1, rhs, mats=mats), np.float64)
    x_ref = np.linalg.solve(dense, np.asarray(rhs, np.float64)[..., None])
    x_ref = x_ref[..., 0]
    scale = np.abs(x_ref).max(axis=1)
    # float32 rounding, as far as these pencils amplify it: 2e-4 at q =
    # 32, 1.2e-3 at q = 64. (On the CPU the chunked and the unchunked
    # FACTOR programs round differently where the polish runs, so the two
    # solves lie 3.7e-5 apart there, as they did while the chunk loop
    # sliced the stores; elsewhere they are equal.)
    tol = np.finfo(np.float32).eps * np.linalg.cond(dense).max()
    # every pencil, in every chunk, as in one chunk
    assert (np.abs(x_c - x_1).max(axis=1) / scale).max() < tol
    # and both as the dense solve
    for x in (x_c, x_1):
        assert (np.abs(x - x_ref).max(axis=1) / scale).max() < tol


def build_rb_dtype(Nz, dtype, matsolver):
    """RB column at a given dtype/matsolver for precision comparisons."""
    return build_rb(16, Nz, matsolver=matsolver, dtype=dtype)


def test_f32_inverse_accuracy_vs_f64_lu():
    """The TPU default solvers (explicit batched inverse; f32) must track
    the f64 LU oracle on a realistic tau-bordered RB pencil system
    (VERDICT weak item 3: the dense-inverse numerics were untested)."""
    s64 = build_rb_dtype(64, np.float64, "BatchedLUFactorized")
    s32 = build_rb_dtype(64, np.float32, "BatchedInverse")
    for _ in range(10):
        s64.step(0.01)
        s32.step(0.01)
    X64 = np.asarray(s64.X)
    X32 = np.asarray(s32.X)
    assert np.isfinite(X32).all()
    scale = np.abs(X64).max()
    assert scale > 1e-6
    # f32 arithmetic + inverse: expect ~1e-5 relative trajectory agreement
    assert np.abs(X64 - X32).max() / scale < 5e-4


def test_refined_inverse_matches_lu_f64():
    """BatchedInverseRefined (f32 inverse + f64 residual polish, the TPU
    path for 64-bit problems) must reach near-f64 accuracy."""
    s_lu = build_rb_dtype(64, np.float64, "BatchedLUFactorized")
    s_ref = build_rb_dtype(64, np.float64, "BatchedInverseRefined")
    for _ in range(10):
        s_lu.step(0.01)
        s_ref.step(0.01)
    Xl = np.asarray(s_lu.X)
    Xr = np.asarray(s_ref.X)
    scale = np.abs(Xl).max()
    assert scale > 1e-6
    assert np.abs(Xl - Xr).max() / scale < 1e-9


def test_banded_min_q_reblocking_equivalence():
    """BANDED_MIN_Q re-blocks the same banded lattice with larger q
    (fewer, fatter scan steps for TPU latency); the solve must agree with
    the structural-q path to rounding."""
    import numpy as np
    from dedalus_tpu.tools.config import config
    from dedalus_tpu.extras.bench_problems import build_rb_solver

    def run(min_q):
        old_s = config["linear algebra"].get("MATRIX_SOLVER", "auto")
        old_q = config["linear algebra"].get("BANDED_MIN_Q", "0")
        config["linear algebra"]["MATRIX_SOLVER"] = "banded"
        config["linear algebra"]["BANDED_MIN_Q"] = str(min_q)
        try:
            solver, b = build_rb_solver(64, 32, np.float64)
            for _ in range(5):
                solver.step(1e-3)
            return np.asarray(solver.X, np.float64), solver.ops
        finally:
            config["linear algebra"]["MATRIX_SOLVER"] = old_s
            config["linear algebra"]["BANDED_MIN_Q"] = old_q

    X0, ops0 = run(0)
    X1, ops1 = run(128)
    assert ops1.q == 128 and ops1.NB < ops0.NB
    assert np.abs(X1 - X0).max() / np.abs(X0).max() < 1e-11


# ---- the packed pivoted path against a float64 dense solve (PR 29) ----
#
# BandedOps.factor keeps the pivoted factors packed (`_factor_interior`:
# triangular inverses in the panel's top, L2 below, pivots beside) and
# `_solve_interior` sweeps them group-minor. Synthetic pencils — random
# bands of half-width q, so every panel pivots — at the structural q of
# the sphere (7), of RB 256x64 (16) and of RB 2048x1024 (32); k = 1 is a
# step's solve, k = 16 the factor-time Woodbury solve Y = B~^-1 E.

PACKED_G = 3


def _packed_case(q, NB, pins, seed=0, G=PACKED_G, dtype=np.float64):
    """(ops, A, dense): a BandedOps over identity permutations, its device
    matrix in `dtype` and the (G, S, S) float64 matrices it represents
    (rounded to `dtype` first). S is two short of NB*q, so the factor
    width is padded."""
    from types import SimpleNamespace
    from dedalus_tpu.libraries.pencilops import BandedOps
    rng = np.random.default_rng(seed + 1000 * q + 10 * NB + pins)
    S = NB * q - 2
    pin_pos = np.sort(rng.choice(S, size=pins, replace=False))
    st = SimpleNamespace(S=S, NB=NB, q=q, t_pins=pins, kl=q, ku=q,
                         row_perm=np.arange(S), col_perm=np.arange(S),
                         pinned_positions=pin_pos)
    ops = BandedOps(st, refine=1)
    n_store = NB * q
    bands = rng.standard_normal((G, 2 * q + 1, n_store))
    cols = np.arange(n_store)[None, :] + np.arange(-q, q + 1)[:, None]
    bands[:, (cols < 0) | (cols >= S)] = 0.0    # off the matrix
    bands[:, :, S:] = 0.0
    bands[:, :, pin_pos] = 0.0                  # pinned rows live in Vt
    Vt = np.zeros((G, pins, n_store))
    Vt[:, :, :S] = rng.standard_normal((G, pins, S))
    host = {"bands": bands.astype(dtype).astype(np.float64),
            "Vt": Vt.astype(dtype).astype(np.float64)}
    dense = np.stack([ops.densify_host(host, g) for g in range(G)])
    return ops, ops.to_device(host, dtype), dense


def _assert_really_pivots(aux):
    perms, _, _, lastP, _ = aux["interior"]
    if perms is None:                               # NB = 1: (G, q)
        moved, ident = lastP, np.arange(lastP.shape[-1])
    else:                                           # (steps, 2q, G)
        moved, ident = perms, np.arange(perms.shape[1])[None, :, None]
    assert np.any(np.asarray(moved) != ident)


@pytest.mark.parametrize("pins", [0, 3])
@pytest.mark.parametrize("NB", [1, 5])
@pytest.mark.parametrize("k", [1, 16])
@pytest.mark.parametrize("q", [7, 16, 32])
def test_packed_solve_matches_dense_f64(q, k, NB, pins):
    ops, A, dense = _packed_case(q, NB, pins)
    aux = ops.factor(A)
    assert "fsub" not in aux            # factor() keeps the packed factors
    _assert_really_pivots(aux)
    rng = np.random.default_rng(q + k)
    S = ops.n
    # the interior sweeps, k columns at once, against B~: A with unit
    # rows at the pins (and on the padded diagonal)
    Bt = np.tile(np.eye(ops.n_pad), (PACKED_G, 1, 1))
    Bt[:, :S, :S] = dense
    Bt[:, ops.pin_pos, :] = 0.0
    Bt[:, ops.pin_pos, ops.pin_pos] = 1.0
    F = rng.standard_normal((PACKED_G, ops.n_pad, k))
    X = np.asarray(ops._solve_interior(aux["interior"], jnp.asarray(F)))
    X_ref = np.linalg.solve(Bt, F)
    assert np.abs(X - X_ref).max() / np.abs(X_ref).max() < 1e-9
    # and the whole solve (Woodbury correction, one refinement sweep)
    rhs = rng.standard_normal((PACKED_G, S))
    x = np.asarray(ops.solve(aux, jnp.asarray(rhs)))
    x_ref = np.linalg.solve(dense, rhs[..., None])[..., 0]
    assert np.abs(x - x_ref).max() / np.abs(x_ref).max() < 1e-10


@pytest.mark.parametrize("pins", [0, 3])
@pytest.mark.parametrize("NB", [1, 5])
@pytest.mark.parametrize("q", [7, 16, 32])
def test_packed_solve_transpose_matches_dense_transpose(q, NB, pins):
    """The adjoint contract on the same cases: `solve_transpose`
    differentiates through the select-and-sum bodies (jax.vjp) and must
    solve with the transposed matrix against the same factors."""
    ops, A, dense = _packed_case(q, NB, pins)
    aux = ops.factor(A)
    _assert_really_pivots(aux)
    rhs = np.random.default_rng(q).standard_normal((PACKED_G, ops.n))
    x = np.asarray(ops.solve_transpose(aux, jnp.asarray(rhs)))
    x_ref = np.linalg.solve(np.swapaxes(dense, 1, 2), rhs[..., None])[..., 0]
    assert np.abs(x - x_ref).max() / np.abs(x_ref).max() < 1e-10


# ---- the band product as a scan over row tiles (PR 38) ----
#
# `BandedOps._band_mv` reads each store once: a scan over tiles of rows,
# the stored diagonals summed inside the body in the order of the loop
# over whole diagonals it replaced, which is kept here as the reference.
# The tile is read off the shapes (`_BAND_TILE_BYTES` a body); the cases
# shrink that number on the instance to force the tilings that can go
# wrong. Two kinds of entries: small integers, where every product and
# sum is exact in either dtype, so that "equal" means equal whatever XLA
# fuses or contracts (the CPU backend contracts a multiply and an add
# into an fma fusion by fusion: through `matvec`, random entries differ
# in the last bit between any two programs, the parent's
# pair and singles among them); and random reals for `_band_mv` alone,
# where the two forms are the same float operations row by row and are
# equal bit for bit.

def _with_loop_band_mv(ops):
    """A copy of `ops` whose `_band_mv` is what it was until PR 38: each
    stored diagonal a shifted pass over the whole padded x."""
    import copy
    import jax
    from dedalus_tpu.tools.array import zeropad
    old = copy.copy(ops)

    def band_mv(mats, x):
        xpad = zeropad(x, ((0, 0), (old.kl, old.ku)))
        ys = []
        for bands, dsel in mats:
            y = jnp.zeros_like(x)
            for i, d in enumerate(dsel):
                y = y + bands[:, i, :] * jax.lax.slice_in_dim(
                    xpad, d, d + bands.shape[-1], axis=1)
            ys.append(y)
        return ys
    old._band_mv = band_mv
    return old


BAND_G = 3
# case: (q, NB, diagonals M keeps, diagonals L drops, rows asked of a
# tile or None for the default, (rows, tiles) the shapes then give)
BAND_CASES = {
    "one_tile": (7, 6, (6, 7, 8), (), None, (42, 1)),
    "width_no_multiple_of_the_tile": (7, 6, (6, 7, 8), (), 16, (16, 3)),
    "width_a_multiple_of_the_tile": (16, 4, (15, 16, 17), (), 16, (16, 4)),
    "tile_narrower_than_kl_plus_ku": (16, 5, (0, 16, 32), (), 8, (8, 10)),
    "dsel_with_gaps": (7, 9, (0, 7, 13), (1, 2, 5, 11), 24, (24, 3)),
}


def _band_case(case, dtype, draw):
    """(ops, M, L, host stores): a BandedOps over random permutations
    with 3 pinned rows, M on three diagonals with no pinned content (its
    Vt is dropped) beside L on the full lattice less `drop`, with pinned
    rows; entries from `draw(rng, shape)`."""
    from types import SimpleNamespace
    from dedalus_tpu.libraries.pencilops import BandedOps
    q, NB, keep, drop, ask, tiling = BAND_CASES[case]
    rng = np.random.default_rng(sum(map(ord, case)))
    S, n_store, pins = NB * q - 2, NB * q, 3
    pin_pos = np.sort(rng.choice(S, size=pins, replace=False))
    st = SimpleNamespace(S=S, NB=NB, q=q, t_pins=pins, kl=q, ku=q,
                         row_perm=rng.permutation(S),
                         col_perm=rng.permutation(S),
                         pinned_positions=pin_pos)
    ops = BandedOps(st, refine=1)
    cols = np.arange(n_store)[None, :] + np.arange(-q, q + 1)[:, None]
    off = (cols < 0) | (cols >= S)
    hosts = {}
    for name, lattice in (("M", keep), ("L", sorted(set(range(2 * q + 1))
                                                   - set(drop)))):
        bands = np.zeros((BAND_G, 2 * q + 1, n_store))
        bands[:, lattice] = draw(rng, (BAND_G, len(lattice), n_store))
        bands[:, off] = 0.0
        bands[:, :, S:] = 0.0
        bands[:, :, pin_pos] = 0.0
        Vt = np.zeros((BAND_G, pins, n_store))
        if name == "L":
            Vt[:, :, :S] = draw(rng, (BAND_G, pins, S))
        hosts[name] = {"bands": bands, "Vt": Vt}
    M, L = (ops.to_device(hosts[n], dtype) for n in ("M", "L"))
    assert M.Vt is None and L.Vt is not None
    if ask is not None:
        ops._BAND_TILE_BYTES = (
            ask * BAND_G * len(L.dsel) * np.dtype(dtype).itemsize)
    assert ops._band_tiles(M.bands, L.bands) == tiling
    return ops, M, L, hosts


def _integers(rng, shape):
    return rng.integers(-4, 5, size=shape).astype(np.float64)


def _reals(rng, shape):
    return rng.standard_normal(shape)


@pytest.mark.parametrize("dtype", [np.float64, np.float32])
@pytest.mark.parametrize("case", list(BAND_CASES))
def test_tiled_band_product(case, dtype):
    """On exact entries: the dense matrix's product, the loop's, the pair
    against the two single products, and `vmap` over an ensemble axis of X
    (M and L unbatched, as core/ensemble.py steps), all equal. On random
    entries: the dense product in float64 to rounding, and `_band_mv`
    bit for bit the loop."""
    import jax
    tiles = BAND_CASES[case][5][1]
    ops, M, L, hosts = _band_case(case, dtype, _integers)
    assert M.dsel == BAND_CASES[case][2]
    assert len(L.dsel) == ops.nd - len(BAND_CASES[case][3])
    old = _with_loop_band_mv(ops)
    rng = np.random.default_rng(7)
    Xe = jnp.asarray(rng.integers(-8, 9, size=(4, BAND_G, ops.n)),
                     dtype=dtype)
    X = Xe[0]
    matvec, pair = jax.jit(ops.matvec), jax.jit(ops.matvec_pair)
    # the scan really is there, where the case asks for tiles
    assert ("while" in matvec.lower(L, X).as_text()) == (tiles > 1)
    dense = {name: np.stack([ops.densify_host(hosts[name], g)
                             for g in range(BAND_G)]) for name in "ML"}
    singles = {"M": matvec(M, X), "L": matvec(L, X)}
    for (name, y), A, both in zip(singles.items(), (M, L), pair(M, L, X)):
        want = np.einsum("gij,gj->gi", dense[name], np.asarray(X, np.float64))
        assert np.array_equal(np.asarray(y), want)
        assert np.array_equal(np.asarray(both), want)
        assert np.array_equal(np.asarray(jax.jit(old.matvec)(A, X)), want)
    for e, (MX, LX) in enumerate(zip(*jax.jit(jax.vmap(
            lambda x: ops.matvec_pair(M, L, x)))(Xe))):
        for name, y in (("M", MX), ("L", LX)):
            assert np.array_equal(np.asarray(y), np.einsum(
                "gij,gj->gi", dense[name], np.asarray(Xe[e], np.float64)))
    # random entries
    ops, M, L, hosts = _band_case(case, dtype, _reals)
    old = _with_loop_band_mv(ops)
    matvec = jax.jit(ops.matvec)
    X = jnp.asarray(rng.standard_normal((BAND_G, ops.n)), dtype=dtype)
    for name, A in (("M", M), ("L", L)):
        dense = np.stack([ops.densify_host(hosts[name], g)
                          for g in range(BAND_G)]).astype(dtype)
        want = np.einsum("gij,gj->gi", dense.astype(np.float64),
                         np.asarray(X, np.float64))
        tol = 1e-13 if dtype == np.float64 else 2e-5
        assert (np.abs(np.asarray(matvec(A, X)) - want).max()
                < tol * np.abs(want).max())
    mats = [(A.bands, A.dsel) for A in (M, L)]
    xp = jnp.asarray(rng.standard_normal((BAND_G, ops.n_store)), dtype=dtype)
    for y, want in zip(jax.jit(lambda x: ops._band_mv(mats, x))(xp),
                       jax.jit(lambda x: old._band_mv(mats, x))(xp)):
        assert np.array_equal(np.asarray(y), np.asarray(want))

"""
Configuration rb3d128x128x128: BASELINE.json configs[4] — 3-D
Rayleigh-Benard, RealFourier(128) x RealFourier(128) x ChebyshevT(128),
through the banded pencil path with the pencil groups sharded over the four
chips of one v5e host (`build(mesh=...)`: the harness passes the cell's
mesh) — with the equations, parameters and initial conditions of upstream
examples/ivp_2d_rayleigh_benard/rayleigh_benard.py carried to 3-D, as
benchmarks/progression.py `build_rb3d` writes them, against
`dedalus_tpu.public` only. Sizes, guarantees, tolerances and the bytes of
the deployment are in rb3d128x128x128.json beside this file.

Two references decide `correct` (rb3d128x128x128.json, `guarantees`):

- the trajectory: chipbench/reference.py asks for float64 on the CPU with
  `matsolver: "dense"`. At the published size dense pencils cannot exist
  (4,096 x 2,596^2 x 8 B = 221 GB), so `build` maps the request onto the
  plainest path that can: float64, banded, unsharded, every fusion off,
  transforms as matrix products (reference.py's PLAIN_PATH). That
  reference shares the banded code with the chip's run;
- the plain dense check of `invariants()`: NumPy float64 on the host, eight
  pencil groups densified from the host matrices, the chips' own sharded
  factors and solve judged by residual: independent of the banded code.
"""

import functools
import json
import os
import pathlib

import numpy as np

SPEC = json.loads(pathlib.Path(__file__).with_suffix(".json").read_text())


def _require_program():
    """Refuse at once, before JAX or the reference child, on a program
    that cannot shard the banded pencils over a mesh: until PR 40 the chunk
    width counted every chip's groups (the packed store padded 4x, out of
    memory before the first step) and the packed solve fell back to plain
    GSPMD under a mesh. The harness lays this file over the parent's
    checkout too; the parent then fails cleanly, with the manifest's
    message and exit code 1. Read from the source text: no import of the
    package, so no JAX."""
    import importlib.util
    from chipbench.manifest import ManifestError
    package = importlib.util.find_spec("dedalus_tpu")
    source = pathlib.Path(package.origin).parent / "libraries" / "pencilops.py"
    if "def shard_over" not in source.read_text():
        raise ManifestError(
            "configuration rb3d128x128x128 needs a program that shards the "
            "banded pencils over a mesh (BandedOps.shard_over, PR 40); "
            f"{source} has none")


_require_program()

# RK222's implicit diagonal (Ascher, Ruuth & Spiteri 1997, sec. 2.6): every
# stage solves (M + dt*gamma*L) x = rhs
RK222_GAMMA = (2 - np.sqrt(2)) / 2
# a dense reference is honoured while its pencil matrices fit a host
DENSE_REFERENCE_LIMIT_BYTES = 8 << 30


def _endpoint_weights(n):
    """Values at z=Lz and z=0 of the basis functions the coefficients
    multiply (orthonormal Chebyshev: P_0 = 1/sqrt(pi), P_n = sqrt(2/pi)
    T_n, T_n(+-1) = (+-1)^n), as rb2048x1024.py has them."""
    top = np.full(n, np.sqrt(2 / np.pi))
    top[0] = 1 / np.sqrt(np.pi)
    return top, top * (-1.0) ** np.arange(n)


def sampled_groups(G, n_random=4, seed=2048):
    """The pencil groups the dense check densifies: the first two, the
    middle, the last, and `n_random` more from a fixed seed."""
    fixed = [0, 1, G // 2, G - 1]
    rest = np.setdiff1d(np.arange(G), fixed)
    extra = np.random.default_rng(seed).choice(
        rest, size=min(n_random, rest.size), replace=False)
    return sorted({int(g) for g in fixed} | {int(g) for g in extra})


@functools.lru_cache(maxsize=None)
def _solve_program(ops):
    """`ops.solve` with its residual matvec as one program, traced once
    per operator object (sharded over the mesh where the solver is)."""
    import jax
    return jax.jit(lambda aux, r, M, L: ops.solve(aux, r, mats=(M, L)))


def dense_residuals(solver, groups, dt, seed=1024):
    """rb2048x1024.py's plain reference of the banded mechanism: for each
    sampled group, M_g + dt*gamma*L_g densified in float64 from the host
    band storage and the row-wise backward error of the program's own
    (sharded) solve of a seeded right-hand side. Returns {g: residual}."""
    ops, stepper = solver.ops, solver.timestepper
    G, S = solver.pencil_shape
    rng = np.random.default_rng(seed)
    rhs = rng.standard_normal((G, S)).astype(solver.pencil_dtype)
    x = np.asarray(_solve_program(ops)(stepper._lhs_aux[0], rhs,
                                       solver.M_mat, solver.L_mat),
                   dtype=np.float64)
    host = solver._matrices
    out = {}
    for g in groups:
        A = ops.densify_host(host["M"], g).astype(np.float64)
        A += (dt * RK222_GAMMA) * ops.densify_host(host["L"], g)
        r = rhs[g].astype(np.float64)
        scale = np.abs(A).sum(axis=1) * np.abs(x[g]).max() + np.abs(r)
        out[g] = float((np.abs(A @ x[g] - r) / scale).max())
    return out


def _pencil_sparse(host, ops, g, a, b):
    """a*M_g + b*L_g as a SciPy CSC matrix in the band storage's permuted
    ordering (Ap[i, j] = A[row_perm[i], col_perm[j]], BandedOps.
    densify_host's), from the host band stores of one group: every stored
    diagonal's nonzeros and the pinned rows. Nothing of the package's
    banded factorization or solve is used."""
    import scipy.sparse as sp
    S = ops.n
    W = host["M"]["bands"].shape[-1]
    rows, cols, vals = [], [], []
    for name, coef in (("M", a), ("L", b)):
        if coef == 0:
            continue
        bands = host[name]["bands"][g]
        for i, d in enumerate(host[name].get("dsel", range(ops.nd))):
            off = d - ops.kl
            p = np.arange(max(0, -off), min(W, W - off))
            v = bands[i, p]
            nz = v != 0
            rows.append(p[nz])
            cols.append(p[nz] + off)
            vals.append(coef * v[nz])
        Vt = host[name]["Vt"]
        if ops.t and Vt is not None and np.any(Vt[g]):
            r, c = np.nonzero(Vt[g])
            rows.append(ops.pin_pos[r])
            cols.append(c)
            vals.append(coef * Vt[g][r, c])
    A = sp.csc_matrix((np.concatenate(vals),
                       (np.concatenate(rows), np.concatenate(cols))),
                      shape=(W, W))
    return A[:S, :S].tocsc()


def plain_implicit_steps(solver):
    """The reference's RK222 step where dense pencils cannot exist: the
    package's own float64 right-hand side (matrix transforms, no fusion:
    reference.py's PLAIN_PATH) and, for the implicit part, each pencil
    group's M, L and stage matrix M + dt*gamma*L as sparse matrices solved
    by SciPy's SuperLU, group by group. Independent of the banded code the
    chip runs, and lean: the package's banded float64 factors of 4,096
    groups are 28 GB and its path held 24 MB a group on a CPU host (32x32x
    128, PR 40), where SuperLU's factors of a group are about 4 MB.
    Replaces `solver.timestepper.step` on this solver only."""
    import concurrent.futures
    import jax
    import jax.numpy as jnp
    from scipy.sparse.linalg import splu
    ts, ops = solver.timestepper, solver.ops
    host = solver._matrices
    G, S = solver.pencil_shape
    pool = concurrent.futures.ThreadPoolExecutor(os.cpu_count())
    per_group = lambda fn: list(pool.map(fn, range(G)))  # noqa: E731
    Ms = per_group(lambda g: _pencil_sparse(host, ops, g, 1.0, 0.0))
    Ls = per_group(lambda g: _pencil_sparse(host, ops, g, 0.0, 1.0))
    # nothing of this step reads the package's band stores again: the
    # host copy and the device copy (17.6 GB each in float64) go
    solver._matrices = solver.M_mat = solver.L_mat = None
    mask = np.asarray(solver.valid_row_mask, dtype=np.float64)
    F = jax.jit(lambda X, t, extra: solver.eval_F(X, t, extra))
    rp, cp = ops.row_perm, ops.col_perm
    lus = {}

    def apply(mats, X):
        out = np.zeros_like(X)
        for g, A in enumerate(mats):
            out[g, rp] = A @ X[g, cp]
        return out

    def solve(lu_list, R):
        def one(g):
            x = np.empty(S)
            x[cp] = lu_list[g].solve(R[g, rp])
            return x
        return np.stack(per_group(one))

    def step(dt):
        A, H, c = ts.A, ts.H, ts.c
        t0 = float(solver.sim_time)
        X0 = np.asarray(solver.X, dtype=np.float64)
        MX0 = apply(Ms, X0)
        Xi, Fs, LXs = X0, [], []
        for i in range(1, ts.stages + 1):
            LXs.append(apply(Ls, Xi))
            Fs.append(np.asarray(F(jnp.asarray(Xi), t0 + c[i - 1] * dt,
                                   solver.rhs_extra()),
                                 dtype=np.float64) * mask)
            R = MX0
            for j in range(i):
                R = R + dt * (A[i, j] * Fs[j] - H[i, j] * LXs[j])
            key = (float(dt), float(H[i, i]))
            if key not in lus:
                b = dt * H[i, i]
                lus[key] = per_group(lambda g: splu(Ms[g] + b * Ls[g]))
            Xi = solve(lus[key], R)
        solver.X = jnp.asarray(Xi)
        solver.sim_time = t0 + dt
        ts.iteration += 1

    ts.step = step


class Deployment:
    """What `build` hands the harness: the solver, and this
    configuration's own way of reading and checking it."""

    def __init__(self, solver, fields, params):
        self.solver = solver
        self.fields = fields
        self.params = params
        self.fixed_dt = SPEC["fixed_dt"]

    def compared(self):
        """Coefficients the reference comparison reads, as float64: b
        without its (kx, ky) = (0, 0) block (the conduction profile, the
        first two modes of each RealFourier axis) together with u: the part
        of the state that is the flow, as rb2048x1024.py compares it."""
        b = np.asarray(self.fields["b"]["c"], dtype=np.float64)
        u = np.asarray(self.fields["u"]["c"], dtype=np.float64)
        keep = np.ones(b.shape[:2], dtype=bool)
        keep[:2, :2] = False
        return np.concatenate([b[keep].ravel(), u.ravel()])

    def invariants(self):
        """{name: (value, tolerance)}: wall values mode by mode in plain
        NumPy from the pulled coefficients (only the (0, 0) Fourier mean
        may differ from zero there), the enforced continuity equation
        through the program's own operators over max(1, max|grad_u|), and
        the dense float64 residual check of the sharded banded factors and
        solve on the sampled groups — as rb2048x1024.py, one axis more."""
        import dedalus_tpu.public as d3
        f, Lz = self.fields, self.params["Lz"]
        tol = SPEC["tolerances"]
        b = np.asarray(f["b"]["c"], dtype=np.float64)
        u = np.asarray(f["u"]["c"], dtype=np.float64)
        top, bottom = _endpoint_weights(b.shape[-1])
        b_bottom = b @ bottom
        b_bottom[0, 0] -= Lz
        wall = max(np.abs(b_bottom).max(), np.abs(b @ top).max(),
                   np.abs(u @ bottom).max(), np.abs(u @ top).max())
        coords = f["u"].tensorsig[0]
        zbasis = f["b"].domain.bases[2]
        ez = coords.unit_vector_fields(f["b"].dist)[2]
        grad_u = d3.grad(f["u"]) + ez * d3.Lift(
            f["tau_u1"], zbasis.derivative_basis(1), -1)
        amax = lambda op: np.abs(np.asarray(  # noqa: E731
            op.evaluate()["g"], dtype=np.float64)).max()
        continuity = amax(d3.trace(grad_u) + f["tau_p"]) \
            / max(1.0, amax(grad_u))
        out = {
            "wall_bc": (float(wall), tol["wall_bc"]["value"]),
            "continuity": (float(continuity), tol["continuity"]["value"]),
        }
        if type(self.solver.ops).__name__ == "BandedOps":
            G = self.solver.pencil_shape[0]
            residuals = dense_residuals(self.solver, sampled_groups(G),
                                        self.fixed_dt)
            out["dense_residual"] = (max(residuals.values()),
                                     tol["dense_residual"]["value"])
        return out


def build(seed, mesh=None, dtype=None, size=None):
    """`build_rb3d`'s script (benchmarks/progression.py), the example's
    parameters and seed 42 included: `seed` is accepted and unused, so that
    every run follows one trajectory (rb256x64.json, `assumed.seed`, says
    why). `mesh` is the cell's (four chips: the pencils are sharded by
    parallel.distribute_solver); `dtype` is the reference's way in (float64
    on the CPU); `size` the tests', and the reference's
    `matsolver: "dense"`."""
    import dedalus_tpu.public as d3
    sizes = dict(SPEC["sizes"], **(size or {}))
    Lx, Ly, Lz = sizes["Lx"], sizes["Ly"], sizes["Lz"]
    Nx, Ny, Nz = sizes["Nx"], sizes["Ny"], sizes["Nz"]
    Rayleigh, Prandtl = sizes["Rayleigh"], sizes["Prandtl"]
    dealias = sizes["dealias"]
    timestepper = getattr(d3, sizes["timestepper"])
    dtype = np.dtype(dtype or sizes["dtype"]).type

    coords = d3.CartesianCoordinates('x', 'y', 'z')
    dist = d3.Distributor(coords, dtype=dtype, mesh=mesh)
    xbasis = d3.RealFourier(coords['x'], size=Nx, bounds=(0, Lx), dealias=dealias)
    ybasis = d3.RealFourier(coords['y'], size=Ny, bounds=(0, Ly), dealias=dealias)
    zbasis = d3.ChebyshevT(coords['z'], size=Nz, bounds=(0, Lz), dealias=dealias)
    bases = (xbasis, ybasis, zbasis)

    p = dist.Field(name='p', bases=bases)
    b = dist.Field(name='b', bases=bases)
    u = dist.VectorField(coords, name='u', bases=bases)
    tau_p = dist.Field(name='tau_p')
    tau_b1 = dist.Field(name='tau_b1', bases=(xbasis, ybasis))
    tau_b2 = dist.Field(name='tau_b2', bases=(xbasis, ybasis))
    tau_u1 = dist.VectorField(coords, name='tau_u1', bases=(xbasis, ybasis))
    tau_u2 = dist.VectorField(coords, name='tau_u2', bases=(xbasis, ybasis))

    kappa = (Rayleigh * Prandtl)**(-1/2)
    nu = (Rayleigh / Prandtl)**(-1/2)
    x, y, z = dist.local_grids(xbasis, ybasis, zbasis)
    ex, ey, ez = coords.unit_vector_fields(dist)
    lift_basis = zbasis.derivative_basis(1)
    lift = lambda A: d3.Lift(A, lift_basis, -1)  # noqa: E731
    grad_u = d3.grad(u) + ez*lift(tau_u1)  # First-order reduction
    grad_b = d3.grad(b) + ez*lift(tau_b1)  # First-order reduction

    problem = d3.IVP([p, b, u, tau_p, tau_b1, tau_b2, tau_u1, tau_u2], namespace=locals())
    problem.add_equation("trace(grad_u) + tau_p = 0")
    problem.add_equation("dt(b) - kappa*div(grad_b) + lift(tau_b2) = - u@grad(b)")
    problem.add_equation("dt(u) - nu*div(grad_u) + grad(p) - b*ez + lift(tau_u2) = - u@grad(u)")
    problem.add_equation("b(z=0) = Lz")
    problem.add_equation("u(z=0) = 0")
    problem.add_equation("b(z=Lz) = 0")
    problem.add_equation("u(z=Lz) = 0")
    problem.add_equation("integ(p) = 0")  # Pressure gauge

    matsolver = sizes["matsolver"]
    banded_reference = False
    if matsolver == "dense":
        # the reference's request: G = (Nx/2)(Ny/2) pencils of
        # S = 4 (5 Nz + 8) + 4 unknowns (four Fourier sign pairs)
        G = (Nx // 2) * (Ny // 2)
        S = 4 * (5 * Nz + 8) + 4
        if G * S * S * np.dtype(dtype).itemsize > DENSE_REFERENCE_LIMIT_BYTES:
            matsolver, banded_reference = "banded", True
    solver = problem.build_solver(
        timestepper, matsolver=None if matsolver == "auto" else matsolver)
    if banded_reference:
        plain_implicit_steps(solver)
    if mesh is not None:
        from dedalus_tpu.parallel import distribute_solver
        distribute_solver(solver, mesh)

    b.fill_random('g', seed=sizes["ic_seed"], distribution='normal', scale=1e-3)
    b['g'] *= z * (Lz - z)
    b['g'] += Lz - z

    fields = {"p": p, "b": b, "u": u, "tau_p": tau_p, "tau_u1": tau_u1}
    return Deployment(solver, fields, {"Lz": Lz, "nu": nu})

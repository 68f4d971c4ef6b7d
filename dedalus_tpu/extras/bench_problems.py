"""
Shared benchmark/test problem builders (the 2-D Rayleigh-Benard flagship
configuration; reference: examples/ivp_2d_rayleigh_benard/
rayleigh_benard.py). Used by the driver entry (__graft_entry__),
benchmarks, and the emulated-f64 regression tests.
"""

import numpy as np


def build_diffusion_solver(size=64, dtype=np.float64):
    """1-D forced nonlinear heat IVP (SBDF2, dense pencil path): the
    shared small problem behind the adjoint and fusion benchmark rows —
    parameter field `a`, forcing `f`, and a Burgers term so the dealiased
    transform chain and per-step residual storage are both exercised.
    ONE definition so the cross-benchmark results.jsonl comparisons stay
    on the same physics."""
    import dedalus_tpu.public as d3
    xc = d3.Coordinate("x")
    dist = d3.Distributor(xc, dtype=dtype)
    xb = d3.RealFourier(xc, size=size, bounds=(0, 2 * np.pi))
    u = dist.Field(name="u", bases=xb)
    a = dist.Field(name="a", bases=xb)
    f = dist.Field(name="f", bases=xb)
    dx = lambda A: d3.Differentiate(A, xc)  # noqa: E731
    problem = d3.IVP([u], namespace={"u": u, "a": a, "f": f,
                                     "lap": d3.lap, "dx": dx})
    problem.add_equation("dt(u) - lap(u) = a*u + f - u*dx(u)")
    x = dist.local_grid(xb)
    u["g"] = np.sin(3 * x)
    a["g"] = 0.1 * np.cos(x)
    f["g"] = 0.05 * np.sin(2 * x)
    return problem.build_solver(d3.SBDF2, warmup_iterations=2,
                                enforce_real_cadence=0)


def build_rb_solver(Nx, Nz, dtype, mesh=None, matsolver=None):
    import dedalus_tpu.public as d3
    Lx, Lz = 4.0, 1.0
    coords = d3.CartesianCoordinates("x", "z")
    dist = d3.Distributor(coords, dtype=dtype, mesh=mesh)
    xbasis = d3.RealFourier(coords["x"], size=Nx, bounds=(0, Lx), dealias=3 / 2)
    zbasis = d3.ChebyshevT(coords["z"], size=Nz, bounds=(0, Lz), dealias=3 / 2)
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
    grad_u = d3.grad(u) + ez * lift(tau_u1)
    grad_b = d3.grad(b) + ez * lift(tau_b1)
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
    # matsolver=None defers to [linear algebra] MATRIX_SOLVER, whose
    # `auto` keeps dense pencils while their matrices stay under
    # BANDED_CUTOFF_BYTES (1 GiB: RB 256x64 is 142 MB, so bench.py, which
    # passes nothing, runs DenseOps) and goes banded above (RB 2048x1024);
    # coldstart.py and serving.py pass "banded" explicitly
    solver = problem.build_solver(d3.RK222, matsolver=matsolver)
    b.fill_random("g", seed=42, distribution="normal", scale=1e-3)
    b["g"] += (Lz - z)
    return solver, b


def build_tau_ivp(Nx=16, Nz=8, cadence=100, matsolver=None,
                  timestepper=None):
    """2-D nonlinear heat IVP with tau lines (Fourier x Chebyshev): the
    shared small sharded-stepping configuration behind the collective-
    placement tests (tests/test_collectives.py, tests/test_distributed.py),
    the weak-scaling benchmark and the compiled-program contract census
    (tools/lint/progcheck.py). Returns (solver, u, x, z) undistributed;
    callers shard it with parallel.distribute_solver or fleet it with
    solver.ensemble. ONE definition so every gather/all-to-all assertion
    runs against the same program shape."""
    import dedalus_tpu.public as d3
    coords = d3.CartesianCoordinates("x", "z")
    dist = d3.Distributor(coords, dtype=np.float64)
    xb = d3.RealFourier(coords["x"], size=Nx, bounds=(0, 4.0), dealias=3 / 2)
    zb = d3.ChebyshevT(coords["z"], size=Nz, bounds=(0, 1.0), dealias=3 / 2)
    u = dist.Field(name="u", bases=(xb, zb))
    t1 = dist.Field(name="t1", bases=xb)
    t2 = dist.Field(name="t2", bases=xb)
    lift = lambda A, n: d3.Lift(A, zb.derivative_basis(2), n)  # noqa: E731
    problem = d3.IVP([u, t1, t2], namespace=locals())
    problem.add_equation("dt(u) - lap(u) + lift(t1,-1) + lift(t2,-2) = - u*u")
    problem.add_equation("u(z=0) = 0")
    problem.add_equation("u(z=1) = 0")
    kw = {"matsolver": matsolver} if matsolver else {}
    solver = problem.build_solver(timestepper or d3.SBDF2,
                                  enforce_real_cadence=cadence, **kw)
    x, z = dist.local_grids(xb, zb)
    u["g"] = np.sin(np.pi * z) * (1 + 0.3 * np.cos(np.pi * x / 2))
    return solver, u, x, z

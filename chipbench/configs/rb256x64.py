"""
Configuration rb256x64: the problem of examples/rayleigh_benard.py (upstream
examples/ivp_2d_rayleigh_benard/rayleigh_benard.py), written against
`dedalus_tpu.public` only. The problem text below is a COPY of the example's,
not an import of extras/bench_problems.py: later PRs may change the program
and its helpers, and not this yardstick. Sizes, guarantees, tolerances and
the example's loop parameters are in rb256x64.json beside this file.
"""

import json
import pathlib

import numpy as np

SPEC = json.loads(pathlib.Path(__file__).with_suffix(".json").read_text())


def _endpoint_weights(n):
    """Values at z=Lz and z=0 of the basis functions the coefficients
    multiply. The package stores ChebyshevT data against the orthonormal
    polynomials (P_0 = 1/sqrt(pi), P_n = sqrt(2/pi) T_n: a fact of the
    data format, checked in chipbench/tests), and T_n(+-1) = (+-1)^n."""
    top = np.full(n, np.sqrt(2 / np.pi))
    top[0] = 1 / np.sqrt(np.pi)
    return top, top * (-1.0) ** np.arange(n)


class Deployment:
    """What `build` hands the harness: the solver, and this
    configuration's own way of reading and checking it."""

    def __init__(self, solver, fields, params):
        self.solver = solver
        self.fields = fields
        self.params = params
        self.fixed_dt = SPEC["fixed_dt"]

    def compared(self):
        """Coefficients the reference comparison reads (b, as float64)."""
        return np.asarray(self.fields["b"]["c"], dtype=np.float64)

    def invariants(self):
        """{name: (value, tolerance)}. Wall values in plain NumPy from the
        pulled coefficients, mode by mode (a Chebyshev series at its
        endpoints is a weighted sum((+-1)^n c_n); only the Fourier mean,
        coefficient 0, may differ from zero there), and the
        enforced continuity equation through the program's own operators
        as chip_smoke.py computes it (bare div(u) carries the tau term),
        over max(1, max|grad_u|)."""
        import dedalus_tpu.public as d3
        f, Lz = self.fields, self.params["Lz"]
        tol = SPEC["tolerances"]
        b = np.asarray(f["b"]["c"], dtype=np.float64)
        u = np.asarray(f["u"]["c"], dtype=np.float64)
        top, bottom = _endpoint_weights(b.shape[-1])
        b_bottom = b @ bottom
        b_bottom[0] -= Lz
        wall = max(np.abs(b_bottom).max(), np.abs(b @ top).max(),
                   np.abs(u @ bottom).max(), np.abs(u @ top).max())
        coords = f["u"].tensorsig[0]
        zbasis = f["b"].domain.bases[1]
        _, ez = coords.unit_vector_fields(f["b"].dist)
        grad_u = d3.grad(f["u"]) + ez * d3.Lift(
            f["tau_u1"], zbasis.derivative_basis(1), -1)
        amax = lambda op: np.abs(np.asarray(  # noqa: E731
            op.evaluate()["g"], dtype=np.float64)).max()
        # relative to the largest velocity gradient once the flow has one:
        # f32 rounding of the terms that cancel grows with them (v5e, PR 23:
        # 4e-6 to 7e-6 absolute in developed convection, |grad u| ~ 1e2)
        continuity = amax(d3.trace(grad_u) + f["tau_p"]) \
            / max(1.0, amax(grad_u))
        return {
            "wall_bc": (float(wall), tol["wall_bc"]["value"]),
            "continuity": (float(continuity), tol["continuity"]["value"]),
        }

    def loop(self, initial_dt, output_dir, output_sim_dt_scale=1.0):
        """The pieces of the example's main loop (rayleigh_benard.py:66-90)
        with its own parameters: the snapshots handler, the CFL and the
        flow property. The harness runs the loop body itself, so that it
        can put its annotations around each call."""
        import dedalus_tpu.public as d3
        p, f, solver = SPEC["loop"], self.fields, self.solver
        snap = p["snapshots"]
        snapshots = solver.evaluator.add_file_handler(
            str(pathlib.Path(output_dir) / "snapshots"),
            sim_dt=snap["sim_dt"] * output_sim_dt_scale,
            max_writes=snap["max_writes"])
        snapshots.add_task(f["b"], name="buoyancy")
        snapshots.add_task(-d3.div(d3.skew(f["u"])), name="vorticity")
        cfl = d3.CFL(solver, initial_dt=initial_dt, **p["cfl"])
        cfl.add_velocity(f["u"])
        flow = d3.GlobalFlowProperty(solver, cadence=p["flow"]["cadence"])
        flow.add_property(np.sqrt(f["u"] @ f["u"]) / self.params["nu"],
                          name="Re")
        return {"cfl": cfl, "max_dt": p["cfl"]["max_dt"],
                "read": lambda: flow.max("Re"),
                "read_every": p["flow"]["read_every"]}


def build(seed, mesh=None, dtype=None, size=None):
    """The example's script from `# Parameters` to `# Initial conditions`,
    its seed 42 included: `seed` is accepted and unused, so that every run
    follows one trajectory (rb256x64.json, `assumed.seed`, says why).
    `dtype` is the reference's way in (float64 on the CPU); `size` the
    CPU rehearsal's."""
    import dedalus_tpu.public as d3
    sizes = dict(SPEC["sizes"], **(size or {}))
    Lx, Lz = sizes["Lx"], sizes["Lz"]
    Nx, Nz = sizes["Nx"], sizes["Nz"]
    Rayleigh, Prandtl = sizes["Rayleigh"], sizes["Prandtl"]
    dealias = sizes["dealias"]
    timestepper = getattr(d3, sizes["timestepper"])
    dtype = np.dtype(dtype or sizes["dtype"]).type

    coords = d3.CartesianCoordinates('x', 'z')
    dist = d3.Distributor(coords, dtype=dtype, mesh=mesh)
    xbasis = d3.RealFourier(coords['x'], size=Nx, bounds=(0, Lx), dealias=dealias)
    zbasis = d3.ChebyshevT(coords['z'], size=Nz, bounds=(0, Lz), dealias=dealias)

    p = dist.Field(name='p', bases=(xbasis, zbasis))
    b = dist.Field(name='b', bases=(xbasis, zbasis))
    u = dist.VectorField(coords, name='u', bases=(xbasis, zbasis))
    tau_p = dist.Field(name='tau_p')
    tau_b1 = dist.Field(name='tau_b1', bases=xbasis)
    tau_b2 = dist.Field(name='tau_b2', bases=xbasis)
    tau_u1 = dist.VectorField(coords, name='tau_u1', bases=xbasis)
    tau_u2 = dist.VectorField(coords, name='tau_u2', bases=xbasis)

    kappa = (Rayleigh * Prandtl)**(-1/2)
    nu = (Rayleigh / Prandtl)**(-1/2)
    x, z = dist.local_grids(xbasis, zbasis)
    ex, ez = coords.unit_vector_fields(dist)
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
    solver = problem.build_solver(
        timestepper, matsolver=None if matsolver == "auto" else matsolver)
    if mesh is not None:
        from dedalus_tpu.parallel import distribute_solver
        distribute_solver(solver, mesh)

    b.fill_random('g', seed=sizes["ic_seed"], distribution='normal', scale=1e-3)
    b['g'] *= z * (Lz - z)
    b['g'] += Lz - z

    fields = {"p": p, "b": b, "u": u, "tau_p": tau_p, "tau_u1": tau_u1}
    return Deployment(solver, fields, {"Lz": Lz, "nu": nu})

"""
Configuration shear512: equations, parameters and initial conditions of
examples/shear_flow.py (upstream examples/ivp_2d_shear_flow/shear_flow.py) at
the driver's progression size 512^2 (BASELINE.json configs[1]), written
against `dedalus_tpu.public` only. A COPY of the example's problem text, not
an import of benchmarks/progression.py. Sizes, guarantees, tolerances and
the example's loop parameters are in shear512.json beside this file.
"""

import json
import pathlib

import numpy as np

SPEC = json.loads(pathlib.Path(__file__).with_suffix(".json").read_text())


def _ddx(c, axis, length):
    """d/dx of RealFourier coefficients along `axis` in plain NumPy. The
    basis keeps mode n as the pair (2n, 2n+1); differentiation maps the
    pair (a, b) to k (-b, a) or k (b, -a) according to the sign convention
    of the second function. Which of the two does not matter for a check
    that a sum of such derivatives vanishes, as long as every axis uses
    the same one."""
    c = np.moveaxis(c, axis, -1)
    k = 2 * np.pi * np.arange(c.shape[-1] // 2) / length
    out = np.empty_like(c)
    out[..., 0::2] = -k * c[..., 1::2]
    out[..., 1::2] = k * c[..., 0::2]
    return np.moveaxis(out, -1, axis)


class Deployment:
    """What `build` hands the harness: the solver, and this
    configuration's own way of reading and checking it."""

    def __init__(self, solver, fields, params):
        self.solver = solver
        self.fields = fields
        self.params = params
        self.fixed_dt = SPEC["fixed_dt"]

    def compared(self):
        """Coefficients the reference comparison reads (s, as float64)."""
        return np.asarray(self.fields["s"]["c"], dtype=np.float64)

    def invariants(self):
        """{name: (value, tolerance)}, in plain NumPy on the pulled
        coefficients: i k.u_k = 0 mode by mode, against the largest
        single term |k u_k|; the pressure gauge (mean p) and tau_p."""
        f, tol = self.fields, SPEC["tolerances"]
        u = np.asarray(f["u"]["c"], dtype=np.float64)
        dxu = _ddx(u[0], 0, self.params["Lx"])
        dzw = _ddx(u[1], 1, self.params["Lz"])
        scale = max(np.abs(dxu).max(), np.abs(dzw).max())
        divergence = np.abs(dxu + dzw).max() / scale
        p00 = abs(float(np.asarray(f["p"]["c"], dtype=np.float64)[0, 0]))
        tau = float(np.abs(np.asarray(f["tau_p"]["c"],
                                      dtype=np.float64)).max())
        return {
            "divergence": (float(divergence), tol["divergence"]["value"]),
            "gauge": (max(p00, tau), tol["gauge"]["value"]),
        }

    def loop(self, initial_dt, output_dir, output_sim_dt_scale=1.0):
        """The pieces of the example's main loop (shear_flow.py:62-80, with
        the snapshots handler of the upstream script)."""
        import dedalus_tpu.public as d3
        p, f, solver = SPEC["loop"], self.fields, self.solver
        snap = p["snapshots"]
        snapshots = solver.evaluator.add_file_handler(
            str(pathlib.Path(output_dir) / "snapshots"),
            sim_dt=snap["sim_dt"] * output_sim_dt_scale,
            max_writes=snap["max_writes"])
        snapshots.add_task(f["s"], name="tracer")
        snapshots.add_task(f["p"], name="pressure")
        snapshots.add_task(-d3.div(d3.skew(f["u"])), name="vorticity")
        cfl = d3.CFL(solver, initial_dt=initial_dt, **p["cfl"])
        cfl.add_velocity(f["u"])
        return {"cfl": cfl, "max_dt": p["cfl"]["max_dt"],
                "read": None, "read_every": 0}


def build(seed, mesh=None, dtype=None, size=None):
    """The example's script from `# Parameters` to `# Solver`. The source's
    initial conditions are deterministic: `seed` is accepted and unused."""
    import dedalus_tpu.public as d3
    sizes = dict(SPEC["sizes"], **(size or {}))
    Lx, Lz = sizes["Lx"], sizes["Lz"]
    Nx, Nz = sizes["Nx"], sizes["Nz"]
    Reynolds, Schmidt = sizes["Reynolds"], sizes["Schmidt"]
    dealias = sizes["dealias"]
    timestepper = getattr(d3, sizes["timestepper"])
    dtype = np.dtype(dtype or sizes["dtype"]).type

    coords = d3.CartesianCoordinates('x', 'z')
    dist = d3.Distributor(coords, dtype=dtype, mesh=mesh)
    xbasis = d3.RealFourier(coords['x'], size=Nx, bounds=(0, Lx), dealias=dealias)
    zbasis = d3.RealFourier(coords['z'], size=Nz, bounds=(-Lz/2, Lz/2), dealias=dealias)

    p = dist.Field(name='p', bases=(xbasis, zbasis))
    s = dist.Field(name='s', bases=(xbasis, zbasis))
    u = dist.VectorField(coords, name='u', bases=(xbasis, zbasis))
    tau_p = dist.Field(name='tau_p')

    nu = 1 / Reynolds
    D = nu / Schmidt
    x, z = dist.local_grids(xbasis, zbasis)
    ex, ez = coords.unit_vector_fields(dist)

    problem = d3.IVP([u, s, p, tau_p], namespace=locals())
    problem.add_equation("dt(u) + grad(p) - nu*lap(u) = - u@grad(u)")
    problem.add_equation("dt(s) - D*lap(s) = - u@grad(s)")
    problem.add_equation("div(u) + tau_p = 0")
    problem.add_equation("integ(p) = 0")

    ug = np.zeros((2,) + tuple(np.broadcast_shapes((Nx, 1), (1, Nz))))
    ug[0] = 1/2 + 1/2 * (np.tanh((z-0.5)/0.1) - np.tanh((z+0.5)/0.1))
    ug[1] = (0.1 * np.sin(2*np.pi*x/Lx) * np.exp(-(z-0.5)**2/0.01)
             + 0.1 * np.sin(2*np.pi*x/Lx) * np.exp(-(z+0.5)**2/0.01))
    u['g'] = ug
    s['g'] = 1/2 + 1/2 * (np.tanh((z-0.5)/0.1) - np.tanh((z+0.5)/0.1))

    matsolver = sizes["matsolver"]
    solver = problem.build_solver(
        timestepper, matsolver=None if matsolver == "auto" else matsolver)
    if mesh is not None:
        from dedalus_tpu.parallel import distribute_solver
        distribute_solver(solver, mesh)

    fields = {"u": u, "s": s, "p": p, "tau_p": tau_p}
    return Deployment(solver, fields, {"Lx": Lx, "Lz": Lz, "nu": nu})

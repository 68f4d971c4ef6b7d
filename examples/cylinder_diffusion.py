"""
Heat diffusion in a periodic cylinder (DirectProduct geometry: Fourier z x
disk), with an exact Fourier-Bessel decay check.

The initial temperature J0(j01 r / R) cos(kz z) is an exact eigenmode of
the Laplacian with homogeneous edge conditions, decaying at rate
kz^2 + (j01 / R)^2 — the cylinder analogue of the reference's heat-equation
oracle tests (no reference example exists for cylinders; geometry from
reference tests/test_cylinder_calculus.py).

Run: python examples/cylinder_diffusion.py
"""

import pathlib
import sys

import numpy as np
from scipy.special import j0, jn_zeros

sys.path.insert(0, str(pathlib.Path(__file__).parent.parent))
import jax  # noqa: E402

# f64 end-to-end (x64 is safe everywhere; f64 Fourier paths route through
# MMT matmuls on TPU automatically)
jax.config.update("jax_enable_x64", True)
import dedalus_tpu.public as d3  # noqa: E402

# Parameters
length, radius = 2.0, 1.5
Nz, Nphi, Nr = 16, 16, 32
dtype = np.float64
timestep = 2e-4
stop_iteration = 200

# Bases
cz = d3.Coordinate("z")
cp = d3.PolarCoordinates("phi", "r")
coords = d3.DirectProduct(cz, cp)
dist = d3.Distributor(coords, dtype=dtype)
zbasis = d3.RealFourier(cz, size=Nz, bounds=(0, length), dealias=3 / 2)
disk = d3.DiskBasis(cp, shape=(Nphi, Nr), dtype=dtype, radius=radius,
                    dealias=3 / 2)

# Fields
u = dist.Field(name="u", bases=(zbasis, disk))
tau = dist.Field(name="tau", bases=(zbasis, disk.edge))

# Problem: dt(u) - lap(u) + lift(tau) = 0 with u(r=R) = 0
lift = lambda A: d3.Lift(A, disk, -1)
problem = d3.IVP([u, tau], namespace=locals())
problem.add_equation("dt(u) - lap(u) + lift(tau) = 0")
problem.add_equation(f"u(r={radius}) = 0")

# Initial condition: exact eigenmode
solver = problem.build_solver(d3.RK443)
solver.stop_iteration = stop_iteration
z, phi, r = dist.local_grids(zbasis, disk)
kz = 2 * np.pi / length
j01 = jn_zeros(0, 1)[0]
u["g"] = j0(j01 * r / radius) * np.cos(kz * z) + 0 * phi
u0 = np.asarray(u["g"]).copy()

# Main loop
solver.dt = timestep
solver.evolve(log_cadence=50)

# Check against the exact decay rate
rate = kz ** 2 + (j01 / radius) ** 2
exact = u0 * np.exp(-rate * solver.sim_time)
err = np.abs(np.asarray(u["g"]) - exact).max() / np.abs(u0).max()
print(f"t = {solver.sim_time:.4f}: max relative error vs exact decay "
      f"= {err:.3e}")
assert err < 1e-6

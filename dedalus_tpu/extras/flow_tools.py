"""
Flow diagnostics and adaptive timestep control
(reference: dedalus/extras/flow_tools.py).
"""

import logging
import numpy as np

from ..tools import tracing

logger = logging.getLogger(__name__)


def _axis_profile(values, axis, ndim):
    """Reshape a 1D per-axis profile for broadcasting over the grid."""
    shape = [1] * ndim
    shape[axis] = np.size(values)
    return np.reshape(values, shape)


def interval_cfl_spacing(basis):
    """
    Local grid spacing of an interval basis at dealias scales, rescaled
    by dealias so the frequency reflects the nominal resolution
    (reference: core/basis.py:6091 CartesianAdvectiveCFL.cfl_spacing).
    """
    from ..core.basis import Jacobi, FourierBase
    dealias = basis.dealias if np.isscalar(basis.dealias) else basis.dealias[0]
    grid = basis.global_grid(dealias)
    N = grid.size
    if isinstance(basis, FourierBase):
        # uniform: dealias * (2 pi / N_dealias) * stretch
        return np.full(N, dealias * 2 * np.pi / N * basis.COV.stretch)
    if isinstance(basis, Jacobi) and basis.a0 == -0.5 and basis.b0 == -0.5:
        # Chebyshev: analytic sin(theta) spacing
        theta = np.pi * (np.arange(N) + 0.5) / N
        return dealias * basis.COV.stretch * np.sin(theta) * np.pi / N
    return dealias * (np.gradient(grid) if N > 1 else np.array([np.inf]))


def advective_cfl_frequency(u, ug, xp=np):
    """
    Advective CFL frequency of velocity field `u` with grid data `ug` on
    the dealias grid, per geometry (reference: core/basis.py:6086-6215
    *AdvectiveCFL.cfl_spacing; component conventions: polar (phi, r),
    spherical (phi, theta, r)). `xp` selects numpy (host) or jax.numpy
    (traced, for the AdvectiveCFL operator); spacing profiles are static
    numpy constants either way.
    """
    from ..core import coords as cmod
    cs = u.tensorsig[0]
    dist = u.dist
    ndim = dist.dim

    def polar_frequency(polar_cs, u_az, u_r):
        basis = u.domain.bases[dist.get_axis(polar_cs.coords[1])]
        if basis is None:
            return 0.0  # velocity constant over the polar factor
        r_axis = basis.first_axis + 1
        r = np.ravel(basis.global_grids(basis.dealias)[1])
        mmax = max(basis.shape[0] // 2 - 1, 0)
        if mmax == 0:
            az = np.array([np.inf])
        elif hasattr(basis, "radii"):  # annulus: spacing r / mmax
            az = r / mmax
        else:  # disk: spacing R / mmax
            az = np.array([basis.radius / mmax])
        dr = basis.dealias[1] * (np.gradient(r) if r.size > 1
                                 else np.array([np.inf]))
        return (xp.abs(u_az) / _axis_profile(az, r_axis, ndim)
                + xp.abs(u_r) / _axis_profile(dr, r_axis, ndim))

    def interval_frequency(coord, u_c):
        axis = dist.get_axis(coord)
        basis = u.domain.bases[axis]
        if basis is None:
            return 0.0
        dx = interval_cfl_spacing(basis)
        return xp.abs(u_c) / _axis_profile(dx, axis, ndim)

    total = 0.0
    if isinstance(cs, cmod.PolarCoordinates):
        total = polar_frequency(cs, ug[0], ug[1])
    elif isinstance(cs, cmod.DirectProduct):
        # cylinder: straight factors get interval spacings, the polar
        # factor its (azimuth, radius) spacings on its component slice
        off = 0
        for sub in cs.coordsystems:
            if isinstance(sub, cmod.PolarCoordinates):
                total = total + polar_frequency(sub, ug[off], ug[off + 1])
            elif isinstance(sub, cmod.CurvilinearCoordinateSystem):
                # an S2/spherical factor must not fall into the polar
                # formula (it would read colatitude as radius, silently)
                raise NotImplementedError(
                    "CFL spacing for this DirectProduct factor.")
            else:
                for j, coord in enumerate(sub.coords):
                    total = total + interval_frequency(coord, ug[off + j])
            off += sub.dim
    elif isinstance(cs, cmod.S2Coordinates):
        basis = u.domain.bases[dist.get_axis(cs.coords[0])]
        u_mag = xp.sqrt(ug[0] ** 2 + ug[1] ** 2)
        Lmax = basis.Lmax
        k = np.sqrt(Lmax * (Lmax + 1)) if Lmax > 0 else 0.0
        total = u_mag * (k / basis.radius)
    elif isinstance(cs, cmod.SphericalCoordinates):
        basis = u.domain.bases[dist.get_axis(cs.coords[2])]
        r_axis = basis.first_axis + 2
        r = np.ravel(basis.global_grids(basis.dealias)[2])
        Lmax = basis.shape[1] - 1
        k = np.sqrt(Lmax * (Lmax + 1)) if Lmax > 0 else 0.0
        u_mag = xp.sqrt(ug[0] ** 2 + ug[1] ** 2)
        if hasattr(basis, "radii"):  # shell: angular spacing r / k
            ang = (k / _axis_profile(r, r_axis, ndim)) if k else 0.0
            total = u_mag * ang
        else:  # ball: angular spacing R / k
            total = u_mag * (k / basis.radius)
        dr = basis.dealias[2] * (np.gradient(r) if r.size > 1
                                 else np.array([np.inf]))
        total = total + xp.abs(ug[2]) / _axis_profile(dr, r_axis, ndim)
    else:
        # Cartesian: per-axis interval spacings
        for i, coord in enumerate(cs.coords):
            total = total + interval_frequency(coord, ug[i])
    if np.isscalar(total):
        total = xp.zeros(ug.shape[1:])
    return total


class GlobalArrayReducer:
    """Global reductions over grid data (reference: extras/flow_tools.py:15).
    Single-controller JAX arrays are already global; reductions are direct."""

    def __init__(self, comm=None, dtype=np.float64):
        self.dtype = dtype

    def reduce_scalar(self, local_scalar, mpi_reduce_op=None):
        return local_scalar

    def global_min(self, data, empty=np.inf):
        return np.min(data) if data.size else empty

    def global_max(self, data, empty=-np.inf):
        return np.max(data) if data.size else empty

    def global_mean(self, data):
        return np.mean(data)


class GlobalFlowProperty:
    """Scheduled scalar diagnostics of flow expressions
    (reference: extras/flow_tools.py:64)."""

    def __init__(self, solver, cadence=1):
        self.solver = solver
        self.cadence = cadence
        self.reducer = GlobalArrayReducer()
        self.properties = solver.evaluator.add_dictionary_handler(iter=cadence)

    def add_property(self, property, name):
        self.properties.add_task(property, name=name)

    def min(self, name):
        return self.reducer.global_min(self.properties[name])

    def max(self, name):
        return self.reducer.global_max(self.properties[name])

    def grid_average(self, name):
        return self.reducer.global_mean(self.properties[name])

    def volume_integral(self, name):
        # tasks are integrals already when requested via integ(...)
        return np.sum(self.properties[name])

    def report(self, names):
        """
        {name: {"max", "min", "avg"}} for the given property names —
        one dict consumable by the health sink (tools/health.py attaches
        it to flight-recorder dumps via `monitor.attach_flow(flow,
        names)`). Properties that have not evaluated yet are skipped.
        """
        out = {}
        for name in names:
            try:
                data = self.properties[name]
            except KeyError:
                continue
            out[name] = {"max": float(self.reducer.global_max(data)),
                         "min": float(self.reducer.global_min(data)),
                         "avg": float(self.reducer.global_mean(data))}
        return out


class CFL:
    """
    Adaptive timestep from advective CFL frequencies
    (reference: extras/flow_tools.py:139 CFL, core/operators.py:4306
    AdvectiveCFL). Frequencies |u_i| / dx_i are computed on the grid and
    reduced to a stable timestep with safety/threshold/bounds logic
    (reference: extras/flow_tools.py:191 compute_timestep).
    """

    def __init__(self, solver, initial_dt, cadence=1, safety=1.0,
                 max_dt=np.inf, min_dt=0.0, max_change=np.inf, min_change=0.0,
                 threshold=0.0, history_size=256):
        from collections import deque
        self.solver = solver
        self.initial_dt = initial_dt
        self.cadence = cadence
        self.safety = safety
        self.max_dt = max_dt
        self.min_dt = min_dt
        self.max_change = max_change
        self.min_change = min_change
        self.threshold = threshold
        self.velocities = []
        self.frequencies = []
        self.current_dt = initial_dt
        # bounded (iteration, dt, freq_max) trail: the flight recorder's
        # dt/CFL-frequency evidence (tools/health.py dt_history)
        self.history = deque(maxlen=max(int(history_size), 1))
        self._last_freq_max = None
        monitor = getattr(solver, "health", None)
        if monitor is not None and hasattr(monitor, "attach_dt_source"):
            monitor.attach_dt_source(self)

    def add_velocity(self, velocity):
        """Register a velocity vector field for CFL frequencies
        (evaluated through the AdvectiveCFL operator's compiled path when
        the velocity is an expression; plain fields use the host path)."""
        self.velocities.append(velocity)

    def add_frequency(self, freq):
        """Register an additional frequency expression."""
        self.frequencies.append(freq)

    def compute_max_frequency(self):
        freq_max = 0.0
        for u in self.velocities:
            u.change_scales(u.domain.dealias)
            ug = np.asarray(u["g"])
            total = advective_cfl_frequency(u, ug, xp=np)
            if total.size:
                freq_max = max(freq_max, np.max(total))
        for fexpr in self.frequencies:
            field = fexpr.evaluate()
            freq_max = max(freq_max, np.max(np.abs(np.asarray(field["g"]))))
        return freq_max

    def compute_timestep(self):
        iteration = self.solver.iteration
        if iteration % self.cadence == 0:
            # the pull of u["g"] and the NumPy reduction
            with tracing.span("cfl"):
                freq_max = self.compute_max_frequency()
            self._last_freq_max = float(freq_max)
            if freq_max == 0.0:
                dt = self.max_dt
            else:
                dt = self.safety / freq_max
            dt = min(dt, self.max_dt)
            dt = max(dt, self.min_dt)
            # bounded relative change with threshold hysteresis
            if self.current_dt:
                change = dt / self.current_dt
                change = min(change, self.max_change)
                change = max(change, self.min_change)
                if abs(change - 1.0) > self.threshold:
                    self.current_dt = self.current_dt * change
            else:
                self.current_dt = dt
        self.history.append({"iteration": int(iteration),
                             "dt": float(self.current_dt),
                             "freq_max": self._last_freq_max})
        return self.current_dt

"""
The solver's scatter of X into its fields as ONE memoised compiled
program (`SolverBase._scatter_program`, the twin of `gather_fields`):
bitwise the values of `scatter_state` called op by op from the host, one
program per field set, one launch per state that is read and none for a
state nobody reads, and the same arrays under a mesh.
"""

import numpy as np
import pytest
import jax
from jax.sharding import Mesh

import dedalus_tpu.public as d3
from dedalus_tpu.core.subsystems import scatter_state, state_key
from dedalus_tpu.extras.bench_problems import build_rb_solver
from dedalus_tpu.parallel import distribute_solver
from dedalus_tpu.tools import retrace, tracing

DT = 1e-3


def advance_rb():
    """RB 32x16 f32: vector, scalar and tau variables (8 of them)."""
    solver, _ = build_rb_solver(32, 16, np.float32)
    for _ in range(2):
        solver.step(DT)
    return solver, solver.X


def advance_complex():
    """Complex-dtype advection-diffusion on ComplexFourier."""
    xc = d3.Coordinate("x")
    dist = d3.Distributor(xc, dtype=np.complex128)
    xb = d3.ComplexFourier(xc, size=32, bounds=(0, 2 * np.pi))
    u = dist.Field(name="u", bases=xb)
    dx = lambda A: d3.Differentiate(A, xc)  # noqa: E731
    problem = d3.IVP([u], namespace=locals())
    problem.add_equation("dt(u) + 1.5*dx(u) - 0.1*lap(u) = 0")
    u["g"] = np.exp(2j * dist.local_grid(xb))
    solver = problem.build_solver(d3.RK222)
    for _ in range(2):
        solver.step(DT)
    return solver, solver.X


def solve_lbvp():
    """Poisson LBVP with two tau variables; X is what `solve` scattered."""
    coords = d3.CartesianCoordinates("x", "z")
    dist = d3.Distributor(coords, dtype=np.float64)
    xb = d3.RealFourier(coords["x"], size=16, bounds=(0, 2 * np.pi))
    zb = d3.ChebyshevT(coords["z"], size=12, bounds=(0, 1))
    f = dist.Field(name="f", bases=(xb, zb))
    t1 = dist.Field(name="t1", bases=xb)
    t2 = dist.Field(name="t2", bases=xb)
    rhs = dist.Field(name="rhs", bases=(xb, zb))
    x, z = dist.local_grids(xb, zb)
    rhs["g"] = np.sin(2 * x) * z
    lift = lambda A, n: d3.Lift(A, zb.derivative_basis(2), n)  # noqa: E731
    problem = d3.LBVP([f, t1, t2], namespace=locals())
    problem.add_equation("lap(f) + lift(t1,-1) + lift(t2,-2) = rhs")
    problem.add_equation("f(z=0) = 0")
    problem.add_equation("f(z=1) = 0")
    solver = problem.build_solver()
    seen = []
    scatter_fields = solver.scatter_fields
    solver.scatter_fields = lambda X: (seen.append(X), scatter_fields(X))
    solver.solve()
    return solver, seen[0]


@pytest.mark.parametrize("advance", [advance_rb, advance_complex, solve_lbvp])
def test_program_is_bitwise_the_eager_scatter(advance):
    solver, X = advance()
    eager = scatter_state(solver.layout, solver.variables, X)
    assert np.abs(np.asarray(X)).max() > 0
    for v in solver.variables:
        got = np.asarray(v.coeff_data())
        want = np.asarray(eager[state_key(v)])
        assert got.dtype == want.dtype and got.shape == want.shape
        assert np.array_equal(got, want), v.name
    assert list(solver._scatter_programs) == [
        tuple(state_key(v) for v in solver.variables)]


def test_one_program_one_launch_per_state_read():
    """Three steps with a DictionaryHandler read each, then steps nobody
    reads: one memoised program, no retrace, one `state/scatter` span per
    state that is read."""
    solver, _ = build_rb_solver(32, 16, np.float32)
    solver.warmup_iterations = 0
    u = next(v for v in solver.problem.variables if v.name == "u")
    flow = d3.GlobalFlowProperty(solver, cadence=1)
    flow.add_property(np.sqrt(u @ u), name="Re")
    was_on = tracing.enabled()
    tracing.enable()
    ring = tracing.recorder()
    try:
        ring.clear()
        solver.step(DT)
        assert flow.max("Re") >= 0
        retraces = retrace.sentinel.post_arm_retraces
        program = solver._scatter_programs[
            tuple(state_key(v) for v in solver.variables)]
        for _ in range(2):
            solver.step(DT)
            assert flow.max("Re") >= 0
            # a second field of the same state: served from the one launch
            solver.variables[1].coeff_data()
        names = [s.name for s in ring.spans()]
        assert names.count("step") == 3
        assert names.count("state/scatter") == 3
        assert retrace.sentinel.post_arm_retraces == retraces
        assert list(solver._scatter_programs.values()) == [program]
        assert program._retrace_state.count == 1
        # steps nobody reads launch nothing
        solver.evaluator.handlers.remove(flow.properties)
        ring.clear()
        for _ in range(3):
            solver.step(DT)
        solver.step_many(2, DT)
        assert "state/scatter" not in [s.name for s in ring.spans()]
        assert program._retrace_state.count == 1
    finally:
        tracing.disable()
        ring.clear()
        if was_on:
            tracing.enable()


@pytest.mark.distributed
@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs >= 4 devices")
def test_sharded_scatter_feeds_the_task_program():
    """Pencil-sharded over 4 virtual CPU devices: the program's arrays are
    bitwise the eager scatter of the same sharded X, equal to the
    unsharded run, and the handler task program takes them as they are."""
    def run(mesh):
        solver, _ = build_rb_solver(32, 16, np.float32)
        if mesh is not None:
            distribute_solver(solver, mesh)
        u = next(v for v in solver.problem.variables if v.name == "u")
        flow = d3.GlobalFlowProperty(solver, cadence=1)
        flow.add_property(np.sqrt(u @ u), name="Re")
        for _ in range(3):
            solver.step(DT)
        return solver, flow

    ref, ref_flow = run(None)
    sh, sh_flow = run(Mesh(np.array(jax.devices()[:4]), ("x",)))
    assert len(sh.X.sharding.device_set) == 4
    eager = scatter_state(sh.layout, sh.variables, sh.X)
    for v_ref, v_sh in zip(ref.variables, sh.variables):
        got = np.asarray(v_sh.coeff_data())
        assert np.array_equal(got, np.asarray(eager[state_key(v_sh)]))
        assert np.array_equal(got, np.asarray(v_ref.coeff_data()))
    assert sh_flow.properties._task_cache["runner"].mode == "compiled"
    np.testing.assert_allclose(sh_flow.max("Re"), ref_flow.max("Re"),
                               rtol=1e-5)
    # and back through the gather program
    assert np.array_equal(np.asarray(sh.gather_fields()), np.asarray(sh.X))
    assert np.asarray(sh.variables[2].require_grid_space()).shape[0] == 2

"""
Emulated-f64 (double-double) IVP stepping oracles.

Each test runs the SAME problem twice: native f64 (the CPU reference
path, matching the reference framework's precision) and the DDIVPRunner
f32-pair path. The dd trajectory must track the f64 trajectory far below
the f32 error floor (~1e-7): agreement at ~1e-12 proves transforms,
matvecs, RHS nonlinearities, and the refined implicit solve all run at
emulated-f64 precision. (VERDICT round-4 item 3.)
"""

import numpy as np
import pytest

import dedalus_tpu.public as d3
from dedalus_tpu.core.ddstep import DDIVPRunner, DDUnsupportedError
from dedalus_tpu.tools.config import config


@pytest.fixture(autouse=True)
def dense_path():
    old = config["linear algebra"].get("MATRIX_SOLVER", "auto")
    config["linear algebra"]["MATRIX_SOLVER"] = "dense"
    yield
    config["linear algebra"]["MATRIX_SOLVER"] = old


def build_heat(N, dtype):
    xcoord = d3.Coordinate("x")
    dist = d3.Distributor(xcoord, dtype=dtype)
    xbasis = d3.RealFourier(xcoord, size=N, bounds=(0, 2 * np.pi))
    u = dist.Field(name="u", bases=xbasis)
    kappa = 0.1
    dx = lambda A: d3.Differentiate(A, xcoord)
    problem = d3.IVP([u], namespace=locals())
    problem.add_equation("dt(u) - kappa*dx(dx(u)) = 0")
    x = dist.local_grids(xbasis)[0]
    return problem, u, x


def test_heat_dd_matches_f64():
    N, dt, n_steps = 64, 1e-3, 200
    problem, u, x = build_heat(N, np.float64)
    u["g"] = np.sin(3 * x) + 0.5 * np.cos(7 * x)
    solver = problem.build_solver(d3.SBDF2)
    runner = DDIVPRunner(solver)
    for _ in range(n_steps):
        solver.step(dt)
        runner.step(dt)
    X64 = np.asarray(solver.X, dtype=np.float64)
    Xdd = runner.state_f64()
    scale = np.abs(X64).max()
    assert np.abs(Xdd - X64).max() / scale < 1e-11
    # and both must match the exact decay
    runner.push_state()
    t = n_steps * dt
    exact = (np.exp(-0.1 * 9 * t) * np.sin(3 * x)
             + 0.5 * np.exp(-0.1 * 49 * t) * np.cos(7 * x))
    assert np.abs(u["g"] - exact).max() < 1e-5   # SBDF2 O(dt^2) time error


def build_kdv(N, dtype):
    xcoord = d3.Coordinate("x")
    dist = d3.Distributor(xcoord, dtype=dtype)
    xbasis = d3.RealFourier(xcoord, size=N, bounds=(0, 10), dealias=3 / 2)
    u = dist.Field(name="u", bases=xbasis)
    a, b = 1e-4, 2e-4
    dx = lambda A: d3.Differentiate(A, xcoord)
    problem = d3.IVP([u], namespace=locals())
    problem.add_equation("dt(u) - a*dx(dx(u)) - b*dx(dx(dx(u))) = - u*dx(u)")
    x = dist.local_grids(xbasis)[0]
    n = 20
    u["g"] = np.log(1 + np.cosh(n) ** 2 / np.cosh(n * (x - 3)) ** 2) / (2 * n)
    return problem, u


def test_kdv_dd_matches_f64():
    # nonlinear RHS: dd transforms + dealiased product + dd matvec chain
    N, dt, n_steps = 256, 5e-4, 100
    problem, u = build_kdv(N, np.float64)
    solver = problem.build_solver(d3.SBDF2)
    runner = DDIVPRunner(solver)
    for _ in range(n_steps):
        solver.step(dt)
        runner.step(dt)
    X64 = np.asarray(solver.X, dtype=np.float64)
    Xdd = runner.state_f64()
    scale = np.abs(X64).max()
    assert np.abs(Xdd - X64).max() / scale < 1e-10


def test_kdv_dd_mass_conservation():
    # f32 stepping drifts mass at ~1e-8 (BENCHMARKS.md); dd must hold
    # f64-grade drift. Mass = the mean (cos-0) Fourier coefficient.
    N, dt, n_steps = 256, 5e-4, 200
    problem, u = build_kdv(N, np.float64)
    solver = problem.build_solver(d3.SBDF2)
    runner = DDIVPRunner(solver)
    mass0 = float(np.mean(u["g"]))   # uniform-grid mean = integral / L
    for _ in range(n_steps):
        runner.step(dt)
    runner.push_state()
    mass1 = float(np.mean(u["g"]))
    assert abs(mass1 - mass0) / abs(mass0) < 1e-12


def test_rk222_dd_matches_f64():
    # Runge-Kutta IMEX path: dd tracks the native-f64 RK trajectory
    N, dt, n_steps = 64, 1e-3, 100
    problem, u, x = build_heat(N, np.float64)
    u["g"] = np.sin(3 * x) + 0.5 * np.cos(7 * x)
    solver = problem.build_solver(d3.RK222)
    runner = DDIVPRunner(solver)
    for _ in range(n_steps):
        solver.step(dt)
        runner.step(dt)
    X64 = np.asarray(solver.X, dtype=np.float64)
    Xdd = runner.state_f64()
    assert np.abs(Xdd - X64).max() / np.abs(X64).max() < 1e-11


def test_rk443_kdv_dd_matches_f64():
    # higher-order RK + nonlinear RHS through the dd interpreter
    N, dt, n_steps = 128, 1e-3, 50
    problem, u = build_kdv(N, np.float64)
    solver = problem.build_solver(d3.RK443)
    runner = DDIVPRunner(solver)
    for _ in range(n_steps):
        solver.step(dt)
        runner.step(dt)
    X64 = np.asarray(solver.X, dtype=np.float64)
    Xdd = runner.state_f64()
    assert np.abs(Xdd - X64).max() / np.abs(X64).max() < 1e-10


def test_forcing_update_mid_run():
    # non-variable RHS fields must be dynamic inputs: updating a forcing
    # between steps changes the trajectory (review finding — baking them
    # as trace-time constants silently froze the first step's forcing)
    xcoord = d3.Coordinate("x")
    dist = d3.Distributor(xcoord, dtype=np.float64)
    xb = d3.RealFourier(xcoord, size=32, bounds=(0, 2 * np.pi))
    u = dist.Field(name="u", bases=xb)
    F = dist.Field(name="F", bases=xb)
    dx = lambda A: d3.Differentiate(A, xcoord)
    problem = d3.IVP([u], namespace=locals())
    problem.add_equation("dt(u) - dx(dx(u)) = F")
    x = dist.local_grids(xb)[0]
    F["g"] = np.sin(x)
    solver = problem.build_solver(d3.SBDF2)
    runner = DDIVPRunner(solver)
    runner.step(1e-3)
    X1 = runner.state_f64().copy()
    F["g"] = 5 * np.cos(2 * x)
    runner.step(1e-3)
    X2 = runner.state_f64()
    # rerun with the forcing never updated: trajectories must differ
    solver2 = problem.build_solver(d3.SBDF2)
    F["g"] = np.sin(x)
    runner2 = DDIVPRunner(solver2)
    runner2.step(1e-3)
    assert np.abs(runner2.state_f64() - X1).max() < 1e-12
    runner2.step(1e-3)
    assert np.abs(runner2.state_f64() - X2).max() > 1e-6


def test_unsupported_rhs_detected_at_construction():
    # a dd-unsupported RHS node must raise at DDIVPRunner construction
    # (where the solver's auto-wiring can fall back to native f64)
    xcoord = d3.Coordinate("x")
    dist = d3.Distributor(xcoord, dtype=np.float64)
    xb = d3.RealFourier(xcoord, size=32, bounds=(0, 2 * np.pi))
    u = dist.Field(name="u", bases=xb)
    sin = np.sin
    problem = d3.IVP([u], namespace=locals())
    problem.add_equation("dt(u) = sin(u)")   # UnaryGridFunction: no dd
    solver = problem.build_solver(d3.SBDF2)
    with pytest.raises(DDUnsupportedError):
        DDIVPRunner(solver)


def test_rayleigh_benard_dd_matches_f64():
    """The flagship 2-D problem end-to-end in dd: vector fields, taus,
    LHS NCCs, Lift, DotProduct RHS, RK222 — tracks native f64."""
    from dedalus_tpu.extras.bench_problems import build_rb_solver
    solver, b = build_rb_solver(32, 8, np.float64)
    runner = DDIVPRunner(solver)
    dt = 1e-3
    for _ in range(10):
        solver.step(dt)
        runner.step(dt)
    X64 = np.asarray(solver.X, dtype=np.float64)
    Xdd = runner.state_f64()
    # tau/pin conditioning at this tiny resolution sets the IR floor at
    # ~1e-10 relative; still ~1000x below the f32 error floor
    assert np.abs(Xdd - X64).max() / np.abs(X64).max() < 1e-9


# ------------------------------------------- the sweeps' inner f32 solves

RB_STEPS, RB_DT = 10, 1e-3


@pytest.fixture(scope="module")
def rb_native():
    """Native float64 RB 32x8 after RB_STEPS steps (LU, the CPU's own)."""
    from dedalus_tpu.extras.bench_problems import build_rb_solver
    solver, _ = build_rb_solver(32, 8, np.float64)
    for _ in range(RB_STEPS):
        solver.step(RB_DT)
    return np.asarray(solver.X, dtype=np.float64)


def _rb_runner(monkeypatch, inner):
    """A runner over RB 32x8 built with the class a TPU takes for 64-bit
    variables, `BatchedInverseRefined`: with the rule's own choice of
    inner solver (`plain`) or with the rule switched off (`refined`: the
    float32 solves as they were before PR 36)."""
    from dedalus_tpu.core import ddstep
    from dedalus_tpu.extras.bench_problems import build_rb_solver
    solver, _ = build_rb_solver(32, 8, np.float64,
                                matsolver="BatchedInverseRefined")
    if inner == "refined":
        monkeypatch.setattr(ddstep, "_inner_ops", lambda ops: ops)
    return DDIVPRunner(solver)


def _tracks_native(monkeypatch, rb_native, inner, cls, reads):
    runner = _rb_runner(monkeypatch, inner)
    for _ in range(RB_STEPS):
        runner.step(RB_DT)
    err = np.abs(runner.state_f64() - rb_native).max() \
        / np.abs(rb_native).max()
    assert err < 1e-9     # test_rayleigh_benard_dd_matches_f64's own
    # RK222: two stages of one first solve and two corrections
    counted = runner.counters()
    assert counted["f32_solver"] == cls
    assert counted["f32_stack_reads_per_step"] == reads
    assert counted["refine"] == 2 and counted["int8_dots_per_step"] > 0


def _worst_pencil_errors(runner, sweeps):
    """Worst pencil's |x - x*|_inf / |x*|_inf of the refined solve of
    (M + dt gamma L) x = M X after each count of dd sweeps, x* from
    numpy.linalg.solve in float64 (PERF.md, PR 36: the table at 256x64)."""
    from dedalus_tpu.core.ddstep import _dd_scalar
    from dedalus_tpu.libraries.doubledouble import dd_from_f64, dd_to_f64
    gamma = float(runner.scheme.H[1, 1])
    lhs = runner._rk_factor([_dd_scalar(RB_DT * gamma)])[0]
    rng = np.random.default_rng(0)
    x = rng.standard_normal(runner.shape)
    A = runner.M_host + RB_DT * gamma * runner.L_host
    rhs = np.einsum("gij,gj->gi", A, x)
    want = np.linalg.solve(A, rhs[..., None])[..., 0]
    out = []
    for n in sweeps:
        got = dd_to_f64(runner._solve_ir(lhs, dd_from_f64(rhs), n))
        each = np.abs(got - want).max(axis=-1) / np.abs(want).max(axis=-1)
        out.append(float(each.max()))
    return out


def _sweeps_contract_alike(monkeypatch):
    sweeps = (0, 1, 2, 3)
    plain = _worst_pencil_errors(_rb_runner(monkeypatch, "plain"), sweeps)
    refined = _worst_pencil_errors(_rb_runner(monkeypatch, "refined"),
                                   sweeps)
    print("plain", plain, "refined", refined)
    # an unrefined float32 answer (cond 1e5 and more at this size: the
    # tau lines), then orders of magnitude a dd sweep down to the floor
    assert 1e-9 < plain[0] < 1e-1
    assert plain[1] < 1e-3 * plain[0] and plain[2] <= plain[1]
    # what the float32 sweeps inside each solve bought after the route's
    # two dd sweeps: nothing (both at the floor cond * the residual's
    # rounding sets)
    assert plain[2] <= 2 * refined[2]
    assert plain[2] < 1e-9


def _the_rule(monkeypatch):
    from dedalus_tpu.core.ddstep import _inner_ops
    from dedalus_tpu.libraries import matsolvers, pencilops, solvecomp
    native = solvecomp.SolvePlan()
    # the default refinement of a 64-bit TPU build: the route is that
    refined = pencilops.DenseOps("BatchedInverseRefined", solve_plan=native)
    assert issubclass(refined.solver_cls, matsolvers.BatchedInverseRefined)
    inner = _inner_ops(refined)
    assert inner.solver_cls is matsolvers.BatchedInverse
    assert inner.kind == "dense" and inner is not refined
    # any other class is used as it is
    for name in ("BatchedLUFactorized", "BatchedInverse"):
        ops = pencilops.DenseOps(name, solve_plan=native)
        assert _inner_ops(ops) is ops
    # a [precision] ladder is the user's statement
    ladder = pencilops.DenseOps(
        None, solve_plan=solvecomp.SolvePlan(dtype="f32", sweeps=1))
    assert issubclass(ladder.solver_cls, matsolvers.BatchedInverseRefined)
    assert _inner_ops(ladder) is ladder
    # and the runner takes what the rule gives, the solver keeps its own
    runner = _rb_runner(monkeypatch, "plain")
    assert runner.f32.solver_cls is matsolvers.BatchedInverse
    assert issubclass(runner.solver.ops.solver_cls,
                      matsolvers.BatchedInverseRefined)
    assert runner.counters()["f32_solver"] == "BatchedInverse"


INNER_CASES = {
    "plain_tracks_f64": lambda mp, ref: _tracks_native(
        mp, ref, "plain", "BatchedInverse", 6),
    "refined_tracks_f64": lambda mp, ref: _tracks_native(
        mp, ref, "refined", "BatchedInverseLadder", 42),
    "sweeps": lambda mp, ref: _sweeps_contract_alike(mp),
    "rule": lambda mp, ref: _the_rule(mp),
}


@pytest.mark.parametrize("case", sorted(INNER_CASES))
def test_inner_float32_solves(case, monkeypatch, rb_native):
    """The float32 solves inside the dd sweeps are plain stored-inverse
    products where the solver's class would refine in float32 (PR 36)."""
    INNER_CASES[case](monkeypatch, rb_native)

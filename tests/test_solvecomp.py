"""
Solve compositions + precision ladder (libraries/solvecomp.py wired
through pencilops/matsolvers/solvers): every [fusion] SOLVE_COMPOSITION
and [precision] SOLVE_DTYPE cell must agree with the sequential f64
path — tolerance-bounded on the banded restructurings (the refinement
polish holds them at the fused tolerance class), bitwise on the dense
path where the compositions are inert — and compose with the adjoint
funnel, EnsembleSolver vmap, the 2-D batch x pencil mesh, the retrace
sentinel, and the assembly/pool key discipline.

Tolerance contract under test (docs/performance.md "Solve depth and the
precision ladder"): ascan/spike trajectories track sequential within
~1e-11 relative (observed ~1e-14 on the small RB); the f32+refinement
ladder holds state error <= 1e-10 vs f64 (observed ~1e-13) with its
sweep count resolved from [precision] REFINE_SWEEPS.
"""

import pathlib
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import dedalus_tpu.public as d3
from dedalus_tpu.libraries import solvecomp
from dedalus_tpu.libraries.matsolvers import (BatchedInverseRefined,
                                              get_solver)
from dedalus_tpu.tools import retrace as retrace_mod
from dedalus_tpu.tools.config import config
from dedalus_tpu.tools.lint.progcheck import scan_lengths

sys.path.insert(0, str(pathlib.Path(__file__).parent))
from test_banded import build_rb  # noqa: E402

pytestmark = pytest.mark.solvecomp

SOLVE_KEYS = (("fusion", "SOLVE_COMPOSITION"), ("fusion", "SPIKE_CHUNKS"),
              ("precision", "SOLVE_DTYPE"), ("precision", "REFINE_SWEEPS"),
              ("precision", "REFINE_TOL"), ("precision", "MMT_DTYPE"),
              ("fusion", "FUSED_SOLVE"))


@pytest.fixture
def solve_cfg():
    """Mutate the solve-plan keys inside a test, restored afterwards."""
    for section in {s for s, _ in SOLVE_KEYS}:
        if not config.has_section(section):
            config.add_section(section)
    saved = {(s, k): config[s].get(k) for s, k in SOLVE_KEYS}

    def set_cfg(composition="auto", solve_dtype="auto", sweeps="auto",
                tol="auto", spike_chunks="auto", mmt="auto",
                fused_solve="auto"):
        config["fusion"]["SOLVE_COMPOSITION"] = composition
        config["fusion"]["SPIKE_CHUNKS"] = spike_chunks
        config["fusion"]["FUSED_SOLVE"] = fused_solve
        config["precision"]["SOLVE_DTYPE"] = solve_dtype
        config["precision"]["REFINE_SWEEPS"] = sweeps
        config["precision"]["REFINE_TOL"] = tol
        config["precision"]["MMT_DTYPE"] = mmt

    set_cfg()
    yield set_cfg
    for (s, k), val in saved.items():
        if val is None:
            config[s].pop(k, None)
        else:
            config[s][k] = val


def rb_trajectory(scheme, n=8, **build_kw):
    solver = build_rb(8, 32, matsolver="banded", timestepper=scheme,
                      **build_kw)
    for _ in range(n):
        solver.step(0.01)
    return np.asarray(solver.X), solver


# sequential-f64 baselines shared across the comparison tests (one build
# per scheme instead of one per test; computed under the solve_cfg
# fixture's default reset, which every caller applies first)
_SEQ_BASELINES = {}


def seq_baseline(scheme):
    key = scheme.__name__
    if key not in _SEQ_BASELINES:
        _SEQ_BASELINES[key], _ = rb_trajectory(scheme)
    return _SEQ_BASELINES[key]


def build_diffusion(scheme=d3.SBDF2, size=48):
    xc = d3.Coordinate("x")
    dist = d3.Distributor(xc, dtype=np.float64)
    xb = d3.RealFourier(xc, size=size, bounds=(0, 2 * np.pi))
    u = dist.Field(name="u", bases=xb)
    dx = lambda A: d3.Differentiate(A, xc)  # noqa: E731
    problem = d3.IVP([u], namespace={"u": u, "lap": d3.lap, "dx": dx})
    problem.add_equation("dt(u) - lap(u) = - u*dx(u)")
    x = dist.local_grid(xb)
    u["g"] = np.sin(3 * x) + 0.2 * np.cos(x)
    return problem.build_solver(scheme, warmup_iterations=2,
                                enforce_real_cadence=0)


# ------------------------------------------------- unit-level recurrences

def test_ascan_apply_matches_reference():
    """ascan_apply == the sequential affine recurrence, for general
    state/input/output widths and multiple RHS columns."""
    rng = np.random.default_rng(0)
    m, G, s, kin, o, k = 7, 3, 4, 2, 5, 2
    A = rng.standard_normal((m, G, s, s)) * 0.3
    B = rng.standard_normal((m, G, s, kin))
    C = rng.standard_normal((m, G, o, s))
    D = rng.standard_normal((m, G, o, kin))
    u = rng.standard_normal((m, G, kin, k))
    v0 = rng.standard_normal((G, s, k))
    outs, v_end = solvecomp.ascan_apply(*map(jnp.asarray, (A, B, C, D, u,
                                                           v0)))
    v = v0
    for j in range(m):
        ref = C[j] @ v + D[j] @ u[j]
        assert np.allclose(np.asarray(outs[j]), ref, atol=1e-12)
        v = A[j] @ v + B[j] @ u[j]
    assert np.allclose(np.asarray(v_end), v, atol=1e-12)


@pytest.mark.parametrize("chunks", [2, 3, 7])
def test_spike_apply_matches_reference(chunks):
    """spike_precompose + spike_apply == the sequential recurrence for
    every chunk count, including non-dividing ones (identity padding)."""
    rng = np.random.default_rng(1)
    m, G, s, kin, o, k = 7, 2, 3, 3, 3, 1
    A = rng.standard_normal((m, G, s, s)) * 0.3
    B = rng.standard_normal((m, G, s, kin))
    C = rng.standard_normal((m, G, o, s))
    D = rng.standard_normal((m, G, o, kin))
    u = rng.standard_normal((m, G, kin, k))
    v0 = rng.standard_normal((G, s, k))
    ops = solvecomp.spike_precompose(*map(jnp.asarray, (A, B, C, D)),
                                     chunks)
    outs, v_end = solvecomp.spike_apply(ops, jnp.asarray(u),
                                        jnp.asarray(v0))
    v = v0
    for j in range(m):
        ref = C[j] @ v + D[j] @ u[j]
        assert np.allclose(np.asarray(outs[j]), ref, atol=1e-12), (chunks, j)
        v = A[j] @ v + B[j] @ u[j]
    assert np.allclose(np.asarray(v_end), v, atol=1e-12)


def test_spike_chunk_count():
    assert solvecomp.spike_chunk_count(3, 0) == 1      # too short to chunk
    assert solvecomp.spike_chunk_count(16, 0) == 4     # auto ~ sqrt
    assert solvecomp.spike_chunk_count(16, 6) == 6
    assert solvecomp.spike_chunk_count(16, 99) == 16   # clamped


# ------------------------------------------ trajectory agreement (banded)

@pytest.mark.parametrize("scheme", [d3.SBDF2, d3.RK222])
@pytest.mark.parametrize("composition", ["ascan", "spike"])
def test_composition_matches_sequential_banded(scheme, composition,
                                               solve_cfg):
    """Every restructured composition tracks the sequential f64 banded
    trajectory within the fused tolerance class; the aux carries the
    structure the composition claims (spike chunk operators / retained
    step operators for ascan)."""
    solve_cfg(composition="sequential")
    x_seq = seq_baseline(scheme)
    solve_cfg(composition=composition)
    x_new, solver = rb_trajectory(scheme)
    assert solver.ops._composition == composition
    aux = solver.timestepper._lhs_aux
    aux0 = (aux[0] if isinstance(aux, list) else aux)["fsub"]
    if composition == "spike":
        assert "spikeF" in aux0 and "spikeB" in aux0
        assert "FwdOp" not in aux0     # dropped: spike consumes chunk ops
        # adjoint contract, directly on the funnel: <A^-1 r, s> must
        # equal <r, A^-T s> against the SAME restructured factors
        ops = solver.ops
        aux_full = aux[0] if isinstance(aux, list) else aux
        mats = (solver.M_mat, solver.L_mat)
        rng = np.random.default_rng(9)
        r = jnp.asarray(rng.standard_normal(solver.pencil_shape))
        s = jnp.asarray(rng.standard_normal(solver.pencil_shape))
        lhs = float(jnp.vdot(ops.solve(aux_full, r, mats=mats), s))
        rhs = float(jnp.vdot(r, ops.solve_transpose(aux_full, s, mats=mats)))
        assert abs(lhs - rhs) <= 1e-10 * max(abs(lhs), 1.0)
    else:
        assert "FwdOp" in aux0
    assert np.isfinite(x_new).all()
    scale = np.max(np.abs(x_seq))
    assert np.max(np.abs(x_new - x_seq)) <= 1e-11 * scale


@pytest.mark.parametrize("composition", ["ascan", "spike"])
def test_composition_inert_on_dense(composition, solve_cfg):
    """The scan compositions are no-ops on the dense pencil path (there
    is no substitution scan): trajectories are BITWISE identical to the
    sequential build under the same config."""
    solve_cfg(composition="sequential")
    s_seq = build_diffusion()
    for _ in range(10):
        s_seq.step(1e-3)
    solve_cfg(composition=composition)
    s_new = build_diffusion()
    for _ in range(10):
        s_new.step(1e-3)
    assert np.array_equal(np.asarray(s_seq.X), np.asarray(s_new.X))


# --------------------------------------------------- the precision ladder

def test_ladder_f32_banded_accuracy(solve_cfg):
    """The f32 ladder stores the fused factors in float32 (halving the
    factor store) and the f64 refinement polish contracts the error by
    ~cond*eps32 per sweep: the auto schedule (2 sweeps, the measured
    rb256x64 speed/accuracy knee) holds this stiffer small RB at the
    1e-9 class (observed 1.2e-10), one more sweep lands the <=1e-10
    ladder bar with orders to spare (observed 4e-15); the telemetry
    block records the resolved plan + achieved residual."""
    solve_cfg()
    x_f64 = seq_baseline(d3.RK222)
    solve_cfg(solve_dtype="f32")
    x_auto, solver = rb_trajectory(d3.RK222)
    aux = solver.timestepper._lhs_aux[0]
    assert aux["fsub"]["lastOp"].dtype == np.float32
    assert solver._solve_plan.sweeps == 2    # auto scales to the gap
    scale = np.max(np.abs(x_f64))
    assert np.max(np.abs(x_auto - x_f64)) <= 1e-9 * scale
    block = solver._precision_summary()
    assert block["solve_dtype"] == "f32"
    assert block["refine_sweeps"] == 2
    assert block["achieved_residual"] <= 1e-8
    solve_cfg(solve_dtype="f32", sweeps="3")
    x_deep, _ = rb_trajectory(d3.RK222)
    assert np.max(np.abs(x_deep - x_f64)) <= 1e-10 * scale


def test_ladder_f32_composes_with_spike(solve_cfg):
    """Ladder x composition: the spike chunk operators cast low too,
    the refined trajectory stays in the 1e-10 class, and the whole
    restructured+laddered program compiles once — zero post-warmup
    retraces across repeated step_many blocks (composition resolved at
    build, never read in traced code)."""
    solve_cfg()
    x_f64 = seq_baseline(d3.RK222)
    solve_cfg(composition="spike", solve_dtype="f32", sweeps="3")
    retrace_mod.sentinel.reset()
    x_new, solver = rb_trajectory(d3.RK222)
    aux = solver.timestepper._lhs_aux[0]
    assert aux["fsub"]["spikeF"]["Y"].dtype == np.float32
    scale = np.max(np.abs(x_f64))
    assert np.max(np.abs(x_new - x_f64)) <= 1e-10 * scale
    solver.step_many(4, 0.01)
    solver.step_many(4, 0.01)
    assert retrace_mod.sentinel.post_arm_retraces == 0


def test_ladder_f32_dense(solve_cfg):
    """Dense arm of the ladder: DenseOps routes through the refined
    low-dtype inverse (matsolvers.refined_ladder) and holds 1e-10."""
    solve_cfg()
    s_f64 = build_diffusion()
    for _ in range(10):
        s_f64.step(1e-3)
    solve_cfg(solve_dtype="f32")
    s_f32 = build_diffusion()
    assert issubclass(s_f32.ops.solver_cls, BatchedInverseRefined)
    assert s_f32.ops.solver_cls.iterations == 2
    for _ in range(10):
        s_f32.step(1e-3)
    scale = np.max(np.abs(np.asarray(s_f64.X)))
    assert np.max(np.abs(np.asarray(s_f32.X) - np.asarray(s_f64.X))) \
        <= 1e-10 * scale


def test_refined_matsolver_schedule_and_depth(solve_cfg):
    """The BatchedInverseRefined sweep count is config-driven (was a
    hardcoded class attribute), the refinement lowers as a fixed-length
    loop (no while — the DTP106-checkable shape), tolerance termination
    freezes converged systems, and residual() reports achieved
    accuracy."""
    solve_cfg(sweeps="5")
    cls = get_solver("batchedinverserefined")
    assert cls.iterations == 5
    rng = np.random.default_rng(3)
    A = jnp.asarray(rng.standard_normal((4, 6, 6)) + 6 * np.eye(6))
    b = jnp.asarray(rng.standard_normal((4, 6)))
    aux = cls.factor(A)
    x = cls.solve(aux, b)
    res = np.asarray(cls.residual(aux, np.asarray(x), b))
    assert res.shape == (4,) and res.max() < 1e-12
    lengths, whiles = scan_lengths(jax.make_jaxpr(cls.solve)(aux, b))
    assert whiles == 0 and max(lengths, default=0) <= 5
    # a saturated tolerance freezes every update: the masked fixed-trip
    # loop returns the unrefined first solve bitwise
    solve_cfg(sweeps="5", tol="1e9")
    frozen_cls = get_solver("batchedinverserefined")
    assert frozen_cls.tol == 1e9
    x_frozen = frozen_cls.solve(aux, b)
    x0 = jnp.einsum("gij,gj->gi", aux[1],
                    b.astype(np.float32)).astype(b.dtype)
    assert np.array_equal(np.asarray(x_frozen), np.asarray(x0))


# ------------------------------------------------ adjoint + fleet + mesh

def test_adjoint_fd_through_composition(solve_cfg):
    """DifferentiableIVP gradients FD-validate through the restructured
    solve: the custom_vjp funnel transposes the same associative-scan
    linear algebra (jax.vjp over the restructured _solve_impl). SPIKE's
    adjoint is pinned by the transpose dot-identity inside
    test_composition_matches_sequential_banded (same funnel, no second
    DifferentiableIVP build)."""
    composition = "ascan"
    solve_cfg(composition=composition)
    solver = build_rb(8, 32, matsolver="banded", timestepper=d3.RK222)
    assert solver.ops._composition == composition
    div = solver.differentiable(wrt=("initial_state",),
                                loss=lambda X: jnp.sum(X ** 2))
    n, dt = 6, 0.01
    X0 = np.asarray(solver.gather_fields()).copy()
    _, grads = div.value_and_grad(n, dt, initial_state=X0)
    g = np.asarray(grads["initial_state"])
    assert np.isfinite(g).all()
    v = np.random.default_rng(0).standard_normal(X0.shape)
    eps = 1e-6
    fd = (div.value(n, dt, initial_state=X0 + eps * v)
          - div.value(n, dt, initial_state=X0 - eps * v)) / (2 * eps)
    an = float(np.sum(g * v))
    assert abs(fd - an) <= 1e-5 * max(abs(fd), 1e-12)


def test_ensemble_vmap_composes_with_spike(solve_cfg):
    """EnsembleSolver vmaps the step bodies over the restructured ops
    (including the vmapped spike factorization): fleet members match
    their serial runs with the composition on."""
    solve_cfg(composition="spike")
    seeds = [21, 22]
    serial = []
    for seed in seeds:
        solver = build_rb(8, 32, matsolver="banded", timestepper=d3.RK222)
        solver.problem.variables[1].fill_random(
            "g", seed=seed, distribution="normal", scale=1e-3)
        solver.step_many(6, 0.01)
        serial.append(np.asarray(solver.X))
    solver = build_rb(8, 32, matsolver="banded", timestepper=d3.RK222)
    assert solver.ops._composition == "spike"
    ens = solver.ensemble(len(seeds), mesh=None)

    def member_init(i):
        solver.problem.variables[1].fill_random(
            "g", seed=seeds[i], distribution="normal", scale=1e-3)

    ens.init_members(member_init)
    ens.step_many(6, 0.01)
    for i in range(len(seeds)):
        err = np.max(np.abs(np.asarray(ens.X[i]) - serial[i]))
        assert err <= 1e-12, (i, err)


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs >= 8 devices")
def test_2d_mesh_fleet_composes_with_ascan(solve_cfg):
    """The 2-D batch x pencil fleet steps through the restructured solve
    (manual batch shard_map over GSPMD-auto pencils) and matches the 1-D
    fleet at roundoff — the composition the north-star run uses. (The
    sequential composition's bitwise 2-D-vs-1-D claim lives in
    tests/test_distributed.py; the associative-scan combine is a tree
    reduction whose fp order GSPMD may legally re-associate across mesh
    layouts, so the contract here is the roundoff class, observed
    ~1e-17.)"""
    from jax.sharding import Mesh
    from dedalus_tpu.extras.bench_problems import build_tau_ivp
    solve_cfg(composition="ascan")
    states = {}
    for label, mesh in (
            ("1d", Mesh(np.array(jax.devices()[:2]), ("batch",))),
            ("2d", Mesh(np.array(jax.devices()[:8]).reshape(2, 4),
                        ("batch", "pencil")))):
        solver, u, x, z = build_tau_ivp(8, 32, matsolver="banded")
        assert solver.ops._composition == "ascan"
        fleet = solver.ensemble(2, mesh=mesh)

        def ics(i):
            u["g"] = np.sin(np.pi * z) * (1 + 0.1 * (i + 1)
                                          * np.cos(np.pi * x / 2))

        fleet.init_members(ics)
        fleet.step_many(6, 1e-3)
        states[label] = np.asarray(fleet.X).copy()
    scale = np.max(np.abs(states["1d"]))
    assert np.max(np.abs(states["1d"] - states["2d"])) <= 1e-13 * scale


# -------------------------------------------------- hygiene + key discipline

def test_solver_and_pool_keys_rekey(solve_cfg):
    """solver_key and pool_key re-key across compositions AND solve
    dtypes: pooled compiled programs can never alias across the plan."""
    from dedalus_tpu.tools import assembly_cache
    keys = []
    for kw in ({"composition": "sequential"}, {"composition": "ascan"},
               {"composition": "spike"}, {"solve_dtype": "f32"},
               {"composition": "spike", "spike_chunks": "3"}):
        solve_cfg(**kw)
        solver = build_diffusion()
        keys.append((assembly_cache.solver_key(solver, solver.matrices),
                     assembly_cache.pool_key(solver)))
    assert all(k[0] is not None and k[1] is not None for k in keys)
    assert len({k[0] for k in keys}) == len(keys)
    assert len({k[1] for k in keys}) == len(keys)


@pytest.mark.parametrize("bad,match", [
    (dict(composition="logdepth"), "SOLVE_COMPOSITION"),
    (dict(solve_dtype="f16"), "SOLVE_DTYPE"),
    (dict(sweeps="-1"), "REFINE_SWEEPS"),
    (dict(spike_chunks="1"), "SPIKE_CHUNKS"),
    (dict(tol="many"), "REFINE_TOL"),
    (dict(mmt="f8"), "MMT_DTYPE")])
def test_config_validation(solve_cfg, bad, match):
    """Unknown [fusion]/[precision] values raise ValueError (never
    silent auto) at the per-build resolve, knob by knob."""
    solve_cfg(**bad)
    with pytest.raises(ValueError, match=match):
        solvecomp.resolve_solve_plan()


def test_config_validation_runs_at_build(solve_cfg):
    """The resolve really runs inside every solver build."""
    solve_cfg(composition="logdepth")
    with pytest.raises(ValueError, match="SOLVE_COMPOSITION"):
        build_diffusion()


def test_composition_needs_fused_solve(solve_cfg):
    """A restructured composition without the fused operators it
    restructures fails loudly at ops construction."""
    solve_cfg(composition="ascan", fused_solve="off")
    with pytest.raises(ValueError, match="FUSED_SOLVE"):
        build_rb(8, 32, matsolver="banded")


def test_solve_knobs_pinned(solve_cfg):
    assert not solvecomp.solve_knobs_pinned()
    solve_cfg(sweeps="3")
    assert solvecomp.solve_knobs_pinned()
    solve_cfg(sweeps="auto")
    assert not solvecomp.solve_knobs_pinned()


def test_plan_source_and_rekey(solve_cfg):
    """plan_source names the selector — `default` with every solve knob
    at auto, `config` once one is pinned — and the pinned knob re-keys
    solver_key/pool_key."""
    from dedalus_tpu.tools import assembly_cache
    solver = build_rb(8, 32, matsolver="banded")
    prov = solver.plan_provenance()
    assert solver._plan_source == prov["plan_source"] == "default"
    assert "tuning" not in prov
    key_default = assembly_cache.solver_key(solver, list(solver.matrices))
    pool_default = assembly_cache.pool_key(solver)

    solve_cfg(composition="ascan")
    pinned = build_rb(8, 32, matsolver="banded")
    assert pinned._plan_source == "config"
    assert pinned._solve_plan.composition == "ascan"
    assert pinned.plan_provenance()["plan_source"] == "config"
    assert assembly_cache.solver_key(pinned, list(pinned.matrices)) != \
        key_default
    assert assembly_cache.pool_key(pinned) != pool_default


@pytest.mark.parametrize("case", ["dense", "banded_fused", "banded_packed",
                                  "banded_pinned"])
def test_plan_provenance_is_exact(solve_cfg, case):
    """plan_provenance() of a build with every solve knob at auto, per
    representation — dense, banded as auto fuses it on the CPU, banded
    with FUSED_SOLVE = off (the packed store) — is exactly the resolved
    plan with `plan_source: default` and nothing else; one pinned solve
    knob turns the source to `config`."""
    solve_cfg(fused_solve="off" if case == "banded_packed" else "auto",
              solve_dtype="f32" if case == "banded_pinned" else "auto")
    solver = build_rb(8, 32, matsolver="dense" if case == "dense"
                      else "banded")
    assert solver.ops.kind == ("dense" if case == "dense" else "banded")
    prov = solver.plan_provenance()
    key = prov.pop("solver_key", None)
    assert key is None or key == str(solver.assembly_key)[:16]
    pinned = case == "banded_pinned"
    assert prov == {
        "plan_version": 1,
        "fusion": {"solve": case != "banded_packed", "matvec": True,
                   "transforms": False, "donate": True},
        "solve_composition": "sequential",
        "solve_dtype": "f32" if pinned else "native",
        "refine_sweeps": 2 if pinned else None,
        "spike_chunks": 0,
        "transpose_chunks": 2,
        "plan_source": "config" if pinned else "default"}
    if case.startswith("banded"):
        assert solver.ops._fused_solve == (case != "banded_packed")

"""
Fused spectral step (core/fusedstep.py + libraries/pencilops fused
paths): fused-vs-unfused equivalence across schemes (SBDF2 + RK222),
problems (diffusion + Rayleigh-Benard) and pencil paths (dense +
banded); composition under EnsembleSolver vmap and DifferentiableIVP
adjoints; donation safety against the snapshot-rewind machinery;
assembly-cache fusion-key invalidation; the [fusion] config discipline
(typo'd values and removed options fail the build); and the fused phase
row in the metrics vocabulary.

Tolerance contract under test (documented in docs/performance.md and
the [fusion] config): FUSED_MATVEC and the dense-path fused layers are
BITWISE identical to the legacy step; the precomposed banded
substitution (FUSED_SOLVE) moves solutions at the eps*cond(block) level
and the refinement polish keeps trajectories within ~1e-12 relative of
the backward-stable sweeps (measured 7e-16 on the rb256x64 headline).
"""

import pathlib
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import dedalus_tpu.public as d3
from dedalus_tpu.core import fusedstep
from dedalus_tpu.tools import retrace as retrace_mod
from dedalus_tpu.tools.config import config
from dedalus_tpu.tools.metrics import Metrics, SUM_PHASES, \
    format_phase_table

sys.path.insert(0, str(pathlib.Path(__file__).parent))
from test_banded import build_rb  # noqa: E402

pytestmark = pytest.mark.fusion

FUSION_KEYS = ("FUSED_SOLVE", "FUSED_MATVEC", "FUSED_TRANSFORMS",
               "DONATE_STEP")


@pytest.fixture
def fusion_cfg():
    """Mutate the [fusion] section inside a test, restored afterwards."""
    if not config.has_section("fusion"):
        config.add_section("fusion")
    saved = {k: config["fusion"].get(k) for k in FUSION_KEYS}

    def set_flags(**kw):
        for key in FUSION_KEYS:
            config["fusion"][key] = kw.get(key.lower(), "auto")

    yield set_flags
    for key, val in saved.items():
        if val is None:
            config["fusion"].pop(key, None)
        else:
            config["fusion"][key] = val


def build_diffusion(scheme, size=64):
    xc = d3.Coordinate("x")
    dist = d3.Distributor(xc, dtype=np.float64)
    xb = d3.RealFourier(xc, size=size, bounds=(0, 2 * np.pi))
    u = dist.Field(name="u", bases=xb)
    a = dist.Field(name="a", bases=xb)
    dx = lambda A: d3.Differentiate(A, xc)  # noqa: E731
    problem = d3.IVP([u], namespace={"u": u, "a": a, "lap": d3.lap,
                                     "dx": dx})
    problem.add_equation("dt(u) - lap(u) = a*u - u*dx(u)")
    x = dist.local_grid(xb)
    u["g"] = np.sin(3 * x) + 0.2 * np.cos(x)
    a["g"] = 0.1 * np.cos(x)
    return problem.build_solver(scheme, warmup_iterations=2,
                                enforce_real_cadence=0)


def rb_states(n, scheme, fusion_flags, set_flags, **build_kw):
    set_flags(**fusion_flags)
    solver = build_rb(8, 32, matsolver="banded", timestepper=scheme,
                      **build_kw)
    for _ in range(n):
        solver.step(0.01)
    return np.asarray(solver.X), solver


# ------------------------------------------------- fused vs unfused step

@pytest.mark.parametrize("scheme", [d3.RK222, d3.SBDF2])
def test_fused_vs_unfused_banded_rb(scheme, fusion_cfg):
    """Banded path (RB): the precomposed substitution + pair matvec +
    donation trajectory tracks the legacy step within the documented
    tolerance class (refinement-polished eps*cond; ~1e-15 observed)."""
    off = {k.lower(): "off" for k in FUSION_KEYS}
    x_off, _ = rb_states(10, scheme, off, fusion_cfg)
    x_on, solver = rb_states(10, scheme, {}, fusion_cfg)
    assert solver.ops._fused_solve
    aux = solver.timestepper._lhs_aux
    aux0 = aux[0] if isinstance(aux, list) else aux
    assert "fsub" in aux0 and "FwdOp" in aux0["fsub"]
    assert np.isfinite(x_on).all()
    scale = np.max(np.abs(x_off))
    assert np.max(np.abs(x_on - x_off)) <= 1e-12 * scale


@pytest.mark.parametrize("scheme", [d3.SBDF2, d3.RK222])
def test_fused_vs_unfused_dense_bitwise(scheme, fusion_cfg):
    """Dense path (diffusion): the fused layers that apply (pair matvec,
    donation) are BITWISE identical to the legacy step."""
    fusion_cfg(**{k.lower(): "off" for k in FUSION_KEYS})
    s_off = build_diffusion(scheme)
    for _ in range(12):
        s_off.step(1e-3)
    fusion_cfg()
    s_on = build_diffusion(scheme)
    assert s_on.timestepper._fusion.matvec
    for _ in range(12):
        s_on.step(1e-3)
    assert np.array_equal(np.asarray(s_off.X), np.asarray(s_on.X))


def test_matvec_pair_bitwise(fusion_cfg):
    """BandedOps.matvec_pair == separate matvecs, bit for bit (shared
    permute/pad only; per-matrix trimmed loops unchanged)."""
    fusion_cfg()
    solver = build_rb(8, 32, matsolver="banded")
    ops, M, L = solver.ops, solver.M_mat, solver.L_mat
    X = jnp.asarray(np.random.default_rng(3).normal(
        size=solver.pencil_shape))
    MX, LX = ops.matvec_pair(M, L, X)
    assert np.array_equal(np.asarray(MX), np.asarray(ops.matvec(M, X)))
    assert np.array_equal(np.asarray(LX), np.asarray(ops.matvec(L, X)))


# --------------------------------------------------- composite transforms

def test_fused_transforms_composites_match(fusion_cfg):
    """FUSED_TRANSFORMS folds the RB grad/div chains into composite
    GEMMs (plan registers nodes) and the trajectory tracks the generic
    transform path."""
    off = {k.lower(): "off" for k in FUSION_KEYS}
    x_off, _ = rb_states(8, d3.RK222, off, fusion_cfg)
    x_on, solver = rb_states(8, d3.RK222,
                             {"fused_transforms": "on"}, fusion_cfg)
    plan = solver._fused_eval_plan
    assert plan is not None and len(plan) > 0
    scale = np.max(np.abs(x_off))
    assert np.max(np.abs(x_on - x_off)) <= 1e-12 * scale


def test_fused_composites_cached_and_invalidated(fusion_cfg, tmp_path,
                                                 monkeypatch):
    """Precomposed composites are cached payloads: the entry lands on
    disk under a fusion-keyed name, a corrupt entry falls back to fresh
    folds, and a fusion-flag flip changes the key so stale composites
    can never be served."""
    monkeypatch.setenv("DEDALUS_TPU_ASSEMBLY_CACHE", str(tmp_path))
    fusion_cfg(fused_transforms="on")
    solver = build_rb(8, 32, matsolver="banded")
    plan = solver._fused_eval_plan
    key = plan.cache_key(solver)
    assert key is not None
    entry = tmp_path / f"asm-{key}.npb"
    assert entry.exists()
    # warm rebuild installs the cached composites (bit-identical arrays)
    solver2 = build_rb(8, 32, matsolver="banded")
    plan2 = solver2._fused_eval_plan
    assert plan2.cache_key(solver2) == key
    for n1, n2 in zip(plan._walk_order, plan2._walk_order):
        for (e1, e2) in zip(plan.nodes[id(n1)], plan2.nodes[id(n2)]):
            assert np.array_equal(e1[3], e2[3])
    # corruption falls back to fresh assembly (entry quarantined+restored)
    entry.write_bytes(b"garbage")
    solver3 = build_rb(8, 32, matsolver="banded")
    assert solver3._fused_eval_plan is not None
    # flag flip -> different resolved token -> different key: a stale
    # composite can never be served under another composition
    tok_on = fusedstep.cache_token()
    fusion_cfg(fused_transforms="on", fused_solve="off")
    assert fusedstep.cache_token() != tok_on


def test_assembly_key_carries_fusion_token(fusion_cfg):
    """The main assembly-cache content key includes the resolved fusion
    composition: a flag flip re-keys the solver payloads too."""
    from dedalus_tpu.tools import assembly_cache
    fusion_cfg()
    s1 = build_rb(8, 32, matsolver="banded")
    k1 = assembly_cache.solver_key(s1, s1.matrices)
    fusion_cfg(fused_solve="off")
    s2 = build_rb(8, 32, matsolver="banded")
    k2 = assembly_cache.solver_key(s2, s2.matrices)
    assert k1 is not None and k2 is not None and k1 != k2


# ------------------------------------------------------ adjoint + ensemble

def test_adjoint_fd_through_fused_banded(fusion_cfg):
    """DifferentiableIVP gradients FD-validate through the fused banded
    solve (the custom_vjp funnel transposes the precomposed-GEMM
    substitution exactly like the legacy sweeps)."""
    fusion_cfg()
    solver = build_rb(8, 32, matsolver="banded", timestepper=d3.RK222)
    assert solver.ops._fused_solve
    div = solver.differentiable(wrt=("initial_state",),
                                loss=lambda X: jnp.sum(X ** 2))
    n, dt = 12, 0.01
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


def test_ensemble_vmap_composes_with_fused_solve(fusion_cfg):
    """EnsembleSolver vmaps the raw step bodies over the fused ops
    (including the vmapped precomposed-inverse factorization): fleet
    members bit-match their serial runs with fusion on."""
    fusion_cfg()
    seeds = [11, 12, 13]

    def build():
        return build_rb(8, 32, matsolver="banded", timestepper=d3.RK222)

    serial = []
    for seed in seeds:
        solver = build()
        solver.problem.variables[1].fill_random(
            "g", seed=seed, distribution="normal", scale=1e-3)
        solver.step_many(6, 0.01)
        serial.append(np.asarray(solver.X))
    solver = build()
    assert solver.ops._fused_solve
    ens = solver.ensemble(len(seeds), mesh=None)

    def member_init(i):
        solver.problem.variables[1].fill_random(
            "g", seed=seeds[i], distribution="normal", scale=1e-3)

    ens.init_members(member_init)
    ens.step_many(6, 0.01)
    for i in range(len(seeds)):
        err = np.max(np.abs(np.asarray(ens.X[i]) - serial[i]))
        assert err <= 1e-12, (i, err)


# ------------------------------------------------------- donation safety

def test_donation_snapshot_rewind_bitwise(fusion_cfg):
    """The donating multistep step program composes with the snapshot
    ring: capture -> step -> rewind -> re-step reproduces the original
    trajectory bitwise, twice from the SAME snapshot (the ring owns
    copies, so donation can never consume its slots)."""
    from dedalus_tpu.tools.resilience import (capture_snapshot,
                                              restore_snapshot)
    fusion_cfg()
    solver = build_diffusion(d3.SBDF2)
    assert solver.timestepper.donates_histories
    for _ in range(5):
        solver.step(1e-3)
    snap = capture_snapshot(solver)
    for _ in range(3):
        solver.step(1e-3)
    x_ref = np.asarray(solver.X).copy()
    for _ in range(2):
        restore_snapshot(solver, snap)
        for _ in range(3):
            solver.step(1e-3)
        assert np.array_equal(np.asarray(solver.X), x_ref)


# ------------------------------------------------------- config discipline

@pytest.mark.parametrize("key", FUSION_KEYS)
def test_typoed_flag_fails_the_build(key, fusion_cfg):
    """A typo'd [fusion] flag must not silently resolve to auto: the
    build fails with a ValueError naming the key."""
    fusion_cfg(**{key.lower(): "offf"})
    with pytest.raises(ValueError, match=key):
        build_diffusion(d3.SBDF2)


@pytest.mark.parametrize("section,key", [
    ("fusion", "PALLAS"), ("autotune", "MODE"), ("autotune", "TUNE_STEPS"),
    ("autotune", "TUNE_BUDGET_SEC")])
def test_removed_option_fails_the_build(section, key, fusion_cfg):
    """A user file that still sets an option PR 30 removed (the
    autotuner's section, the Pallas substitution kernel's switch) fails
    the build with a ValueError naming the key and the PR, instead of
    being silently ignored."""
    fusion_cfg()
    had_section = config.has_section(section)
    if not had_section:
        config.add_section(section)
    config[section][key] = "off"
    try:
        with pytest.raises(ValueError, match=rf"\[{section}\] {key}.*PR 30"):
            build_diffusion(d3.SBDF2)
    finally:
        if had_section:
            config[section].pop(key)
        else:
            config.remove_section(section)
    build_diffusion(d3.SBDF2)   # and the build is whole again without it


# ----------------------------------------------- metrics + retrace hygiene

def test_fused_phase_row_and_zero_retraces(fusion_cfg):
    """The sampler records the fused whole-step row (excluded from the
    decomposition sum), format_phase_table renders it, and the fused
    step program compiles exactly once (zero post-warmup retraces)."""
    fusion_cfg()
    retrace_mod.sentinel.reset()
    metrics = Metrics(sample_cadence=2, sink=None, enabled=True,
                      sampling=True)
    solver = build_diffusion(d3.SBDF2)
    solver.metrics = metrics
    for _ in range(4):
        solver.step(1e-3)
    solver.step_many(8, 1e-3)
    solver.step_many(8, 1e-3)
    record = solver.flush_metrics()
    assert record["phase_samples"] > 0
    assert record["phase_mean_sec"]["fused"] > 0.0
    # the fused row overlaps the decomposition: excluded from the sum
    wall = record["loop_wall_sec"]
    decomp = sum(record["phase_total_sec"][p] for p in SUM_PHASES)
    # the record rounds the fraction to four decimals (tools/metrics.py):
    # near 0.04, where a loaded host puts it, that alone is 1.3e-3 relative
    assert record["phase_sum_frac"] == pytest.approx(
        decomp / wall, rel=1e-3, abs=1e-4)
    lines = "\n".join(format_phase_table(record))
    assert "fused" in lines and "excluded from sum" in lines
    assert retrace_mod.sentinel.post_arm_retraces == 0
    assert record["retraces_post_warmup"] == 0

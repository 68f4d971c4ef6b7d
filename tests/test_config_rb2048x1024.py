"""
rb2048x1024 at a size the CPU holds (RB 64x32, banded forced), through the
configuration's own file, chipbench/configs/rb2048x1024.py: what the cell
`rb2048x1024.block10` relies on, checked where it costs no chip time.

  (a) ten steps through `BandedOps`, factored incrementally in several
      chunks, against the dense path of the same problem;
  (b) the plain dense check of `invariants()` for EVERY pencil group, and
      the proof that it bites: without the Woodbury correction it fails;
  (c) the incremental factorization is the one-dispatch factorization;
  (d) the scopes and spans the per-layer metrics read are in the program;
  (e) `[fusion] FUSED_SOLVE = auto` yields to the device's memory.
"""

import pathlib

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from dedalus_tpu.libraries import pencilops
from dedalus_tpu.tools import tracing
from dedalus_tpu.tools.config import config

ROOT = pathlib.Path(__file__).resolve().parents[1]
SIZE = {"Nx": 64, "Nz": 32}
STEPS = 10
# the chip's reading and one bf16 pass's are in rb2048x1024.json; these are
# the CPU's at 64x32: float32 reads 2.6e-4 (dense and banded alike: the
# compared coefficients are the 1e-3 noise, not the conduction profile),
# float64 1e-14
TRAJECTORY_TOL = {"float32": 2e-3, "float64": 1e-10}


@pytest.fixture(scope="module")
def rb():
    from chipbench.manifest import load_module
    return load_module(ROOT / "chipbench" / "configs" / "rb2048x1024.py")


def _chunked(mp):
    """Options small enough that 32 groups factor in several chunks, one
    dispatch per chunk, as 1024 groups do at the published size; and a
    device small enough that `FUSED_SOLVE = auto` keeps the factors
    packed, as a v5e's 16 GB make it at the published size."""
    mp.setitem(config["linear algebra"], "BANDED_CHUNK_MB", "1")
    mp.setitem(config["linear algebra"], "BANDED_INCREMENTAL_GB", "0.001")
    mp.setattr(pencilops, "device_memory_bytes", lambda: 4e5)


@pytest.fixture()
def chunked(monkeypatch):
    _chunked(monkeypatch)


def stepped(rb, dtype, matsolver):
    dep = rb.build(0, dtype=np.dtype(dtype), size=dict(SIZE,
                                                       matsolver=matsolver))
    for _ in range(STEPS):
        dep.solver.step(dep.fixed_dt)
    return dep


@pytest.fixture(scope="module")
def deployments(rb):
    """One build and ten steps per dtype for the whole module."""
    mp = pytest.MonkeyPatch()
    _chunked(mp)
    try:
        yield {dtype: stepped(rb, dtype, "banded")
               for dtype in ("float32", "float64")}
    finally:
        mp.undo()


@pytest.fixture(scope="module")
def dense(rb):
    return stepped(rb, "float64", "dense")


@pytest.fixture()
def banded(deployments, request):
    return deployments[request.param]


both = pytest.mark.parametrize("banded", ["float32", "float64"],
                               indirect=True)


@both
def test_ten_steps_match_the_dense_path(banded, dense):
    solver = banded.solver
    assert type(solver.ops).__name__ == "BandedOps"
    assert solver.ops._g_chunks > 1
    assert "interior" in solver.timestepper._lhs_aux[0]   # packed factors
    dtype = np.dtype(solver.pencil_dtype).name
    assert type(dense.solver.ops).__name__ == "DenseOps"
    got, ref = banded.compared(), dense.compared()
    assert np.isfinite(got).all()
    rel = np.linalg.norm(got - ref) / np.linalg.norm(ref)
    assert rel < TRAJECTORY_TOL[dtype], rel


@both
def test_compared_leaves_the_conduction_profile_out(banded):
    b = np.asarray(banded.fields["b"]["c"], dtype=np.float64)
    u = np.asarray(banded.fields["u"]["c"], dtype=np.float64)
    got = banded.compared()
    assert got.size == b[2:].size + u.size
    # the two kx = 0 rows hold the conduction profile: nearly all of b
    assert np.linalg.norm(b[:2]) > 100 * np.linalg.norm(b[2:])


@both
def test_invariants_hold(rb, banded):
    tol = rb.SPEC["tolerances"]
    values = banded.invariants()
    assert set(values) == {"wall_bc", "continuity", "dense_residual"}
    for name, (value, bound) in values.items():
        assert bound == tol[name]["value"]
        assert value <= bound, (name, value)


@both
def test_dense_check_of_every_group(rb, banded):
    solver = banded.solver
    G = solver.pencil_shape[0]
    residuals = rb.dense_residuals(solver, range(G), banded.fixed_dt)
    assert len(residuals) == G
    bound = rb.SPEC["tolerances"]["dense_residual"]["value"]
    assert max(residuals.values()) <= bound, residuals
    assert set(rb.sampled_groups(G)) >= {0, 1, G // 2, G - 1}
    assert len(rb.sampled_groups(G)) == 8


@both
def test_dense_check_fails_without_the_woodbury_correction(rb, banded):
    solver = banded.solver
    stepper = solver.timestepper
    whole = stepper._lhs_aux
    broken = dict(whole[0], YbT=jnp.zeros_like(whole[0]["YbT"]))
    stepper._lhs_aux = [broken] * len(whole)
    try:
        residuals = rb.dense_residuals(solver, range(solver.pencil_shape[0]),
                                       banded.fixed_dt)
    finally:
        stepper._lhs_aux = whole
    bound = rb.SPEC["tolerances"]["dense_residual"]["value"]
    assert max(residuals.values()) > bound, residuals


@pytest.mark.parametrize("dtype", ["float32", "float64"])
def test_incremental_factor_is_the_one_dispatch_factor(rb, chunked, dtype):
    dep = rb.build(0, dtype=np.dtype(dtype), size=dict(SIZE,
                                                       matsolver="banded"))
    solver = dep.solver
    ops, M, L = solver.ops, solver.M_mat, solver.L_mat
    rd = solver.real_dtype
    a = jnp.asarray(1.0, dtype=rd)
    b = jnp.asarray(dep.fixed_dt * rb.RK222_GAMMA, dtype=rd)
    assert ops.use_incremental_factor(*np.shape(solver.X)[:1],
                                      np.dtype(rd).itemsize)
    stepwise = ops.factor_lincomb_incremental(a, M, L, b_scale=b)
    whole = jax.jit(lambda M, L: ops.factor_lincomb(a, M, b, L))(M, L)
    assert jax.tree.structure(stepwise) == jax.tree.structure(whole)
    eps = np.finfo(rd).eps
    for got, want in zip(jax.tree.leaves(stepwise), jax.tree.leaves(whole)):
        assert got.shape == want.shape and got.dtype == want.dtype
        got, want = np.asarray(got), np.asarray(want)
        if got.dtype.kind in "iu":
            assert (got == want).all()
        else:
            scale = max(np.abs(want).max(), 1.0)
            assert np.abs(got - want).max() <= 64 * eps * scale


NEW_SCOPES = ["banded.fwd", "banded.bwd", "banded.woodbury", "banded.refine"]


@pytest.fixture(scope="module", params=["on", "off"])
def lowered_step_text(rb, request):
    """The single-step program's lowering, once per substitution."""
    mp = pytest.MonkeyPatch()
    mp.setitem(config["fusion"], "FUSED_SOLVE", request.param)
    try:
        dep = rb.build(0, size=dict(SIZE, matsolver="banded"))
        solver = dep.solver
        ts = solver.timestepper
        ts._ensure_factor(dep.fixed_dt)
        rd = solver.real_dtype
        yield ts._step.lower(
            solver.M_mat, solver.L_mat, solver.X,
            jnp.asarray(0.0, dtype=rd), jnp.asarray(dep.fixed_dt, dtype=rd),
            solver.rhs_extra(), ts._lhs_aux).as_text(debug_info=True)
    finally:
        mp.undo()


@pytest.mark.parametrize("scope", NEW_SCOPES + ["banded.matvec_pair",
                                                "banded.solve"])
def test_solve_scopes_are_in_the_lowered_step(lowered_step_text, scope):
    assert f"dedalus/matsolve/{scope}" in lowered_step_text


def test_factor_chunk_spans_count_the_chunks(rb, chunked):
    dep = rb.build(0, size=dict(SIZE, matsolver="banded"))
    was = tracing.enabled()
    tracing.enable()
    try:
        tracing.recorder().clear()
        dep.solver.step(dep.fixed_dt)
        spans = tracing.recorder().spans()
    finally:
        if not was:
            tracing.disable()
    chunks = [s for s in spans if s.name == "factor/chunk"]
    C = dep.solver.ops._g_chunks
    assert C > 1 and len(chunks) == C
    assert sorted(s.attrs["chunk"] for s in chunks) == list(range(C))
    assert {s.attrs["chunks"] for s in chunks} == {C}
    factor = [s for s in spans if s.name == "step/factor"]
    assert len(factor) == 1
    # inside step/factor (under the build's own `build/factor` there: the
    # run's first factorization is booked as cold start)
    booked = [s for s in spans if s.name == "build/factor"
              and s.parent_id == factor[0].span_id]
    assert len(booked) == 1
    assert {s.parent_id for s in chunks} == {booked[0].span_id}
    # phases are exclusive (PR 37): what the span brackets is `factor` and
    # the uploads of the factor programs' lifted constants inside it
    record = dep.solver.build_phases.record()
    assert record["factor_sec"] + record["upload_sec"] >= booked[0].dur
    assert record["factor_sec"] > 0.5 * booked[0].dur


@pytest.mark.parametrize("limit, setting, fused", [
    (None, "auto", True),       # a backend that reports no limit: the CPU
    (16.9e9, "auto", True),     # a v5e holds 32 groups of anything
    (4e5, "auto", False),       # operators + bands + Woodbury > 3/4 of it
    (4e5, "on", True),          # an explicit `on` is never overridden
    (4e5, "off", False),
])
def test_fused_solve_auto_yields_to_device_memory(rb, monkeypatch, limit,
                                                  setting, fused):
    monkeypatch.setattr(pencilops, "device_memory_bytes", lambda: limit)
    monkeypatch.setitem(config["fusion"], "FUSED_SOLVE", setting)
    dep = rb.build(0, size=dict(SIZE, matsolver="banded"))
    dep.solver.step(dep.fixed_dt)
    aux = dep.solver.timestepper._lhs_aux[0]
    assert ("fsub" in aux) == fused and ("interior" in aux) == (not fused)
    assert np.isfinite(np.asarray(dep.solver.X)).all()


def test_published_size_maps_a_dense_reference_onto_banded(rb):
    """552 GB of dense float64 pencils cannot exist; 64x32's 19 MB can."""
    limit = rb.DENSE_REFERENCE_LIMIT_BYTES
    sizes = rb.SPEC["sizes"]
    published = (sizes["Nx"] // 2) * (8 * sizes["Nz"] + 14) ** 2 * 8
    rehearsal = (SIZE["Nx"] // 2) * (8 * SIZE["Nz"] + 14) ** 2 * 8
    assert rehearsal < limit < published
    assert rb.SPEC["reference"]["dt"] == rb.SPEC["fixed_dt"]
    assert rb.SPEC["rehearsal"]["matsolver"] == "banded"

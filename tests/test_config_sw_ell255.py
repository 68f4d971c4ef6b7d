"""
sw_ell255 at a size the CPU holds (sphere shallow water 32 x 32, banded
forced), through the configuration's own file,
chipbench/configs/sw_ell255.py: what the cell `sw_ell255.block` relies on,
checked where it costs no chip time.

  (a) ten float32 steps through `BandedOps` against the float64 plain path
      (dense pencils, fusion off, transforms as matrix products), within
      the tolerance the configuration's file states;
  (b) `compared()` is decided by the part of the state that evolves: the
      steady zonal part, which it leaves out, is nearly all of the norm;
  (c) the invariants hold, and `dense_residual` bites on a broken factor;
  (d) `swsh_synthesis` against SciPy, every azimuthal order, float64;
  (e) the scopes the per-layer metrics read are in the lowered step, no
      stack product is left under a bare `dedalus/evaluator/rhs`, the
      products are as many as sw_ell255.json's `swsh_shapes` says, and the
      ladder stacks (diagonal in l) are multiplies by a table;
  (f) the balanced height satisfies the LBVP's equation.
"""

import collections
import pathlib
import re

import numpy as np
import pytest
import jax.numpy as jnp

from dedalus_tpu.tools.config import config

ROOT = pathlib.Path(__file__).resolve().parents[1]
SIZE = {"Nphi": 32, "Ntheta": 32}
STEPS = 10
STACK_PRODUCT = "gji,...gpi->...gpj"        # apply_group_stack's einsum
SCOPES = {"swsh.bwd": "dedalus/transform/SphereBasis.swsh.bwd",
          "swsh.fwd": "dedalus/transform/SphereBasis.swsh.fwd",
          "group_stack": "dedalus/evaluator/group_stack"}


@pytest.fixture(scope="module")
def sw():
    from chipbench.manifest import load_module
    return load_module(ROOT / "chipbench" / "configs" / "sw_ell255.py")


def stepped(sw, dtype, matsolver):
    dep = sw.build(0, dtype=np.dtype(dtype),
                   size=dict(SIZE, matsolver=matsolver))
    dep.start = dep.compared()
    for _ in range(STEPS):
        dep.solver.step(dep.fixed_dt)
    return dep


@pytest.fixture(scope="module")
def banded(sw):
    """float32 through BandedOps, as the cell runs it."""
    return stepped(sw, "float32", "banded")


@pytest.fixture(scope="module")
def plain(sw):
    """What the reference child does (chipbench/reference.py): float64,
    dense pencils, every fusion off, transforms as matrix products."""
    from chipbench.reference import PLAIN_PATH
    mp = pytest.MonkeyPatch()
    for section, keys in PLAIN_PATH.items():
        for key, value in keys.items():
            mp.setitem(config[section], key, value)
    try:
        yield stepped(sw, "float64", "dense")
    finally:
        mp.undo()


def test_ten_steps_match_the_float64_plain_path(sw, banded, plain):
    assert type(banded.solver.ops).__name__ == "BandedOps"
    assert banded.solver.ops.q == 7
    assert type(plain.solver.ops).__name__ == "DenseOps"
    got, ref = banded.compared(), plain.compared()
    assert np.isfinite(got).all()
    rel = np.linalg.norm(got - ref) / np.linalg.norm(ref)
    assert rel <= sw.SPEC["tolerances"]["ref_rel_l2"]["value"], rel
    # and the ten steps moved what is compared by more than its own size:
    # a run that stood still would not pass for one that stepped
    assert np.linalg.norm(ref - plain.start) > np.linalg.norm(plain.start)


def test_compared_is_decided_by_the_evolving_part(sw, plain):
    """The m = 0 rows (the jet and its balanced height) carry over a
    hundred times the norm of what `compared()` reads; an error of the
    tolerance's size in ONE m != 0 coefficient fails the comparison, and
    would be a hundredth of the tolerance against the whole state."""
    p, f = plain.params, plain.fields
    h = np.asarray(f["h"]["c"], dtype=np.float64)
    u = np.asarray(f["u"]["c"], dtype=np.float64)
    ref = plain.compared()
    assert ref.size == h[2:].size + u[:, 2:].size
    zonal = np.sqrt(p["g"] * (h[:2] ** 2).sum() + p["H"] * (u[:, :2] ** 2).sum())
    assert zonal > 100 * np.linalg.norm(ref)
    tol = sw.SPEC["tolerances"]["ref_rel_l2"]["value"]
    error = 1.5 * tol * np.linalg.norm(ref) / np.sqrt(p["g"])
    wrong = h.copy()
    wrong[2 * 3, 5] += error                       # m = 3, l = 5, cos slot
    f["h"]["c"] = wrong
    try:
        got = plain.compared()
    finally:
        f["h"]["c"] = h
    assert np.linalg.norm(got - ref) / np.linalg.norm(ref) > tol
    assert np.sqrt(p["g"]) * error / zonal < tol / 50


def test_invariants_hold(sw, banded):
    tol = sw.SPEC["tolerances"]
    values = banded.invariants()
    assert set(values) == {"mass", "lbvp_balance", "dense_residual",
                           "swsh_synthesis"}
    for name, (value, bound) in values.items():
        assert bound == tol[name]["value"]
        assert 0 <= value <= bound, (name, value)


def test_mass_sees_a_drifting_mean(sw, banded):
    h = banded.fields["h"]
    was = np.asarray(h["c"]).copy()
    moved = was.copy()
    moved[0, 0] += 10 * sw.SPEC["tolerances"]["mass"]["value"] \
        * banded.params["h_scale"]
    h["c"] = moved
    try:
        value, bound = banded.invariants()["mass"]
    finally:
        h["c"] = was
    assert value > bound


def test_dense_check_fails_on_a_broken_factor(sw, banded):
    """Without the Woodbury correction the banded solve is wrong in the
    pinned rows, and the float64 dense check says so."""
    checks = sw._banded_checks()
    solver = banded.solver
    stepper = solver.timestepper
    whole = stepper._lhs_aux
    groups = range(solver.pencil_shape[0])
    bound = sw.SPEC["tolerances"]["dense_residual"]["value"]
    good = checks.dense_residuals(solver, groups, banded.fixed_dt)
    assert max(good.values()) <= bound, good
    broken = dict(whole[0], YbT=jnp.zeros_like(whole[0]["YbT"]))
    stepper._lhs_aux = [broken] * len(whole)
    try:
        bad = checks.dense_residuals(solver, groups, banded.fixed_dt)
    finally:
        stepper._lhs_aux = whole
    assert max(bad.values()) > bound, bad


def test_swsh_synthesis_agrees_with_scipy_for_every_order(sw):
    """The package-independent check, at float64 on every m: the
    normalisation `synthesis_by_scipy` states is the data format's."""
    import dedalus_tpu.public as d3
    coords = d3.S2Coordinates("phi", "theta")
    dist = d3.Distributor(coords, dtype=np.float64)
    basis = d3.SphereBasis(coords, shape=(32, 16), dtype=np.float64,
                           radius=1, dealias=3 / 2)
    G = basis.shape[0] // 2
    assert sw.swsh_synthesis_error(basis, dist, list(range(G))) < 1e-12
    picked = sw.sampled_orders(256)
    assert len(picked) == 8 and {0, 1, 128, 255} <= set(picked)
    # a wrong normalisation is seen: one coefficient off by a percent
    f = dist.Field(name="f", bases=basis)
    c = np.zeros(basis.shape)
    c[2 * 3, 5] = 1.0
    f["c"] = c
    f.change_scales(basis.dealias)
    phi, theta = basis.global_grids(basis.dealias)
    rows = np.zeros((2, 16))
    rows[0, 5] = 1.01
    want = sw.synthesis_by_scipy(rows, [3], np.ravel(phi), np.ravel(theta))
    assert np.abs(np.asarray(f["g"]) - want).max() > 1e-3


@pytest.fixture(scope="module")
def stack_products(banded):
    """{scope: count} of the stack products (`apply_group_stack`'s einsum)
    in the lowered single step, by the innermost scope that names them, and
    of the multiplies under the group stacks' scope; and the text."""
    solver = banded.solver
    ts, rd = solver.timestepper, solver.real_dtype
    text = ts._step.lower(
        solver.M_mat, solver.L_mat, solver.X, jnp.asarray(0.0, dtype=rd),
        jnp.asarray(banded.fixed_dt, dtype=rd), solver.rhs_extra(),
        ts._lhs_aux).as_text(debug_info=True)
    paths = dict(re.findall(r"^(#loc\d+) = loc\((.*)\)$", text, flags=re.M))
    counts = collections.Counter()
    for line in text.splitlines():
        op = re.search(r"stablehlo\.(dot_general|multiply).*"
                       r"loc\((#loc\d+)\)\s*$", line)
        path = paths.get(op.group(2), "") if op else ""
        if op and op.group(1) == "multiply":
            counts["group_stack.multiply"] += SCOPES["group_stack"] in path
        elif STACK_PRODUCT in path or SCOPES["group_stack"] in path:
            named = [k for k, scope in SCOPES.items() if scope in path]
            counts[named[0] if named else "bare"] += 1
    return counts, text


@pytest.mark.parametrize("scope", sorted(SCOPES.values())
                         + ["dedalus/transform/spin_recombination"])
def test_new_scopes_are_in_the_lowered_step(stack_products, scope):
    assert scope in stack_products[1]


def test_no_stack_product_is_left_under_a_bare_rhs(sw, banded, stack_products):
    """Every product of a per-m stack carries a transform's scope, and a
    step holds as many as `swsh_shapes.calls_per_rhs` counts (the numerator
    of `swsh_mmt_roofline`): 12 onto the colatitude grid, 8 back from it.
    The 12 ladder stacks in coefficient space are diagonal in l: 12
    multiplies by a (Nphi, Ntheta) table under their scope, no product, and
    no (G, Ntheta, Ntheta) stack among the step's arguments."""
    counts, text = stack_products
    assert counts["bare"] == 0, counts
    calls = sw.SPEC["swsh_shapes"]["calls_per_rhs"]
    stages = 2                                              # RK222
    assert counts["swsh.bwd"] == stages * sum(calls["bwd"].values()) == 12
    assert counts["swsh.fwd"] == stages * sum(calls["fwd"].values()) == 8
    assert counts["group_stack"] == 0
    assert counts["group_stack.multiply"] == 12
    G, N = SIZE["Nphi"] // 2, SIZE["Ntheta"]
    signature = text.split("@main(")[1].split(") ->")[0]
    assert f"tensor<{G}x{N * 3 // 2}x{N}x" in signature     # a SWSH stack
    assert f"tensor<{G}x{N}x{N}x" not in signature
    assert banded.solver.build_phases.record()["group_stacks"] == {
        "diagonal": {"stacks": 4, "applications": 12},
        "matmul": {"stacks": 0, "applications": 0}}


@pytest.mark.parametrize("dtype, bound", [("float32", None),
                                          ("float64", 1e-12)])
def test_balanced_height_satisfies_the_lbvp(sw, banded, plain, dtype, bound):
    dep = banded if dtype == "float32" else plain
    if bound is None:
        bound = sw.SPEC["tolerances"]["lbvp_balance"]["value"]
    assert 0 < dep.params["lbvp_balance"] <= bound


def test_published_size_maps_a_dense_reference_onto_banded(sw):
    """Three float64 stacks of 4.8 GB beside the run's own build do not
    fit a 40 GiB host; the rehearsal's 19 MB do."""
    limit = sw.DENSE_REFERENCE_LIMIT_BYTES
    sizes, small = sw.SPEC["sizes"], sw.SPEC["rehearsal"]
    stack = lambda s: (s["Nphi"] // 2) * (6 * s["Ntheta"]) ** 2 * 8  # noqa: E731,E501
    assert stack(small) < limit < stack(sizes)
    assert sw.SPEC["reference"]["dt"] == sw.SPEC["fixed_dt"]
    assert small["matsolver"] == "banded"
    assert sw.SPEC["banded_shapes"]["q"] == 7

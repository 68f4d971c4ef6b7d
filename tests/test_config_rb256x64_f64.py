"""
rb256x64-f64 at a size the CPU holds (Rayleigh-Benard 64 x 16, float64),
through the configuration's own file, chipbench/configs/rb256x64-f64.py:
what the cell `rb256x64-f64.block10` relies on, checked where it costs no
chip time. On the CPU a float64 build wires no double-double runner, so
the builds here are steered onto the route a TPU takes from inside the
test: the backend name is monkeypatched around `build`, as
tests/test_chip_compile.py does, and everything then runs on the CPU.

  (a) ten `solver.step` and one `solver.step_many(10)` on the dd route
      agree with the native float64 plain path (dense pencils, fusion off,
      transforms as matrix products) within the file's limit, and with
      each other;
  (b) a float32 state fails the file's limits: the same deployment built
      in float32 by two orders and more, the dd state merely rounded to
      float32 too;
  (c) `f64_route` fails on a float32 build and names the route otherwise;
  (d) the scopes the per-layer metrics read are in the lowered dd step, no
      int8 plane product is left outside them, and the pencil products are
      as many as rb256x64-f64.json's `dd_shapes` says;
  (e) the host spans are in the ring after a traced step and block, and the
      first factorization is booked as the build's `factor` phase;
  (f) `chipbench/costs_dd.py` against counts by hand at two shapes.
"""

import collections
import contextlib
import pathlib
import re

import numpy as np
import pytest
import jax

from dedalus_tpu.tools import tracing
from dedalus_tpu.tools.config import config

ROOT = pathlib.Path(__file__).resolve().parents[1]
STEPS = 10
PRODUCTS = 6      # RK222 at two sweeps: M X0, L X1, 4 A x
SCOPES = {
    "matvec": "dedalus/matsolve/dd.matvec",
    "residual": "dedalus/matsolve/dd.residual",
    "refine": "dedalus/matsolve/dd.refine",
    "fourier.bwd": "dedalus/transform/RealFourier.dd.bwd",
    "fourier.fwd": "dedalus/transform/RealFourier.dd.fwd",
    "chebyshev.bwd": "dedalus/transform/Jacobi.dd.bwd",   # ChebyshevT's class
    "chebyshev.fwd": "dedalus/transform/Jacobi.dd.fwd",
    "rhs": "dedalus/evaluator/dd.rhs",
    "combine": "dedalus/step/dd.combine",
}


@pytest.fixture(scope="module")
def cfg():
    from chipbench.manifest import load_module
    return load_module(ROOT / "chipbench" / "configs" / "rb256x64-f64.py")


@contextlib.contextmanager
def plain_path():
    """chipbench/reference.py's settings, as its child sets them."""
    from chipbench.reference import PLAIN_PATH
    was = {(s, k): config[s].get(k) for s, keys in PLAIN_PATH.items()
           for k in keys}
    for section, keys in PLAIN_PATH.items():
        config[section].update(keys)
    try:
        yield
    finally:
        for (section, key), value in was.items():
            if value is None:
                config.remove_option(section, key)
            else:
                config[section][key] = value


def build_as_on_a_tpu(cfg, **size):
    """The configuration's `build` with the backend name a TPU gives, so
    that `InitialValueSolver` wires what it wires there."""
    mp = pytest.MonkeyPatch()
    mp.setattr(jax, "default_backend", lambda: "tpu")
    try:
        return cfg.build(0, size=dict(cfg.SPEC["rehearsal"], **size))
    finally:
        mp.undo()


@pytest.fixture(scope="module")
def native(cfg):
    """The reference's path: native float64 on the CPU, no runner."""
    with plain_path():
        dep = cfg.build(0, dtype=np.dtype("float64"),
                        size=dict(cfg.SPEC["rehearsal"], matsolver="dense"))
        assert dep.solver._dd is None
        for _ in range(STEPS):
            dep.solver.step(dep.fixed_dt)
        return dep, dep.compared()


def advanced(cfg, advance):
    """(deployment, compared(), spans) of a dd-route build advanced by
    `advance(dep)` and read once, under `tracing.enable()`."""
    dep = build_as_on_a_tpu(cfg)
    was = tracing.enabled()
    tracing.enable()
    try:
        tracing.recorder().clear()
        advance(dep)
        got = dep.compared()
        spans = tracing.recorder().spans()
    finally:
        if not was:
            tracing.disable()
    return dep, got, spans


@pytest.fixture(scope="module")
def stepped(cfg):
    """The dd route by ten `solver.step`."""
    return advanced(cfg, lambda dep: [dep.solver.step(dep.fixed_dt)
                                      for _ in range(STEPS)])


@pytest.fixture(scope="module")
def blocked(cfg):
    """The dd route by one `solver.step_many(10)`."""
    return advanced(cfg, lambda dep: dep.solver.step_many(STEPS,
                                                          dep.fixed_dt))


@pytest.fixture(scope="module")
def single(cfg):
    """The same deployment built in float32, ten steps."""
    dep = build_as_on_a_tpu(cfg, dtype="float32")
    for _ in range(STEPS):
        dep.solver.step(dep.fixed_dt)
    return dep, dep.compared()


def rel_l2(got, ref):
    return float(np.linalg.norm(got - ref) / np.linalg.norm(ref))


# ---- (a) ----

@pytest.mark.parametrize("advance", ["stepped", "blocked"])
def test_dd_route_agrees_with_native_float64(cfg, native, advance, request):
    dep, got, _ = request.getfixturevalue(advance)
    solver = dep.solver
    assert solver._dd is not None and solver.iteration == STEPS
    assert type(solver.ops).__name__ == "DenseOps"
    assert solver.X.dtype == np.float32            # the f32 view, hi
    assert got.dtype == np.float64
    limit = cfg.SPEC["tolerances"]["ref_rel_l2"]["value"]
    assert limit <= 1e-9
    assert rel_l2(got, native[1]) < limit


def test_step_and_step_many_agree(stepped, blocked):
    assert rel_l2(blocked[1], stepped[1]) < 1e-12
    assert blocked[0].solver.sim_time == pytest.approx(
        stepped[0].solver.sim_time, rel=1e-14)


def test_invariants_hold_on_the_dd_route(stepped):
    dep = stepped[0]
    for name, (value, limit) in dep.invariants().items():
        assert value <= limit, (name, value, limit)


# ---- (b), (c) ----

def test_a_float32_build_fails_by_two_orders(cfg, native, single):
    dep, got = single
    tol = cfg.SPEC["tolerances"]
    assert rel_l2(got, native[1]) > 100 * tol["ref_rel_l2"]["value"]
    found = dep.invariants()
    assert found["wall_bc"][0] > 100 * tol["wall_bc"]["value"]
    assert found["f64_route"][0] > found["f64_route"][1]
    assert dep.solver.build_phases.record()["f64_route"] is None


def test_the_dd_state_rounded_to_float32_fails(cfg, native, stepped):
    dep, got, _ = stepped
    tol = cfg.SPEC["tolerances"]
    rounded = got.astype(np.float32).astype(np.float64)
    assert rel_l2(rounded, native[1]) > 10 * tol["ref_rel_l2"]["value"]
    # the walls: rb256x64's endpoint sums on the rounded coefficients
    from chipbench.manifest import load_module
    rb = load_module(ROOT / "chipbench" / "configs" / "rb256x64.py")
    top, bottom = rb._endpoint_weights(rounded.shape[-1])
    b_bottom = rounded @ bottom
    b_bottom[0] -= cfg.SPEC["sizes"]["Lz"]
    wall = max(np.abs(b_bottom).max(), np.abs(rounded @ top).max())
    assert wall > 100 * tol["wall_bc"]["value"]


@pytest.mark.parametrize("advance, route", [
    ("stepped", "dd"), ("blocked", "dd"), ("native", "xla_f64")])
def test_f64_route_names_the_route(advance, route, request):
    dep = request.getfixturevalue(advance)[0]
    record = dep.solver.build_phases.record()
    assert record["f64_route"] == route
    if route == "dd":
        assert record["dd"]["slices"] == 8 and record["dd"]["refine"] == 2
    else:
        assert "dd" not in record
    found = dep.invariants()["f64_route"]
    assert found[0] <= found[1]


# ---- (d) ----

@pytest.fixture(scope="module")
def lowered(stepped):
    """{scope: int8 dot_generals} of the lowered single dd step by the
    innermost scope of this PR that names them, those with a (G, S, S)
    plane as operand apart, and the text."""
    from dedalus_tpu.core.ddstep import _dd_scalar
    dep = stepped[0]
    dd = dep.solver._dd
    lhs_list, t_dd = dd._rk_prepare(dep.fixed_dt)
    text = dd._rk_step.lower(
        dd.X, t_dd, _dd_scalar(dep.fixed_dt), lhs_list,
        dd._extras_dd()).as_text(debug_info=True)
    paths = dict(re.findall(r"^(#loc\d+) = loc\((.*)\)$", text, flags=re.M))
    G, S = dep.solver.pencil_shape
    counts, pencil = collections.Counter(), collections.Counter()
    innermost = sorted(SCOPES.values(), key=lambda s: (
        "matsolve/dd.refine" in s, "evaluator" in s))   # outer scopes last
    for line in text.splitlines():
        op = re.search(r"stablehlo\.dot_general.*xi8>.*loc\((#loc\d+)\)\s*$",
                       line)
        if not op:
            continue
        path = paths.get(op.group(1), "")
        named = [s for s in innermost if s in path]
        counts[named[0] if named else "bare"] += 1
        if f"tensor<{G}x{S}x{S}xi8>" in line:
            pencil[named[0] if named else "bare"] += 1
    return counts, pencil, text


@pytest.mark.parametrize("scope", sorted(SCOPES.values()))
def test_dd_scopes_are_in_the_lowered_step(lowered, scope):
    assert scope in lowered[2]


def test_no_int8_product_is_outside_the_dd_scopes(cfg, stepped, lowered):
    counts, pencil, _ = lowered
    assert counts["bare"] == 0, counts
    # 36 plane products a product (8 slices: levels p + q < 8)
    per = 8 * 9 // 2
    # M X0 and L X1 (L X0 is read by no stage of RK222 and is not traced)
    assert pencil == {SCOPES["matvec"]: 2 * per, SCOPES["residual"]: 4 * per}
    from chipbench import costs_dd
    assert costs_dd.products_per_step(cfg.SPEC["dd_shapes"], stages=2) \
        == sum(pencil.values()) // per == PRODUCTS
    # what the program counted of itself while it was traced
    dd = stepped[0].solver.build_phases.record()["dd"]
    assert dd["int8_dots_per_step"] == sum(counts.values())
    G, S = stepped[0].solver.pencil_shape
    assert dd["plane_MB"] >= round(3 * 8 * G * S * S / 1e6, 1)   # M, L, A
    # the sweeps' float32 solves: the stored inverse alone (PR 36), read
    # once by each of a stage's first solve and two corrections
    assert dd["f32_solver"] == "BatchedInverse"
    assert dd["f32_stack_reads_per_step"] == 2 * 3


def test_scan_block_counts_one_step(stepped, blocked):
    """`step_many` traces one step inside its scan: the same count."""
    one = stepped[0].solver.build_phases.record()["dd"]
    assert blocked[0].solver.build_phases.record()["dd"] == one


# ---- (e) ----

def test_spans_of_the_dd_route_are_in_the_ring(stepped, blocked):
    for (dep, _, spans), name, n in ((stepped, "step", STEPS),
                                     (blocked, "step_many", 1)):
        roots = [s for s in spans if s.name == name]
        assert len(roots) == n
        assert {s.attrs["route"] for s in roots} == {"dd"}
        assert roots[0].attrs["iteration"] == 0
        factor = [s for s in spans if s.name == "step/factor"]
        assert len(factor) == 1 and factor[0].parent_id == roots[0].span_id
        assert factor[0].attrs["dt"] == dep.fixed_dt
        booked = [s for s in spans if s.name == "build/factor"
                  and s.parent_id == factor[0].span_id]
        assert len(booked) == 1
        record = dep.solver.build_phases.record()
        # phases are exclusive (PR 37): the span brackets `factor` and the
        # upload of the factor program's lifted constants (M's and L's
        # float32 pairs) inside it
        assert record["factor_sec"] + record["upload_sec"] \
            >= booked[0].dur > 0
        assert record["factor_sec"] > 0
        assert record["compile_sec"] > 0
        # the initial conditions were set on the fields: one re-gather
        gather = [s for s in spans if s.name == "dd/gather"]
        assert len(gather) == 1 and gather[0].parent_id == roots[0].span_id
        # one state was read (compared()): one pull for all its fields
        assert sum(s.name == "dd/pull" for s in spans) == 1
    assert [s.attrs["n"] for s in blocked[2] if s.name == "step_many"] \
        == [STEPS]


# ---- (f) ----

@pytest.mark.parametrize("G, S, stages, sweeps, products, nbytes", [
    (2, 3, 2, 2, 6, 6 * 8 * 2 * 9),                     # by hand
    (128, 526, 2, 2, 6, 1_699_897_344),                 # the published size
    (128, 526, 3, 1, 5, 1_416_581_120),                 # 1 + 1 + 3
])
def test_costs_dd_by_hand(cfg, G, S, stages, sweeps, products, nbytes):
    from chipbench import costs_dd
    shapes = dict(cfg.SPEC["dd_shapes"], sweeps=sweeps)
    cost = costs_dd.rk_dd_step(shapes, G, S, stages)
    assert cost["products"] == products
    assert cost["bytes_per_product"] == 8 * G * S * S
    assert cost["bytes"] == nbytes

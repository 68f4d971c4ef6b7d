"""
Group stacks that are diagonal in their coupled index are applied as a
multiply (core/curvilinear.apply_group_stack, `stack_diagonal`): the rule
reads the stack's own entries, the multiply equals the batched product, any
other stack keeps the product, and the tally says which way a program went.

  (a) the sphere's ladder and Laplacian stacks, real and complex: the rule
      says diagonal, and the table is the diagonals, a row per slot of the
      packed azimuthal axis;
  (b) `apply_term` on seeded data against the dense einsum, float64 and
      float32, scalar, vector and rank-2 operands, group width 2 and 1;
  (c) tridiagonal sphere stacks, disk and annulus ladder stacks and a
      rectangular SWSH transform stack: the rule says no, and the lowered
      op is still the `dot_general`;
  (d) the table is memoised per stack object and lifted as ONE constant;
  (e) the tally counts what was lowered, once per traced application, and
      a built solver carries it.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import dedalus_tpu.public as d3
from dedalus_tpu.core import curvilinear
from dedalus_tpu.core.curvilinear import (
    apply_group_stack, gblocks_snapshot, gblocks_tally, stack_diagonal)
from dedalus_tpu.core.operators import GROUP_STACK_SCOPE, apply_term
from dedalus_tpu.tools.jitlift import lifted_jit

SPINS = (-2, -1, 0, 1, 2)
DIAGONAL = [("ladder_stack", (s, ds)) for s in SPINS for ds in (+1, -1)] \
    + [("laplacian_stack", (s,)) for s in SPINS]
DTYPES = {"real": np.float64, "complex": np.complex128}


def ident(param):
    name, args = param
    return name.split("_")[0] + "".join(f"{a:+d}" for a in args)


def sphere(kind, shape=(16, 12)):
    """Basis and the width of one azimuthal group: a (cos, -sin) pair of
    rows for a real dtype, one row for a complex one."""
    cs = d3.S2Coordinates("phi", "theta")
    basis = d3.SphereBasis(cs, shape=shape, dtype=DTYPES[kind], radius=1.5,
                           dealias=(3 / 2, 3 / 2))
    return basis, basis.sub_group_shape(0)


def polar(which):
    cs = d3.PolarCoordinates("phi", "r")
    if which == "disk":
        return d3.DiskBasis(cs, shape=(16, 12), dtype=np.float64, radius=1.5)
    return d3.AnnulusBasis(cs, shape=(16, 12), dtype=np.float64,
                           radii=(1.0, 2.0))


def dense(stack, data, width):
    """The batched product, as NumPy writes it in float64."""
    G, N = stack.shape[0], data.shape[-1]
    d = data.reshape(data.shape[:-2] + (G, width, N))
    out = np.einsum("gji,...gpi->...gpj", stack, d)
    return out.reshape(data.shape[:-2] + (G * width, stack.shape[1]))


def seeded(shape, kind, seed):
    rng = np.random.default_rng(seed)
    data = rng.standard_normal(shape)
    if kind == "complex":
        data = data + 1j * rng.standard_normal(shape)
    return data


def lowered_ops(fn, *args):
    """{stablehlo op: count} of the lowered program."""
    text = jax.jit(fn).lower(*args).as_text()
    return {op: text.count(f"stablehlo.{op} ") + text.count(f"stablehlo.{op}(")
            for op in ("dot_general", "multiply")}


# ---------------------------------------------------------------- (a)

@pytest.mark.parametrize("kind", sorted(DTYPES))
@pytest.mark.parametrize("which", DIAGONAL, ids=ident)
def test_sphere_stack_diagonal_in_l_is_seen(kind, which):
    basis, width = sphere(kind)
    stack = getattr(basis, which[0])(*which[1])
    G, N = stack.shape[:2]
    assert stack.shape == (G, N, N) and G * width == basis.shape[0]
    table = stack_diagonal(stack, width)
    assert table is not None and table.shape == (G * width, N)
    assert np.count_nonzero(table) == width * np.count_nonzero(stack) > 0
    for g in range(G):
        for p in range(width):
            assert np.array_equal(table[g * width + p], np.diag(stack[g]))


# ---------------------------------------------------------------- (b)

@pytest.mark.parametrize("precision", ["float64", "float32"])
@pytest.mark.parametrize("rank", [0, 1, 2])
@pytest.mark.parametrize("kind", sorted(DTYPES))
def test_apply_term_equals_the_dense_product(kind, rank, precision):
    """Every diagonal stack of the basis on one operand: scalar (rank 0),
    vector, rank-2 tensor; the real basis packs two rows a group, the
    complex one."""
    basis, width = sphere(kind)
    tshape = (2,) * rank
    data = seeded(tshape + basis.shape, kind, seed=rank)
    low = precision == "float32"
    if low:
        data = data.astype(np.complex64 if kind == "complex" else np.float32)
    for which in DIAGONAL:
        stack = getattr(basis, which[0])(*which[1])
        descrs = [None, ("gblocks", 0, stack)]
        got = np.asarray(apply_term(jnp.asarray(data), None, descrs,
                                    tshape, tshape, rank))
        assert got.dtype == data.dtype and got.shape == data.shape
        if low:
            # against the product in the same precision: one rounding of
            # one product either way, so at most one unit in the last place
            want = np.asarray(jnp.einsum(
                "gji,...gpi->...gpj", jnp.asarray(stack, dtype=np.float32),
                jnp.asarray(data).reshape(tshape + (-1, width,
                                                    data.shape[-1]))))
            want = want.reshape(data.shape)
            ulp = np.spacing(np.maximum(np.abs(want.real), np.abs(want.imag))
                             .astype(np.float32))
            assert (np.abs(got - want) <= ulp).all(), which
        else:
            want = dense(stack, data, width)
            assert np.abs(got - want).max() <= 1e-14 * np.abs(want).max(), \
                which


# ---------------------------------------------------------------- (c)

# stacks that are NOT diagonal, each with two rows a group (real dtypes)
OTHERS = {
    "sphere.cos": lambda: sphere("real")[0].cos_stack(1),
    "sphere.sin": lambda: sphere("real")[0].sin_stack(1, 0),
    "sphere.swsh_backward":
        lambda: sphere("real")[0].radial_backward_stack(0, 1.5),
    "sphere.swsh_forward":
        lambda: sphere("real")[0].radial_forward_stack(1, 1.5),
    "disk.ladder": lambda: polar("disk").ladder_stack(0, +1),
    "disk.laplacian": lambda: polar("disk").laplacian_stack(0),
    "annulus.ladder": lambda: polar("annulus").ladder_stack(0, +1),
    "annulus.laplacian": lambda: polar("annulus").laplacian_stack(0),
}


@pytest.mark.parametrize("name", sorted(OTHERS))
def test_any_other_stack_keeps_the_product(name):
    stack, width = OTHERS[name](), 2
    assert stack_diagonal(stack, width) is None
    data = seeded((2, stack.shape[0] * width, stack.shape[2]), "real", seed=3)
    fn = lambda d: apply_group_stack(d, stack, 1, 2, width)   # noqa: E731
    ops = lowered_ops(fn, data)
    assert ops["dot_general"] == 1 and ops["multiply"] == 0, ops
    got = np.asarray(fn(jnp.asarray(data)))
    want = dense(stack, data, width)
    assert np.abs(got - want).max() <= 1e-13 * np.abs(want).max()


def test_a_diagonal_stack_lowers_to_a_multiply():
    basis, width = sphere("real")
    stack = basis.ladder_stack(1, -1)
    data = seeded((2,) + basis.shape, "real", seed=4)
    ops = lowered_ops(lambda d: apply_group_stack(d, stack, 1, 2, width),
                      data)
    assert ops["dot_general"] == 0 and ops["multiply"] == 1, ops


def test_one_entry_off_the_diagonal_is_enough():
    """The rule counts entries; it does not trust a producer's name."""
    basis, width = sphere("real")
    stack = basis.ladder_stack(0, +1).copy()
    assert stack_diagonal(stack, width) is not None
    off = stack.copy()
    off[1, 3, 4] = 1e-300
    assert stack_diagonal(off, width) is None
    assert stack_diagonal(jnp.asarray(stack), width) is None    # no host data


# ---------------------------------------------------------------- (d)

def test_the_table_is_memoised_with_its_stack():
    basis, width = sphere("real")
    stack = basis.laplacian_stack(1)
    assert basis.laplacian_stack(1) is stack            # producer-cached
    table = stack_diagonal(stack, width)
    assert stack_diagonal(stack, width) is table
    # another object with the same entries is decided for itself
    twin = stack.copy()
    assert stack_diagonal(twin, width) is not table
    assert np.array_equal(stack_diagonal(twin, width), table)


def test_the_table_is_lifted_as_one_constant():
    """At a size `match_precision` lifts (over 16 KB): the program takes
    the (G w, N) table as an argument, once for two applications, and no
    (G, N, N) stack."""
    basis, width = sphere("real", shape=(64, 48))
    stack = basis.ladder_stack(0, +1)
    G, N = stack.shape[:2]
    descrs = [None, ("gblocks", 0, stack)]

    def twice(d):
        once = apply_term(d, None, descrs, (), (), 0)
        return apply_term(once, None, descrs, (), (), 0)

    data = jnp.asarray(seeded(basis.shape, "real", seed=5))
    text = lifted_jit(twice).lower(data).as_text()
    signature = text.split("@main(")[1].split("->")[0]
    assert signature.count(f"tensor<{G * width}x{N}xf64>") == 2    # + data
    assert f"tensor<{G}x{N}x{N}xf64>" not in text


# ---------------------------------------------------------------- (e)

def test_the_tally_reads_what_was_lowered():
    """Two ladder applications and a cosine in one lifted program: the
    discovery pass of `lifted_jit` does not count, the trace does."""
    basis, width = sphere("real")
    up, down = basis.ladder_stack(0, +1), basis.ladder_stack(1, -1)
    cos = basis.cos_stack(0)

    def program(d):
        for stack in (up, down, cos, up):
            d = apply_term(d, None, [None, ("gblocks", 0, stack)], (), (), 0)
        return d

    data = jnp.asarray(seeded(basis.shape, "real", seed=6))
    before = gblocks_snapshot()
    text = lifted_jit(program).lower(data).as_text(debug_info=True)
    assert gblocks_tally(since=before) == {
        "diagonal": {"stacks": 2, "applications": 3},
        "matmul": {"stacks": 1, "applications": 1}}
    assert text.count("stablehlo.dot_general") == 1
    assert GROUP_STACK_SCOPE in text
    # the process's own total holds them too
    total = gblocks_tally()
    assert total["diagonal"]["applications"] >= 3
    assert total["matmul"]["applications"] >= 1
    # and a transform's stack product is no `gblocks` term: not counted
    before = gblocks_snapshot()
    apply_group_stack(data, basis.radial_backward_stack(0, 1.5), 0, 1, width)
    assert gblocks_tally(since=before) == {
        "diagonal": {"stacks": 0, "applications": 0},
        "matmul": {"stacks": 0, "applications": 0}}


def sphere_solver():
    """Linear shallow water with a Coriolis cosine on the right-hand side:
    div(u) is a ladder stack a spin component, MulCosine a tridiagonal."""
    cs = d3.S2Coordinates("phi", "theta")
    dist = d3.Distributor(cs, dtype=np.float64)
    basis = d3.SphereBasis(cs, shape=(16, 8), dtype=np.float64, radius=1,
                           dealias=(3 / 2, 3 / 2))
    u = dist.VectorField(cs, name="u", bases=basis)
    h = dist.Field(name="h", bases=basis)
    problem = d3.IVP([u, h], namespace=locals())
    problem.add_equation("dt(u) + grad(h) = - MulCosine(Skew(u))")
    problem.add_equation("dt(h) = - div(u)")
    h.fill_random("g", seed=7, scale=1e-2)
    return problem.build_solver(d3.RK222), {
        "diagonal": {"stacks": 2, "applications": 4},
        "matmul": {"stacks": 2, "applications": 4}}


def disk_solver():
    """Diffusion on the disk with half the Laplacian on the right-hand side:
    a Zernike stack, dense in the radial index."""
    cs = d3.PolarCoordinates("phi", "r")
    dist = d3.Distributor(cs, dtype=np.float64)
    disk = d3.DiskBasis(cs, shape=(16, 8), dtype=np.float64, radius=1)
    u = dist.Field(name="u", bases=disk)
    tau = dist.Field(name="tau", bases=disk.edge)
    lift = lambda A: d3.Lift(A, disk.derivative_basis(2), -1)  # noqa: E731
    problem = d3.IVP([u, tau], namespace=locals())
    problem.add_equation("dt(u) - lap(u) + lift(tau) = lap(u)")
    problem.add_equation("u(r=1) = 0")
    u.fill_random("g", seed=8, scale=1e-2)
    return problem.build_solver(d3.RK222), {
        "diagonal": {"stacks": 0, "applications": 0},
        "matmul": {"stacks": 1, "applications": 2}}


def cartesian_solver():
    coords = d3.CartesianCoordinates("x")
    dist = d3.Distributor(coords, dtype=np.float64)
    xbasis = d3.RealFourier(coords["x"], size=16, bounds=(0, 1))
    u = dist.Field(name="u", bases=xbasis)
    dx = lambda A: d3.Differentiate(A, coords["x"])     # noqa: E731
    problem = d3.IVP([u], namespace=locals())
    problem.add_equation("dt(u) - dx(dx(u)) = - u*dx(u)")
    u.fill_random("g", seed=9, scale=1e-2)
    return problem.build_solver(d3.RK222), {
        "diagonal": {"stacks": 0, "applications": 0},
        "matmul": {"stacks": 0, "applications": 0}}


@pytest.mark.parametrize("build", [sphere_solver, disk_solver,
                                   cartesian_solver],
                         ids=["sphere", "disk", "cartesian"])
def test_a_built_solver_carries_the_tally(build):
    """`build_phases.record()` has no tally before the first advance lowers
    the step program, and the step's own after it."""
    solver, expected = build()
    assert "group_stacks" not in solver.build_phases.record()
    solver.step(1e-3)
    assert solver.build_phases.record()["group_stacks"] == expected
    solver.step(1e-3)
    assert solver.build_phases.record()["group_stacks"] == expected
    assert np.isfinite(np.asarray(solver.X)).all()
    assert curvilinear.gblocks_tally()["matmul"]["applications"] >= \
        expected["matmul"]["applications"]

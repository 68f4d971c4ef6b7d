"""
The fleet product (libraries/matsolvers.py): inside an ensemble's programs
(`member_fleet`) a dense pencil product whose vector alone carries the
member axis is ONE contraction over S for every member where
`fleet_contraction` says so, and the vmapped multiply-reduce elsewhere.

  (a) `DenseOps.matvec`, `matvec_pair` and the stored inverse's solve,
      vmapped over N members in float32, against NumPy float64 `einsum`,
      at N = 1, 7 and 256 under both backends' rules: the form the rule
      picks is the one in the program (a `dot_general` under
      `dense.fleet_matvec` / `dense.fleet_solve`, or none), and outside
      `member_fleet` the program is the single-member one; a fleet's FFTs
      past `meshctx.FLEET_FFT_ROWS` rows go in member chunks;
  (b) the fleet's scan program at RB 64 x 16 with 64 members, compiled
      for a DESCRIBED v5e (no chip: the topology fixture of
      tests/test_chip_compile.py): the five products of a step are
      `convolution` ops (the TPU's dot) under the fleet scopes, and no
      instruction of the scan body copies a stack — the only stack-shaped
      instructions there are bitcasts; the copies into the dot's layout
      are made once a launch, at its head (at the published size, 1,024
      members, the same: compiled once by hand, PERF.md section 4); the
      transforms are `convolution`s under the `fleet_matrix` scopes, and
      the program holds no `fft`.
"""

import contextlib
import pathlib
import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from dedalus_tpu.libraries import matsolvers
from dedalus_tpu.libraries.pencilops import DenseOps

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "chipbench" / "configs"
G, S = 4, 24
SCOPES = ("dedalus/matsolve/dense.fleet_matvec",
          "dedalus/matsolve/dense.fleet_solve")


@pytest.fixture(scope="module")
def stacks():
    rng = np.random.default_rng(42)
    A = rng.standard_normal((G, S, S)) + S * np.eye(S)
    B = rng.standard_normal((G, S, S))
    return A, B


def members_x(N):
    return np.random.default_rng(N).standard_normal((N, G, S))


def fleet_program(fn, N):
    """`fn` vmapped over the members' vectors, traced inside
    `member_fleet` as a fleet program is."""
    def body(X):
        with matsolvers.member_fleet():
            return jax.vmap(fn)(X)
    return jax.jit(body)


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
@pytest.mark.parametrize("N", [1, 7, 256])
def test_fleet_products_against_numpy(stacks, monkeypatch, backend, N):
    A, B = stacks
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    ops = DenseOps(matsolver="BatchedInverse")
    A32, B32 = jnp.asarray(A, jnp.float32), jnp.asarray(B, jnp.float32)
    inv = ops.factor(A32)
    X = members_x(N)
    want = {
        "matvec": np.einsum("gij,ngj->ngi", A, X),
        "pair": np.einsum("gij,ngj->ngi", B, X),
        "solve": np.einsum("gij,ngj->ngi", np.linalg.inv(A), X),
    }
    programs = {
        "matvec": lambda x: ops.matvec(A32, x),
        "pair": lambda x: ops.matvec_pair(A32, B32, x)[1],
        "solve": lambda x: ops.solve(inv, x),
    }
    contraction = matsolvers.fleet_contraction(N, backend)
    assert contraction == (N >= 16)
    for name, fn in programs.items():
        prog = fleet_program(fn, N)
        got = np.asarray(prog(jnp.asarray(X, jnp.float32)), np.float64)
        err = np.abs(got - want[name]).max() / np.abs(want[name]).max()
        assert err < 2e-6, (name, err)
        text = prog.lower(jnp.zeros((N, G, S), jnp.float32)).as_text(
            debug_info=True)
        scope = SCOPES[name == "solve"]
        assert ("dot_general" in text) == contraction, name
        assert (scope in text) == contraction, name


def test_single_member_programs_are_untouched(stacks):
    """Outside `member_fleet` the products are `batched_matvec`: the
    program a single solver traces is the one it traced before, and a
    vmap without the context keeps the multiply-reduce."""
    A, _ = stacks
    ops = DenseOps(matsolver="BatchedInverse")
    A32 = jnp.asarray(A, jnp.float32)
    x = jnp.asarray(members_x(1)[0], jnp.float32)
    inv = ops.factor(A32)
    jaxpr = lambda fn, *a: str(jax.make_jaxpr(fn)(*a))  # noqa: E731
    plain = jaxpr(matsolvers.batched_matvec, A32, x)
    assert jaxpr(ops.matvec, A32, x) == plain
    assert jaxpr(lambda r: ops.solve(inv, r), x) == jaxpr(
        lambda r: ops.solve(inv, r), x)
    assert "dot_general" not in jaxpr(
        jax.vmap(lambda r: ops.solve(inv, r)),
        jnp.zeros((256, G, S), jnp.float32))
    assert "custom_vmap" not in jaxpr(lambda r: ops.solve(inv, r), x)


@pytest.mark.parametrize("members", [32, 37])
def test_fleet_ffts_go_in_member_chunks(monkeypatch, members):
    """Inside a fleet program an FFT whose rows pass `FLEET_FFT_ROWS`
    goes over the member axis in chunks (evenly where a chunk count
    divides the members, a shorter last chunk where none does) and equals
    the whole transform; below the bound, and outside a fleet, it is the
    one FFT call it was."""
    from dedalus_tpu.core import meshctx
    x = jnp.asarray(np.random.default_rng(members).standard_normal(
        (members, 3, 5, 16)), jnp.float32)

    def member(a, fleet=True):
        with (matsolvers.member_fleet() if fleet else contextlib.nullcontext()):
            return meshctx.local_fft(
                lambda v: jnp.fft.irfft(jnp.fft.rfft(v, axis=-1), n=16,
                                        axis=-1), a, None)

    want = np.asarray(jax.vmap(lambda a: member(a, fleet=False))(x))
    def ffts():
        """FFT calls in the fleet's jaxpr, a nested call's counted where
        it is made."""
        def count(jaxpr):
            n = 0
            for eqn in jaxpr.eqns:
                n += eqn.primitive.name == "fft"
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    n += count(sub)
            return n
        return count(jax.make_jaxpr(jax.vmap(member))(x).jaxpr)

    assert ffts() == 2
    monkeypatch.setattr(meshctx, "FLEET_FFT_ROWS", 100)
    # 15 rows a member, at least 5 chunks: 8 of 4 members where 32 divide
    # evenly, 6 of at most 7 where 37 members divide by nothing under 20
    assert ffts() == 2 * (8 if members == 32 else 6)
    got = np.asarray(jax.jit(jax.vmap(member))(x))
    assert np.abs(got - want).max() < 1e-6
    assert "custom_vmap" not in str(jax.make_jaxpr(
        lambda a: member(a, fleet=False))(x[0]))


# ---- the fleet's scan program, compiled for a described v5e ----

MEMBERS, NX, NZ, BLOCK = 64, 64, 16, 10


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")   # else the compiler logs to /tmp
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        mp.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    mp.setattr(jax, "default_backend", lambda: "tpu")
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", cache_was)
    compilation_cache.reset_cache()
    mp.undo()


@pytest.fixture(scope="module")
def fleet_compiled(topo):
    """rb256x64's problem at the rehearsal size as a TPU builds it (the
    stored inverse), a fleet of 64 members over it, and its scan program
    of ten steps compiled for one described chip."""
    from jax.sharding import SingleDeviceSharding
    from chipbench.manifest import load_module
    rb = load_module(CONFIGS / "rb256x64.py")
    base = rb.build(0, size={"Nx": NX, "Nz": NZ})
    assert base.solver.ops.solver_cls is matsolvers.BatchedInverse
    fleet = base.solver.ensemble(MEMBERS, mesh=None)
    fleet.set_fleet_dt(0.005)
    prog, args = fleet.step_program_handle(BLOCK)
    one = SingleDeviceSharding(topo.devices[0])
    abstract = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        np.shape(a), a.dtype, sharding=one), args)
    compiled = prog.lower(*abstract).compile()
    return base.solver.pencil_shape, compiled.as_text()


def _computations(text):
    """{computation name: its instruction lines} of an HLO module."""
    out, name = {}, None
    for ln in text.splitlines():
        if ln and not ln.startswith(" ") and "{" in ln:
            name = "ENTRY" if ln.startswith("ENTRY") else ln.split()[0]
            out[name] = []
        elif name is not None:
            out[name].append(ln)
    return out


def test_fleet_products_are_dots_on_the_mxu(fleet_compiled):
    _, text = fleet_compiled
    dots = [ln for ln in text.splitlines()
            if re.search(r" (convolution|dot)\(", ln)
            and any(s in ln for s in SCOPES)]
    # M X0, two L Xi, two stored-inverse solves: one contraction each
    assert sum(SCOPES[0] in ln for ln in dots) == 3, dots
    assert sum(SCOPES[1] in ln for ln in dots) == 2, dots


def test_fleet_transforms_are_dots_on_the_mxu(fleet_compiled):
    """The fleet's transforms are its bases' matrices (core/transforms.py):
    no `fft` instruction in the program, and every transform contraction
    a `convolution` (the TPU's dot) under a `fleet_matrix` scope, one a
    traced call: per evaluation of the right-hand side (two a RK222 step)
    u, grad b and grad u to the grid in x, one field in z (the fused
    composites take the others' z axis), u.grad u and u.grad b back in x
    and z."""
    _, text = fleet_compiled
    assert not re.search(r" fft\(", text)
    scope = re.compile(r'op_name="[^"]*dedalus/transform/(\w+)\.(\w+)\.(\w+)')
    dots = {}
    for ln in text.splitlines():
        m = scope.search(ln)
        if m and re.search(r" (convolution|dot)\(", ln):
            dots[m.groups()] = dots.get(m.groups(), 0) + 1
    assert dots == {("RealFourier", "fleet_matrix", "bwd"): 6,
                    ("RealFourier", "fleet_matrix", "fwd"): 4,
                    ("Jacobi", "fleet_matrix", "bwd"): 2,
                    ("Jacobi", "fleet_matrix", "fwd"): 4}, dots


def test_scan_body_copies_no_stack(fleet_compiled):
    (G, S), text = fleet_compiled
    stack = re.compile(rf"= f32\[{G},{S},{S}\]\S* (\w[\w-]*)\(")
    comps = _computations(text)
    heads = 0
    for name, lines in comps.items():
        for ln in lines:
            m = stack.search(ln)
            if not m or m.group(1) in ("parameter", "get-tuple-element",
                                       "tuple", "bitcast"):
                continue
            if name == "ENTRY":
                # outside the loop: what a launch does once
                heads += m.group(1) == "copy" and "{2,1,0" in ln
            else:
                # inside the loop only bitcasts may make a stack's shape: a
                # bitcast fusion, or (at this size, where a stack fits the
                # chip's fast memory) the concatenation of a prefetch's
                # slices of the stack as it lies
                assert (m.group(1) == "fusion" and "bitcast_fusion" in ln) \
                    or 'custom_call_target="ConcatBitcast"' in ln, ln
    # M, L and the two stages' inverse, each laid out for the dot once
    assert heads == 4

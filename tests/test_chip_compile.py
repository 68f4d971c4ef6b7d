"""
Ask the chip's compiler before the chip: the IVP main path at its
published size (Rayleigh-Benard 256x64, RK222, f32) compiled for a
DESCRIBED TPU v5e — no device attached, nothing runs, no chip time.

What this guards (and a CPU run cannot): the TPU branch of the step —
`BatchedInverse` solves and fused transforms, chosen by
`jax.default_backend() == "tpu"` at build time — is a different program
from the one every other test compiles. The build here steers that
choice from inside the test (the backend name is monkeypatched for this
module only), then hands the jitted step/factor/scan programs shapes
placed on the described device. A refusal by the TPU compiler (tiling,
memory, an f64 op in an f32 program, a gather in the sharded step)
fails here instead of on the chip.

The topology is described inside a module-scoped fixture — never at
import: only one process at a time may load the TPU library, and every
xdist worker imports every test file. The compile cache is off around
the compiles (an entry written for a described chip cannot be read back
without one). A compile that passes is not a chip run: `chip_smoke.py`
is that.
"""

import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

import dedalus_tpu.public as d3  # noqa: F401  (solver stack ready)
from dedalus_tpu.extras.bench_problems import build_rb_solver
from dedalus_tpu.tools.lint.progcheck import collective_counts

NX, NZ = 256, 64
SCAN_STEPS = 50


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
    # every build and compile of this module sees the TPU branch
    mp.setattr(jax, "default_backend", lambda: "tpu")
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", cache_was)
    compilation_cache.reset_cache()
    mp.undo()


def _programs(solver, place):
    """{name: (lifted program, abstract args)} of one built RK solver:
    every runtime argument — the lifted matrices included — as a
    ShapeDtypeStruct placed by `place(leaf)`. The factor's output shapes
    come from eval_shape, so no inverse is ever computed on the CPU."""
    ts = solver.timestepper
    rd = solver.real_dtype
    tree = lambda t: jax.tree.map(          # noqa: E731
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                       sharding=place(a)), t)
    scalar = jax.ShapeDtypeStruct((), rd, sharding=place(np.zeros(())))
    M, L, X = tree(solver.M_mat), tree(solver.L_mat), tree(solver.X)
    extra = tree(solver.rhs_extra())
    aux = tree(jax.eval_shape(ts._factor, solver.M_mat, solver.L_mat,
                              jnp.asarray(0.01, dtype=rd)))
    return {
        "step": (ts._step, (M, L, X, scalar, scalar, extra, aux)),
        "factor": (ts._factor_uniq, (M, L, scalar)),
        "step_many": (ts._step_n, (M, L, X, scalar, scalar, extra, aux,
                                   SCAN_STEPS)),
    }


@pytest.fixture(scope="module")
def rb_programs(topo):
    """One host build per matsolver (cheap on the CPU: the factor is
    lazy), both under the TPU backend name."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    built = {}
    for matsolver, ops_name in ((None, "DenseOps"), ("banded", "BandedOps")):
        solver, _ = build_rb_solver(NX, NZ, np.float32, matsolver=matsolver)
        assert type(solver.ops).__name__ == ops_name
        built[ops_name] = _programs(solver, lambda a: one_chip)
    return built


def _compile_f32(program, args):
    compiled = program.lower(*args).compile()
    text = compiled.as_text()
    for wide in ("f64[", "c128["):
        assert wide not in text, f"{wide} in an f32 program for the TPU"
    print(compiled.memory_analysis())
    return compiled, text


def _compile_once(programs, name):
    """`_compile_f32` of one entry of a `_programs` dict, kept in the dict:
    the tests that read one program's text share its one compile."""
    done = programs.setdefault("compiled", {})
    if name not in done:
        done[name] = _compile_f32(*programs[name])
    return done[name]


def _fits_a_v5e(compiled):
    """One program's arguments + temporaries fit one v5e chip (16 GB)."""
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes) < 16e9


def _pencil_sharded(solver, mesh):
    """What parallel.distribute_solver records, minus the device_put a
    described device cannot take (the step bodies read the mesh at trace
    time: timesteppers._mesh_pin, field.mesh_transforms); returns where
    each argument then lives."""
    solver.dist.mesh = mesh
    G = solver.pencil_shape[0]

    def place(a):
        lead = np.ndim(a) and np.shape(a)[0] == G
        return NamedSharding(mesh, P("x") if lead else P())
    return place


def _moves_by_all_to_all(text):
    """Pencils move by all-to-all, never by a full-state all-gather: the
    tests/test_collectives.py assertion, asked of the TPU compiler."""
    counts = collective_counts(text)
    assert counts["all-to-all"] >= 2, f"transposes missing: {counts}"
    assert counts["all-gather"] == 0, f"full-state gathers: {counts}"


@pytest.mark.parametrize("program", ["step", "factor", "step_many"])
@pytest.mark.parametrize("ops", ["DenseOps", "BandedOps"])
def test_rb_program_compiles_for_v5e(rb_programs, ops, program):
    compiled, _ = _compile_once(rb_programs[ops], program)
    _fits_a_v5e(compiled)


# ---- the dense stacks are read where they lie (PR 32) ----
#
# The TPU keeps a (G, S, S) operator stack pencil-minor. A product that
# reaches XLA as a dot wants it row-major and layout assignment copies the
# whole stack in front of it on every call: four 142 MB copies in RB
# 256x64's `step` and one inside `step_many`'s scan body were 56% and 27%
# of the device step (PERF_LEDGER.jsonl, PR 30: `unscoped/copy.*`) and
# carried no scope, so no layer metric saw them.
# `matsolvers.batched_matvec` says the product as a multiply and a sum.

_PLUMBING = {"parameter", "get-tuple-element", "tuple", "while", "bitcast"}


def _stack_copies(text, G, S):
    """The instructions of an optimised program that make a second
    (G, S, S) array in memory: a `copy`, or a fusion whose OUTPUT is
    stack-shaped."""
    made = re.compile(rf"= f32\[{G},{S},{S}\]\S* (copy|fusion)\(")
    return [ln for ln in text.splitlines() if made.search(ln)]


def _stack_is_read_in_place(text, G, S):
    """No instruction of the optimised program copies a stack, and every
    instruction that takes a stack as an operand, the fusions' own
    instructions included, carries a `dedalus/matsolve/` scope: what
    reads 142 MB is counted by `solve_ms_per_step`, and `solve_roofline`
    cannot lose its denominator to an op without a name."""
    assert not _stack_copies(text, G, S)
    stacks = set(re.findall(rf"%(\S+) = f32\[{G},{S},{S}\]\S* [\w-]+\(",
                            text))
    assert stacks, "no stack in this program"
    readers = 0
    for ln in text.splitlines():
        _, _, rest = ln.partition(" = ")
        op = re.search(r" ([a-z][\w-]*)\(", " " + rest)
        if not op or op.group(1) in _PLUMBING:
            continue
        if stacks & set(re.findall(r"%([\w.-]+)", rest)):
            readers += 1
            assert "dedalus/matsolve/" in ln, ln
    assert readers, "nothing reads the stacks"


@pytest.mark.parametrize("program", ["step", "step_many"])
def test_dense_step_programs_copy_no_stack(rb_programs, program):
    _, args = rb_programs["DenseOps"][program]
    G, S = args[2].shape
    compiled, text = _compile_once(rb_programs["DenseOps"], program)
    _stack_is_read_in_place(text, G, S)
    # and nothing stack-sized among the temporaries: 351 MB (`step`) and
    # 175 MB (`step_many`) while the copies were there, 2.2 MB without
    assert compiled.memory_analysis().temp_size_in_bytes < 50e6


def test_dense_step_took_the_tpu_branch(rb_programs):
    """The steered build really is the TPU one: the factor is the batched
    inverse (one (G, S, S) matrix per stage), not LU factors + pivots."""
    _, args = rb_programs["DenseOps"]["step"]
    G, S = args[2].shape
    lhs_aux = args[-1]
    for leaf in jax.tree.leaves(lhs_aux):
        assert leaf.shape == (G, S, S) and leaf.dtype == np.float32


def test_sharded_step_compiles_for_four_v5e_chips(topo):
    """The distributed step on a Mesh of the four described chips:
    pencils move by all-to-all, never by a full-state all-gather — the
    tests/test_collectives.py assertion, asked of the TPU compiler."""
    mesh = Mesh(np.array(topo.devices), ("x",))
    solver, _ = build_rb_solver(NX, NZ, np.float32)
    program, args = _programs(solver, _pencil_sharded(solver, mesh))["step"]
    _, text = _compile_f32(program, args)
    _moves_by_all_to_all(text)
    G, S = solver.pencil_shape
    assert not _stack_copies(text, G // 4, S)    # a chip's 32 pencils


# ---- the fit of RB 2048x1024 (chipbench cell rb2048x1024.block10) ----
#
# The record of the fit, in place of benchmarks/memcheck_rb.py and its log
# (a CPU build, which knows nothing of (8, 128) tiles or of the layouts the
# TPU assigns): the programs that hold the 10 GB of bands and factors,
# compiled for the described v5e at the published shapes from
# ShapeDtypeStructs. Only the pencil's STRUCTURE is assembled, from four
# pencils of the same 8206 unknowns (Nx = 8); G = 1024 is a shape.

NORTH_STAR_G = 1024
V5E_HBM_BYTES = 15.75 * 2 ** 30     # what the compiler's own error states


@pytest.fixture(scope="module")
def north_star(topo):
    from dedalus_tpu.libraries import pencilops
    mp = pytest.MonkeyPatch()
    # FUSED_SOLVE = auto asks the device for its memory: a v5e's answer
    mp.setattr(pencilops, "device_memory_bytes", lambda: V5E_HBM_BYTES)
    try:
        solver, _ = build_rb_solver(8, 1024, np.float32, matsolver="banded")
        ops, ts = solver.ops, solver.timestepper
        one_chip = SingleDeviceSharding(topo.devices[0])
        G, S = NORTH_STAR_G, solver.pencil_shape[1]
        sds = lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731,E501
            tuple(shape), dtype, sharding=one_chip)
        grow = lambda a: None if a is None else sds(           # noqa: E731
            (G,) + a.shape[1:], a.dtype)
        M, L = (pencilops.BandedMatrix(grow(A.bands), grow(A.Vt), A.dsel)
                for A in (solver.M_mat, solver.L_mat))
        write, store, C, Gc = ops.incremental_chunk_program(M, L)
        store = jax.tree.map(lambda a: sds(a.shape, a.dtype), store)
        chunk = lambda a: None if a is None else sds(          # noqa: E731
            (Gc,) + a.shape[1:], a.dtype)
        X, scalar = sds((G, S)), sds(())
        aux = ops._aux_from_core(store, {"ab": (scalar, scalar)})
        yield {
            "ops": ops, "C": C, "Gc": Gc, "S": S, "aux": aux,
            "incremental": ops.use_incremental_factor(G, 4),
            "factor_chunk": (write, (store, sds((), jnp.int32),
                                     chunk(M.bands), chunk(L.bands),
                                     chunk(M.Vt), chunk(L.Vt), scalar,
                                     scalar)),
            "mx0": (ts._mx0, (M, X)),
            "L@X": (jax.jit(ops.matvec), (L, X)),
            "pair": (jax.jit(ops.matvec_pair), (M, L, X)),
            "stage_solve": (ts._stage_solve, (2, X, [X, X], [X, X], scalar,
                                              aux, M, L)),
        }
    finally:
        mp.undo()


def test_north_star_shapes_and_choices(north_star):
    """What the defaults resolve to for 1024 pencils on a v5e: q stays
    the structural 32 (257 block rows), the factorization is incremental
    in 8 chunks of 128 groups (the lane width: the packed factors are
    kept group-minor), and FUSED_SOLVE = auto keeps them packed."""
    ops = north_star["ops"]
    assert (north_star["S"], ops.q, ops.NB, ops.n_pad) == (8206, 32, 257,
                                                           8224)
    assert north_star["incremental"]
    assert (north_star["C"], north_star["Gc"]) == (8, 128)
    aux = north_star["aux"]
    assert "interior" in aux and "fsub" not in aux
    perms, panelLU, U12, _, _ = aux["interior"]
    assert panelLU.shape == U12.shape == (8, 256, 2048, 128)
    assert perms.shape == (8, 256, 64, 128)


@pytest.mark.parametrize("program", ["factor_chunk", "mx0", "stage_solve"])
def test_north_star_program_fits_a_v5e(north_star, program):
    """The compiler refuses a program that does not fit ("Used 16.49G of
    15.75G hbm" is how the packed factors in (C, steps, Gc, flat) form
    answered); arguments + temporaries + outputs that are not aliased
    are what it holds at its peak."""
    compiled, _ = _compile_f32(*north_star[program])
    mem = compiled.memory_analysis()
    held = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert held < V5E_HBM_BYTES
    # and the step's programs leave room for the state, the RHS's grid
    # fields and the transform plans beside them: a stage solve holds
    # 9.79 GB of arguments and 1.77 of temporaries since the refinement
    # residual's pair reads its stores in place (4.56 GB of temporaries,
    # 14.4 GB held, 0.85 of the chip, until PR 38)
    if program == "stage_solve":
        assert held < 0.72 * V5E_HBM_BYTES


def test_north_star_sweep_bodies_are_straight_line(north_star):
    """The engagement check of PR 29, asked of the chip's compiler: the
    packed path has ONE pair of scan bodies, and on the v5e they hold no
    gather (the pivots are a select against an iota), no custom call (the
    panel keeps the triangular inverses: no `InvertDiagBlocks`) and no
    copy of a slice of the group-minor store (the products read it as it
    lies). A body is every line of the optimised HLO whose op_name lies
    under `dedalus/matsolve/banded.fwd` or `banded.bwd` and inside a
    `while`: the fused computations' own instructions carry it too."""
    _, text = _compile_f32(*north_star["stage_solve"])
    store_copy = re.compile(r"= \w+\[[\d,]*(2048|64),128\]\S* copy\(")
    # both scans are there, under the scopes the benchmark's readers sum
    for scan in ("banded.fwd/while/body", "banded.bwd/while/body"):
        lines = [ln for ln in text.splitlines() if scan in ln]
        assert lines, scan
        for ln in lines:
            assert " gather(" not in ln, ln
            assert "custom-call(" not in ln, ln
            assert not store_copy.search(ln), ln


# ---- the band product reads its store once (PR 38) ----
#
# `BandedOps._band_mv` was a Python loop over the stored diagonals, each a
# shifted pass over the whole padded X. On the v5e, whose layout keeps a
# band store group-minor with the diagonal index major, that loop did not
# become one fusion: multi-output `slice` fusions copied 50 of the 54
# diagonals out of the store as f32[1024,1,8224] temporaries, 19 shifted
# copies of X were written, and the root fusion read all of it back: 8 GB
# moved for 1.8 GB of bands, 1.6 GB of temporaries a product, 3.4 GB for
# the refinement residual's pair inside every stage solve. As a scan over
# tiles of rows the body's fusions slice the store where it lies.

def _top_level(text):
    """(computation, line) of every instruction of an optimised program
    that is executed on its own: not those of the fused computations,
    which only say what a fusion computes."""
    name = None
    for ln in text.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\{$", ln)
        if head:
            name = head.group(1)
        elif name and "fused" not in name and " = " in ln:
            yield name, ln


def _band_product_reads_the_store_in_place(text, bands, tiled=True):
    """Under `dedalus/matsolve/banded.matvec*`: no instruction but a
    fusion or a `while` takes a band store (an array of the shape
    `bands`), none makes an array of a band slab's shape — (G, k, n), any
    k and n: a copy or a slice of a store —, and where the store is tiled
    the scan's body is where it is read, by fusions that take the store
    ITSELF as an operand."""
    G, D, n = bands
    slab = re.compile(rf"f32\[{G},\d+,\d+\]")
    stores, readers, whiles = set(), [], 0
    for comp, ln in _top_level(text):
        out, _, rest = ln.partition(" = ")
        op = re.search(r" ([a-z][\w-]*)\(", " " + rest)
        if not op:
            continue
        op, made = op.group(1), rest[:rest.index(f" {op.group(1)}(")]
        name = out.split()[-1].lstrip("%")
        if op in ("parameter", "get-tuple-element") and made.startswith(
                f"f32[{G},{D},{n}]"):
            stores.add(name)       # the store itself, handed on
            continue
        scoped = "banded.matvec" in ln
        whiles += scoped and op == "while"
        if not scoped or op in _PLUMBING:
            continue
        assert not slab.search(made), ln[:300]
        if stores & set(re.findall(r"%([\w.\-]+)", rest)):
            assert op == "fusion", ln[:300]
            readers.append(comp)
    assert stores
    if tiled:
        assert whiles == 1, whiles
        assert readers and all("region" in comp for comp in readers), readers
    else:
        # (a store that fits one tile may be prefetched whole into the
        # memory space nearer the core and read from there: the
        # compiler's own `copy-start` / `slice-start`, under no scope)
        assert whiles == 0, whiles


@pytest.mark.parametrize("program,temp_MB", [("mx0", 200), ("L@X", 200),
                                             ("pair", 400)])
def test_north_star_band_product_reads_its_store_once(north_star, program,
                                                      temp_MB):
    """M @ X0, L @ X and the refinement residual's pair at G = 1024: one
    scan of 28 bodies of 296 rows (65 MB of a store each) whatever the
    product, and no temporary of a store's size: 0.2, 0.4 and 34 MB (the
    pair's second result) where the loop over diagonals held 1,618, 1,247
    and 3,403 MB."""
    ops = north_star["ops"]
    compiled, text = _compile_f32(*north_star[program])
    assert ops._band_tiling == (296, 28)
    _band_product_reads_the_store_in_place(text, (NORTH_STAR_G, 54, 8224))
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < temp_MB * 1e6, temp

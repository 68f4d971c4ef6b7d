"""
Ask the chip's compiler about the benchmark's other programs: what the
cells of BENCHMARK.json run that tests/test_chip_compile.py does not
compile — the second configuration (shear flow 512^2, Fourier x Fourier,
G = 65,536 pencils of S = 20), the packed banded store that
`rb2048x1024` runs (`FUSED_SOLVE = off`) at 256x64, the `cfl` cell's two
other programs (the scatter of the state and the snapshots handler's
tasks), and `step_many` and the shear step on the four-chip mesh.

A file of its own because the driver hands a file to one worker
(`--dist loadfile`) and each compile is 30-40 s of a CPU host; the
fixtures, and the rules they follow (the topology is described inside a
fixture, never at import), are tests/test_chip_compile.py's. Two files
mean two workers may describe the topology at once: the driver's command
allows that (`ALLOW_MULTIPLE_LIBTPU_LOAD=1`); without it the worker that
comes second skips its file, it does not fail.
"""

import pathlib
import re
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

import dedalus_tpu.public as d3
from dedalus_tpu.extras.bench_problems import build_rb_solver
from dedalus_tpu.tools.config import config
from dedalus_tpu.tools.lint.progcheck import collective_counts

sys.path.insert(0, str(pathlib.Path(__file__).parent))
from test_chip_compile import (  # noqa: E402,F401
    NX, NZ, _chunk_copies, _compile_f32, _compile_once, _fits_a_v5e,
    _moves_by_all_to_all, _pencil_sharded, _programs, _stack_copies,
    _stack_is_read_in_place, topo)

EXAMPLES = pathlib.Path(__file__).parent.parent / "examples"
PROGRAMS = ["step", "factor", "step_many"]


def build_shear(Nx=512, Nz=512, dtype=np.float32):
    """examples/shear_flow.py's own text from `# Parameters` to its
    `# Solver` block, at the benchmark's size and dtype (the driver's
    progression size; the example publishes 128 x 256 in float64)."""
    text = (EXAMPLES / "shear_flow.py").read_text()
    params, rest = text.split("# Bases\n")
    ns = {"np": np, "d3": d3}
    exec(params.split("# Parameters\n")[1], ns)
    ns.update(Nx=Nx, Nz=Nz, dtype=dtype)
    exec(rest.split("# CFL\n")[0], ns)
    return ns["solver"]


@pytest.fixture(scope="module")
def shear_programs(topo):
    solver = build_shear()
    assert type(solver.ops).__name__ == "DenseOps"
    assert solver.pencil_shape == (65536, 20)
    one_chip = SingleDeviceSharding(topo.devices[0])
    return _programs(solver, lambda a: one_chip)


@pytest.mark.parametrize("program", PROGRAMS)
def test_shear_program_compiles_for_v5e(shear_programs, program):
    compiled, _ = _compile_once(shear_programs, program)
    _fits_a_v5e(compiled)


@pytest.mark.parametrize("program", ["step", "step_many"])
def test_shear_step_programs_copy_no_stack(shear_programs, program):
    """The control of PR 32: at 65,536 pencils of 20 the compiler had
    already made every product a multiply-reduce over the resident
    `f32[65536,20,20]` stacks (no copy at the parent either); this pins
    it. `factor` is not held to it: the LU's own layouts, once per dt."""
    _, text = _compile_once(shear_programs, program)
    _stack_is_read_in_place(text, *shear_programs[program][1][2].shape)


@pytest.fixture(scope="module")
def packed_programs(topo):
    """RB 256x64 through BandedOps with the factors kept packed, the
    store `rb2048x1024.block10` runs (there `FUSED_SOLVE = auto` yields
    to the device's memory; here the option says so)."""
    had = config["fusion"].get("FUSED_SOLVE")
    config["fusion"]["FUSED_SOLVE"] = "off"
    try:
        solver, _ = build_rb_solver(NX, NZ, np.float32, matsolver="banded")
    finally:
        config["fusion"]["FUSED_SOLVE"] = had
    assert type(solver.ops).__name__ == "BandedOps"
    assert not solver.ops._fused_solve
    one_chip = SingleDeviceSharding(topo.devices[0])
    programs = _programs(solver, lambda a: one_chip)
    aux = programs["step"][1][-1]
    aux0 = aux[0] if isinstance(aux, list) else aux
    assert "interior" in aux0 and "fsub" not in aux0
    return programs


@pytest.mark.parametrize("program", PROGRAMS)
def test_packed_banded_program_compiles_for_v5e(packed_programs, program):
    compiled, _ = _compile_f32(*packed_programs[program])
    _fits_a_v5e(compiled)


@pytest.fixture(scope="module")
def cfl_solver(topo, tmp_path_factory):
    """RB 256x64 as `rb256x64.cfl` runs it: dense pencils, and the
    example's snapshots handler (examples/rayleigh_benard.py:69-72)."""
    solver, b = build_rb_solver(NX, NZ, np.float32)
    u = solver.problem.namespace["u"]
    snapshots = solver.evaluator.add_file_handler(
        str(tmp_path_factory.mktemp("cfl") / "snapshots"), sim_dt=0.25,
        max_writes=50)
    snapshots.add_task(b, name="buoyancy")
    snapshots.add_task(-d3.div(d3.skew(u)), name="vorticity")
    return solver, snapshots


def test_scatter_program_compiles_for_v5e(topo, cfl_solver):
    """`solver._scatter_program` (PR 27): the state to its eight fields in
    one launch, with no gather of the pencil axis in it."""
    solver, _ = cfl_solver
    one_chip = SingleDeviceSharding(topo.devices[0])
    X = jax.ShapeDtypeStruct(solver.X.shape, solver.X.dtype,
                             sharding=one_chip)
    compiled, text = _compile_f32(
        solver._scatter_program(solver.variables), (X,))
    _fits_a_v5e(compiled)
    assert " gather(" not in text


def test_snapshots_task_program_compiles_for_v5e(topo, cfl_solver):
    """The snapshots handler's task program: both tasks from the fields'
    coefficients to their grids in one launch."""
    _, snapshots = cfl_solver
    one_chip = SingleDeviceSharding(topo.devices[0])
    runner = snapshots._compile_tasks()
    arrays = [jax.ShapeDtypeStruct(np.shape(f.coeff_data()),
                                   f.coeff_data().dtype, sharding=one_chip)
              for f in runner.fields]
    compiled, _ = _compile_f32(runner.fn, (arrays,))
    _fits_a_v5e(compiled)
    out = jax.tree.map(lambda a: a.shape, compiled.out_info)
    assert out == {"buoyancy": (NX, NZ), "vorticity": (NX, NZ)}


def test_sharded_step_many_compiles_for_four_v5e_chips(topo):
    """The scan block of `rb256x64.block` on a Mesh of the four described
    chips: inside the scan too, pencils move by all-to-all and never by
    a full-state all-gather."""
    mesh = Mesh(np.array(topo.devices), ("x",))
    solver, _ = build_rb_solver(NX, NZ, np.float32)
    program, args = _programs(solver, _pencil_sharded(solver, mesh))[
        "step_many"]
    _, text = _compile_f32(program, args)
    _moves_by_all_to_all(text)
    G, S = solver.pencil_shape
    assert not _stack_copies(text, G // 4, S)


def test_sharded_shear_step_compiles_for_four_v5e_chips(topo):
    """The shear step on the same mesh: Fourier x Fourier, 16,384 pencils
    a chip."""
    mesh = Mesh(np.array(topo.devices), ("x",))
    solver = build_shear()
    program, args = _programs(solver, _pencil_sharded(solver, mesh))["step"]
    _, text = _compile_f32(program, args)
    _moves_by_all_to_all(text)
    G, S = solver.pencil_shape
    assert not _stack_copies(text, G // 4, S)


# ---- rb3d128x128x128 on four chips (PR 40) ----
#
# The cell `rb3d128x128x128.block10`: 3-D RB through BandedOps with the
# pencil groups sharded over the four chips of one host. The step program
# needs the transform layout of a real build, so it is compiled at 32^2 x
# 128 (the published pencil, 64 groups a chip); the factor and a stage
# solve, which need only shapes, at the cell's 4,096 groups.

RB3D_CELL_G = 4096


@pytest.fixture(scope="module")
def rb3d(topo):
    import json
    from chipbench.manifest import load_module
    from dedalus_tpu.libraries import pencilops
    from test_chip_compile import V5E_HBM_BYTES
    root = pathlib.Path(__file__).resolve().parents[1]
    rb = load_module(root / "chipbench" / "configs" / "rb3d128x128x128.py")
    mp = pytest.MonkeyPatch()
    mp.setattr(pencilops, "device_memory_bytes", lambda: V5E_HBM_BYTES)
    mesh = Mesh(np.array(topo.devices), ("x",))
    try:
        size = {"Nx": 32, "Ny": 32, "Nz": 128, "matsolver": "banded"}
        solver = rb.build(0, size=size).solver
        ops, ts = solver.ops, solver.timestepper
        # what parallel.distribute_solver records (a described chip takes
        # no device_put)
        solver.dist.mesh = mesh
        ops.shard_over(mesh)

        def place(tree, G=None):
            def one(a, axis):
                shape = np.shape(a)
                if G is not None and axis >= 0:
                    shape = shape[:axis] + (G,) + shape[axis + 1:]
                spec = [None] * len(shape)
                if axis >= 0:
                    spec[axis] = "x"
                return jax.ShapeDtypeStruct(
                    shape, a.dtype, sharding=NamedSharding(mesh, P(*spec)))
            return jax.tree.map(one, tree, ops.group_axes(tree))

        rd = solver.real_dtype
        scalar = place(jnp.asarray(0.0, rd))
        M, L, X = (place(a) for a in (solver.M_mat, solver.L_mat, solver.X))
        aux = [place(a) for a in jax.eval_shape(ts._factor_uniq, M, L,
                                                scalar)]
        G = solver.pencil_shape[0]
        extra = jax.tree.map(
            lambda a: place(a) if np.ndim(a) and np.shape(a)[0] == G
            else jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                      sharding=NamedSharding(mesh, P())),
            solver.rhs_extra())
        # the cell's shapes: 4,096 groups
        Mc, Lc, Xc = (place(a, RB3D_CELL_G)
                      for a in (solver.M_mat, solver.L_mat, solver.X))
        auxc = [place(a) for a in jax.eval_shape(ts._factor_uniq, Mc, Lc,
                                                 scalar)]
        yield {
            "spec": dict(rb.SPEC, sizes=dict(rb.SPEC["sizes"], **size)),
            "ops": ops, "ts": ts, "aux": aux, "auxc": auxc,
            "step_many": (ts._step_n, (M, L, X, scalar, scalar, extra,
                                       [aux[j] for j in ts.stage_slot],
                                       10)),
            "factor": (ts._factor_uniq, (Mc, Lc, scalar)),
            "stage_solve": (ts._stage_solve, (2, Xc, [Xc, Xc], [Xc, Xc],
                                              scalar, auxc[0], Mc, Lc)),
        }
    finally:
        mp.undo()


def test_rb3d_step_many_on_four_chips(rb3d):
    """`step_many(10)` on the four described chips: no all-gather; every
    all_to_all under `dedalus/transpose/`, as many as
    chipbench/costs_transpose.py counts (5 exchanges a stage, four chunks
    each on an accelerator); no copy of the packed store under the banded
    solve: nothing that executes on its own (a copy, a fusion or any other
    instruction) makes an array of the store's or the pivots' shape."""
    from chipbench import costs_transpose
    ts = rb3d["ts"]
    _, text = _compile_f32(*rb3d["step_many"])
    counts = collective_counts(text)
    assert counts["all-gather"] == 0, counts
    a2a = [ln for ln in text.splitlines()
           if " all-to-all(" in ln or " all-to-all-start(" in ln]
    assert all("dedalus/transpose/" in ln for ln in a2a)
    want = costs_transpose.rk_step(rb3d["spec"], 4, ts.stages, 4)
    assert len(a2a) == counts["all-to-all"] == want["ops"] == 40
    # a chip's packed store, one chunk of 64 groups: (steps, 2q^2, 64).
    # (The entry computation re-lays-out panelLU of both stages' aux once
    # a call, unscoped, {1,2,0} -> {2,1,0}: the program's arguments at
    # this size, outside the solve.)
    copies = _chunk_copies(text, "f32[32,12800,64]", "s32[32,160,64]")
    copies = [ln for ln in copies if "banded.solve" in ln]
    assert not copies, [ln[:300] for ln in copies]


@pytest.mark.parametrize("program", ["factor", "stage_solve"])
def test_rb3d_cell_programs_fit_a_chip(rb3d, program):
    """At the cell's 4,096 groups each chip factors and solves its 1,024
    (8 chunks of 128 lanes) within its 16 GB: arguments, temporaries and
    outputs that are not aliased, per chip."""
    from test_chip_compile import V5E_HBM_BYTES
    ops = rb3d["ops"]
    compiled, text = _compile_f32(*rb3d[program])
    mem = compiled.memory_analysis()
    held = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert held < V5E_HBM_BYTES
    assert collective_counts(text)["all-gather"] == 0
    assert (ops._g_chunks, ops._chunk_width) == (8, 128)
    panel = rb3d["auxc"][0]["interior"][1]
    assert panel.shape == (8, 32, 12800, 512)
    if program == "stage_solve":
        # the chunk loop writes out no chunk of a chip's packed stores
        # (f32[32,12800,128], 210 MB, and the pivots' s32[32,160,128]):
        # its sweeps read block (c, i) where it lies. 958 MB of
        # temporaries a chip; 1,411 while four chunk buffers were made
        copies = _chunk_copies(text, "f32[32,12800,128]", "s32[32,160,128]")
        assert not copies, [ln[:300] for ln in copies]
        assert mem.temp_size_in_bytes <= 1000e6

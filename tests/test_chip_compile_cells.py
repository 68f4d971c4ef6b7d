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
import sys

import numpy as np
import pytest
import jax
from jax.sharding import Mesh, SingleDeviceSharding

import dedalus_tpu.public as d3
from dedalus_tpu.extras.bench_problems import build_rb_solver
from dedalus_tpu.tools.config import config

sys.path.insert(0, str(pathlib.Path(__file__).parent))
from test_chip_compile import (  # noqa: E402,F401
    NX, NZ, _compile_f32, _compile_once, _fits_a_v5e, _moves_by_all_to_all,
    _pencil_sharded, _programs, _stack_copies, _stack_is_read_in_place, topo)

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

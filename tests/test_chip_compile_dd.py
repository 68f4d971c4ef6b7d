"""
Ask the chip's compiler about the float64 route (chipbench cell
rb256x64-f64.block10): the double-double step of Rayleigh-Benard that
`InitialValueSolver` wires for float64 pencils on a TPU, compiled for a
DESCRIBED v5e at the configuration's rehearsal size 64 x 16 — the TPU
branch of the int8 plane `dot_general`s, of the float64 converts and
arithmetic the compiler has to emulate, and of the float32 solver the
runner takes for its sweeps' inner solves where a TPU build's own class is
`BatchedInverseRefined` (the plain `BatchedInverse`: one read of the
stored inverse a solve, PR 36). What a CPU run
cannot see and a chip run pays minutes for: a refusal by the TPU compiler
(an f64 op its rewriter does not know, a 64-bit bitcast, a tiling) fails
here. The program's size in operations, and so its compile time, hardly
depends on the resolution: the published size is the builder's own check
before the chip (87 s the step, 96 s the scan block; PERF.md, PR 35), not
a test. Only the single step is compiled here: the scan block's body is
the same step.

A file of its own because the driver hands a file to one worker
(`--dist loadfile`); the fixtures and the rules they follow are
tests/test_chip_compile.py's.
"""

import pathlib
import re
import sys

import numpy as np
import pytest
import jax
from jax.sharding import SingleDeviceSharding

sys.path.insert(0, str(pathlib.Path(__file__).parent))
from test_chip_compile import (  # noqa: E402,F401
    V5E_HBM_BYTES, _stack_copies, topo)

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def programs(topo):
    """{name: (compiled, text, float32 stack reads traced)} of the dd
    route's factor and single-step programs; the build sees the TPU's
    backend name (the `topo` fixture patches it for the module)."""
    from chipbench.manifest import load_module
    from dedalus_tpu.core.ddstep import _dd_scalar
    cfg = load_module(ROOT / "chipbench" / "configs" / "rb256x64-f64.py")
    dep = cfg.build(0, size=dict(cfg.SPEC["rehearsal"]))
    dd = dep.solver._dd
    assert dd is not None
    assert dep.solver.build_phases.record()["f64_route"] == "dd"
    one_chip = SingleDeviceSharding(topo.devices[0])
    tree = lambda t: jax.tree.map(          # noqa: E731
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                       sharding=one_chip), t)
    dt = tree(_dd_scalar(dep.fixed_dt))
    # the factor's output shapes from eval_shape: nothing is inverted here
    lhs = tree(jax.eval_shape(dd._rk_factor.fn, [dt]))[0]
    args = {"factor": (dd._rk_factor, ([dt],)),
            "step": (dd._rk_step, (tree(dd.X), tree(dd._t_dd()), dt,
                                   [lhs, lhs], tree(dd._extras_dd())))}
    done = {}
    for name, (program, abstract) in args.items():
        reads_before = dd.f32_reads_traced
        compiled = program.lower(*abstract).compile()
        done[name] = (compiled, compiled.as_text(),
                      dd.f32_reads_traced - reads_before)
    return dep, done


@pytest.mark.parametrize("program", ["factor", "step"])
def test_dd_program_compiles_for_v5e(programs, program):
    compiled, text, _ = programs[1][program]
    mem = compiled.memory_analysis()
    print(mem)
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes) < V5E_HBM_BYTES
    # the chip has no float64 unit: the compiler has rewritten every one
    assert "f64[" not in text


def test_dd_step_took_the_tpu_branch(programs):
    dep, done = programs
    text = done["step"][1]
    G, S = dep.solver.pencil_shape
    # the pencil matrices arrive as sets of 8 int8 planes and their
    # products leave as int32 (the compiler may say them as a dot, a
    # convolution or a multiply and a sum: its choice, not pinned here)
    assert f"s8[8,{G},{S},{S}]" in text
    assert re.search(rf"s32\[{G},{S}(,1)?\]", text)
    # the solver's own class is the one a TPU takes for 64-bit variables;
    # the runner's inner solves are the plain stored inverse, one read of
    # it each: 2 stages x (a first solve + 2 corrections)
    from dedalus_tpu.libraries.matsolvers import BatchedInverseRefined
    assert issubclass(dep.solver.ops.solver_cls, BatchedInverseRefined)
    assert dep.solver._dd.counters()["f32_solver"] == "BatchedInverse"
    assert "dedalus/matsolve/BatchedInverse.solve" in text
    assert "BatchedInverseRefined" not in text
    assert done["step"][2] == 6
    # read as it lies: no float32 (G, S, S) stack is copied, none is the
    # operand of a dot (matsolvers.batched_matvec: a multiply and a sum)
    assert not _stack_copies(text, G, S)
    stacks = set(re.findall(rf"%(\S+) = f32\[{G},{S},{S}\]\S* [\w-]+\(",
                            text))
    assert stacks, "no float32 stack in this program"
    dots = [ln for ln in text.splitlines()
            if re.search(r" (dot|convolution)\(", ln)
            and stacks & set(re.findall(r"%([\w.-]+)",
                                        ln.partition(" = ")[2]))]
    assert not dots, dots[:3]
    for scope in ("dedalus/matsolve/dd.matvec", "dedalus/matsolve/dd.residual",
                  "dedalus/evaluator/dd.rhs", "dedalus/transform/Jacobi.dd."):
        assert scope in text

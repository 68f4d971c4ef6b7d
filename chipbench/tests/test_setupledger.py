"""The readers of the program's set-up ledger (PR 37): their arithmetic on
a hand-made ledger, the refusals (a tree without a ledger, a record from
before it), the entries of BENCHMARK.json against their files, traced CPU
rehearsals that print every new name in the cells that list it, and a
profiler started BEFORE a build, whose host plane then holds the
`dedalus/build/<name>` and `dedalus/compile/<label>` rows, each once."""

import json
import os
import pathlib
import subprocess
import sys

import pytest

from chipbench import setupledger, xplane
from chipbench.manifest import Manifest
from dedalus_tpu.tools import metrics, retrace

ROOT = pathlib.Path(__file__).resolve().parents[2]
CELLS = ["rb256x64.block", "rb256x64.cfl", "shear512.block",
         "rb2048x1024.block10", "sw_ell255.block", "rb256x64-f64.block10"]
# metric -> (unit, source, layer, cells): the issue's table
NEW = {
    "compile_s": ("s", "program_span", "step program", CELLS),
    "trace_lower_s": ("s", "program_span", "step program", CELLS),
    "cache_load_s": ("s", "program_span", "step program", CELLS),
    "xla_cache_misses": ("count", "program_counter", "step program", CELLS),
    "eager_compile_s": ("s", "program_span", "entry", CELLS),
    "init_unnamed_s": ("s", "program_span", "entry", CELLS),
    "script_s": ("s", "program_span", "entry", CELLS),
    "upload_s": ("s", "program_span", "host assembly", CELLS),
    "basis_stacks_s": ("s", "program_span", "transforms", [CELLS[4]]),
    "dd_prepare_s": ("s", "program_span", "pencil solve", [CELLS[5]]),
}


def row(label, first, discover, trace, lower, backend, retrieval, cache):
    return {"label": label, "t0": 0.0, "first_call_sec": first,
            "discover_sec": discover, "trace_sec": trace, "lower_sec": lower,
            "backend_sec": backend, "retrieval_sec": retrieval,
            "cache": cache, "owner": "InitialValueSolver#2"}


@pytest.fixture
def ledger(monkeypatch):
    """A hand-made ledger in place of the program's: three rows, two eager
    names, an LBVP's and an IVP's clocks and the process-level one."""
    retrace.sentinel.reset()
    for r in (row("step_body", 10.0, 2.0, 3.0, 1.0, 3.5, 3.0, "hit"),
              row("_step_n", 20.0, 4.0, 5.0, 2.0, 8.0, 0.0, "miss"),
              row("health/probe", 1.0, 0.0, 0.2, 0.1, 0.6, 0.0, "off")):
        retrace.sentinel._book(r)
    retrace.sentinel.eager.update({"add": [3, 0.5], "convert": [1, 0.25]})
    lbvp, ivp = metrics.BuildPhases("Lbvp"), metrics.BuildPhases("Ivp")
    process = metrics.BuildPhases()
    lbvp.init_sec, ivp.init_sec = 2.0, 7.0
    lbvp.add("basis_stacks", 4.0)
    ivp.add("basis_stacks", 1.0)
    process.add("basis_stacks", 0.5)
    ivp.add("upload", 1.5)
    ivp.add("dd_prepare", 3.0)
    monkeypatch.setattr(metrics, "_all_phases", [lbvp, ivp])
    monkeypatch.setattr(metrics, "_process_phases", process)
    yield {"build_s": 12.0, "build_phases": dict(ivp.record(),
                                                 unnamed_sec=0.75)}
    retrace.sentinel.reset()


def test_readers_on_a_known_ledger(ledger):
    want = {"compile_s": 31.0, "trace_lower_s": 17.3, "cache_load_s": 3.0,
            "xla_cache_misses": 1.0, "eager_compile_s": 0.75,
            "init_unnamed_s": 0.75, "script_s": 3.0, "upload_s": 1.5,
            "basis_stacks_s": 5.5, "dd_prepare_s": 3.0}
    assert set(want) == set(NEW)
    for name, value in want.items():
        got = Manifest().layer_reader(name)(ledger)
        assert isinstance(got, float) and got == pytest.approx(value), name


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_says_nothing_where_there_is_no_ledger(monkeypatch, name):
    """The parent's tree: a sentinel without rows, no list of clocks, a
    record with the four old keys."""
    monkeypatch.setattr(setupledger, "totals", lambda: None)
    monkeypatch.delattr(metrics, "all_phases")
    old = {"host_assembly_sec": 1.0, "structure_sec": 0.0,
           "factor_sec": 2.0, "compile_sec": 3.0, "assembly_cache": "hit"}
    read = Manifest().layer_reader(name)
    assert read({"build_s": 9.0, "build_phases": old}) is None
    assert read({}) is None


@pytest.mark.parametrize("name", sorted(NEW))
def test_entry_has_its_file_unit_and_cells(name):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    unit, source, layer, cells = NEW[name]
    assert (ROOT / "chipbench" / "layers" / f"{name}.py").is_file()
    assert entry == {"name": name, "unit": unit, "better": "lower",
                     "source": source, "layer": layer, "moves": "setup_s",
                     "workloads": cells}
    assert [m["name"] for m in bench["per_layer"][-10:]] == list(NEW)
    # a layer the benchmark already names, letter for letter
    assert layer in {m["layer"] for m in bench["per_layer"][:-10]}


def traced_rehearsal(cell):
    args = [sys.executable, "-m", "chipbench.run", "--workload", cell,
            "--seed", "3000000037", "--seconds", "2", "--trace", "1",
            "--rehearse-cpu"]
    done = subprocess.run(args, cwd=ROOT, capture_output=True, text=True,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"),
                          timeout=900)
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-3000:]
    lines = done.stdout.strip().splitlines()
    checks = next(json.loads(l.split("chipbench checks: ", 1)[1])
                  for l in lines if l.startswith("chipbench checks: "))
    return json.loads(lines[-1])["metrics"], checks


@pytest.mark.parametrize("cell", [CELLS[0], CELLS[4], CELLS[5]])
def test_traced_rehearsal_prints_the_cells_new_names(cell):
    metrics_out, checks = traced_rehearsal(cell)
    mine = {name for name, (_, _, _, cells) in NEW.items() if cell in cells}
    assert set(NEW) & set(metrics_out) == mine
    value = {name: metrics_out[name]["value"] for name in mine}
    assert value["compile_s"] >= value["trace_lower_s"] \
        + value["cache_load_s"] > 0
    assert value["script_s"] <= metrics_out["build_s"]["value"]
    phases = checks["build_phases"]
    assert value["init_unnamed_s"] == phases["unnamed_sec"]
    assert phases["init_sec"] >= phases["unnamed_sec"] >= 0
    assert len(phases["programs"]["rows"]) <= 12
    # the old metrics are still beside them
    assert {"build_s", "host_assembly_s", "ref_rel_l2"} <= set(metrics_out)
    if cell == CELLS[4]:
        assert value["basis_stacks_s"] > 0
        assert {"factor_s", "structure_s"} <= set(metrics_out)


def test_a_profiler_started_before_a_build_sees_it(tmp_path):
    """`dedalus/build/<name>` once per entry (no second annotation inside
    it) and `dedalus/compile/<label>` rows, on the profiler's host plane."""
    import jax
    import numpy as np
    from dedalus_tpu.extras.bench_problems import build_rb_solver
    from chipbench import tracered
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        solver, _ = build_rb_solver(16, 20, np.float32)
        solver.step(1e-3)
        jax.block_until_ready(solver.X)
    finally:
        jax.profiler.stop_trace()
    path = tracered.newest_xplane(str(tmp_path))
    rows = [(start, end, name)
            for plane in xplane.read(
                path, lambda plane, line: plane.startswith("/host:"))
            for line in plane["lines"]
            for start, end, name, _ in line["events"]
            if name.startswith("dedalus/")]
    names = [name for _, _, name in rows]
    assert "dedalus/step" in names
    compiles = [r for r in rows if r[2].startswith("dedalus/compile/")]
    assert any(n.endswith("step_body") for _, _, n in compiles)
    builds = [r for r in rows if r[2].startswith("dedalus/build/")]
    assert {"dedalus/build/factor", "dedalus/build/upload"} \
        <= {n for _, _, n in builds}
    # no build row sits inside another of the same name
    for s, e, n in builds:
        assert not any(n2 == n and (s2, e2) != (s, e) and s2 <= s and e <= e2
                       for s2, e2, n2 in builds), n
    # the step program's compile row lies inside the step row
    s0, e0 = next((s, e) for s, e, n in rows if n == "dedalus/step")
    s1, e1, _ = next(r for r in compiles if r[2].endswith("step_body"))
    assert s0 <= s1 and e1 <= e0

"""
Step-loop spans (tools/tracing.span on the host side of solver.step):
every span of the vocabulary lands in the ring under its stated parent,
the gate (`tracing.live()`: the [tracing] switch OR a capturing
jax.profiler), the refactorization count behind `step/factor`, and the
eager-fallback mode behind `handler/eval`.

That the step program's HLO is identical with spans live is NOT
re-checked here: the progcheck `traced_step` census lowers a step that
ran under `tracing.enable()` (so every span of `solver.step` was live)
and DTP107 compares it byte for byte (tests/test_progcheck.py).
"""

import logging

import numpy as np
import pytest

import dedalus_tpu.public as d3
from dedalus_tpu.extras.bench_problems import build_rb_solver
from dedalus_tpu.tools import metrics as metrics_mod
from dedalus_tpu.tools import tracing

DT = 1e-3


@pytest.fixture
def ring():
    """The span ring, cleared, with the [tracing] switch as found."""
    was_on = tracing.enabled()
    tracing.disable()
    tracing.recorder().clear()
    yield tracing.recorder()
    tracing.disable()
    tracing.recorder().clear()
    if was_on:
        tracing.enable()


def rb_loop(tmp_path):
    """RB 32x16 f32 with the example's loop pieces at short cadences: a
    file handler, a flow property (dictionary handler), CFL, and the
    metrics sampler and health probe every 4 iterations."""
    solver, b = build_rb_solver(32, 16, np.float32)
    solver.warmup_iterations = 2
    solver.metrics = metrics_mod.resolve(True, sink=None, cadence=4)
    solver.health.cadence = 4
    u = next(v for v in solver.problem.variables if v.name == "u")
    snapshots = solver.evaluator.add_file_handler(
        str(tmp_path / "snapshots"), iter=3)
    snapshots.add_task(b, name="buoyancy")
    flow = d3.GlobalFlowProperty(solver, cadence=2)
    flow.add_property(np.sqrt(u @ u), name="Re")
    cfl = d3.CFL(solver, initial_dt=DT, cadence=2, safety=0.5,
                 threshold=0.05, max_dt=1e-2)
    cfl.add_velocity(u)
    return solver, cfl


def parents(spans):
    """{span name: set of parent span names (None for a root)}."""
    by_id = {s.span_id: s for s in spans}
    out = {}
    for s in spans:
        parent = by_id[s.parent_id].name if s.parent_id else None
        out.setdefault(s.name, set()).add(parent)
    return out


@pytest.fixture(scope="module")
def loop_spans(tmp_path_factory):
    """One short CFL loop and one block under `tracing.enable()`."""
    solver, cfl = rb_loop(tmp_path_factory.mktemp("loop"))
    was_on = tracing.enabled()
    tracing.enable()
    tracing.recorder().clear()
    try:
        for _ in range(12):
            solver.step(cfl.compute_timestep())
        solver.step_many(4, DT)
        return tracing.recorder().spans()
    finally:
        tracing.disable()
        tracing.recorder().clear()
        if was_on:
            tracing.enable()


@pytest.mark.parametrize("name,parent", [
    ("step", None),
    ("step_many", None),
    ("step/factor", "step"),
    ("step/handlers", "step"),
    ("handler/eval", "step/handlers"),
    ("handler/pull", "step/handlers"),
    ("handler/write", "step/handlers"),
    ("state/scatter", "handler/eval"),
    ("cfl", None),
    ("metrics/drain", "step"),
    ("metrics/sample", "step"),
    ("health/check", "step"),
])
def test_span_under_its_parent(loop_spans, name, parent):
    found = parents(loop_spans)
    assert name in found, f"no {name!r} span; the ring holds {sorted(found)}"
    assert parent in found[name]


def test_span_attrs(loop_spans):
    by_name = {}
    for s in loop_spans:
        by_name.setdefault(s.name, []).append(s)
    assert [s.attrs["iteration"] for s in by_name["step"]] == list(range(12))
    assert by_name["step_many"][0].attrs == {"iteration": 12, "n": 4}
    assert all(s.attrs["dt"] > 0 for s in by_name["step/factor"])
    assert all(s.attrs["n_due"] >= 1 for s in by_name["step/handlers"])
    labels = {s.attrs["handler"] for s in by_name["handler/eval"]}
    assert labels == {"FileHandler:buoyancy", "DictionaryHandler:Re"}
    assert {s.attrs["mode"] for s in by_name["handler/eval"]} == {"compiled"}
    assert all(s.attrs["bytes"] > 0 for s in by_name["handler/pull"])
    assert {s.attrs["handler"] for s in by_name["handler/write"]} \
        == {"FileHandler:buoyancy"}
    # an iteration with nothing due opens one span: its own
    children = {s.parent_id for s in loop_spans}
    assert any(s.span_id not in children for s in by_name["step"])
    # an orphan step is its own trace, and its children share it
    assert len({s.trace_id for s in by_name["step"]}) == 12
    by_id = {s.span_id: s for s in loop_spans}
    assert all(s.trace_id == by_id[s.parent_id].trace_id
               for s in loop_spans if s.parent_id)


def test_off_path_is_the_shared_noop(ring, tmp_path):
    solver, cfl = rb_loop(tmp_path)
    assert not tracing.live()
    assert tracing.span("step", {"iteration": 0}) is tracing.span("cfl")
    for _ in range(5):
        solver.step(cfl.compute_timestep())
    assert ring.spans() == []


def test_live_while_a_profiler_captures(ring, tmp_path):
    import jax
    solver, cfl = rb_loop(tmp_path)
    for _ in range(3):
        solver.step(cfl.compute_timestep())
    jax.profiler.start_trace(str(tmp_path / "profile"))
    try:
        assert tracing.live() and not tracing.enabled()
        for _ in range(4):
            solver.step(cfl.compute_timestep())
    finally:
        jax.profiler.stop_trace()
    assert not tracing.live()
    captured = ring.spans()
    assert [s.name for s in captured].count("step") == 4
    assert {"cfl", "step/handlers", "handler/pull"} <= set(parents(captured))
    for _ in range(3):
        solver.step(cfl.compute_timestep())
    assert len(ring.spans()) == len(captured)


def build_diffusion(scheme):
    coords = d3.CartesianCoordinates("x")
    dist = d3.Distributor(coords, dtype=np.float64)
    xb = d3.RealFourier(coords["x"], size=16, bounds=(0, 2 * np.pi))
    s = dist.Field(name="s", bases=xb)
    problem = d3.IVP([s], namespace={"lap": d3.lap, "s": s})
    problem.add_equation("dt(s) - lap(s) = 0")
    solver = problem.build_solver(scheme)
    s["g"] = np.sin(dist.local_grid(xb))
    return solver, dist, xb, s


@pytest.mark.parametrize("scheme,advance,count,parent", [
    # one-stage key: a refactorization per dt MOVE, not per step
    (d3.RK222, [DT, DT, 2 * DT, 2 * DT, DT, DT], 3, "step"),
    # the multistep key also moves with the order ramp (SBDF1, then SBDF2)
    (d3.SBDF2, [DT] * 6, 2, "step"),
    # ... which a block runs as single steps before it scans
    (d3.SBDF2, 6, 2, "step_many"),
])
def test_factor_span_per_refactorization(ring, scheme, advance, count,
                                         parent):
    solver = build_diffusion(scheme)[0]
    tracing.enable()
    if isinstance(advance, int):
        solver.step_many(advance, DT)
    else:
        for dt in advance:
            solver.step(dt)
    spans = ring.spans()
    assert [s.name for s in spans].count("step/factor") == count
    assert parents(spans)["step/factor"] == {parent}


def test_eager_fallback_is_named_and_warns_once(ring, caplog):
    """A task the tracer cannot take (host NumPy on its operand) falls
    back permanently: `handler/eval` says `eager`, the log says so once."""
    solver, dist, xb, s = build_diffusion(d3.RK222)
    host_only = d3.GeneralFunction(
        dist, s.domain, (), s.dtype, "g",
        lambda data: np.asarray(data) ** 2, args=(s,), pure=True)
    handler = solver.evaluator.add_dictionary_handler(iter=1)
    handler.add_task(host_only, name="squared")
    tracing.enable()
    with caplog.at_level(logging.WARNING, logger="dedalus_tpu"):
        for _ in range(3):
            solver.step(DT)
    evals = [s for s in ring.spans() if s.name == "handler/eval"]
    assert [s.attrs["mode"] for s in evals] == ["eager"] * 3
    # once per CompiledWithFallback: the handler's program, and the
    # expression's own inside the eager walk; not once per evaluation
    warned = [r.getMessage().split(":")[0] for r in caplog.records
              if "falling back to eager" in r.getMessage()]
    assert warned == ["handler tasks ['squared']", "GeneralFunction(s)"]
    np.testing.assert_allclose(handler["squared"],
                               np.asarray(s["g"]) ** 2, atol=1e-12)

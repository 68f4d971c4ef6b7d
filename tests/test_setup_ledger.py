"""
The set-up ledger (PR 37): `tools/retrace.py`'s program rows and eager
aggregate, `tools/metrics.BuildPhases`' names (`init`, `basis_stacks`,
`plans`, `upload`, `dd_prepare`, `unnamed_sec`, `compile_sec` as the sum of
the solver's own rows), the `compile/<label>` and `build/<name>` spans,
and the ten per-layer metrics of `setup_s` that read them
(`chipbench/setupledger.py`, `chipbench/layers/`). All on the CPU at sizes
of a second or two; the chip's readings are `chipbench/tests/chip_setup.sh`.
"""

import json
import pathlib

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import dedalus_tpu.public as d3
from dedalus_tpu.extras.bench_problems import build_rb_solver
from dedalus_tpu.tools import metrics as metrics_mod
from dedalus_tpu.tools import retrace, tracing
from dedalus_tpu.tools.jitlift import lifted_jit

ROOT = pathlib.Path(__file__).resolve().parents[1]
sentinel = retrace.sentinel
ROW_KEYS = {"label", "t0", "first_call_sec", "discover_sec", "trace_sec",
            "lower_sec", "backend_sec", "retrieval_sec", "cache", "owner"}
DT = 1e-3


def rows_of(label_part):
    return [r for r in sentinel.program_rows() if label_part in r["label"]]


@pytest.fixture
def clean():
    """A ledger of this test's own, and no solver current on the thread."""
    sentinel.reset()
    metrics_mod._current.phases = None
    yield
    sentinel.reset()
    metrics_mod._current.phases = None


@pytest.fixture(scope="module")
def rb():
    """RB 32 x 16 float32, past warm-up by `step`, then one `step_many`."""
    solver, _ = build_rb_solver(32, 16, np.float32)
    for _ in range(solver.warmup_iterations + 2):
        solver.step(DT)
    solver.step_many(5, DT)
    return solver


# ------------------------------------------------------------ program rows

def test_first_call_books_one_row_per_signature(clean):
    def double_it(x):
        return 2.0 * x

    program = lifted_jit(double_it)
    program(jnp.ones(3))
    mine = rows_of("double_it")
    assert len(mine) == 1
    row = mine[0]
    assert set(row) == ROW_KEYS
    assert row["label"] == double_it.__qualname__
    assert row["owner"] is None and row["cache"] in ("hit", "miss", "off")
    assert row["first_call_sec"] > 0 and row["discover_sec"] > 0
    assert row["trace_sec"] > 0 and row["lower_sec"] > 0
    assert row["backend_sec"] >= row["retrieval_sec"] >= 0
    program(jnp.ones(3))                       # the same signature: no row
    assert len(rows_of("double_it")) == 1
    program(jnp.ones(4))                       # a new one: a second row
    assert len(rows_of("double_it")) == 2
    assert sentinel.program_totals()["programs"] == 2


def test_nested_jit_counts_once(clean):
    inner = jax.jit(lambda x: jnp.sin(x) @ jnp.cos(x).T)

    def outer_body(x):
        y = inner(x)
        return jax.jit(lambda z: z + 1.0)(y) + inner(2 * x)

    lifted_jit(outer_body)(jnp.ones((8, 8)))
    (row,) = rows_of("outer_body")
    named = row["trace_sec"] + row["lower_sec"] + row["backend_sec"]
    assert 0 < named <= row["first_call_sec"]
    assert named + row["discover_sec"] <= row["first_call_sec"] + 1e-6


def test_self_seconds_gives_a_parent_what_its_children_left():
    tail = []
    assert retrace._self_seconds(tail, 1.0, 2.0) == 1.0
    assert retrace._self_seconds(tail, 2.5, 3.0) == 0.5
    # the parent of both arrives last and takes their place
    assert retrace._self_seconds(tail, 0.5, 4.0) == pytest.approx(2.0)
    assert tail == [(0.5, 4.0)]
    assert retrace._self_seconds(tail, 5.0, 6.0) == 1.0
    assert tail == [(0.5, 4.0), (5.0, 6.0)]


def test_persistent_cache_miss_then_hit(clean, tmp_path):
    was = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compilation_cache.reset_cache()
    try:
        def cached_program(x):
            return jnp.tanh(x) * 3.0 + x ** 2

        lifted_jit(cached_program)(jnp.ones(37))
        (first,) = rows_of("cached_program")
        assert first["cache"] == "miss" and first["retrieval_sec"] == 0
        jax.clear_caches()
        lifted_jit(cached_program)(jnp.ones(37))
        second = rows_of("cached_program")[1]
        assert second["cache"] == "hit"
        assert 0 < second["retrieval_sec"] <= second["backend_sec"]
        totals = sentinel.program_totals()
        assert totals["cache_hits"] == 1 and totals["cache_misses"] == 1
    finally:
        for key, value in was.items():
            jax.config.update(key, value)
        compilation_cache.reset_cache()


def test_eager_operations_land_in_the_aggregate(clean):
    x = jnp.arange(11.0)
    jnp.cumsum(x * 3.0).block_until_ready()    # no row open: eager
    assert not sentinel.program_rows()
    eager = sentinel.eager_programs()
    assert eager and all(set(e) == {"count", "sec"} for e in eager.values())
    totals = sentinel.program_totals()["eager"]
    assert totals["count"] >= 1 and totals["sec"] > 0
    assert not any(name.startswith("jit") for name in eager)


def test_the_aggregate_and_the_rows_are_bounded(clean):
    for i in range(retrace.EAGER_NAMES + 40):
        sentinel._eager_span(f"op{i}", "backend_sec", float(i), i + 0.5)
    eager = sentinel.eager_programs()
    assert len(eager) == retrace.EAGER_NAMES + 1
    assert eager["other"]["count"] == 40
    assert eager["other"]["sec"] == pytest.approx(20.0)
    state = retrace.TraceCount("bounded")
    for _ in range(retrace.ROW_CAP + 7):
        sentinel.open_row(state).close()
    assert len(sentinel.program_rows()) == retrace.ROW_CAP
    assert sentinel.rows_dropped == 7
    assert sentinel.program_totals()["rows_dropped"] == 7
    sentinel.reset()
    assert not sentinel.program_rows() and not sentinel.eager_programs()
    assert sentinel.rows_dropped == 0


def test_a_first_call_inside_a_bracket_is_that_brackets_time(clean):
    inner = lifted_jit(lambda x: x * 5.0)

    def outer_program(x):
        return inner(x) + 1.0

    lifted_jit(outer_program)(jnp.ones(5))
    rows = sentinel.program_rows()
    assert [r["label"] for r in rows] == [outer_program.__qualname__]
    assert getattr(retrace._local, "row", None) is None


def test_a_build_phase_inside_a_first_call_is_the_phases_time(clean):
    import time
    phases = metrics_mod.BuildPhases("Paused")
    phases.enter()

    def slow_stack(x):
        with metrics_mod.build_scope("basis_stacks"):
            time.sleep(0.05)         # a stack built at trace time
        return x + 1.0

    start = time.time()
    lifted_jit(slow_stack)(jnp.ones(3))
    wall = time.time() - start
    (row,) = rows_of("slow_stack")
    # the body ran twice: the discovery pass and the trace
    assert phases.seconds["basis_stacks"] >= 0.1
    assert row["first_call_sec"] <= wall - 0.1 + 1e-3
    assert row["discover_sec"] < wall - 0.1
    assert row["trace_sec"] + row["lower_sec"] + row["backend_sec"] \
        <= row["first_call_sec"]
    assert phases.compile_sec == row["first_call_sec"]


def test_noted_jit_books_a_row_and_warm_calls_none(clean):
    phases = metrics_mod.BuildPhases("Probe")
    phases.enter()
    probe = retrace.noted_jit(lambda x: jnp.sum(x * x), "ledger/probe")
    assert float(probe(jnp.ones(6))) == 6.0
    (row,) = rows_of("ledger/probe")
    assert row["owner"] == phases.name and row["discover_sec"] < 1e-3
    assert row["trace_sec"] + row["lower_sec"] + row["backend_sec"] \
        <= row["first_call_sec"]
    assert phases.compile_sec == row["first_call_sec"]
    calls = sentinel.listener_calls
    for _ in range(20):
        probe(jnp.ones(6))
    assert len(rows_of("ledger/probe")) == 1
    assert sentinel.listener_calls == calls
    probe(jnp.ones(7))
    assert len(rows_of("ledger/probe")) == 2


# ------------------------------------------------------------ build phases

def test_init_covers_its_phases_and_compile_covers_every_program(rb):
    record = rb.build_phases.record()
    for name in metrics_mod.BUILD_PHASES:
        assert record[f"{name}_sec"] >= 0
    assert record["init_sec"] > 0 and record["unnamed_sec"] >= 0
    assert record["unnamed_sec"] <= record["init_sec"]
    # what was named inside __init__ is no more than __init__
    assert rb.build_phases._named_in_init <= record["init_sec"] + 1e-6
    assert record["plans_sec"] > 0 and record["upload_sec"] > 0
    programs = record["programs"]
    labels = [r["label"] for r in programs["rows"]]
    assert any(label.endswith("step_body") for label in labels)
    assert any(label.endswith("_step_n") for label in labels)   # step_many's
    assert "health/probe" in labels
    assert len(programs["rows"]) <= metrics_mod.BuildPhases.TOP_ROWS
    firsts = [r["first_call_sec"] for r in programs["rows"]]
    assert firsts == sorted(firsts, reverse=True)
    assert record["compile_sec"] == pytest.approx(
        programs["first_call_sec"], abs=1e-3)
    assert record["compile_sec"] >= sum(firsts) - 1e-3 > 0
    assert programs["trace_sec"] + programs["lower_sec"] \
        + programs["backend_sec"] <= programs["first_call_sec"]
    assert {r["owner"] for r in programs["rows"]} == {rb.build_phases.name}
    assert rb.build_phases.name.startswith("InitialValueSolver#")
    assert rb.build_phases in metrics_mod.all_phases()
    json.dumps(record)


def test_two_solvers_do_not_see_each_others_rows(rb):
    other, _ = build_rb_solver(24, 12, np.float32)
    before = rb.build_phases.record()["programs"]["programs"]
    other.step(DT)
    mine = other.build_phases.record()["programs"]
    assert mine["programs"] >= 1
    assert {r["owner"] for r in mine["rows"]} == {other.build_phases.name}
    assert rb.build_phases.record()["programs"]["programs"] == before
    assert other.build_phases.name != rb.build_phases.name
    # and the entry point made it the thread's current one
    assert metrics_mod.current_phases() is other.build_phases
    rb.step(DT)
    assert metrics_mod.current_phases() is rb.build_phases


def test_a_warmed_step_opens_no_row_and_calls_no_listener(rb):
    rb.step(DT)
    rows, calls = len(sentinel.program_rows()), sentinel.listener_calls
    dropped = sentinel.rows_dropped
    for _ in range(100):
        rb.step(DT)
    rb.step_many(5, DT)
    assert len(sentinel.program_rows()) == rows
    assert sentinel.rows_dropped == dropped
    assert sentinel.listener_calls == calls
    assert sentinel.post_arm_retraces == 0


def test_scopes_are_flat_and_exclusive():
    phases = metrics_mod.BuildPhases("Exclusive")
    import time
    with phases.init():
        assert metrics_mod.current_phases() is phases
        with phases.scope("factor"):
            time.sleep(0.02)
            with metrics_mod.build_scope("upload"):
                time.sleep(0.03)
        time.sleep(0.01)
    record = phases.record()
    assert 0.02 <= record["factor_sec"] < 0.03 + 0.02
    assert record["upload_sec"] >= 0.03
    assert record["init_sec"] >= record["factor_sec"] + record["upload_sec"]
    assert 0.01 <= record["unnamed_sec"] < 0.03
    # after __init__ a phase is still booked, and leaves unnamed_sec alone
    with phases.scope("factor"):
        time.sleep(0.01)
    assert phases.record()["factor_sec"] >= record["factor_sec"] + 0.01
    assert phases.record()["unnamed_sec"] == record["unnamed_sec"]


def test_a_sphere_basis_books_its_stacks(clean):
    process = metrics_mod.process_phases()
    before = process.seconds.get("basis_stacks", 0.0)
    cs = d3.S2Coordinates("phi", "theta")
    dist = d3.Distributor(cs, dtype=np.float64)
    basis = d3.SphereBasis(cs, shape=(20, 10), dtype=np.float64, radius=1,
                           dealias=(3 / 2, 3 / 2))
    h = dist.Field(name="h", bases=basis)
    h.fill_random("g", seed=3, scale=1e-2)
    h["c"]          # no solver is current: the process-level phases
    assert process.seconds.get("basis_stacks", 0.0) > before
    u = dist.VectorField(cs, name="u", bases=basis)
    problem = d3.IVP([u, h], namespace=locals())
    problem.add_equation("dt(u) + grad(h) = - MulCosine(Skew(u))")
    problem.add_equation("dt(h) = - div(u)")
    solver = problem.build_solver(d3.RK222)
    solver.step(DT)   # the ladder and cosine stacks: the solver's own
    assert solver.build_phases.record()["basis_stacks_sec"] > 0
    assert solver.build_phases.record()["group_stacks"]["diagonal"][
        "applications"] > 0


def test_the_dd_route_books_dd_prepare():
    from chipbench.manifest import load_module
    cfg = load_module(ROOT / "chipbench" / "configs" / "rb256x64-f64.py")
    mp = pytest.MonkeyPatch()
    mp.setattr(jax, "default_backend", lambda: "tpu")
    try:
        dep = cfg.build(0, size=dict(cfg.SPEC["rehearsal"], Nx=16, Nz=8))
    finally:
        mp.undo()
    record = dep.solver.build_phases.record()
    assert record["f64_route"] == "dd" and record["dd"]["slices"] == 8
    assert record["dd_prepare_sec"] > 0
    assert record["unnamed_sec"] <= record["init_sec"]
    # the clock outlives its solver and holds no reference to it
    name = dep.solver.build_phases.name
    phases = dep.solver.build_phases
    del dep
    import gc
    gc.collect()
    assert "dd" not in phases.record()
    assert name in [p.name for p in metrics_mod.all_phases()]


# ------------------------------------------------------------------- spans

def test_live_spans_compile_under_step_and_build_once(clean):
    was = tracing.enabled()
    tracing.enable()
    try:
        tracing.recorder().clear()
        solver, _ = build_rb_solver(16, 12, np.float32)
        solver.step(DT)
        spans = tracing.recorder().spans()
    finally:
        if not was:
            tracing.disable()
    (step,) = [s for s in spans if s.name == "step"]
    compiles = [s for s in spans if s.name.startswith("compile/")]
    body = [s for s in compiles if s.name.endswith("step_body")]
    assert len(body) == 1
    ids = {s.span_id: s for s in spans}

    def ancestors(s):
        while s.parent_id is not None and s.parent_id in ids:
            s = ids[s.parent_id]
            yield s.name

    assert "step" in list(ancestors(body[0]))
    assert body[0].attrs["cache"] in ("hit", "miss", "off")
    assert {"trace_sec", "lower_sec", "backend_sec"} <= set(body[0].attrs)
    # the first factorization's program: compile/ under build/factor under
    # step/factor under step
    factor = [s for s in compiles if "factor" in s.name]
    assert factor and "build/factor" in list(ancestors(factor[0]))
    # each entry of a build scope is ONE span (no second annotation)
    builds = [s for s in spans if s.name.startswith("build/")]
    assert builds and all(s.name.split("/", 1)[1] in metrics_mod.BUILD_PHASES
                          for s in builds)
    in_step = [s for s in builds if s.name == "build/factor"
               and "step" in list(ancestors(s))]
    assert len(in_step) == 1
    assert step.dur >= body[0].dur


def test_log_stats_and_the_record_carry_the_ledger(rb, caplog):
    import logging
    with caplog.at_level(logging.INFO, logger="dedalus_tpu.core.solvers"):
        rb.log_stats()
    text = "\n".join(r.getMessage() for r in caplog.records)
    assert "Build phases:" in text and "unnamed" in text
    assert "Programs:" in text and "largest:" in text
    record = rb.flush_metrics()
    phases = record["build_phases"]
    assert phases["programs"]["programs"] >= 3 and "init_sec" in phases
    lines = metrics_mod.format_build_phases(phases)
    assert len(lines) == 2 and "cache load" in lines[1]
    # a record from before the ledger prints what it has
    old = {"host_assembly_sec": 1.0, "structure_sec": 0.0, "factor_sec": 2.0,
           "compile_sec": 3.0, "assembly_cache": "hit"}
    assert len(metrics_mod.format_build_phases(old)) == 1
    assert metrics_mod.format_build_phases(None) == []


# ------------------------------------------------- the per-layer metrics

# every cell, the four-chip one and the ensemble's appended
ALL_CELLS = ["rb256x64.block", "rb256x64.cfl", "shear512.block",
             "rb2048x1024.block10", "sw_ell255.block",
             "rb256x64-f64.block10", "rb3d128x128x128.block10",
             "rb256x64-ens.block10"]
NEW_METRICS = [
    ("compile_s", "s", "program_span", "step program", ALL_CELLS),
    ("trace_lower_s", "s", "program_span", "step program", ALL_CELLS),
    ("cache_load_s", "s", "program_span", "step program", ALL_CELLS),
    ("xla_cache_misses", "count", "program_counter", "step program",
     ALL_CELLS),
    ("eager_compile_s", "s", "program_span", "entry", ALL_CELLS),
    ("init_unnamed_s", "s", "program_span", "entry", ALL_CELLS),
    ("script_s", "s", "program_span", "entry", ALL_CELLS),
    ("upload_s", "s", "program_span", "host assembly", ALL_CELLS),
    ("basis_stacks_s", "s", "program_span", "transforms",
     ["sw_ell255.block"]),
    ("dd_prepare_s", "s", "program_span", "pencil solve",
     ["rb256x64-f64.block10"]),
]


@pytest.mark.parametrize("name, unit, source, layer, cells", NEW_METRICS,
                         ids=[m[0] for m in NEW_METRICS])
def test_per_layer_entry_and_reader(name, unit, source, layer, cells, rb,
                                    monkeypatch):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    names = [m["name"] for m in bench["per_layer"]]
    # the ten follow one another in the table's order (the metrics of
    # later cells come after them)
    first = names.index(NEW_METRICS[0][0])
    assert names[first:first + len(NEW_METRICS)] == [m[0] for m in
                                                      NEW_METRICS]
    entry = bench["per_layer"][names.index(name)]
    assert entry == {"name": name, "unit": unit, "better": "lower",
                     "source": source, "layer": layer, "moves": "setup_s",
                     "workloads": cells}
    assert layer in {m["layer"] for m in bench["per_layer"][:first]}
    assert (ROOT / "chipbench" / "layers" / f"{name}.py").is_file()
    from chipbench.manifest import Manifest
    read = Manifest().layer_reader(name)
    ctx = {"build_s": 5.0, "build_phases": rb.build_phases.record()}
    lifted_jit(lambda x: x - 2.0)(jnp.ones(2))    # a row, whatever ran before
    value = read(ctx)
    assert isinstance(value, float) and value >= 0
    # a context and a tree without a ledger: nothing, and no error
    from chipbench import setupledger
    monkeypatch.setattr(setupledger, "totals", lambda: None)
    monkeypatch.delattr(metrics_mod, "all_phases")
    old = {"host_assembly_sec": 0.0, "structure_sec": 0.0,
           "factor_sec": 1.0, "compile_sec": 2.0}
    assert read({"build_s": 5.0, "build_phases": old}) is None
    assert read({}) is None


def test_the_readers_add_up(rb):
    from chipbench import setupledger
    lifted_jit(lambda x: jnp.cos(x) - x)(jnp.ones(9))
    compile_s = setupledger.total_seconds("first_call_sec")
    trace_lower = setupledger.total_seconds("discover_sec", "trace_sec",
                                            "lower_sec")
    load = setupledger.total_seconds("retrieval_sec")
    assert compile_s >= trace_lower + load > 0
    found = setupledger.totals()
    assert found["cache_hits"] + found["cache_misses"] <= found["programs"]
    total = sum(p.init_sec for p in metrics_mod.all_phases())
    assert setupledger.script_seconds({"build_s": total + 2.5}) \
        == pytest.approx(2.5)
    assert setupledger.script_seconds({}) is None

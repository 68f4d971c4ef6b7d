"""The readers of the program's step-loop spans: the self-time arithmetic
on hand-made span lists, the refusals (an empty ring, a full one,
`[tracing]` on), the entries of BENCHMARK.json against their files, and a
traced CPU rehearsal that prints every new name and leaves the program's
spans on the host plane of the xplane, on the profiler's clock."""

import json
import os
import pathlib
import subprocess
import sys

import pytest

from chipbench import loopspans, xplane
from chipbench.manifest import Manifest
from dedalus_tpu.tools import tracing
from dedalus_tpu.tools.tracing import Span

ROOT = pathlib.Path(__file__).resolve().parents[2]
CFL, BLOCKS = "rb256x64.cfl", ["rb256x64.block", "shear512.block"]
# metric -> the cells the issue's table gives it
NEW = {
    "step_dispatch_ms_per_step": [BLOCKS[0], CFL, BLOCKS[1]],
    "handler_eval_ms_per_step": [CFL],
    "handler_pull_ms_per_step": [CFL],
    "handler_write_ms_per_step": [CFL],
    "handler_eager_pct": [CFL],
    "cfl_ms_per_step": [CFL],
    "refactors_per_100": [CFL],
    "probe_ms_per_step": [BLOCKS[0], CFL, BLOCKS[1]],
}


def span(span_id, parent_id, name, dur, **attrs):
    return Span("t", span_id, parent_id, name, 0.0, dur, attrs=attrs)


def test_self_time_nested_sibling_and_orphan():
    spans = [
        # a step with a factor and a handlers child; handlers has its own
        span(1, None, "step", 10.0),
        span(2, 1, "step/factor", 1.0),
        span(3, 1, "step/handlers", 6.0),
        span(4, 3, "handler/eval", 2.0),
        span(5, 3, "handler/pull", 3.0),
        # a sibling step with nothing under it
        span(6, None, "step", 2.0),
        # an orphan: its parent left the ring; it shortens nobody
        span(7, 99, "handler/write", 5.0),
        # children that overrun their parent never make time negative
        span(8, None, "step_many", 1.0),
        span(9, 8, "health/check", 1.5),
    ]
    assert loopspans.self_seconds(spans, ("step",)) == 3.0 + 2.0
    assert loopspans.self_seconds(spans, ("step", "step_many")) == 5.0
    # grandchildren are the child's business, not the grandparent's
    assert loopspans.self_seconds(spans, ("step/handlers",)) == 1.0
    assert loopspans.total_seconds(spans, ("step",)) == 12.0
    assert loopspans.total_seconds(
        spans, ("handler/eval", "handler/pull", "handler/write")) == 10.0
    assert loopspans.total_seconds(spans, ("cfl",)) == 0.0


@pytest.fixture
def ring(monkeypatch):
    """A small ring of its own in place of the program's, [tracing] off."""
    ring = tracing.TraceRecorder(capacity=16)
    monkeypatch.setattr(tracing, "_recorder", ring)
    monkeypatch.setattr(tracing, "_enabled", False)
    return ring


def window(ring, eager=0):
    """Two iterations' worth of spans."""
    for i, (name, parent, dur, attrs) in enumerate([
            ("step", None, 0.004, {}), ("step/factor", 1, 0.001, {}),
            ("step/handlers", 1, 0.002, {}),
            ("handler/eval", 3, 0.0005,
             {"mode": "eager" if eager else "compiled"}),
            ("handler/pull", 3, 0.001, {}), ("handler/write", 3, 0.0004, {}),
            ("cfl", None, 0.0002, {}), ("step", None, 0.001, {}),
            ("metrics/sample", 8, 0.0003, {}),
            ("health/check", 8, 0.0001, {}),
            # the sampler's wait for queued steps: nobody's probe time
            ("metrics/drain", 8, 0.0002, {})], start=1):
        ring.record(span(i, parent, name, dur, **attrs))


def test_readers_on_a_known_window(ring):
    window(ring, eager=1)
    read = Manifest().layer_reader
    ctx = {"iterations": 2}
    want = {"step_dispatch_ms_per_step": (0.001 + 0.0004) * 500,
            "handler_eval_ms_per_step": 0.25,
            "handler_pull_ms_per_step": 0.5,
            "handler_write_ms_per_step": 0.2, "handler_eager_pct": 100.0,
            "cfl_ms_per_step": 0.1, "refactors_per_100": 50.0,
            "probe_ms_per_step": 0.2}
    assert set(want) == set(NEW)
    for name, value in want.items():
        assert read(name)(ctx) == pytest.approx(value), name


@pytest.mark.parametrize("name", sorted(NEW))
@pytest.mark.parametrize("state", ["empty", "full", "tracing_on"])
def test_reader_says_nothing_rather_than_something_wrong(ring, monkeypatch,
                                                         name, state):
    if state != "empty":
        window(ring)
    if state == "full":
        for i in range(ring.capacity):
            ring.record(span(100 + i, None, "step", 0.001))
    if state == "tracing_on":
        monkeypatch.setattr(tracing, "_enabled", True)
    assert Manifest().layer_reader(name)({"iterations": 2}) is None


def test_no_iterations_no_rate(ring):
    window(ring)
    for name in NEW:
        if name != "handler_eager_pct":
            assert Manifest().layer_reader(name)({"iterations": 0}) is None


def test_a_window_without_the_span_reads_zero(ring):
    """A block cell's window holds no probe: 0, since the ring was read."""
    ring.record(span(1, None, "step_many", 0.001))
    read = Manifest().layer_reader
    assert read("probe_ms_per_step")({"iterations": 50}) == 0.0
    assert read("refactors_per_100")({"iterations": 50}) == 0.0
    assert read("handler_eager_pct")({"iterations": 50}) is None


@pytest.mark.parametrize("name", sorted(NEW))
def test_entry_has_its_file_unit_and_cells(name):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert (ROOT / "chipbench" / "layers" / f"{name}.py").is_file()
    assert entry["unit"] and entry["better"] == "lower"
    assert entry["layer"] == "host loop"
    assert entry["source"] in ("program_span", "program_counter")
    assert entry["workloads"] == NEW[name]
    # run.py skips a metric whose `moves` the cell does not report
    for cell in entry["workloads"]:
        reported = {m["name"] for m in bench["end_to_end"]
                    if cell in m.get("workloads", [cell])}
        assert entry["moves"] in reported


def traced_rehearsal(cell, keep=None):
    args = [sys.executable, "-m", "chipbench.run", "--workload", cell,
            "--seed", "3000000019", "--seconds", "2", "--trace", "1",
            "--rehearse-cpu"] + (["--keep-trace", str(keep)] if keep else [])
    done = subprocess.run(args, cwd=ROOT, capture_output=True, text=True,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"),
                          timeout=900)
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1])["metrics"]


@pytest.fixture(scope="module")
def cfl_rehearsal(tmp_path_factory):
    keep = tmp_path_factory.mktemp("xplane")
    return traced_rehearsal(CFL, keep), keep / f"{CFL}.xplane.pb"


def test_traced_cfl_rehearsal_prints_all_eight(cfl_rehearsal):
    metrics, _ = cfl_rehearsal
    assert set(NEW) <= set(metrics)
    assert metrics["step_dispatch_ms_per_step"]["value"] > 0
    assert metrics["handler_pull_ms_per_step"]["value"] > 0
    assert metrics["handler_write_ms_per_step"]["value"] > 0
    assert metrics["cfl_ms_per_step"]["value"] > 0
    assert metrics["handler_eager_pct"]["value"] == 0.0
    # the old metrics are still beside them
    assert {"dt_changes_per_100", "build_s", "ref_rel_l2"} <= set(metrics)


def test_traced_block_rehearsal_prints_its_two():
    metrics = traced_rehearsal(BLOCKS[0])
    assert set(NEW) & set(metrics) == {"step_dispatch_ms_per_step",
                                       "probe_ms_per_step"}
    assert metrics["step_dispatch_ms_per_step"]["value"] > 0


def test_spans_are_rows_of_the_host_plane(cfl_rehearsal):
    """The shared clock: the program's spans sit on a /host: plane of the
    profiler's own file, inside the harness's window span."""
    _, path = cfl_rehearsal
    rows = [(start, end, name)
            for plane in xplane.read(
                path, lambda plane, line: plane.startswith("/host:"))
            for line in plane["lines"]
            for start, end, name, _ in line["events"]]
    names = {name for _, _, name in rows}
    assert {"dedalus/step", "dedalus/handler/pull", "dedalus/cfl",
            "chipbench/window"} <= names
    lo, hi = next((s, e) for s, e, n in rows if n == "chipbench/window")
    steps = [(s, e) for s, e, n in rows if n == "dedalus/step"]
    assert steps and all(lo <= s and e <= hi for s, e in steps)

"""BENCHMARK.json against the contract's character rules and against the
files under chipbench/, and the proof that the harness is driven by data:
a new configuration, traffic mix, cell (on 4 chips) and per-layer metric
are added to a temporary copy as new files and new entries only, and the
copy runs the new cell."""

import json
import os
import pathlib
import re
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
HERE = ROOT / "chipbench"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def one_line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_top_level_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["chipbench"]
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    assert len(bench["command"]) <= 32
    assert all(one_line(w) and not w.startswith("/") and ".." not in w
               for w in bench["command"])
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024


def test_names_units_and_keys(bench):
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[group]]
        assert len(names) == len(set(names)), group
        assert all(NAME.match(n) for n in names), group
    metrics = bench["end_to_end"] + bench["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert one_line(m["layer"])
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert one_line(c["source"]) and one_line(c["why"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and one_line(w["why"])
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(len(bench["workloads"]) // 2, 1)


def test_every_named_file_exists(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    files = [c["file"] for c in configs.values()]
    assert len(files) == len(set(files))
    for c in configs.values():
        assert c["file"].startswith("chipbench/")
        spec = json.loads((ROOT / c["file"]).read_text())
        assert spec["name"] == c["name"]
        assert spec["source"] == c["source"]
        assert spec["reduced"] == c["reduced"]
        assert set(spec["reduced_why"]) == set(spec["reduced"])
        for key in ("guarantees", "assumed", "tolerances", "reference",
                    "rehearsal", "fixed_dt", "loop", "sizes"):
            assert key in spec, (c["name"], key)
        assert all("why" in t for t in spec["tolerances"].values())
        assert (HERE / "configs" / f"{c['name']}.py").is_file()
    used = set()
    for w in bench["workloads"]:
        assert w["config"] in configs
        used.add(w["config"])
        assert (HERE / "traffic" / f"{w['traffic']}.json").is_file()
        assert (HERE / "workloads" / f"{w['name']}.json").is_file()
    assert used == set(configs)
    for m in bench["per_layer"]:
        assert (HERE / "layers" / f"{m['name']}.py").is_file()
    for path in HERE.rglob("*"):
        if "__pycache__" in path.parts or path.suffix == ".pyc":
            continue
        assert re.match(r"^[A-Za-z0-9_.\-/]+$",
                        str(path.relative_to(ROOT))), path


def test_every_cell_reports_what_the_contract_asks(bench):
    cells = [w["name"] for w in bench["workloads"]]
    known = set(cells)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert set(m.get("workloads", [])) <= known, m["name"]
    for cell in cells:
        e2e = {m["name"] for m in bench["end_to_end"]
               if cell in m.get("workloads", [cell])}
        assert "setup_s" in e2e and len(e2e) >= 2, cell
        layers = [m for m in bench["per_layer"]
                  if cell in m.get("workloads", [cell])]
        assert layers, cell
        for m in layers:
            # a per-layer metric is reported only where the metric it
            # moves is
            assert m["moves"] in e2e, (cell, m["name"])


DUMMY_READER = '''"""dummy layer metric: iterations traced."""


def read(ctx):
    return ctx.get("iterations")
'''


def test_new_cell_config_traffic_and_metric_are_files_and_entries(tmp_path):
    """Nothing that exists is edited: files are added, entries appended."""
    copy = tmp_path / "checkout"
    copy.mkdir()
    shutil.copytree(HERE, copy / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (copy / "chipbench").rglob("*")
              if p.is_file()}
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())

    here = copy / "chipbench"
    spec = json.loads((here / "configs" / "rb256x64.json").read_text())
    spec["name"] = "dummy-rb.v2"
    spec["sizes"].update(spec["rehearsal"])
    (here / "configs" / "dummy-rb.v2.json").write_text(json.dumps(spec))
    shutil.copy(here / "configs" / "rb256x64.py",
                here / "configs" / "dummy-rb.v2.py")
    (here / "traffic" / "dummy-steps.json").write_text(json.dumps({
        "advance": "step", "dt": "fixed", "loop": False,
        "spinup_sim_time": 0.05, "spinup_block": 5, "warm_units": 2,
        "trace_units": 12}))
    (here / "workloads" / "dummy-rb.v2.dummy-steps.json").write_text(
        json.dumps({"expect": {"ops": "DenseOps"}, "why": "dummy"}))
    (here / "layers" / "dummy_iterations.py").write_text(DUMMY_READER)
    bench["configs"].append({
        "name": "dummy-rb.v2", "source": spec["source"],
        "file": "chipbench/configs/dummy-rb.v2.json",
        "reduced": spec["reduced"], "why": "dummy"})
    bench["workloads"].append({
        "name": "dummy-rb.v2.dummy-steps", "config": "dummy-rb.v2",
        "traffic": "dummy-steps", "chips": 4, "why": "dummy"})
    bench["per_layer"].append({
        "name": "dummy_iterations", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "step program",
        "moves": "steps_per_s",
        "workloads": ["dummy-rb.v2.dummy-steps"]})
    (copy / "BENCHMARK.json").write_text(json.dumps(bench))

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(copy), str(ROOT)]))
    lines = {}
    for trace in ("0", "1"):
        done = subprocess.run(
            [sys.executable, "-m", "chipbench.run", "--workload",
             "dummy-rb.v2.dummy-steps", "--seed", "5", "--seconds", "1",
             "--trace", trace, "--rehearse-cpu"],
            cwd=copy, env=env, capture_output=True, text=True, timeout=600)
        assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-3000:]
        lines[trace] = json.loads(done.stdout.strip().splitlines()[-1])
    assert lines["0"]["device"] == {"platform": "cpu", "kind": "cpu",
                                    "count": 4, "memory_peak_bytes": 0}
    assert lines["0"]["correct"] is False and lines["0"]["failed"] == 0
    assert set(lines["0"]["metrics"]) == {"steps_per_s", "setup_s"}
    assert lines["1"]["metrics"]["dummy_iterations"] == {
        "value": 12.0, "unit": "count"}
    after = {p: p.read_bytes() for p in before}
    assert after == before

"""A CPU rehearsal of run.py for each cell, at the configuration's
rehearsal size: it reaches the last line, says `cpu`, and never says
`correct: true`. And the refusals: no TPU, no result."""

import json
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run(args, **env):
    return subprocess.run(
        [sys.executable, "-m", "chipbench.run"] + args, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu", **env),
        capture_output=True, text=True, timeout=900)


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_reaches_the_last_line(cell):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    done = run(["--workload", cell, "--seed", "7", "--seconds", "2",
                "--trace", "0", "--rehearse-cpu"])
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-3000:]
    assert "chipbench device: " in done.stdout
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert set(line) == KEYS
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is False
    assert line["failed"] == 0 and line["attempted"] > 0
    want = {m["name"] for m in bench["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert set(line["metrics"]) == want
    assert all(m["value"] > 0 for m in line["metrics"].values())
    checks = json.loads(next(
        text for text in done.stdout.splitlines()
        if text.startswith("chipbench checks: ")).split(": ", 1)[1])
    # everything but the platform holds at the rehearsal size too
    assert all(checks["checks"].values()), checks


def test_traced_rehearsal_names_no_device_number():
    done = run(["--workload", "rb256x64.cfl", "--seed", "7", "--seconds",
                "2", "--trace", "1", "--rehearse-cpu"])
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is False
    # no device plane in a CPU trace: the device metrics are left out,
    # never written as 0
    assert not {"device_ms_per_step", "device_idle_pct", "solve_roofline",
                "transform_ms_per_step", "peak_hbm_MB"} & set(line["metrics"])
    assert {"build_s", "ref_rel_l2", "dt_changes_per_100",
            "retraces_in_window"} <= set(line["metrics"])
    assert "busy_s" not in line["device"]


def test_refuses_without_a_tpu():
    done = run(["--workload", "rb256x64.block", "--seed", "7", "--seconds",
                "1", "--trace", "0"])
    assert done.returncode != 0
    assert "no TPU" in done.stderr
    assert not done.stdout.strip().splitlines()[-1].startswith("{\"correct")


def test_refuses_an_unknown_cell():
    done = run(["--workload", "nope", "--seed", "7", "--seconds", "1",
                "--trace", "0", "--rehearse-cpu"])
    assert done.returncode != 0 and "no workload" in done.stderr

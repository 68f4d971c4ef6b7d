"""
The launchers that remain after the bring-up (PR 22): `_append_result`
stamps every results row with the environment fingerprint, and
`chip_smoke.py` — rehearsed here on the CPU in a subprocess — runs its
phases, reports each, and still refuses to say ok for anything but a TPU.
"""

import json
import os
import pathlib
import subprocess
import sys

REPO = pathlib.Path(__file__).parent.parent
sys.path.insert(0, str(REPO))

import __graft_entry__ as graft  # noqa: E402


def test_append_result_stamps_env_fingerprint(tmp_path):
    """Every results.jsonl row grows the host/environment fingerprint —
    the provenance perfwatch needs to tell host drift from regressions."""
    path = tmp_path / "results.jsonl"
    graft._append_result({"config": "x", "value": 1.0}, path=path)
    row = json.loads(path.read_text().splitlines()[0])
    env = row["env"]
    assert env["env_version"] == 1
    assert env["python"] and env["host"]
    assert isinstance(env["cpu_count"], int)
    # an explicit env on the record is never overwritten
    graft._append_result({"config": "y", "env": {"canned": True}},
                         path=path)
    row2 = json.loads(path.read_text().splitlines()[1])
    assert row2["env"] == {"canned": True}


def test_chip_smoke_cpu_rehearsal_passes_phases_but_never_says_ok():
    """`chip_smoke.py --nx 64 --nz 16` under JAX_PLATFORMS=cpu: the main
    phases report a pass, the exit code is non-zero, and no line says
    `"ok": true` — a CPU run can never stand in for the chip."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py"), "--nx", "64",
         "--nz", "16"], env=env, cwd=str(REPO), capture_output=True,
        text=True, timeout=600)
    lines = [json.loads(line) for line in proc.stdout.splitlines()
             if line.startswith("{")]
    phases = {rec["phase"]: rec for rec in lines if "phase" in rec}
    for name in ("rb_f32", "rb_f32_banded"):
        assert phases[name]["passed"] is True, phases[name]
    assert proc.returncode != 0
    assert not any(rec.get("ok") for rec in lines)
    assert lines[-1] == {"ok": False, "device": lines[-1]["device"]}
    assert lines[-1]["device"]["platform"] == "cpu"
    assert proc.stdout.rstrip().splitlines()[-1].startswith('{"ok": false')


def test_chip_smoke_refuses_a_cpu_at_the_published_size():
    """No arguments on a CPU (how the driver's sandbox check runs it):
    exits non-zero at once, with no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                          env=env, cwd=str(REPO), capture_output=True,
                          text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout

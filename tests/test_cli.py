"""
CLI smoke tests: `get_config` and `report` run in fresh subprocesses so a
regression in the command-line surface fails tier-1.
"""

import json
import os
import pathlib
import subprocess
import sys

REPO = pathlib.Path(__file__).parent.parent


def _run_cli(args, timeout=120):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, "-m", "dedalus_tpu", *args],
                          cwd=REPO, env=env, capture_output=True, text=True,
                          timeout=timeout)


def test_get_config_subprocess():
    proc = _run_cli(["get_config"])
    assert proc.returncode == 0, proc.stderr
    assert "[profiling]" in proc.stdout
    assert "SAMPLE_CADENCE" in proc.stdout
    assert "METRICS_DEFAULT" in proc.stdout


def test_report_subprocess(tmp_path):
    fixture = tmp_path / "metrics.jsonl"
    records = [
        {"kind": "step_metrics", "ts": 1.0, "config": "rb_fixture",
         "backend": "cpu", "dtype": "float32", "iterations": 20,
         "loop_wall_sec": 2.0, "steps_per_sec": 10.0, "sample_cadence": 5,
         "phase_samples": 4,
         "phase_mean_sec": {"transform": 0.03, "matsolve": 0.04,
                            "transpose": 0.0, "evaluator": 0.02},
         "phase_total_sec": {"transform": 0.6, "matsolve": 0.8,
                             "transpose": 0.0, "evaluator": 0.4},
         "phase_sum_frac": 0.9, "device_mem_peak_bytes": 123456789,
         "mem_source": "live_arrays", "counters": {"steps": 20}},
        # a bench-style row rides along in the same file
        {"config": "rb256x64_bench", "metric": "RB2D_steps_per_sec",
         "value": 12.3, "unit": "steps/sec", "ts": 2.0},
    ]
    fixture.write_text("".join(json.dumps(r) + "\n" for r in records))
    proc = _run_cli(["report", str(fixture)])
    assert proc.returncode == 0, proc.stderr
    out = proc.stdout
    assert "rb_fixture" in out
    for phase in ("transform", "matsolve", "transpose", "evaluator"):
        assert phase in out
    assert "1 metrics record(s), 1 other" in out
    assert "RB2D_steps_per_sec" in out


def test_report_heterogeneous_rows(tmp_path):
    """Pre-PR-2 records missing keys, postmortem rows, and non-object JSON
    lines must not crash the report; each lands in the right bucket."""
    fixture = tmp_path / "mixed.jsonl"
    rows = [
        '{"kind": "step_metrics"}',                        # bare, no keys
        '{"kind": "step_metrics", "iterations": 5, '
        '"health": {"ok": false, "reason": "boom", "checks": 2}}',
        '{"kind": "health_postmortem", "iteration": 7, '
        '"sim_time": 0.7, "reason": "non-finite state"}',
        '{"metric": "RB2D_steps_per_sec", "value": 1.0, "stale": true}',
        '[1, 2, 3]',                                       # not an object
        'not json at all',
    ]
    fixture.write_text("\n".join(rows) + "\n")
    proc = _run_cli(["report", str(fixture)])
    assert proc.returncode == 0, proc.stderr
    assert "2 metrics record(s), 1 other, 1 postmortem, 2 unparsable" \
        in proc.stdout
    assert "health: FAILED: boom" in proc.stdout
    assert "non-finite state" in proc.stdout
    assert "[stale]" in proc.stdout


def test_report_last_filter(tmp_path):
    fixture = tmp_path / "many.jsonl"
    rows = [{"kind": "step_metrics", "iterations": i} for i in range(5)]
    fixture.write_text("".join(json.dumps(r) + "\n" for r in rows))
    proc = _run_cli(["report", str(fixture), "--last", "2"])
    assert proc.returncode == 0, proc.stderr
    assert "2 metrics record(s)" in proc.stdout
    assert "3 iters" in proc.stdout and "4 iters" in proc.stdout
    assert "0 iters" not in proc.stdout
    proc = _run_cli(["report", str(fixture), "--last", "notanint"])
    assert proc.returncode == 2
    assert "--last" in proc.stderr


def test_postmortem_subprocess(tmp_path):
    """`postmortem <dir>` summarizes a flight-recorder dump; the record
    fields round-trip into the printed summary."""
    pm = tmp_path / "postmortem_i00000042"
    pm.mkdir()
    record = {
        "kind": "health_postmortem", "ts": 1.0,
        "reason": "non-finite state: field 'u' has 3 NaN / 0 Inf entries",
        "iteration": 42, "sim_time": 4.2, "dt": 0.1,
        "checks": 9, "warnings": 1,
        "fields": {"u": {"nan": 3, "inf": 0, "max_abs": 1.5, "l2": 2.5,
                         "tail_frac": {"z": 0.4}}},
        "dt_history": [{"iteration": 41, "dt": 0.1, "freq_max": 12.0}],
        "checkpoint": "state_at_failure.h5",
        "backend": "cpu", "dtype": "float32",
    }
    (pm / "postmortem.json").write_text(json.dumps(record))
    (pm / "health_ring.jsonl").write_text(
        json.dumps({"kind": "health_sample", "iteration": 41}) + "\n"
        + json.dumps({"kind": "health_sample", "iteration": 42}) + "\n")
    proc = _run_cli(["postmortem", str(pm)])
    assert proc.returncode == 0, proc.stderr
    out = proc.stdout
    assert "non-finite state: field 'u'" in out
    assert "iteration=42" in out
    assert "backend=cpu" in out
    assert "ring buffer: 2 records" in out
    assert "freq_max=12.0" in out
    assert "state_at_failure.h5" in out


def test_postmortem_missing_dir():
    proc = _run_cli(["postmortem", "/nonexistent/pm_dir"])
    assert proc.returncode == 1
    assert "cannot read" in proc.stderr


def test_postmortem_usage():
    proc = _run_cli(["postmortem"])
    assert proc.returncode == 2
    assert "usage" in proc.stderr


def test_report_missing_file():
    proc = _run_cli(["report", "/nonexistent/metrics.jsonl"])
    assert proc.returncode != 0
    assert "cannot read" in proc.stderr


def test_report_usage():
    proc = _run_cli(["report"])
    assert proc.returncode == 2
    assert "usage" in proc.stderr


def test_unknown_command():
    proc = _run_cli(["not_a_command"])
    assert proc.returncode == 2
    assert "report" in proc.stderr  # listed in usage


def _trace_fixture(trace_id):
    spans = [
        {"trace_id": trace_id, "span_id": 1, "parent_id": None,
         "name": "request", "t0": 100.0, "dur_sec": 0.5, "tid": 1,
         "attrs": {"outcome": "ok", "plan": {"plan_version": 1}}},
        {"trace_id": trace_id, "span_id": 2, "parent_id": 1,
         "name": "queue", "t0": 100.05, "dur_sec": 0.01, "tid": 1},
        {"trace_id": trace_id, "span_id": 3, "parent_id": 1,
         "name": "run", "t0": 100.1, "dur_sec": 0.4, "tid": 2},
    ]
    return {"kind": "trace", "trace_id": trace_id, "ts": 1.0,
            "spans": spans}


def test_trace_subcommand(tmp_path):
    """`trace` renders span trees, filters by id prefix, summarizes, and
    exports valid Chrome trace-event JSON."""
    fixture = tmp_path / "served.jsonl"
    rows = [_trace_fixture("aaaa000011112222"),
            _trace_fixture("bbbb000011112222"),
            {"kind": "step_metrics", "iterations": 5}]   # ignored
    fixture.write_text("".join(json.dumps(r) + "\n" for r in rows))

    proc = _run_cli(["trace", str(fixture)])
    assert proc.returncode == 0, proc.stderr
    assert "trace aaaa000011112222" in proc.stdout
    assert "trace bbbb000011112222" in proc.stdout
    assert "request" in proc.stdout and "queue" in proc.stdout

    proc = _run_cli(["trace", str(fixture), "--trace-id", "bbbb",
                     "--summary"])
    assert proc.returncode == 0, proc.stderr
    assert "aaaa" not in proc.stdout
    assert "root request 500.000 ms, 3 spans" in proc.stdout

    out = tmp_path / "chrome.json"
    proc = _run_cli(["trace", str(fixture), "--last", "1", "--chrome",
                     str(out)])
    assert proc.returncode == 0, proc.stderr
    assert "wrote 1 trace(s), 3 span(s)" in proc.stdout
    doc = json.loads(out.read_text())
    assert doc["displayTimeUnit"] == "ms"
    assert len(doc["traceEvents"]) == 3
    for ev in doc["traceEvents"]:
        assert ev["ph"] == "X" and ev["ts"] > 0
    root = next(ev for ev in doc["traceEvents"] if ev["name"] == "request")
    assert root["args"]["trace_id"] == "bbbb000011112222"


def test_trace_subcommand_errors(tmp_path):
    proc = _run_cli(["trace", "/nonexistent/traces.jsonl"])
    assert proc.returncode == 1
    assert "cannot read" in proc.stderr
    fixture = tmp_path / "t.jsonl"
    fixture.write_text(json.dumps(_trace_fixture("aaaa")) + "\n")
    proc = _run_cli(["trace", str(fixture), "--trace-id", "zzzz"])
    assert proc.returncode == 1
    assert "no matching" in proc.stderr


def test_report_plan_provenance_and_backfill(tmp_path):
    """Report renders resolved plan provenance on stamped rows and the
    literal `plan=unversioned` on pre-provenance rows (the backfill
    guard: absence is explicit, never faked or crashed on)."""
    fixture = tmp_path / "mixed.jsonl"
    plan = {"plan_version": 1,
            "fusion": {"solve": True, "matvec": True, "transforms": False,
                       "donate": True},
            "solve_composition": "sequential", "solve_dtype": "native",
            "spike_chunks": 0, "transpose_chunks": 2,
            "solver_key": "f760738c9e28c192"}
    rows = [
        {"kind": "step_metrics", "iterations": 5, "plan": plan},
        # a pre-PR-16 row: no plan block at all
        {"kind": "step_metrics", "iterations": 7},
        # bench-style row with provenance
        {"config": "rb256x64_tracing", "overhead_frac": 0.004,
         "plan": plan, "ts": 2.0},
    ]
    fixture.write_text("".join(json.dumps(r) + "\n" for r in rows))
    proc = _run_cli(["report", str(fixture)])
    assert proc.returncode == 0, proc.stderr
    out = proc.stdout
    assert out.count("plan[v1]: fusion=solve+matvec+donate, "
                     "solve=sequential/native, spike=0, chunks=2, "
                     "key=f760738c9e28c192") == 2
    assert out.count("plan=unversioned") == 1


def test_report_service_stats_error_codes(tmp_path):
    """The service_stats faults block's per-error-code counters render as
    a census line; uptime rides the header line."""
    fixture = tmp_path / "stats.jsonl"
    record = {"kind": "service_stats", "requests_served": 9, "errors": 3,
              "uptime_sec": 42.5,
              "pool": {"hits": 5, "misses": 4, "evictions": 1,
                       "entries": []},
              "faults": {"shed": 2, "error_codes": {"overloaded": 2,
                                                    "bad-spec": 1}}}
    fixture.write_text(json.dumps(record) + "\n")
    proc = _run_cli(["report", str(fixture)])
    assert proc.returncode == 0, proc.stderr
    assert "uptime 42.5s" in proc.stdout
    assert "error codes: 1 bad-spec, 2 overloaded" in proc.stdout


def test_report_trace_record_line(tmp_path):
    """`kind: trace` records in a telemetry file get a one-line summary
    pointing at the `trace` subcommand."""
    fixture = tmp_path / "served.jsonl"
    fixture.write_text(json.dumps(_trace_fixture("cccc000011112222"))
                       + "\n")
    proc = _run_cli(["report", str(fixture)])
    assert proc.returncode == 0, proc.stderr
    assert "(trace) cccc000011112222: root request 500.0 ms, 3 spans" \
        in proc.stdout


def test_report_ledger_rows_with_deltas(tmp_path):
    """`kind: ledger` rows render one line per program with deltas vs
    the previous round of the same (program, backend) series; rows from
    before the cost tier versioned its fields render as the literal
    `ledger=unversioned` backfill instead of crashing."""
    fixture = tmp_path / "ledger.jsonl"
    plan = {"plan_version": 1, "solve_composition": "sequential"}
    rows = [
        {"kind": "ledger", "config": "progcheck_census",
         "program": "diffusion_step", "backend": "cpu",
         "ledger_version": 1, "flops": 1000000, "bytes_accessed": 5000000,
         "peak_bytes": 2000000, "hlo_instructions": 300,
         "scan_max_length": 6, "plan": plan, "ts": 1.0},
        {"kind": "ledger", "config": "progcheck_census",
         "program": "diffusion_step", "backend": "cpu",
         "ledger_version": 1, "flops": 1200000, "bytes_accessed": 5000000,
         "peak_bytes": 2500000, "hlo_instructions": 300,
         "scan_max_length": 6, "plan": plan, "ts": 2.0},
        # a pre-cost-tier row: no ledger_version, no fields
        {"kind": "ledger", "program": "old_prog", "ts": 3.0},
    ]
    fixture.write_text("".join(json.dumps(r) + "\n" for r in rows))
    proc = _run_cli(["report", str(fixture)])
    assert proc.returncode == 0, proc.stderr
    out = proc.stdout
    assert out.count("(ledger) diffusion_step [cpu]:") == 2
    assert "flops=1,000,000" in out                  # first round, no delta
    assert "flops=1,200,000 (+20.0%)" in out         # second round delta
    assert "peak_mem=2,500,000 (+25.0%)" in out
    assert "scan_depth=6" in out
    assert "solve=sequential" in out                 # plan provenance line
    assert out.count("ledger=unversioned") == 1      # the backfill guard
    assert "3 other" in out


def test_report_perfwatch_trend_table(tmp_path):
    """With enough history in the file, report appends the perfwatch
    trend table before the summary line; a short fixture renders none
    (analyzed-series-only keeps young files quiet)."""
    fixture = tmp_path / "trend.jsonl"
    rows = [{"config": "trendcfg", "backend": "cpu", "steps_per_sec": v,
             "ts": float(i)}
            for i, v in enumerate([10.0, 10.1, 9.9, 10.0, 6.0])]
    fixture.write_text("".join(json.dumps(r) + "\n" for r in rows))
    proc = _run_cli(["report", str(fixture)])
    assert proc.returncode == 0, proc.stderr
    out = proc.stdout
    assert "perfwatch trends:" in out
    assert "steps_per_sec:trendcfg:cpu:unversioned" in out
    assert "regression" in out
    # the trend table precedes the summary line
    assert out.index("perfwatch trends:") < out.index("metrics record(s)")
    # a young file adds no table
    short = tmp_path / "short.jsonl"
    short.write_text(json.dumps(rows[0]) + "\n")
    proc = _run_cli(["report", str(short)])
    assert proc.returncode == 0, proc.stderr
    assert "perfwatch trends:" not in proc.stdout


def test_perfwatch_subprocess(tmp_path):
    """`python -m dedalus_tpu perfwatch` end to end: rc 0 + summary on a
    stable fixture, rc 1 + named finding under --check on a regressed
    one."""
    stable = tmp_path / "stable.jsonl"
    rows = [{"config": "c", "backend": "cpu", "steps_per_sec": v,
             "ts": float(i)}
            for i, v in enumerate([10.0, 10.1, 9.9, 10.0, 10.05])]
    stable.write_text("".join(json.dumps(r) + "\n" for r in rows))
    proc = _run_cli(["perfwatch", str(stable)])
    assert proc.returncode == 0, proc.stderr
    assert "1 analyzed, 0 regression(s)" in proc.stdout
    regressed = tmp_path / "regressed.jsonl"
    rows[-1]["steps_per_sec"] = 6.0
    regressed.write_text("".join(json.dumps(r) + "\n" for r in rows))
    proc = _run_cli(["perfwatch", str(regressed), "--check"])
    assert proc.returncode == 1
    assert "perfwatch regression: steps_per_sec:c:cpu:unversioned" \
        in proc.stdout

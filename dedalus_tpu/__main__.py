"""
Command-line interface (reference: dedalus/__main__.py:1-45), argparse
subcommands — `python -m dedalus_tpu <command> --help` documents each:

    test          run the tier-1 test suite
    cov           test suite under coverage
    bench         run the benchmark (bench.py)
    get_config    print the resolved configuration
    get_examples  print the examples directory
    report        summarize a metrics/results JSONL file
    postmortem    summarize a health post-mortem directory
    lint          static analysis: AST jit-hygiene rules, and the
                  compiled-program contract census under --programs
    serve         warm-pool solver daemon (dedalus_tpu/service/)
    submit        submit one run to a serve daemon
    route         spec-hash router fronting a replica fleet
"""

import argparse
import json
import pathlib
import sys


def test(args=None):
    import pytest
    # fail fast on a missing/stale lint baseline: tests/test_lint.py would
    # fail anyway, but only after the whole suite ran — and a stale
    # baseline usually means a fixed hazard whose grandfathering should be
    # dropped in the SAME commit
    from .tools.lint import check_baseline_fresh
    problems = check_baseline_fresh()
    if problems:
        for problem in problems:
            print(f"test: {problem}", file=sys.stderr)
        sys.exit(1)
    root = pathlib.Path(__file__).parent.parent
    # tier-1 semantics: slow-marked tests (long timing runs) are opt-in
    # via pytest directly; chaos-marked fault-injection tests
    # (tests/test_resilience.py) and service-marked daemon tests
    # (tests/test_service.py) are fast and run by default — recovery and
    # serving paths that are not exercised do not exist
    sys.exit(pytest.main([str(root / "tests"), "-q", "-m", "not slow"]))


def bench(args=None):
    import runpy
    root = pathlib.Path(__file__).parent.parent
    bench_path = root / "bench.py"
    if not bench_path.exists():
        print("bench.py not found next to the package", file=sys.stderr)
        sys.exit(1)
    runpy.run_path(str(bench_path), run_name="__main__")


def cov(args=None):
    """Test suite under coverage (reference: dedalus/tests/__init__.py:30
    cov). Requires the `coverage` package. Runs in a fresh interpreter so
    coverage measures modules imported by the package itself (starting
    coverage after this import would under-report __init__/tools)."""
    try:
        import coverage  # noqa: F401
    except ImportError:
        print("cov requires the 'coverage' package (pip install coverage)",
              file=sys.stderr)
        sys.exit(1)
    import subprocess
    root = pathlib.Path(__file__).parent.parent
    rc = subprocess.run(
        [sys.executable, "-m", "coverage", "run", "--source=dedalus_tpu",
         "-m", "pytest", str(root / "tests"), "-q", "-m", "not slow"],
        cwd=root).returncode
    subprocess.run([sys.executable, "-m", "coverage", "report"], cwd=root)
    sys.exit(rc)


def get_config(args=None):
    from .tools.config import config
    config.write(sys.stdout)


def get_examples(args=None):
    root = pathlib.Path(__file__).parent.parent / "examples"
    print(root)


def _format_plan(record):
    """One-line resolved-plan provenance for a metrics/bench row. Rows
    written before plan stamping existed (PR 16) carry no `plan` block
    and must still render — as the literal `plan=unversioned` — rather
    than crash or silently vanish."""
    plan = record.get("plan")
    if not isinstance(plan, dict):
        return "plan=unversioned"
    parts = []
    fusion = plan.get("fusion")
    if isinstance(fusion, dict):
        on = "+".join(k for k in ("solve", "matvec", "transforms",
                                  "donate", "pallas")
                      if fusion.get(k)) or "off"
        parts.append(f"fusion={on}")
    if plan.get("solve_composition"):
        solve = str(plan["solve_composition"])
        if plan.get("solve_dtype"):
            solve += f"/{plan['solve_dtype']}"
        parts.append(f"solve={solve}")
    if plan.get("refine_sweeps") is not None:
        parts.append(f"sweeps={plan['refine_sweeps']}")
    if plan.get("spike_chunks") is not None:
        parts.append(f"spike={plan['spike_chunks']}")
    if plan.get("transpose_chunks") is not None:
        parts.append(f"chunks={plan['transpose_chunks']}")
    if plan.get("solver_key"):
        parts.append(f"key={plan['solver_key']}")
    # how the plan was chosen; rows from before plan_source existed
    # simply omit the column
    if plan.get("plan_source"):
        parts.append(f"source={plan['plan_source']}")
    return (f"plan[v{plan.get('plan_version', '?')}]: "
            + (", ".join(parts) or "(empty)"))


def report(args):
    """Summarize a metrics JSONL file (tools/metrics.py records; bench rows
    from benchmarks/results.jsonl listed briefly; health post-mortem and
    service records get their own lines). Tolerates heterogeneous rows —
    records from before any given key existed print with defaults rather
    than crashing. `--last N` restricts to the N most recent parsable
    rows."""
    from .tools.metrics import format_build_phases, format_phase_table
    path = pathlib.Path(args.jsonl)
    try:
        lines = path.read_text().splitlines()
    except OSError as exc:
        print(f"report: cannot read {path}: {exc}", file=sys.stderr)
        sys.exit(1)
    records = []
    n_bad = 0
    for line in lines:
        line = line.strip()
        if not line:
            continue
        try:
            record = json.loads(line)
        except ValueError:
            n_bad += 1
            continue
        if not isinstance(record, dict):
            n_bad += 1
            continue
        records.append(record)
    if args.last is not None:
        records = records[-args.last:] if args.last > 0 else []
    n_metrics = n_post = n_other = 0
    prev_ledger = {}      # (program, backend) -> previous ledger row
    for record in records:
        kind = record.get("kind")
        if kind == "step_metrics":
            n_metrics += 1
            ident = " ".join(
                f"{k}={record[k]}" for k in ("config", "backend", "dtype")
                if record.get(k) is not None)
            print(f"[{n_metrics}] {ident or 'step_metrics'}: "
                  f"{record.get('iterations', 0)} iters, "
                  f"{record.get('steps_per_sec', 0.0)} steps/s, "
                  f"{record.get('phase_samples', 0)} samples "
                  f"(cadence {record.get('sample_cadence', '?')})")
            # format_phase_table's first line repeats the sample count
            # already printed in the header above
            for tline in format_phase_table(record, indent="    ")[1:]:
                print(tline)
            for tline in format_build_phases(record.get("build_phases"),
                                             indent="    "):
                print(tline)
            health = record.get("health")
            if isinstance(health, dict):
                status = "ok" if health.get("ok", True) else \
                    f"FAILED: {health.get('reason', '?')}"
                print(f"    health: {status}, "
                      f"{health.get('checks', 0)} checks, "
                      f"{health.get('warnings', 0)} warnings")
            ensemble = record.get("ensemble")
            if isinstance(ensemble, dict):
                parts = [f"{ensemble.get('members', '?')} members",
                         f"{ensemble.get('active', '?')} active",
                         f"{ensemble.get('dropped', 0)} dropped"]
                if ensemble.get("rewinds"):
                    parts.append(f"{ensemble['rewinds']} rewinds")
                if ensemble.get("reshards"):
                    parts.append(f"{ensemble['reshards']} reshards")
                parts.append(
                    f"{ensemble.get('ensemble_steps_per_sec', 0.0)} "
                    f"member-steps/s")
                if ensemble.get("devices"):
                    parts.append(f"{ensemble['devices']} device(s)")
                print(f"    ensemble: {', '.join(parts)}")
                if ensemble.get("dropped_members"):
                    print(f"    dropped members: "
                          f"{ensemble['dropped_members']}")
            resilience = record.get("resilience")
            if isinstance(resilience, dict):
                parts = [f"{resilience.get('rewinds', 0)} rewinds",
                         f"{resilience.get('retries', 0)} retries"]
                if resilience.get("dt_limit") is not None:
                    parts.append(f"dt capped {resilience['dt_limit']}")
                if resilience.get("stopped_by"):
                    parts.append(f"stopped by {resilience['stopped_by']}")
                if resilience.get("resumed_from"):
                    parts.append(
                        f"resumed from {resilience['resumed_from']} "
                        f"(write {resilience.get('resume_write', '?')})")
                if resilience.get("sdc_checks") is not None:
                    # the SDC sentinel trajectory: checks run / silent
                    # corruptions caught (tools/resilience.py)
                    parts.append(f"sdc {resilience.get('sdc_detected', 0)}"
                                 f"/{resilience['sdc_checks']}")
                print(f"    resilience: {', '.join(parts)}")
                ckpt = resilience.get("checkpoint")
                if isinstance(ckpt, dict):
                    # durable-checkpoint stall column: format (+async),
                    # cumulative step-loop stall, writes landed
                    line = (f"    checkpoint: {ckpt.get('format', '?')}"
                            f"{'+async' if ckpt.get('async') else ''}, "
                            f"stall {ckpt.get('stall_sec', 0.0)}s")
                    if ckpt.get("written") is not None:
                        line += f", {ckpt['written']} written"
                    if ckpt.get("max_inflight"):
                        line += (f", max in-flight "
                                 f"{ckpt['max_inflight']}")
                    if ckpt.get("errors"):
                        line += f", {ckpt['errors']} ERRORS"
                    print(line)
            adjoint = record.get("adjoint")
            if isinstance(adjoint, dict):
                # differentiable-solve telemetry (core/adjoint.py):
                # grad throughput, remat segments, memory
                parts = [f"{adjoint.get('grad_calls', 0)} grad calls",
                         f"{adjoint.get('grad_steps_per_sec', '?')} "
                         f"grad-steps/s"]
                if adjoint.get("grad_forward_ratio") is not None:
                    parts.append(f"{adjoint['grad_forward_ratio']}x "
                                 "forward cost")
                if adjoint.get("checkpoint_segments") is not None:
                    parts.append(
                        f"{adjoint['checkpoint_segments']} segments")
                mem = adjoint.get("device_mem_peak_bytes")
                if mem:
                    parts.append(f"peak {mem / 1e9:.3f} GB")
                if adjoint.get("wrt"):
                    parts.append(f"wrt={','.join(adjoint['wrt'])}")
                print(f"    adjoint: {', '.join(parts)}")
            serving = record.get("serving")
            if isinstance(serving, dict):
                # served-latency columns (dedalus_tpu/service/): the pool
                # verdict and time-to-first-step ARE the serving story
                parts = [f"pool={serving.get('pool_verdict', '?')}",
                         f"queue={serving.get('queue_sec', '?')}s",
                         f"ttfs={serving.get('time_to_first_step_sec')}s"]
                if serving.get("build_sec"):
                    parts.append(f"build={serving['build_sec']}s")
                if serving.get("deadline_sec") is not None:
                    parts.append(f"deadline={serving['deadline_sec']}s")
                if serving.get("request_id"):
                    parts.append(f"request={serving['request_id']}")
                batch = serving.get("batch")
                if isinstance(batch, dict):
                    parts.append(
                        f"batch={batch.get('id', '?')}"
                        f"#{batch.get('seat', '?')}"
                        + (" (late join)" if batch.get("late_join")
                           else ""))
                print(f"    serving: {', '.join(parts)}")
            print(f"    {_format_plan(record)}")
        elif kind == "health_postmortem":
            n_post += 1
            resilience = record.get("resilience")
            lineage = ""
            if isinstance(resilience, dict) and resilience.get("retries"):
                lineage = (f" (retry {resilience['retries']}, "
                           f"{resilience.get('rewinds', 0)} rewinds)")
            print(f"(postmortem) iter={record.get('iteration', '?')} "
                  f"sim_time={record.get('sim_time', '?')}: "
                  f"{record.get('reason', '(no reason)')}{lineage}"
                  + (f" [{record.get('directory')}]"
                     if record.get("directory") else ""))
        elif kind == "service_stats":
            n_other += 1
            pool = record.get("pool") or {}
            print(f"(service) {record.get('requests_served', 0)} requests, "
                  f"{record.get('errors', 0)} errors, "
                  f"pool {pool.get('hits', 0)} hits / "
                  f"{pool.get('misses', 0)} misses / "
                  f"{pool.get('evictions', 0)} evictions, "
                  f"{len(pool.get('entries', []))} warm entr(ies), "
                  f"uptime {record.get('uptime_sec', '?')}s")
            faults = record.get("faults") or {}
            if faults:
                # the fault-tolerance trajectory (service/faults.py):
                # shed/deadline/watchdog/drop/replay + breaker counters
                breaker = faults.get("breaker") or {}
                line = (f"    faults: {faults.get('shed', 0)} shed, "
                        f"{faults.get('deadline_exceeded', 0)} "
                        "deadline-exceeded, "
                        f"{faults.get('watchdog_fires', 0)} watchdog, "
                        f"{faults.get('client_drops', 0)} client drops, "
                        f"{faults.get('replays', 0)} replays, "
                        f"breaker {breaker.get('opens', 0)} opens / "
                        f"{breaker.get('fastfails', 0)} fast-fails")
                if faults.get("mem_evictions"):
                    line += (f", {faults['mem_evictions']} "
                             "memory evictions")
                if breaker.get("open"):
                    line += f", OPEN circuits: {breaker['open']}"
                print(line)
                codes = faults.get("error_codes") or {}
                if codes:
                    # per-error-code refusal census (server._send_error):
                    # which failure mode dominates, at a glance
                    print("    error codes: "
                          + ", ".join(f"{v} {k}"
                                      for k, v in sorted(codes.items())))
            batching = (record.get("serving") or {}).get("batching") or {}
            if batching.get("enabled"):
                # continuous-batching occupancy (service/batching.py):
                # how full the micro-batches actually ran, and why
                # members left them
                det = batching.get("detached") or {}
                det_txt = ", ".join(f"{v} {k}"
                                    for k, v in sorted(det.items())) \
                    or "none"
                print(f"    batching: {batching.get('batches', 0)} "
                      f"batches, {batching.get('members', 0)} members "
                      f"({batching.get('late_joins', 0)} late joins), "
                      f"peak {batching.get('peak_members', 0)}"
                      f"/{batching.get('batch_max', '?')} seats, "
                      f"{batching.get('blocks', 0)} blocks, "
                      f"detached: {det_txt}")
                for ev in batching.get("recent_batches") or []:
                    det = ev.get("detached") or {}
                    det_txt = ", ".join(
                        f"{v} {k}" for k, v in sorted(det.items())) \
                        or "none"
                    print(f"      {ev.get('batch_id', '?')} "
                          f"[{ev.get('spec', '?')}]: "
                          f"{ev.get('members', 0)} members "
                          f"({ev.get('late_joins', 0)} late), peak "
                          f"{ev.get('peak_active', 0)} active, "
                          f"{ev.get('blocks', 0)} blocks, {det_txt}"
                          + (" [ABANDONED]" if ev.get("abandoned")
                             else ""))
        elif kind == "router_stats":
            n_other += 1
            router = record.get("router") or {}
            fleet = record.get("fleet") or {}
            forward = router.get("forward") or {}
            ring = router.get("ring_members") or []
            print(f"(router) {router.get('forwarded', 0)} forwarded, "
                  f"{router.get('failovers', 0)} failovers, "
                  f"{router.get('shed', 0)} shed, "
                  f"{router.get('refusals', 0)} refusals absorbed, "
                  f"ring [{', '.join(ring) or 'empty'}], "
                  f"forward p50 {forward.get('p50_ms', '?')} ms / "
                  f"p95 {forward.get('p95_ms', '?')} ms, "
                  f"uptime {record.get('uptime_sec', '?')}s")
            # fleet health census (service/fleet.py): one line per
            # replica so a wedged or flapping member reads off directly
            if fleet:
                print(f"    fleet: {fleet.get('restarts', 0)} restarts, "
                      f"{fleet.get('crashes', 0)} crashes, "
                      f"{fleet.get('wedges', 0)} wedges, "
                      f"{fleet.get('watchdog_fires', 0)} watchdog "
                      "postmortems")
                for name, rep in sorted(
                        (fleet.get("replicas") or {}).items()):
                    state = rep.get("state", "?")
                    if rep.get("draining"):
                        state += " (draining)"
                    print(f"      {name}: {state}, "
                          f"{rep.get('restarts', 0)} restarts, "
                          f"port {rep.get('port', '?')}"
                          + (f", pid {rep['pid']}"
                             if rep.get("pid") else ""))
            codes = router.get("error_codes") or {}
            if codes:
                print("    error codes: "
                      + ", ".join(f"{v} {k}"
                                  for k, v in sorted(codes.items())))
        elif kind == "trace":
            n_other += 1
            from .tools.tracing import summarize_trace
            summary = summarize_trace(record)
            print(f"(trace) {summary['trace_id']}: "
                  f"root {summary['root'] or '?'} "
                  f"{round((summary['root_sec'] or 0.0) * 1e3, 3)} ms, "
                  f"{summary['spans']} spans "
                  f"(`python -m dedalus_tpu trace` for the span tree)")
        elif kind == "watchdog_postmortem":
            n_post += 1
            stacks = record.get("stacks") or []
            print(f"(watchdog) request={record.get('request_id', '?')} "
                  f"stuck {record.get('stuck_sec', '?')}s "
                  f"(limit {record.get('watchdog_sec', '?')}s) at "
                  f"iter={record.get('iteration', '?')}, "
                  f"{len(stacks)} thread stack(s) recorded")
            # held-locks map beside the stacks: recorded only when the
            # daemon ran with the lock-order sanitizer on ([sanitize]
            # LOCK_ORDER) — on a deadlock postmortem this names the lock
            # each thread is blocked on, not just the frame it sits in
            for tname, locks in sorted(
                    (record.get("held_locks") or {}).items()):
                held = ", ".join(locks.get("held") or []) or "none"
                waiting = locks.get("waiting")
                print(f"    locks[{tname}]: held {held}"
                      + (f"; waiting on {waiting}" if waiting else ""))
        elif kind == "ledger":
            # resource-ledger rows (tools/lint/progcheck.py cost tier):
            # one line per census program with deltas against the
            # previous round of the same (program, backend) series, so
            # compile-cost creep reads off the report directly
            n_other += 1
            program = record.get("program") or "?"
            series = (program, record.get("backend"))
            prev = prev_ledger.get(series) or {}
            prev_ledger[series] = record
            if record.get("ledger_version") is None:
                # a row written before the cost tier versioned its
                # fields must render, not crash (mirrors the
                # plan=unversioned backfill rule)
                print(f"(ledger) {program}: ledger=unversioned")
                continue
            cells = []
            for key, label in (("flops", "flops"),
                               ("bytes_accessed", "bytes"),
                               ("peak_bytes", "peak_mem"),
                               ("hlo_instructions", "hlo"),
                               ("scan_max_length", "scan_depth")):
                value = record.get(key)
                if value is None:
                    continue
                cell = f"{label}={value:,}" if isinstance(value, int) \
                    else f"{label}={value}"
                before = prev.get(key)
                if isinstance(before, (int, float)) \
                        and not isinstance(before, bool) and before:
                    delta = 100.0 * (value - before) / before
                    cell += f" ({delta:+.1f}%)"
                cells.append(cell)
            print(f"(ledger) {program} "
                  f"[{record.get('backend') or '?'}]: "
                  + (", ".join(cells) or "no cost data"))
            print(f"    {_format_plan(record)}")
        else:
            n_other += 1
            ident = record.get("metric") or record.get("config") or "record"
            val = record.get("value")
            unit = record.get("unit", "")
            extra = f" = {val} {unit}".rstrip() if val is not None else ""
            stale = " [stale]" if record.get("stale") else ""
            print(f"(other) {ident}{extra}{stale}")
            print(f"    {_format_plan(record)}")
            # ensemble benchmark rows (benchmarks/ensemble.py): one line
            # per sweep point so speedups read without opening the JSONL
            sweep = record.get("sweep")
            if isinstance(sweep, list) and sweep \
                    and isinstance(sweep[0], dict) \
                    and "ensemble_steps_per_sec" in sweep[0]:
                serial = record.get("serial") or {}
                if serial.get("steps_per_sec") is not None:
                    print(f"    serial baseline: "
                          f"{serial['steps_per_sec']} steps/s")
                for point in sweep:
                    print(f"    N={point.get('members', '?')}: "
                          f"{point.get('ensemble_steps_per_sec', '?')} "
                          f"member-steps/s "
                          f"({point.get('speedup_vs_serial', '?')}x serial,"
                          f" {point.get('devices', '?')} device(s))")
            # weak-scaling rows (benchmarks/scaling.py): steps/s per
            # device count with the transpose overlap phase split, the
            # chunked-vs-monolithic guard, north star, and the 2-D
            # batch x pencil fleet bit-match
            if record.get("benchmark") == "scaling" \
                    and isinstance(record.get("sweep"), list):
                for point in record["sweep"]:
                    line = (f"    d={point.get('devices', '?')} "
                            f"{'x'.join(str(s) for s in point.get('shape', []))}: "
                            f"{point.get('steps_per_sec', '?')} steps/s")
                    if point.get("transpose_exposed_sec") is not None:
                        line += (f", transpose exposed "
                                 f"{point['transpose_exposed_sec']}s / "
                                 f"overlapped "
                                 f"{point.get('transpose_overlapped_sec', '?')}s")
                    if point.get("all_gathers") is not None:
                        line += (f", {point.get('all_to_alls', '?')} a2a / "
                                 f"{point['all_gathers']} gathers")
                    print(line)
                guard = record.get("chunked_vs_mono")
                if isinstance(guard, dict):
                    print(f"    chunked({record.get('chunks', '?')}) vs "
                          f"mono: {guard.get('chunked_steps_per_sec', '?')} "
                          f"vs {guard.get('mono_steps_per_sec', '?')} "
                          f"steps/s ({guard.get('ratio', '?')}x, "
                          f"bit_identical="
                          f"{guard.get('bit_identical', '?')})")
                ns = record.get("northstar")
                if isinstance(ns, dict) and ns.get("steps_per_sec"):
                    print(f"    north star "
                          f"{'x'.join(str(s) for s in ns.get('shape', []))}"
                          f" on {ns.get('devices', '?')} devices: "
                          f"{ns['steps_per_sec']} steps/s "
                          f"(finite={ns.get('finite', '?')})")
                fleet = record.get("fleet2d")
                if isinstance(fleet, dict):
                    print(f"    2-D fleet {fleet.get('members', '?')} "
                          f"members on "
                          f"{'x'.join(str(s) for s in fleet.get('mesh', []))}"
                          f" batch x pencil: bit_match_1d="
                          f"{fleet.get('bit_match_1d', '?')}")
            # fusion benchmark rows (benchmarks/fusion.py): fused vs
            # unfused steps/s and the documented trajectory tolerance
            if record.get("fusion_speedup") is not None:
                plan = record.get("fusion") or {}
                on = "+".join(k for k in ("solve", "matvec", "transforms",
                                          "donate", "pallas")
                              if plan.get(k)) or "off"
                print(f"    fusion: "
                      f"{record.get('steps_per_sec_unfused', '?')} -> "
                      f"{record.get('steps_per_sec_fused', '?')} steps/s "
                      f"({record.get('fusion_speedup', '?')}x, {on}; "
                      f"state rel diff "
                      f"{record.get('state_rel_diff', '?')})")
            # solve-composition sweep rows (benchmarks/fusion.py
            # run_solve_sweep): per-cell steps/s + accuracy, and the
            # two acceptance bars in one summary line
            if record.get("benchmark") == "solvecomp" \
                    and isinstance(record.get("sweep"), list):
                for cell in record["sweep"]:
                    line = (f"    {cell.get('composition', '?')}/"
                            f"{cell.get('solve_dtype', '?')}: "
                            f"{cell.get('steps_per_sec', '?')} steps/s")
                    if cell.get("baseline"):
                        line += " (baseline)"
                    else:
                        line += (f" ({cell.get('speedup', '?')}x, err "
                                 f"{cell.get('state_rel_err', '?')})")
                    if cell.get("achieved_residual") is not None:
                        line += (f", resid {cell['achieved_residual']:.1e}"
                                 f" @ {cell.get('refine_sweeps', '?')} "
                                 "sweep(s)")
                    print(line)
                best = record.get("best_f64_accurate")
                ladder = record.get("ladder")
                if best:
                    print(f"    best f64-accurate: {best['composition']}/"
                          f"{best['solve_dtype']} {best.get('speedup', '?')}x"
                          f" (meets_1p15x={record.get('meets_1p15x', '?')})")
                if ladder:
                    print(f"    ladder: {ladder['composition']}/"
                          f"{ladder['solve_dtype']} "
                          f"{ladder.get('speedup', '?')}x, state err "
                          f"{ladder.get('state_rel_err', '?')} "
                          f"(meets_1e10="
                          f"{record.get('ladder_meets_1e10', '?')})")
            # serving benchmark rows (benchmarks/serving.py): the cold-
            # miss vs warm-hit time-to-first-step comparison in one line
            if record.get("ttfs_cold_sec") is not None \
                    or record.get("ttfs_warm_sec") is not None:
                line = (f"    serving: ttfs cold "
                        f"{record.get('ttfs_cold_sec', '?')}s -> warm "
                        f"{record.get('ttfs_warm_sec', '?')}s "
                        f"({record.get('ttfs_speedup', '?')}x)")
                if record.get("throughput_requests_per_sec") is not None:
                    line += (f", {record['throughput_requests_per_sec']} "
                             "requests/s")
                print(line)
            # adjoint benchmark rows (benchmarks/adjoint.py): the grad/
            # forward cost ratio and the segment-memory sweep in one block
            if record.get("grad_forward_ratio") is not None:
                line = (f"    adjoint: grad "
                        f"{record.get('grad_steps_per_sec', '?')} steps/s "
                        f"vs forward "
                        f"{record.get('forward_steps_per_sec', '?')} "
                        f"steps/s ({record['grad_forward_ratio']}x)")
                if record.get("fd_rel_err") is not None:
                    line += f", fd_rel={record['fd_rel_err']:.1e}"
                print(line)
                for point in record.get("segments_sweep") or []:
                    if point.get("error"):
                        print(f"      K={point.get('segments', '?')}: "
                              f"{point['error']}")
                        continue
                    rss = point.get("peak_rss_bytes")
                    line = (f"      K={point.get('segments', '?')}: "
                            f"{point.get('grad_steps_per_sec', '?')} "
                            f"grad-steps/s")
                    if rss:
                        line += f", peak RSS {rss / 1e6:.1f} MB"
                    print(line)
            # checkpoint benchmark rows (benchmarks/checkpointing.py):
            # per-checkpoint step-loop stall by mode + fault-restore wall
            if record.get("stall_async_sharded_sec") is not None:
                line = (f"    checkpoint: stall hdf5 "
                        f"{record.get('stall_sync_hdf5_sec', '?')}s / "
                        f"sharded {record.get('stall_sync_sharded_sec', '?')}"
                        f"s / async {record['stall_async_sharded_sec']}s"
                        f" ({record.get('stall_reduction_async_vs_hdf5', '?')}"
                        f"x less stall)")
                if record.get("restore_after_fault_sec") is not None:
                    line += (f", restore-after-fault "
                             f"{record['restore_after_fault_sec']}s")
                print(line)
            # continuous-batching benchmark rows (benchmarks/serving.py
            # run_batching): the requests/s multiplier in one line
            if record.get("requests_speedup") is not None:
                print(f"    batching: "
                      f"{record.get('batched_requests_per_sec', '?')} "
                      f"vs {record.get('baseline_requests_per_sec', '?')}"
                      f" requests/s ({record['requests_speedup']}x, "
                      f"{record.get('clients', '?')} clients, "
                      f"{record.get('batches', '?')} batches, "
                      f"{record.get('late_joins', '?')} late joins, "
                      f"peak {record.get('peak_batch_members', '?')} "
                      "seats)")
            # overload benchmark rows (benchmarks/serving.py storm): the
            # shed-rate and bounded-latency story in one line
            if record.get("shed_rate") is not None:
                shed_pct = round(100.0 * record["shed_rate"], 1)
                line = (f"    overload: {record.get('storm_rate_x', '?')}x "
                        f"capacity storm, {shed_pct}% shed, accepted p50 "
                        f"{record.get('accepted_p50_sec', '?')}s / p95 "
                        f"{record.get('accepted_p95_sec', '?')}s "
                        f"(bound {record.get('latency_bound_sec', '?')}s), "
                        f"{record.get('daemon_restarts', '?')} daemon "
                        "restarts")
                if record.get("max_queued_observed") is not None:
                    line += (f", max queued "
                             f"{record['max_queued_observed']}"
                             f"/{record.get('queue_depth', '?')}")
                print(line)
            # replica-fleet scaling rows (benchmarks/serving.py
            # run_router_scaling): aggregate requests/s per replica
            # count plus the routing tax, in one line
            if record.get("requests_speedup_4v1") is not None:
                sweep = record.get("replica_requests_per_sec") or {}
                sweep_txt = ", ".join(
                    f"{n}r={v}" for n, v in sorted(sweep.items()))
                print(f"    router: {sweep_txt} requests/s "
                      f"({record['requests_speedup_4v1']}x at 4 "
                      f"replicas, {record.get('specs', '?')} specs, "
                      f"{record.get('clients', '?')} clients, forward "
                      f"overhead p50 "
                      f"{record.get('forward_overhead_p50_ms', '?')} ms)")
    # perf-trajectory trend table (tools/perfwatch.py): only series with
    # enough history to analyze render, so short fixture files and fresh
    # sinks add nothing here
    try:
        from .tools import perfwatch
        trends = perfwatch.trend_lines(records)
    except Exception:
        trends = []
    if trends:
        print("perfwatch trends:")
        for tline in trends:
            print(f"    {tline}")
    print(f"{n_metrics} metrics record(s), {n_other} other, "
          f"{n_post} postmortem, {n_bad} unparsable")
    if n_metrics == 0 and n_other == 0 and n_post == 0:
        sys.exit(1)


def trace(args):
    """Inspect request traces (tools/tracing.py records, written by
    `serve --trace` or the metrics sink): indented span trees by default,
    `--chrome OUT` exports Chrome trace-event JSON for Perfetto /
    chrome://tracing, `--summary` one line per trace."""
    from .tools import tracing
    try:
        records = tracing.load_trace_records(args.jsonl)
    except OSError as exc:
        print(f"trace: cannot read {args.jsonl}: {exc}", file=sys.stderr)
        sys.exit(1)
    if args.trace_id:
        records = [r for r in records
                   if str(r.get("trace_id", "")).startswith(args.trace_id)]
    if args.last is not None:
        records = records[-args.last:] if args.last > 0 else []
    if not records:
        print("trace: no matching trace records", file=sys.stderr)
        sys.exit(1)
    if args.chrome:
        out = pathlib.Path(args.chrome)
        out.write_text(json.dumps(tracing.chrome_trace_from_records(records)))
        total = sum(len(r.get("spans", [])) for r in records)
        print(f"wrote {len(records)} trace(s), {total} span(s) -> {out}")
        return
    for record in records:
        if args.summary:
            summary = tracing.summarize_trace(record)
            top = ", ".join(
                f"{name} {sec * 1e3:.3f}ms"
                for name, sec in list(summary["by_name"].items())[:4])
            print(f"{summary['trace_id']}: "
                  f"root {summary['root'] or '?'} "
                  f"{(summary['root_sec'] or 0.0) * 1e3:.3f} ms, "
                  f"{summary['spans']} spans ({top})")
        else:
            for line in tracing.format_trace_tree(record):
                print(line)


def postmortem(args):
    """Summarize a health flight-recorder dump (tools/health.py): accepts
    the post-mortem directory or a record file inside it."""
    from .tools.health import read_postmortem, format_postmortem
    path = pathlib.Path(args.directory)
    try:
        record, ring = read_postmortem(path)
    except (OSError, ValueError) as exc:
        print(f"postmortem: cannot read {path}: {exc}", file=sys.stderr)
        sys.exit(1)
    for line in format_postmortem(record, ring):
        print(line)


def lint(argv):
    """Static analysis (tools/lint): the DTL AST rule set plus, under
    --programs, the DTP compiled-program contract census
    (tools/lint/progcheck.py — collective placement, donation aliasing,
    forbidden primitives, manual-region integrity over the lowered
    step/fleet/grad programs; CPU-only). Nonzero exit on findings not
    covered by the per-tier baseline."""
    from .tools.lint.cli import main as lint_main
    sys.exit(lint_main(argv))


def perfwatch(argv):
    """Perf-trajectory regression sentinel (tools/perfwatch.py): noise-
    banded trend analysis over benchmarks/results.jsonl; `--check` exits
    nonzero on an unwaived regression."""
    from .tools.perfwatch import main as perfwatch_main
    sys.exit(perfwatch_main(argv))


def serve(argv):
    """Warm-pool solver daemon (dedalus_tpu/service/server.py)."""
    from .service.server import main as serve_main
    sys.exit(serve_main(argv))


def submit(argv):
    """Submit one run to a serve daemon (dedalus_tpu/service/client.py)."""
    from .service.client import main as submit_main
    sys.exit(submit_main(argv))


def route(argv):
    """Spec-hash router fronting a SolverService replica fleet
    (dedalus_tpu/service/router.py; docs/serving.md#replica-fleet)."""
    from .service.router import main as route_main
    sys.exit(route_main(argv))


# Subcommands that own their whole argument surface (each has its own
# argparse parser, including --help): dispatched BEFORE the top-level
# parser sees the argv tail — argparse's REMAINDER does not reliably
# capture leading options like `--help`, so forwarding must bypass it.
PASSTHROUGH = {"lint": lint, "perfwatch": perfwatch, "serve": serve,
               "submit": submit, "route": route}


def build_parser():
    doc_lines = (__doc__ or "").strip().splitlines()
    parser = argparse.ArgumentParser(
        prog="python -m dedalus_tpu",
        # docstrings are stripped under -OO: fall back rather than index
        description=doc_lines[0] if doc_lines
        else "dedalus_tpu command-line interface")
    sub = parser.add_subparsers(dest="command", metavar="command",
                                required=True)
    sub.add_parser("test", help="run the tier-1 test suite "
                                "(slow-marked tests excluded)"
                   ).set_defaults(func=test)
    sub.add_parser("bench", help="run the benchmark (bench.py)"
                   ).set_defaults(func=bench)
    sub.add_parser("cov", help="test suite under coverage"
                   ).set_defaults(func=cov)
    sub.add_parser("get_config", help="print the resolved configuration"
                   ).set_defaults(func=get_config)
    sub.add_parser("get_examples", help="print the examples directory"
                   ).set_defaults(func=get_examples)
    p = sub.add_parser("report", help="summarize a metrics/results JSONL "
                                      "file (tools/metrics.py records)")
    p.add_argument("jsonl", help="path to the JSONL file")
    p.add_argument("--last", type=int, default=None, metavar="N",
                   help="only the N most recent parsable rows")
    p.set_defaults(func=report)
    p = sub.add_parser("trace", help="inspect request traces "
                                     "(span trees, Chrome JSON export)")
    p.add_argument("jsonl", help="trace/metrics JSONL file "
                                 "(serve --trace output or telemetry sink)")
    p.add_argument("--trace-id", default=None, metavar="PREFIX",
                   help="only traces whose id starts with PREFIX")
    p.add_argument("--last", type=int, default=None, metavar="N",
                   help="only the N most recent matching traces")
    p.add_argument("--chrome", default=None, metavar="OUT",
                   help="write Chrome trace-event JSON (Perfetto / "
                        "chrome://tracing) instead of printing trees")
    p.add_argument("--summary", action="store_true",
                   help="one line per trace instead of the span tree")
    p.set_defaults(func=trace)
    p = sub.add_parser("postmortem", help="summarize a health post-mortem "
                                          "dump (tools/health.py)")
    p.add_argument("directory", help="post-mortem directory or record file")
    p.set_defaults(func=postmortem)
    # pass-through subcommands: listed here so the top-level --help names
    # them, but main() dispatches them before this parser ever runs
    for name, helptext in (
            ("lint", "static analysis (DTL AST rules; DTP program "
                     "contracts via --programs); see `lint --help`"),
            ("perfwatch", "perf-trajectory regression sentinel over "
                          "benchmarks/results.jsonl; see "
                          "`perfwatch --help`"),
            ("serve", "warm-pool solver daemon (docs/serving.md); "
                      "see `serve --help`"),
            ("submit", "submit one run to a serve daemon; "
                       "see `submit --help`"),
            ("route", "spec-hash router fronting a replica fleet "
                      "(docs/serving.md#replica-fleet); see "
                      "`route --help`")):
        sub.add_parser(name, help=helptext, add_help=False)
    return parser


def main():
    if len(sys.argv) > 1 and sys.argv[1] in PASSTHROUGH:
        PASSTHROUGH[sys.argv[1]](sys.argv[2:])
        return
    args = build_parser().parse_args()
    args.func(args)


if __name__ == "__main__":
    main()

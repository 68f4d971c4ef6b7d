"""
Run one cell of BENCHMARK.json once.

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, which holds the chip. It prints platform, device kind and
count first and FAILS without a TPU (or with fewer chips than the cell asks
for): no result line, exit code 1. Then: the float64 CPU reference (a child
process, before this one imports JAX; cached), build, warm-up of this
cell's own programs, the comparison with the reference, the window of
`--seconds`, the invariants. The last line of stdout is one JSON object
with `correct`, `attempted`, `failed`, `metrics`, `device` (and `breakdown`
with --trace 1) and no other key. With --trace 0 the metrics are the cell's
end-to-end metrics; with --trace 1 the harness traces a short window of its
own and the metrics are the cell's per-layer metrics.

`--rehearse-cpu` is the only way to run without a chip: it needs
JAX_PLATFORMS=cpu, runs the configuration's `rehearsal` size, labels the
device `cpu` and never reports `correct: true`. Its numbers are not device
numbers.
"""

import os
import sys
import time

_IMPORTED_AT = time.time()
MARK_EVERY = 5.0    # seconds between the progress marks of the checks line


def process_start_time():
    """Wall-clock time at which this process started (Linux /proc), so
    that setup_s counts the interpreter's own start; the import of this
    module where /proc cannot say."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - ticks / os.sysconf("SC_CLK_TCK")
        if 0 <= age < 3600:
            return time.time() - age
    except (OSError, ValueError, IndexError):
        pass
    return _IMPORTED_AT


def say(text):
    print(text, flush=True)


def main(argv=None):
    import argparse

    started = process_start_time()
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse-cpu", action="store_true")
    parser.add_argument("--keep-trace", default=None, metavar="DIR",
                        help="copy the raw .xplane.pb of a traced run here")
    args = parser.parse_args(argv)

    import importlib.util
    from . import reference
    from .manifest import ROOT, Manifest, ManifestError

    if importlib.util.find_spec("dedalus_tpu") is None:
        sys.stderr.write("chipbench: the system under test (dedalus_tpu) is "
                         "not in this checkout\n")
        return 1
    manifest = Manifest()
    try:
        cell = manifest.cell(args.workload)
        traffic = manifest.traffic(cell)
        workload = manifest.workload(cell)
        config = manifest.config_module(cell)
    except ManifestError as exc:
        sys.stderr.write(f"chipbench: {exc}\n")
        return 1
    spec = config.SPEC
    rehearse = args.rehearse_cpu
    if rehearse and os.environ.get("JAX_PLATFORMS") != "cpu":
        sys.stderr.write("chipbench: --rehearse-cpu needs JAX_PLATFORMS=cpu\n")
        return 1

    # caches inside the checkout, at fixed paths (the path is part of the
    # XLA cache's key; the package puts that one at <checkout>/.cache/xla
    # unless JAX_COMPILATION_CACHE_DIR is set)
    os.environ["DEDALUS_TPU_ASSEMBLY_CACHE"] = str(ROOT / ".cache" / "assembly")

    pending = reference.start(
        manifest.here / "configs" / f"{cell['config']}.py", cell["config"],
        args.seed, seeded=spec.get("seeded", True), rehearse=rehearse)
    try:
        return measure(args, manifest, cell, traffic, workload, config,
                       pending, started)
    finally:
        pending.abandon()


def measure(args, manifest, cell, traffic, workload, config, pending,
            started):
    import json
    import shutil
    import tempfile
    spec = config.SPEC
    rehearse = args.rehearse_cpu

    last = time.time()
    stages = {"interpreter_and_manifest": round(last - started, 3)}

    def stage(name):
        """Seconds since the stage before, for the checks line."""
        nonlocal last
        now = time.time()
        stages[name] = round(now - last, 3)
        last = now

    import jax
    import numpy as np
    devices = jax.devices()
    stage("import_jax_and_reach_the_chip")
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    say("chipbench device: " + json.dumps(device))
    if rehearse:
        if device["platform"] != "cpu":
            sys.stderr.write("chipbench: --rehearse-cpu found a "
                             f"{device['platform']}; run the cell itself\n")
            return 1
    elif device["platform"] != "tpu":
        sys.stderr.write(f"chipbench: no TPU (JAX reports "
                         f"{device['platform']!r}); nothing is measured on "
                         "anything else\n")
        return 1
    chips = int(cell["chips"])
    if len(devices) < chips and not rehearse:
        sys.stderr.write(f"chipbench: {cell['name']} needs {chips} chips, "
                         f"JAX reports {len(devices)}\n")
        return 1
    used = devices[:min(chips, len(devices))]
    peaks = None if rehearse else manifest.peaks(device["kind"])

    import dedalus_tpu  # noqa: F401  (x64, matmul precision, XLA cache)
    from dedalus_tpu.tools.retrace import sentinel
    from . import tracered
    from .traffic import Driver

    stage("import_dedalus_tpu")
    mesh = None
    if len(used) > 1:
        from jax.sharding import Mesh
        mesh = Mesh(np.array(used), ("x",))

    # ------------------------------------------------------------ set-up
    t0 = time.perf_counter()
    dep = config.build(args.seed, mesh=mesh,
                       size=dict(spec["rehearsal"]) if rehearse else None)
    solver = dep.solver
    build_s = time.perf_counter() - t0
    stepper = solver.timestepper
    facts = {
        "ops": type(solver.ops).__name__,
        "G": int(solver.pencil_shape[0]), "S": int(solver.pencil_shape[1]),
        "itemsize": int(np.dtype(solver.pencil_dtype).itemsize),
        # Runge-Kutta schemes carry a tableau; multistep schemes do not
        "rk_stages": int(stepper.stages) if hasattr(stepper, "H") else 0,
    }
    say(f"chipbench built: {json.dumps(facts)} in {build_s:.2f} s")
    stage("build")

    # the comparison with the reference: the same fixed-dt single steps,
    # which also cross the solver's warm-up and arm the retrace sentinel
    for _ in range(int(spec["reference"]["steps"])):
        solver.step(float(spec["reference"]["dt"]))
    got = dep.compared()
    stage("single_steps_factor_and_compile")
    ref = pending.load()
    stage("wait_for_reference")
    ref_rel_l2 = float(np.linalg.norm(got - ref["coeffs"])
                       / np.linalg.norm(ref["coeffs"]))
    tol = spec["tolerances"]["ref_rel_l2"]["value"]
    checks = {"reference": ref_rel_l2 <= tol,
              "ops": facts["ops"] == workload["expect"].get("ops",
                                                            facts["ops"])}
    say(f"chipbench reference: rel_l2 {ref_rel_l2:.3e} (tolerance {tol:g}, "
        f"{'computed' if pending.computed else 'cached'})")

    out_dir = tempfile.mkdtemp(prefix="chipbench-out-")
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") \
        if args.trace else None
    try:
        driver = Driver(traffic, dep, out_dir, tracing=bool(args.trace))
        driver.warm()
        jax.block_until_ready(solver.X)
        stage("spin_up_and_warm_up")
        retraces_before = sentinel.post_arm_retraces
        sim_before = float(solver.sim_time)
        attempted = completed = 0
        failure = None
        marks = []      # [seconds, iterations, sim_time] every MARK_EVERY s

        # ------------------------------------------------------ window
        if args.trace:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        setup_s = time.time() - started
        w0 = time.perf_counter()
        try:
            with driver.span("window"):
                units = 0
                while True:
                    attempted += driver.unit_size
                    driver.unit()
                    units += 1
                    if solver.health_error is not None:
                        failure = f"health halt: {solver.health_error}"
                        break
                    completed += driver.unit_size
                    if args.trace:
                        if units >= int(traffic["trace_units"]):
                            break
                    else:
                        now = time.perf_counter() - w0
                        if now >= MARK_EVERY * (len(marks) + 1):
                            marks.append([round(now, 4), completed,
                                          float(solver.sim_time)])
                        if now >= args.seconds:
                            break
                jax.block_until_ready(solver.X)
        except Exception as exc:   # a failed iteration is a result, not a crash
            failure = f"{type(exc).__name__}: {exc}"
        wall = time.perf_counter() - w0
        if args.trace:
            jax.profiler.stop_trace()
        if failure:
            say(f"chipbench window failed: {failure}")

        # --------------------------------------------------- after it
        retraces = sentinel.post_arm_retraces - retraces_before
        sim_advanced = float(solver.sim_time) - sim_before
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in used)
        state = np.asarray(solver.X)
        checks["finite"] = bool(np.isfinite(state).all())
        checks["completed"] = failure is None and completed > 0
        checks["no_retrace"] = retraces == 0
        invariants = dep.invariants() if checks["finite"] else {}
        for name, (value, bound) in invariants.items():
            checks[name] = bool(value <= bound)
        dts = np.asarray(driver.dts, dtype=float)
        if traffic["dt"] == "cfl":
            max_dt = driver.pieces["max_dt"]
            checks["dt_valid"] = bool(dts.size and np.isfinite(dts).all()
                                      and (dts <= max_dt).all()
                                      and (dts > 0).all())
            if workload["expect"].get("cfl_active"):
                checks["cfl_active"] = bool(dts.size
                                            and (dts < max_dt).any())
        say("chipbench checks: " + json.dumps(
            {"checks": checks,
             "invariants": {k: v[0] for k, v in invariants.items()},
             "iterations": completed, "wall_s": round(wall, 4),
             "sim_time": float(solver.sim_time), "retraces": retraces,
             "sim_before": sim_before, "marks": marks,
             "setup_s": round(setup_s, 3), "setup_stages": stages,
             "dt_min_median_max": [float(dts.min()), float(np.median(dts)),
                                   float(dts.max())] if dts.size else None,
             "build_phases": solver.build_phases.record()}))
        correct = bool(all(checks.values()) and not rehearse
                       and device["platform"] == "tpu")

        # -------------------------------------------------- the line
        device_out = {"platform": device["platform"], "kind": device["kind"],
                      "count": len(used),
                      "memory_peak_bytes": int(peak)}
        result = {"correct": correct, "attempted": int(attempted),
                  "failed": int(attempted - completed)}
        if not args.trace:
            values = {"steps_per_s": completed / wall,
                      "sim_per_s": sim_advanced / wall,
                      "setup_s": setup_s}
            wanted = manifest.metrics("end_to_end", cell["name"])
            missing = [m["name"] for m in wanted if m["name"] not in values]
            if missing:
                sys.stderr.write("chipbench: run.py does not measure "
                                 f"{missing}\n")
                return 1
            result["metrics"] = {
                m["name"]: {"value": float(values[m["name"]]),
                            "unit": m["unit"]} for m in wanted}
        else:
            xplane = tracered.newest_xplane(trace_dir)
            reduced = tracered.reduce(tracered.Trace.from_file(xplane)) \
                if xplane else None
            if args.keep_trace and xplane:
                os.makedirs(args.keep_trace, exist_ok=True)
                shutil.copy(xplane, os.path.join(
                    args.keep_trace, f"{cell['name']}.xplane.pb"))
            if not rehearse and (not reduced or reduced["busy_s"] <= 0):
                sys.stderr.write("chipbench: the trace holds no device "
                                 "operation\n")
                return 1
            ctx = {"reduced": reduced, "iterations": completed,
                   "build_s": build_s,
                   "build_phases": solver.build_phases.record(),
                   "retraces_in_window": retraces, "dts": driver.dts,
                   "dt_mode": traffic["dt"], "ref_rel_l2": ref_rel_l2,
                   "memory_peak_bytes": int(peak), "peaks": peaks,
                   "facts": facts}
            reported = {m["name"] for m in
                        manifest.metrics("end_to_end", cell["name"])}
            metrics = {}
            for m in manifest.metrics("per_layer", cell["name"]):
                if m["moves"] not in reported:
                    continue
                value = manifest.layer_reader(m["name"])(ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": float(value),
                                          "unit": m["unit"]}
            result["metrics"] = metrics
            if reduced:
                device_out["busy_s"] = reduced["busy_s"]
                device_out["window_s"] = reduced["window_s"]
                per = 1e3 / max(completed, 1)
                tr = tracered.scope_seconds(reduced, "dedalus/transform/")
                so = tracered.scope_seconds(reduced, "dedalus/matsolve/")
                say("chipbench trace: " + json.dumps({
                    "iterations": completed,
                    "device_ms_per_step": reduced["busy_s"] * per,
                    "transform_ms_per_step": tr * per,
                    "solve_ms_per_step": so * per,
                    "remainder_ms_per_step":
                        (reduced["busy_s"] - tr - so) * per,
                    "summed_over_union": reduced["summed_durations_s"]
                    / reduced["busy_s"] if reduced["busy_s"] else None,
                    "scopes_ms_per_step": {k: v * per for k, v in
                                           sorted(reduced["scopes"].items())},
                    "span_counts": reduced["span_counts"]}))
                result["breakdown"] = {
                    "device_ops": reduced["device_ops"][:10],
                    "idle_gaps": reduced["idle_gaps"][:10]}
        result["device"] = device_out
        say(json.dumps(result))
        return 0
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())

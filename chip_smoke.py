"""
chip_smoke.py — the quickest proof that dedalus_tpu still starts on the chip.

Drives the IVP main path once, through the entry points a user calls
(`import dedalus_tpu.public as d3`, `problem.build_solver`, `solver.step`,
`solver.step_many`), on the problem of examples/rayleigh_benard.py at its
published 256x64 (RK222, ICs from seed 42), and checks what comes out by
the repo's own means. One process, no subprocess, no probe, no retry: a
chip belongs to one process at a time.

    python chip_smoke.py              one TPU chip: phases rb_f32,
                                      rb_f32_banded, rb_f64
    python chip_smoke.py --chips 4    four chips: the sharded RB step and
                                      the unsharded run it is compared with
    JAX_PLATFORMS=cpu python chip_smoke.py --nx 64 --nz 16
                                      CPU rehearsal of the same phases at a
                                      size of its own; never reports ok

Every phase prints one JSON line; the LAST line of stdout is
`{"ok": ..., "device": {"platform", "kind", "count"}}` and nothing else.
`ok` is true only on platform `tpu` with every phase passed; the exit code
is 0 only then. A device, compile or phase failure is never caught: it
ends the process with its own traceback and no result line. The steps/s
printed here are smoke readings, not a benchmark, and `peak_bytes_in_use`
is the process's peak so far (it never resets between phases).
"""

import argparse
import json
import os
import sys
import time

import numpy as np

DT = 0.01
SINGLES = 10          # solver.step calls (cross the 10-iteration warmup)
BLOCK = 50            # solver.step_many block; run twice, second one timed
F64_STEPS = 20

# f32 tolerances; beside each, the CPU-rehearsal reading (64x16, this
# script) and the first chip reading (256x64, TPU v5 lite, PR 22)
TOL_BC = 1e-5          # rehearsal 5.4e-7, chip 2.4e-7: wall errors of b, u
TOL_CONTINUITY = 1e-5  # rehearsal 2.6e-7, chip 2.3e-7: |trace(grad_u)+tau_p|
TOL_BANDED = 5e-5      # rehearsal 2.1e-6, chip 4.2e-6: rel. L2 of b coeffs
TOL_F64 = 2e-5         # rehearsal 6.1e-7 (dd runner steered onto the CPU),
#                        chip 1.1e-6: rel. L2 of b coeffs, rb_f64 against
#                        rb_f32 at step 10. What it reads is FLOAT32's
#                        error: it shows that the two runs are the same
#                        problem, not that rb_f64 is float64. The float64
#                        guarantee (1e-9 against a native float64 run) is
#                        held by the benchmark cell rb256x64-f64.block10
TOL_SHARDED = 5e-5     # rehearsal 0.0 (4 virtual CPU devices, bit-identical)


def emit(obj):
    print(json.dumps(obj), flush=True)


def rel_l2(a, b):
    a = np.asarray(a, dtype=np.float64)
    b = np.asarray(b, dtype=np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def b_coeffs(b):
    return np.asarray(b["c"], dtype=np.float64)


def physics_checks(solver, Lz=1.0):
    """The checks of .claude/skills/verify drive-flows 2 and 22: boundary
    conditions by interpolation, and the tau-corrected continuity equation
    (the enforced one — bare div(u) carries the tau correction)."""
    import dedalus_tpu.public as d3
    p, b, u, tau_p, tau_b1, tau_b2, tau_u1, tau_u2 = solver.state
    coords = u.tensorsig[0]
    zbasis = b.domain.bases[1]
    _, ez = coords.unit_vector_fields(b.dist)
    grad_u = d3.grad(u) + ez * d3.Lift(tau_u1, zbasis.derivative_basis(1), -1)
    amax = lambda op: float(np.abs(np.asarray(   # noqa: E731
        op.evaluate()["g"], dtype=np.float64)).max())
    return {
        "b_bottom_err": amax(b(z=0) - Lz),
        "b_top_err": amax(b(z=Lz)),
        "u_bottom_max": amax(u(z=0)),
        "u_top_max": amax(u(z=Lz)),
        "continuity_max": amax(d3.trace(grad_u) + tau_p),
    }


def run_rb(name, nx, nz, dtype, matsolver=None, mesh=None, singles=SINGLES,
           blocks=2, snapshot_at=None):
    """Build RB nx x nz through the public API and step it: `singles`
    blocking solver.step calls, then `blocks` step_many blocks. Returns
    (solver, report, checks, snapshots) — snapshots of b's coefficients
    keyed by iteration."""
    import jax
    from dedalus_tpu.extras.bench_problems import build_rb_solver
    from dedalus_tpu.tools.retrace import sentinel

    retraces_before = sentinel.post_arm_retraces
    t0 = time.perf_counter()
    solver, b = build_rb_solver(nx, nz, dtype, mesh=mesh, matsolver=matsolver)
    if mesh is not None:
        from dedalus_tpu.parallel import distribute_solver
        distribute_solver(solver, mesh)
    build_sec = time.perf_counter() - t0

    snapshots = {}
    t0 = time.perf_counter()
    solver.step(DT)
    jax.block_until_ready(solver.X)
    first_step_sec = time.perf_counter() - t0
    single_sec = 0.0
    for _ in range(singles - 1):
        t0 = time.perf_counter()
        solver.step(DT)
        jax.block_until_ready(solver.X)
        single_sec += time.perf_counter() - t0
        if solver.iteration == snapshot_at:
            snapshots[solver.iteration] = b_coeffs(b)
    snapshots[solver.iteration] = b_coeffs(b)
    block_sec = None
    for _ in range(blocks):
        t0 = time.perf_counter()
        solver.step_many(BLOCK, DT)
        jax.block_until_ready(solver.X)
        block_sec = time.perf_counter() - t0
    snapshots[solver.iteration] = b_coeffs(b)

    X = solver.X
    mem = jax.devices()[0].memory_stats() or {}
    report = {
        "phase": name, "nx": nx, "nz": nz, "dtype": np.dtype(dtype).name,
        "ops": type(solver.ops).__name__,
        "solve_plan": str(getattr(solver, "_solve_plan", None)),
        "emulated_f64": solver._dd is not None,
        "x_devices": sorted(str(d) for d in X.devices()),
        "x_shape": list(X.shape), "iterations": solver.iteration,
        "build_sec": round(build_sec, 3),
        "build_phases": solver.build_phases.record(),
        "first_step_sec": round(first_step_sec, 3),
        # smoke readings, not a benchmark: blocking single steps after the
        # first, and the last step_many block (the first one compiles)
        "single_steps_per_sec": round((singles - 1) / single_sec, 2),
        "block_steps_per_sec": (round(BLOCK / block_sec, 2)
                                if blocks > 1 else None),
        "peak_bytes_in_use": mem.get("peak_bytes_in_use"),
        "post_arm_retraces": sentinel.post_arm_retraces - retraces_before,
    }
    checks = {
        "finite": bool(np.isfinite(np.asarray(X)).all()),
        "no_retrace": report["post_arm_retraces"] == 0,
        "on_platform": {d.platform for d in X.devices()}
        == {jax.devices()[0].platform},
    }
    return solver, report, checks, snapshots


def finish_phase(report, checks):
    report["checks"] = checks
    report["passed"] = all(checks.values())
    emit(report)
    return report["passed"]


def add_physics(solver, report, checks):
    phys = physics_checks(solver)
    report["physics"] = phys
    checks["bcs"] = max(phys["b_bottom_err"], phys["b_top_err"],
                        phys["u_bottom_max"], phys["u_top_max"]) < TOL_BC
    checks["continuity"] = phys["continuity_max"] < TOL_CONTINUITY


def one_chip_phases(nx, nz, on_tpu):
    passed = []

    solver, report, checks, ref = run_rb("rb_f32", nx, nz, np.float32)
    add_physics(solver, report, checks)
    checks["dense_ops"] = report["ops"] == "DenseOps"
    passed.append(finish_phase(report, checks))
    last = max(ref)

    solver, report, checks, snaps = run_rb("rb_f32_banded", nx, nz,
                                           np.float32, matsolver="banded")
    add_physics(solver, report, checks)
    report["rel_l2_vs_rb_f32"] = rel_l2(snaps[last], ref[last])
    checks["banded_ops"] = report["ops"] == "BandedOps"
    checks["agrees_with_rb_f32"] = report["rel_l2_vs_rb_f32"] < TOL_BANDED
    passed.append(finish_phase(report, checks))

    # the example's own dtype. On the chip this must be the emulated-f64
    # (double-double) runner, not XLA's software f64; compared with rb_f32
    # at the last single step both runs share, then stepped on to 20. The
    # comparison is as good as rb_f32 is: that the float64 run delivers
    # float64 is for the cell rb256x64-f64.block10 to say, not this smoke.
    solver, report, checks, snaps = run_rb(
        "rb_f64", nx, nz, np.float64, singles=F64_STEPS, blocks=0,
        snapshot_at=SINGLES)
    report["rel_l2_vs_rb_f32"] = rel_l2(snaps[SINGLES], ref[SINGLES])
    checks["same_problem_as_rb_f32_to_f32_error"] = \
        report["rel_l2_vs_rb_f32"] < TOL_F64
    if on_tpu:
        checks["emulated_f64_runner"] = report["emulated_f64"]
    passed.append(finish_phase(report, checks))
    return all(passed)


def four_chip_phase(nx, nz):
    """RB f32 sharded over Mesh(jax.devices()[:4], ("x",)) against the
    same run unsharded in this process."""
    import jax
    from jax.sharding import Mesh
    from dedalus_tpu.core.timesteppers import step_program_handle
    from dedalus_tpu.tools.lint.progcheck import collective_counts

    _, report, checks, ref = run_rb("rb_f32_unsharded", nx, nz, np.float32)
    passed = [finish_phase(report, checks)]
    last = max(ref)

    mesh = Mesh(np.array(jax.devices()[:4]), ("x",))
    solver, report, checks, snaps = run_rb("rb_f32_sharded", nx, nz,
                                           np.float32, mesh=mesh)
    shard_devices = sorted(str(s.device)
                           for s in solver.X.addressable_shards)
    program, args = step_program_handle(solver, DT)
    counts = collective_counts(program.lower(*args).compile().as_text())
    report["shard_devices"] = shard_devices
    report["sharding"] = str(solver.X.sharding)
    report["collectives"] = {k: int(v) for k, v in counts.items()}
    report["rel_l2_vs_unsharded"] = rel_l2(snaps[last], ref[last])
    checks["four_distinct_devices"] = len(set(shard_devices)) == 4
    checks["all_to_all_no_all_gather"] = (counts["all-to-all"] >= 2
                                          and counts["all-gather"] == 0)
    checks["agrees_with_unsharded"] = \
        report["rel_l2_vs_unsharded"] < TOL_SHARDED
    passed.append(finish_phase(report, checks))
    return all(passed)


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--nx", type=int, default=None,
                        help="rehearsal size (CPU only; the chip runs the "
                             "published 256)")
    parser.add_argument("--nz", type=int, default=None)
    args = parser.parse_args(argv)

    import jax
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    emit({"phase": "devices", "device": device,
          "devices": [str(d) for d in devices]})
    on_tpu = device["platform"] == "tpu"
    asked_cpu = "cpu" in os.environ.get("JAX_PLATFORMS", "").split(",")
    sized = bool(args.nx and args.nz)
    refusal = None
    if not on_tpu and not asked_cpu:
        refusal = ("JAX found no TPU and fell back to "
                   f"{device['platform']!r} on its own")
    elif on_tpu and (args.nx or args.nz):
        refusal = ("--nx/--nz are for the CPU rehearsal; the chip runs the "
                   "published 256x64")
    elif not on_tpu and not sized:
        refusal = ("no accelerator; a CPU rehearsal names its own size "
                   "(--nx 64 --nz 16)")
    elif len(devices) < args.chips:
        refusal = (f"--chips {args.chips} needs {args.chips} devices, JAX "
                   f"reports {len(devices)}")
    if refusal:
        emit({"phase": "devices", "passed": False, "error": refusal})
        return 1
    nx, nz = (256, 64) if on_tpu else (args.nx, args.nz)

    import dedalus_tpu  # noqa: F401  (x64 + compile-cache placement)
    emit({"phase": "config", "nx": nx, "nz": nz, "chips": args.chips,
          "compilation_cache_dir": jax.config.jax_compilation_cache_dir,
          "rehearsal": not on_tpu})
    if args.chips == 4:
        passed = four_chip_phase(nx, nz)
    else:
        passed = one_chip_phases(nx, nz, on_tpu)
    ok = bool(passed and on_tpu)
    emit({"ok": ok, "device": device})
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

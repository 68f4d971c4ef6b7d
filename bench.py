"""
Benchmark: 2D Rayleigh-Benard IVP timesteps/sec on one chip
(progression config 3 from BASELINE.md: Fourier x Chebyshev, reference
example: examples/ivp_2d_rayleigh_benard; `matsolver` is left to `auto`,
which is the dense-inverse path at 256x64 — ROADMAP A3).

Runs `run_benchmark()` in THIS process: one process per chip, no child, no
probe, no cached row, no CPU fallback. Prints the platform, device kind and
device count first, then ONE JSON line on stdout: {"metric", "value",
"unit", "vs_baseline", ...}; progress markers go to stderr. Exits non-zero
when the platform is not `tpu`, unless JAX_PLATFORMS names `cpu` itself (a
CPU run is then what was asked for, and the metric name says `cpu`).

Baseline estimate: the reference example (256x64, RK222+CFL, stop_sim_time=50)
takes ~5 cpu-minutes on a 4-core workstation (reference docstring,
examples/ivp_2d_rayleigh_benard/rayleigh_benard.py:6). With the example's
adaptive dt averaging ~0.03, that is ~1700 steps / 300 s ~= 5.7 steps/sec.
"""

import json
import os
import sys
import time

T0 = time.time()
BASELINE_STEPS_PER_SEC = 5.7
NX, NZ = 256, 64
WARMUP = 10
MEASURE = 50
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from __graft_entry__ import _append_result  # noqa: E402


def mark(msg):
    print(f"[bench {time.time() - T0:7.1f}s] {msg}", file=sys.stderr, flush=True)


def run_benchmark():
    """The measurement itself; assumes the backend in this process works."""
    mark("importing jax")
    import numpy as np
    import jax

    backend = jax.default_backend()
    mark(f"backend={backend} devices={len(jax.devices())}")
    # TPU: no c128, f64 emulated -> bench the f32 path on TPU, f64 on CPU.
    dtype = np.float32 if backend != "cpu" else np.float64

    from __graft_entry__ import _build_rb_solver

    mark(f"building RB {NX}x{NZ} solver dtype={np.dtype(dtype).name}")
    t_build = time.time()
    solver, b = _build_rb_solver(NX, NZ, dtype)
    build_sec = time.time() - t_build
    dt = 0.01
    mark("warmup (first step compiles)")
    for i in range(WARMUP):
        solver.step(dt)
        if i == 0:
            solver.X.block_until_ready()
            mark("first step done (compile finished)")
    solver.X.block_until_ready()
    mark(f"compiling {MEASURE}-step block")
    solver.step_many(MEASURE, dt)   # one lax.scan dispatch per block
    solver.X.block_until_ready()
    mark(f"measuring {MEASURE}-step block")
    t0 = time.time()
    solver.step_many(MEASURE, dt)
    solver.X.block_until_ready()
    elapsed = time.time() - t0
    steps_per_sec = MEASURE / elapsed
    mark(f"measured {steps_per_sec:.2f} steps/s")

    assert np.all(np.isfinite(np.asarray(solver.X))), "non-finite state"
    record = {
        "metric": f"RB2D_{NX}x{NZ}_IVP_steps_per_sec_{np.dtype(dtype).name}_{backend}",
        "value": round(steps_per_sec, 3),
        "unit": "steps/sec",
        "vs_baseline": round(steps_per_sec / BASELINE_STEPS_PER_SEC, 3),
        # cold-start accounting: solver-construction wall time plus the
        # host_assembly/structure/factor/compile split and assembly-cache
        # verdict (tools/metrics.BuildPhases; benchmarks/coldstart.py is
        # the dedicated cold-vs-warm study)
        "build_sec": round(build_sec, 3),
        "build_phases": solver.build_phases.record(),
    }
    # Attach the sampled per-phase breakdown (tools/metrics.py; default-on,
    # cadence-gated so it never blocked inside the measured region)
    try:
        metrics_rec = solver.flush_metrics()
    except Exception as exc:
        mark(f"metrics flush failed (non-fatal): {exc}")
        metrics_rec = None
    if metrics_rec and metrics_rec.get("phase_samples"):
        record["phase_total_sec"] = metrics_rec["phase_total_sec"]
        record["phase_sum_frac"] = metrics_rec["phase_sum_frac"]
        record["phase_samples"] = metrics_rec["phase_samples"]
        if metrics_rec.get("device_mem_peak_bytes"):
            record["device_mem_peak_bytes"] = \
                metrics_rec["device_mem_peak_bytes"]
    # Numerical-health summary (tools/health.py; default-on, cadence-gated
    # like the phase sampler): checks run, warnings, ok/failed.
    try:
        health_sum = solver.health.summary()
    except Exception as exc:
        mark(f"health summary failed (non-fatal): {exc}")
        health_sum = None
    if health_sum is not None:
        record["health"] = health_sum
    # Jit-hygiene sentinels, so the perf trajectory shows hygiene
    # regressions alongside steps/sec: post-warmup retrace count
    # (tools/retrace.py; anything nonzero means the measured loop paid
    # compile time) and static-analysis cleanliness vs the checked-in
    # baseline (tools/lint).
    from dedalus_tpu.tools.retrace import sentinel
    record["retraces_post_warmup"] = sentinel.post_arm_retraces
    try:
        from dedalus_tpu.tools.lint import lint_package
        lint_summary = lint_package()
        record["lint_clean"] = (lint_summary["new"] == 0
                                and not lint_summary["stale"])
        record["lint_new_findings"] = lint_summary["new"]
    except Exception as exc:
        mark(f"lint status failed (non-fatal): {exc}")
    return record


def main():
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    print(f"platform={platform} device_kind={devices[0].device_kind} "
          f"count={len(devices)}", flush=True)
    asked_cpu = "cpu" in os.environ.get("JAX_PLATFORMS", "").split(",")
    if platform != "tpu" and not asked_cpu:
        mark(f"no TPU: JAX fell back to {platform!r} on its own; not "
             "measuring")
        sys.exit(1)
    record = run_benchmark()
    record.update(platform=platform, device_kind=devices[0].device_kind,
                  device_count=len(devices))
    _append_result({"config": f"rb{NX}x{NZ}_bench", **record})
    print(json.dumps(record), flush=True)


if __name__ == "__main__":
    main()

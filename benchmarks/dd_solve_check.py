"""The float64 route's refined solve, pencil by pencil (PR 36): the
double-double runner's own `solve_ir` program on rb256x64-f64's matrices
(A = M + dt gamma L, all 128 pencils of 526) after 0, 1, 2 and 3 dd sweeps
against `numpy.linalg.solve` in float64 on the host, for two right-hand
sides (M X of a stepped state, and A x of a random x) and for both inner
float32 solvers: the one the runner's rule picks (`plain`: the stored
inverse alone where the solver's class is `BatchedInverseRefined`) and the
solver's own class (`refined`: the rule switched off, the solves as they
were before PR 36). One process, which holds the chip:

    chiprun -- python3 benchmarks/dd_solve_check.py

Here, `JAX_PLATFORMS=cpu python3 benchmarks/dd_solve_check.py` rehearses it
at the configuration's rehearsal size with the build steered onto the route
a TPU takes. Prints one JSON line per (inner solver, right-hand side,
sweeps). A diagnosis outside every timed window, not a metric: what
`chipbench/tests/f64_ops.py`'s last section printed until PR 36 (that
section calls `solver.ops.solve` on the runner's `aux32`, which is the
inverse alone now)."""

import json
import pathlib
import sys

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def main():
    import jax
    from chipbench.manifest import load_module
    from dedalus_tpu.core import ddstep
    from dedalus_tpu.libraries.doubledouble import dd_from_f64, dd_to_f64
    cfg = load_module(ROOT / "chipbench" / "configs" / "rb256x64-f64.py")
    platform = jax.devices()[0].platform
    if platform == "tpu":
        dep = cfg.build(0)
    else:
        real, jax.default_backend = jax.default_backend, lambda: "tpu"
        try:
            dep = cfg.build(0, size=dict(cfg.SPEC["rehearsal"]))
        finally:
            jax.default_backend = real
    solver, dt = dep.solver, cfg.SPEC["fixed_dt"]
    runner = solver._dd
    for _ in range(3):
        solver.step(dt)
    X = runner.state_f64()
    M, L = runner.M_host, runner.L_host
    gamma = float(solver.timestepper.H[1, 1])
    A = M + dt * gamma * L
    x = np.random.default_rng(0).standard_normal(X.shape)
    sides = {"M X of a stepped state": np.einsum("gij,gj->gi", M, X),
             "A x of a random x": np.einsum("gij,gj->gi", A, x)}
    wanted = {side: np.linalg.solve(A, r[..., None])[..., 0]
              for side, r in sides.items()}
    for inner in ("plain", "refined"):
        if inner == "refined":
            ddstep._inner_ops = lambda ops: ops
            runner = ddstep.DDIVPRunner(solver)
        lhs = runner._rk_factor([ddstep._dd_scalar(dt * gamma)])[0]
        for side, r in sides.items():
            want = wanted[side]
            for sweeps in (0, 1, 2, 3):
                got = dd_to_f64(runner._solve_ir(lhs, dd_from_f64(r), sweeps))
                each = np.linalg.norm(got - want, axis=-1) \
                    / np.linalg.norm(want, axis=-1)
                print(json.dumps({
                    "platform": platform, "inner": inner,
                    "f32_solver": runner.counters()["f32_solver"],
                    "rhs": side, "sweeps": sweeps,
                    "rel_l2": float(np.linalg.norm(got - want)
                                    / np.linalg.norm(want)),
                    "worst_pencil_l2": float(each.max()),
                    "which": int(each.argmax()),
                    "pencils_over_1e_10": int((each > 1e-10).sum()),
                    "worst_pencil_inf": float(np.max(
                        np.abs(got - want).max(axis=-1)
                        / np.abs(want).max(axis=-1)))}), flush=True)


if __name__ == "__main__":
    main()

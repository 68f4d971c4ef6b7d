"""Where the double-double route's digits go (PR 35): rb256x64-f64's ten
reference steps on the chip with another number of refinement sweeps than
the runner's own 2, against the cached native float64 reference. One
process, which holds the chip; run after the cell itself, whose run leaves
the reference in .cache/chipbench:

    python3 chipbench/tests/f64_digits.py <sweeps> [<sweeps> ...]

`refine` is a constructor argument of `DDIVPRunner`, not an option: the
runner is replaced on the built solver, as the tier-1 tests do. Prints one
JSON line per count. A diagnosis, not a metric."""

import json
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def main(argv):
    import jax
    from chipbench import reference
    from chipbench.manifest import load_module
    from dedalus_tpu.core.ddstep import DDIVPRunner
    cfg = load_module(ROOT / "chipbench" / "configs" / "rb256x64-f64.py")
    spec = cfg.SPEC
    ref = np.load(reference.cache_path(
        spec["name"], 0, seeded=False, rehearse=False))["coeffs"]
    for sweeps in map(int, argv):
        dep = cfg.build(0)
        solver = dep.solver
        solver._dd = DDIVPRunner(solver, refine=sweeps)
        t0 = time.perf_counter()
        for _ in range(spec["reference"]["steps"]):
            solver.step(spec["reference"]["dt"])
        got = dep.compared()
        print(json.dumps({
            "refine": sweeps, "platform": jax.devices()[0].platform,
            "ref_rel_l2": float(np.linalg.norm(got - ref)
                                / np.linalg.norm(ref)),
            "ten_steps_s": round(time.perf_counter() - t0, 1)}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])

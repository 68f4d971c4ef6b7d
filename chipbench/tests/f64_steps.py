"""When the double-double route's error appears on the chip (PR 35): the
state after each of the ten reference steps, variable by variable, against
a native float64 run of the same steps on a CPU: an .npz of the eleven
gathered states, made beforehand on the CPU backend through the plain path
(chipbench/reference.py's PLAIN_PATH, dense pencils),

    JAX_PLATFORMS=cpu python3 chipbench/tests/f64_steps.py --make .scratch/ref_states.npz

and then, on the chip,

    python3 chipbench/tests/f64_steps.py .scratch/ref_states.npz

Prints one JSON line per step: the error's L2 norm over the reference's,
for the whole state and per variable, and where the largest entry sits. A
diagnosis, not a metric."""

import json
import pathlib
import sys

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def make(path):
    """The eleven native float64 states, on the CPU backend."""
    from chipbench.manifest import load_module
    from chipbench.reference import PLAIN_PATH
    from dedalus_tpu.tools.config import config
    for section, keys in PLAIN_PATH.items():
        config[section].update(keys)
    cfg = load_module(ROOT / "chipbench" / "configs" / "rb256x64-f64.py")
    solver = cfg.build(0, size={"matsolver": "dense"}).solver
    assert solver._dd is None, "the reference states are native float64"
    import jax.numpy as jnp
    mask = np.asarray(solver.valid_row_mask, dtype=np.float64)

    def rhs(X):
        """The masked right-hand side F(X) as a stage evaluates it."""
        return np.asarray(solver.eval_F(jnp.asarray(X), jnp.asarray(0.0),
                                        solver.rhs_extra())) * mask
    states = [np.asarray(solver.gather_fields(), dtype=np.float64)]
    for _ in range(cfg.SPEC["reference"]["steps"]):
        solver.step(cfg.SPEC["reference"]["dt"])
        states.append(np.asarray(solver.X, dtype=np.float64))
    np.savez(path, states=np.stack(states),
             rhs=np.stack([rhs(X) for X in states]))


def explicit_half(runner, states, rhs, slots):
    """The dd interpreter's F on the CPU's own states against the CPU's
    native F: the explicit half alone, with nothing of a step around it.
    `eval_F_dd` is a closure of the step program's body: taken from
    there, for this diagnosis only."""
    from dedalus_tpu.libraries.doubledouble import (DD, dd_from_f64,
                                                    dd_to_f64)
    from dedalus_tpu.tools.jitlift import lifted_jit
    import jax.numpy as jnp
    eval_F = next(c.cell_contents for c in runner._rk_step.fn.__closure__
                  if getattr(c.cell_contents, "__name__", "") == "eval_F_dd")
    program = lifted_jit(eval_F)
    zero = DD(jnp.float32(0.0), jnp.float32(0.0))
    for step, (X, want) in enumerate(zip(states, rhs)):
        got = dd_to_f64(program(dd_from_f64(X), zero, runner._extras_dd()))
        err = got - want
        g, s = np.unravel_index(np.abs(err).argmax(), err.shape)
        line = {"rhs_of_state": step,
                "all": float(np.linalg.norm(err) / np.linalg.norm(want))
                if want.any() else float(np.linalg.norm(err)),
                "largest": [int(g), int(s), float(err[g, s]),
                            float(want[g, s])]}
        for name, where in slots.items():
            line[name] = [float(np.linalg.norm(err[:, where])),
                          float(np.linalg.norm(want[:, where]))]
        print(json.dumps(line), flush=True)


def main(path):
    import jax
    from chipbench.manifest import load_module
    cfg = load_module(ROOT / "chipbench" / "configs" / "rb256x64-f64.py")
    want = np.load(path)["states"]
    dep = cfg.build(0)
    solver, runner = dep.solver, dep.solver._dd
    runner.sync_state()
    slots, at = {}, 0
    for v in solver.variables:
        size = solver.layout.slot_size(v.domain, v.tensorsig)
        slots[v.name] = slice(at, at + size)
        at += size
    if "rhs" in np.load(path):
        explicit_half(runner, want, np.load(path)["rhs"], slots)
    for step, ref in enumerate(want):
        if step:
            solver.step(cfg.SPEC["reference"]["dt"])
        err = runner.state_f64() - ref
        g, s = np.unravel_index(np.abs(err).argmax(), err.shape)
        line = {"step": step, "platform": jax.devices()[0].platform,
                "all": np.linalg.norm(err) / np.linalg.norm(ref),
                "largest": [int(g), int(s), float(err[g, s]),
                            float(ref[g, s])],
                "group0_share": np.linalg.norm(err[0]) / np.linalg.norm(err)
                if err.any() else 0.0}
        for name, where in slots.items():
            norm = np.linalg.norm(ref[:, where])
            line[name] = [float(np.linalg.norm(err[:, where])), float(norm)]
        print(json.dumps({k: float(v) if isinstance(v, np.floating) else v
                          for k, v in line.items()}), flush=True)


if __name__ == "__main__":
    if sys.argv[1] == "--make":
        make(sys.argv[2])
    else:
        main(sys.argv[1])

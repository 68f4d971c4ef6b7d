"""
Configuration rb256x64-f64: the problem of examples/rayleigh_benard.py
(upstream examples/ivp_2d_rayleigh_benard/rayleigh_benard.py) in the dtype
the example is published in, float64. Nothing of the source is cut.

The problem text is an IMPORT of rb256x64.py's `build` and `Deployment`,
not a second copy: the two configurations are one script that differs in
one word (`dtype`), and a copy would let them drift apart in anything
else. rb256x64.py is a file of the benchmark like this one, so a PR that
may not edit the one may not edit the other. Everything this file states
for itself is in rb256x64-f64.json beside it: the sizes it hands
rb256x64's `build` (every one of them, `dtype: "float64"` among them), the
fixed dt, the guarantees and the limits `correct` holds the run to.

On a TPU a float64 problem takes another route through the program than
any float32 cell: `[execution] EMULATED_F64 = auto` wires the double-double
runner (core/ddstep.py), or the build falls back to XLA's software
float64. Which one ran is in `build_phases.record()["f64_route"]`, and the
invariant `f64_route` fails where it names none: a run that silently
stepped in float32 must not count. The reference (chipbench/reference.py)
is this same deployment on the CPU backend in NATIVE float64, where no
runner is ever wired: it shares the assembly and the expression tree with
the system and nothing of ddstep.py or doubledouble.py.
"""

import json
import pathlib

SPEC = json.loads(pathlib.Path(__file__).with_suffix(".json").read_text())
F64_ROUTES = ("dd", "xla_f64")


def _require_program():
    """Refuse at once, before JAX or the reference child, on a program
    that does not say which float64 route it runs (a tree before PR 35:
    its dd route opens no span and books no build phase, so the metrics
    every cell reports would find nothing). The harness lays this file
    over the parent's checkout too; the parent then fails cleanly, with
    the manifest's message and exit code 1. Read from the source text: no
    import of the package, so no JAX."""
    import importlib.util
    from chipbench.manifest import ManifestError
    package = importlib.util.find_spec("dedalus_tpu")
    source = pathlib.Path(package.origin).parent / "tools" / "metrics.py"
    if "f64_route" not in source.read_text():
        raise ManifestError(
            "configuration rb256x64-f64 needs a program whose "
            "build_phases.record() names its float64 route (PR 35); "
            f"{source} names none")


_require_program()


class Deployment:
    """rb256x64's reading of the state — `compared()` and its two
    invariants, from the float64 the fields pull (hi + lo on the dd
    route) — held to this file's limits, and the route."""

    def __init__(self, base):
        self.base = base
        self.solver = base.solver
        self.fields = base.fields
        self.fixed_dt = SPEC["fixed_dt"]
        self.compared = base.compared

    def invariants(self):
        tol = SPEC["tolerances"]
        out = {name: (value, tol[name]["value"])
               for name, (value, _) in self.base.invariants().items()}
        route = self.solver.build_phases.record().get("f64_route")
        # 0 where the program names a float64 route, 1 where it names
        # none (a float32 build reads null)
        out["f64_route"] = (float(route not in F64_ROUTES),
                            tol["f64_route"]["value"])
        return out


def build(seed, mesh=None, dtype=None, size=None):
    """rb256x64's `build` on this file's sizes (`seed` accepted and
    unused there: one trajectory, the example's seed 42). `dtype` is the
    reference's way in, and float64 like the run's own; `size` the CPU
    rehearsal's."""
    from chipbench.manifest import load_module
    rb = load_module(pathlib.Path(__file__).with_name("rb256x64.py"))
    return Deployment(rb.build(seed, mesh=mesh, dtype=dtype,
                               size=dict(SPEC["sizes"], **(size or {}))))

"""
Configuration rb256x64-ens: an initial-condition ensemble of the problem of
examples/rayleigh_benard.py (upstream
examples/ivp_2d_rayleigh_benard/rayleigh_benard.py): `members` copies of
RB 256x64 with the example's parameters, member m's noise drawn by the
example's own `fill_random` with seed `ic_seed + m` (member 0 is the
example's seed 42), advanced together on one chip by the package's fleet
solver, `solver.ensemble(...)` (core/ensemble.py): one compiled step for
every member, the pencil matrices and their factorization shared.

The problem text is an IMPORT of rb256x64.py's `build` and `Deployment`, as
rb256x64-f64.py imports them: both files are the benchmark's, and a copy
could drift. Everything this file states for itself is in
rb256x64-ens.json beside it: the sizes it hands rb256x64's `build`, the
member count, the fixed dt, the sampled members, the guarantees and the
limits `correct` holds the run to.

Two ways through `build`:

- the run: rb256x64's solver in float32 as the template of a fleet of
  `members` members (common dt, `policy="drop"`, the harness's `mesh`:
  None on one chip), every member's initial conditions installed by
  `init_members`;
- the reference (chipbench/reference.py asks for float64 on the CPU): ONE
  plain float64 rb256x64 solver, no fleet and nothing of core/ensemble.py,
  stepping each sampled member's state in turn. RK222 carries nothing from
  one step to the next but the state, so a solver that is handed member
  m's state and stepped is member m's run.

`correct` reads eight sampled members (0, 1, N/2, N-1 and four from a
fixed seed): their kx != 0 coefficients of b and u against the reference,
rb256x64's wall and continuity checks on each, that no member was dropped,
and that the sampled members are still distinct realisations.
"""

import json
import pathlib

import numpy as np

SPEC = json.loads(pathlib.Path(__file__).with_suffix(".json").read_text())


def _require_program():
    """Refuse at once, before JAX or the reference child, on a program
    whose fleet is no solver the harness can run (an older tree: no
    `sim_time`, no `health_error`, no `ops`, no spans, no set-up ledger;
    its pencil products the multiply-reduce vmapped per member). The
    harness lays this file over the parent's checkout too; the parent then
    fails cleanly, with the manifest's message and exit code 1. Read from
    the source text: no import of the package, so no JAX."""
    import importlib.util
    from chipbench.manifest import ManifestError
    package = importlib.util.find_spec("dedalus_tpu")
    root = pathlib.Path(package.origin).parent
    wanted = ((root / "core" / "ensemble.py", "def health_error"),
              (root / "libraries" / "matsolvers.py", "def fleet_matvec"))
    for source, needle in wanted:
        if needle not in source.read_text():
            raise ManifestError(
                "configuration rb256x64-ens needs a program whose ensemble "
                "runs on the normal path with member-batched pencil "
                f"products; {source} has no {needle!r}")


_require_program()


def sampled_members(N, n_random=None, seed=None):
    """The members `correct` reads: the first two, the middle, the last,
    and `n_random` more from a fixed seed."""
    pick = SPEC["sampled"]
    n_random = pick["n_random"] if n_random is None else n_random
    fixed = [0, 1, N // 2, N - 1]
    rest = np.setdiff1d(np.arange(N), fixed)
    extra = np.random.default_rng(pick["seed"] if seed is None else seed) \
        .choice(rest, size=min(n_random, rest.size), replace=False)
    return sorted({int(m) for m in fixed} | {int(m) for m in extra})


def member_ic(base, ic_seed):
    """`fn(m)` for `init_members`: the example's initial conditions with
    the noise of seed `ic_seed + m` (rb256x64.py's last three lines)."""
    b = base.fields["b"]
    Lz = base.params["Lz"]
    _, z = b.dist.local_grids(*b.domain.bases)

    def fill(m):
        b.fill_random('g', seed=ic_seed + m, distribution='normal',
                      scale=1e-3)
        b['g'] *= z * (Lz - z)
        b['g'] += Lz - z
    return fill


def flow(fields):
    """The part of one member's state the comparison reads, as float64:
    b and u without their kx = 0 rows (the first two of the RealFourier
    axis). The conduction profile b = Lz - z holds nearly all of b's norm
    and would hide an error in a member's 1e-3 noise (rb2048x1024.py's
    reading)."""
    b = np.asarray(fields["b"]["c"], dtype=np.float64)
    u = np.asarray(fields["u"]["c"], dtype=np.float64)
    return np.concatenate([b[2:].ravel(), u[:, 2:].ravel()])


def smallest_difference(flows):
    """The smallest pairwise relative difference of the members' flows,
    |f_a - f_b| / max(|f_a|, |f_b|): independent realisations read near
    sqrt(2), a fleet collapsed onto one member 0."""
    flows = list(flows)
    out = np.inf
    for i, fa in enumerate(flows):
        for fb in flows[i + 1:]:
            scale = max(np.linalg.norm(fa), np.linalg.norm(fb))
            out = min(out, np.linalg.norm(fa - fb) / scale)
    return float(out)


class Deployment:
    """The fleet, and this configuration's reading of it: the sampled
    members through `load_member`, each read as rb256x64's Deployment
    reads its solver's fields."""

    def __init__(self, fleet, base, members):
        self.solver = fleet
        self.base = base
        self.fields = base.fields
        self.members = members
        self.fixed_dt = SPEC["fixed_dt"]

    def _each(self, read):
        out = []
        for m in self.members:
            self.solver.load_member(m)
            out.append(read())
        return out

    def compared(self):
        return np.concatenate(self._each(lambda: flow(self.fields)))

    def invariants(self):
        tol = SPEC["tolerances"]
        per_member = self._each(self.base.invariants)
        out = {name: (max(inv[name][0] for inv in per_member),
                      tol[name]["value"])
               for name in ("wall_bc", "continuity")}
        out["members_dropped"] = (float(len(self.solver.dropped)),
                                  tol["members_dropped"]["value"])
        # the check reads value <= limit: the difference, negated
        out["members_distinct"] = (
            -smallest_difference(self._each(lambda: flow(self.fields))),
            -tol["members_distinct"]["value"])
        return out


class SampledMembers:
    """The reference's stepper: one plain solver, handed each sampled
    member's state in turn and stepped once (`step`), with the members'
    common clock and iteration count. What the reference child calls
    `solver.step` on."""

    def __init__(self, solver, states):
        self.solver = solver
        self.states = states
        self.sim_time = 0.0
        self.iteration = 0

    def step(self, dt):
        solver = self.solver
        for m, X in self.states.items():
            # the state is the solver's X, not its fields: no re-gather
            solver.X = X
            solver.snapshot_versions()
            solver.sim_time, solver.iteration = self.sim_time, self.iteration
            solver.step(dt)
            self.states[m] = solver.X
        self.sim_time += dt
        self.iteration += 1

    def load_member(self, m):
        # the fields read member m's state when next read (a pending pull
        # of the last state stepped would win over a preset)
        self.solver.defer_scatter(self.states[m])


class Reference(Deployment):
    """The sampled members of the plain float64 reference, read as the
    fleet's are."""

    def __init__(self, base, states):
        super().__init__(SampledMembers(base.solver, states), base,
                         list(states))


def build(seed, mesh=None, dtype=None, size=None):
    """rb256x64's `build` on this file's sizes (`seed` accepted and
    unused there and here: member m's noise is seed `ic_seed + m` in
    every run), then the fleet of `members` members over its solver, or,
    where `dtype` is float64 (the reference's way in), the plain reference
    of the sampled members. `mesh` is the harness's (None on one chip: the
    fleet stays on the one device); `size` the CPU rehearsal's."""
    from chipbench.manifest import load_module
    rb = load_module(pathlib.Path(__file__).with_name("rb256x64.py"))
    sizes = dict(SPEC["sizes"], **(size or {}))
    N = int(sizes.pop("members"))
    base = rb.build(seed, mesh=None, dtype=dtype, size=sizes)
    fill = member_ic(base, sizes["ic_seed"])
    members = sampled_members(N)
    if np.dtype(dtype or sizes["dtype"]) == np.float64:
        states = {}
        for m in members:
            fill(m)
            states[m] = base.solver.gather_fields()
        return Reference(base, states)
    if mesh is not None:
        from jax.sharding import Mesh
        mesh = Mesh(mesh.devices, ("batch",))
    fleet = base.solver.ensemble(N, mesh=mesh, policy="drop")
    fleet.init_members(fill)
    return Deployment(fleet, base, members)

"""
IMEX timesteppers (reference: dedalus/core/timesteppers.py).

Schemes integrate M.dt(X) + L.X = F with implicit L and explicit F.

Multistep form (reference: core/timesteppers.py:22 MultistepIMEX):
    sum_j a_j M.X(n-j) + sum_j b_j L.X(n-j) = sum_{j>=1} c_j F(n-j)
with variable-timestep coefficients. The SBDF family generates its
coefficients from Lagrange derivative/extrapolation weights (equivalent to
the reference's closed forms from Wang & Ruuth 2008, JCM 26).

IMEX Runge-Kutta form (reference: core/timesteppers.py:486 RungeKuttaIMEX,
tableaux from Ascher, Ruuth & Spiteri 1997):
    M.X(i) - M.X(0) = dt * sum_j [ A[i,j] F(j) - H[i,j] L.X(j) ]

Device design: each step is ONE jitted call (gather -> F evaluation with
transforms -> batched LU solve -> scatter); the LHS factorization
(a0*M + b0*L or M + dt*H[i,i]*L) is recomputed only when the leading
coefficients change (reference: core/timesteppers.py:123-128,160-168).
"""

import contextlib

import numpy as np
import jax
import jax.numpy as jnp

from ..libraries import pencilops
from ..tools.jitlift import lifted_jit
from ..tools.config import config
from ..tools import tracing

schemes = {}


def _mesh_pin(solver):
    """
    Pencil-sharding pin for step-program intermediates: when the solver is
    distributed (parallel/sharding.distribute_solver recorded a mesh on the
    distributor), XLA's sharding propagation alone does NOT keep the
    factor/solve boundary sharded — the factored LHS comes back replicated
    and every solve then all-gathers its RHS (observed on the virtual CPU
    mesh). Returns pin(tree, lead=0): constrains every array leaf whose
    `lead` axis is the pencil-group axis (length G) onto the mesh's first
    axis; identity when no mesh is active, so unsharded runs trace zero
    extra ops. Resolved at trace time (closure over the solver) so the
    same step bodies serve both the unsharded and post-distribute traces.
    """
    mesh = getattr(solver.dist, "mesh", None)
    if mesh is None:
        return lambda tree, lead=0: tree
    from jax.sharding import NamedSharding, PartitionSpec
    name = mesh.axis_names[0]
    n = mesh.shape[name]
    G = solver.pencil_shape[0]

    def pin(tree, lead=0):
        def one(a):
            ndim = getattr(a, "ndim", None)
            # only pencil-batched leaves: chunked banded factors (leading
            # chunk axis) and scalars pass through unconstrained
            if ndim is None or ndim <= lead or a.shape[lead] != G or G % n:
                return a
            spec = [None] * ndim
            spec[lead] = name
            return jax.lax.with_sharding_constraint(
                a, NamedSharding(mesh, PartitionSpec(*spec)))
        return jax.tree.map(one, tree)

    return pin


def _use_split_step(solver):
    """
    Whether to compile the step as SEVERAL small device programs (per-stage
    eval/solve dispatches) instead of one fused program. Monolithic step
    programs at very large pencil counts have wedged the TPU AOT compiler;
    above the mode threshold the ~ms of extra per-step dispatch latency is
    negligible against the per-step device time.
    """
    mode = config["execution"].get("STEP_PROGRAM", "auto").lower()
    if mode in ("fused", "split"):
        return mode == "split"
    G, S = solver.pencil_shape
    threshold = int(config["execution"].get("STEP_SPLIT_MODES", str(1 << 22)))
    return G * S > threshold


def add_scheme(cls):
    schemes[cls.__name__] = cls
    return cls


def _lagrange_derivative_weights(nodes):
    """Weights w: sum_j w_j p(nodes_j) = p'(0) for all deg < len(nodes)."""
    n = len(nodes)
    V = np.vander(np.asarray(nodes, dtype=float), n, increasing=True).T
    d = np.zeros(n)
    if n > 1:
        d[1] = 1.0
    return np.linalg.solve(V, d)


def _lagrange_extrapolation_weights(nodes):
    """Weights e: sum_j e_j p(nodes_j) = p(0)."""
    n = len(nodes)
    V = np.vander(np.asarray(nodes, dtype=float), n, increasing=True).T
    d = np.zeros(n)
    d[0] = 1.0
    return np.linalg.solve(V, d)


def _past_times(dt_hist, s):
    """[0, -k0, -(k0+k1), ...] for s+1 time levels."""
    times = [0.0]
    acc = 0.0
    for j in range(s):
        acc += dt_hist[j]
        times.append(-acc)
    return times


def _ensure_lhs(stepper, key, dt, *coeffs):
    """Refactor the stepper's LHS for `coeffs` unless `key` is the one it
    holds: the one place a step refactors, and the `step/factor` span
    (one per refactorization, live only when something looks)."""
    if key == stepper._lhs_key:
        return
    solver = stepper.solver
    rd = solver.real_dtype
    # the run's first factorization is cold start: waited for and booked
    # as the build's `factor` phase (with the upload of M and L), so that
    # `compile` is what is left of the first step
    first = stepper._lhs_key is None
    booked = solver.build_phases.scope("factor") if first \
        else contextlib.nullcontext()
    with tracing.span("step/factor", {"dt": float(dt)}), booked:
        stepper._lhs_key = key
        stepper._lhs_aux = stepper._factor(
            solver.M_mat, solver.L_mat,
            *(jnp.asarray(c, dtype=rd) for c in coeffs))
        if first:
            jax.block_until_ready(stepper._lhs_aux)  # dedalus-lint: disable=DTL001


class MultistepIMEX:
    """Base multistep IMEX integrator (reference: core/timesteppers.py:22)."""

    steps = None
    stages = 1

    def __init__(self, solver):
        self.solver = solver
        G, S = solver.pencil_shape
        s = self.steps
        # fused-step plan: the one the SOLVER resolved at build start
        # (core/solvers.py), so a mid-build/mid-run config edit can
        # never split one scheme across two compositions; donation
        # applies to the fused (non-split) step programs only
        from .fusedstep import resolve_fusion
        self._fusion = getattr(solver, "_fusion_plan", None) \
            or resolve_fusion()
        self._split = _use_split_step(solver)
        self.donates_histories = self._fusion.donate and not self._split
        # three DISTINCT zero buffers: the donating step program aliases
        # each history input to its output, so sharing one interned zeros
        # array across the three would alias two donated params
        self.F_hist = jnp.zeros((s, G, S), dtype=solver.pencil_dtype)
        self.MX_hist = jnp.zeros((s, G, S), dtype=solver.pencil_dtype)
        self.LX_hist = jnp.zeros((s, G, S), dtype=solver.pencil_dtype)
        self.dt_hist = []
        self._lhs_key = None
        self._lhs_aux = None
        self.iteration = 0
        # per-run state lives in the block above; reset_run() must mirror
        # any addition here or pooled served runs stop bit-matching fresh
        # solves (tests/test_service.py::test_pool_reset_bit_identity)

        eval_F = solver.eval_F
        from ..tools.jitlift import device_constant
        mask_np, mask_dt = solver.valid_row_mask, solver.real_dtype
        # resolved inside each trace so the (G, S) mask is lifted to a
        # program argument instead of an inline constant
        mask = lambda: device_constant(mask_np, dtype=mask_dt)
        ops = solver.ops

        # M and L are explicit arguments (not closure constants) so the
        # compiled HLO stays small and the arrays live as device buffers.
        def _factor_body(M, L, a0, b0):
            # pinned + shard_map-routed: an unconstrained factor replicates
            # under GSPMD (forcing an all-gather into every solve), and the
            # pivoted-LU custom calls are unpartitionable without the
            # pencil_mesh shard_map routing (libraries/pencilops.py)
            with pencilops.pencil_mesh(getattr(solver.dist, "mesh", None)):
                return _mesh_pin(solver)(ops.factor_lincomb(a0, M, b0, L))
        _factor_jit = lifted_jit(_factor_body)
        G = solver.pencil_shape[0]
        itemsize = np.dtype(solver.pencil_dtype).itemsize

        def _factor(M, L, a0, b0):
            # very large factor outputs go chunk-by-chunk in separate
            # dispatches (caps the transient HBM peak; pencilops)
            if (hasattr(ops, "use_incremental_factor")
                    and ops.use_incremental_factor(G, itemsize)):
                return ops.factor_lincomb_incremental(a0, M, L, b_scale=b0)
            return _factor_jit(M, L, a0, b0)

        # the fused step body composes the same two pieces the split mode
        # dispatches separately, so the numerics cannot drift between modes
        pair = (self._fusion.matvec and hasattr(ops, "matvec_pair"))

        def eval_parts(M, L, X, t, extra):
            pin = _mesh_pin(solver)
            if pair:
                # one-pass M/L pair (bitwise-identical components;
                # core/fusedstep.py FUSED_MATVEC)
                MXn, LXn = ops.matvec_pair(M, L, X)
            else:
                MXn, LXn = ops.matvec(M, X), ops.matvec(L, X)
            return pin((eval_F(X, t, extra) * mask(), MXn, LXn))

        def update_solve(Fn, MXn, LXn, F_hist, MX_hist, LX_hist, a, b, c,
                         lhs_aux, M, L):
            pin = _mesh_pin(solver)
            F_hist = jnp.concatenate([Fn[None], F_hist[:-1]])
            MX_hist = jnp.concatenate([MXn[None], MX_hist[:-1]])
            LX_hist = jnp.concatenate([LXn[None], LX_hist[:-1]])
            RHS = (jnp.tensordot(c, F_hist, axes=1)
                   - jnp.tensordot(a[1:], MX_hist, axes=1)
                   - jnp.tensordot(b[1:], LX_hist, axes=1))
            with pencilops.pencil_mesh(getattr(solver.dist, "mesh", None)):
                Xn = pin(ops.solve(lhs_aux, RHS, mats=(M, L)))
            return Xn, pin(F_hist, lead=1), pin(MX_hist, lead=1), \
                pin(LX_hist, lead=1)

        def advance_body(M, L, X, t, extra, F_hist, MX_hist, LX_hist, a, b, c,
                         lhs_aux):
            with jax.named_scope("dedalus/step/advance"):
                Fn, MXn, LXn = eval_parts(M, L, X, t, extra)
                return update_solve(Fn, MXn, LXn, F_hist, MX_hist, LX_hist,
                                    a, b, c, lhs_aux, M, L)

        def _advance_n(M, L, X, t, extra, F_hist, MX_hist, LX_hist, a, b, c,
                       n, dt, lhs_aux):
            # n constant-coefficient steps in one lax.scan dispatch
            def body(carry, _):
                X, t, Fh, MXh, LXh = carry
                Xn, Fh, MXh, LXh = advance_body(M, L, X, t, extra, Fh, MXh,
                                                LXh, a, b, c, lhs_aux)
                return (Xn, t + dt, Fh, MXh, LXh), None
            carry, _ = jax.lax.scan(body, (X, t, F_hist, MX_hist, LX_hist),
                                    None, length=n)
            Xn, _, F_hist, MX_hist, LX_hist = carry
            return Xn, F_hist, MX_hist, LX_hist

        self._factor = _factor
        # the fused whole-step programs donate the history buffers
        # (args 5-7: F/MX/LX) when DONATE_STEP is on, so XLA rolls the
        # histories in place instead of allocating fresh ones each step;
        # cross-step reference holders (snapshot ring, async checkpoint
        # capture, the probe cache below) copy under donates_histories
        donate = (5, 6, 7) if self.donates_histories else ()
        self._advance = lifted_jit(advance_body, donate_argnums=donate)
        self._advance_n = lifted_jit(_advance_n, static_argnums=(11,),
                                     donate_argnums=donate)
        # non-donating twin for the fused-phase probe: a donating program
        # would consume the probe cache's snapshot inputs on first use
        # (compiled once at warmup end, outside measured windows)
        self._advance_probe = self._advance if not donate \
            else lifted_jit(advance_body)
        # ensemble hook (core/ensemble.py): the raw, un-jitted step body,
        # vmapped over a leading member axis by EnsembleSolver — the same
        # composition the fused program compiles, so fleet numerics cannot
        # drift from the serial step
        self.advance_body = advance_body

        # split-step pieces: the SAME bodies the fused program composes,
        # compiled as separate (smaller) device programs for very large
        # systems (see _use_split_step; self._split set in __init__ ahead
        # of the donation wiring)
        self._eval_parts = lifted_jit(eval_parts)
        self._update_solve = lifted_jit(update_solve)

    def compute_coefficients(self, dt_hist, order):
        """Return (a[0..order], b[0..order], c[1..order])."""
        raise NotImplementedError

    def _pad_coeffs(self, a, b, c):
        """Pad (a, b, c) to the stationary lengths (s+1, s+1, s) that
        advance_body consumes, exactly as step() does."""
        s = self.steps
        a = np.concatenate([a, np.zeros(s + 1 - len(a))])
        b = np.concatenate([b, np.zeros(s + 1 - len(b))])
        c = np.concatenate([c, np.zeros(s - len(c))])
        return a, b, c

    def coefficient_schedule(self, dt, n):
        """
        Host-side constant-dt coefficient schedule for an n-step run from
        a FRESH history (zero F/MX/LX hists), replaying exactly what n
        calls of step(dt) would produce: the startup ramp's per-step
        padded (a, b, c) triples (orders 1..min(s-1, n)) followed by the
        stationary triple covering every later step. The differentiable
        scan (core/adjoint.py) consumes this so adjoint forward passes
        are bit-identical to the stepping loop.
        """
        s = self.steps
        dt = float(dt)
        ramp = []
        for it in range(1, min(s - 1, int(n)) + 1):
            a, b, c = self.compute_coefficients([dt] * it, it)
            ramp.append(self._pad_coeffs(a, b, c))
        a, b, c = self.compute_coefficients([dt] * s, s)
        return ramp, self._pad_coeffs(a, b, c)

    def reset_run(self):
        """Rewind per-run state to just-constructed values IN PLACE (the
        warm-pool service's between-request reset, service/pool.py) —
        the instance survives because it owns the compiled step
        programs. The multistep ramp restarts; the LHS factorization
        cache (_lhs_key/_lhs_aux) is deliberately KEPT: it is a pure
        function of (M, L, scheme coefficients, dt history), all
        request-invariant on one pooled solver, and step() re-keys it
        whenever the dt pattern differs — exactly the check a fresh
        solver performs."""
        solver = self.solver
        G, S = solver.pencil_shape
        # distinct buffers: see __init__ (donated inputs must not alias)
        self.F_hist = jnp.zeros((self.steps, G, S),
                                dtype=solver.pencil_dtype)
        self.MX_hist = jnp.zeros((self.steps, G, S),
                                 dtype=solver.pencil_dtype)
        self.LX_hist = jnp.zeros((self.steps, G, S),
                                 dtype=solver.pencil_dtype)
        self.dt_hist = []
        self.iteration = 0

    def step(self, dt, wall_time=None):
        solver = self.solver
        s = self.steps
        self.dt_hist = [float(dt)] + self.dt_hist[:s - 1]
        self.iteration += 1
        order = min(s, self.iteration)
        a, b, c = self._pad_coeffs(
            *self.compute_coefficients(self.dt_hist, order))
        key = (round(float(a[0]), 14), round(float(b[0]), 14))
        rd = self.solver.real_dtype
        _ensure_lhs(self, key, dt, a[0], b[0])
        if self._split:
            Fn, MXn, LXn = self._eval_parts(
                solver.M_mat, solver.L_mat, solver.X,
                jnp.asarray(solver.sim_time, dtype=rd), solver.rhs_extra())
            X, self.F_hist, self.MX_hist, self.LX_hist = self._update_solve(
                Fn, MXn, LXn, self.F_hist, self.MX_hist, self.LX_hist,
                jnp.asarray(a, dtype=rd), jnp.asarray(b, dtype=rd),
                jnp.asarray(c, dtype=rd), self._lhs_aux,
                solver.M_mat, solver.L_mat)
        else:
            X, self.F_hist, self.MX_hist, self.LX_hist = self._advance(
                solver.M_mat, solver.L_mat, solver.X,
                jnp.asarray(solver.sim_time, dtype=rd), solver.rhs_extra(),
                self.F_hist, self.MX_hist, self.LX_hist, jnp.asarray(a, dtype=rd),
                jnp.asarray(b, dtype=rd), jnp.asarray(c, dtype=rd), self._lhs_aux)
        solver.X = X
        solver.sim_time = float(solver.sim_time) + float(dt)

    def step_many(self, n, dt):
        """
        n constant-dt steps in one device dispatch. The startup ramp (order
        build-up) and any dt change run as single steps until the multistep
        coefficients are stationary; the remainder scans on device.
        """
        solver = self.solver
        s = self.steps
        n = int(n)
        if self._split:
            # split mode targets huge systems where per-step device time
            # dominates dispatch latency; no need for the scanned block
            for _ in range(n):
                self.step(dt)
            return
        while n > 0 and not (self.iteration >= s
                             and len(self.dt_hist) == s
                             and all(abs(k - float(dt)) < 1e-15 * abs(dt)
                                     for k in self.dt_hist)):
            self.step(dt)
            n -= 1
        if n == 0:
            return
        rd = solver.real_dtype
        a, b, c = self.compute_coefficients(self.dt_hist, s)
        key = (round(float(a[0]), 14), round(float(b[0]), 14))
        _ensure_lhs(self, key, dt, a[0], b[0])
        X, self.F_hist, self.MX_hist, self.LX_hist = self._advance_n(
            solver.M_mat, solver.L_mat, solver.X,
            jnp.asarray(solver.sim_time, dtype=rd), solver.rhs_extra(),
            self.F_hist, self.MX_hist, self.LX_hist,
            jnp.asarray(a, dtype=rd), jnp.asarray(b, dtype=rd),
            jnp.asarray(c, dtype=rd), n, jnp.asarray(float(dt), dtype=rd),
            self._lhs_aux)
        solver.X = X
        solver.sim_time = float(solver.sim_time) + n * float(dt)
        self.iteration += n

    def phase_probes(self):
        """Measurement thunks re-running the already-compiled step pieces
        (eval vs. solve) on a snapshot of the current state — no state
        mutation: {name: (thunk, per-step scale)}. None until the first
        step has factored the LHS. Probe inputs are cached per LHS key:
        dense/banded compute time is value-independent, so stale values
        time the same programs without re-deriving fresh stage inputs each
        sample — but a dt/coefficient change drops the cache so the
        superseded factorization (the largest device allocation) is not
        pinned by the thunk closures. The cache does pin a handful of
        state-sized buffers (X snapshot, eval parts, the history tuple)
        for the run — a few (G, S) arrays, small next to the factors and
        band/dense stores."""
        if self._lhs_aux is None or not self.dt_hist:
            return None
        cache = getattr(self, "_probe_cache", None)
        if cache is not None and cache[0] != self._lhs_key:
            cache = None
        if cache is None:
            solver = self.solver
            rd = solver.real_dtype
            s = self.steps
            M, L, X = solver.M_mat, solver.L_mat, solver.X
            t = jnp.asarray(float(solver.sim_time), dtype=rd)
            extra = solver.rhs_extra()
            a, b, c = self._pad_coeffs(*self.compute_coefficients(
                self.dt_hist, min(s, max(self.iteration, 1))))
            aj, bj, cj = (jnp.asarray(v, dtype=rd) for v in (a, b, c))
            Fn, MXn, LXn = self._eval_parts(M, L, X, t, extra)
            # probe-input warm: runs once per LHS key under the metrics
            # cadence gate, never in the measured step path
            jax.block_until_ready((Fn, MXn, LXn))  # dedalus-lint: disable=DTL001
            # the probe cache holds cross-step references: copy under
            # donation (the shared contract lives in guard_histories)
            from .fusedstep import guard_histories
            hists = guard_histories(self)
            lhs_aux = self._lhs_aux

            def eval_thunk():
                return self._eval_parts(M, L, X, t, extra)

            def solve_thunk():
                return self._update_solve(Fn, MXn, LXn, *hists,
                                          aj, bj, cj, lhs_aux, M, L)

            probes = {"rhs_eval": (eval_thunk, 1.0),
                      "matsolve": (solve_thunk, 1.0)}
            if not self._split:
                # the whole fused step program (transform -> solve in one
                # dispatch), probed via the non-donating twin: the
                # `fused` row of the sampled phase table (tools/metrics)
                def fused_thunk():
                    return self._advance_probe(M, L, X, t, extra, *hists,
                                               aj, bj, cj, lhs_aux)

                probes["fused_step"] = (fused_thunk, 1.0)
            cache = self._probe_cache = (self._lhs_key, probes)
        return cache[1]


@add_scheme
class CNAB1(MultistepIMEX):
    """Crank-Nicolson / Adams-Bashforth 1 (reference: core/timesteppers.py:179)."""
    steps = 1

    def compute_coefficients(self, dt_hist, order):
        k0 = dt_hist[0]
        return np.array([1/k0, -1/k0]), np.array([0.5, 0.5]), np.array([1.0])


@add_scheme
class SBDF1(MultistepIMEX):
    """1st-order semi-implicit BDF / backward Euler (reference: :212)."""
    steps = 1

    def compute_coefficients(self, dt_hist, order):
        k0 = dt_hist[0]
        return np.array([1/k0, -1/k0]), np.array([1.0, 0.0]), np.array([1.0])


class SBDFBase(MultistepIMEX):
    """Variable-step SBDF via Lagrange weights."""

    def compute_coefficients(self, dt_hist, order):
        p = min(order, self.steps)
        times = _past_times(dt_hist, p)
        a = _lagrange_derivative_weights(times)
        b = np.zeros(p + 1)
        b[0] = 1.0
        c = _lagrange_extrapolation_weights(times[1:])
        return a, b, c


@add_scheme
class SBDF2(SBDFBase):
    """2nd-order SBDF (reference: core/timesteppers.py:321)."""
    steps = 2


@add_scheme
class SBDF3(SBDFBase):
    """3rd-order SBDF (reference: core/timesteppers.py:398)."""
    steps = 3


@add_scheme
class SBDF4(SBDFBase):
    """4th-order SBDF (reference: core/timesteppers.py:439)."""
    steps = 4


@add_scheme
class CNAB2(MultistepIMEX):
    """Crank-Nicolson / Adams-Bashforth 2 (reference: :244)."""
    steps = 2

    def compute_coefficients(self, dt_hist, order):
        if order == 1:
            return CNAB1.compute_coefficients(self, dt_hist, order)
        k0, k1 = dt_hist[0], dt_hist[1]
        w = k0 / k1
        a = np.array([1/k0, -1/k0, 0.0])
        b = np.array([0.5, 0.5, 0.0])
        c = np.array([1 + w/2, -w/2])
        return a, b, c


@add_scheme
class MCNAB2(MultistepIMEX):
    """Modified CNAB2 (Wang & Ruuth 2008; reference: :282)."""
    steps = 2

    def compute_coefficients(self, dt_hist, order):
        if order == 1:
            return CNAB1.compute_coefficients(self, dt_hist, order)
        k0, k1 = dt_hist[0], dt_hist[1]
        w = k0 / k1
        a = np.array([1/k0, -1/k0, 0.0])
        b = np.array([(8 + 1/w)/16, (7 - 1/w)/16, 1/16])  # Wang 2008 eqn 2.10
        c = np.array([1 + w/2, -w/2])
        return a, b, c


@add_scheme
class CNLF2(MultistepIMEX):
    """Crank-Nicolson leapfrog (reference: core/timesteppers.py:359)."""
    steps = 2

    def compute_coefficients(self, dt_hist, order):
        if order == 1:
            return CNAB1.compute_coefficients(self, dt_hist, order)
        k0, k1 = dt_hist[0], dt_hist[1]
        w = k0 / k1
        # Wang 2008 eqn 2.11 (variable-step leapfrog + wide Crank-Nicolson)
        a = np.array([1/((1 + w)*k0), (w - 1)/k0, -w**2/((1 + w)*k0)])
        b = np.array([1/(2*w), (1 - 1/w)/2, 0.5])
        c = np.array([1.0, 0.0])
        return a, b, c


class RungeKuttaIMEX:
    """IMEX Runge-Kutta base (reference: core/timesteppers.py:486)."""

    stages = None
    A = None  # explicit tableau (s+1, s+1)
    H = None  # implicit tableau (s+1, s+1)
    c = None  # stage times (s+1,)
    steps = 1

    def __init__(self, solver):
        self.solver = solver
        self.iteration = 0
        self._lhs_key = None
        self._lhs_aux = None
        # RK stages carry no cross-step history buffers: nothing to
        # donate (the fused-solve/matvec layers of core/fusedstep.py
        # apply through solver.ops regardless; plan kept for
        # introspection parity with MultistepIMEX)
        from .fusedstep import resolve_fusion
        self._fusion = getattr(solver, "_fusion_plan", None) \
            or resolve_fusion()
        self.donates_histories = False

        eval_F = solver.eval_F  # (reset_run mirrors the per-run state)
        rd = solver.real_dtype
        from ..tools.jitlift import device_constant
        mask_np = solver.valid_row_mask
        mask = lambda: device_constant(mask_np, dtype=rd)
        A = jnp.asarray(self.A, dtype=rd)
        H = jnp.asarray(self.H, dtype=rd)
        c = jnp.asarray(self.c, dtype=rd)
        s = self.stages
        ops = solver.ops
        one = jnp.asarray(1.0, dtype=rd)

        # M and L are explicit arguments (not closure constants): keeps the
        # compiled HLO small and shares one device buffer across calls.
        # Stages with equal implicit diagonal coefficients H[i,i] share one
        # factorization (all ARS tableaux here have constant diagonals, so
        # typically a single LHS factor serves every stage).
        H_diag = [float(self.H[i, i]) for i in range(1, s + 1)]
        uniq = sorted(set(H_diag))
        stage_slot = [uniq.index(h) for h in H_diag]

        # one factorization per UNIQUE implicit diagonal; the per-stage list
        # is assembled OUTSIDE the jit so stages sharing a factor alias the
        # same device buffers instead of duplicating the jit's outputs
        def _factor_uniq(M, L, dt):
            # pinned + shard_map-routed: see MultistepIMEX._factor_body
            pin = _mesh_pin(solver)
            with pencilops.pencil_mesh(getattr(solver.dist, "mesh", None)):
                return [pin(ops.factor_lincomb(one, M, dt * h, L))
                        for h in uniq]
        _factor_uniq = lifted_jit(_factor_uniq)
        G = solver.pencil_shape[0]
        itemsize = np.dtype(solver.pencil_dtype).itemsize

        def _factor(M, L, dt):
            # very large factor outputs go chunk-by-chunk in separate
            # dispatches (caps the transient HBM peak; pencilops)
            if (hasattr(ops, "use_incremental_factor")
                    and ops.use_incremental_factor(G, itemsize)):
                auxs = [ops.factor_lincomb_incremental(one, M, L,
                                                       b_scale=dt * h)
                        for h in uniq]
            else:
                auxs = _factor_uniq(M, L, dt)
            return [auxs[j] for j in stage_slot]
        self._factor_uniq = _factor_uniq

        # the fused step body composes the same per-stage pieces the split
        # mode dispatches separately, so the numerics cannot drift
        def stage_eval(M, L, Xi, ti, extra):
            pin = _mesh_pin(solver)
            return pin((ops.matvec(L, Xi), eval_F(Xi, ti, extra) * mask()))

        def stage_solve(i, MX0, Fs, LXs, dt, lhs_aux, M, L):
            RHS = MX0
            for j in range(i):
                RHS = RHS + dt * (A[i, j] * Fs[j] - H[i, j] * LXs[j])
            with pencilops.pencil_mesh(getattr(solver.dist, "mesh", None)):
                return _mesh_pin(solver)(ops.solve(lhs_aux, RHS,
                                                   mats=(M, L)))

        def step_body(M, L, X0, t0, dt, extra, lhs_auxs):
            MX0 = ops.matvec(M, X0)
            LXs = []
            Fs = []
            Xi = X0
            for i in range(1, s + 1):
                with jax.named_scope(f"dedalus/step/stage{i}"):
                    LXi, Fi = stage_eval(M, L, Xi, t0 + c[i - 1] * dt, extra)
                    LXs.append(LXi)
                    Fs.append(Fi)
                    Xi = stage_solve(i, MX0, Fs, LXs, dt, lhs_auxs[i - 1],
                                     M, L)
            return Xi

        def _step_n(M, L, X0, t0, dt, extra, lhs_auxs, n):
            # n device steps in one lax.scan: one dispatch per block
            # instead of per step (small problems are host-latency bound)
            def body(carry, _):
                X, t = carry
                Xn = step_body(M, L, X, t, dt, extra, lhs_auxs)
                return (Xn, t + dt), None
            (Xn, _), _ = jax.lax.scan(body, (X0, t0), None, length=n)
            return Xn

        self._factor = _factor
        self._step = lifted_jit(step_body)
        self._step_n = lifted_jit(_step_n, static_argnums=(7,))
        # ensemble hooks (core/ensemble.py): the raw step body for member
        # vmapping, plus the unique-implicit-diagonal bookkeeping so the
        # per-member-dt mode can vmap its own factorization
        self.step_body = step_body
        self.uniq_H_diag = uniq
        self.stage_slot = stage_slot

        # split-step pieces: the SAME per-stage bodies the fused program
        # composes, compiled as separate device programs (see _use_split_step)
        self._split = _use_split_step(solver)
        self._mx0 = lifted_jit(lambda M, X0: ops.matvec(M, X0))
        self._stage_eval = lifted_jit(stage_eval)
        self._stage_solve = lifted_jit(stage_solve, static_argnums=(0,))

    def _step_split(self, dt):
        solver = self.solver
        rd = solver.real_dtype
        M, L = solver.M_mat, solver.L_mat
        extra = solver.rhs_extra()
        dtj = jnp.asarray(float(dt), dtype=rd)
        t0 = float(solver.sim_time)
        MX0 = self._mx0(M, solver.X)
        Fs, LXs = [], []
        Xi = solver.X
        for i in range(1, self.stages + 1):
            # stage time in rd arithmetic (t0 + c*dt term-by-term), exactly
            # matching the fused body's on-device rd computation
            ti = jnp.asarray(rd.type(t0)
                             + rd.type(self.c[i - 1]) * rd.type(dt), dtype=rd)
            LXi, Fi = self._stage_eval(M, L, Xi, ti, extra)
            LXs.append(LXi)
            Fs.append(Fi)
            Xi = self._stage_solve(i, MX0, Fs, LXs, dtj,
                                   self._lhs_aux[i - 1], M, L)
        return Xi

    def reset_run(self):
        """Per-run reset (see MultistepIMEX.reset_run): RK schemes carry
        no ramp history, only the step count; the LHS factorization
        cache is deliberately kept — _ensure_factor re-keys on dt."""
        self.iteration = 0

    def _ensure_factor(self, dt):
        _ensure_lhs(self, round(float(dt), 14), dt, float(dt))

    def step(self, dt, wall_time=None):
        solver = self.solver
        rd = solver.real_dtype
        self._ensure_factor(dt)
        if self._split:
            solver.X = self._step_split(dt)
        else:
            solver.X = self._step(solver.M_mat, solver.L_mat, solver.X,
                                  jnp.asarray(solver.sim_time, dtype=rd),
                                  jnp.asarray(float(dt), dtype=rd),
                                  solver.rhs_extra(), self._lhs_aux)
        solver.sim_time = float(solver.sim_time) + float(dt)
        self.iteration += 1

    def step_many(self, n, dt):
        """n constant-dt steps in one device dispatch (lax.scan); split
        mode steps singly (dispatch latency is negligible at that size)."""
        solver = self.solver
        rd = solver.real_dtype
        self._ensure_factor(dt)
        if self._split:
            for _ in range(int(n)):
                self.step(dt)
            return
        solver.X = self._step_n(solver.M_mat, solver.L_mat, solver.X,
                                jnp.asarray(solver.sim_time, dtype=rd),
                                jnp.asarray(float(dt), dtype=rd),
                                solver.rhs_extra(), self._lhs_aux, int(n))
        solver.sim_time = float(solver.sim_time) + n * float(dt)
        self.iteration += n

    def phase_probes(self):
        """Measurement thunks re-running one already-compiled stage (eval
        vs. solve) on a snapshot of the current state — no state mutation:
        {name: (thunk, per-step scale)}, scale = stages. None until the
        first step has factored the LHS. Stage inputs are cached per LHS
        key (stage compute time is value-independent); a dt change drops
        the cache so the superseded factorization is not pinned. The
        cache does pin a few state-sized buffers (X snapshot, one stage's
        MX0/LX/F) for the run — small next to the factors."""
        if self._lhs_aux is None:
            return None
        cache = getattr(self, "_probe_cache", None)
        if cache is not None and cache[0] != self._lhs_key:
            cache = None
        if cache is None:
            solver = self.solver
            rd = solver.real_dtype
            M, L, X = solver.M_mat, solver.L_mat, solver.X
            t = jnp.asarray(float(solver.sim_time), dtype=rd)
            dtj = jnp.asarray(float(self._lhs_key or 0.0), dtype=rd)
            extra = solver.rhs_extra()
            s = float(self.stages)
            MX0 = self._mx0(M, X)
            LX1, F1 = self._stage_eval(M, L, X, t, extra)
            # probe-input warm: runs once per LHS key under the metrics
            # cadence gate, never in the measured step path
            jax.block_until_ready((MX0, LX1, F1))  # dedalus-lint: disable=DTL001
            aux0 = self._lhs_aux[0]

            def eval_thunk():
                return self._stage_eval(M, L, X, t, extra)

            def solve_thunk():
                return self._stage_solve(1, MX0, [F1], [LX1], dtj, aux0,
                                         M, L)

            probes = {"rhs_eval": (eval_thunk, s),
                      "matsolve": (solve_thunk, s)}
            if not self._split:
                # the whole fused step program (all stages in one
                # dispatch); non-mutating — step_body returns a fresh X
                lhs_auxs = self._lhs_aux

                def fused_thunk():
                    return self._step(M, L, X, t, dtj, extra, lhs_auxs)

                probes["fused_step"] = (fused_thunk, 1.0)
            cache = self._probe_cache = (self._lhs_key, probes)
        return cache[1]


@add_scheme
class RK111(RungeKuttaIMEX):
    """1st-order 1-stage IMEX RK (reference: core/timesteppers.py:636)."""
    stages = 1
    A = np.array([[0., 0.], [1., 0.]])
    H = np.array([[0., 0.], [0., 1.]])
    c = np.array([0., 1.])


@add_scheme
class RK222(RungeKuttaIMEX):
    """2nd-order 2-stage IMEX RK, ARS(2,2,2) (reference: :651)."""
    stages = 2
    _gamma = (2. - np.sqrt(2.)) / 2.
    _delta = 1. - 1. / (2. * _gamma)
    A = np.array([[0., 0., 0.],
                  [_gamma, 0., 0.],
                  [_delta, 1. - _delta, 0.]])
    H = np.array([[0., 0., 0.],
                  [0., _gamma, 0.],
                  [0., 1. - _gamma, _gamma]])
    c = np.array([0., _gamma, 1.])


@add_scheme
class RKSMR(RungeKuttaIMEX):
    """(3-eps)-order 3-stage DIRK+ERK scheme of Spalart, Moser & Rogers
    (1991, Appendix); coefficients are the published constants
    (reference: core/timesteppers.py:692 RKSMR)."""
    stages = 3
    _a1, _a2, _a3 = (29/96, -3/40, 1/6)
    _b1, _b2, _b3 = (37/160, 5/24, 1/6)
    _g1, _g2, _g3 = (8/15, 5/12, 3/4)
    _z2, _z3 = (-17/60, -5/12)
    A = np.array([[0., 0., 0., 0.],
                  [_g1, 0., 0., 0.],
                  [_g1 + _z2, _g2, 0., 0.],
                  [_g1 + _z2, _g2 + _z3, _g3, 0.]])
    H = np.array([[0., 0., 0., 0.],
                  [_a1, _b1, 0., 0.],
                  [_a1, _b1 + _a2, _b2, 0.],
                  [_a1, _b1 + _a2, _b2 + _a3, _b3]])
    c = np.array([0., 8/15, 2/3, 1.])


@add_scheme
class RK443(RungeKuttaIMEX):
    """3rd-order 4-stage IMEX RK, ARS(4,4,3) (reference: :671)."""
    stages = 4
    A = np.array([[0., 0., 0., 0., 0.],
                  [1/2, 0., 0., 0., 0.],
                  [11/18, 1/18, 0., 0., 0.],
                  [5/6, -5/6, 1/2, 0., 0.],
                  [1/4, 7/4, 3/4, -7/4, 0.]])
    H = np.array([[0., 0., 0., 0., 0.],
                  [0., 1/2, 0., 0., 0.],
                  [0., 1/6, 1/2, 0., 0.],
                  [0., -1/2, 1/2, 1/2, 0.],
                  [0., 3/2, -3/2, 1/2, 1/2]])
    c = np.array([0., 1/2, 2/3, 1/2, 1.])


@add_scheme
class RKGFY(RungeKuttaIMEX):
    """2nd-order 2-stage IMEX RK of Hollerbach & Marti (published
    tableau; reference keeps it unregistered at core/timesteppers.py:715
    — registered here for completeness)."""
    stages = 2
    A = np.array([[0., 0., 0.],
                  [1., 0., 0.],
                  [0.5, 0.5, 0.]])
    H = np.array([[0., 0., 0.],
                  [0.5, 0.5, 0.],
                  [0.5, 0., 0.5]])
    c = np.array([0., 1., 1.])


def step_program_handle(solver, dt=1e-3):
    """(program, args) of the solver's compiled single-step program — the
    shared inspection handle behind the compiled-program contract checker
    (tools/lint/progcheck.py), the collective-placement tests
    (tests/test_collectives.py) and benchmarks/scaling.py. `program` is
    the lifted_jit wrapper the step loop actually dispatches (multistep
    `_advance` / RK `_step`), so `program.lower(*args)` reproduces the
    executing program text — including the donate_argnums aliasing
    contract — and `program.jaxpr(*args)` its primitive structure.
    Requires a factored solver (one `solver.step(dt)` builds the LHS
    factorization); raises RuntimeError otherwise rather than lowering a
    program the step loop would never run.
    """
    ts = solver.timestepper
    if getattr(ts, "_lhs_aux", None) is None:
        raise RuntimeError(
            "step_program_handle needs a factored solver: call "
            "solver.step(dt) once before lowering the step program")
    rd = solver.real_dtype
    if isinstance(ts, MultistepIMEX):
        s = ts.steps + 1
        a = b = jnp.zeros(s, dtype=rd)
        c = jnp.zeros(ts.steps, dtype=rd)
        args = (solver.M_mat, solver.L_mat, solver.X,
                jnp.asarray(0.0, dtype=rd), solver.rhs_extra(),
                ts.F_hist, ts.MX_hist, ts.LX_hist, a, b, c, ts._lhs_aux)
        return ts._advance, args
    args = (solver.M_mat, solver.L_mat, solver.X,
            jnp.asarray(0.0, dtype=rd), jnp.asarray(float(dt), dtype=rd),
            solver.rhs_extra(), ts._lhs_aux)
    return ts._step, args

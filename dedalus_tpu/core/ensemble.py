"""
EnsembleSolver: one compiled step, thousands of simulations.

The production workload for a spectral-PDE service is rarely one big run —
it is parameter sweeps, uncertainty ensembles, and per-request scenarios:
thousands of *independent* IVPs that, stepped serially, each pay their own
dispatch and Python loop overhead. This module turns the repo's unit of
work from "a run" into "a fleet": it takes ONE built
`InitialValueSolver` (whose pencil matrices are already batched over
groups) and vmaps the timestepper's raw step body over a second, leading
**member** axis, then shards that axis over a 1-D
`jax.sharding.Mesh(("batch",))` so N members on D devices advance as one
XLA program — no per-member dispatch, no per-member compile, and (with a
common dt) ONE shared LHS factorization serving the whole fleet.

Batched operands per member:
  * initial conditions         — the gathered pencil state X, (N, G, S)
  * RHS parameters / NCC data  — every non-variable field feeding F
                                 (forcings, parameter fields) becomes a
                                 batched operand of the compiled step
  * simulation time            — (N,) device clock (members drift apart
                                 after drops/rewinds)
  * dt                         — (N,) operand; heterogeneous values need
                                 `per_member_dt=True` (RK schemes), which
                                 vmaps the LHS factorization too

Shared operands: the pencil matrices M/L, the (common-dt) factorization,
and the multistep coefficient vectors — replicated over the mesh.

Sharding layout (the SNIPPETS `get_naive_sharding` pattern): every
member-batched array leads with the member axis and is placed by ONE
`device_put` with `NamedSharding(mesh, P("batch"))`; the fleet step runs
inside `shard_map` over that axis (each device steps only its local
member block — XLA cannot partition fft/LU ops, so plain GSPMD would
all-gather; see core/meshctx.py and libraries/pencilops.shard_groups for
the same discipline on the group axis).

Per-member health: a jitted per-member probe (NaN/Inf count + max|coeff|)
runs on the PR-2 cadence machinery; a diverged member is restored from
its slot in the rolling fleet-snapshot ring (PR-4's capture-by-reference
trick — device arrays are immutable, so snapshots are O(1) and sync-free)
and either **dropped** (frozen + masked out, the default) or **rewound**
with a per-member dt backoff (`policy="rewind"`, RK + per_member_dt) —
without stopping the batch, and without retracing the compiled step (the
active mask is a value operand, not a shape).

Device loss: a fleet dispatch that loses a device (in production: an
XlaRuntimeError from the runtime; in tests: the chaos `lose_device`
fault) is reported through `notify_device_loss(d)` and handled before
the next dispatch — the fleet RE-SHARDS onto the surviving devices: live
member blocks are reconstructed host-side from the surviving shards
only, the lost device's members are restored from the newest finite
FleetSnapshot ring slot or from the last durable sharded checkpoint
(tools/dcheckpoint.py, `evolve(checkpoint_dir=...)`), a fresh 1-D mesh
over the survivors is built (members re-padded to the new device
multiple), and every block-memoized fleet program is rebuilt for the new
layout. Members with no finite snapshot and no checkpoint drop. Reshard
events are counted (`ensemble/reshards`) and itemized in
`reshard_events`.

Durable fleet checkpoints use the sharded format exclusively — each
device's member block is already the natural shard — written
synchronously or asynchronously on a cadence from `evolve`, and restored
ELASTICALLY: `restore_checkpoint` re-pads the true member rows onto
whatever mesh the restoring fleet has, so a checkpoint taken on 8
devices restores onto 4 or 1 (and vice versa) bit-identically.

Telemetry: `ensemble/...` counters (fleet_steps, member_steps, dropped,
rewinds, health_checks, reshards, checkpoints_written) plus an
`ensemble` summary block (members / active / dropped / reshards /
ensemble-steps-per-s) in every flushed record — `python -m dedalus_tpu
report` renders it as its own column set.

Serving (continuous batching, service/batching.py): a fleet can also be
driven as a **micro-batch of independent served requests** — members
attach (`attach_member`) and detach (`detach_member`) at block
boundaries as value operands (never a retrace), each carries its own
steps-remaining budget (`R`, carried through the scan so a finished
member freezes mid-block without leaving the compiled program), a
multistep member joining a running fleet replays its own order build-up
with everyone else frozen (`ramp_members` — bit-identical to a solo
run's ramp), per-member Hermitian-projection phases follow each
member's OWN iteration count (`project_members`), and `step_fleet`
dispatches steady blocks without the fleet-global cadence/ramp logic
the serving driver owns.
"""

import functools
import logging
import time as time_mod

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .subsystems import scatter_state, state_key
from . import timesteppers as timesteppers_mod
from ..tools import dcheckpoint
from ..tools import metrics as metrics_mod
from ..tools import retrace as retrace_mod
from ..tools.compat import shard_map
from ..tools.config import cfg_get
from ..tools.exceptions import CheckpointError

logger = logging.getLogger(__name__)

__all__ = ["EnsembleSolver", "FleetSnapshot"]

MEMBER_AXIS = "batch"

# default per-member steps-remaining budget: effectively unbounded (the
# classic evolve/step_many drivers stop the whole fleet, so members never
# exhaust it); the serving driver sets true per-request budgets
UNBOUNDED_STEPS = 1 << 30


def _repad(a, members, n_pad, pad_value=None):
    """Re-pad a member-leading host array onto a new padded length: the
    true member rows are kept, padding rows are clones of member 0 (or
    `pad_value`-filled for masks/counters). The single helper behind the
    two recovery paths that must stay bit-identical (device-loss reshard
    and elastic checkpoint restore)."""
    a = np.asarray(a)[:members]
    pad = n_pad - members
    if not pad:
        return a
    if pad_value is None:
        tail = np.broadcast_to(a[:1], (pad,) + a.shape[1:])
    else:
        tail = np.full((pad,) + a.shape[1:], pad_value, a.dtype)
    return np.concatenate([a, tail])


class FleetSnapshot:
    """One last-known-good capture of the whole fleet. Device arrays are
    held by REFERENCE (immutable), so capture is O(1) and never syncs;
    each member's slice doubles as that member's snapshot slot on the
    recovery path (restores are per-member `where` masks)."""

    __slots__ = ("X", "T", "hists", "iteration", "sim_times",
                 "wall_ts", "_finite", "_probe")

    def __init__(self, X, T, hists, iteration, sim_times, probe=None):
        self.X = X
        self.T = T
        self.hists = hists          # (F, MX, LX) or None for RK
        self.iteration = int(iteration)
        self.sim_times = np.array(sim_times)
        self.wall_ts = time_mod.time()
        self._finite = None
        self._probe = probe

    def member_finite(self, m):
        """Whether member m's captured state is fully finite. Routed
        through the fleet's jitted per-member probe (`probe` at capture):
        the reduction runs on device and only the (N,) nonfinite-count
        vector comes back — never the full fleet state. Recovery path
        only, never the stepping loop."""
        if self._finite is None:
            if self._probe is not None:
                nonfinite, _ = jax.device_get(self._probe(self.X))
                self._finite = np.asarray(nonfinite) == 0
            else:
                flat = np.asarray(self.X).reshape(self.X.shape[0], -1)
                self._finite = np.all(np.isfinite(flat), axis=1)
        return bool(self._finite[m])


class EnsembleSolver:
    """
    Fleet driver over one built `InitialValueSolver` template.

    Parameters
    ----------
    solver : InitialValueSolver
        The built template (undistributed, native-precision step path).
        Its state at construction seeds every member's default IC.
    members : int
        Number of ensemble members N.
    mesh : "auto" | None | jax.sharding.Mesh
        "auto" builds a 1-D Mesh(("batch",)) over all local devices when
        more than one is visible (the member count is padded up to a
        multiple of the device count with inactive clones); None disables
        sharding; an explicit 1-D mesh is used as given.
    per_member_dt : bool
        Carry dt as a genuinely heterogeneous (N,) operand, vmapping the
        LHS factorization per member (RK schemes only — multistep
        coefficient ramps are fleet-global). Required for
        policy="rewind"'s per-member dt backoff. Chosen at construction
        so the compiled program never switches variants mid-run (which
        would retrace).
    policy : "drop" | "rewind"
        What to do with a diverged member: freeze it at its newest
        finite snapshot slot and mask it out ("drop"), or restore it and
        retry with its dt scaled by `dt_backoff`, dropping after
        `max_member_retries` failed retries ("rewind").
    health_cadence, snapshot_cadence, ring_size, dt_backoff,
    max_member_retries :
        Recovery knobs; defaults from the [health]/[resilience] config
        sections.
    metrics, metrics_file :
        Fleet telemetry (tools/metrics.py); `metrics.iterations` counts
        MEMBER-steps, so the flushed `steps_per_sec` IS
        ensemble-steps-per-second.
    """

    def __init__(self, solver, members, mesh="auto", per_member_dt=False,
                 policy="drop", health_cadence=None, snapshot_cadence=None,
                 ring_size=None, dt_backoff=None, max_member_retries=None,
                 warmup_iterations=None, metrics=None, metrics_file=None):
        if getattr(solver, "_dd", None) is not None:
            raise ValueError(
                "EnsembleSolver requires the native step path; the template "
                "uses the emulated-f64 (double-double) runner. Build it "
                "with [execution] EMULATED_F64 = never.")
        if getattr(solver.dist, "mesh", None) is not None:
            raise ValueError(
                "EnsembleSolver shards the MEMBER axis; the template must "
                "be undistributed (no spatial mesh on the Distributor).")
        ts = solver.timestepper
        self._multistep = isinstance(ts, timesteppers_mod.MultistepIMEX)
        if not self._multistep and not isinstance(
                ts, timesteppers_mod.RungeKuttaIMEX):
            raise ValueError(f"Unsupported timestepper {type(ts).__name__}")
        if per_member_dt and self._multistep:
            raise ValueError(
                "per_member_dt requires a Runge-Kutta scheme (multistep "
                "coefficient ramps are fleet-global); use e.g. RK222.")
        if policy not in ("drop", "rewind"):
            raise ValueError(f"policy must be 'drop' or 'rewind', "
                             f"got {policy!r}")
        if policy == "rewind" and not per_member_dt:
            raise ValueError(
                "policy='rewind' retries with a per-member dt backoff; "
                "pass per_member_dt=True (RK schemes).")
        self.solver = solver
        self.timestepper = ts
        self.members = int(members)
        self.per_member_dt = bool(per_member_dt)
        self.policy = policy
        self.rd = solver.real_dtype
        # pencil axis of a 2-D batch x pencil mesh (None on 1-D meshes):
        # set by _resolve_mesh when the composition is active
        self.pencil_axis = None
        self.mesh = self._resolve_mesh(mesh)
        D = self.mesh.shape[MEMBER_AXIS] if self.mesh is not None else 1
        self.n_pad = -(-self.members // D) * D
        # ---------------------------------------------------- fleet state
        G, S = solver.pencil_shape
        X0 = solver.gather_fields()
        self.X = self._put(jnp.broadcast_to(X0, (self.n_pad, G, S)),
                           pencil_dim=1)
        self.sim_times = np.full(self.n_pad, float(solver.sim_time))
        self.T = self._put_host(self.sim_times, dtype=self.rd)
        self.dts = np.zeros(self.n_pad)
        self.DT = self._put(jnp.zeros(self.n_pad, dtype=self.rd))
        self.active_host = np.zeros(self.n_pad, dtype=bool)
        self.active_host[:self.members] = True
        self._active_dev = self._put_host(self.active_host)
        # per-member steps-remaining budget (host mirror + device value
        # operand carried through the fleet scan): a member whose budget
        # hits zero freezes mid-block — per-member stop without leaving
        # the compiled program. Unbounded by default.
        self.steps_left = np.full(self.n_pad, UNBOUNDED_STEPS,
                                  dtype=np.int64)
        self.R = self._put_host(self.steps_left, dtype=jnp.int32)
        if self._multistep:
            s = ts.steps
            zeros = jnp.zeros((self.n_pad, s, G, S),
                              dtype=solver.pencil_dtype)
            self.F_hist = self._put(zeros, pencil_dim=2)
            self.MX_hist = self._put(zeros, pencil_dim=2)
            self.LX_hist = self._put(zeros, pencil_dim=2)
            self._ms_iter = 0
            self._dt_hist = []
        # per-member RHS operands: every extra field batched (N, ...)
        self._extras = [self._put(jnp.broadcast_to(
            arr, (self.n_pad,) + arr.shape))
            for arr in solver.rhs_extra()]
        # ------------------------------------------------------- programs
        self._programs = {}
        self._project_prog = None
        self._probe_prog = None
        self._vfactor_prog = None
        self._lhs_key = None
        self._lhs_aux = None
        # ------------------------------------------------------- recovery
        self.iteration = 0
        self.ring = []
        self.ring_size = int(ring_size if ring_size is not None
                             else cfg_get("resilience", "RING_SNAPSHOTS", "4"))
        self.snapshot_cadence = int(
            snapshot_cadence if snapshot_cadence is not None
            else cfg_get("resilience", "SNAPSHOT_CADENCE", "50"))
        self.health_cadence = int(
            health_cadence if health_cadence is not None
            else cfg_get("health", "CHECK_CADENCE", "200"))
        self.max_abs_limit = float(cfg_get("health", "MAX_ABS_LIMIT", "1e12"))
        self.dt_backoff = float(dt_backoff if dt_backoff is not None
                                else cfg_get("resilience", "DT_BACKOFF", "0.5"))
        self.max_member_retries = int(
            max_member_retries if max_member_retries is not None
            else cfg_get("resilience", "MAX_RETRIES", "3"))
        self._health_gate = metrics_mod.CadenceGate(self.health_cadence)
        self._snapshot_gate = metrics_mod.CadenceGate(self.snapshot_cadence)
        self._retries = np.zeros(self.n_pad, dtype=int)
        self.dropped = []
        self.rewound = []
        # device-loss / reshard bookkeeping
        self._lost_devices = []
        self.reshard_events = []
        # durable sharded checkpoints (tools/dcheckpoint.py)
        self._checkpoint_dir = None
        self._checkpointer = None
        # ------------------------------------------------------ telemetry
        self.warmup_iterations = int(
            warmup_iterations if warmup_iterations is not None
            else solver.warmup_iterations)
        self._warmed = False
        self.metrics = metrics_mod.resolve(
            metrics, sink=metrics_file,
            meta={"config": f"ensemble[{self.members}]",
                  "backend": jax.default_backend(),
                  "dtype": str(np.dtype(solver.pencil_dtype)),
                  "pencil_shape": list(solver.pencil_shape),
                  "members": self.members})
        self.metrics.inc("ensemble/members", self.members)
        pencil_txt = (f" x {self.mesh.shape[self.pencil_axis]} pencil "
                      f"device(s)" if self.pencil_axis is not None else "")
        logger.info(
            f"EnsembleSolver: {self.members} members (padded {self.n_pad}) "
            f"on {D} batch device(s){pencil_txt}, "
            f"{'per-member' if self.per_member_dt else 'common'} dt, "
            f"policy={self.policy}")

    # ------------------------------------------------------------ plumbing

    def _resolve_mesh(self, mesh):
        if mesh is None:
            return None
        if mesh == "auto":
            devices = jax.devices()
            if len(devices) < 2:
                return None
            return Mesh(np.array(devices), (MEMBER_AXIS,))
        if len(mesh.axis_names) not in (1, 2):
            raise ValueError(
                "EnsembleSolver requires a 1-D member mesh or a 2-D "
                "batch x pencil mesh.")
        if mesh.axis_names[0] != MEMBER_AXIS:
            raise ValueError(
                f"member mesh axis must be named {MEMBER_AXIS!r} and "
                f"come first")
        if len(mesh.axis_names) == 2:
            # 2-D composition: members vmap over `batch` while every
            # member's pencil state distributes over the second axis —
            # the fleet programs run manual over batch with the pencil
            # axis in GSPMD auto mode, and the per-member transform
            # walks/solves route through meshctx/pencilops over the
            # pencil axis (the same discipline as distribute_solver's
            # 1-D pencil mesh, composed under the member axis)
            pencil = mesh.axis_names[1]
            if pencil == MEMBER_AXIS:
                raise ValueError("the pencil mesh axis must not be "
                                 f"named {MEMBER_AXIS!r}")
            if self.per_member_dt:
                raise ValueError(
                    "per_member_dt is not supported on a 2-D batch x "
                    "pencil mesh (the vmapped per-member factorization "
                    "is member-manual); use a 1-D member mesh.")
            G = self.solver.pencil_shape[0]
            n = mesh.shape[pencil]
            if G % n:
                raise ValueError(
                    f"pencil mesh axis {pencil!r} (size {n}) does not "
                    f"divide the pencil-group count {G}; choose "
                    f"resolutions with G % n == 0.")
            self.pencil_axis = pencil
        return mesh

    def _put(self, arr, pencil_dim=None):
        """One device_put onto the member sharding (SNIPPETS §[2]
        get_naive_sharding: lead axis on the batch mesh axis). On a 2-D
        batch x pencil mesh, `pencil_dim` names the array dim carrying
        the pencil-group axis (1 for the (N, G, S) state, 2 for the
        (N, steps, G, S) histories), sharded over the pencil axis."""
        if self.mesh is None:
            return jnp.asarray(arr)
        spec = [MEMBER_AXIS]
        if self.pencil_axis is not None and pencil_dim is not None:
            spec += [None] * (pencil_dim - 1) + [self.pencil_axis]
        return jax.device_put(arr, NamedSharding(self.mesh, P(*spec)))

    def _put_host(self, arr, dtype=None):
        """Place a HOST mirror (active mask, dts, clocks, step budgets)
        on device BY COPY. `jnp.asarray` zero-copies aligned numpy
        buffers on CPU, so placing a mirror without the copy aliases the
        device operand to the very buffer later in-place mutations
        (`active_host[m] = ...`, `sim_times += ...`) rewrite — which
        retroactively changes the operand of dispatches still queued on
        the async stream (observed: members silently freezing for the
        tail of a batch when a detach flipped the aliased mask)."""
        return self._put(jnp.array(arr, dtype=dtype))

    @property
    def layout(self):
        return self.solver.layout

    @property
    def variables(self):
        return self.solver.variables

    @property
    def active(self):
        """Per-member activity mask (true member count, no padding)."""
        return self.active_host[:self.members].copy()

    @property
    def n_active(self):
        return int(self.active_host[:self.members].sum())

    # ----------------------------------------------------------- member IO

    def init_members(self, fn):
        """
        Initialize the fleet: `fn(i)` is called for each member index and
        should set the template problem's fields (state variables AND any
        parameter/forcing fields) for member i; the gathered state and
        every RHS extra field are recorded as that member's batched
        operands. Fields `fn` leaves untouched simply repeat across
        members.
        """
        solver = self.solver
        X_rows, extra_rows = [], []
        for i in range(self.members):
            fn(i)
            X_rows.append(solver.gather_fields())
            extra_rows.append([jnp.asarray(a) for a in solver.rhs_extra()])
        pad = self.n_pad - self.members
        X_rows += [X_rows[0]] * pad
        extra_rows += [extra_rows[0]] * pad
        self.X = self._put(jnp.stack(X_rows), pencil_dim=1)
        self._extras = [self._put(jnp.stack([row[k] for row in extra_rows]))
                        for k in range(len(extra_rows[0]))]
        return self

    def set_states(self, X):
        """Install per-member initial pencil states directly:
        X is (members, G, S)."""
        X = jnp.asarray(X, dtype=self.solver.pencil_dtype)
        if X.shape[0] != self.members:
            raise ValueError(f"expected leading dim {self.members}, "
                             f"got {X.shape[0]}")
        pad = self.n_pad - self.members
        if pad:
            X = jnp.concatenate([X, jnp.broadcast_to(
                X[:1], (pad,) + X.shape[1:])])
        self.X = self._put(X, pencil_dim=1)
        return self

    def member_arrays(self, m):
        """{state_key: coefficient array} of member m's current state."""
        if not 0 <= m < self.members:
            raise IndexError(f"member {m} out of range [0, {self.members})")
        arrays = scatter_state(self.layout, self.variables, self.X[m])
        return {k: np.asarray(v) for k, v in arrays.items()}

    def load_member(self, m):
        """Scatter member m's state into the template problem fields (for
        plotting/analysis with the normal Field API)."""
        solver = self.solver
        arrays = scatter_state(self.layout, self.variables, self.X[m])
        for v in self.variables:
            v.preset_coeff(arrays[state_key(v)])
            v.mark_modified()
        return solver.state

    # ------------------------------------------------------------ programs

    def _specs(self, tree, batched):
        spec = P(MEMBER_AXIS) if batched else P()
        return jax.tree.map(lambda _: spec, tree)

    def _pencil_contexts(self, fn):
        """Wrap a fleet body so its TRACE runs under the pencil routing
        contexts of the 2-D batch x pencil composition: factor/solve
        funnels shard over the pencil axis (pencilops.pencil_mesh) and
        the per-member transform walks publish the mesh
        (field.mesh_transforms; meshctx.walk_axis_names filters the
        batch axis out, so the walks transpose over the pencil axes
        only). Identity on 1-D member meshes."""
        if self.pencil_axis is None:
            return fn
        from . import field as field_mod
        from ..libraries import pencilops

        def with_contexts(*args):
            # traced INSIDE the member-manual shard_map: nested shard_maps
            # must name the context mesh (member axis Manual, pencil axis
            # Auto), not the concrete all-Auto `self.mesh`
            mesh = jax.sharding.get_abstract_mesh()
            with pencilops.pencil_mesh(mesh, self.pencil_axis), \
                    field_mod.mesh_transforms(
                        mesh,
                        chunks=getattr(self.solver, "_transpose_chunks",
                                       None)):
                return fn(*args)

        return with_contexts

    def _wrap(self, raw, label, args, batched_flags):
        """jit (and shard_map, when a mesh is active) one fleet program.
        `batched_flags` marks which top-level args carry the member axis;
        specs are built per-leaf from the actual argument tree. On a 2-D
        batch x pencil mesh the shard_map is MANUAL over the member axis
        only, with the pencil axis in GSPMD auto mode — inside, the
        vmapped bodies route their ffts/solves through nested shard_maps
        over the pencil axis (core/meshctx.local_fft,
        libraries/pencilops.shard_groups), the same targeted routing the
        1-D distributed solver uses, composed under the member axis."""
        fn = retrace_mod.noted(self._pencil_contexts(raw), label)
        if self.mesh is not None:
            in_specs = tuple(self._specs(a, b)
                             for a, b in zip(args, batched_flags))
            if self.pencil_axis is not None:
                fn = shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                               out_specs=P(MEMBER_AXIS), check_vma=False,
                               axis_names=frozenset({MEMBER_AXIS}))
            else:
                fn = shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                               out_specs=P(MEMBER_AXIS))
        # every call site memoizes the wrapper (self._programs[n] /
        # self._project_prog / self._vfactor_prog), so each fleet program
        # is built and traced exactly once
        return jax.jit(fn)  # dedalus-lint: disable=DTL003

    @staticmethod
    def _freeze(new, old, act):
        """Hold inactive members at their previous values (a dropped
        member's slice never advances; NaN arithmetic from a poisoned
        member is computed then discarded — vmap guarantees no
        cross-member reduction, so poison cannot leak)."""
        def one(a, b):
            keep = act.reshape((-1,) + (1,) * (a.ndim - 1))
            return jnp.where(keep, a, b)
        return jax.tree.map(one, new, old)

    def _fleet_multistep(self, n, M, L, X, T, DT, act, R, extras,
                         Fh, MXh, LXh, a, b, c, aux):
        body_fn = self.timestepper.advance_body

        def body(carry, _):
            X, T, R, Fh, MXh, LXh = carry
            # per-step liveness: the active mask AND a positive steps-
            # remaining budget — a member that finishes inside the block
            # freezes for the rest of the scan (computed-then-discarded,
            # same as a dropped member)
            live = act & (R > 0)
            af = live.astype(self.rd)
            with jax.named_scope("dedalus/ensemble/step"):
                Xn, Fhn, MXhn, LXhn = jax.vmap(
                    body_fn,
                    in_axes=(None, None, 0, 0, 0, 0, 0, 0,
                             None, None, None, None))(
                    M, L, X, T, extras, Fh, MXh, LXh, a, b, c, aux)
            Xn, Fhn, MXhn, LXhn = self._freeze(
                (Xn, Fhn, MXhn, LXhn), (X, Fh, MXh, LXh), live)
            return (Xn, T + DT * af, R - live, Fhn, MXhn, LXhn), None

        carry, _ = jax.lax.scan(body, (X, T, R, Fh, MXh, LXh), None,
                                length=n)
        return carry

    def _fleet_rk(self, n, M, L, X, T, DT, act, R, extras, auxs):
        body_fn = self.timestepper.step_body
        aux_ax = 0 if self.per_member_dt else None

        def body(carry, _):
            X, T, R = carry
            live = act & (R > 0)
            af = live.astype(self.rd)
            with jax.named_scope("dedalus/ensemble/step"):
                Xn = jax.vmap(
                    body_fn,
                    in_axes=(None, None, 0, 0, 0, 0, aux_ax))(
                    M, L, X, T, DT, extras, auxs)
            Xn = self._freeze(Xn, X, live)
            return (Xn, T + DT * af, R - live), None

        carry, _ = jax.lax.scan(body, (X, T, R), None, length=n)
        return carry

    def _program(self, n, args, batched_flags):
        # memoized per block size in self._programs (cache-subscript
        # guard): one wrapper per static n, so fixed-size drivers trace
        # each program exactly once and the retrace sentinel stays quiet
        prog = self._programs.get(n)
        if prog is None:
            raw = functools.partial(
                self._fleet_multistep if self._multistep else self._fleet_rk,
                n)
            prog = self._programs[n] = self._wrap(
                raw, f"ensemble/fleet_step[{n}]", args, batched_flags)
        return prog

    def _pencil_project_body(self):
        """Per-member dealias-roundtrip projection for the 2-D batch x
        pencil composition. The solver's own projection body is reused
        where a layout walk exists; variables too low-dimensional to
        walk (1-D tau fields: their only axis IS the pencil-sharded one)
        route their whole roundtrip through meshctx.gathered_apply —
        gather over the pencil axis, transform locally, slice the block
        back — instead of leaving an unrouted fft in the GSPMD-auto
        region (which the SPMD partitioner cannot place)."""
        from . import meshctx
        from .field import (transform_to_grid, transform_to_coeff,
                            _walk_divisible)
        from .subsystems import gather_state, scatter_state, state_key
        solver = self.solver
        layout, variables = solver.layout, solver.variables
        pencil = self.pencil_axis

        def project(X):
            # the context mesh of the enclosing member-manual shard_map
            # (see _pencil_contexts)
            mesh = jax.sharding.get_abstract_mesh()
            arrays = scatter_state(layout, variables, X)
            out = {}
            for v in variables:
                scales = tuple(v.domain.dealias)
                tdim = len(v.tensorsig)
                data = arrays[state_key(v)]

                def roundtrip(a, v=v, scales=scales, tdim=tdim):
                    g = transform_to_grid(a, v.domain, scales, tdim,
                                          tensorsig=v.tensorsig)
                    return transform_to_coeff(g, v.domain, scales, tdim,
                                              tensorsig=v.tensorsig)

                walkable = (v.domain.dim > 1
                            and _walk_divisible(data, v.domain, scales,
                                                tdim, mesh, (pencil,)))
                if walkable:
                    out[state_key(v)] = roundtrip(data)
                else:
                    out[state_key(v)] = meshctx.gathered_apply(
                        roundtrip, data, mesh, pencil, dim=tdim)
            return gather_state(layout, variables, out)

        return project

    def _ensure_project_prog(self):
        if self._project_prog is None:
            if self.pencil_axis is None:
                self.solver._ensure_project()
                proj = self.solver._project_body
            else:
                proj = self._pencil_project_body()

            def raw(X, act):
                Xp = jax.vmap(proj)(X)
                return self._freeze(Xp, X, act)

            self._project_prog = self._wrap(
                raw, "ensemble/project", (self.X, self._active_dev),
                (True, True))
        return self._project_prog

    def _project_fleet(self):
        """Vmapped Hermitian/valid-mode re-projection of active members
        (mirrors solver.enforce_hermitian_symmetry; inactive members are
        frozen through it)."""
        self.X = self._ensure_project_prog()(self.X, self._active_dev)

    def _probe(self, X=None):
        """Per-member health reduction: (nonfinite count, max |coeff|) —
        one jitted program, host-read only on the health cadence. Also
        runs over ring-snapshot states (FleetSnapshot.member_finite), so
        snapshot validation never gathers the fleet to host."""
        if self._probe_prog is None:
            def raw(X):
                def one(x):
                    ax = jnp.abs(x)
                    return (jnp.sum(~jnp.isfinite(x)), jnp.max(ax))
                with metrics_mod.trace_scope("ensemble", "probe"):
                    return jax.vmap(one)(X)
            self._probe_prog = retrace_mod.noted_jit(
                raw, "ensemble/probe")
        return self._probe_prog(self.X if X is None else X)

    # ------------------------------------------------------ factorization

    def _factor_context(self):
        """Pencil routing for the (host-driven) LHS factorization of a
        2-D batch x pencil fleet: the factor program traces with the
        pencil mesh active, so the factors come out sharded over the
        pencil axis like the fleet state they solve against (the
        timestepper's own pencil_mesh(None) wrapper inherits this outer
        context). Null context on 1-D member meshes."""
        import contextlib
        if self.pencil_axis is None:
            return contextlib.nullcontext()
        from ..libraries import pencilops
        return pencilops.pencil_mesh(self.mesh, self.pencil_axis)

    def _ensure_factor_rk(self, dt):
        ts = self.timestepper
        solver = self.solver
        if not self.per_member_dt:
            key = round(float(dt), 14)
            if key != self._lhs_key:
                self._lhs_key = key
                with self._factor_context():
                    self._lhs_aux = ts._factor(
                        solver.M_mat, solver.L_mat,
                        jnp.asarray(float(dt), dtype=self.rd))
            return
        key = tuple(np.round(self.dts, 14))
        if key == self._lhs_key:
            return
        self._lhs_key = key
        if self._vfactor_prog is None:
            ops = solver.ops
            uniq = ts.uniq_H_diag
            slot = ts.stage_slot
            one = jnp.asarray(1.0, dtype=self.rd)

            def raw(M, L, dts):
                def member(dt):
                    return [ops.factor_lincomb(one, M, dt * h, L)
                            for h in uniq]
                auxs = jax.vmap(member)(dts)
                return [auxs[j] for j in slot]

            self._vfactor_prog = self._wrap(
                raw, "ensemble/vfactor",
                (solver.M_mat, solver.L_mat, self.DT),
                (False, False, True))
        self._lhs_aux = self._vfactor_prog(
            solver.M_mat, solver.L_mat, self.DT)

    def _ensure_factor_ms(self, a0, b0):
        key = (round(float(a0), 14), round(float(b0), 14))
        if key != self._lhs_key:
            self._lhs_key = key
            with self._factor_context():
                self._lhs_aux = self.timestepper._factor(
                    self.solver.M_mat, self.solver.L_mat,
                    jnp.asarray(a0, dtype=self.rd),
                    jnp.asarray(b0, dtype=self.rd))

    # ------------------------------------------------------------ stepping

    def _set_common_dt(self, dt):
        dt = float(dt)
        target = np.full(self.n_pad, dt)
        if self.per_member_dt:
            # members mid-rewind keep their backed-off dt (capped by the
            # request): a per-step driving loop re-passes the same scalar
            # dt every call, and overwriting the backoff would make the
            # member re-diverge identically until its retries burn out
            backed = self._retries > 0
            target[backed] = np.minimum(self.dts[backed], dt)
        live = self.active_host | (self.dts == 0.0)
        if not np.all(self.dts[live] == target[live]):
            self.dts = target
            self.DT = self._put_host(target, dtype=self.rd)

    def _dispatch(self, n, a=None, b=None, c=None, act_dev=None,
                  act_host=None):
        """One scanned fleet dispatch of n steps. `act_dev`/`act_host`
        override the activity mask for this dispatch only (the cohort-
        ramp path freezes everyone but the ramping members); both must
        describe the same membership. Returns the per-member steps
        actually taken (host array, padding rows included)."""
        solver = self.solver
        if act_dev is None:
            act_dev = self._active_dev
        if act_host is None:
            act_host = self.active_host
        if self._multistep:
            args = (solver.M_mat, solver.L_mat, self.X, self.T, self.DT,
                    act_dev, self.R, self._extras, self.F_hist,
                    self.MX_hist, self.LX_hist, a, b, c, self._lhs_aux)
            flags = (False, False, True, True, True, True, True, True,
                     True, True, True, False, False, False, False)
            prog = self._program(n, args, flags)
            self.X, self.T, self.R, self.F_hist, self.MX_hist, \
                self.LX_hist = prog(*args)
        else:
            args = (solver.M_mat, solver.L_mat, self.X, self.T, self.DT,
                    act_dev, self.R, self._extras, self._lhs_aux)
            flags = (False, False, True, True, True, True, True, True,
                     self.per_member_dt)
            prog = self._program(n, args, flags)
            self.X, self.T, self.R = prog(*args)
        self.iteration += n
        # host mirror of the in-scan liveness rule: an active member
        # takes min(n, budget) steps, everyone else none
        taken = np.where(act_host,
                         np.minimum(n, np.maximum(self.steps_left, 0)), 0)
        self.steps_left = self.steps_left - taken
        self.sim_times += taken * self.dts
        self.metrics.inc("ensemble/fleet_steps", n)
        member_steps = int(taken[:self.members].sum())
        self.metrics.inc("ensemble/member_steps", member_steps)
        self.metrics.observe_steps(member_steps)
        return taken

    def step_program_handle(self, n=None):
        """(program, args) of a compiled fleet step program — the
        inspection handle the program contract checker
        (tools/lint/progcheck.py) lowers: `program.lower(*args)` is the
        same jitted shard_map program `_dispatch` runs for a block of n
        steps, so collective placement (zero full-state gathers, the
        all-to-all census) and the manual/auto shard_map structure are
        checked on the EXECUTING program, not a reconstruction. Requires
        a warmed fleet (step_many has run at least one scanned block so
        factors and — for multistep schemes — the coefficient ramp
        exist). `n` defaults to the largest block already traced."""
        ts = self.timestepper
        if n is None:
            if not self._programs:
                raise RuntimeError(
                    "step_program_handle needs a stepped fleet: run "
                    "step_many first so a block program exists")
            n = max(self._programs)
        n = int(n)
        if self._multistep:
            s = ts.steps
            if len(self._dt_hist) < s:
                raise RuntimeError(
                    "step_program_handle needs the multistep ramp "
                    "complete: run step_many past the first "
                    f"{s} steps first")
            a, b, c = ts.compute_coefficients(self._dt_hist, s)
            a = np.concatenate([a, np.zeros(s + 1 - len(a))])
            b = np.concatenate([b, np.zeros(s + 1 - len(b))])
            c = np.concatenate([c, np.zeros(s - len(c))])
            self._ensure_factor_ms(a[0], b[0])
            args = (self.solver.M_mat, self.solver.L_mat, self.X, self.T,
                    self.DT, self._active_dev, self.R, self._extras,
                    self.F_hist, self.MX_hist, self.LX_hist,
                    jnp.asarray(a, dtype=self.rd),
                    jnp.asarray(b, dtype=self.rd),
                    jnp.asarray(c, dtype=self.rd), self._lhs_aux)
            flags = (False, False, True, True, True, True, True, True,
                     True, True, True, False, False, False, False)
        else:
            self._ensure_factor_rk(self.dts[0])
            args = (self.solver.M_mat, self.solver.L_mat, self.X, self.T,
                    self.DT, self._active_dev, self.R, self._extras,
                    self._lhs_aux)
            flags = (False, False, True, True, True, True, True, True,
                     self.per_member_dt)
        return self._program(n, args, flags), args

    def _ms_single(self, dt):
        """One fleet multistep step with the ramp's order build-up
        (mirrors MultistepIMEX.step coefficient handling)."""
        ts = self.timestepper
        s = ts.steps
        self._dt_hist = [float(dt)] + self._dt_hist[:s - 1]
        self._ms_iter += 1
        order = min(s, self._ms_iter)
        a, b, c = ts.compute_coefficients(self._dt_hist, order)
        a = np.concatenate([a, np.zeros(s + 1 - len(a))])
        b = np.concatenate([b, np.zeros(s + 1 - len(b))])
        c = np.concatenate([c, np.zeros(s - len(c))])
        self._ensure_factor_ms(a[0], b[0])
        self._dispatch(1, jnp.asarray(a, dtype=self.rd),
                       jnp.asarray(b, dtype=self.rd),
                       jnp.asarray(c, dtype=self.rd))

    def step(self, dt=None):
        self.step_many(1, dt)

    def step_many(self, n, dt=None):
        """
        Advance the whole fleet n constant-dt steps: the multistep ramp
        (order build-up) runs as single fleet steps, the remainder as ONE
        scanned device dispatch. With per_member_dt, `dt` may be a
        (members,) array; scalars apply fleet-wide.
        """
        n = int(n)
        if n <= 0:
            return
        if self._lost_devices:
            # pending device-loss notifications are drained BEFORE any
            # dispatch (and before the health probe can mistake the lost
            # shard's garbage for per-member divergence)
            self._handle_device_loss()
        solver = self.solver
        ts = self.timestepper
        if dt is not None:
            if np.ndim(dt) == 0:
                self._set_common_dt(dt)
            else:
                self.set_member_dts(dt)
        if not np.all(np.isfinite(self.dts[self.active_host])) \
                or not np.any(self.dts):
            raise ValueError(f"invalid ensemble dt state: {self.dts}")
        # Hermitian/valid-mode re-projection cadence (mirrors
        # solver.step_many's block condition)
        cadence = solver.enforce_real_cadence
        if cadence:
            r = self.iteration % cadence
            if (n >= cadence or r < ts.steps or (cadence - r) < n):
                self._project_fleet()
        if self._multistep:
            dt0 = float(self.dts[0])
            s = ts.steps
            while n > 0 and not (self._ms_iter >= s
                                 and len(self._dt_hist) == s
                                 and all(abs(k - dt0) < 1e-15 * abs(dt0)
                                         for k in self._dt_hist)):
                self._ms_single(dt0)
                n -= 1
            if n > 0:
                a, b, c = ts.compute_coefficients(self._dt_hist, s)
                self._ensure_factor_ms(a[0], b[0])
                self._dispatch(n, jnp.asarray(a, dtype=self.rd),
                               jnp.asarray(b, dtype=self.rd),
                               jnp.asarray(c, dtype=self.rd))
        else:
            self._ensure_factor_rk(self.dts[0])
            self._dispatch(n)
        if not self._warmed and self.iteration >= self.warmup_iterations:
            self._end_warmup()
        if self._health_gate.due(self.iteration):
            self.check_health()

    def set_member_dts(self, dts):
        """Install per-member timesteps (requires per_member_dt=True)."""
        if not self.per_member_dt:
            raise ValueError("per-member dt values require "
                             "per_member_dt=True")
        dts = np.asarray(dts, dtype=float)
        if dts.shape != (self.members,):
            raise ValueError(f"expected shape ({self.members},), "
                             f"got {dts.shape}")
        full = np.concatenate([dts, np.full(self.n_pad - self.members,
                                            dts[0] if len(dts) else 0.0)])
        if not np.array_equal(full, self.dts):
            self.dts = full
            self.DT = self._put_host(full, dtype=self.rd)

    def _end_warmup(self):
        """Warmup boundary: compile-bearing first dispatches stay out of
        the measured loop window; the retrace sentinel arms (each fleet
        program wrapper must trace exactly once from here on)."""
        self._warmed = True
        jax.block_until_ready(self.X)
        self.metrics.reset_loop()
        retrace_mod.sentinel.arm()

    # --------------------------------------- serving attach/detach/stepping
    #
    # The continuous-batching driver (service/batching.py) treats the
    # fleet as seats: requests attach and detach at block boundaries,
    # each with its own steps budget and projection phase. Everything
    # here is a VALUE-operand mutation of the already-compiled fleet
    # programs — zero post-warmup retraces across join/detach is the
    # serving acceptance bar.

    def _seat_mask(self, ms):
        mask = np.zeros(self.n_pad, dtype=bool)
        for m in np.atleast_1d(np.asarray(ms, dtype=int)):
            if not 0 <= m < self.members:
                raise IndexError(
                    f"member {m} out of range [0, {self.members})")
            mask[m] = True
        return mask

    def _masked_write(self, arr, mask_dev, row):
        """Seat write as a value-operand `where` (an `.at[m]` update
        would bake the seat index into the compiled scatter — one XLA
        program per seat; the mask form is one program per array
        shape)."""
        keep = mask_dev.reshape((-1,) + (1,) * (arr.ndim - 1))
        return jnp.where(keep, jnp.asarray(row, dtype=arr.dtype)[None],
                         arr)

    def attach_member(self, m, X_row, extras_rows=None, sim_time=0.0,
                      steps=None):
        """Seat a new member at index `m` (a serving join): install its
        state (and, when given, per-member RHS extra operand) rows, zero
        its multistep history, reset its clock/retry accounting, set its
        steps-remaining budget, and activate it. Multistep members
        seated into a running fleet still need their order build-up —
        call `ramp_members([m])` before steady stepping."""
        m = int(m)
        mask = self._seat_mask([m])
        if self.active_host[m]:
            raise ValueError(f"seat {m} is already active")
        mask_dev = self._put(jnp.asarray(mask))
        self.X = self._masked_write(self.X, mask_dev, X_row)
        if extras_rows is not None:
            if len(extras_rows) != len(self._extras):
                raise ValueError(
                    f"expected {len(self._extras)} extra operand row(s), "
                    f"got {len(extras_rows)}")
            self._extras = [self._masked_write(e, mask_dev, row)
                            for e, row in zip(self._extras, extras_rows)]
        if self._multistep:
            zeros = jnp.zeros((self.timestepper.steps,)
                              + tuple(self.solver.pencil_shape),
                              dtype=self.solver.pencil_dtype)
            self.F_hist = self._masked_write(self.F_hist, mask_dev, zeros)
            self.MX_hist = self._masked_write(self.MX_hist, mask_dev, zeros)
            self.LX_hist = self._masked_write(self.LX_hist, mask_dev, zeros)
        self.sim_times[m] = float(sim_time)
        # the member's device clock is seat-written (NOT rebuilt from the
        # host mirror: running members' device clocks are per-step
        # accumulations whose bits the per-dispatch host mirror does not
        # reproduce — clobbering them would perturb t-dependent RHSs)
        self.T = self._masked_write(
            self.T, mask_dev, jnp.asarray(float(sim_time), dtype=self.rd))
        self.steps_left[m] = int(steps) if steps is not None \
            else UNBOUNDED_STEPS
        self.R = self._put_host(self.steps_left, dtype=jnp.int32)
        self._retries[m] = 0
        self.active_host[m] = True
        self._active_dev = self._put_host(self.active_host)
        return m

    def detach_member(self, m):
        """Release seat `m` (completion, deadline, divergence, or a gone
        client): mask it out and zero its budget. Its row stays frozen —
        extract results BEFORE detaching."""
        m = int(m)
        self._seat_mask([m])   # range check
        self.active_host[m] = False
        self.steps_left[m] = 0
        self._active_dev = self._put_host(self.active_host)
        self.R = self._put_host(self.steps_left, dtype=jnp.int32)

    def set_fleet_dt(self, dt):
        """Serving: one uniform dt for every seat, unconditionally (the
        step-path `_set_common_dt` preserves per-member rewind backoffs
        a serving fleet never carries, and skips the update entirely
        when no seat is live — wrong for a fleet being re-armed between
        batches)."""
        dt = float(dt)
        if not np.isfinite(dt) or dt <= 0:
            raise ValueError(f"invalid fleet dt {dt!r}")
        self.dts = np.full(self.n_pad, dt)
        self.DT = self._put_host(self.dts, dtype=self.rd)

    def project_members(self, ms):
        """Masked Hermitian/valid-mode re-projection of a member subset:
        under serving, each member's projection cadence follows its OWN
        iteration count, not the fleet's (bit-identity with a solo run
        requires projecting exactly where the solo loop would). Same
        compiled program as the fleet-wide projection — the mask is a
        value operand."""
        mask = self._seat_mask(ms) & self.active_host
        if not mask.any():
            return
        self.X = self._ensure_project_prog()(
            self.X, self._put(jnp.asarray(mask)))

    def ramp_members(self, ms, project=False):
        """Multistep order build-up for newly attached members: `steps`
        single fleet dispatches with every OTHER member frozen, each
        using the ramp-order coefficients a fresh solo solver would use
        at that iteration — a member joining a running fleet bit-matches
        its own solo run. Requires the (uniform) fleet dt to be set.
        `project=True` re-projects the ramping cohort before each ramp
        step (solo projects on every iteration of the ramp window
        whenever a cadence is enabled). No-op for RK schemes. Returns
        the number of ramp dispatches."""
        if not self._multistep:
            return 0
        ts = self.timestepper
        s = ts.steps
        mask = self._seat_mask(ms) & self.active_host
        if not mask.any():
            return 0
        dts = self.dts[mask]
        dt = float(dts[0])
        if not np.all(dts == dt) or dt <= 0 or not np.isfinite(dt):
            raise ValueError(
                f"ramp_members requires one positive uniform dt for the "
                f"cohort, got {sorted(set(dts.tolist()))}")
        mask_dev = self._put(jnp.asarray(mask))
        for k in range(1, s + 1):
            if project:
                self.project_members(np.flatnonzero(mask))
            order = min(k, s)
            a, b, c = ts.compute_coefficients([dt] * order, order)
            a = np.concatenate([a, np.zeros(s + 1 - len(a))])
            b = np.concatenate([b, np.zeros(s + 1 - len(b))])
            c = np.concatenate([c, np.zeros(s - len(c))])
            self._ensure_factor_ms(a[0], b[0])
            self._dispatch(1, jnp.asarray(a, dtype=self.rd),
                           jnp.asarray(b, dtype=self.rd),
                           jnp.asarray(c, dtype=self.rd),
                           act_dev=mask_dev, act_host=mask)
        return s

    def step_fleet(self, n):
        """Serving steady dispatch: advance every active member by up to
        `n` steps, honoring each member's steps-remaining budget (a
        finished member freezes mid-scan — per-member stop without
        leaving the compiled program). Unlike `step_many` this never
        applies the fleet-global projection cadence or the multistep
        ramp — the serving driver owns per-member projection phases
        (`project_members`) and cohort ramps (`ramp_members`). Returns
        the per-member steps actually taken."""
        n = int(n)
        if n <= 0:
            return np.zeros(self.n_pad, dtype=np.int64)
        if self._lost_devices:
            self._handle_device_loss()
        ts = self.timestepper
        dt = float(self.dts[0])
        if not np.isfinite(dt) or dt <= 0:
            raise ValueError(f"invalid fleet dt {dt!r}")
        if self._multistep:
            s = ts.steps
            a, b, c = ts.compute_coefficients([dt] * s, s)
            a = np.concatenate([a, np.zeros(s + 1 - len(a))])
            b = np.concatenate([b, np.zeros(s + 1 - len(b))])
            c = np.concatenate([c, np.zeros(s - len(c))])
            self._ensure_factor_ms(a[0], b[0])
            taken = self._dispatch(n, jnp.asarray(a, dtype=self.rd),
                                   jnp.asarray(b, dtype=self.rd),
                                   jnp.asarray(c, dtype=self.rd))
        else:
            self._ensure_factor_rk(dt)
            taken = self._dispatch(n)
        if not self._warmed and self.iteration >= self.warmup_iterations:
            self._end_warmup()
        return taken

    # ------------------------------------------------- health and recovery

    def check_health(self):
        """Run the per-member probe now; diverged members are dropped or
        rewound per `policy`. Returns the list of member events handled."""
        nonfinite, max_abs = jax.device_get(self._probe())
        self.metrics.inc("ensemble/health_checks")
        bad = []
        for m in range(self.members):
            if not self.active_host[m]:
                continue
            if nonfinite[m]:
                bad.append((m, f"non-finite state ({int(nonfinite[m])} "
                               f"entries) at iteration {self.iteration}"))
            elif np.isfinite(self.max_abs_limit) \
                    and max_abs[m] > self.max_abs_limit:
                bad.append((m, f"growth bound exceeded: max|coeff| = "
                               f"{max_abs[m]:.3e} > {self.max_abs_limit:.3e}"
                               f" at iteration {self.iteration}"))
        if bad:
            self._handle_bad(bad)
        return bad

    def _newest_finite_slot(self, m):
        for snap in reversed(self.ring):
            if snap.member_finite(m):
                return snap
        return None

    def _restore_members(self, mask_np, snap):
        """Per-member rewind: `where` the snapshot slots of the masked
        members back into the fleet arrays (other members untouched)."""
        mask = self._put(jnp.asarray(mask_np))

        def back(cur, old):
            keep = mask.reshape((-1,) + (1,) * (cur.ndim - 1))
            return jnp.where(keep, old, cur)

        self.X = back(self.X, snap.X)
        self.T = back(self.T, snap.T)
        if self._multistep and snap.hists is not None:
            self.F_hist, self.MX_hist, self.LX_hist = jax.tree.map(
                back, (self.F_hist, self.MX_hist, self.LX_hist), snap.hists)
        self.sim_times[mask_np] = snap.sim_times[mask_np]

    def _handle_bad(self, bad):
        by_snap = {}
        for m, reason in bad:
            event = {"member": m, "iteration": self.iteration,
                     "reason": reason}
            snap = self._newest_finite_slot(m)
            rewind = (self.policy == "rewind"
                      and self._retries[m] < self.max_member_retries
                      and snap is not None)
            if rewind:
                self._retries[m] += 1
                new_dt = self.dts[m] * self.dt_backoff
                event.update(outcome="rewound",
                             rewind_iteration=snap.iteration,
                             retry=int(self._retries[m]), dt=new_dt)
                self.dts[m] = new_dt
                self.rewound.append(event)
                self.metrics.inc("ensemble/rewinds")
                logger.warning(
                    f"ensemble: member {m} diverged ({reason}); rewound to "
                    f"iteration {snap.iteration}, dt backed off to "
                    f"{new_dt:.3e} (retry {self._retries[m]}/"
                    f"{self.max_member_retries})")
            else:
                self.active_host[m] = False
                event.update(
                    outcome="dropped",
                    frozen_iteration=snap.iteration if snap else None)
                self.dropped.append(event)
                self.metrics.inc("ensemble/dropped")
                logger.warning(
                    f"ensemble: member {m} diverged ({reason}); dropped"
                    + (f", frozen at snapshot iteration {snap.iteration}"
                       if snap else " (no finite snapshot: state left "
                       "as-is, masked out)"))
            if snap is not None:
                by_snap.setdefault(id(snap), (snap, []))[1].append(m)
        for snap, ms in by_snap.values():
            mask = np.zeros(self.n_pad, dtype=bool)
            mask[ms] = True
            self._restore_members(mask, snap)
        self._active_dev = self._put_host(self.active_host)
        if self.per_member_dt:
            self.DT = self._put_host(self.dts, dtype=self.rd)
            self._lhs_key = None   # refactor with the backed-off dts

    def snapshot(self):
        """Capture the fleet (sync-free device references)."""
        hists = ((self.F_hist, self.MX_hist, self.LX_hist)
                 if self._multistep else None)
        self.ring.append(FleetSnapshot(
            self.X, self.T, hists, self.iteration, self.sim_times,
            probe=self._probe))
        del self.ring[:-self.ring_size]
        self.metrics.inc("ensemble/snapshots")

    # ------------------------------------------------- device-loss recovery

    def members_on_device(self, device_index):
        """Member indices (including inactive padding clones) whose shard
        lives on local device `device_index` under the 1-D batch
        sharding (contiguous equal blocks)."""
        if self.mesh is None:
            return list(range(self.n_pad)) if device_index == 0 else []
        D = self.mesh.shape[MEMBER_AXIS]
        per = self.n_pad // D
        d = int(device_index)
        return list(range(d * per, min((d + 1) * per, self.n_pad)))

    def notify_device_loss(self, device_index):
        """Report that a mesh device is lost (its shard of every fleet
        array is unreadable or garbage). In production this is the
        XlaRuntimeError path of a fleet dispatch; the chaos harness
        (`lose_device`) delivers the same notification deterministically.
        Handled before the next dispatch (`step_many` drains pending
        losses first)."""
        self._lost_devices.append(int(device_index))

    def _host_from_shards(self, arr, lost_devices, failed_out=None):
        """Host copy of a fleet array assembled from its SURVIVING shards
        only — the lost device's block is never read (it is gone, or
        garbage pretending not to be). Lost rows come back zero-filled
        and MUST be overwritten by the caller before use. A surviving
        shard that FAILS to read is recorded in `failed_out` — the
        caller promotes its device to lost so those members are restored
        too, never left as silently-finite zeros."""
        out = np.zeros(arr.shape, arr.dtype)
        shards = getattr(arr, "addressable_shards", None)
        if not shards:
            return np.array(arr)
        for sh in shards:
            if sh.device in lost_devices:
                continue
            try:
                out[sh.index] = np.asarray(sh.data)
            except Exception as exc:
                logger.warning(f"ensemble: surviving shard on "
                               f"{sh.device} unreadable: {exc}")
                if failed_out is not None:
                    failed_out.add(sh.device)
        return out

    def _host_best_effort(self, arr, failed_out=None):
        """Host copy of a fleet array trying EVERY shard — recovery may
        still be able to read a 'lost' device's block (poisoned-not-
        destroyed); shards that fail to read leave zeros for the caller
        to overwrite from the durable checkpoint, and are recorded in
        `failed_out` so their devices' members count as affected. Read
        failures must never escape: they would turn recovery into the
        crash it prevents."""
        shards = getattr(arr, "addressable_shards", None)
        if not shards:
            return np.array(arr)
        out = np.zeros(arr.shape, arr.dtype)
        for sh in shards:
            try:
                out[sh.index] = np.asarray(sh.data)
            except Exception as exc:
                logger.warning(f"ensemble: shard on {sh.device} "
                               f"unreadable during recovery: {exc}")
                if failed_out is not None:
                    failed_out.add(sh.device)
        return out

    def _validate_fleet_meta(self, meta, path):
        """Raise CheckpointError unless `meta` describes THIS fleet (an
        incompatible checkpoint must never be installed member-wise)."""
        if meta.get("kind") != "ensemble":
            raise CheckpointError(
                f"checkpoint {path} holds {meta.get('kind')!r} state, "
                f"not a fleet", path=path)
        if int(meta.get("members", -1)) != self.members:
            raise CheckpointError(
                f"checkpoint {path} holds {meta.get('members')} members, "
                f"this fleet has {self.members}", path=path)
        if list(meta.get("pencil_shape", [])) != \
                list(self.solver.pencil_shape):
            raise CheckpointError(
                f"checkpoint {path} pencil shape "
                f"{meta.get('pencil_shape')} does not match this solver's "
                f"{list(self.solver.pencil_shape)}", path=path)
        if meta.get("scheme") != type(self.timestepper).__name__:
            raise CheckpointError(
                f"checkpoint {path} was written by scheme "
                f"{meta.get('scheme')}, this fleet runs "
                f"{type(self.timestepper).__name__}", path=path)
        n_extras = meta.get("n_extras")
        if n_extras is not None and int(n_extras) != len(self._extras):
            raise CheckpointError(
                f"checkpoint {path} carries {n_extras} RHS parameter "
                f"operand(s), this fleet's problem has "
                f"{len(self._extras)} — different problem configuration",
                path=path)

    def _checkpoint_members(self):
        """Member-row arrays + meta from the newest valid durable sharded
        checkpoint, or None (no directory / nothing restorable /
        incompatible). Drains the async writer first so an in-flight
        (manifest-less) write is never quarantined out from under it."""
        if self._checkpoint_dir is None:
            return None
        quarantine = True
        if self._checkpointer is not None:
            self._checkpointer.drain()
            # drain can time out with a write still in flight: restore
            # must then leave its manifest-less directory alone
            quarantine = self._checkpointer.pending == 0
        try:
            event = dcheckpoint.restore_latest(self._checkpoint_dir,
                                               quarantine=quarantine)
            if event is not None:
                self._validate_fleet_meta(event["meta"], event["path"])
        except CheckpointError as exc:
            logger.warning(f"ensemble: durable checkpoint unusable for "
                           f"member restore: {exc}")
            return None
        return event

    def _handle_device_loss(self):
        """Re-shard the fleet onto the surviving devices. Live member
        blocks are rebuilt host-side from surviving shards; the lost
        device's members are restored from the newest finite
        FleetSnapshot slot (its arrays predate the loss) or, when the
        ring has nothing finite for a member, from the last durable
        sharded checkpoint; members with neither drop. Then a fresh 1-D
        mesh over the survivors is built, members re-pad to the new
        device multiple, and every block-memoized program is rebuilt for
        the new layout (fresh wrappers — a compile, not a retrace)."""
        pending = sorted(set(self._lost_devices))
        self._lost_devices = []
        if self.pencil_axis is not None:
            raise RuntimeError(
                "device-loss recovery supports 1-D member meshes only: a "
                "2-D batch x pencil fleet loses a SLICE of every member's "
                "pencil state with a device, so restore onto survivors "
                "must come from a durable sharded checkpoint "
                "(restore_checkpoint) on a rebuilt fleet.")
        if self.mesh is None:
            if pending:
                raise RuntimeError(
                    "device loss reported without a device mesh: a single-"
                    "device fleet has no surviving devices to reshard onto")
            return
        old_devices = list(self.mesh.devices.flat)
        # range-filter BEFORE deciding anything happened: a stale/bogus
        # index must not trigger a spurious reshard (program rebuilds +
        # a cleared snapshot ring are expensive AND destroy rewind
        # targets)
        lost = sorted({d for d in pending if 0 <= d < len(old_devices)})
        if not lost:
            if pending:
                logger.warning(f"ensemble: device-loss notification(s) "
                               f"{pending} out of range for a "
                               f"{len(old_devices)}-device mesh; ignored")
            return
        t0 = time_mod.perf_counter()
        lost_devs = {old_devices[d] for d in lost}
        # ---- host reconstruction from surviving shards only; a surviving
        # shard that fails to read promotes its device to lost so its
        # members are restored below instead of running on zeros
        failed = set()
        host = {"X": self._host_from_shards(self.X, lost_devs, failed),
                "T": self._host_from_shards(self.T, lost_devs, failed)}
        if self._multistep:
            host["F_hist"] = self._host_from_shards(
                self.F_hist, lost_devs, failed)
            host["MX_hist"] = self._host_from_shards(
                self.MX_hist, lost_devs, failed)
            host["LX_hist"] = self._host_from_shards(
                self.LX_hist, lost_devs, failed)
        # RHS parameter operands: constant per member mid-run; every
        # readable shard is recovered best-effort (a poisoned-not-
        # destroyed device's blocks survive), and the checkpoint branch
        # below overwrites affected rows from the durable extra<k> arrays
        host_extras = [self._host_best_effort(e, failed)
                       for e in self._extras]
        promoted = sorted(old_devices.index(dev) for dev in failed
                          if dev in old_devices and dev not in lost_devs)
        if promoted:
            logger.warning(f"ensemble: device(s) {promoted} failed reads "
                           f"during recovery; treating as lost too")
            lost = sorted(set(lost) | set(promoted))
            lost_devs |= {old_devices[d] for d in promoted}
        from . import meshctx
        survivors = meshctx.surviving_devices(self.mesh, lost)
        if not survivors:
            raise RuntimeError("ensemble: every mesh device lost")
        affected = sorted({m for d in lost
                           for m in self.members_on_device(d)
                           if m < self.members})
        # ---- restore the lost device's members. Ring first (its
        # snapshots predate the loss), durable checkpoint second, drop
        # last — and NOTHING here may raise for a read failure: a ring
        # slot whose shards died with the device must fall through to
        # the checkpoint, not crash the fleet.
        checkpoint = None
        restored, dropped_now, frozen_lost = [], [], []
        for m in affected:
            # INACTIVE members are walked too: a previously-dropped
            # member's row is its frozen last-good state (the drop
            # policy's contract) — losing its device must restore that
            # row, not silently replace it with zeros
            was_active = bool(self.active_host[m])
            rows = None
            try:
                snap = self._newest_finite_slot(m)
                if snap is not None:
                    rows = {"X": np.asarray(snap.X[m]),
                            "T": np.asarray(snap.T[m])}
                    if self._multistep and snap.hists is not None:
                        for name, h in zip(
                                ("F_hist", "MX_hist", "LX_hist"),
                                snap.hists):
                            rows[name] = np.asarray(h[m])
                    sim_time = snap.sim_times[m]
                    iteration = snap.iteration
            except Exception as exc:
                logger.warning(
                    f"ensemble: ring restore for member {m} failed "
                    f"({exc}); trying the durable checkpoint")
                rows = None
            if rows is not None:
                for name, row in rows.items():
                    host[name][m] = row
                self.sim_times[m] = sim_time
                entry = {"member": m, "source": "ring",
                         "iteration": iteration}
                if not was_active:
                    entry["frozen"] = True
                restored.append(entry)
                continue
            if checkpoint is None:
                checkpoint = self._checkpoint_members() or False
            if checkpoint:
                arrays, meta = checkpoint["arrays"], checkpoint["meta"]
                host["X"][m] = arrays["X"][m]
                host["T"][m] = arrays["T"][m]
                if self._multistep and "F_hist" in arrays:
                    for name in ("F_hist", "MX_hist", "LX_hist"):
                        host[name][m] = arrays[name][m]
                for k in range(len(host_extras)):
                    if f"extra{k}" in arrays:
                        host_extras[k][m] = arrays[f"extra{k}"][m]
                self.sim_times[m] = float(meta["sim_times"][m])
                entry = {"member": m, "source": "checkpoint",
                         "iteration": int(meta["iteration"])}
                if not was_active:
                    entry["frozen"] = True
                restored.append(entry)
                continue
            if not was_active:
                # already dropped AND no source: the frozen state is
                # genuinely gone — say so instead of pretending the
                # zero-filled row is data
                frozen_lost.append(m)
                logger.warning(
                    f"ensemble: dropped member {m}'s frozen state was on "
                    f"the lost device and no snapshot/checkpoint holds "
                    f"it; its row is zeroed")
                continue
            self.active_host[m] = False
            event = {"member": m, "iteration": self.iteration,
                     "reason": f"device {lost} lost, no finite snapshot "
                               f"or durable checkpoint to restore from",
                     "outcome": "dropped", "frozen_iteration": None}
            self.dropped.append(event)
            dropped_now.append(m)
            self.metrics.inc("ensemble/dropped")
        # ring-restored members got their X/hists from the (pre-loss)
        # snapshot, but their RHS parameter rows came from the
        # best-effort read of the LOST device — untrusted by definition.
        # When a durable checkpoint exists, its extra<k> rows (constant
        # per member mid-run, so any checkpoint's copy is the original)
        # replace them; without one the best-effort read stands (the
        # poisoned-not-destroyed case, as documented).
        ring_members = [r["member"] for r in restored
                        if r["source"] == "ring"]
        if ring_members and self._checkpoint_dir is not None \
                and host_extras:
            if checkpoint is None:
                checkpoint = self._checkpoint_members() or False
            if checkpoint:
                arrays = checkpoint["arrays"]
                for m in ring_members:
                    for k in range(len(host_extras)):
                        if f"extra{k}" in arrays:
                            host_extras[k][m] = arrays[f"extra{k}"][m]
        # ---- rebuild the mesh over the survivors and re-pad (same
        # meshctx.surviving_devices filter behind both, so the mesh and
        # the padding can never disagree)
        D2 = len(survivors)
        self.mesh = meshctx.surviving_mesh(self.mesh, lost)
        n_pad2 = -(-self.members // D2) * D2 if self.mesh is not None \
            else self.members
        repad = functools.partial(_repad, members=self.members,
                                  n_pad=n_pad2)
        self.n_pad = n_pad2
        self.X = self._put(jnp.asarray(repad(host["X"])))
        self.T = self._put(jnp.asarray(repad(host["T"])))
        if self._multistep:
            self.F_hist = self._put(jnp.asarray(repad(host["F_hist"])))
            self.MX_hist = self._put(jnp.asarray(repad(host["MX_hist"])))
            self.LX_hist = self._put(jnp.asarray(repad(host["LX_hist"])))
        self._extras = [self._put(jnp.asarray(repad(e)))
                        for e in host_extras]
        self.sim_times = repad(self.sim_times)
        self.dts = repad(self.dts)
        self.DT = self._put_host(self.dts, dtype=self.rd)
        self.active_host = repad(self.active_host, pad_value=False)
        self._retries = repad(self._retries, pad_value=0)
        self._active_dev = self._put_host(self.active_host)
        self.steps_left = repad(self.steps_left, pad_value=0)
        self.R = self._put_host(self.steps_left, dtype=jnp.int32)
        # the compiled fleet programs are layout-specific: rebuild (fresh
        # wrappers trace once each — a compile, not a retrace)
        self._programs = {}
        self._project_prog = None
        self._probe_prog = None
        self._vfactor_prog = None
        self._lhs_key = None
        self._lhs_aux = None
        # ring snapshots reference the old layout; fresh post-reshard anchor
        self.ring = []
        self.snapshot()
        event = {
            "iteration": self.iteration,
            "lost_devices": lost,
            "devices": D2,
            "restored": restored,
            "dropped": dropped_now,
            "wall_sec": round(time_mod.perf_counter() - t0, 4),
        }
        if frozen_lost:
            event["frozen_lost"] = frozen_lost
        self.reshard_events.append(event)
        self.metrics.inc("ensemble/reshards")
        sources = (", ".join(sorted({r["source"] for r in restored}))
                   if restored else "none")
        logger.warning(
            f"ensemble: lost device(s) {lost} at iteration "
            f"{self.iteration}; resharded {self.members} members onto "
            f"{D2} surviving device(s) — {len(restored)} member(s) "
            f"restored (source: {sources}), {len(dropped_now)} dropped, "
            f"{event['wall_sec']}s")

    # ---------------------------------------------------- durable checkpoints

    def init_checkpoints(self, directory, async_write=None, inflight=None,
                         keep=None, chaos=None):
        """Arm durable sharded fleet checkpoints under `directory`
        (tools/dcheckpoint.py; defaults from [resilience]
        CHECKPOINT_ASYNC / CHECKPOINT_INFLIGHT / CHECKPOINT_KEEP)."""
        from ..tools.resilience import _as_bool, io_retry_policy
        if async_write is None:
            async_write = _as_bool(cfg_get(
                "resilience", "CHECKPOINT_ASYNC", "False"))
        self._checkpoint_dir = directory
        self._checkpointer = dcheckpoint.ShardedCheckpointer(
            directory, async_write=_as_bool(async_write),
            inflight=int(inflight if inflight is not None
                         else cfg_get("resilience", "CHECKPOINT_INFLIGHT",
                                      "2")),
            keep=int(keep if keep is not None
                     else cfg_get("resilience", "CHECKPOINT_KEEP", "2")),
            io_retry=io_retry_policy(on_retry=lambda attempt, exc:
                self.metrics.inc("ensemble/io_retries")))
        if chaos is not None:
            wire = getattr(chaos, "wire_checkpointer", None)
            if wire is not None:
                wire(self._checkpointer)
        return self._checkpointer

    def write_checkpoint(self):
        """Write (or, async, submit) one durable sharded fleet checkpoint:
        the member axis is already the shard axis, so each device's block
        goes to its own checksummed file and the capture is a dict of
        immutable references — sync-free."""
        if self._checkpointer is None:
            raise ValueError("call init_checkpoints(directory) first (or "
                             "evolve(checkpoint_dir=...))")
        arrays = {"X": self.X, "T": self.T}
        if self._multistep:
            arrays.update(F_hist=self.F_hist, MX_hist=self.MX_hist,
                          LX_hist=self.LX_hist)
        for k, extra in enumerate(self._extras):
            arrays[f"extra{k}"] = extra
        meta = {
            "kind": "ensemble",
            "members": self.members,
            "n_pad": self.n_pad,
            "n_extras": len(self._extras),
            "iteration": int(self.iteration),
            "scheme": type(self.timestepper).__name__,
            "per_member_dt": self.per_member_dt,
            "pencil_shape": list(self.solver.pencil_shape),
            "sim_times": [float(v) for v in self.sim_times],
            "dts": [float(v) for v in self.dts],
            "active": [bool(v) for v in self.active_host],
            "retries": [int(v) for v in self._retries],
        }
        if self._multistep:
            meta["ms_iter"] = int(self._ms_iter)
            meta["dt_hist"] = [float(v) for v in self._dt_hist]
        result = self._checkpointer.save(arrays, meta)
        self.metrics.inc("ensemble/checkpoints_written")
        return result

    def restore_checkpoint(self, directory=None):
        """Elastic restore from the newest valid sharded fleet checkpoint
        (per-shard checksums validated, torn checkpoints quarantined with
        fallback): the TRUE member rows are re-padded onto THIS fleet's
        mesh — the writing and restoring device counts are independent,
        and member states restore bit-identically. Raises CheckpointError
        when nothing under `directory` is restorable."""
        directory = directory if directory is not None \
            else self._checkpoint_dir
        if directory is None:
            raise ValueError("restore_checkpoint requires a directory")
        quarantine = True
        if self._checkpointer is not None:
            # never quarantine a write the async writer has in flight
            self._checkpointer.drain()
            quarantine = self._checkpointer.pending == 0
        event = dcheckpoint.restore_latest(directory, quarantine=quarantine)
        if event is None:
            raise CheckpointError(
                f"no sharded checkpoint under {directory}", path=directory)
        arrays = event.pop("arrays")
        meta = event["meta"]
        self._validate_fleet_meta(meta, event["path"])
        repad = functools.partial(_repad, members=self.members,
                                  n_pad=self.n_pad)
        self.X = self._put(jnp.asarray(repad(arrays["X"])), pencil_dim=1)
        self.T = self._put(jnp.asarray(repad(arrays["T"])))
        if self._multistep and "F_hist" in arrays:
            self.F_hist = self._put(jnp.asarray(repad(arrays["F_hist"])),
                                    pencil_dim=2)
            self.MX_hist = self._put(jnp.asarray(repad(arrays["MX_hist"])),
                                     pencil_dim=2)
            self.LX_hist = self._put(jnp.asarray(repad(arrays["LX_hist"])),
                                     pencil_dim=2)
            self._ms_iter = int(meta.get("ms_iter", 0))
            self._dt_hist = [float(v) for v in meta.get("dt_hist", [])]
        extras = []
        for k in range(len(self._extras)):
            name = f"extra{k}"
            if name not in arrays:
                # _validate_fleet_meta already rejects count mismatches
                # for checkpoints that record n_extras; this guards the
                # same hazard for older manifests — a partial install
                # (checkpoint state + current parameters) would be a
                # silently inconsistent fleet
                raise CheckpointError(
                    f"checkpoint {event['path']} lacks the RHS parameter "
                    f"operand {name} this fleet's problem requires",
                    path=event["path"])
            extras.append(self._put(jnp.asarray(repad(arrays[name]))))
        self._extras = extras
        self.iteration = int(meta["iteration"])
        self.sim_times = repad(np.asarray(meta["sim_times"], dtype=float))
        self.dts = repad(np.asarray(meta["dts"], dtype=float))
        self.DT = self._put_host(self.dts, dtype=self.rd)
        self.active_host = repad(
            np.asarray(meta["active"], dtype=bool), pad_value=False)
        self._retries = repad(
            np.asarray(meta["retries"], dtype=int), pad_value=0)
        self._active_dev = self._put_host(self.active_host)
        self._lhs_key = None
        self._lhs_aux = None
        self.ring = []
        self.snapshot()
        self.metrics.inc("ensemble/restores")
        logger.info(
            f"ensemble: restored {self.members} members from "
            f"{event['path']} (iteration {self.iteration}) onto "
            f"{self.mesh.shape[MEMBER_AXIS] if self.mesh else 1} device(s)")
        return event

    # ------------------------------------------------------------ the loop

    def evolve(self, dt=None, stop_iteration=None, block=None, chaos=None,
               log_cadence=100, checkpoint_dir=None, checkpoint_iter=0,
               checkpoint_async=None):
        """
        Drive the fleet to `stop_iteration` in fixed-size scanned blocks
        (sizes {block, 1} only, so each program traces once): snapshot
        ring + per-member health on their cadences, chaos hooks for fault
        injection, durable sharded checkpoints every `checkpoint_iter`
        iterations (plus one final write) when `checkpoint_dir` is given,
        telemetry flush at the end. Returns the summary dict.
        """
        if stop_iteration is None:
            raise ValueError("evolve requires stop_iteration")
        block = int(block or min(16, max(self.snapshot_cadence, 1)))
        if dt is not None and np.ndim(dt) == 0:
            self._set_common_dt(dt)
        elif dt is not None:
            self.set_member_dts(dt)
        ckpt_gate = None
        if checkpoint_dir is not None:
            self.init_checkpoints(checkpoint_dir,
                                  async_write=checkpoint_async, chaos=chaos)
            if checkpoint_iter:
                ckpt_gate = metrics_mod.CadenceGate(int(checkpoint_iter))
                ckpt_gate.reset(self.iteration)
        self.snapshot()   # iteration-0 anchor
        while self.iteration < stop_iteration and self.n_active:
            n = block if stop_iteration - self.iteration >= block else 1
            self.step_many(n)
            if chaos is not None:
                chaos.after_step(self)
            if self._snapshot_gate.due(self.iteration):
                self.snapshot()
            if ckpt_gate is not None and ckpt_gate.due(self.iteration):
                try:
                    self.write_checkpoint()
                except Exception as exc:
                    logger.warning(f"periodic fleet checkpoint failed: "
                                   f"{exc}")
            if log_cadence and self.iteration % log_cadence < n:
                logger.info(
                    f"Ensemble iteration={self.iteration}, "
                    f"active={self.n_active}/{self.members}, "
                    f"dropped={len(self.dropped)}")
        if self._lost_devices:
            # a loss delivered after the last dispatch: recover before
            # the final checkpoint/flush reads the fleet state
            self._handle_device_loss()
        if self._checkpointer is not None:
            try:
                self.write_checkpoint()
            except Exception as exc:
                logger.warning(f"final fleet checkpoint failed: {exc}")
            for exc in self._checkpointer.close():
                logger.error(f"async fleet checkpoint write failed: {exc}")
        self.flush_metrics()
        return self.summary()

    # ----------------------------------------------------------- telemetry

    def summary(self):
        """Compact ensemble record (the `ensemble` block of flushed
        telemetry; `report` renders it as member columns)."""
        m = self.metrics
        wall = m.loop_wall()
        member_steps = m.iterations
        return {
            "members": self.members,
            "active": self.n_active,
            "dropped": len(self.dropped),
            "rewinds": len(self.rewound),
            "fleet_steps": self.iteration,
            "member_steps": member_steps,
            "ensemble_steps_per_sec": round(member_steps / wall, 4)
            if wall > 0 else 0.0,
            "devices": (int(np.prod(list(self.mesh.shape.values())))
                        if self.mesh is not None else 1),
            **({"mesh": dict(self.mesh.shape)}
               if self.pencil_axis is not None else {}),
            "per_member_dt": self.per_member_dt,
            "policy": self.policy,
            "dropped_members": [e["member"] for e in self.dropped],
            "reshards": len(self.reshard_events),
            **({"checkpoint": self._checkpointer.summary()}
               if self._checkpointer is not None else {}),
        }

    def flush_metrics(self, extra=None):
        """Block on the fleet state and flush one telemetry record with
        the `ensemble` summary block attached."""
        try:
            jax.block_until_ready(self.X)
        except Exception:
            pass
        extra = dict(extra or {})
        extra.setdefault("ensemble", self.summary())
        extra.setdefault("retraces_post_warmup",
                         retrace_mod.sentinel.post_arm_retraces)
        # the fleet compiles against the template solver's resolved plan,
        # so its provenance IS the fleet's provenance
        if hasattr(self.solver, "plan_provenance"):
            extra.setdefault("plan", self.solver.plan_provenance())
        return self.metrics.flush(extra=extra)

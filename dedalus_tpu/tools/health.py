"""
Numerical-health monitor + divergence flight recorder for the IVP loop.

PR 1 made wall time observable; this module makes the *numerics*
observable. A single jitted, cadence-gated probe (one fused reduction
over the gathered (G, S) pencil state) computes, per state field:

  * NaN and Inf entry counts,
  * max |coefficient| and the L2 norm,
  * the spectral tail-energy fraction per basis axis — energy carried by
    the top third of modes, the classic under-resolution tell (energy
    piling into the truncation edge instead of decaying).

Cadence gating reuses the [profiling] machinery (`metrics.CadenceGate`):
off-cadence iterations pay one Python attribute check and never touch the
device; on-cadence iterations dispatch the probe and pull back a handful
of scalars (the only host round-trip, riding the same sampled-sync budget
as the phase timers). When health is disabled the probe is never built or
compiled — the zero-overhead path.

Failure policy: NaN/Inf anywhere in the state, or max|coefficient| above
the configurable growth bound, is fatal. The solver halts *gracefully* —
`solver.proceed` flips False, a structured `SolverHealthError` becomes
available as `solver.health_error`, scheduled output handlers are skipped from the
detecting step onward (a detected-poisoned state is never written as a
"good" checkpoint; detection granularity is the probe cadence) —
and the monitor dumps a **flight recorder**: one post-mortem directory
holding the ring buffer of recent health records, the metrics flush, the
CFL/dt history of any attached `extras.flow_tools.CFL`, flow-property
snapshots of attached `GlobalFlowProperty` instances, and a
`load_state`-compatible state checkpoint, plus a
`benchmarks/results.jsonl`-compatible summary record. Tail energy above
the warn threshold logs an under-resolution warning (once per
field/axis) naming the offending field and basis axis.

Summarize a dump with `python -m dedalus_tpu postmortem <dir>`; the
`[health]` config section controls cadence, thresholds, ring size, and
the on/off default.
"""

import json
import logging
import os
import pathlib
import time
from collections import deque

import numpy as np

from .config import config
from .exceptions import SolverHealthError
from . import metrics as metrics_mod
from . import tracing

logger = logging.getLogger(__name__)

__all__ = ["HealthMonitor", "SolverHealthError", "resolve",
           "read_postmortem", "format_postmortem"]

# Tail = top third of the resolved modes along an axis (by wavenumber
# magnitude for separable/Fourier axes, by polynomial degree for coupled
# axes). A well-resolved spectrum decays through the tail; a flat or
# rising one means the truncation is doing physics.
TAIL_FRACTION = 1.0 / 3.0
# Fields with less energy than this (L2) are spectrally meaningless noise:
# no tail warning (a zero-initialized velocity field would otherwise warn
# on its round-off content).
TAIL_ENERGY_FLOOR = 1e-10


def _jsonable(obj):
    """Recursively replace non-finite floats with their repr strings
    ('inf', '-inf', 'nan'): a diverged state produces exactly these values,
    and Python's json would emit non-strict NaN/Infinity literals that
    break downstream results.jsonl consumers."""
    if isinstance(obj, dict):
        return {k: _jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    if isinstance(obj, float) and not np.isfinite(obj):
        return repr(obj)
    return obj


def _fmt(value):
    """Format a maybe-sanitized numeric for the postmortem CLI."""
    if isinstance(value, (int, float)):
        return f"{value:#.4g}"
    return str(value)


def _tau_like(name):
    """Tau fields absorb boundary/gauge error and are spectrally broad by
    construction — their tail fraction is not an under-resolution signal,
    so they are exempt from tail WARNINGS (NaN/Inf and growth checks still
    apply, and their tail stats still land in every record). Uses the
    reference naming convention (tau_*) plus unnamed fields."""
    return name == "tau" or name.startswith("tau_") \
        or name.startswith("_anon_")


def _axis_label(basis, axis):
    """Coordinate name of one axis of a (possibly multi-dim) basis."""
    if getattr(basis, "dim", 1) == 1:
        return basis.coord.name
    sub = axis - basis.first_axis
    names = getattr(getattr(basis, "cs", None), "names", None)
    if names is not None and sub < len(names):
        return names[sub]
    return f"axis{axis}"


class HealthMonitor:
    """
    Per-solver numerical-health state: the jitted probe (built lazily, so
    disabled monitors never compile anything), the ring buffer of recent
    records, threshold bookkeeping, and the flight-recorder dump.
    """

    def __init__(self, enabled=True, cadence=200, ring_size=64,
                 max_abs_limit=1e12, tail_warn_frac=0.25,
                 postmortem_dir="postmortems"):
        self.enabled = bool(enabled)
        self.solver = None
        self.cadence = int(cadence)   # property: also (re)builds the gate
        self.ring = deque(maxlen=max(int(ring_size), 1))
        self.max_abs_limit = float(max_abs_limit)
        self.tail_warn_frac = float(tail_warn_frac)
        self.postmortem_dir = postmortem_dir
        self.checks = 0
        self.warnings = 0
        self.failed_reason = None
        self.postmortem_path = None
        self._probe = None
        self._specs = None
        self._warned = set()
        self._dt_dumped = False
        self._dt_sources = []     # CFL instances (dt/frequency history)
        self._flow_sources = []   # (GlobalFlowProperty, names) pairs

    # ------------------------------------------------------------ wiring

    @property
    def cadence(self):
        return self._cadence

    @cadence.setter
    def cadence(self, value):
        """Assigning a new cadence rebuilds the gate (re-anchored at the
        solver's current iteration when attached), so tuning
        `solver.health.cadence` mid-run takes effect instead of being a
        silent no-op against the already-armed gate."""
        self._cadence = int(value)
        self.gate = metrics_mod.CadenceGate(self._cadence)
        if self.solver is not None:
            self.gate.reset(int(self.solver.iteration))

    def attach(self, solver):
        self.solver = solver
        return self

    def reset_failure(self):
        """Clear the failure latch after a resilient rewind
        (tools/resilience.py): the solver's health_error is dropped,
        `proceed` can flip True again, and the probe gate re-anchors at
        the rewound iteration. Forensic state (ring, postmortem_path,
        check/warning counts) is preserved — the flight recordings of
        every attempt remain on disk and in the ring."""
        self.failed_reason = None
        self._dt_dumped = False
        if self.solver is not None:
            self.solver._health_error = None
            self.gate.reset(int(self.solver.iteration))

    def reset_run(self):
        """Fresh-run reset for a POOLED solver (service/pool.py), called
        between served requests: clears the failure latch AND the per-run
        forensic state — unlike `reset_failure`, which deliberately
        preserves the ring and counters across a resilient rewind within
        one run. The compiled probe survives (it is what makes the pool
        warm); postmortem dumps already written stay on disk."""
        self.ring.clear()
        self.checks = 0
        self.warnings = 0
        self.postmortem_path = None
        self._warned = set()
        self.reset_failure()

    def attach_dt_source(self, cfl):
        """Register a CFL controller whose dt/frequency history feeds the
        flight recorder (extras.flow_tools.CFL self-registers)."""
        if cfl not in self._dt_sources:
            self._dt_sources.append(cfl)

    def attach_flow(self, flow, names):
        """Register a GlobalFlowProperty whose `report(names)` snapshot is
        included in post-mortem dumps."""
        self._flow_sources.append((flow, list(names)))

    # ------------------------------------------------------------- probe

    def _build_specs(self):
        """Host-side probe plan: per state field, the (offset, size) slice
        of the gathered X and the tail masks per monitored basis axis.
        Masks factorize over the (G, slot) layout: a separable axis mask
        depends only on the group index (G-vector), a coupled axis mask
        only on the slot position (S_f-vector) — so the probe stays one
        fused reduction with no reshapes."""
        from ..core.subsystems import state_key
        solver = self.solver
        layout = solver.layout
        groups = None
        specs = []
        offset = 0
        for v in solver.variables:
            size = layout.slot_size(v.domain, v.tensorsig)
            slot_shape = layout.slot_shape(v.domain, v.tensorsig)
            axes = []
            for axis, basis in enumerate(v.domain.bases):
                if basis is None:
                    continue
                label = _axis_label(basis, axis)
                if axis in layout.sep_widths:
                    # separable axis: tail by |wavenumber| over groups
                    if (getattr(basis, "dim", 1) != 1
                            or not hasattr(basis, "group_wavenumber")):
                        continue
                    n_ax = layout.sep_n_groups[axis]
                    if n_ax < 4:
                        continue
                    k = np.abs(np.asarray(basis.group_wavenumber(
                        np.arange(n_ax)), dtype=float))
                    kmax = k.max()
                    if kmax <= 0:
                        continue
                    tail_ax = k > (1.0 - TAIL_FRACTION) * kmax
                    if groups is None:
                        groups = list(layout.groups())
                    mask = np.array([tail_ax[g[axis]] for g in groups],
                                    dtype=float)
                    axes.append((label, "group", mask))
                else:
                    # coupled axis: tail by mode position in the slot
                    n_ax = slot_shape[1 + axis]
                    if n_ax < 4:
                        continue
                    idx = np.indices(slot_shape)[1 + axis].reshape(-1)
                    cut = int(np.ceil((1.0 - TAIL_FRACTION) * n_ax))
                    mask = (idx >= cut).astype(float)
                    axes.append((label, "slot", mask))
            specs.append((state_key(v), offset, size, axes))
            offset += size
        return specs

    def _ensure_probe(self):
        """Compile the fused health reduction (once; only when enabled)."""
        if self._probe is not None:
            return self._probe
        import jax
        import jax.numpy as jnp
        self._specs = specs = self._build_specs()

        def probe(X):
            with metrics_mod.trace_scope("health", "probe"):
                out = {}
                for name, off, size, axes in specs:
                    Xf = X[:, off:off + size]
                    absXf = jnp.abs(Xf)
                    a2 = jnp.square(absXf)
                    total = jnp.sum(a2)
                    tails = {}
                    for label, kind, mask in axes:
                        m = jnp.asarray(mask, dtype=a2.dtype)
                        if kind == "group":
                            te = jnp.sum(a2 * m[:, None])
                        else:
                            te = jnp.sum(a2 * m[None, :])
                        tails[label] = jnp.where(total > 0.0, te / total, 0.0)
                    out[name] = {
                        "nan": jnp.sum(jnp.isnan(Xf).astype(jnp.int32)),
                        "inf": jnp.sum(jnp.isinf(Xf).astype(jnp.int32)),
                        "max_abs": jnp.max(absXf),
                        "l2": jnp.sqrt(total),
                        "tail_frac": tails,
                    }
                return out

        # noted(): the probe participates in the retrace sentinel like the
        # lifted_jit step programs (tools/retrace.py)
        from . import retrace as retrace_mod
        self._probe = retrace_mod.noted_jit(probe, "health/probe")
        return self._probe

    # ------------------------------------------------------------- ticks

    def warm(self, X):
        """Compile the probe and take a baseline record (called at warmup
        end, like the metrics phase probes, so probe compilation stays out
        of measured windows)."""
        if not self.enabled or self.solver is None:
            return
        try:
            self.check(X)
        except SolverHealthError:
            raise
        except Exception as exc:
            # telemetry firewall: a probe failure disables health
            # monitoring instead of killing the simulation
            logger.warning(f"health probe disabled: {exc}")
            self.enabled = False

    def tick(self, n=1):
        """Per-step hook: cadence-check the solver state. Off-cadence cost
        is one gate comparison; nothing device-side happens."""
        if not self.enabled or self.failed_reason is not None:
            return
        solver = self.solver
        if solver is None or not self.gate.due(solver.iteration):
            return
        try:
            self.check(solver.X)
        except SolverHealthError:
            raise
        except Exception as exc:
            logger.warning(f"health probe disabled: {exc}")
            self.enabled = False

    def check(self, X=None):
        """Run the probe now, record, and evaluate thresholds. Returns the
        health record. Fatal findings mark the solver (graceful halt);
        they do not raise from here."""
        solver = self.solver
        if X is None:
            X = solver.X
        import jax
        with tracing.span("health/check"):
            stats = jax.device_get(self._ensure_probe()(X))
        self.checks += 1
        fields = {}
        for name, s in stats.items():
            fields[name] = {
                "nan": int(s["nan"]),
                "inf": int(s["inf"]),
                "max_abs": float(s["max_abs"]),
                "l2": float(s["l2"]),
                "tail_frac": {lab: round(float(v), 6)
                              for lab, v in s["tail_frac"].items()},
            }
        record = {
            "kind": "health_sample",
            "ts": round(time.time(), 3),
            "iteration": int(solver.iteration),
            "sim_time": float(solver.sim_time),
            "dt": float(solver.dt) if solver.dt is not None else None,
            "fields": fields,
        }
        self.ring.append(record)
        self._evaluate(record)
        return record

    def _ensure_value_probe(self):
        """The fused non-finite count over a list of device leaves (one
        jitted reduction, scalar output) shared by `check_values` and
        `nonfinite_count`."""
        import jax
        import jax.numpy as jnp
        probe = getattr(self, "_value_probe", None)
        if probe is None:
            from . import retrace as retrace_mod

            def raw(leaves):
                with metrics_mod.trace_scope("health", "values"):
                    total = jnp.zeros((), dtype=jnp.int32)
                    for leaf in leaves:
                        total = total + jnp.sum(
                            (~jnp.isfinite(leaf)).astype(jnp.int32))
                    return total
            # memoized on self just above (one wrapper per monitor, so
            # the retrace sentinel counts real signature churn only)
            probe = self._value_probe = retrace_mod.noted_jit(
                raw, "health/values")
        return probe

    def nonfinite_count(self, tree, phase="values"):
        """
        Fused device-side non-finite entry count over a pytree of device
        values: one jitted reduction, ONE scalar host pull, no verdict.
        This is the sync-light spelling of "is this state finite?" — the
        snapshot-validation paths (tools/resilience.Snapshot.is_finite,
        core/ensemble.FleetSnapshot) route through it instead of
        gathering the whole state to host (`np.asarray(X)` was a full
        device→host transfer per capture validation). Like
        `check_values` it is an explicit-call API: it works on a monitor
        built with enabled=False and never latches a failure.
        """
        import jax
        leaves = [leaf for leaf in jax.tree.leaves(tree)
                  if hasattr(leaf, "dtype")]
        if not leaves:
            return 0
        probe = self._ensure_value_probe()
        with tracing.span(f"health/{phase}"):
            return int(jax.device_get(probe(leaves)))

    def check_values(self, tree, phase="adjoint", context=None):
        """
        Explicit fused non-finite check over an arbitrary pytree of device
        values (the differentiable-solve path routes its loss + gradients
        through here, core/adjoint.py): one jitted reduction, one scalar
        host pull, and a structured `SolverHealthError` naming `phase`
        when anything is non-finite. Unlike the cadence-gated state probe
        this is an explicit-call API: it runs even on a monitor built
        with enabled=False (the zero-overhead contract covers the step
        loop's implicit ticks, not a caller asking for a verdict), it
        counts toward `checks`, and it does NOT latch the monitor failed
        — the solver state itself may be fine; only the requested
        computation is poisoned. Returns the non-finite entry count (0
        when healthy; the error is raised, not returned).
        """
        import jax
        leaves = [leaf for leaf in jax.tree.leaves(tree)
                  if hasattr(leaf, "dtype")]
        self.checks += 1
        if not leaves:
            return 0
        probe = self._ensure_value_probe()
        with tracing.span(f"health/{phase}"):
            bad = int(jax.device_get(probe(leaves)))
        if bad:
            solver = self.solver
            reason = (f"{phase}: non-finite values "
                      f"({bad} entries across the checked outputs)"
                      + (f" — {context}" if context else ""))
            raise SolverHealthError(
                reason,
                iteration=int(solver.iteration) if solver else None,
                sim_time=float(solver.sim_time) if solver else None)
        return 0

    def _evaluate(self, record):
        fatal = None
        for name, s in record["fields"].items():
            if s["nan"] or s["inf"]:
                fatal = (f"non-finite state: field '{name}' has "
                         f"{s['nan']} NaN / {s['inf']} Inf entries at "
                         f"iteration {record['iteration']}, "
                         f"sim_time {record['sim_time']:.6e}")
                break
            if np.isfinite(self.max_abs_limit) \
                    and s["max_abs"] > self.max_abs_limit:
                fatal = (f"growth bound exceeded: field '{name}' "
                         f"max|coeff| = {s['max_abs']:.3e} > "
                         f"{self.max_abs_limit:.3e} at iteration "
                         f"{record['iteration']}, "
                         f"sim_time {record['sim_time']:.6e}")
                break
            if s["l2"] > TAIL_ENERGY_FLOOR and not _tau_like(name):
                for label, frac in s["tail_frac"].items():
                    if frac > self.tail_warn_frac \
                            and (name, label) not in self._warned:
                        self._warned.add((name, label))
                        self.warnings += 1
                        logger.warning(
                            f"under-resolution: field '{name}' axis "
                            f"'{label}' holds {100 * frac:.1f}% of its "
                            f"energy in the top-third modes (warn "
                            f"threshold {100 * self.tail_warn_frac:.0f}%) "
                            f"at iteration {record['iteration']} — "
                            f"consider raising the resolution")
        if fatal:
            err = self._fail(fatal, record)
            self.solver._health_error = err
            logger.error(f"Numerical health failure, halting run: {fatal}"
                         + (f" (post-mortem: {err.postmortem_dir})"
                            if err.postmortem_dir else ""))

    # ----------------------------------------------------------- failure

    def invalid_dt(self, dt):
        """Structured error for a non-finite timestep (the CFL-blow-up
        path): dumps the flight recorder (when enabled, once per run) and
        returns the SolverHealthError for the caller to raise. Unlike a
        non-finite STATE this does not poison the solver — the state is
        still fine, so a legacy `except ValueError: retry with min_dt`
        guard keeps the run alive (as the SolverHealthError docstring
        promises); only the raise itself stops an unguarded loop."""
        solver = self.solver
        reason = (f"Invalid timestep: dt={dt!r} is non-finite at iteration "
                  f"{solver.iteration}, sim_time {solver.sim_time:.6e} "
                  f"(adaptive-CFL frequency blow-up upstream?)")
        pm = None
        if self.enabled and not self._dt_dumped:
            self._dt_dumped = True   # one forensic dump, not one per retry
            try:
                pm = self.dump_postmortem(reason)
            except Exception as exc:
                logger.warning(f"flight-recorder dump failed: {exc}")
        logger.error(f"Numerical health failure: {reason}"
                     + (f" (post-mortem: {pm})" if pm else ""))
        return SolverHealthError(
            reason, iteration=int(solver.iteration),
            sim_time=float(solver.sim_time),
            record=self.ring[-1] if self.ring else None,
            postmortem_dir=str(pm) if pm else None)

    def _fail(self, reason, record=None):
        """Mark failed, dump the flight recorder, build the error."""
        self.failed_reason = reason
        pm = None
        if self.enabled:
            try:
                pm = self.dump_postmortem(reason)
            except Exception as exc:
                logger.warning(f"flight-recorder dump failed: {exc}")
        self.postmortem_path = pm
        solver = self.solver
        if record is None and self.ring:
            record = self.ring[-1]
        return SolverHealthError(
            reason,
            iteration=int(solver.iteration) if solver else None,
            sim_time=float(solver.sim_time) if solver else None,
            record=record,
            postmortem_dir=str(pm) if pm else None)

    # --------------------------------------------------- flight recorder

    def dt_history(self):
        """Recent (iteration, dt, freq_max) entries from attached CFL
        controllers, oldest first."""
        out = []
        for src in self._dt_sources:
            out.extend(dict(e) for e in getattr(src, "history", ()))
        out.sort(key=lambda e: e.get("iteration", 0))
        return out

    def flow_report(self):
        """{name: stats} snapshots of attached GlobalFlowProperty sources
        (best-effort: a source whose tasks never evaluated is skipped)."""
        out = {}
        for flow, names in self._flow_sources:
            try:
                out.update(flow.report(names))
            except Exception as exc:
                logger.debug(f"flow report skipped: {exc}")
        return out

    def dump_postmortem(self, reason):
        """
        Write the post-mortem directory:
          postmortem.json       — the summary record (indented)
          record.jsonl          — the same record, one results.jsonl line
          health_ring.jsonl     — the ring buffer, one record per line
          state_at_failure.h5   — load_state-compatible checkpoint of the
                                  (possibly non-finite) state, clearly
                                  named as forensic evidence, never as a
                                  restartable "good" write
        Also appends the summary record to the metrics JSONL sink when one
        is configured. Returns the directory path.
        """
        solver = self.solver
        base = pathlib.Path(self.postmortem_dir)
        # collision-proof naming: iteration + wall-clock timestamp stem,
        # plus a counter for same-second repeats — a rewind-retry-fail
        # cycle rediverging at the SAME iteration must never overwrite an
        # earlier flight recording
        stamp = time.strftime("%Y%m%dT%H%M%S", time.gmtime())
        stem = f"postmortem_i{int(solver.iteration):08d}_{stamp}"
        path = base / stem
        n = 0
        while path.exists():
            n += 1
            path = base / f"{stem}_{n}"
        path.mkdir(parents=True)
        # visible to summary() before the flush below, so the step_metrics
        # record emitted during the dump already carries the pointer
        self.postmortem_path = path
        with open(path / "health_ring.jsonl", "w") as f:
            for rec in self.ring:
                f.write(json.dumps(_jsonable(rec)) + "\n")
        metrics_rec = None
        try:
            metrics_rec = solver.flush_metrics()
        except Exception as exc:
            logger.warning(f"post-mortem metrics flush failed: {exc}")
        checkpoint = None
        try:
            checkpoint = self._write_checkpoint(path / "state_at_failure.h5")
        except Exception as exc:
            logger.warning(f"post-mortem checkpoint failed: {exc}")
        record = {
            "kind": "health_postmortem",
            "ts": round(time.time(), 3),
            "reason": reason,
            "iteration": int(solver.iteration),
            "sim_time": float(solver.sim_time),
            "dt": float(solver.dt) if solver.dt is not None else None,
            "checks": self.checks,
            "warnings": self.warnings,
            "ring_records": len(self.ring),
            "fields": self.ring[-1]["fields"] if self.ring else {},
            "dt_history": self.dt_history(),
            "flow": self.flow_report(),
            "metrics": metrics_rec,
            "checkpoint": checkpoint,
            "directory": str(path),
        }
        resilience = getattr(solver, "resilience", None)
        if resilience is not None:
            # retry lineage: which rewind/backoff attempts preceded this
            # dump (tools/resilience.py), so a chain of flight recordings
            # reads as one story
            record["resilience"] = resilience.summary()
        record.update({k: v for k, v in solver.metrics.meta.items()
                       if k not in record})
        record = _jsonable(record)
        with open(path / "postmortem.json", "w") as f:
            json.dump(record, f, indent=2)
        with open(path / "record.jsonl", "w") as f:
            f.write(json.dumps(record) + "\n")
        solver.metrics.emit(record)
        return path

    def _write_checkpoint(self, path):
        """One-write HDF5 state dump with the FileHandler/load_state schema
        (scales/sim_time|iteration|write_number|timestep, tasks/<name>)."""
        import h5py
        from ..core.subsystems import state_key
        solver = self.solver
        with h5py.File(path, "w") as f:
            scales = f.create_group("scales")
            dt = solver.dt if solver.dt is not None else np.nan
            for key, val in (("sim_time", solver.sim_time),
                             ("iteration", solver.iteration),
                             ("write_number", 1),
                             ("timestep", dt)):
                scales.create_dataset(
                    key, data=np.array([val], dtype=np.float64))
            tasks = f.create_group("tasks")
            for var in solver.state:
                var.change_scales(1)
                data = np.asarray(var["g"])
                tasks.create_dataset(state_key(var), data=data[None])
        return path.name

    # ----------------------------------------------------------- summary

    def summary(self):
        """Compact health summary attached to telemetry flushes and bench
        records (None when disabled)."""
        if not self.enabled and self.failed_reason is None:
            return None
        out = {"checks": self.checks, "warnings": self.warnings,
               "ok": self.failed_reason is None}
        if self.failed_reason is not None:
            out["reason"] = self.failed_reason
        if self.postmortem_path is not None:
            # set early in dump_postmortem, so even the metrics record
            # flushed DURING the dump carries the pointer; also covers
            # invalid-dt dumps (which do not mark the monitor failed)
            out["postmortem"] = str(self.postmortem_path)
        if self.ring:
            last = self.ring[-1]
            out["last_iteration"] = last["iteration"]
            out["max_abs"] = max(
                (s["max_abs"] for s in last["fields"].values()), default=0.0)
            out["max_tail_frac"] = max(
                (v for s in last["fields"].values()
                 for v in s["tail_frac"].values()), default=0.0)
        # diverged states put inf/nan here; keep the summary strict-JSON
        return _jsonable(out)


def resolve(spec=None, solver=None, cadence=None, ring_size=None,
            postmortem_dir=None):
    """
    Resolve a solver's `health` argument against the [health] config: a
    HealthMonitor passes through (attached to the solver); True/None build
    from config (None respects HEALTH_DEFAULT, True forces on); False
    builds a disabled monitor (still attached, so `solver.health` always
    exists and the invalid-dt path stays structured).
    """
    if isinstance(spec, HealthMonitor):
        return spec.attach(solver)
    section = config["health"] if config.has_section("health") else {}

    def get(key, fallback):
        try:
            return section.get(key, fallback) or fallback
        except AttributeError:
            return fallback

    if spec is None:
        default = str(get("HEALTH_DEFAULT", "True")).strip().lower()
        enabled = default in ("1", "true", "yes", "on")
    else:
        enabled = bool(spec)
    if cadence is None:
        cadence = int(get("CHECK_CADENCE", "200"))
    if ring_size is None:
        ring_size = int(get("RING_SIZE", "64"))
    if postmortem_dir is None:
        postmortem_dir = get("POSTMORTEM_DIR", "postmortems")
    monitor = HealthMonitor(
        enabled=enabled, cadence=cadence, ring_size=ring_size,
        max_abs_limit=float(get("MAX_ABS_LIMIT", "1e12")),
        tail_warn_frac=float(get("TAIL_WARN_FRAC", "0.25")),
        postmortem_dir=postmortem_dir)
    return monitor.attach(solver)


# ------------------------------------------------------- post-mortem CLI

def read_postmortem(path):
    """Load a post-mortem summary record from a directory (postmortem.json
    / record.jsonl) or a record file path. Returns (record, ring) where
    ring is the list of health records (empty when absent)."""
    path = pathlib.Path(path)
    if path.is_dir():
        for name in ("postmortem.json", "record.jsonl"):
            cand = path / name
            if cand.exists():
                rec_path = cand
                break
        else:
            raise FileNotFoundError(
                f"{path}: no postmortem.json or record.jsonl")
        ring_path = path / "health_ring.jsonl"
    else:
        rec_path = path
        ring_path = path.parent / "health_ring.jsonl"
    text = rec_path.read_text().strip()
    record = json.loads(text.splitlines()[0]) if rec_path.suffix == ".jsonl" \
        else json.loads(text)
    ring = []
    if ring_path.exists():
        for line in ring_path.read_text().splitlines():
            line = line.strip()
            if line:
                try:
                    ring.append(json.loads(line))
                except ValueError:
                    pass
    return record, ring


def format_postmortem(record, ring=()):
    """Render a post-mortem record as text lines (the `postmortem` CLI)."""
    lines = []
    lines.append(f"Post-mortem: {record.get('reason', '(no reason recorded)')}")
    it = record.get("iteration")
    st = record.get("sim_time")
    dt = record.get("dt")
    lines.append(f"  iteration={it}  sim_time={st}  dt={dt}")
    ident = " ".join(f"{k}={record[k]}"
                     for k in ("config", "backend", "dtype")
                     if record.get(k) is not None)
    if ident:
        lines.append(f"  {ident}")
    fields = record.get("fields") or {}
    if fields:
        lines.append(f"  fields at failure ({len(fields)}):")
        for name, s in fields.items():
            tails = s.get("tail_frac") or {}
            numeric = [v for v in tails.values()
                       if isinstance(v, (int, float))]
            strings = [v for v in tails.values() if isinstance(v, str)]
            worst = strings[0] if strings else max(numeric, default=0.0)
            lines.append(
                f"    {name:<12} nan={s.get('nan', 0):<6} "
                f"inf={s.get('inf', 0):<6} "
                f"max|c|={_fmt(s.get('max_abs', 0.0))}  "
                f"L2={_fmt(s.get('l2', 0.0))}  tail={_fmt(worst)}")
    hist = record.get("dt_history") or []
    if hist:
        last = hist[-1]
        lines.append(f"  dt history: {len(hist)} entries, last "
                     f"dt={last.get('dt')} freq_max={last.get('freq_max')} "
                     f"at iteration {last.get('iteration')}")
    flow = record.get("flow") or {}
    for name, s in flow.items():
        lines.append(f"  flow {name}: {s}")
    if ring:
        lines.append(f"  ring buffer: {len(ring)} records, iterations "
                     f"{ring[0].get('iteration')}..{ring[-1].get('iteration')}")
    metrics_rec = record.get("metrics")
    if metrics_rec:
        lines.append(f"  metrics: {metrics_rec.get('iterations', 0)} "
                     f"iterations, "
                     f"{metrics_rec.get('steps_per_sec', 0.0)} steps/s")
    if record.get("checkpoint"):
        lines.append(f"  checkpoint: {record['checkpoint']} "
                     f"(state at failure — forensic, may be non-finite)")
    resilience = record.get("resilience")
    if isinstance(resilience, dict):
        lines.append(
            f"  resilience: {resilience.get('rewinds', 0)} rewind(s), "
            f"{resilience.get('retries', 0)} retry(ies)"
            + (f", resumed from {resilience['resumed_from']}"
               if resilience.get("resumed_from") else ""))
        for attempt in resilience.get("lineage") or []:
            lines.append(
                f"    attempt {attempt.get('attempt', '?')}: failed at "
                f"iteration {attempt.get('failure_iteration', '?')} "
                f"({attempt.get('reason', '?')}) -> "
                f"{attempt.get('outcome', '?')}"
                + (f" @ iteration {attempt['rewind_iteration']}, "
                   f"dt capped {_fmt(attempt.get('dt_limit'))}"
                   if attempt.get("rewind_iteration") is not None else ""))
    lines.append(f"  checks={record.get('checks', 0)} "
                 f"warnings={record.get('warnings', 0)}")
    return lines

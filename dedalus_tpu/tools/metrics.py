"""
Step-loop metrics: named counters, phase timers, device-memory watermarks,
and a JSONL telemetry sink.

Async-dispatch awareness: JAX dispatch is asynchronous, so a host timer
around a dispatched computation measures enqueue latency, not device work,
unless the result is blocked on — and blocking every iteration serializes
the dispatch pipeline. Phase timers therefore bracket `block_until_ready`
only on sampled iterations (every `SAMPLE_CADENCE`-th step, config section
[profiling]); off-cadence iterations pay one counter bump and no device
sync. Sampled phase times are re-measurements of the already-compiled step
pieces on the current state (the solver supplies the thunks), so sampling
never perturbs the solution.

Naming scheme: phase timer names are the `jax.named_scope` labels on the
corresponding traced code, prefixed `dedalus/` — `dedalus/transform/...`,
`dedalus/matsolve/...`, `dedalus/transpose/...`, `dedalus/evaluator/...`,
`dedalus/step...`, `dedalus/health/...` (the numerical-health probe,
tools/health.py), `dedalus/adjoint/...` (the differentiable-solve
forward/loss scopes and grad dispatch annotations, core/adjoint.py) — so
per-phase wall aggregates in the JSONL record and op rows in a
`jax.profiler` trace share one vocabulary. Records flushed by a
DifferentiableIVP carry an `adjoint` sub-dict (grad_steps_per_sec,
checkpoint segments, grad/forward cost ratio, peak device memory) that
`report` renders as its own block.

Flush emits ONE record per call, shaped like `benchmarks/results.jsonl`
rows (flat JSON object, `ts` + `config`/`backend`/`dtype` keys) with the
phase breakdown attached; `python -m dedalus_tpu report <file.jsonl>`
summarizes the records.

Resilience vocabulary: the `resilience/...` counter scope carries the
recovery trajectory (rewinds, retries, dt_backoffs, snapshots,
io_retries, checkpoints_written/validated, resumes) plus the durability
and integrity columns added with the sharded tier —
`resilience/checkpoint_stall_sec` (cumulative wall the step loop was
held by durable checkpoint writes: the whole write for synchronous
formats, just the submit/overrun-barrier wait for async sharded ones),
`resilience/sdc_checks` / `resilience/sdc_detected` (silent-corruption
sentinel re-executions and caught mismatches). The flushed `resilience`
block mirrors them and adds a `checkpoint` sub-dict
(format/async/written/stall_sec/max_inflight/errors from the
dcheckpoint writer). Fleet records add `ensemble/reshards` and a
`reshards` field in the `ensemble` block — one per device-loss
re-sharding event (core/ensemble.py).

Served-latency vocabulary: records flushed by the warm-pool service
(dedalus_tpu/service/) carry a `serving` sub-dict —
`queue_sec` (accept -> dispatch wait), `pool_verdict`
("hit" | "warm-cache" | "cold": warm pool reuse / fresh build off the
persistent assembly cache / fully cold build), `time_to_first_step_sec`
(dispatch -> first step complete, including any build+compile a miss
pays), `build_sec`, `request_id`, and `deadline_sec` when the request
set one. Service-level fault-tolerance counters (shed, deadline
exceeded, watchdog fires, circuit-breaker opens/fast-fails, client
drops, idempotent replays, memory-watermark evictions) ride the `stats`
reply and the drain-time `service_stats` record under `faults`; a hung
dispatch additionally leaves a `watchdog_postmortem` record (request
id, stuck seconds, thread stacks). This sink format doubles as the
service's wire format, so streamed frames and the daemon's JSONL file
are the same records.

Trajectory vocabulary: every row appended through the bench driver or
the lint cost tier is stamped with an `env` host/environment fingerprint
(tools/envinfo.py: backend, device kind/count, jax/jaxlib/python
versions, hashed hostname, load average) so cross-host history is
attributable. `kind: ledger` rows (tools/lint/progcheck.py cost tier,
`lint --programs --ledger`) carry per-census-program compile-time
resource costs — flops, transcendentals, bytes accessed,
argument/output/temp/peak memory, HLO instruction count, scan depths —
plus the resolved-plan provenance block. `python -m dedalus_tpu
perfwatch` reads the whole file as a perf trajectory and flags
noise-band regressions per series (docs/observability.md).
"""

import atexit
import collections
import contextlib
import functools
import itertools
import json
import os
import signal
import threading
import time
import weakref

import numpy as np
import jax

from . import retrace
from . import tracing
from .config import config
from .lint.threadcheck import named_lock

__all__ = ["PHASES", "SUM_PHASES", "BUILD_PHASES", "CadenceGate", "Counter",
           "PhaseTimer",
           "MemoryWatermark", "Metrics", "BuildPhases", "trace_scope",
           "annotate", "scoped", "resolve", "format_phase_table",
           "register_exit_flush", "flush_pending", "process_rss_bytes"]

# The hot-path phase vocabulary (shared with trace annotations).
# SUM_PHASES is the step DECOMPOSITION: rows that partition one step and
# should sum to ~the loop wall. The `fused` row (present when the fused
# step path is active, core/fusedstep.py) is an ALTERNATIVE whole-step
# attribution — the one-dispatch fused program re-measured end-to-end —
# that OVERLAPS the decomposition rows, so it is excluded from phase
# sums: `fused` below the decomposition sum is the fusion win (separate
# dispatches pay per-phase boundaries the fused program elides).
SUM_PHASES = ("transform", "matsolve", "transpose", "evaluator")
# `transpose_exposed` / `transpose_overlapped` split the distributed
# transpose wall of an OVERLAPPED chunked walk (parallel/transposes.py,
# [distributed] TRANSPOSE_CHUNKS): exposed = communication the step
# still waits on after chunking; overlapped = communication hidden
# under the interleaved chunk transforms. Like `fused`, they OVERLAP
# the `transpose` decomposition row (exposed + overlapped ~= the
# monolithic transpose wall), so they are excluded from phase sums —
# benchmarks/scaling.py measures and records them per device count.
PHASES = SUM_PHASES + ("fused", "transpose_exposed", "transpose_overlapped")

# The cold-start (build) phase vocabulary (BuildPhases has each one's
# meaning). Labels double as `build/<name>` spans and `dedalus/build/...`
# profiler rows, so traces and telemetry share one vocabulary. `compile`
# is not among them: it is the sum of the solver's program rows.
BUILD_PHASES = ("layout", "assembly_cache", "host_assembly", "pattern",
                "structure", "factor", "basis_stacks", "plans", "upload",
                "dd_prepare", "shard", "ensemble")


def trace_scope(phase, detail=None):
    """Named scope for traced code: labels the XLA ops compiled under it so
    profiler traces group by the same phase names the timers report."""
    name = f"dedalus/{phase}" + (f"/{detail}" if detail else "")
    return jax.named_scope(name)


def annotate(label, **kwargs):
    """Host-level profiler annotation (TraceMe row around a dispatch);
    near-free when no trace is being captured."""
    return jax.profiler.TraceAnnotation(label, **kwargs)


def scoped(fn, label):
    """Wrap a callable in a jax.named_scope so profiler traces label the
    ops it compiles with the shared phase vocabulary (the single helper
    behind the transform-plan and matsolver wrapping)."""
    def wrapper(*args, **kw):
        with jax.named_scope(label):
            return fn(*args, **kw)
    wrapper.__name__ = getattr(fn, "__name__", "scoped")
    return wrapper


class CadenceGate:
    """
    Consuming iteration-cadence gate: `due(iterations)` fires once per
    cadence crossing and advances the next due point past the observed
    count (a block of steps crossing several multiples fires once). The
    single gating primitive behind both the [profiling] phase sampler and
    the [health] probe, so the two subsystems cannot drift in semantics.
    """

    __slots__ = ("cadence", "_next_due")

    def __init__(self, cadence):
        self.cadence = int(cadence)
        self._next_due = max(self.cadence, 1)

    def reset(self, iterations=0):
        """Re-anchor: the next fire is one full cadence past `iterations`."""
        self._next_due = iterations + max(self.cadence, 1)

    def due(self, iterations):
        if self.cadence <= 0:
            return False
        if iterations >= self._next_due:
            self._next_due = iterations + self.cadence
            return True
        return False


class BuildPhases:
    """
    Wall-clock accounting of one solver's BUILD (cold start), the set-up
    sibling of the step-loop PhaseTimer. Always on; nothing per step.

    `init()` brackets the solver's whole `__init__` (`init_sec`;
    `timed_init` wraps every solver class's). `scope(name)` brackets one
    phase, accumulating across re-entries (Newton rebuilds), and is a
    `build/<name>` span (and, where `tracing.live()`, a
    `dedalus/build/<name>` row on the profiler's host plane). Phases are
    FLAT AND EXCLUSIVE: a scope opened inside another takes its time out
    of it, and one opened inside a program's first call (a stack built the
    first time a trace asks for it) out of that row. The names
    (`BUILD_PHASES`):

        layout          pencil layout, subproblems, validity masks
        assembly_cache  the persistent assembly cache: key, load and
                        install (the dense scatter of a cached COO store
                        with it) or export and store

        host_assembly   symbolic assembly of the pencil matrices
        pattern         the banded attempt's per-group views of the
                        assembled entries and their magnitude scale
        structure       the banded structural analysis
        factor          upload of M and L + the run's first factorization
                        with its program's compile or cache load, waited for
        basis_stacks    the curvilinear bases' host-built per-m / per-ell
                        matrix stacks (sphere, disk, annulus, shell, ball)
        plans           transform plans and the fused evaluator's plan
        upload          host -> device copies that are not M's and L's:
                        every program's lifted constants (stacks, planes,
                        masks) on their first use
        dd_prepare      the float64 route's host work: float64 copies of M
                        and L, their int8 planes and float32 pairs, the
                        double-double state (core/ddstep.py)
        shard           parallel.distribute_solver: the state's, M's and
                        L's copies onto the mesh, each leaf split on its
                        pencil-group axis
        ensemble        an EnsembleSolver over this solver (it books to
                        its template's ledger): the fleet's state and
                        operands, the members' initial conditions and
                        their upload

    `compile` is no scope: `compile_sec` is the sum of `first_call_sec`
    over the program rows this solver owns (tools/retrace.py: the thread's
    current BuildPhases when a program was first called), every program to
    date, and may overlap `factor` (the factor program's first call).

    "Current" is the BuildPhases whose `init()` is open on this thread,
    else the one whose `init()` closed or that called `enter()` (a
    solver's `step`, `step_many`, `solve`) last: `build_scope(name)` books
    there, or to the process-level `process_phases()` where the thread has
    none (a basis building a stack before any solver exists).

    `record()` flattens to the `<name>_sec` keys telemetry records and
    bench rows carry, plus `init_sec`, `unnamed_sec` (= `init_sec` less
    everything named inside `init()`: the phases and, outside any phase,
    the first calls of programs, `init_compile_sec`), the assembly-cache
    verdict, the float64 route, the first step program's `group_stacks`
    tally, and `programs`: this solver's totals of the set-up ledger and
    its twelve rows with the largest `first_call_sec`.
    """

    TOP_ROWS = 12

    def __init__(self, owner="process"):
        self.name = f"{owner}#{next(_phase_serial)}"
        self.seconds = {}
        self.cache = "off"   # off | miss | hit
        # which way the first step program applies its `gblocks` stacks
        # (core/curvilinear.gblocks_tally), once it has been lowered
        self.group_stacks = None
        # which route keeps a float64 problem's guarantee: "dd" (the
        # emulated-f64 runner, core/ddstep.py; `dd` is then the callable
        # that gives its counters, held weakly: this object outlives its
        # solver in the process's list), "xla_f64" (XLA's own float64:
        # native on a CPU, software on a TPU), None for a narrower dtype
        self.f64_route = None
        self.dd = None
        # where a solver is sharded over a pencil mesh, the callable that
        # gives {chips, groups_per_chip, Gc_per_chip}
        # (parallel.distribute_solver; it holds its operators weakly)
        self.pencils = None
        self.init_sec = 0.0
        self._init_depth = 0
        self._named_in_init = 0.0
        self.init_compile_sec = 0.0
        self._rows = []        # the TOP_ROWS largest rows this solver owns
        self._row_totals = dict.fromkeys(retrace.ROW_SECONDS, 0.0)
        self._row_counts = {"programs": 0, "cache_hits": 0,
                            "cache_misses": 0}
        if owner != "process":
            _all_phases.append(self)

    # ------------------------------------------------------------ current

    def enter(self):
        """Make this the thread's current BuildPhases: the entry points'
        ONE assignment (`step`, `step_many`, `solve`)."""
        _current.phases = self

    @contextlib.contextmanager
    def init(self):
        """Bracket a solver's `__init__`: `init_sec` (the outermost of a
        class chain's), with this the thread's current BuildPhases."""
        prev = getattr(_current, "phases", None)
        _current.phases = self
        self._init_depth += 1
        t0 = time.perf_counter()
        try:
            yield self
        finally:
            self._init_depth -= 1
            if not self._init_depth:
                self.init_sec += time.perf_counter() - t0
                # built inside another solver's __init__: that one goes
                # on; else what follows (initial conditions) is this one's
                if prev is not None and prev._init_depth:
                    _current.phases = prev

    # ------------------------------------------------------------- phases

    class _Scope:
        def __init__(self, phases, name):
            self.phases = phases
            self.name = name

        def __enter__(self):
            # child span under the ambient trace (the server's
            # pool_acquire span when a cold build runs inside a request);
            # a live span is its own `dedalus/build/<name>` profiler row
            self.span = tracing.span(f"build/{self.name}")
            self.span.__enter__()
            self.inner = 0.0
            _open_scopes().append(self)
            # a phase inside a program's first call is the phase's time
            self.pause = retrace.pause_row()
            self.pause.__enter__()
            self.t0 = time.perf_counter()
            return self

        def __exit__(self, *exc):
            dt = time.perf_counter() - self.t0
            self.pause.__exit__(*exc)
            stack = _open_scopes()
            if stack and stack[-1] is self:
                stack.pop()
            if stack:
                stack[-1].inner += dt    # exclusive: the parent loses it
            self.phases.add(self.name, max(dt - self.inner, 0.0))
            return self.span.__exit__(*exc)

    def scope(self, name):
        return self._Scope(self, name)

    def add(self, name, seconds):
        seconds = float(seconds)
        self.seconds[name] = self.seconds.get(name, 0.0) + seconds
        if self._init_depth:
            self._named_in_init += seconds

    def book_program(self, row):
        """One closed row of the set-up ledger that this solver owns
        (tools/retrace.ProgramRow.close)."""
        for key in retrace.ROW_SECONDS:
            self._row_totals[key] += row[key]
        self._row_counts["programs"] += 1
        if row["cache"] == "hit":
            self._row_counts["cache_hits"] += 1
        elif row["cache"] == "miss":
            self._row_counts["cache_misses"] += 1
        self._rows.append(row)
        self._rows.sort(key=lambda r: -r["first_call_sec"])
        del self._rows[self.TOP_ROWS:]
        if self._init_depth and not _open_scopes():
            # inside __init__ and under no phase: named by the row
            self.init_compile_sec += row["first_call_sec"]
            self._named_in_init += row["first_call_sec"]

    @property
    def compile_sec(self):
        return self._row_totals["first_call_sec"]

    def programs(self):
        """This solver's totals of the set-up ledger, the process's eager
        aggregate, and its largest rows."""
        out = dict(self._row_counts)
        out.update({k: round(v, 4) for k, v in self._row_totals.items()})
        out["eager"] = retrace.sentinel.eager_totals()
        out["rows"] = [dict(r) for r in self._rows]
        return out

    def record(self):
        out = {f"{name}_sec": round(self.seconds.get(name, 0.0), 4)
               for name in BUILD_PHASES}
        out["compile_sec"] = round(self.compile_sec, 4)
        out["init_sec"] = round(self.init_sec, 4)
        out["init_compile_sec"] = round(self.init_compile_sec, 4)
        out["unnamed_sec"] = round(
            max(self.init_sec - self._named_in_init, 0.0), 4)
        out["assembly_cache"] = self.cache
        if self.group_stacks is not None:
            out["group_stacks"] = self.group_stacks
        out["f64_route"] = self.f64_route
        dd = self.dd() if self.dd is not None else None
        if dd is not None:
            out["dd"] = dd()
        if self.pencils is not None:
            out.update(self.pencils())
        out["programs"] = self.programs()
        return out


_phase_serial = itertools.count(1)
_current = threading.local()    # .phases: current BuildPhases; .scopes
# every solver's BuildPhases of the process, newest last. Bounded, and held
# strongly: a BuildPhases holds no reference to its solver, and a reader
# after the fact wants the seconds of solvers that are gone (the LBVP a
# configuration solves on its way to the IVP)
_all_phases = collections.deque(maxlen=256)


def _open_scopes():
    stack = getattr(_current, "scopes", None)
    if stack is None:
        stack = _current.scopes = []
    return stack


_process_phases = BuildPhases()


def current_phases():
    """The thread's current BuildPhases (see BuildPhases), or None."""
    return getattr(_current, "phases", None)


def process_phases():
    """Where `build_scope` books when the thread has no current solver."""
    return _process_phases


def all_phases():
    """The BuildPhases of every solver this process built (the newest
    256), oldest first; `name` is `<SolverClass>#<n>`."""
    return list(_all_phases)


def build_scope(name):
    """`scope(name)` of the thread's current BuildPhases, else of the
    process-level one: for set-up code with no solver in reach."""
    return (current_phases() or _process_phases).scope(name)


def in_build_scope(name):
    """Decorator: the function's calls run inside `build_scope(name)`."""
    def decorate(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with build_scope(name):
                return fn(*args, **kwargs)
        return wrapper
    return decorate


def timed_init(init):
    """Decorator for a solver class's `__init__`: the whole of it runs
    inside `self.build_phases.init()` (created here, under the class's
    name, by the first `__init__` of a chain to run)."""
    @functools.wraps(init)
    def wrapper(self, *args, **kwargs):
        phases = self.__dict__.get("build_phases")
        if phases is None:
            phases = self.build_phases = BuildPhases(type(self).__name__)
        with phases.init():
            return init(self, *args, **kwargs)
    return wrapper


class Counter:
    """Named monotonic counter."""

    __slots__ = ("name", "value")

    def __init__(self, name):
        self.name = name
        self.value = 0

    def inc(self, n=1):
        self.value += n
        return self.value


class PhaseTimer:
    """Accumulates sampled per-step seconds for each phase, plus a
    log-bucketed histogram per phase (tools/tracing.LogHistogram) so
    flushed records and the `report` CLI carry tail percentiles
    (p50/p95/p99), not just means — the tails are what a serving tier
    lives or dies by. The histogram feed is always on (one log + one
    dict bump per sample) regardless of whether tracing is enabled."""

    def __init__(self, phases=PHASES):
        self.totals = {p: 0.0 for p in phases}
        self.counts = {p: 0 for p in phases}
        self.hists = {}

    def add(self, phase, seconds):
        self.totals[phase] = self.totals.get(phase, 0.0) + float(seconds)
        self.counts[phase] = self.counts.get(phase, 0) + 1
        h = self.hists.get(phase)
        if h is None:
            h = self.hists[phase] = tracing.LogHistogram()
        h.add(seconds)

    def mean(self, phase):
        n = self.counts.get(phase, 0)
        return self.totals.get(phase, 0.0) / n if n else 0.0

    def percentiles(self, phase):
        """{p50, p95, p99} seconds for one phase, or None when the phase
        has no samples."""
        h = self.hists.get(phase)
        if h is None or not h.total:
            return None
        return {"p50": h.percentile(50), "p95": h.percentile(95),
                "p99": h.percentile(99)}

    @property
    def samples(self):
        return max(self.counts.values(), default=0)


def process_rss_bytes():
    """Resident-set size of THIS process in bytes (0 when unreadable).
    The device-side MemoryWatermark tracks accelerator allocations; this
    is its host-side sibling — the number the serving daemon's
    memory-watermark shedding ([service] MEM_WATERMARK_MB) compares
    against, since on CPU backends the pooled solvers' matrices and
    compiled programs all live in process RSS."""
    try:
        with open("/proc/self/statm") as f:
            return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
    except (OSError, ValueError, IndexError, AttributeError):
        try:
            import resource
            import sys
            # ru_maxrss is KiB on Linux but BYTES on macOS (peak, not
            # current — still a usable over-estimate where /proc is
            # unavailable)
            scale = 1 if sys.platform == "darwin" else 1024
            return resource.getrusage(
                resource.RUSAGE_SELF).ru_maxrss * scale
        except Exception:
            return 0


class MemoryWatermark:
    """Tracks peak device-memory use across samples. Prefers the backend's
    allocator stats (`device.memory_stats()`, available on TPU/GPU); falls
    back to summing live device arrays where the backend exposes no stats
    (CPU)."""

    def __init__(self):
        self.peak_bytes = 0
        self.source = None

    def sample(self):
        current = None
        try:
            stats = jax.local_devices()[0].memory_stats()
            if stats:
                current = stats.get("peak_bytes_in_use",
                                    stats.get("bytes_in_use"))
                if current is not None:
                    self.source = "memory_stats"
        except Exception:
            current = None
        if current is None:
            try:
                current = sum(int(a.nbytes) for a in jax.live_arrays())
                self.source = "live_arrays"
            except Exception:
                return self.peak_bytes
        self.peak_bytes = max(self.peak_bytes, int(current))
        return self.peak_bytes


class Metrics:
    """
    Registry of counters, one phase timer, and a memory watermark, with
    cadence-gated sampling and a JSONL sink.

    Loop accounting: `observe_steps(n)` counts iterations and stamps the
    loop clock (the first call — or `reset_loop()`, which the solver calls
    at warmup end so compile time stays out of the window — anchors t0).
    `flush()` turns the sampled per-step phase means into loop-total
    estimates and appends one JSONL record to `sink` when set.
    """

    def __init__(self, sample_cadence=200, sink=None, enabled=True,
                 sampling=True, meta=None):
        self.enabled = bool(enabled)
        self.sampling = bool(sampling) and self.enabled
        # constructed intent, restored by reset_run(): the phase-sampling
        # firewall (_try_sample_phases) may flip `sampling` off mid-run
        self._sampling_default = self.sampling
        self.sample_cadence = int(sample_cadence)
        self.sink = str(sink) if sink else None
        self.meta = dict(meta or {})
        self.counters = {}
        self.timer = PhaseTimer()
        self.memory = MemoryWatermark()
        self.iterations = 0
        # unflushed-activity latch: set by step/counter observations,
        # cleared by flush() — the exit-flush hooks use it to decide
        # whether an interrupted run still owes a telemetry record
        self.dirty = False
        self._loop_t0 = None
        self._gate = CadenceGate(self.sample_cadence)
        self._warmed = set()

    def reset_run(self, meta=None):
        """Zero the per-run accounting (counters, phase samples, memory
        watermark, loop window, dirty latch) while keeping identity:
        sink, cadence, enabled flags, meta, and retrace-sentinel
        subscriptions all survive. The warm-pool service
        (dedalus_tpu/service/pool.py) calls this between requests so one
        Metrics instance per pooled solver serves many runs without one
        request's counters bleeding into the next record."""
        self.counters = {}
        self.timer = PhaseTimer()
        self.memory = MemoryWatermark()
        self.iterations = 0
        self.dirty = False
        self._loop_t0 = None
        self._gate.reset(0)
        self._warmed = set()
        # a probe failure's firewall disable (sampling=False) is per-run
        # state, not identity — the next request samples again
        self.sampling = self._sampling_default
        if meta:
            self.meta.update(meta)

    # ------------------------------------------------------------- counters

    def counter(self, name):
        c = self.counters.get(name)
        if c is None:
            c = self.counters[name] = Counter(name)
        return c

    def inc(self, name, n=1):
        if not self.enabled:
            return 0
        self.dirty = True
        return self.counter(name).inc(n)

    # ----------------------------------------------------------------- loop

    def observe_steps(self, n=1):
        """Count n completed steps (non-blocking; no device sync)."""
        if not self.enabled:
            return
        if self._loop_t0 is None:
            self._loop_t0 = time.perf_counter()
        self.iterations += int(n)
        self.dirty = True

    def reset_loop(self):
        """Re-anchor the loop window (called at warmup end so compile and
        ramp time stay out of the per-step accounting)."""
        self.iterations = 0
        self._loop_t0 = time.perf_counter()
        self._gate.reset(0)

    def loop_wall(self):
        if self._loop_t0 is None:
            return 0.0
        return time.perf_counter() - self._loop_t0

    # ------------------------------------------------------------- sampling

    def due(self):
        """Whether a phase sample is due at the current iteration count;
        consuming (the next due point advances by one cadence)."""
        if not self.sampling:
            return False
        return self._gate.due(self.iterations)

    def time_thunk(self, name, thunk):
        """Wall-time one thunk, bracketing `block_until_ready`. The first
        call per name runs untimed (jit compilation / cache warm)."""
        if name not in self._warmed:
            jax.block_until_ready(thunk())
            self._warmed.add(name)
        t0 = time.perf_counter()
        jax.block_until_ready(thunk())
        return time.perf_counter() - t0

    def add_phase_sample(self, seconds_by_phase):
        """Record one sampled per-step attribution {phase: seconds}. With
        tracing enabled each measurement also lands as a `phase/<name>`
        span under the ambient trace (the request's `run` span when the
        sample fires inside a served step loop)."""
        for phase, sec in seconds_by_phase.items():
            self.timer.add(phase, sec)
            if tracing.enabled():
                tracing.add_span(f"phase/{phase}", sec)
        self.inc("phase_samples")
        self.memory.sample()

    # ---------------------------------------------------------------- flush

    def emit(self, record):
        """Append one arbitrary record to the configured JSONL sink — the
        shared telemetry channel used by flush() step records and the
        health monitor's post-mortem records. Returns the record (with a
        `ts` stamped when missing), or None when disabled or sinkless."""
        if not (self.enabled and self.sink):
            return None
        record = dict(record)
        record.setdefault("ts", round(time.time(), 1))

        def write():
            parent = os.path.dirname(os.path.abspath(self.sink))
            os.makedirs(parent, exist_ok=True)
            with open(self.sink, "a") as f:
                f.write(json.dumps(record) + "\n")

        # transient host/IO faults (flaky disk/NFS) are retried with
        # backoff under the [resilience] IO_RETRIES/IO_BASE_DELAY budget
        # (tools/resilience.io_retry_policy classification); a
        # persistently failing sink degrades to a warning — telemetry
        # must never kill the simulation
        try:
            from .resilience import io_retry_policy
            io_retry_policy().call(
                write, label=f"metrics sink {self.sink}")
        except OSError as exc:
            import logging
            logging.getLogger(__name__).warning(
                f"metrics sink {self.sink}: {exc}")
        return record

    def flush(self, extra=None):
        """Build one telemetry record (and append it to the JSONL sink when
        configured). Callers should block on outstanding device work first
        (the solver's `flush_metrics` does) so the loop wall time covers
        the device tail of the final dispatch."""
        if not self.enabled:
            return None
        self.memory.sample()
        wall = self.loop_wall()
        iters = self.iterations
        phase_mean = {p: self.timer.mean(p) for p in PHASES}
        phase_total = {p: phase_mean[p] * iters for p in PHASES}
        phase_pct = {}
        for p in PHASES:
            pct = self.timer.percentiles(p)
            if pct:
                phase_pct[p] = {k: round(v, 6) for k, v in pct.items()}
        # the fused whole-step row overlaps the decomposition rows (see
        # the PHASES note): only the decomposition enters the sum
        phase_sum = sum(phase_total[p] for p in SUM_PHASES)
        record = {
            "kind": "step_metrics",
            "ts": round(time.time(), 1),
            "iterations": iters,
            "loop_wall_sec": round(wall, 6),
            "steps_per_sec": round(iters / wall, 4) if wall > 0 else 0.0,
            "sample_cadence": self.sample_cadence,
            "phase_samples": self.timer.samples,
            "phase_mean_sec": {p: round(v, 6) for p, v in phase_mean.items()},
            "phase_pct_sec": phase_pct,
            "phase_total_sec": {p: round(v, 6) for p, v in phase_total.items()},
            "phase_sum_frac": round(phase_sum / wall, 4) if wall > 0 else 0.0,
            "device_mem_peak_bytes": self.memory.peak_bytes,
            "mem_source": self.memory.source,
            "counters": {name: c.value for name, c in self.counters.items()},
        }
        record.update(self.meta)
        if extra:
            record.update(extra)
        self.emit(record)
        self.dirty = False
        return record


# --------------------------------------------------- abnormal-exit flush
#
# A run killed by an exception or a termination signal should still leave
# a complete results.jsonl record. Solvers register themselves here; the
# atexit hook (and, for SIGTERM/SIGINT — SIGTERM's default action skips
# atexit entirely, and a Ctrl-C KeyboardInterrupt swallowed by broad
# except clauses can exit without ever re-raising — chaining signal
# hooks) flushes any registered solver whose metrics have unflushed
# activity and a configured sink. Each signal is only hooked while its
# DEFAULT disposition is in place (SIG_DFL for SIGTERM, the
# KeyboardInterrupt-raising default_int_handler for SIGINT), so a user-
# or ResilientLoop- or service-installed handler is never stomped; after
# flushing, the previous disposition is restored and the signal
# re-delivered, preserving the original exit semantics.

_exit_solvers = []          # weakrefs to registered solvers
_signal_previous = {}       # {signum: previous handler} once installed
_exit_lock = named_lock("tools/metrics.py:_exit_lock")


def flush_pending(source="atexit"):
    """Flush every registered solver with unflushed activity and a JSONL
    sink. Best-effort: one failing flush never blocks the others."""
    for ref in list(_exit_solvers):
        solver = ref()
        if solver is None:
            continue
        m = getattr(solver, "metrics", None)
        if m is None or not (m.enabled and m.sink and m.dirty):
            continue
        try:
            solver.flush_metrics(extra={"flush_source": source})
        except Exception:
            pass


def _signal_flush(signum, frame):
    """Chaining SIGTERM/SIGINT hook: restore the previous disposition,
    flush, and re-deliver so the process still terminates with the
    original signal semantics (exit code / KeyboardInterrupt, parent
    observation). The restore comes FIRST on purpose: the flush blocks
    on in-flight device work (flush_metrics syncs the state, and an XLA
    compile can hold it for tens of seconds), so a SECOND Ctrl-C during
    the flush must get default semantics — an immediate
    KeyboardInterrupt escape that abandons the telemetry — instead of
    re-entering this handler and blocking again."""
    previous = _signal_previous.get(signum, signal.SIG_DFL)
    try:
        signal.signal(signum, previous)
        restored = True
    except (ValueError, OSError):
        restored = False
    flush_pending(source=f"signal:{signum}")
    if restored:
        os.kill(os.getpid(), signum)


# per-signal "still the default?" test: SIGINT's default disposition in
# CPython is the KeyboardInterrupt-raising default_int_handler, not
# SIG_DFL, so an == SIG_DFL check would never hook Ctrl-C
_HOOKABLE_DEFAULTS = {
    signal.SIGTERM: (signal.SIG_DFL,),
    signal.SIGINT: (signal.SIG_DFL, signal.default_int_handler),
}


def register_exit_flush(solver):
    """Register a solver for the abnormal-exit telemetry flush (atexit +
    SIGTERM + SIGINT). Idempotent per solver; each signal hook is
    installed once, and only where that signal's default disposition is
    still in place (a user- or ResilientLoop- or service-installed
    handler is never stomped)."""
    with _exit_lock:
        if not any(ref() is solver for ref in _exit_solvers):
            _exit_solvers.append(weakref.ref(solver))
        _exit_solvers[:] = [ref for ref in _exit_solvers
                            if ref() is not None]
        for signum, defaults in _HOOKABLE_DEFAULTS.items():
            if signum in _signal_previous:
                continue
            try:
                current = signal.getsignal(signum)
                if current in defaults:
                    _signal_previous[signum] = current
                    signal.signal(signum, _signal_flush)
            except (ValueError, OSError):
                pass   # non-main thread / unsupported platform


atexit.register(flush_pending)


def resolve(spec=None, sink=None, cadence=None, meta=None):
    """
    Resolve a solver's `metrics` argument against the [profiling] config:
    a Metrics instance passes through (meta keys are merged in); True/None
    build from config (None respects METRICS_DEFAULT, True forces on);
    False disables.
    """
    if isinstance(spec, Metrics):
        for key, val in (meta or {}).items():
            spec.meta.setdefault(key, val)
        return spec
    section = config["profiling"]
    if spec is None:
        enabled = section.getboolean("METRICS_DEFAULT", fallback=True)
    else:
        enabled = bool(spec)
    if cadence is None:
        cadence = int(section.get("SAMPLE_CADENCE", "200") or 200)
    if sink is None:
        sink = section.get("METRICS_FILE", "").strip() or None
    return Metrics(sample_cadence=cadence, sink=sink, enabled=enabled,
                   meta=meta)


def format_build_phases(bp, format=".4g", indent=""):
    """A `BuildPhases.record()` as text lines (used by `log_stats` and the
    `report` CLI): the phases with what `init_sec` leaves unnamed, then
    the set-up ledger's totals with the three largest program rows.
    Records from before a key existed print what they have."""
    if not bp:
        return []
    named = [f"{name} {bp[f'{name}_sec']:{format}}" for name in BUILD_PHASES
             if bp.get(f"{name}_sec")]
    line = f"{indent}Build phases: " + (", ".join(named) or "none") + " s"
    if "init_sec" in bp:
        line += (f"; init {bp['init_sec']:{format}} s, unnamed "
                 f"{bp.get('unnamed_sec', 0.0):{format}} s")
    line += (f"; compile {bp.get('compile_sec', 0.0):{format}} s "
             f"(assembly cache: {bp.get('assembly_cache', '?')})")
    lines = [line]
    prog = bp.get("programs")
    if prog:
        top = ", ".join(
            f"{r['label']} {r['first_call_sec']:{format}} s ({r['cache']})"
            for r in prog.get("rows", [])[:3])
        lines.append(
            f"{indent}Programs: {prog.get('programs', 0)} first calls "
            f"{prog.get('first_call_sec', 0.0):{format}} s = trace "
            f"{prog.get('trace_sec', 0.0):{format}} + lower "
            f"{prog.get('lower_sec', 0.0):{format}} + backend "
            f"{prog.get('backend_sec', 0.0):{format}} (cache load "
            f"{prog.get('retrieval_sec', 0.0):{format}}; "
            f"{prog.get('cache_hits', 0)} hits, "
            f"{prog.get('cache_misses', 0)} misses) + discovery "
            f"{prog.get('discover_sec', 0.0):{format}}; eager "
            f"{prog.get('eager', {}).get('count', 0)} programs "
            f"{prog.get('eager', {}).get('sec', 0.0):{format}} s"
            + (f"; largest: {top}" if top else ""))
    return lines


def format_phase_table(record, indent="  "):
    """Render a flushed record's phase breakdown as aligned text lines
    (used by `log_stats` and the `report` CLI)."""
    if not record:
        return []
    wall = record.get("loop_wall_sec") or 0.0
    iters = record.get("iterations") or 0
    total = record.get("phase_total_sec") or {}
    mean = record.get("phase_mean_sec") or {}
    pct = record.get("phase_pct_sec") or {}
    lines = [f"Per-phase wall time ({record.get('phase_samples', 0)} samples,"
             f" cadence {record.get('sample_cadence', '?')}):"]
    for phase in SUM_PHASES:
        t = total.get(phase, 0.0)
        frac = 100.0 * t / wall if wall > 0 else 0.0
        line = (f"{indent}{phase:<10} {mean.get(phase, 0.0):#.4g} s/step"
                f"  {t:#.4g} s total  {frac:5.1f}%")
        p = pct.get(phase)
        if p:
            # tail columns from the log-bucketed sample histogram —
            # absent on records flushed before the percentile tier
            line += (f"  p50/p95/p99 {p.get('p50', 0.0):#.3g}"
                     f"/{p.get('p95', 0.0):#.3g}"
                     f"/{p.get('p99', 0.0):#.3g} s")
        lines.append(line)
    psum = sum(total.get(p, 0.0) for p in SUM_PHASES)
    frac = 100.0 * psum / wall if wall > 0 else 0.0
    lines.append(f"{indent}{'sum':<10} {psum:#.4g} s of {wall:#.4g} s loop"
                 f" wall ({frac:.1f}%), {iters} iterations")
    if total.get("fused"):
        # whole-step fused-program re-measurement (overlaps the rows
        # above; core/fusedstep.py) — below the sum when fusion wins
        lines.append(
            f"{indent}{'fused':<10} {mean.get('fused', 0.0):#.4g} s/step"
            f"  (whole fused step program; overlaps the split rows, "
            f"excluded from sum)")
    if total.get("transpose_exposed") or total.get("transpose_overlapped"):
        # overlapped-chunked-walk split of the transpose wall
        # (parallel/transposes.py): exposed = still waited on,
        # overlapped = hidden under the interleaved chunk transforms
        exp = total.get("transpose_exposed", 0.0)
        ovl = total.get("transpose_overlapped", 0.0)
        tot = exp + ovl
        pct = 100.0 * ovl / tot if tot > 0 else 0.0
        lines.append(
            f"{indent}{'transpose':<10} exposed {exp:#.4g} s / overlapped "
            f"{ovl:#.4g} s ({pct:.0f}% hidden; overlaps the transpose "
            f"row, excluded from sum)")
    mem = record.get("device_mem_peak_bytes")
    if mem:
        lines.append(f"{indent}device memory peak: {mem / 1e9:.3f} GB"
                     f" ({record.get('mem_source')})")
    return lines

"""
Retrace sentinel: runtime counterpart of the DTL003 lint rule.

A compiled step loop should trace each program once during warmup and
never again; a post-warmup retrace means something in the hot path is
producing fresh signatures (shape/dtype drift, unstable static args,
rebuilt wrappers) and the loop is silently paying compile time per step.
The static analyzer cannot see that — it is a runtime property — so the
traced functions carry a trace-time side effect: their Python bodies only
execute while JAX is tracing, so a counter bump there counts compiles,
not calls.

Wiring: `tools.jitlift.lifted_jit` notes every trace of every instance
(covering the solver step/factor/eval programs), and `noted()` wraps raw
`jax.jit` users (the health probe). The solver arms the sentinel at
warmup end; an armed retrace logs a structured warning, records an
event, and bumps a `dedalus/retrace` counter on every subscribed Metrics
instance — so it lands in the JSONL telemetry next to steps/sec and is
assertable in tests (`sentinel.post_arm_retraces == 0`).

Counting granularity is the WRAPPER INSTANCE, deliberately: the first
trace of a fresh wrapper (e.g. the step_many scan block compiled after
warmup) is a compile, not a retrace — but within one wrapper, every
post-warmup trace counts, including "new signature" traces. Under jax a
recompile is ALWAYS a new signature (identical signatures hit the cache),
so counting per cache key instead would make per-step shape/static-arg
drift — the exact hazard — invisible as an endless stream of "first
compiles". Corollary: a driver that varies step_many block sizes
post-warmup is flagged, correctly — each new block length pays a full
trace+compile; fix the driver to use fixed block sizes.

The set-up ledger (always on; nothing per step). The same per-wrapper
records carry the TIME of every program's first call. `lifted_jit` (and
`noted_jit`, for raw `jax.jit` users: the health probe) brackets the first
call of each signature with `sentinel.open_row(...)` / `row.close()` and
books ONE row:

    label           the wrapper's `TraceCount.label` (the function's
                    qualname; every lifted program lowers as `wrapped`)
    t0              `time.time()` when the bracket opened
    first_call_sec  host wall of the bracket (discovery pass + first
                    launch; the launch is asynchronous, so no device time),
                    less the build phases that ran inside it
                    (`metrics.BuildPhases` scopes pause the row: the upload
                    of the program's lifted constants, a basis stack built
                    the first time a trace asks for it)
    discover_sec    the abstract pass that finds the lifted constants
    trace_sec       JAX's tracing of the body       }  exclusive: a nested
    lower_sec       jaxpr -> MLIR module            }  jit traced inside
    backend_sec     XLA compile OR cache retrieval  }  counts once, so
                    trace + lower + backend <= first_call_sec
    retrieval_sec   of backend_sec: reading the executable from the
                    persistent cache
    cache           hit | miss (compiled, and written to the cache) | off
                    (not consulted, or compiled under the cache's minimum
                    compile time and not kept)
    owner           `<SolverClass>#<n>`: the thread's current
                    `metrics.BuildPhases` when the row opened, or None

The four durations and hit/miss come from `jax.monitoring` listeners,
registered once at import, which add to the row open on the calling
thread. Events with no row open (eager `jnp` operations dispatched one by
one from the host) go to ONE aggregate keyed by `fun_name`: count and
seconds per name, at most `EAGER_NAMES` names and an `other`. Rows are
bounded (`ROW_CAP`, then `rows_dropped` counts); `reset()` clears both.
Where `tracing.live()` the bracket is also a `compile/<label>` span (and a
`dedalus/compile/<label>` row on the profiler's host plane).
"""

import collections
import contextlib
import logging
import threading
import time
import weakref

import jax
from jax import monitoring

from . import tracing

logger = logging.getLogger(__name__)

__all__ = ["TraceCount", "RetraceSentinel", "ProgramRow", "sentinel",
           "noted", "noted_jit"]

# bounded accounting: a per-step retrace storm (the exact pathology the
# sentinel exists to catch) must not itself leak memory or flood the log
EVENT_RING_SIZE = 256
WARNINGS_PER_LABEL = 5
# the set-up ledger's bounds: closed rows kept, names of the eager aggregate
ROW_CAP = 512
EAGER_NAMES = 256
# how many finished intervals an eager thread remembers, to find the
# children of the next one (a nested jit's event arrives just before its
# parent's)
EAGER_TAIL = 32

# jax.monitoring's vocabulary (jax/_src/dispatch.py, compiler.py,
# compilation_cache.py): the three durations arrive as time spans on
# time.time()'s clock with `fun_name`, the rest as bare events
_SPAN_KINDS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_sec",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_sec",
    "/jax/core/compile/backend_compile_duration": "backend_sec",
}
_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"

ROW_SECONDS = ("first_call_sec", "discover_sec", "trace_sec", "lower_sec",
               "backend_sec", "retrieval_sec")

_local = threading.local()   # .row: the row open on this thread


def _self_seconds(tail, start, end):
    """Exclusive seconds of the interval [start, end]. `tail` holds the
    disjoint intervals already booked on this thread, in order of
    completion: those the new one contains are its children (a nested jit
    traced inside it, an eager compile inside a trace) and keep their own
    time; it takes their place in `tail`."""
    inner = 0.0
    while tail and tail[-1][0] >= start:
        s, e = tail.pop()
        inner += e - s
    tail.append((start, end))
    return max(end - start - inner, 0.0)


class TraceCount:
    """Per-wrapper trace counter (one per lifted_jit / noted() wrapper).
    `calling` / `row`: a `noted_jit` program's call in flight and the
    ledger row its trace opened."""

    __slots__ = ("label", "count", "calling", "row")

    def __init__(self, label):
        self.label = str(label)
        self.count = 0
        self.calling = False
        self.row = None


class RetraceSentinel:
    """Process-wide trace accounting. Counts are per wrapper instance (a
    fresh solver's first traces never look like retraces), the armed flag
    is global (once any solver is past warmup, a retrace anywhere in the
    process is a hygiene event)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._metrics = weakref.WeakSet()
        self._warned = {}   # label -> warnings emitted (rate limit)
        self.armed = False
        self.total_traces = 0
        self.retraces = 0
        self.post_arm_retraces = 0
        self.events = collections.deque(maxlen=EVENT_RING_SIZE)
        self._clear_ledger()

    def _clear_ledger(self):
        self.rows = []            # closed program rows (dicts), oldest first
        self.rows_dropped = 0
        self.eager = {}           # fun_name -> [programs, seconds]
        self.listener_calls = 0   # monitoring events that reached a listener

    def subscribe(self, metrics):
        """Register a Metrics instance to receive `dedalus/retrace`
        counter bumps on armed retraces (held weakly)."""
        # under the lock: note() snapshots the set while holding it, and a
        # solver can be constructed while another thread is mid-trace
        with self._lock:
            self._metrics.add(metrics)

    def arm(self):
        """Mark warmup complete: from now on retraces warn and count."""
        self.armed = True

    def reset(self):
        """Test hook: disarm and zero the global accounting. Per-wrapper
        counts live on the wrappers and are NOT cleared — an old wrapper
        retracing after a reset is still a retrace."""
        with self._lock:
            self.armed = False
            self.total_traces = 0
            self.retraces = 0
            self.post_arm_retraces = 0
            self.events = collections.deque(maxlen=EVENT_RING_SIZE)
            self._warned = {}
            self._clear_ledger()

    # ---------------------------------------------------- the set-up ledger

    def open_row(self, state, owner=None):
        """Open the row of one program's first call on this thread
        (`state`: the wrapper's TraceCount; `owner`: the thread's current
        `metrics.BuildPhases` or None). A first call inside another one's
        bracket (a lifted program called while a lifted program is traced)
        is that one's time: it gets the shared no-op row."""
        if getattr(_local, "row", None) is not None:
            return _NO_ROW
        row = _local.row = ProgramRow(state.label, owner)
        return row

    def _book(self, row):
        with self._lock:
            if len(self.rows) < ROW_CAP:
                self.rows.append(row)
            else:
                self.rows_dropped += 1

    def _eager_span(self, name, kind, start, end):
        """A trace, lowering or compile with no row open on the calling
        thread: an operation dispatched by itself from the host."""
        tail = getattr(_local, "eager_tail", None)
        if tail is None:
            tail = _local.eager_tail = []
        sec = _self_seconds(tail, start, end)
        del tail[:-EAGER_TAIL]
        name = str(name or "?")
        # a trace says `<fun>`, its module `jit_<fun>`, a bare
        # primitive's `jit(<primitive>)`
        if name.startswith("jit_"):
            name = name[4:]
        elif name.startswith("jit(") and name.endswith(")"):
            name = name[4:-1]
        with self._lock:
            entry = self.eager.get(name)
            if entry is None:
                if len(self.eager) >= EAGER_NAMES:
                    name = "other"
                entry = self.eager.setdefault(name, [0, 0.0])
            entry[0] += kind == "backend_sec"
            entry[1] += sec

    def program_rows(self):
        """The closed rows, oldest first (copies)."""
        with self._lock:
            return [dict(r) for r in self.rows]

    def eager_programs(self):
        """{fun_name: {count, sec}} of the eager aggregate."""
        with self._lock:
            return {k: {"count": int(n), "sec": round(sec, 6)}
                    for k, (n, sec) in self.eager.items()}

    def program_totals(self, rows=None):
        """The ledger's sums: over `rows` (default: every row of the
        process), with the eager aggregate's."""
        rows = self.program_rows() if rows is None else rows
        out = {"programs": len(rows)}
        for key in ROW_SECONDS:
            out[key] = round(sum(r[key] for r in rows), 6)
        out["cache_hits"] = sum(r["cache"] == "hit" for r in rows)
        out["cache_misses"] = sum(r["cache"] == "miss" for r in rows)
        out["eager"] = self.eager_totals()
        out["rows_dropped"] = self.rows_dropped
        return out

    def eager_totals(self):
        """{count, sec} over the whole eager aggregate."""
        with self._lock:
            return {"count": int(sum(n for n, _ in self.eager.values())),
                    "sec": round(sum(s for _, s in self.eager.values()), 6)}

    def note(self, state):
        """Record one trace of the wrapper owning `state`. Called from
        inside traced bodies: runs at trace time only."""
        with self._lock:
            state.count += 1
            self.total_traces += 1
            if state.count <= 1:
                return
            self.retraces += 1
            if not self.armed:
                return
            self.post_arm_retraces += 1
            event = {"kind": "retrace", "label": state.label,
                     "trace_number": state.count,
                     "post_arm_index": self.post_arm_retraces}
            self.events.append(event)
            warned = self._warned.get(state.label, 0)
            self._warned[state.label] = warned + 1
            metrics_instances = list(self._metrics)
        # outside the lock: logging/metrics must not deadlock a nested note
        if warned < WARNINGS_PER_LABEL:
            tail = ("; further retraces of this program will be counted "
                    "but not logged" if warned == WARNINGS_PER_LABEL - 1
                    else "")
            logger.warning(
                f"post-warmup retrace of '{state.label}' (trace "
                f"#{state.count}): a hot-path program recompiled after "
                "warmup — check for changing shapes/dtypes or unstable "
                f"static arguments (DTL003 territory){tail}")
        for m in metrics_instances:
            try:
                m.inc("dedalus/retrace")
            except Exception:
                pass


class ProgramRow:
    """One program's first call, open on the calling thread from
    `sentinel.open_row` to `close()`; the module docstring has the fields.
    `discovered()` ends the discovery pass, `paused()` brackets work that
    is not the program's (`pause_row`: a build phase inside the bracket):
    neither its wall nor its events are the row's."""

    __slots__ = ("label", "owner", "t0", "discover_sec", "trace_sec",
                 "lower_sec", "backend_sec", "retrieval_sec", "hits",
                 "misses", "discovering", "_paused_sec", "_tail", "_span")

    def __init__(self, label, owner):
        self.label = label
        self.owner = owner
        self.discover_sec = self.trace_sec = self.lower_sec = 0.0
        self.backend_sec = self.retrieval_sec = self._paused_sec = 0.0
        self.hits = self.misses = 0
        self.discovering = True
        self._tail = []
        self._span = tracing.span(f"compile/{label}")
        self._span.__enter__()
        self.t0 = time.time()

    def discovered(self):
        self.discover_sec = max(
            time.time() - self.t0 - self._paused_sec, 0.0)
        self.discovering = False

    @contextlib.contextmanager
    def paused(self):
        start = time.time()
        _local.row = None
        try:
            yield
        finally:
            _local.row = self
            end = time.time()
            self._paused_sec += end - start
            if not self.discovering:
                # a child of the trace JAX is timing around it
                _self_seconds(self._tail, start, end)

    def close(self):
        _local.row = None
        first = max(time.time() - self.t0 - self._paused_sec, 0.0)
        cache = "miss" if self.misses else "hit" if self.hits else "off"
        owner = self.owner
        row = {"label": self.label, "t0": round(self.t0, 6),
               "first_call_sec": round(first, 6),
               "discover_sec": round(self.discover_sec, 6),
               "trace_sec": round(self.trace_sec, 6),
               "lower_sec": round(self.lower_sec, 6),
               "backend_sec": round(self.backend_sec, 6),
               "retrieval_sec": round(self.retrieval_sec, 6),
               "cache": cache,
               "owner": None if owner is None else owner.name}
        self._span.set(cache=cache, trace_sec=row["trace_sec"],
                       lower_sec=row["lower_sec"],
                       backend_sec=row["backend_sec"])
        self._span.__exit__(None, None, None)
        sentinel._book(row)
        if owner is not None:
            owner.book_program(row)


class _NoRow:
    """The shared row of a first call nested in another's bracket."""

    __slots__ = ()

    def discovered(self):
        pass

    def close(self):
        pass


_NO_ROW = _NoRow()
_NOT_PAUSED = contextlib.nullcontext()
sentinel = RetraceSentinel()


def pause_row():
    """Context manager: the row open on this thread, if any, stands still
    inside it. A build phase that runs at trace time (a basis building its
    stack the first time a program needs it, the upload of a lifted
    constant) is that phase's time, not the program's tracing."""
    row = getattr(_local, "row", None)
    return _NOT_PAUSED if row is None else row.paused()


def _on_time_span(event, start_time, end_time, **kwargs):
    kind = _SPAN_KINDS.get(event)
    if kind is None:
        return
    sentinel.listener_calls += 1
    row = getattr(_local, "row", None)
    if row is None:
        sentinel._eager_span(kwargs.get("fun_name"), kind, start_time,
                             end_time)
    elif not row.discovering:    # discover_sec has the discovery pass whole
        # (a `noted_jit` row opens inside the trace JAX is timing)
        sec = _self_seconds(row._tail, max(start_time, row.t0), end_time)
        setattr(row, kind, getattr(row, kind) + sec)


def _on_duration(event, duration_secs, **kwargs):
    if event != _RETRIEVAL:
        return
    sentinel.listener_calls += 1
    row = getattr(_local, "row", None)
    if row is not None and not row.discovering:
        row.retrieval_sec += duration_secs


def _on_event(event, **kwargs):
    if event != _HIT and event != _MISS:
        return
    sentinel.listener_calls += 1
    row = getattr(_local, "row", None)
    if row is not None and not row.discovering:
        if event == _HIT:
            row.hits += 1
        else:
            row.misses += 1


# once, at import: the listeners are called only when JAX traces, lowers
# or compiles something, never on a warmed program's call
monitoring.register_event_time_span_listener(_on_time_span)
monitoring.register_event_duration_secs_listener(_on_duration)
monitoring.register_event_listener(_on_event)


def noted(fn, label=None):
    """Wrap a function destined for `jax.jit` (or another tracer) with the
    trace-time sentinel side effect. The wrapper must only be called under
    tracing (e.g. `jax.jit(noted(probe, "health/probe"))`); calling it
    eagerly would count executions as traces. `noted_jit` is the jitted
    form whose first call is also a ledger row."""
    state = TraceCount(label or getattr(fn, "__qualname__", "traced_fn"))

    def wrapper(*args, **kwargs):
        sentinel.note(state)
        if state.calling and state.row is None:
            # a `noted_jit` call that traces: its row opens here, where
            # only a trace comes, and closes when the call returns
            from . import metrics
            row = sentinel.open_row(state, metrics.current_phases())
            if row is not _NO_ROW:
                row.discovered()    # no discovery pass
                state.row = row
        return fn(*args, **kwargs)

    wrapper.__name__ = getattr(fn, "__name__", "noted")
    wrapper._retrace_state = state
    return wrapper


class _NotedJit:
    """What `noted_jit` returns: the jitted program, its first call of a
    signature bracketed as a ledger row. The row opens where the trace
    starts (inside `noted`'s wrapper, which only a trace runs) and closes
    here when the call returns, so a warmed call pays one comparison."""

    __slots__ = ("jitted", "_state", "__name__")

    def __init__(self, jfn, state, name):
        self.jitted = jfn
        self._state = state
        self.__name__ = name

    def __call__(self, *args, **kwargs):
        state = self._state
        state.calling = True
        try:
            return self.jitted(*args, **kwargs)
        finally:
            state.calling = False
            row = state.row
            if row is not None:
                state.row = None
                row.close()


def noted_jit(fn, label=None, **jit_kwargs):
    """`jax.jit(noted(fn, label), **jit_kwargs)` whose first call of each
    signature books a program row like a `lifted_jit` program's (no
    discovery pass: `discover_sec` is 0). What the lowering sees is what
    `jax.jit(noted(fn, label))` gives it."""
    inner = noted(fn, label)
    # one wrapper per call site, memoised there (health.py, resilience.py)
    return _NotedJit(jax.jit(inner, **jit_kwargs),  # dedalus-lint: disable=DTL003
                     inner._retrace_state, inner.__name__)

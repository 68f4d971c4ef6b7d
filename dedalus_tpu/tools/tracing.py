"""
End-to-end request tracing: bounded ring-buffered spans, log-bucketed
latency histograms, and Chrome trace-event export.

One TRACE per served request (or per run, when enabled outside the
daemon): a tree of `Span` records — (trace_id, span_id, parent_id, name,
wall interval, attrs) — linking the full lifecycle the repo's serving
tier composes per request:

    request                         root (server/_receive)
      accept                        header+payload read off the socket
      admission                     queue-slot + breaker verdict
      queue                         accept -> worker dispatch wait
      pool_acquire                  warm-pool verdict (hit | warm-cache
        build/<name>                  | cold); cold builds carry the
          compile/<label>             BuildPhases child spans (<name>:
                                      layout, assembly_cache,
                                      host_assembly, pattern, structure,
                                      factor, basis_stacks, plans, upload,
                                      dd_prepare; flat and exclusive,
                                      tools/metrics.py), and every
                                      program's first call is a
                                      compile/<label> span wherever it
                                      falls: under a build/<name>, step,
                                      step_many, step/factor or
                                      handler/eval (attrs cache,
                                      trace_sec, lower_sec, backend_sec;
                                      the set-up ledger, tools/retrace.py)
      batch/seat  batch/join        continuous-batching membership
      batch/block                   one fixed-size block of fused steps
      batch/boundary                the per-block probe sync
      run                           the solo ResilientLoop execution
      phase/<name>                  sampled step-phase re-measurements
      checkpoint/write              durable checkpoint stall intervals
      checkpoint/submit             async sharded submit + overrun wait
      result_send                   record + result frames on the wire
      error                         terminal error frame (code attr)

      step | step_many              one solver iteration (or block), host
        compile/<label>               a program's first call (the run's
                                      first step, a new block length)
        step/factor                   side: LHS refactorization launch
          build/factor                  the run's first one, waited for
            compile/<label>               and its program's first call
        step/handlers                 scheduled handlers that fell due
          handler/eval                  task program launch (mode attr)
            state/scatter                 one launch: X into the fields
          handler/pull                  results to the host (bytes attr)
          handler/write                 HDF5 write
        metrics/drain                 the sampler's wait for queued steps
        metrics/sample  health/check  the cadence-gated probes
      cfl                           CFL.compute_timestep when due

Spans are recorded HOST-SIDE ONLY — never inside jit-traced code — so
tracing changes no compiled program: with tracing disabled the step HLO
is bit-identical (machine-checked by the progcheck `traced_step` census
program + DTP107), and with tracing enabled the cost is a few host
timestamps per span. A span is LIVE (`live()`) when the [tracing] switch
is on OR a `jax.profiler` trace is being captured: whoever starts a
profiler gets the step-loop spans in the ring and, as `dedalus/<name>`
rows, on the host plane of the same xplane, with no switch of this
package to set: a profiler started before a build shows which gap of the
device is which phase (`dedalus/build/<name>`) and which program's
tracing, lowering and compile or cache load (`dedalus/compile/<label>`). The `span()` fast path when nothing looks is a shared
no-op context manager: zero allocation, zero branches inside traced
code, nothing registered anywhere.

Cross-thread propagation: the server's reader thread opens the trace,
the worker thread resumes it (`resume(ctx)` pushes the context onto the
resuming thread's stack), and the batcher stamps per-member child spans
against each member's context explicitly — so one request's spans share
one trace_id across threads. When a span opens while tracing is enabled
it also enters a `jax.profiler.TraceAnnotation("dedalus/<name>",
trace_id=...)`, so XLA profiler rows align with serving spans and carry
the request's trace id.

Export: `chrome_trace(spans)` produces Chrome trace-event JSON ("X"
complete events, microsecond ts/dur) loadable in Perfetto or
`chrome://tracing`; `flush_trace(trace_id)` pops one finished trace
from the ring and appends a single `{"kind": "trace", ...}` record to
the configured JSONL sink (the same stream the metrics records ride),
which `python -m dedalus_tpu trace` dumps, converts, or summarizes.

Config ([tracing]): TRACE_DEFAULT (off), RING_SPANS (ring capacity),
TRACE_FILE (default JSONL sink when enabled without an explicit one).
"""

import json
import math
import os
import threading
import time
import uuid

import jax

from .config import config
from .lint.threadcheck import named_lock

__all__ = ["Span", "LogHistogram", "TraceRecorder", "TraceContext",
           "enabled", "live", "enable", "disable", "trace_sink", "recorder",
           "new_trace",
           "span", "resume", "add_span", "current_context",
           "chrome_trace_events", "chrome_trace", "trace_record",
           "flush_trace", "load_trace_records", "summarize_trace",
           "format_trace_tree"]


# --------------------------------------------------------------- histogram

# Bucket boundaries grow geometrically by 2**(1/4) per bucket (~19%/bucket,
# <10% worst-case midpoint error on percentile extraction), floored at 1 ns
# so degenerate zero/negative samples land in bucket 0.
_LOG_BASE = 2.0 ** 0.25
_LOG_FLOOR = 1e-9
_INV_LOG_BASE = 1.0 / math.log(_LOG_BASE)


class LogHistogram:
    """Log-bucketed latency histogram: O(1) `add`, tail percentiles by
    cumulative bucket walk with geometric-midpoint interpolation. The
    always-on accumulator behind the PhaseTimer's p50/p95/p99 columns —
    cheap enough (one log + one dict bump) to feed on every sampled
    phase measurement regardless of whether tracing is enabled."""

    __slots__ = ("counts", "total", "sum", "min", "max")

    def __init__(self):
        self.counts = {}
        self.total = 0
        self.sum = 0.0
        self.min = None
        self.max = None

    def _bucket(self, seconds):
        if seconds <= _LOG_FLOOR:
            return 0
        return 1 + int(math.log(seconds / _LOG_FLOOR) * _INV_LOG_BASE)

    def add(self, seconds):
        seconds = float(seconds)
        b = self._bucket(seconds)
        self.counts[b] = self.counts.get(b, 0) + 1
        self.total += 1
        self.sum += seconds
        self.min = seconds if self.min is None else min(self.min, seconds)
        self.max = seconds if self.max is None else max(self.max, seconds)

    def percentile(self, q):
        """q in [0, 100]. Geometric bucket midpoint, clamped to the
        observed min/max so small-sample percentiles never exceed the
        data range."""
        if not self.total:
            return 0.0
        rank = q / 100.0 * self.total
        seen = 0
        for b in sorted(self.counts):
            seen += self.counts[b]
            if seen >= rank:
                if b == 0:
                    value = _LOG_FLOOR
                else:
                    # geometric midpoint of [floor*base^(b-1), floor*base^b]
                    value = _LOG_FLOOR * _LOG_BASE ** (b - 0.5)
                return min(max(value, self.min), self.max)
        return self.max

    def summary(self):
        return {"count": self.total,
                "p50": self.percentile(50),
                "p95": self.percentile(95),
                "p99": self.percentile(99)}


# -------------------------------------------------------------------- spans

class Span:
    """One closed wall-clock interval in a trace tree. `t0` is an epoch
    timestamp (time.time domain) so spans from different processes and
    threads order on a shared axis; `dur` is measured with perf_counter
    deltas where possible."""

    __slots__ = ("trace_id", "span_id", "parent_id", "name", "t0", "dur",
                 "attrs", "tid")

    def __init__(self, trace_id, span_id, parent_id, name, t0, dur,
                 attrs=None, tid=None):
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.t0 = t0
        self.dur = dur
        self.attrs = attrs or {}
        self.tid = tid if tid is not None else threading.get_ident()

    def to_dict(self):
        d = {"trace_id": self.trace_id, "span_id": self.span_id,
             "parent_id": self.parent_id, "name": self.name,
             "t0": round(self.t0, 6), "dur_sec": round(self.dur, 6),
             "tid": self.tid}
        if self.attrs:
            d["attrs"] = self.attrs
        return d


class TraceRecorder:
    """Process-wide bounded span ring. Thread-safe; spans beyond the ring
    capacity evict oldest-first, so a leaked trace can never grow host
    memory unboundedly. `pop_trace` removes and returns one finished
    trace's spans (flush-once semantics for the JSONL sink)."""

    def __init__(self, capacity=None):
        if capacity is None:
            capacity = int(config.get("tracing", "RING_SPANS",
                                      fallback="4096") or 4096)
        self.capacity = max(int(capacity), 16)
        self._spans = []
        self._lock = named_lock("tools/tracing.py:TraceRecorder._lock")
        self._next_id = 0

    def next_span_id(self):
        with self._lock:
            self._next_id += 1
            return self._next_id

    def record(self, s):
        with self._lock:
            self._spans.append(s)
            if len(self._spans) > self.capacity:
                del self._spans[:len(self._spans) - self.capacity]

    def spans(self, trace_id=None):
        with self._lock:
            if trace_id is None:
                return list(self._spans)
            return [s for s in self._spans if s.trace_id == trace_id]

    def pop_trace(self, trace_id):
        with self._lock:
            mine = [s for s in self._spans if s.trace_id == trace_id]
            if mine:
                self._spans = [s for s in self._spans
                               if s.trace_id != trace_id]
            return mine

    def clear(self):
        with self._lock:
            self._spans = []


_recorder = None
_recorder_lock = named_lock("tools/tracing.py:_recorder_lock")


def recorder():
    """The process-wide span recorder (lazily constructed)."""
    global _recorder
    if _recorder is None:
        with _recorder_lock:
            if _recorder is None:
                _recorder = TraceRecorder()
    return _recorder


# ----------------------------------------------------------- enable/disable

_enabled = config.getboolean("tracing", "TRACE_DEFAULT", fallback=False)
_sink = (config.get("tracing", "TRACE_FILE", fallback="").strip() or None)


_profiling = jax.profiler.TraceAnnotation.is_enabled


def enabled():
    """The [tracing] switch: request traces are opened, flushed and kept
    for the operator (the serving code tests this)."""
    return _enabled


def live():
    """Whether a span opened now is recorded: the [tracing] switch is on,
    or a `jax.profiler` trace is being captured (two flag reads)."""
    return _enabled or _profiling()


def enable(sink=None):
    """Turn tracing on process-wide. `sink` (a JSONL path) sets where
    `flush_trace` appends trace records; None keeps the configured
    [tracing] TRACE_FILE (or leaves traces in the ring only)."""
    global _enabled, _sink
    _enabled = True
    if sink is not None:
        _sink = str(sink)
    return recorder()


def disable():
    global _enabled
    _enabled = False


def trace_sink():
    """The configured trace-record JSONL path (None when unset)."""
    return _sink


# ----------------------------------------------------- thread-local context

_tls = threading.local()


def _stack():
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    return stack


def current_context():
    """(trace_id, span_id) of the innermost open span on THIS thread, or
    None when no trace is active here."""
    stack = _stack()
    return stack[-1] if stack else None


class TraceContext:
    """One trace's identity: a fresh trace_id plus a pre-allocated root
    span id, so child spans recorded before the root CLOSES (it closes
    last, when the request finishes) still parent correctly. Pass the
    context across threads and stamp children with `resume(ctx)` or
    `parent=ctx`; call `finish(**attrs)` exactly once to record the
    root span."""

    __slots__ = ("trace_id", "root_id", "name", "t0", "_t0_perf", "attrs",
                 "_done")

    def __init__(self, name, attrs=None):
        self.trace_id = uuid.uuid4().hex[:16]
        self.root_id = recorder().next_span_id()
        self.name = name
        self.t0 = time.time()
        self._t0_perf = time.perf_counter()
        self.attrs = dict(attrs or {})
        self._done = False

    def finish(self, **attrs):
        """Record the root span (idempotent). Returns it (or None when
        tracing got disabled mid-request)."""
        if self._done:
            return None
        self._done = True
        if not _enabled:
            return None
        self.attrs.update(attrs)
        s = Span(self.trace_id, self.root_id, None, self.name, self.t0,
                 time.perf_counter() - self._t0_perf, attrs=self.attrs)
        recorder().record(s)
        return s


def new_trace(name, attrs=None):
    """Open a new trace (returns a TraceContext, or None when tracing is
    off — callers thread the None through untouched; every consumer here
    tolerates it)."""
    if not _enabled:
        return None
    return TraceContext(name, attrs)


def _parent_ids(parent):
    """Resolve an explicit parent (TraceContext | Span | (trace, span)
    tuple | None) or fall back to the thread-local stack."""
    if parent is not None:
        if isinstance(parent, TraceContext):
            return parent.trace_id, parent.root_id
        if isinstance(parent, Span):
            return parent.trace_id, parent.span_id
        return parent  # (trace_id, span_id)
    return current_context() or (None, None)


class _NoopSpan:
    """Shared do-nothing context manager: the `span()` fast path when
    tracing is disabled and no profiler captures (no allocation per
    call)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        return self


_NOOP = _NoopSpan()


class _LiveSpan:
    __slots__ = ("name", "attrs", "_parent", "trace_id", "span_id",
                 "_t0", "_t0_perf", "_ann", "_pushed")

    def __init__(self, name, attrs, parent):
        self.name = name
        self.attrs = dict(attrs or {})
        self._parent = parent
        self.trace_id = None
        self.span_id = None
        self._ann = None
        self._pushed = False

    def set(self, **attrs):
        self.attrs.update(attrs)
        return self

    def __enter__(self):
        trace_id, parent_id = _parent_ids(self._parent)
        if trace_id is None:
            # no ambient trace: each orphan span becomes its own
            # single-span trace so nothing recorded is ever unlinked
            trace_id = uuid.uuid4().hex[:16]
            parent_id = None
        self.trace_id = trace_id
        self._parent = parent_id
        self.span_id = recorder().next_span_id()
        _stack().append((trace_id, self.span_id))
        self._pushed = True
        try:
            self._ann = jax.profiler.TraceAnnotation(
                f"dedalus/{self.name}", trace_id=trace_id)
            self._ann.__enter__()
        except Exception:
            self._ann = None
        self._t0 = time.time()
        self._t0_perf = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter() - self._t0_perf
        if self._ann is not None:
            try:
                self._ann.__exit__(*exc)
            except Exception:
                pass
        if self._pushed:
            stack = _stack()
            if stack and stack[-1] == (self.trace_id, self.span_id):
                stack.pop()
            elif stack:
                try:
                    stack.remove((self.trace_id, self.span_id))
                except ValueError:
                    pass
        if live():
            recorder().record(Span(self.trace_id, self.span_id,
                                   self._parent, self.name, self._t0, dur,
                                   attrs=self.attrs))
        return False


def span(name, attrs=None, parent=None):
    """Context manager recording one span around the `with` body. Parent
    resolution: explicit `parent` (TraceContext / Span / (trace, span)
    pair) > this thread's innermost open span > a fresh one-span trace.
    When nothing looks (`live()` false), returns a shared no-op (zero
    allocation per call)."""
    if not (_enabled or _profiling()):
        return _NOOP
    return _LiveSpan(name, attrs, parent)


class _Resume:
    __slots__ = ("_ids", "_pushed")

    def __init__(self, ids):
        self._ids = ids
        self._pushed = False

    def __enter__(self):
        if self._ids is not None:
            _stack().append(self._ids)
            self._pushed = True
        return self

    def __exit__(self, *exc):
        if self._pushed:
            stack = _stack()
            if stack and stack[-1] == self._ids:
                stack.pop()
            elif stack:
                try:
                    stack.remove(self._ids)
                except ValueError:
                    pass
        return False


def resume(ctx):
    """Adopt a trace context on THIS thread: spans opened inside the
    `with` body parent under `ctx` (a TraceContext, Span, or (trace_id,
    span_id) pair; None is a no-op, so the off path threads through)."""
    if ctx is None or not _enabled:
        return _Resume(None)
    return _Resume(_parent_ids(ctx))


def add_span(name, dur, parent=None, end=None, attrs=None):
    """Record one already-measured interval after the fact (the accept
    and queue waits are measured before their trace exists on the
    current thread). `end` is the interval's epoch end time (defaults
    to now); t0 is reconstructed as end - dur."""
    if not _enabled:
        return None
    trace_id, parent_id = _parent_ids(parent)
    if trace_id is None:
        trace_id, parent_id = uuid.uuid4().hex[:16], None
    end = time.time() if end is None else end
    s = Span(trace_id, recorder().next_span_id(), parent_id, name,
             end - float(dur), float(dur), attrs=dict(attrs or {}))
    recorder().record(s)
    return s


# ------------------------------------------------------------------- export

def chrome_trace_events(spans):
    """Chrome trace-event list: one "X" (complete) event per span,
    microsecond timestamps, span identity and attrs in `args`."""
    pid = os.getpid()
    events = []
    for s in spans:
        args = {"trace_id": s.trace_id, "span_id": s.span_id}
        if s.parent_id is not None:
            args["parent_id"] = s.parent_id
        args.update(s.attrs)
        events.append({"name": s.name, "ph": "X", "cat": "dedalus",
                       "ts": round(s.t0 * 1e6, 3),
                       "dur": round(s.dur * 1e6, 3),
                       "pid": pid, "tid": s.tid, "args": args})
    return events


def chrome_trace(spans):
    """Full Chrome trace-event JSON object (loads in Perfetto /
    chrome://tracing)."""
    return {"traceEvents": chrome_trace_events(spans),
            "displayTimeUnit": "ms"}


def chrome_trace_from_records(records):
    """Chrome trace-event JSON built back from flushed trace records
    (dict-shaped spans, `python -m dedalus_tpu trace --chrome`)."""
    pid = os.getpid()
    events = []
    for rec in records:
        for s in _span_dicts(rec):
            args = {"trace_id": s.get("trace_id"),
                    "span_id": s.get("span_id")}
            if s.get("parent_id") is not None:
                args["parent_id"] = s["parent_id"]
            args.update(s.get("attrs") or {})
            events.append({"name": s.get("name", "?"), "ph": "X",
                           "cat": "dedalus",
                           "ts": round(s.get("t0", 0.0) * 1e6, 3),
                           "dur": round(s.get("dur_sec", 0.0) * 1e6, 3),
                           "pid": pid, "tid": s.get("tid", 0),
                           "args": args})
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def trace_record(trace_id, spans, **extra):
    """One structured JSONL record holding a whole trace (the shape the
    metrics sink carries and `python -m dedalus_tpu trace` reads)."""
    record = {"kind": "trace", "trace_id": trace_id,
              "ts": round(time.time(), 1),
              "spans": [s.to_dict() for s in spans]}
    record.update(extra)
    return record


def flush_trace(trace_id, sink=None, **extra):
    """Pop one finished trace from the ring and append its record to the
    JSONL sink (explicit arg > [tracing] TRACE_FILE). Never raises —
    telemetry must never kill a request. Returns the record (or None
    when the trace has no spans)."""
    if trace_id is None:
        return None
    try:
        spans = recorder().pop_trace(trace_id)
        if not spans:
            return None
        record = trace_record(trace_id, spans, **extra)
        path = sink or _sink
        if path:
            parent = os.path.dirname(os.path.abspath(path))
            if parent:
                os.makedirs(parent, exist_ok=True)
            with open(path, "a") as f:
                f.write(json.dumps(record) + "\n")
        return record
    except Exception:
        return None


def load_trace_records(path):
    """All `kind == "trace"` records from a JSONL file (unparseable lines
    skipped, like `report`)."""
    records = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if isinstance(rec, dict) and rec.get("kind") == "trace":
                records.append(rec)
    return records


def _span_dicts(record):
    return sorted(record.get("spans", []), key=lambda s: s.get("t0", 0.0))


def summarize_trace(record):
    """One-line-per-trace summary dict: root name/duration, span count,
    and the per-name duration totals (sorted by wall)."""
    spans = _span_dicts(record)
    by_name = {}
    root = None
    for s in spans:
        by_name[s["name"]] = by_name.get(s["name"], 0.0) + s.get("dur_sec", 0.0)
        if s.get("parent_id") is None:
            root = s
    return {"trace_id": record.get("trace_id"),
            "spans": len(spans),
            "root": (root or {}).get("name"),
            "root_sec": (root or {}).get("dur_sec", 0.0),
            "root_attrs": (root or {}).get("attrs", {}),
            "by_name": dict(sorted(by_name.items(),
                                   key=lambda kv: -kv[1]))}


def format_trace_tree(record, indent="  "):
    """Render one trace record as an indented span tree (the `trace`
    CLI's default view). Orphans (parent evicted from the ring) print
    at top level."""
    spans = _span_dicts(record)
    ids = {s["span_id"] for s in spans}
    children = {}
    roots = []
    for s in spans:
        parent = s.get("parent_id")
        if parent is None or parent not in ids:
            roots.append(s)
        else:
            children.setdefault(parent, []).append(s)
    lines = [f"trace {record.get('trace_id')}  "
             f"({len(spans)} spans, ts {record.get('ts')})"]

    def walk(s, depth):
        attrs = s.get("attrs") or {}
        detail = ""
        if attrs:
            keys = sorted(attrs)[:4]
            detail = "  " + " ".join(f"{k}={attrs[k]}" for k in keys)
        lines.append(f"{indent * depth}{s['name']:<20} "
                     f"{s.get('dur_sec', 0.0) * 1e3:9.3f} ms{detail}")
        for child in children.get(s["span_id"], []):
            walk(child, depth + 1)

    for root in roots:
        walk(root, 1)
    return lines

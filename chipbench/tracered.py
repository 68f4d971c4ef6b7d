"""
From a profiler trace to numbers: the one reduction every PR is read by.

`jax.profiler` writes an .xplane.pb; chipbench/xplane.py reads it. A device
plane ("/device:TPU:n") has a line "XLA Ops" whose events are the HLO
operations as they ran; the `op_name` of each (the jax.named_scope path) is
the stat `tf_op` of the event's metadata. Events nest (a `while` holds the
operations of its body), so durations are never summed as they stand: every
line is first flattened into segments that do not overlap, each owned by
the innermost event that covers it. The sum of the segments IS the busy
union, and an operation's time is its self time.

Host spans (`chipbench/...` TraceAnnotations) are on the same clock; the
traced window is the `chipbench/window` span, and each idle gap of the
device inside it is given to the host span that was open at the time.
"""

import glob
import os

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
OP_NAME_STAT = "tf_op"
SPAN_PREFIX = "chipbench/"
WINDOW_SPAN = "chipbench/window"
SCOPE_ROOT = "dedalus"
SCOPE_DEPTH = 3   # dedalus/<layer>/<what>: every scope of the vocabulary


def newest_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    return files[-1] if files else None


def flatten(events):
    """[(start, end, label)] in any order, nested or overlapping ->
    [(start, end, label)] that do not overlap, each labelled by the
    innermost (latest-started) event covering it."""
    out = []
    stack = []      # (end, label) of the events open at `cursor`
    cursor = None

    def close_until(t):
        nonlocal cursor
        while stack and stack[-1][0] <= t:
            end, label = stack.pop()
            if end > cursor:
                out.append((cursor, end, label))
                cursor = end

    for start, end, label in sorted(events, key=lambda e: (e[0], -e[1])):
        if end <= start:
            continue
        if stack:
            close_until(start)
        if stack and start > cursor:
            out.append((cursor, start, stack[-1][1]))
        cursor = start if not stack else max(cursor, start)
        stack.append((end, label))
    if stack:
        close_until(float("inf"))
    return out


def clip(segments, lo, hi):
    return [(max(s, lo), min(e, hi), label) for s, e, label in segments
            if min(e, hi) > max(s, lo)]


def scope_of(op_name):
    """Innermost `dedalus/<layer>/<what>` scope of an op_name path, or
    None: 'jit(f)/dedalus/step/stage1/dedalus/matsolve/dense.solve/dot'
    -> 'dedalus/matsolve/dense.solve'."""
    parts = op_name.split("/")
    for i in range(len(parts) - SCOPE_DEPTH, -1, -1):
        if parts[i] == SCOPE_ROOT:
            return "/".join(parts[i:i + SCOPE_DEPTH])
    return None


class Trace:
    """device: {plane name: [(start_ns, end_ns, hlo name, op_name)]} from
    the XLA Ops lines; spans: [(start_ns, end_ns, name)] of the harness's
    host annotations."""

    def __init__(self, device, spans):
        self.device = device
        self.spans = spans

    @classmethod
    def from_file(cls, path):
        from . import xplane
        planes = xplane.read(path, lambda plane, line: (
            plane.startswith(DEVICE_PLANE) and line == OPS_LINE)
            or plane.startswith("/host:"))
        device, spans = {}, []
        for plane in planes:
            for line in plane["lines"]:
                if plane["name"].startswith(DEVICE_PLANE):
                    device.setdefault(plane["name"], []).extend(
                        (start * 1e-3, end * 1e-3, name,
                         stats.get(OP_NAME_STAT, "").rstrip(":"))
                        for start, end, name, stats in line["events"])
                else:
                    spans.extend(
                        (start * 1e-3, end * 1e-3, name)
                        for start, end, name, _ in line["events"]
                        if name.startswith(SPAN_PREFIX))
        return cls(device, spans)

    def window(self):
        wins = [(s, e) for s, e, name in self.spans if name == WINDOW_SPAN]
        if wins:
            return min(s for s, _ in wins), max(e for _, e in wins)
        every = [t for evs in self.device.values()
                 for s, e, _, _ in evs for t in (s, e)]
        return (min(every), max(every)) if every else (0.0, 0.0)


def reduce(trace):
    """The numbers the per-layer readers and `breakdown` take from one
    trace, or None where the trace has no device plane. Seconds
    throughout; busy and the per-name sums are averaged over the device
    planes used (one per chip)."""
    lo, hi = trace.window()
    names = sorted(trace.device)
    if not names:
        return None     # no device plane (a CPU rehearsal): nothing to say
    ops, scopes, unscoped = {}, {}, {}
    busy = summed = 0.0
    gaps = []
    for plane in names:
        segs = clip(flatten([(s, e, (hlo, op)) for s, e, hlo, op
                             in trace.device[plane]]), lo, hi)
        cursor = lo
        for s, e, (hlo, op) in segs:
            d = (e - s) * 1e-9
            busy += d
            scope = scope_of(op)
            key = scope or f"unscoped/{hlo}"
            ops[key] = ops.get(key, 0.0) + d
            if scope:
                scopes[scope] = scopes.get(scope, 0.0) + d
            else:
                unscoped[hlo] = unscoped.get(hlo, 0.0) + d
            if s > cursor:
                gaps.append((cursor, s))
            cursor = max(cursor, e)
        if hi > cursor:
            gaps.append((cursor, hi))
        summed += sum(min(e, hi) - max(s, lo) for s, e, _, _
                      in trace.device[plane]
                      if min(e, hi) > max(s, lo)) * 1e-9
    n = max(len(names), 1)
    host = flatten([(s, e, name) for s, e, name in trace.spans
                    if name != WINDOW_SPAN])
    idle = {}
    for g0, g1 in gaps:
        left = g1 - g0
        for s, e, name in clip(host, g0, g1):
            idle[name] = idle.get(name, 0.0) + (e - s) * 1e-9 / n
            left -= e - s
        if left > 0:
            idle["host/unannotated"] = \
                idle.get("host/unannotated", 0.0) + left * 1e-9 / n
    top = lambda d: sorted(([k, v] for k, v in d.items()),  # noqa: E731
                           key=lambda kv: -kv[1])
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy / n,
        # durations as they stand, nested ones counted twice: what the
        # flattening is there to avoid; kept so that the two can be compared
        "summed_durations_s": summed / n,
        "planes": names,
        "scopes": {k: v / n for k, v in scopes.items()},
        "unscoped_s": sum(unscoped.values()) / n,
        "device_ops": [[k, v / n] for k, v in top(ops)],
        "idle_gaps": top(idle),
        "span_counts": _counts(trace.spans),
    }


def _counts(spans):
    out = {}
    for _, _, name in spans:
        out[name] = out.get(name, 0) + 1
    return out


def scope_seconds(reduced, needle):
    """Seconds of the scopes whose name contains `needle`."""
    return sum(v for k, v in reduced["scopes"].items() if needle in k)

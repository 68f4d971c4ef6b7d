"""
The program's own step-loop spans, read after the traced window.

`dedalus_tpu.tools.tracing.span` is live while a profiler captures, so
after `stop_trace` the program's span ring holds the spans of the traced
window and nothing else: `step` / `step_many` (the host side of one
iteration or block) with `step/factor`, `step/handlers` (`handler/eval`,
`handler/pull`, `handler/write`), `metrics/drain`, `metrics/sample` and
`health/check` under them, and `cfl`. The readers under layers/ take sums over them.
The same spans are `dedalus/<name>` rows on the host plane of the xplane.

Every function returns None where there is nothing sound to read, and the
metric is then left out: a program without these spans (an older tree:
the ring is empty), `[tracing]` switched on (the ring may hold more than
the window), a full ring (its oldest spans are gone).
"""


def window_spans():
    """The ring's spans, or None where they are not the window's."""
    try:
        from dedalus_tpu.tools import tracing
    except ImportError:
        return None
    if tracing.enabled():
        return None
    ring = tracing.recorder()
    spans = ring.spans()
    if not spans or len(spans) >= ring.capacity:
        return None
    return spans


def total_seconds(spans, names):
    """Summed durations of the spans called one of `names`."""
    return sum(s.dur for s in spans if s.name in names)


def self_seconds(spans, names):
    """Summed self time of the spans called one of `names`: a span's
    duration less what its child spans (by parent_id) cover. Children of
    one parent run one after another on its thread, so what they cover
    is the sum of their durations."""
    covered = {}
    for s in spans:
        if s.parent_id is not None:
            covered[s.parent_id] = covered.get(s.parent_id, 0.0) + s.dur
    return sum(max(s.dur - covered.get(s.span_id, 0.0), 0.0)
               for s in spans if s.name in names)


def ms_per_step(ctx, names, self_time=False):
    """Milliseconds per iteration of the window in the spans called one
    of `names` (0.0 where the window opened none), or None."""
    spans, n = window_spans(), ctx.get("iterations")
    if spans is None or not n:
        return None
    seconds = self_seconds if self_time else total_seconds
    return 1e3 * seconds(spans, names) / n


def per_100_steps(ctx, name):
    """Spans called `name` per 100 iterations of the window, or None."""
    spans, n = window_spans(), ctx.get("iterations")
    if spans is None or not n:
        return None
    return 100.0 * sum(s.name == name for s in spans) / n


def attr_share_pct(name, key, value):
    """Percent of the spans called `name` whose attr `key` is `value`,
    or None where the window opened none."""
    spans = window_spans()
    mine = [s for s in spans or () if s.name == name]
    if not mine:
        return None
    return 100.0 * sum(s.attrs.get(key) == value for s in mine) / len(mine)

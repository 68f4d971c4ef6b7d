"""
The program's own set-up ledger, read after the window.

Since PR 37 the program names its set-up from inside, always on:
`dedalus_tpu.tools.retrace.sentinel` keeps one row for every program's
first call (`label`, `t0`, `first_call_sec`, `discover_sec`, `trace_sec`,
`lower_sec`, `backend_sec`, `retrieval_sec`, `cache` hit | miss | off,
`owner`) and ONE aggregate of what JAX traced and compiled for operations
dispatched one by one from the host, and every solver's
`metrics.BuildPhases` its phases (`<name>_sec`), the wall of its whole
`__init__` (`init_sec`) and what of that no phase and no program row
names (`unnamed_sec`). The readers under layers/ take sums over them;
`ctx["build_phases"]` is the deployment's solver's `record()`, taken
after the window.

Every function returns None where the tree has no ledger (an older tree:
the parent of PR 37), and the metric is then left out.
"""


def totals():
    """The ledger's sums over every row of the process with the eager
    aggregate's (`sentinel.program_totals()`: `first_call_sec`,
    `discover_sec`, `trace_sec`, `lower_sec`, `retrieval_sec`,
    `cache_misses`, `eager: {count, sec}`, ...), or None."""
    try:
        from dedalus_tpu.tools.retrace import sentinel
        return sentinel.program_totals()
    except (ImportError, AttributeError):
        return None


def total_seconds(*keys):
    """Sum of the totals `keys`, or None."""
    found = totals()
    return None if found is None else float(sum(found[k] for k in keys))


def phase_seconds(ctx, key):
    """`key` (`<name>_sec`, `unnamed_sec`) of the deployment's solver's
    record; None where the record has no such key."""
    phases = ctx.get("build_phases") or {}
    if key not in phases or "init_sec" not in phases:
        return None
    return float(phases[key])


def process_phase_seconds(name):
    """The build phase `name` summed over every solver the process built
    and the process-level phases (set-up code books to whichever solver is
    current on its thread, or to the process where none is: a basis builds
    a stack the first time a field is transformed, which may be before the
    deployment's solver exists), or None."""
    try:
        from dedalus_tpu.tools import metrics
        every = metrics.all_phases() + [metrics.process_phases()]
    except (ImportError, AttributeError):
        return None
    return float(sum(p.seconds.get(name, 0.0) for p in every))


def script_seconds(ctx):
    """`build_s` less the `init_sec` of every solver the process built:
    the configuration's script outside any solver's construction."""
    try:
        from dedalus_tpu.tools import metrics
        solvers = metrics.all_phases()
    except (ImportError, AttributeError):
        return None
    if ctx.get("build_s") is None:
        return None
    return max(float(ctx["build_s"])
               - sum(float(p.init_sec) for p in solvers), 0.0)

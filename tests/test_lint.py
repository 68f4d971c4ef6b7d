"""
Jit-hygiene analyzer (tools/lint) + runtime sentinels (tools/retrace,
jitlift trace probe, leak_check marker).

Self-enforcement lives here: test_package_lints_clean runs the analyzer
over the installed package against the checked-in baseline, so tier-1
fails on any new un-baselined violation. Every rule gets a good/bad
fixture pair plus suppression and baseline coverage, and the retrace
sentinel is asserted to stay at zero across the RB step loop.
"""

import json
import logging
import pathlib
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from dedalus_tpu.tools import retrace as retrace_mod
from dedalus_tpu.tools import metrics as metrics_mod
from dedalus_tpu.tools.lint import (all_rules, apply_baseline,
                                    check_baseline_fresh, lint_package,
                                    make_baseline, run_lint, DEFAULT_BASELINE,
                                    PACKAGE_DIR)
from dedalus_tpu.tools.lint.cli import main as lint_main

REPO = pathlib.Path(__file__).parent.parent


def _lint_src(tmp_path, relname, src):
    """Write a fixture module and lint it. relname controls path-scoped
    rules (e.g. 'core/timesteppers.py' opts into the hot-path scope)."""
    path = tmp_path / relname
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(src)
    return run_lint([path])


def _rules_fired(result):
    return sorted({f.rule for f in result.findings})


# ----------------------------------------------------------------- rule set

def test_rule_catalog():
    # the DTC thread-safety rules (tools/lint/threadcheck.py) register
    # in the shared rule set so the default run covers them
    rules = all_rules()
    assert [r.id for r in rules] == ["DTC001", "DTC002", "DTC003",
                                     "DTL001", "DTL002", "DTL003",
                                     "DTL004", "DTL005", "DTL006",
                                     "DTL007", "DTL008", "DTL009"]
    for r in rules:
        assert r.severity in ("error", "warning")
        assert r.title
        assert r.__doc__


def test_dtl001_fires_on_hot_path_syncs(tmp_path):
    result = _lint_src(tmp_path, "core/timesteppers.py", """
import jax
import jax.numpy as jnp

def step(solver, dt):
    err = float(jnp.max(solver.X))
    solver.X.block_until_ready()
    jax.block_until_ready(solver.X)
    return err + solver.X[0, 0].item()
""")
    assert _rules_fired(result) == ["DTL001"]
    assert len(result.findings) == 4


def test_dtl001_quiet_on_host_setup(tmp_path):
    result = _lint_src(tmp_path, "core/timesteppers.py", """
import numpy as np

def coefficients(dt_hist):
    a = np.asarray(dt_hist)   # host-side setup: fine
    return float(a[0])        # float of a host value: fine
""")
    assert result.findings == []


def test_dtl001_state_gather_in_resilience_module(tmp_path):
    """tools/resilience.py is hot-module scoped, and np.asarray of a
    device-state attribute there (the shipped Snapshot.is_finite full
    gather) flags — so the fix stays fixed."""
    bad = _lint_src(tmp_path, "tools/resilience.py", """
import numpy as np

def is_finite(snap):
    # the shipped hazard: full device->host gather per capture validation
    return bool(np.all(np.isfinite(np.asarray(snap.X))))

def fleet_finite(snap):
    return np.asarray(snap.F_hist)
""")
    assert _rules_fired(bad) == ["DTL001"]
    assert len(bad.findings) == 2
    assert "gathers the full state" in bad.findings[0].message


def test_dtl001_fires_in_transposes_module(tmp_path):
    """parallel/transposes.py is hot-module scoped: the overlapped
    chunked walk stages compile into every sharded step, so a stray
    host sync there stalls the whole transpose pipeline. Fixture-pinned
    so the scope can never silently regress."""
    bad = _lint_src(tmp_path, "parallel/transposes.py", """
import jax
import jax.numpy as jnp

def overlapped_stage(data, mesh):
    jax.block_until_ready(data)        # sync between chunk issues
    return float(jnp.max(data))        # host read of the moved block
""")
    assert _rules_fired(bad) == ["DTL001"]
    assert len(bad.findings) == 2


def test_dtl001_quiet_on_transposes_host_setup(tmp_path):
    """Host-side chunk bookkeeping (divisor clamping, spec lists) in the
    transposes module is not a device sync."""
    result = _lint_src(tmp_path, "parallel/transposes.py", """
import numpy as np

def stage_chunks(requested, block):
    c = max(1, min(int(requested), int(block)))   # host chunk math
    while block % c:
        c -= 1
    return c

def specs(layout, ndim):
    return [layout.get(d) for d in range(ndim)]
""")
    assert result.findings == []


def test_dtl001_state_gather_quiet_on_host_conversions(tmp_path):
    """The dtype= convention and non-state attributes stay quiet: host
    bookkeeping in the hot modules is not a device sync."""
    result = _lint_src(tmp_path, "tools/resilience.py", """
import numpy as np

def bookkeeping(snap, times):
    a = np.asarray(times)                       # bare Name: host data
    b = np.asarray(snap.sim_times, dtype=float) # dtype=: deliberate host
    c = np.array(snap.lineage)                  # not a state attribute
    return a, b, c
""")
    assert result.findings == []


def test_dtl001_state_gather_scoped_to_hot_modules(tmp_path):
    """The state-attribute heuristic is hot-module scoped: analysis/
    plotting code reading solver.X to host is legitimate."""
    result = _lint_src(tmp_path, "tools/post.py", """
import numpy as np

def to_host(solver):
    return np.asarray(solver.X)
""")
    assert result.findings == []


def test_dtl001_covers_fusedstep_module(tmp_path):
    """core/fusedstep.py is a declared hot module (its grid_eval
    bodies compile into the step program through the evaluator call
    graph): a stray sync there fires whole-file, and host-side
    precomposition stays quiet."""
    bad = _lint_src(tmp_path, "core/fusedstep.py", """
import jax

def grid_eval(plan, node, data):
    jax.block_until_ready(data)
    return data
""")
    assert _rules_fired(bad) == ["DTL001"]
    good = _lint_src(tmp_path, "core/fusedstep.py", """
import numpy as np

def composite(backward, term):
    return np.ascontiguousarray(np.asarray(backward) @ term)
""")
    assert good.findings == []


def test_dtl001_covers_solvecomp_module(tmp_path):
    """libraries/solvecomp.py is a declared hot module (the restructured
    substitution programs trace into every fused solve through
    BandedOps/DenseOps): a stray sync there fires whole-file, and the
    pure-jnp prefix/chunk builders stay quiet."""
    bad = _lint_src(tmp_path, "libraries/solvecomp.py", """
import jax

def spike_apply(ops, u, v0):
    jax.block_until_ready(u)
    return u
""")
    assert _rules_fired(bad) == ["DTL001"]
    good = _lint_src(tmp_path, "libraries/solvecomp.py", """
import jax.numpy as jnp

def ascan_combine(prev, nxt):
    A1, b1 = prev
    A2, b2 = nxt
    return A2 @ A1, A2 @ b1 + b2
""")
    assert good.findings == []


def test_dtl001_traced_concretization_any_module(tmp_path):
    bad = _lint_src(tmp_path, "anywhere.py", """
import numpy as np
import jax

def body(x):
    return np.asarray(x) + 1

jitted = jax.jit(body)
""")
    assert _rules_fired(bad) == ["DTL001"]
    good = _lint_src(tmp_path, "anywhere2.py", """
import numpy as np

def body(x):
    return np.asarray(x) + 1   # never traced: host helper
""")
    assert good.findings == []


def test_dtl002_fires_in_traced_context_module(tmp_path):
    result = _lint_src(tmp_path, "core/transforms.py", """
import jax.numpy as jnp

def apply_plan(plan, data):
    return jnp.asarray(plan.matrix) @ data
""")
    assert _rules_fired(result) == ["DTL002"]


def test_dtl002_fires_in_detected_traced_function(tmp_path):
    result = _lint_src(tmp_path, "mymodule.py", """
import jax.numpy as jnp
from dedalus_tpu.tools.jitlift import lifted_jit

def matmul(M, x):
    return jnp.asarray(M) @ x

matmul_j = lifted_jit(matmul)
""")
    assert _rules_fired(result) == ["DTL002"]


def test_dtl002_quiet_on_funnel_and_dtype_forms(tmp_path):
    result = _lint_src(tmp_path, "core/transforms.py", """
import jax.numpy as jnp
from dedalus_tpu.tools.jitlift import device_constant

def apply_plan(plan, data, rd):
    a = jnp.asarray(plan.shift, dtype=rd)      # scalar conversion: fine
    return device_constant(plan.matrix) @ data + a
""")
    assert result.findings == []


def test_dtl003_fires_on_wrapper_in_call_path(tmp_path):
    result = _lint_src(tmp_path, "solver.py", """
import jax

def solve(A, b):
    fn = jax.jit(lambda x: A @ x)
    return fn(b)
""")
    assert _rules_fired(result) == ["DTL003"]


def test_dtl003_exempts_init_self_and_module_scope(tmp_path):
    result = _lint_src(tmp_path, "stepper.py", """
import jax
from dedalus_tpu.tools.jitlift import lifted_jit

topfn = jax.jit(lambda x: x)

class Stepper:
    def __init__(self):
        self._fn = lifted_jit(lambda x: x + 1)
        self._cache = {}

    def rebuild(self, key, fn):
        self._fn = jax.jit(fn)                    # memoized on self
        out = self._cache[key] = jax.jit(fn)      # memoized in a cache
        return out
""")
    assert result.findings == []


def test_dtl004_fires_on_wide_device_dtypes(tmp_path):
    result = _lint_src(tmp_path, "widen.py", """
import numpy as np
import jax.numpy as jnp

def widen(x):
    y = jnp.zeros(4, dtype=np.complex128)
    return y + jnp.asarray(x, jnp.float64)
""")
    assert _rules_fired(result) == ["DTL004"]
    assert len(result.findings) == 2


def test_dtl004_quiet_on_host_numpy(tmp_path):
    result = _lint_src(tmp_path, "host.py", """
import numpy as np

def quadrature(n):
    return np.zeros(n, dtype=np.float64)   # host assembly: house precision
""")
    assert result.findings == []


def test_dtl005_fires_on_private_jax_imports(tmp_path):
    result = _lint_src(tmp_path, "internals.py", """
from jax._src.core import trace_ctx
import jax

def peek():
    return jax._src
""")
    assert _rules_fired(result) == ["DTL005"]
    assert len(result.findings) == 2


def test_dtl005_quiet_on_public_surface(tmp_path):
    result = _lint_src(tmp_path, "public.py", """
from jax.core import trace_state_clean

def clean():
    return trace_state_clean()
""")
    assert result.findings == []


def test_dtl006_fires_on_gradient_breakers_in_step_body(tmp_path):
    result = _lint_src(tmp_path, "core/timesteppers.py", """
import functools
import jax
from jax.experimental import io_callback

def step_body(M, L, X, t):
    Xd = jax.lax.stop_gradient(X)
    io_callback(print, None, t)
    return Xd

@functools.partial(jax.jit, donate_argnums=0)
def write_state(store, X):
    return store.at[0].set(X)
""")
    assert "DTL006" in _rules_fired(result)
    dtl6 = [f for f in result.findings if f.rule == "DTL006"]
    assert len(dtl6) == 3
    messages = " ".join(f.message for f in dtl6)
    assert "stop_gradient" in messages
    assert "host callback" in messages
    assert "donate" in messages


def test_dtl006_quiet_outside_step_bodies_and_without_donation(tmp_path):
    # stop_gradient in a non-step-body module: out of scope
    outside = _lint_src(tmp_path, "core/adjoint_helpers.py", """
import jax

def detach(x):
    return jax.lax.stop_gradient(x)
""")
    assert "DTL006" not in _rules_fired(outside)
    # .at[].set without donation, and on a local (not a donated
    # parameter): fine — functional updates are the jnp idiom
    undonated = _lint_src(tmp_path, "core/ddstep.py", """
import jax
import jax.numpy as jnp

def update(store, X):
    fresh = jnp.zeros_like(store)
    return fresh.at[0].set(X)

update_j = jax.jit(update)
""")
    assert undonated.findings == []


def test_dtl007_fires_on_aliased_host_mirror(tmp_path):
    """The PR-11 race encoded: jnp.asarray zero-copies the host mirror,
    a later in-place mutation rewrites the queued device operand. Both
    the attribute-mirror form (placement and mutation in different
    methods) and the same-function local form flag."""
    result = _lint_src(tmp_path, "core/ensemble.py", """
import jax.numpy as jnp

class Fleet:
    def place(self):
        self._active_dev = jnp.asarray(self.active_host)   # zero-copy

    def detach(self, m):
        self.active_host[m] = False                        # rewrites it

def budgets(steps_left):
    dev = jnp.asarray(steps_left)
    steps_left[0] = 0        # later in-place write, same function
    return dev
""")
    assert _rules_fired(result) == ["DTL007"]
    assert len(result.findings) == 2
    assert "zero-copies" in result.findings[0].message


def test_dtl007_quiet_on_copying_placements(tmp_path):
    """The sanctioned spellings stay quiet: jnp.array copies by default
    (the _put_host fix), build-then-place locals mutate BEFORE the
    placement, and numpy-side asarray is host bookkeeping."""
    result = _lint_src(tmp_path, "core/ensemble.py", """
import numpy as np
import jax.numpy as jnp

class Fleet:
    def place(self):
        self._active_dev = jnp.array(self.active_host)     # copies

    def detach(self, m):
        self.active_host[m] = False

def build_mask(n):
    mask = np.zeros(n, dtype=bool)
    mask[0] = True                 # mutation BEFORE placement: build
    return jnp.asarray(mask)

def host_only(snap):
    snap.lineage[0] = "x"
    return np.asarray(snap.lineage)
""")
    assert result.findings == []


def test_dtl008_fires_on_step_path_config_reads(tmp_path):
    """Config reads on the step/dispatch path of a hot module (and
    inside traced code anywhere) violate the resolved-once-per-build
    invariant the assembly/pool keys depend on."""
    bad = _lint_src(tmp_path, "core/timesteppers.py", """
from ..tools.config import config, cfg_get

class Stepper:
    def step(self, dt):
        mode = config["fusion"].get("FUSED_SOLVE", "auto")   # per step!
        return mode

    def _dispatch(self, n):
        return cfg_get("distributed", "TRANSPOSE_CHUNKS", "auto")
""")
    assert _rules_fired(bad) == ["DTL008"]
    assert len(bad.findings) == 2
    assert "solver-key" in bad.findings[0].message \
        or "pool keys" in bad.findings[0].message
    traced = _lint_src(tmp_path, "anymodule.py", """
import jax
from dedalus_tpu.tools.config import cfg_get

def body(x):
    chunks = int(cfg_get("distributed", "TRANSPOSE_CHUNKS", "2"))
    return x * chunks

jitted = jax.jit(body)
""")
    assert _rules_fired(traced) == ["DTL008"]
    assert "traced" in traced.findings[0].message


def test_dtl008_quiet_on_build_time_reads(tmp_path):
    """Build/factor-time resolution is the sanctioned pattern: reads in
    __init__, module-level helpers, and resolve_* functions stay quiet
    (the resolved value is stored before solver_key seals it)."""
    result = _lint_src(tmp_path, "core/timesteppers.py", """
from ..tools.config import config, cfg_get

def _use_split_step(solver):
    return config["execution"].get("STEP_PROGRAM", "auto") == "split"

def resolve_chunks():
    return cfg_get("distributed", "TRANSPOSE_CHUNKS", "auto")

class Stepper:
    def __init__(self):
        self._mode = config["fusion"].get("FUSED_SOLVE", "auto")

    def step(self, dt):
        return self._mode      # resolved once, read from self
""")
    assert result.findings == []
    # step-path reads OUTSIDE the hot modules are out of scope (tools,
    # analysis code) unless traced
    cold = _lint_src(tmp_path, "tools/post.py", """
from .config import cfg_get

def step(data):
    return cfg_get("analysis", "FORMAT", "h5")
""")
    assert cold.findings == []


def test_dtl009_fires_on_gspmd_fragile_ops(tmp_path):
    """jnp.pad / lax.map restored into a manual-region module — the
    jaxlib SPMD-partitioner crash classes PR 13 fixed — flag whole-file;
    the zeropad funnel and out-of-scope modules stay quiet."""
    bad = _lint_src(tmp_path, "core/transforms.py", """
import jax
import jax.numpy as jnp

def backward(data, n):
    padded = jnp.pad(data, ((0, 0), (0, n)))
    return jax.lax.map(lambda x: x * 2, padded)
""")
    assert _rules_fired(bad) == ["DTL009"]
    assert len(bad.findings) == 2
    messages = " ".join(f.message for f in bad.findings)
    assert "zeropad" in messages and "_shard_chunked" in messages
    good = _lint_src(tmp_path, "core/transforms.py", """
from ..tools.array import zeropad

def backward(data, n):
    return zeropad(data, ((0, 0), (0, n)))
""")
    assert good.findings == []
    # pencilops is deliberately out of scope (documented: its chunk maps
    # route through _shard_chunked; DTP105 guards the lowered programs)
    scoped = _lint_src(tmp_path, "libraries/pencilops.py", """
import jax.numpy as jnp

def pad_groups(arr, n):
    return jnp.pad(arr, ((0, n),), mode="edge")
""")
    assert scoped.findings == []


def test_dtl006_suppression_and_baseline_zero():
    """The shipped step bodies carry ZERO grandfathered DTL006 entries —
    the differentiable path depends on them staying gradient-clean."""
    import json
    data = json.loads(DEFAULT_BASELINE.read_text())
    assert [e for e in data["entries"] if e["rule"] == "DTL006"] == []


# -------------------------------------------- suppressions and the baseline

def test_same_line_suppression(tmp_path):
    result = _lint_src(tmp_path, "core/timesteppers.py", """
import jax

def warm(x):
    jax.block_until_ready(x)  # dedalus-lint: disable=DTL001 (probe warm)
""")
    assert result.findings == []
    assert len(result.suppressed) == 1
    assert result.suppressed[0].rule == "DTL001"


def test_file_level_suppression(tmp_path):
    result = _lint_src(tmp_path, "core/timesteppers.py", """
# dedalus-lint: disable-file=DTL001
import jax

def warm(x):
    jax.block_until_ready(x)

def warm2(x):
    jax.block_until_ready(x)
""")
    assert result.findings == []
    assert len(result.suppressed) == 2


def test_suppression_in_string_literal_is_inert(tmp_path):
    """Suppression syntax QUOTED in a docstring/string (e.g. docs of the
    mechanism itself) must not suppress anything."""
    result = _lint_src(tmp_path, "core/timesteppers.py", '''
"""Docs: add `# dedalus-lint: disable-file=DTL001` to silence a file."""
import jax

HOWTO = "# dedalus-lint: disable-file=DTL001"

def warm(x):
    jax.block_until_ready(x)
''')
    assert _rules_fired(result) == ["DTL001"]
    assert result.suppressed == []


def test_suppression_is_rule_specific(tmp_path):
    result = _lint_src(tmp_path, "core/timesteppers.py", """
import jax

def warm(x):
    jax.block_until_ready(x)  # dedalus-lint: disable=DTL002
""")
    # wrong rule named: the DTL001 finding stays active
    assert _rules_fired(result) == ["DTL001"]


def test_baseline_grandfathers_and_goes_stale(tmp_path):
    path = tmp_path / "core" / "timesteppers.py"
    path.parent.mkdir(parents=True)
    path.write_text("""
import jax

def warm(x):
    jax.block_until_ready(x)

def drain(x):
    jax.block_until_ready(x)
""")
    findings = run_lint([path]).findings
    assert len(findings) == 2
    baseline = {}
    for key, n in ((f.key(), 1) for f in findings):
        baseline[key] = baseline.get(key, 0) + n
    # grandfathered: nothing new, nothing stale
    new, stale = apply_baseline(findings, baseline)
    assert new == [] and stale == []
    # a third occurrence of the same snippet exceeds the baseline count
    path.write_text(path.read_text()
                    + "\n\ndef extra(x):\n    jax.block_until_ready(x)\n")
    new, stale = apply_baseline(run_lint([path]).findings, baseline)
    assert len(new) == 1 and stale == []
    # fixing every occurrence leaves the baseline stale
    path.write_text("import jax\n")
    new, stale = apply_baseline(run_lint([path]).findings, baseline)
    assert new == []
    assert len(stale) == 1 and stale[0]["rule"] == "DTL001"


def test_make_baseline_roundtrip(tmp_path):
    result = _lint_src(tmp_path, "core/timesteppers.py", """
import jax

def warm(x):
    jax.block_until_ready(x)
""")
    data = make_baseline(result.findings)
    assert data["version"] == 1
    assert len(data["entries"]) == 1
    entry = data["entries"][0]
    assert entry["rule"] == "DTL001"
    assert entry["snippet"] == "jax.block_until_ready(x)"


def test_multi_rule_same_line_suppression(tmp_path):
    """One comment can disable several rules on its line; each finding
    is counted separately (whitespace after commas tolerated)."""
    result = _lint_src(tmp_path, "mymod.py", """
import jax
import jax.numpy as jnp

def body(plan, data):
    jax.block_until_ready(data)  # dedalus-lint: disable=DTL001,DTL002
    return jnp.asarray(plan.matrix) @ data  # dedalus-lint: disable=DTL002, DTL001

jitted = jax.jit(body)
""")
    assert result.findings == []
    assert sorted(f.rule for f in result.suppressed) == ["DTL001", "DTL002"]


def test_multi_rule_disable_file(tmp_path):
    """disable-file accepts a rule list too, and leaves unnamed rules
    active."""
    result = _lint_src(tmp_path, "mymod.py", """
# dedalus-lint: disable-file=DTL002,DTL004
import jax
import jax.numpy as jnp
import numpy as np

def body(plan, data):
    a = jnp.asarray(plan.matrix)            # DTL002: file-suppressed
    b = jnp.zeros(4, dtype=np.float64)      # DTL004: file-suppressed
    jax.block_until_ready(data)             # DTL001: still active
    return a @ data + b

jitted = jax.jit(body)
""")
    assert _rules_fired(result) == ["DTL001"]
    assert sorted({f.rule for f in result.suppressed}) == ["DTL002",
                                                           "DTL004"]


def test_traced_detection_partial_jit_decorator(tmp_path):
    """functools.partial(jax.jit, ...) — decorator form AND call form —
    marks the function traced, so in-trace hazards fire without a plain
    jax.jit in sight."""
    result = _lint_src(tmp_path, "mymod.py", """
import functools
import numpy as np
import jax

@functools.partial(jax.jit, static_argnums=0)
def decorated(n, x):
    return np.asarray(x) + n          # DTL001: concretizes a tracer

def plain(x):
    return np.asarray(x) * 2          # DTL001 via the call form below

jitted = functools.partial(jax.jit, donate_argnums=())(plain)
""")
    dtl1 = [f for f in result.findings if f.rule == "DTL001"]
    assert len(dtl1) == 2, [f.format() for f in result.findings]


def test_traced_detection_noncall_contexts_stay_host(tmp_path):
    """A function never handed to a trace wrapper stays host code even
    when it LOOKS jit-adjacent (named like one, called next to one)."""
    result = _lint_src(tmp_path, "mymod2.py", """
import numpy as np
import jax

def jit_helper(x):
    return np.asarray(x)      # host: never traced

def run(x):
    return jax.jit(lambda v: v + 1)(x) + jit_helper(x).sum()
""")
    assert "DTL001" not in _rules_fired(result)


def test_dtl000_syntax_error_carries_location(tmp_path):
    """Unparsable modules surface as DTL000 findings with the parse
    error's line, participate in the baseline like any finding, and do
    not abort the scan of other files."""
    broken = tmp_path / "pkg" / "broken.py"
    broken.parent.mkdir(parents=True)
    broken.write_text("def f(:\n    pass\n")
    fine = broken.parent / "fine.py"
    fine.write_text("x = 1\n")
    result = run_lint([broken.parent])
    assert _rules_fired(result) == ["DTL000"]
    f = result.findings[0]
    assert f.line == 1 and "unparsable" in f.message
    # baseline round-trip: DTL000 grandfathering works like any rule
    new, stale = apply_baseline(result.findings, {f.key(): 1})
    assert new == [] and stale == []


def test_parallel_scan_matches_serial():
    """jobs>1 fans the per-file scan over a process pool; findings and
    suppressions must be IDENTICAL (content and order) to the serial
    pass over the real package tree."""
    serial = run_lint([PACKAGE_DIR])
    parallel = run_lint([PACKAGE_DIR], jobs=2)
    assert [f.to_dict() for f in parallel.findings] \
        == [f.to_dict() for f in serial.findings]
    assert [f.to_dict() for f in parallel.suppressed] \
        == [f.to_dict() for f in serial.suppressed]


def test_rules_filter_cli(tmp_path, capsys):
    """--rules runs the named subset only (and never reports package-
    baseline staleness, which a filtered run cannot judge); unknown ids
    are a usage error."""
    bad = tmp_path / "core" / "timesteppers.py"
    bad.parent.mkdir(parents=True)
    bad.write_text("""
import jax
import jax.numpy as jnp
import numpy as np

def step(x):
    jax.block_until_ready(x)                  # DTL001
    return jnp.zeros(4, dtype=np.float64)     # DTL004
""")
    rc = lint_main([str(bad), "--no-baseline", "--rules", "DTL004"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "DTL004" in out and "DTL001" not in out
    rc = lint_main([str(bad), "--rules", "DTL999"])
    assert rc == 2
    assert "unknown rule id" in capsys.readouterr().err


def test_stale_entries_render_with_fixed_count_by_default(tmp_path,
                                                         capsys):
    """The framework docstring promise, now rendered: a DEFAULT run
    prints stale entries as warnings with the fixed-hazard count, so the
    baseline visibly shrinks without anyone running --update-baseline."""
    bad = tmp_path / "core" / "timesteppers.py"
    bad.parent.mkdir(parents=True)
    bad.write_text("import jax\n\ndef f(x):\n    jax.block_until_ready(x)"
                   "\n\ndef g(x):\n    jax.block_until_ready(x)\n")
    baseline = tmp_path / "baseline.json"
    assert lint_main([str(bad), "--baseline", str(baseline),
                      "--update-baseline"]) == 0
    capsys.readouterr()
    bad.write_text("import jax\n")   # both hazards fixed
    rc = lint_main([str(bad), "--baseline", str(baseline)])
    out = capsys.readouterr().out
    assert rc == 1
    assert "stale baseline entry" in out
    assert "2 grandfathered occurrences no longer found" in out
    assert "1 stale baseline entry" in out


# --------------------------------------------------------- package hygiene

def test_package_lints_clean_against_baseline():
    """Self-enforcement: the shipped package has no un-baselined findings
    and no stale baseline entries. A new hot-path sync / inlined constant /
    nested jit / wide dtype / private import fails tier-1 here."""
    summary = lint_package()
    assert summary["new"] == 0, summary["findings"]
    assert summary["stale"] == []
    # the baseline is a short grandfather list, not a dumping ground
    assert summary["baselined"] <= 10


def test_known_bad_fixture_fails_lint(tmp_path, capsys):
    bad = tmp_path / "core" / "timesteppers.py"
    bad.parent.mkdir(parents=True)
    bad.write_text("import jax\n\ndef f(x):\n    jax.block_until_ready(x)\n")
    rc = lint_main([str(PACKAGE_DIR), str(bad)])
    out = capsys.readouterr().out
    assert rc == 1
    assert "DTL001" in out
    assert "1 new" in out


def test_cli_baseline_workflow(tmp_path, capsys):
    bad = tmp_path / "core" / "timesteppers.py"
    bad.parent.mkdir(parents=True)
    bad.write_text("import jax\n\ndef f(x):\n    jax.block_until_ready(x)\n")
    baseline = tmp_path / "baseline.json"
    assert lint_main([str(bad), "--no-baseline"]) == 1
    assert lint_main([str(bad), "--baseline", str(baseline),
                      "--update-baseline"]) == 0
    assert lint_main([str(bad), "--baseline", str(baseline)]) == 0
    # fixing the finding leaves the baseline stale -> nonzero until refreshed
    bad.write_text("import jax\n")
    assert lint_main([str(bad), "--baseline", str(baseline)]) == 1
    assert "stale" in capsys.readouterr().out


def test_update_baseline_refuses_path_subset(capsys):
    """Regenerating the PACKAGE baseline from a subset of paths would
    silently wipe every grandfathered entry outside them — including when
    the package baseline is spelled as a relative --baseline path."""
    before = DEFAULT_BASELINE.read_text()
    rc = lint_main([str(PACKAGE_DIR / "tools" / "health.py"),
                    "--update-baseline"])
    assert rc == 2
    assert "refusing" in capsys.readouterr().err
    assert DEFAULT_BASELINE.read_text() == before
    import os
    rel = os.path.relpath(DEFAULT_BASELINE)
    rc = lint_main([str(PACKAGE_DIR / "tools" / "health.py"),
                    "--update-baseline", "--baseline", rel])
    assert rc == 2
    assert DEFAULT_BASELINE.read_text() == before


def test_subset_scan_does_not_report_package_baseline_stale(capsys):
    """Linting one clean file against the default baseline must not call
    the out-of-scope grandfathered entries stale (they are unmatched
    because they were not scanned, not because they were fixed)."""
    rc = lint_main([str(PACKAGE_DIR / "tools" / "health.py")])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "stale" not in out or "0 stale" in out


def test_nonexistent_path_is_a_usage_error(tmp_path, capsys):
    rc = lint_main([str(tmp_path / "nope" / "missing.py"), "--no-baseline"])
    assert rc == 2
    assert "no such file" in capsys.readouterr().err


def test_cli_json_format(tmp_path, capsys):
    bad = tmp_path / "core" / "timesteppers.py"
    bad.parent.mkdir(parents=True)
    bad.write_text("import jax\n\ndef f(x):\n    jax.block_until_ready(x)\n")
    rc = lint_main([str(bad), "--no-baseline", "--format", "json"])
    assert rc == 1
    report = json.loads(capsys.readouterr().out)
    assert report["summary"]["new"] == 1
    assert report["findings"][0]["rule"] == "DTL001"
    assert report["findings"][0]["line"] == 4


def test_unparsable_file_is_a_finding(tmp_path):
    result = _lint_src(tmp_path, "broken.py", "def f(:\n")
    assert _rules_fired(result) == ["DTL000"]


def test_check_baseline_fresh(tmp_path):
    # shipped baseline: present and fresh
    assert check_baseline_fresh() == []
    assert DEFAULT_BASELINE.exists()
    missing = check_baseline_fresh(tmp_path / "nope.json")
    assert len(missing) == 1 and "missing" in missing[0]
    stale_file = tmp_path / "stale.json"
    stale_file.write_text(json.dumps({"version": 1, "entries": [
        {"rule": "DTL001", "path": "core/timesteppers.py",
         "snippet": "zzz_never_there()", "count": 1}]}))
    problems = check_baseline_fresh(stale_file)
    assert len(problems) == 1 and "stale" in problems[0]


def test_lint_cli_subprocess():
    """`python -m dedalus_tpu lint` is registered and exits 0 on the
    shipped tree (the acceptance-criteria invocation)."""
    proc = subprocess.run(
        [sys.executable, "-m", "dedalus_tpu", "lint", "dedalus_tpu/"],
        cwd=REPO, capture_output=True, text=True, timeout=240,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 new" in proc.stdout


# -------------------------------------------------------- retrace sentinel

@pytest.fixture
def clean_sentinel():
    retrace_mod.sentinel.reset()
    yield retrace_mod.sentinel
    retrace_mod.sentinel.reset()


def test_retrace_counts_and_warns_after_arm(clean_sentinel, caplog):
    from dedalus_tpu.tools.jitlift import lifted_jit
    m = metrics_mod.Metrics(sample_cadence=0, sampling=False)
    clean_sentinel.subscribe(m)
    fn = lifted_jit(lambda x: x * 2)
    fn(jnp.ones(3))
    fn(jnp.ones(3))          # cached signature: no new trace
    assert clean_sentinel.retraces == 0
    clean_sentinel.arm()
    with caplog.at_level(logging.WARNING, logger="dedalus_tpu.tools.retrace"):
        fn(jnp.ones(4))      # new signature after warmup: retrace
    assert clean_sentinel.post_arm_retraces == 1
    assert m.counter("dedalus/retrace").value == 1
    assert clean_sentinel.events[0]["kind"] == "retrace"
    assert any("post-warmup retrace" in r.message for r in caplog.records)


def test_first_trace_after_arm_is_not_a_retrace(clean_sentinel):
    from dedalus_tpu.tools.jitlift import lifted_jit
    clean_sentinel.arm()
    fn = lifted_jit(lambda x: x + 1)
    fn(jnp.ones(2))          # first compile of a fresh program: expected
    assert clean_sentinel.post_arm_retraces == 0
    assert clean_sentinel.total_traces >= 1


def test_noted_wrapper_participates(clean_sentinel):
    wrapped = retrace_mod.noted(lambda x: x + 1, "health/probe")
    j = jax.jit(wrapped)
    j(jnp.ones(2))
    j(jnp.ones(2))
    assert wrapped._retrace_state.count == 1
    clean_sentinel.arm()
    j(jnp.ones(3))
    assert clean_sentinel.post_arm_retraces == 1
    assert clean_sentinel.events[0]["label"] == "health/probe"


def test_retrace_warning_rate_limit_and_bounded_events(clean_sentinel,
                                                       caplog):
    """A retrace storm (the pathology the sentinel exists to catch) is
    fully counted but neither floods the log nor grows memory without
    bound."""
    from dedalus_tpu.tools.jitlift import lifted_jit
    fn = lifted_jit(lambda x: x.sum())
    fn(jnp.ones(1))
    clean_sentinel.arm()
    with caplog.at_level(logging.WARNING, logger="dedalus_tpu.tools.retrace"):
        for n in range(2, 10):          # 8 fresh signatures -> 8 retraces
            fn(jnp.ones(n))
    assert clean_sentinel.post_arm_retraces == 8
    warnings = [r for r in caplog.records
                if "post-warmup retrace" in r.message]
    assert len(warnings) == retrace_mod.WARNINGS_PER_LABEL
    assert "counted but not logged" in warnings[-1].message
    assert clean_sentinel.events.maxlen == retrace_mod.EVENT_RING_SIZE


def test_rb_step_loop_zero_post_warmup_retraces(clean_sentinel):
    """The acceptance-criteria sentinel assertion: the RB step loop —
    single steps and a scanned step_many block — compiles during/at
    warmup and never retraces afterwards; the verdict rides in the
    flushed telemetry record."""
    from dedalus_tpu.extras.bench_problems import build_rb_solver
    solver, b = build_rb_solver(32, 16, np.float64)
    solver.warmup_iterations = 2
    dt = 1e-4
    for _ in range(3):
        solver.step(dt)          # crosses warmup -> sentinel arms
    assert clean_sentinel.armed
    for _ in range(4):
        solver.step(dt)
    solver.step_many(4, dt)      # scan-block compile: first trace, no alarm
    solver.step_many(4, dt)
    assert clean_sentinel.post_arm_retraces == 0
    record = solver.flush_metrics()
    assert record["retraces_post_warmup"] == 0
    assert np.all(np.isfinite(np.asarray(solver.X)))


# ----------------------------------------------------- tracing-state probe

def test_tracing_active_public_path():
    from dedalus_tpu.tools import jitlift
    assert jitlift.tracing_active() is False
    seen = {}

    def f(x):
        seen["tracing"] = jitlift.tracing_active()
        return x

    jax.jit(f)(jnp.ones(2))
    assert seen["tracing"] is True
    assert jitlift.tracing_active() is False


def test_tracing_probe_degrades_with_one_warning(caplog):
    from dedalus_tpu.tools.jitlift import _resolve_tracing_probe

    def broken():
        raise ImportError("simulated jax API drift")

    with caplog.at_level(logging.WARNING, logger="dedalus_tpu.tools.jitlift"):
        probe = _resolve_tracing_probe(candidates=(broken, broken))
    assert probe() is False
    warnings = [r for r in caplog.records
                if "trace-state" in r.message]
    assert len(warnings) == 1


def test_tracing_probe_private_fallback_still_resolves():
    from dedalus_tpu.tools.jitlift import (_probe_private,
                                           _resolve_tracing_probe)

    def broken():
        raise AttributeError("public surface renamed")

    probe = _resolve_tracing_probe(candidates=(broken, _probe_private))
    assert probe() is False   # eager context: not tracing


def test_degraded_probe_does_not_poison_registry(monkeypatch):
    """With the probe degraded to never-tracing, a device_constant
    reached inside a foreign trace must NOT cache the resulting tracer
    in the process-global registry (jnp.asarray of a numpy array under
    a trace IS a tracer)."""
    from dedalus_tpu.tools import jitlift
    monkeypatch.setattr(jitlift, "_tracing_probe", jitlift._degraded_probe)
    assert jitlift.tracing_state_known() is False
    arr = np.arange(8.0)

    def f(x):
        return x + jitlift.device_constant(arr)

    assert np.allclose(np.asarray(jax.jit(f)(jnp.ones(8))), arr + 1)
    # the registry survived the foreign trace: eager use still works
    assert np.allclose(np.asarray(jitlift.device_constant(arr)), arr)
    assert np.allclose(np.asarray(jax.jit(f)(jnp.ones(8))), arr + 1)


def test_degraded_probe_keeps_general_function_callback_path(monkeypatch):
    """operators._tracing_active reports True when the probe degraded:
    an argless impure GeneralFunction has no tracer arguments for the
    call-site scan to catch, so unknown trace state must keep the
    io_callback path."""
    from dedalus_tpu.tools import jitlift
    from dedalus_tpu.core import operators
    assert operators._tracing_active() is False   # healthy probe, eager
    monkeypatch.setattr(jitlift, "_tracing_probe", jitlift._degraded_probe)
    assert operators._tracing_active() is True


# ------------------------------------------------------------ leak sentinel

@pytest.mark.leak_check
def test_lifted_jit_under_leak_check():
    """jitlift's discover/substitute machinery holds no tracers across
    trace boundaries (the registry caches numpy, never tracers); the
    leak_check marker runs this under jax.checking_leaks()."""
    from dedalus_tpu.tools.jitlift import lifted_jit
    fn = lifted_jit(lambda x: x * 3 + 1)
    out = fn(jnp.arange(4.0))
    assert np.allclose(np.asarray(out), np.arange(4.0) * 3 + 1)

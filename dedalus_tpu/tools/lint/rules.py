"""
The DTL rule set. Every rule is grounded in a hazard this codebase has
actually hit (see docstrings); each documents its heuristic boundaries so
a quiet pass is never mistaken for a proof.

Scopes:
  HOT_PATH_MODULES     — the step-loop modules where a stray host sync
                         serializes the dispatch pipeline every iteration.
  TRACED_CONTEXT_MODULES — device math libraries whose functions run under
                         jit via the transform/solve call graph even though
                         no jit wrapper appears in-module (static tracing
                         detection cannot see through the call graph, so
                         these are declared).
  FUNNEL_MODULES       — the sanctioned precision/constant funnels; exempt
                         from DTL002 (they ARE the device_constant route).
"""

import ast

from .framework import Rule, register, name_matches, module_matches

HOT_PATH_MODULES = (
    "core/timesteppers.py",
    "core/ddstep.py",
    "libraries/pencilops.py",
    "parallel/transposes.py",
    # the resilient loop brackets every step: a stray sync here (the
    # shipped case: Snapshot.is_finite gathering the full state per
    # capture validation) stalls the same pipeline the step modules do
    "tools/resilience.py",
    # the continuous-batching dispatcher brackets every fleet block: a
    # stray host sync between boundaries serializes the whole batch's
    # dispatch pipeline (member IO belongs in core/ensemble seat APIs,
    # reply-phase IO after the boundary probe)
    "service/batching.py",
    # the fused-step module's grid_eval bodies compile into the
    # step program through the evaluator call graph (no in-module jit
    # wrapper for the structural pass to see) — a stray sync here lands
    # inside every fused step
    "core/fusedstep.py",
    # the restructured-substitution programs (associative-scan prefix,
    # SPIKE chunk solves, the precision-ladder refinement) trace into
    # every fused solve through BandedOps/DenseOps — same exposure as
    # pencilops itself
    "libraries/solvecomp.py",
    # request tracing brackets every step/request phase by contract as
    # HOST-ONLY bookkeeping (docs/observability.md): a device gather or
    # block_until_ready smuggled into a span helper would charge every
    # instrumented phase a sync and break the <1% overhead budget
    "tools/tracing.py",
    # chaos hooks wrap step/IO callables IN PLACE on the hot loop: a
    # fault injector that gathers state to decide whether to fire would
    # charge every un-faulted step the sync the suite exists to forbid
    "tools/chaos.py",
    # spec digesting + IC decoding run per request on the serving path;
    # result encoding is the one place device arrays legitimately land
    # on the host, but it must do so ONCE (explicitly), not via stray
    # per-field syncs smuggled into validation helpers
    "service/protocol.py",
    # the router relays every served frame and the supervisor probes
    # every replica each probe tick: both are pure host/socket plumbing
    # by contract — any device call here would charge every forwarded
    # request (or every health probe) a sync it has no business paying
    "service/router.py",
    "service/fleet.py",
)

# Device-state attribute names (the gathered pencil/fleet state and its
# companions). By codebase contract these attributes hold jax device
# arrays; `np.asarray` of one is a full device->host gather.
STATE_ARRAY_ATTRS = frozenset({
    "X", "dd_X", "T", "DT", "F_hist", "MX_hist", "LX_hist",
})

TRACED_CONTEXT_MODULES = (
    "core/transforms.py",
    "core/weighted_jacobi.py",
    "libraries/pencilops.py",
    "libraries/matsolvers.py",
    "libraries/solvecomp.py",
    "libraries/sphere.py",
    "libraries/zernike.py",
    "libraries/spin_intertwiners.py",
)

FUNNEL_MODULES = (
    "tools/array.py",
    "tools/jitlift.py",
)

STEP_BODY_MODULES = (
    "core/timesteppers.py",
    "core/ddstep.py",
)


def _contains_jax_call(ctx, node):
    """Whether the expression contains a call into jax/jax.numpy."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call):
            name = ctx.canon(sub.func)
            if name is not None and (name.startswith("jax.")
                                     or name == "jax"):
                return True
    return False


@register
class HostSyncInHotPath(Rule):
    """DTL001: host synchronization in the step loop.

    JAX dispatch is asynchronous; `.item()`, `float()/int()` of a device
    value, `np.asarray()` of a tracer, and `block_until_ready` each force
    the host to wait on the device (or worse, bake a sync into every
    iteration), which serializes the dispatch pipeline the whole metrics
    subsystem was built to keep clean (tools/metrics.py module docstring).
    The only sanctioned blocking is the cadence-gated sampler in
    tools/metrics.py — which is outside this rule's scope by construction.

    Heuristics: fires in HOT_PATH_MODULES (whole file) and inside traced
    functions anywhere. `float()/int()` only flag when the argument
    contains a jax/jnp call (`float(dt)` on host scalars is fine);
    `np.asarray/np.array` only flag bare-Name arguments inside traced code
    (attribute chains like `scheme.A` are host tableau constants).
    Additionally, anywhere in HOT_PATH_MODULES, `np.asarray/np.array`
    of a STATE-array attribute (`.X`, `.F_hist`, ... — device arrays by
    codebase contract, see STATE_ARRAY_ATTRS) with no dtype= flags as a
    full device->host state gather: the shipped case was
    `np.all(np.isfinite(np.asarray(self.X)))` in the snapshot-capture
    validation (tools/resilience.py), fixed by routing through the
    HealthMonitor's fused device-side probe.
    """

    id = "DTL001"
    severity = "error"
    title = "host-sync-in-hot-path"

    def check(self, ctx):
        hot = module_matches(ctx.rel, HOT_PATH_MODULES)
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            in_scope = hot or ctx.in_traced(node)
            if not in_scope:
                continue
            func = node.func
            if isinstance(func, ast.Attribute) and func.attr == "item" \
                    and not node.args:
                yield self.finding(
                    ctx, node, ".item() forces a device->host sync in the "
                    "hot path; keep reductions on device or move the read "
                    "behind a metrics/health cadence gate")
                continue
            if (isinstance(func, ast.Attribute)
                    and func.attr == "block_until_ready") or (
                    (name := ctx.canon(func)) is not None
                    and name_matches(name, "jax.block_until_ready")):
                yield self.finding(
                    ctx, node, "block_until_ready in the hot path "
                    "serializes the dispatch pipeline; only the "
                    "cadence-gated sampler in tools/metrics.py may block")
                continue
            name = ctx.canon(func)
            if name in ("float", "int") and node.args \
                    and _contains_jax_call(ctx, node.args[0]):
                yield self.finding(
                    ctx, node, f"{name}() of a jax expression synchronously "
                    "pulls the value to host; keep the computation on "
                    "device or sample it behind a cadence gate")
                continue
            # exact match: suffix-tolerant matching would also catch
            # jax.numpy.asarray, which is the trace-safe spelling
            if name in ("numpy.asarray", "numpy.array") \
                    and node.args and isinstance(node.args[0], ast.Name) \
                    and ctx.in_traced(node):
                yield self.finding(
                    ctx, node, f"{name.split('.')[-1]}() on a local inside "
                    "traced code concretizes a tracer (host sync or trace "
                    "error); use jnp, or hoist host work out of the trace")
                continue
            # state-attribute gather: np.asarray(self.X) and friends in a
            # hot module is a full device->host transfer of the pencil/
            # fleet state (dtype= marks a deliberate host conversion of
            # host-side data and is exempt, matching DTL002's convention)
            if hot and name in ("numpy.asarray", "numpy.array") \
                    and node.args \
                    and isinstance(node.args[0], ast.Attribute) \
                    and node.args[0].attr in STATE_ARRAY_ATTRS \
                    and len(node.args) < 2 \
                    and not any(kw.arg == "dtype" for kw in node.keywords):
                yield self.finding(
                    ctx, node, f"{name.split('.')[-1]}() of the device "
                    f"state attribute .{node.args[0].attr} gathers the "
                    "full state to host; use the HealthMonitor fused "
                    "probe (nonfinite_count) or a jitted device-side "
                    "reduction with a scalar pull instead")


@register
class InlinedDeviceConstant(Rule):
    """DTL002: host array inlined into compiled program text.

    This JAX version inlines every non-splat array constant into the
    lowered MLIR — a 100 MB transform stack adds ~400 MB of program text,
    and spectral kernels are built from exactly such constants
    (tools/jitlift.py module docstring; the multi-GB programs that
    motivated lifted_jit). Host matrices entering traced code must route
    through tools.jitlift.device_constant (directly or via the
    tools.array.match_precision funnel) so they become runtime ARGUMENTS.

    Heuristic: flags `jnp.asarray(x)` / `jnp.array(x)` where x is a bare
    Name or attribute chain and no dtype= is given, inside traced
    functions anywhere plus anywhere in TRACED_CONTEXT_MODULES (device
    libraries reached under jit through the call graph). Calls that pass
    dtype= are the deliberate small-scalar/coefficient conversions the
    step path makes (e.g. `jnp.asarray(a, dtype=rd)`); the bare no-dtype
    form is the "just ship the matrix" pattern that inlines (the shipped
    case: core/weighted_jacobi.py's radial matmul before it was routed
    through the funnel).
    """

    id = "DTL002"
    severity = "error"
    title = "inlined-device-constant"

    def check(self, ctx):
        if module_matches(ctx.rel, FUNNEL_MODULES):
            return
        lib = module_matches(ctx.rel, TRACED_CONTEXT_MODULES)
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            name = ctx.canon(node.func)
            if name is None or not name_matches(
                    name, "jax.numpy.asarray", "jax.numpy.array"):
                continue
            # a dtype argument (kwarg or positional) marks the deliberate
            # scalar/coefficient conversions of the step path
            if len(node.args) >= 2 \
                    or any(kw.arg == "dtype" for kw in node.keywords):
                continue
            if not node.args or not isinstance(node.args[0],
                                               (ast.Name, ast.Attribute)):
                continue
            if lib or ctx.in_traced(node):
                yield self.finding(
                    ctx, node, "host array converted in traced context is "
                    "inlined into program text; route it through "
                    "tools.jitlift.device_constant (or the "
                    "tools.array.match_precision funnel) so it becomes a "
                    "runtime argument")


@register
class JitInCallPath(Rule):
    """DTL003: jit wrapper constructed inside a call path.

    `jax.jit` / `lifted_jit` build a fresh trace cache per wrapper object:
    constructing one inside a function that runs per step (or per solve)
    retraces and recompiles on every call — the program-cache equivalent
    of a host sync, and it also defeats lifted_jit's constant interning.
    Wrappers belong at module scope, in `__init__`, or memoized.

    Heuristic: flags jit/lifted_jit calls (including
    functools.partial(jax.jit, ...) used as a decorator) lexically inside
    a function body, EXCEPT inside `__init__` and except when the result
    is stored to `self.<attr>` or into a subscripted cache (both memoized-
    once patterns used across this codebase). Hand-rolled `if cache is
    None` guards around a plain local are invisible to this pass — carry
    a suppression comment naming the cache.
    """

    id = "DTL003"
    severity = "error"
    title = "jit-in-call-path"

    def _exempt_assignment(self, ctx, node):
        """Whether the jit call's value lands in a memoized slot."""
        cur = node
        parent = ctx.parent(cur)
        while parent is not None and not isinstance(parent, ast.stmt):
            cur, parent = parent, ctx.parent(parent)
        if isinstance(parent, ast.Assign):
            return any(isinstance(t, (ast.Attribute, ast.Subscript))
                       for t in parent.targets)
        if isinstance(parent, (ast.AnnAssign, ast.AugAssign)):
            return isinstance(parent.target, (ast.Attribute, ast.Subscript))
        return False

    def check(self, ctx):
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Call) and ctx._jitish(node):
                name = ctx.canon(node.func)
                # only the jit constructors; tracing combinators like
                # lax.scan/vmap run inside traces by design
                if name is None or not (
                        name_matches(name, "jax.jit", "lifted_jit")
                        or (name_matches(name, "functools.partial")
                            and node.args
                            and (inner := ctx.canon(node.args[0])) is not None
                            and name_matches(inner, "jax.jit"))):
                    continue
                enclosing = ctx.enclosing_function(node)
                if enclosing is None or enclosing.name == "__init__":
                    continue
                if self._exempt_assignment(ctx, node):
                    continue
                yield self.finding(
                    ctx, node, "jit wrapper constructed inside a function "
                    "retraces per call; hoist to module scope/__init__, "
                    "memoize on self or in a cache, or suppress with the "
                    "cache named")


@register
class DtypeLiteralHygiene(Rule):
    """DTL004: hard-coded wide dtype on the device path.

    TPU has no complex128 and emulates float64; working precision is
    chosen once per problem and funneled through tools/array.py
    (match_precision) and the solver's pencil/real dtypes. A literal
    `jnp.float64` / `jnp.complex128` — or numpy's spelled as a jnp dtype=
    argument — silently promotes device arrays past the configured
    precision, costing memory and MXU throughput exactly where it is
    least visible.

    Heuristic: flags `jnp.float64` / `jnp.complex128` attributes anywhere,
    `np.float64` / `np.complex128` when passed as dtype= to a jnp call,
    and `.astype(np.float64/complex128)` inside traced code. Host-side
    numpy float64 (quadrature, matrix assembly) is the house precision
    and intentionally not flagged.
    """

    id = "DTL004"
    severity = "warning"
    title = "dtype-literal-hygiene"

    _WIDE_JNP = ("jax.numpy.float64", "jax.numpy.complex128")
    _WIDE_NP = ("numpy.float64", "numpy.complex128")

    def check(self, ctx):
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Attribute):
                name = ctx.canon(node)
                if name is not None and name_matches(name, *self._WIDE_JNP):
                    yield self.finding(
                        ctx, node, f"hard-coded {name.split('.')[-1]} "
                        "bypasses the precision funnel (tools/array.py); "
                        "derive the dtype from the data or the solver's "
                        "configured precision")
            elif isinstance(node, ast.Call):
                fname = ctx.canon(node.func)
                if fname is not None and fname.startswith("jax.numpy."):
                    for kw in node.keywords:
                        if kw.arg != "dtype":
                            continue
                        dname = ctx.canon(kw.value)
                        if dname is not None and name_matches(
                                dname, *self._WIDE_NP):
                            yield self.finding(
                                ctx, node, f"dtype={dname.split('.')[-1]} "
                                "on a jnp call bypasses the precision "
                                "funnel (tools/array.py)")
                elif (isinstance(node.func, ast.Attribute)
                        and node.func.attr == "astype" and node.args
                        and ctx.in_traced(node)):
                    dname = ctx.canon(node.args[0])
                    if dname is not None and name_matches(
                            dname, *self._WIDE_NP, *self._WIDE_JNP):
                        yield self.finding(
                            ctx, node, f".astype({dname.split('.')[-1]}) "
                            "inside traced code bypasses the precision "
                            "funnel (tools/array.py)")


@register
class PrivateJaxApi(Rule):
    """DTL005: dependency on jax._src internals.

    `jax._src` has no stability contract; imports from it are the part of
    this codebase that breaks on every JAX upgrade (the historical
    `_tracing_active` probe in tools/jitlift.py). Public equivalents or a
    guarded fallback (try public, degrade with one warning) are required;
    the single sanctioned fallback carries a suppression naming why.
    """

    id = "DTL005"
    severity = "warning"
    title = "private-jax-api"

    def check(self, ctx):
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.ImportFrom):
                mod = node.module or ""
                if node.level == 0 and (mod == "jax._src"
                                        or mod.startswith("jax._src.")):
                    yield self.finding(
                        ctx, node, f"import from {mod} (no stability "
                        "contract); prefer the public jax.* surface with "
                        "a guarded fallback")
            elif isinstance(node, ast.Import):
                for alias in node.names:
                    if alias.name == "jax._src" \
                            or alias.name.startswith("jax._src."):
                        yield self.finding(
                            ctx, node, f"import of {alias.name} (no "
                            "stability contract); prefer the public jax.* "
                            "surface with a guarded fallback")
            elif isinstance(node, ast.Attribute) and node.attr == "_src":
                name = ctx.canon(node)
                if name == "jax._src":
                    yield self.finding(
                        ctx, node, "jax._src attribute access (no "
                        "stability contract); prefer the public jax.* "
                        "surface with a guarded fallback")


@register
class NonDifferentiableOpInStepBody(Rule):
    """DTL006: gradient-breaking op in a raw step body.

    The raw step bodies (`MultistepIMEX.advance_body`,
    `RungeKuttaIMEX.step_body`) are the pure functions the differentiable
    subsystem scans and backpropagates through (core/adjoint.py), and the
    ensemble solver vmaps. Three op classes silently break that contract:

      * `jax.lax.stop_gradient` — zeroes the cotangent flow mid-loop, so
        adjoint gradients come back wrong with no error;
      * host callbacks (`io_callback`, `pure_callback`,
        `jax.debug.callback`, `host_callback.call`) — have no transpose
        rule, so `jax.grad` through the step raises (or, for debug
        callbacks, detaches silently);
      * `.at[...].set()` on a DONATED buffer — in-place aliasing of an
        input whose value the backward pass still needs to replay.

    Heuristics: fires only in STEP_BODY_MODULES. stop_gradient and the
    callbacks flag anywhere in those modules (the whole file compiles
    into step programs). The donated-buffer case flags `.at[...].set()`
    whose base is a PARAMETER of a function that some jit wrapper in the
    same module marks with donate_argnums (lexical detection only —
    donation via call sites in other modules is invisible to this pass;
    carry a suppression naming the owner if such a case is ever
    deliberate).
    """

    id = "DTL006"
    severity = "error"
    title = "non-differentiable-op-in-step-body"

    _CALLBACKS = ("jax.experimental.io_callback", "io_callback",
                  "jax.pure_callback", "jax.debug.callback",
                  "jax.experimental.host_callback.call")

    def _donated_functions(self, ctx):
        """Names of functions traced by a jit-ish call (or decorated)
        that passes donate_argnums in this module."""
        names = set()
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            kwargs = {kw.arg for kw in node.keywords}
            if "donate_argnums" not in kwargs:
                continue
            name = ctx.canon(node.func)
            if name is None:
                continue
            jitish = name_matches(name, "jax.jit", "lifted_jit") or (
                name_matches(name, "functools.partial") and node.args
                and (inner := ctx.canon(node.args[0])) is not None
                and name_matches(inner, "jax.jit", "lifted_jit"))
            if not jitish:
                continue
            for arg in node.args:
                if isinstance(arg, ast.Name):
                    names.add(arg.id)
            parent = ctx.parent(node)
            # decorator form: @functools.partial(jax.jit, donate_argnums=..)
            if isinstance(parent, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and node in parent.decorator_list:
                names.add(parent.name)
        return names

    @staticmethod
    def _at_set_base(node):
        """For a call `X.at[...].set(...)`, the root expression X (None
        when the call is not an at-set chain)."""
        func = node.func
        if not (isinstance(func, ast.Attribute) and func.attr == "set"):
            return None
        sub = func.value
        if not isinstance(sub, ast.Subscript):
            return None
        base = sub.value
        if not (isinstance(base, ast.Attribute) and base.attr == "at"):
            return None
        return base.value

    def check(self, ctx):
        if not module_matches(ctx.rel, STEP_BODY_MODULES):
            return
        donated = None
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            name = ctx.canon(node.func)
            if name is not None and name_matches(name,
                                                 "jax.lax.stop_gradient"):
                yield self.finding(
                    ctx, node, "stop_gradient inside a step body zeroes "
                    "the adjoint cotangent flow silently (core/adjoint.py "
                    "backpropagates through these bodies); compute the "
                    "detached value outside the step")
                continue
            if name is not None and name_matches(name, *self._CALLBACKS):
                yield self.finding(
                    ctx, node, "host callback inside a step body has no "
                    "transpose rule: jax.grad through the step loop "
                    "raises (or silently detaches); hoist the host work "
                    "out of the traced step")
                continue
            base = self._at_set_base(node)
            if base is None or not isinstance(base, ast.Name):
                continue
            enclosing = ctx.enclosing_function(node)
            if enclosing is None:
                continue
            if donated is None:
                donated = self._donated_functions(ctx)
            if enclosing.name not in donated:
                continue
            params = {a.arg for a in enclosing.args.args
                      + enclosing.args.posonlyargs
                      + enclosing.args.kwonlyargs}
            if base.id in params:
                yield self.finding(
                    ctx, node, f".at[].set on parameter '{base.id}' of a "
                    "donate_argnums-jitted step body aliases a donated "
                    "input the backward pass still needs; drop the "
                    "donation or write to a fresh buffer")


# Modules whose traced bodies run inside (or compose into) shard_map
# manual/partial-auto regions — the scope of DTL009. libraries/pencilops.py
# is deliberately NOT listed: its lax.map chunk dispatches route through
# BandedOps._shard_chunked manual shard_maps / static unrolls (the PR-13
# fixes), and its one surviving jnp.pad is mode="edge" factor-time padding
# that tools.array.zeropad cannot express — the compiled-program contract
# DTP105 (tools/lint/progcheck.py) still guards the lowered result.
MANUAL_REGION_MODULES = (
    "core/transforms.py",
    "core/subsystems.py",
    "core/field.py",
    "core/ensemble.py",
    "core/fusedstep.py",
    "core/timesteppers.py",
    "core/meshctx.py",
    "parallel/transposes.py",
)

# Function names that ARE the step/dispatch path in the hot modules: code
# here runs per step (or per fleet block), strictly after the solver key
# was sealed. Curated exact names, not substrings — build-time helpers
# like timesteppers._use_split_step legitimately read config.
STEP_PATH_FUNCTIONS = frozenset({
    "step", "step_many", "step_fleet", "advance", "advance_body",
    "step_body", "_step_split", "_dispatch", "_ms_single", "solve",
    "solve_transpose", "matvec", "matvec_pair", "evolve",
    "evolve_resilient",
})


def _dotted(node):
    """Dotted source name of a Name/Attribute chain ('self.active_host',
    'dts'); None when the base is not a plain name."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        return ".".join([node.id] + parts[::-1])
    return None


@register
class HostMirrorAliasing(Rule):
    """DTL007: zero-copy device placement of a mutated host mirror.

    `jnp.asarray` of an aligned numpy buffer is ZERO-COPY on CPU: the
    device array aliases the very memory later in-place writes mutate,
    which retroactively rewrites the value operand of every dispatch
    still queued on the async stream. The shipped case (PR 11): the
    ensemble host mirrors (`active_host[m] = False`, `sim_times += ...`)
    silently froze members for the tail of a served batch by rewriting
    queued fleet operands. The sanctioned spellings copy:
    `jnp.array(arr)` (copy=True by default — core/ensemble._put_host) or
    an explicit `.copy()` on the source.

    Heuristics: flags `jnp.asarray(x)` where x is
      * an attribute chain (`self.active_host`, `snap.X`) that is
        subscript-mutated (`x[...] = ...`, `x[...] += ...`) ANYWHERE in
        the module — mirrors live on objects and the placement and the
        mutation are typically in different methods; or
      * a bare local name subscript-mutated LATER in the same function —
        a buffer built in place and then placed (mutations before the
        placement) is the legitimate construction pattern and stays
        quiet.
    `jnp.array(...)` never flags (it copies). The dotted-name match is
    textual (no alias analysis): two objects sharing an attribute name in
    one module can false-positive — carry a suppression naming why the
    buffers are distinct.
    """

    id = "DTL007"
    severity = "error"
    title = "host-mirror-aliasing"

    @staticmethod
    def _mutations(ctx):
        """{dotted name: [mutation nodes]} for subscript stores."""
        out = {}
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Assign):
                targets = node.targets
            elif isinstance(node, ast.AugAssign):
                targets = [node.target]
            else:
                continue
            for target in targets:
                if isinstance(target, ast.Subscript):
                    name = _dotted(target.value)
                    if name:
                        out.setdefault(name, []).append(node)
        return out

    def check(self, ctx):
        mutated = None
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            name = ctx.canon(node.func)
            # only the zero-copy spelling; jnp.array copies by default
            if name is None or not name_matches(name, "jax.numpy.asarray"):
                continue
            arg = node.args[0]
            src = _dotted(arg)
            if src is None:
                continue
            if mutated is None:
                mutated = self._mutations(ctx)
            writes = mutated.get(src)
            if not writes:
                continue
            if isinstance(arg, ast.Name):
                fn = ctx.enclosing_function(node)
                later = [w for w in writes
                         if ctx.enclosing_function(w) is fn
                         and w.lineno > node.lineno]
                if fn is None or not later:
                    continue
            yield self.finding(
                ctx, node, f"jnp.asarray({src}) zero-copies a host "
                "buffer that is mutated in place elsewhere "
                f"(line {writes[0].lineno}): queued dispatches would see "
                "the rewritten value; place mirrors by copy "
                "(jnp.array, or .copy() the source)")


@register
class ConfigReadInStepPath(Rule):
    """DTL008: config read on the step/dispatch path after solver-key
    resolution.

    The load-bearing invariant of PRs 12-13: every config knob a compiled
    program depends on is resolved ONCE per solver build, stored on the
    solver (`solver._fusion_plan`, `solver._transpose_chunks`) BEFORE
    `assembly_cache.solver_key` seals it, and folded into the assembly
    and serving pool keys — so two configs can never alias one compiled
    program. A `cfg_get`/`config[...]` read inside the step path (or
    inside traced code, where it bakes into one program variant at trace
    time) reintroduces exactly the aliasing the keys exist to prevent:
    the value read at step N is invisible to every cache key.

    Heuristics: flags config reads (tools.config.cfg_get /
    config[...] subscripts) inside traced functions ANYWHERE, and — in
    the HOT_PATH_MODULES — inside functions named in STEP_PATH_FUNCTIONS
    (exact names; walk-up through nested functions). Build/factor-time
    reads (`__init__`, `_use_split_step`, `resolve_*`) are the sanctioned
    pattern and stay quiet; a step-path function that must consult config
    should take the resolved value as an argument instead.
    """

    id = "DTL008"
    severity = "error"
    title = "config-read-in-step-path"

    @staticmethod
    def _is_config_read(ctx, node):
        if isinstance(node, ast.Call):
            name = ctx.canon(node.func)
            return name is not None and name_matches(name, "cfg_get")
        if isinstance(node, ast.Subscript):
            name = ctx.canon(node.value)
            # exact forms only: the tools.config singleton (however
            # imported) or a bare `config` name — `self.config`/other
            # attributes named config are not the global read
            return name == "config" \
                or (name is not None
                    and name.endswith("tools.config.config"))
        return False

    def _in_step_path(self, ctx, node):
        cur = ctx.parent(node)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and cur.name in STEP_PATH_FUNCTIONS:
                return True
            cur = ctx.parent(cur)
        return False

    def check(self, ctx):
        hot = module_matches(ctx.rel, HOT_PATH_MODULES)
        for node in ast.walk(ctx.tree):
            if not self._is_config_read(ctx, node):
                continue
            # Subscript STORES (config["x"]["Y"] = ...) are test/setup
            # mutations, not reads
            if isinstance(node, ast.Subscript) \
                    and isinstance(getattr(node, "ctx", None),
                                   (ast.Store, ast.Del)):
                continue
            if ctx.in_traced(node):
                yield self.finding(
                    ctx, node, "config read inside traced code bakes the "
                    "value into one program variant invisibly to the "
                    "solver/pool keys; resolve it once per build and "
                    "pass the resolved value in")
            elif hot and self._in_step_path(ctx, node):
                yield self.finding(
                    ctx, node, "config read on the step/dispatch path "
                    "(after solver-key resolution): the value is "
                    "invisible to the assembly/pool keys, so two configs "
                    "could alias one compiled program; resolve once per "
                    "build (before solver_key) and store it on the "
                    "solver")


@register
class GspmdFragileOp(Rule):
    """DTL009: GSPMD-fragile op in a manual-region module.

    jaxlib 0.4.37's SPMD partitioner hard-crashes on `pad` ops inside the
    GSPMD-auto subregion of a partially-manual shard_map
    (hlo_sharding_util CHECK IsManualSubgroup), and miscompiles
    `lax.map`-style chunk scans under GSPMD (s64/s32
    dynamic_update_slice mismatch) — the three crash classes PR 13 fixed.
    The traced bodies of MANUAL_REGION_MODULES compose into exactly those
    regions (the 2-D batch x pencil fleet wraps them all), so zero
    padding there must lower through `tools.array.zeropad`
    (concatenation, bitwise identical) and chunk maps must route through
    an explicit manual shard_map or a static unroll
    (libraries/pencilops.BandedOps._shard_chunked is the model).

    Heuristic: flags any `jnp.pad` / `jax.lax.map` call in the scoped
    modules, whole-file — these modules' functions are reached under the
    fleet composition regardless of where in the file they sit. The
    compiled-program contract DTP105 (tools/lint/progcheck.py) is the
    backstop that checks the LOWERED programs, including modules outside
    this scope.
    """

    id = "DTL009"
    severity = "error"
    title = "gspmd-fragile-op"

    def check(self, ctx):
        if not module_matches(ctx.rel, MANUAL_REGION_MODULES):
            return
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            name = ctx.canon(node.func)
            if name is None:
                continue
            if name_matches(name, "jax.numpy.pad"):
                yield self.finding(
                    ctx, node, "jnp.pad in a manual-region module: the "
                    "SPMD partitioner crashes on pad inside partial-auto "
                    "shard_map regions; use tools.array.zeropad for zero "
                    "padding (non-zero modes need explicit manual "
                    "shard_map routing)")
            elif name_matches(name, "jax.lax.map"):
                yield self.finding(
                    ctx, node, "lax.map in a manual-region module "
                    "miscompiles under GSPMD; route the chunk map "
                    "through a manual shard_map or a static unroll "
                    "(see pencilops.BandedOps._shard_chunked)")

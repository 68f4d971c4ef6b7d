"""
Emulated-float64 IVP stepping on accelerators without native f64 speed.

The reference framework runs float64/complex128 end-to-end (SURVEY.md §7
hard part 7). On TPU, XLA's native F64 is software-emulated on the scalar
units and the MXU has no f64 path at all, so a straight f64 build loses
the batched-matmul design's entire advantage. `DDIVPRunner` wraps a built
`InitialValueSolver` and advances its state in double-double (f32 x 2)
arithmetic (libraries/doubledouble.py):

  * M/L matvecs and the residual matvec of the implicit solve run as
    Ozaki int8 slice matmuls on the MXU (exact int32 accumulation);
  * the implicit solve is an f32 factorization plus dd-residual
    iterative refinement sweeps (mixed-precision IR: f64-grade solutions
    for cond(A) well below 1/eps32). The f32 solves inside the sweeps are
    plain ones: where the solver's own class refines in float32 (a TPU's
    `BatchedInverseRefined` for 64-bit variables) the runner takes the
    stored inverse alone, one read of it a solve (`_inner_ops`);
  * the RHS expression tree is evaluated by a dd interpreter mirroring
    the Future.ev protocol: linear operators via their host descriptor
    matrices, Add / pointwise products elementwise, grid<->coeff
    transforms through each basis's MMT ("matrix" library) plan.

Selection: `InitialValueSolver` auto-wires a runner for float64 pencils
on a TPU backend under `[execution] EMULATED_F64 = auto`, falling back
to native XLA f64 when construction raises `DDUnsupportedError`
(non-dense pencil paths, RHS nodes outside the dd set — validated by an
abstract trace at construction). Multistep AND Runge-Kutta IMEX schemes
are covered; the dd interpreter handles linear operators (full/blocks
descriptor terms and tensor factors), Add, pointwise and dot products —
enough for Cartesian scalar/vector problems through full 2-D
Rayleigh-Benard (tests/test_ddstep.py tracks native f64 at ~1e-10).
`maybe_dd_runner(solver)` is the explicit hook with the same rules.
"""

import contextlib
import logging

import numpy as np
import jax
import jax.numpy as jnp
from jax.extend.core import Var

from ..libraries import doubledouble
from ..libraries.doubledouble import (
    DD, DEFAULT_SLICES, dd_from_f64, dd_to_f64, dd_split_host, dd_add,
    dd_sub, dd_neg, dd_mul, dd_mul_f32, dd_matmul, dd_slices_from_f64,
    dd_zeros)
from ..tools import tracing
from ..tools.jitlift import lifted_jit, device_constant, discovering
from ..tools.metrics import build_scope

logger = logging.getLogger(__name__)

__all__ = ["DDIVPRunner", "DDUnsupportedError", "maybe_dd_runner"]


class DDUnsupportedError(NotImplementedError):
    """Raised when an expression node has no double-double evaluation."""


def _dd_scalar(x):
    """Host float -> dd scalar constant (exact two-term f32 split)."""
    x = float(x)
    hi = np.float32(x)
    lo = np.float32(x - float(hi))
    return DD(jnp.float32(hi), jnp.float32(lo))


def _dd_vector(xs):
    """Host float sequence -> DD of f32 vectors (exact per-entry split);
    dynamic program inputs, one per-entry scalar via dd indexing."""
    return dd_from_f64(xs)


# ------------------------------------------------------------- dd kernels

class _HostConstCache:
    """Per-host-array caches keyed by object id, so repeated traces reuse
    one slice decomposition / dd split and the jitlift registry interns
    one copy. Entries are evicted when the SOURCE array is collected (a
    weakref finalizer) — holding it strongly would pin every pencil /
    transform matrix ever decomposed for the process lifetime."""

    def __init__(self):
        self.slices = {}
        self.pairs = {}

    def _register(self, store, key, M):
        import weakref
        try:
            weakref.finalize(M, store.pop, key, None)
        except TypeError:
            pass  # not weakref-able: entry lives as long as the process

    def matrix_slices(self, M):
        key = id(M)
        if key not in self.slices:
            with build_scope("dd_prepare"):
                A = M.toarray() if hasattr(M, "toarray") else np.asarray(M)
                self.slices[key] = dd_slices_from_f64(
                    np.asarray(A, dtype=np.float64), axis=-1)
            self._register(self.slices, key, M)
        return self.slices[key]

    def dd_pair(self, M):
        key = id(M)
        if key not in self.pairs:
            with build_scope("dd_prepare"):
                A = np.asarray(M.toarray() if hasattr(M, "toarray") else M,
                               dtype=np.float64)
                self.pairs[key] = dd_split_host(A)
            self._register(self.pairs, key, M)
        return self.pairs[key]


_consts = _HostConstCache()


def dd_apply_matrix(M, X, axis):
    """apply_matrix_jax mirror: contract host matrix M (m, k) with DD X
    along `axis` via the cached int8 slice decomposition."""
    planes_np, inv_np = _consts.matrix_slices(M)
    planes = device_constant(planes_np)
    inv = device_constant(inv_np)
    hi = jnp.moveaxis(X.hi, axis, -1)
    lo = jnp.moveaxis(X.lo, axis, -1)
    batch = hi.shape[:-1]
    k = hi.shape[-1]
    B = DD(hi.reshape(-1, k).T, lo.reshape(-1, k).T)        # (k, n)
    C = dd_matmul(None, B, a_planes=(planes, inv))           # (m, n)
    m = C.hi.shape[0]
    out_hi = jnp.moveaxis(C.hi.T.reshape(batch + (m,)), -1, axis)
    out_lo = jnp.moveaxis(C.lo.T.reshape(batch + (m,)), -1, axis)
    return DD(out_hi, out_lo)


def dd_apply_axis_blocks(blocks, X, axis):
    """apply_axis_blocks mirror: per-group (G, so, si) blocks along an
    axis of size G*si, in dd (blocks enter as exact f32-pair constants;
    si/so are small — Fourier derivative blocks are 2x2)."""
    bh_np, bl_np = _consts.dd_pair(blocks)
    bh = device_constant(bh_np)
    bl = device_constant(bl_np)
    G, so, si = bh_np.shape
    hi = jnp.moveaxis(X.hi, axis, -1)
    lo = jnp.moveaxis(X.lo, axis, -1)
    lead = hi.shape[:-1]
    hi = hi.reshape(lead + (G, si))
    lo = lo.reshape(lead + (G, si))
    outs = []
    for i in range(so):
        tot = None
        for j in range(si):
            b = DD(bh[:, i, j], bl[:, i, j])                 # (G,)
            term = dd_mul(DD(hi[..., j], lo[..., j]), b)
            tot = term if tot is None else dd_add(tot, term)
        outs.append(tot)
    out_hi = jnp.stack([o.hi for o in outs], axis=-1)        # (..., G, so)
    out_lo = jnp.stack([o.lo for o in outs], axis=-1)
    out_hi = out_hi.reshape(lead + (G * so,))
    out_lo = out_lo.reshape(lead + (G * so,))
    return DD(jnp.moveaxis(out_hi, -1, axis),
              jnp.moveaxis(out_lo, -1, axis))


def dd_apply_term(data, tensor_factor, axis_descrs, tshape_in, tshape_out):
    """apply_term mirror for the supported descriptor kinds."""
    out = data
    tdim_in = len(tshape_in)
    for axis, descr in enumerate(axis_descrs):
        if descr is None:
            continue
        kind = descr[0]
        if kind == "full":
            out = dd_apply_matrix(descr[1], out, tdim_in + axis)
        elif kind == "blocks":
            out = dd_apply_axis_blocks(descr[1], out, tdim_in + axis)
        else:
            raise DDUnsupportedError(
                f"dd evaluation of '{kind}' operator terms (curvilinear "
                "group stacks) is not supported.")
    if tensor_factor is not None:
        # (ncomp_out, ncomp_in) host factor on the flattened tensor axes;
        # small and exact in f64 value space
        from ..libraries.doubledouble import _to64, _from64
        factor = np.asarray(tensor_factor, dtype=np.float64)
        spatial = out.hi.shape[tdim_in:]
        nin = int(np.prod(tshape_in, dtype=int)) if tshape_in else 1
        v = _to64(out).reshape((nin,) + spatial)
        w = jnp.tensordot(jnp.asarray(factor), v, axes=(1, 0))
        return _from64(w.reshape(tuple(tshape_out) + spatial))
    if tuple(tshape_in) != tuple(tshape_out):
        raise DDUnsupportedError("dd tensor shape change")
    return out


# --------------------------------------------------------- dd transforms

def dd_transform_axis(basis, data, axis, scale, forward):
    """One-axis grid<->coeff dd transform through the basis's MMT plan."""
    plan = basis.transform_plan(scale, library="matrix")
    M = plan.forward_mat if forward else plan.backward_mat
    # the dd interpreter never calls the plan's own (labelled) methods
    with jax.named_scope(f"dedalus/transform/{type(basis).__name__}.dd."
                         + ("fwd" if forward else "bwd")):
        return dd_apply_matrix(M, data, axis)


def dd_to_layout(data, domain, scales, tdim, layout):
    """Full-domain dd transform walk (single-process; mirrors
    field.transform_to_grid/_to_coeff axis ordering)."""
    if layout == "g":
        for axis in range(domain.dim - 1, -1, -1):
            basis = domain.bases[axis]
            if basis is None:
                continue
            data = dd_transform_axis(basis, data, tdim + axis,
                                     scales[axis], forward=False)
    else:
        for axis in range(domain.dim):
            basis = domain.bases[axis]
            if basis is None:
                continue
            data = dd_transform_axis(basis, data, tdim + axis,
                                     scales[axis], forward=True)
    return data


# ------------------------------------------------------- dd tree evaluator

class DDEvalContext:
    """Substitutions (Field -> DD coeff data) and the per-trace memo."""

    def __init__(self, subs):
        self.subs = subs
        self.memo = {}

    def field_data(self, field, layout):
        key = (id(field), layout)
        if key in self.memo:
            return self.memo[key]
        if field in self.subs:
            coeff = self.subs[field]
        else:
            # non-variable input (parameter/forcing): exact host split
            hi, lo = dd_split_host(np.asarray(field.require_coeff_space()))
            coeff = DD(device_constant(hi), device_constant(lo))
        if layout == "c":
            out = coeff
        else:
            out = dd_to_layout(coeff, field.domain, field.domain.dealias,
                               field.tdim, "g")
        self.memo[key] = out
        return out


def dd_ev(node, ctx, layout):
    from .field import Field
    from .future import Future
    if isinstance(node, Field):
        return ctx.field_data(node, layout)
    if not isinstance(node, Future):     # plain number
        return node
    key = (id(node), layout)
    if key in ctx.memo:
        return ctx.memo[key]
    from .arithmetic import ScalarMultiply
    if isinstance(node, ScalarMultiply):
        # layout-agnostic (mirrors ScalarMultiply.ev): scale in the
        # requested layout, no extra transform roundtrip
        out = dd_mul(dd_ev(node.operand, ctx, layout),
                     _dd_scalar(node.scalar))
        ctx.memo[key] = out
        return out
    if layout == node.natural_layout:
        out = _dd_ev_impl(node, ctx)
    elif layout == "g":
        out = dd_to_layout(dd_ev(node, ctx, "c"), node.domain,
                           node.domain.dealias, node.tdim, "g")
    else:
        out = dd_to_layout(dd_ev(node, ctx, "g"), node.domain,
                           node.domain.dealias, node.tdim, "c")
    ctx.memo[key] = out
    return out


def _dd_ev_impl(node, ctx):
    from .arithmetic import Add, MultiplyFields
    from .field import Field
    from .future import Future
    from .operators import LinearOperator

    if isinstance(node, Add):
        total = None
        for a in node.args:
            if isinstance(a, (Field, Future)):
                d = dd_ev(a, ctx, "g")
            elif np.isscalar(a):
                d = dd_from_f64(np.float64(a))
            else:
                raise DDUnsupportedError(f"dd Add operand {a!r}")
            total = d if total is None else dd_add(total, d)
        return total

    if isinstance(node, MultiplyFields):
        a, b = node.args
        da = dd_ev(a, ctx, "g")
        db = dd_ev(b, ctx, "g")
        ta, tb = a.tdim, b.tdim
        sh = da.hi.shape[:ta] + (1,) * tb + da.hi.shape[ta:]
        return dd_mul(DD(da.hi.reshape(sh), da.lo.reshape(sh)), db)

    from .arithmetic import DotProduct
    if isinstance(node, DotProduct):
        # grid-space contraction over one tensor index; the contraction
        # dim is tiny (coordinate dimension), exact in f64 value space
        from ..libraries.doubledouble import _to64, _from64
        a, b = node.args
        da = dd_ev(a, ctx, "g")
        db = dd_ev(b, ctx, "g")
        l_sub, r_sub, o_sub = DotProduct.contraction_subscripts(
            a.tdim, b.tdim)
        return _from64(jnp.einsum(f"{l_sub},{r_sub}->{o_sub}",
                                  _to64(da), _to64(db)))

    if isinstance(node, LinearOperator):
        data = dd_ev(node.operand, ctx, "c")
        total = None
        for tensor_factor, axis_descrs in node.device_terms():
            term = dd_apply_term(data, tensor_factor, axis_descrs,
                                 node.operand.tshape, node.tshape)
            total = term if total is None else dd_add(total, term)
        return total

    # scalar multiples arrive as Multiply dispatch products; anything else
    # is out of the supported dd set
    raise DDUnsupportedError(
        f"dd evaluation of {type(node).__name__} nodes; supported: linear "
        "operators (full/blocks terms), Add, pointwise products.")


# --------------------------------------------------------------- runner

def _inner_ops(ops):
    """The float32 solver of the dd sweeps' inner solves, from the
    solver's own dense `ops`. The route never takes a REFINED class by
    default: the dd sweeps around each solve are the refinement, their
    residual is float64-grade, and float32 sweeps inside a float32 solve
    cannot pass cond * 2^-24, which the next dd sweep repeats properly
    (PERF.md, PR 36: the same 1e-12 after two dd sweeps either way, at 7
    reads of a (G, S, S) stack a solve against 1). So the class
    `_dense_matsolver` resolves for 64-bit variables on a TPU,
    `BatchedInverseRefined`, becomes the plain `BatchedInverse`. Any
    other class (LU on a CPU) is used as it is, and so is a refined
    class that a non-native `[precision]` plan asked for: that is the
    user's own statement."""
    from ..libraries.matsolvers import BatchedInverseRefined
    from ..libraries.pencilops import DenseOps
    cls = ops.solver_cls
    refined = isinstance(cls, type) and issubclass(cls, BatchedInverseRefined)
    if refined and ops._solve_plan.dtype == "native":
        return DenseOps("BatchedInverse", solve_plan=ops._solve_plan)
    return ops


def _stack_reads(jaxpr, stacks, times=1):
    """How often `jaxpr` reads the variables `stacks`: its equations that
    take one, those of a sub-program (a jit, a custom call, a loop's body)
    followed by position, a scan's body times its length."""
    n = 0
    for eqn in jaxpr.eqns:
        held = [i for i, v in enumerate(eqn.invars)
                if isinstance(v, Var) and v in stacks]
        if not held:
            continue
        subs = [getattr(p, "jaxpr", p) for p in eqn.params.values()
                if hasattr(p, "eqns") or hasattr(p, "jaxpr")]
        if not subs:
            n += times
        for sub in subs:
            # a sub-program's inputs are the equation's last ones
            off = len(eqn.invars) - len(sub.invars)
            n += _stack_reads(
                sub, {sub.invars[i - off] for i in held if i >= off},
                times * eqn.params.get("length", 1))
    return n


def _stack_reads_per_solve(ops, G, S):
    """Reads of a stored (G, S, S) stack by one float32 `ops.solve`,
    counted in its jaxpr from shapes alone: 1 for a stored inverse, 7
    for `BatchedInverseRefined` at its 3 sweeps, 2 for LU."""
    f32 = jnp.float32
    aux = jax.eval_shape(ops.factor, jax.ShapeDtypeStruct((G, S, S), f32))
    jaxpr = jax.make_jaxpr(ops.solve)(
        aux, jax.ShapeDtypeStruct((G, S), f32)).jaxpr
    return _stack_reads(jaxpr, {
        v for v, leaf in zip(jaxpr.invars, jax.tree.leaves(aux))
        if leaf.ndim == 3})


class DDIVPRunner:
    """Advance an InitialValueSolver's IVP in emulated f64 (see module
    docstring). Usage:

        solver = problem.build_solver(d3.SBDF2)
        runner = DDIVPRunner(solver)        # or maybe_dd_runner(solver)
        for _ in range(n):
            runner.step(dt)
        runner.push_state()                 # write dd state back to fields

    Supports MultistepIMEX and RungeKuttaIMEX schemes (the scheme class
    is taken from the solver's timestepper). The wrapped solver is left
    untouched except by push_state().

    The runner owns the float32 solver of its sweeps, `self.f32`
    (`_inner_ops`): `solver.ops` itself, or the plain stored inverse
    where `solver.ops` would refine in float32 by default. `counters()`
    names the class it met (`f32_solver`) and how many (G, S, S) float32
    stacks a step reads through it (`f32_stack_reads_per_step`).
    """

    def __init__(self, solver, refine=2):
        from .timesteppers import MultistepIMEX, RungeKuttaIMEX
        self.solver = solver
        self.refine = int(refine)
        self.slices = DEFAULT_SLICES
        # {int8_dots_per_step, plane_MB, f32_stack_reads_per_step} of the
        # first step program run
        self.program_counts = None
        # reads of a stored float32 stack by the inner solves traced so
        # far (a running tally, as doubledouble.plane_dots_traced)
        self.f32_reads_traced = 0
        ts = solver.timestepper
        if isinstance(ts, MultistepIMEX):
            self.kind = "multistep"
            self.steps = ts.steps
        elif isinstance(ts, RungeKuttaIMEX):
            self.kind = "rk"
            self.steps = 1
        else:
            raise DDUnsupportedError(
                "DDIVPRunner supports multistep and Runge-Kutta IMEX "
                f"schemes (got {type(ts).__name__}).")
        self.scheme = ts
        ops = solver.ops
        if getattr(ops, "kind", "dense") != "dense":
            raise DDUnsupportedError(
                "DDIVPRunner currently requires the dense pencil path "
                "(set MATRIX_SOLVER='dense' for emulated-f64 runs).")
        self.f32 = _inner_ops(ops)
        if self.f32 is not ops:
            logger.info(
                f"dd route: inner float32 solves by "
                f"{self.f32.solver_cls.__name__} in place of "
                f"{ops.solver_cls.__name__} (the dd sweeps refine)")
        # host f64 pencil matrices
        self.M_host = np.asarray(solver._matrices["M"], dtype=np.float64)
        self.L_host = np.asarray(solver._matrices["L"], dtype=np.float64)
        G, S = solver.pencil_shape
        self.shape = (G, S)
        self.mask_np = np.asarray(solver.valid_row_mask, dtype=np.float32)
        self.X = self._gather_dd()
        zero = (dd_zeros((self.steps, G, S)) if self.kind == "multistep"
                else None)
        self.F_hist = zero
        self.MX_hist = zero
        self.LX_hist = zero
        self.dt_hist = []
        self.iteration = 0
        self.sim_time = 0.0
        self._lhs_key = None
        self._lhs = None
        self._build_programs()

    # ------------------------------------------------------------ state io

    def _gather_dd(self):
        from .solvers import gather_state, state_key
        layout, variables = self.solver.layout, self.solver.variables
        his, los = {}, {}
        for v in variables:
            hi, lo = dd_split_host(np.asarray(v.require_coeff_space()))
            his[state_key(v)] = jnp.asarray(hi)
            los[state_key(v)] = jnp.asarray(lo)
        # gather_state is pure data movement: exact componentwise
        return DD(gather_state(layout, variables, his),
                  gather_state(layout, variables, los))

    def push_state(self):
        """Write the dd state back into the solver's fields (f64 host)."""
        from .solvers import scatter_state, state_key
        layout, variables = self.solver.layout, self.solver.variables
        his = scatter_state(layout, variables, self.X.hi)
        los = scatter_state(layout, variables, self.X.lo)
        for v in variables:
            data = (np.asarray(his[state_key(v)], dtype=np.float64)
                    + np.asarray(los[state_key(v)], dtype=np.float64))
            v.preset_coeff(jnp.asarray(data) if v.dtype == np.float64
                           else jnp.asarray(data, dtype=v.dtype))
            v.mark_modified()

    def state_f64(self):
        return dd_to_f64(self.X)

    def sync_state(self):
        """Re-gather the dd state from the solver's fields (call after
        setting initial conditions or editing fields when stepping the
        runner directly; solver.step() does this automatically via its
        dirty tracking)."""
        with tracing.span("dd/gather"):
            self.X = self._gather_dd()

    def reset_history(self, sim_time):
        """Restart the multistep ramp from `sim_time` with the current
        state (checkpoint restart / discontinuous state edit: the stored
        histories predate the new state; RK keeps no history)."""
        if self.kind == "multistep":
            G, S = self.shape
            zero = dd_zeros((self.steps, G, S))
            self.F_hist = zero
            self.MX_hist = zero
            self.LX_hist = zero
        self.dt_hist = []
        self.iteration = 0
        self.sim_time = float(sim_time)

    def _extras_dd(self):
        """Current dd data of the RHS's non-variable field inputs,
        version-cached (host split only when a field changed)."""
        out = []
        for f in self._extra_fields:
            cached = self._extra_cache.get(id(f))
            if cached is None or cached[0] != f._version:
                cached = (f._version,
                          dd_from_f64(np.asarray(f.require_coeff_space())))
                self._extra_cache[id(f)] = cached
            out.append(cached[1])
        return out

    # ------------------------------------------------------------ programs

    def _build_programs(self):
        solver = self.solver
        problem = solver.problem
        layout = solver.layout
        variables = solver.variables
        equations = solver.equations
        masks = solver._member_masks()
        time_field = problem.time
        from .field import Field as _Field
        from .future import Future as _Future
        from .solvers import scatter_state, state_key

        # non-variable fields feeding the RHS become dynamic inputs of the
        # step program (mirrors build_rhs_evaluator's extra_fields): baking
        # them as trace-time constants would silently freeze mid-run
        # updates to forcings/parameters
        extra = set()
        for eq in equations:
            for member, cond in eq["members"]:
                expr = member.get("F")
                if isinstance(expr, (_Field, _Future)):
                    extra |= expr.atoms(_Field)
        extra -= set(variables)
        if time_field is not None:
            extra.discard(time_field)
        self._extra_fields = sorted(extra, key=lambda f: (f.name or "", id(f)))
        self._extra_cache = {}

        def eval_F_dd(X, t, extra_dd):
            with jax.named_scope("dedalus/evaluator/dd.rhs"):
                return eval_F_inner(X, t, extra_dd)

        def eval_F_inner(X, t, extra_dd):
            arrays_hi = scatter_state(layout, variables, X.hi)
            arrays_lo = scatter_state(layout, variables, X.lo)
            subs = {v: DD(arrays_hi[state_key(v)], arrays_lo[state_key(v)])
                    for v in variables}
            subs.update(zip(self._extra_fields, extra_dd))
            if time_field is not None:
                dim = solver.dist.dim
                shape = (1,) * dim
                subs[time_field] = DD(
                    jnp.reshape(jnp.asarray(t.hi, jnp.float32), shape),
                    jnp.reshape(jnp.asarray(t.lo, jnp.float32), shape))
            ctx = DDEvalContext(subs)
            parts_hi, parts_lo = [], []
            for eq, eq_masks in zip(equations, masks):
                size = layout.slot_size(eq["domain"], eq["tensorsig"])
                total = None
                for (member, cond), mask in zip(eq["members"], eq_masks):
                    expr = member.get("F")
                    if expr is None:
                        continue
                    data = dd_ev(expr, ctx, "c")
                    part = DD(layout.gather(data.hi, eq["domain"],
                                            eq["tensorsig"]),
                              layout.gather(data.lo, eq["domain"],
                                            eq["tensorsig"]))
                    if mask is not None:
                        m = jnp.asarray(mask, jnp.float32)[:, None]
                        part = dd_mul_f32(part, m)
                    total = part if total is None else dd_add(total, part)
                if total is None:
                    z = jnp.zeros((layout.n_groups, size), jnp.float32)
                    total = DD(z, z)
                parts_hi.append(total.hi)
                parts_lo.append(total.lo)
            F = DD(jnp.concatenate(parts_hi, axis=1),
                   jnp.concatenate(parts_lo, axis=1))
            return dd_mul_f32(F, device_constant(self.mask_np))

        f32 = self.f32
        reads_per_solve = _stack_reads_per_solve(f32, *self.shape)

        def solve32(aux32, rhs):
            """One float32 solve, its stack reads tallied as traced."""
            if not discovering():
                self.f32_reads_traced += reads_per_solve
            return f32.solve(aux32, rhs)

        M_planes = _consts.matrix_slices(self.M_host)
        L_planes = _consts.matrix_slices(self.L_host)
        # the float32 pairs `build_A_dd` takes, split here and not inside
        # the factor program's first trace: host work of the build
        _consts.dd_pair(self.M_host)
        _consts.dd_pair(self.L_host)

        def matvec(a_planes, X):
            B = DD(X.hi[..., None], X.lo[..., None])        # (G, S, 1)
            C = dd_matmul(None, B, a_planes=a_planes)
            return DD(C.hi[..., 0], C.lo[..., 0])

        def mx(planes_np, X):
            with jax.named_scope("dedalus/matsolve/dd.matvec"):
                return matvec((device_constant(planes_np[0]),
                               device_constant(planes_np[1])), X)

        # dd A = a0*M + b0*L built from exact dd pairs of M and L; the
        # coefficients are dd SCALARS (dynamic inputs — one compiled
        # factorization serves every dt) — rounding a0 = 1.5/dt to one
        # f32 perturbs the scheme at ~1e-7 relative per step (observed:
        # a 4e-8 trajectory error floor with non-binary dt)
        def build_A_dd(a0, b0):
            Mh, Mlo = _consts.dd_pair(self.M_host)
            Lh, Llo = _consts.dd_pair(self.L_host)
            Mdd = DD(device_constant(Mh), device_constant(Mlo))
            Ldd = DD(device_constant(Lh), device_constant(Llo))
            return dd_add(dd_mul(Mdd, a0), dd_mul(Ldd, b0))

        def factor(a0, b0):
            # the plane slicing of A; the f32 factorization inside keeps
            # its own scope (dense.factor)
            with jax.named_scope("dedalus/matsolve/dd.factor"):
                A = build_A_dd(a0, b0)
                planes, inv = doubledouble._dd_slices(
                    A, axis=-1, slices=self.slices)
                aux32 = f32.factor(A.hi)
            return {"planes": planes, "inv": inv, "aux32": aux32}

        def solve_ir(lhs, rhs, refine=self.refine):
            """f32 solve + dd-residual iterative refinement: `refine`
            sweeps, each a dd.residual (A x in int8 planes) and one plain
            f32 solve of what is left. The f32 solves keep their own
            scopes (dense.solve and the solver class's) and refine
            nothing themselves (`_inner_ops`)."""
            with jax.named_scope("dedalus/matsolve/dd.refine"):
                x32 = solve32(lhs["aux32"], rhs.hi)
                x = DD(x32, jnp.zeros_like(x32))
                for _ in range(refine):
                    with jax.named_scope("dedalus/matsolve/dd.residual"):
                        Ax = matvec((lhs["planes"], lhs["inv"]), x)
                    r = dd_sub(rhs, Ax)
                    dx = solve32(lhs["aux32"], r.hi)
                    x = dd_add(x, DD(dx, jnp.zeros_like(dx)))
            return x

        def step_body(X, t, F_hist, MX_hist, LX_hist, lhs, a, b, c,
                      extra_dd):
            # histories enter with slot 0 = current step's evaluations.
            # a, b, c are DD coefficient VECTORS (dynamic inputs): one
            # compiled program serves every startup order and timestep —
            # static coefficients would recompile the whole step on any
            # dt change (review finding; native path is dynamic too)
            Fn = eval_F_dd(X, t, extra_dd)
            MXn = mx(M_planes, X)
            LXn = mx(L_planes, X)
            roll = lambda H, new: DD(
                jnp.concatenate([new.hi[None], H.hi[:-1]]),
                jnp.concatenate([new.lo[None], H.lo[:-1]]))
            F_hist = roll(F_hist, Fn)
            MX_hist = roll(MX_hist, MXn)
            LX_hist = roll(LX_hist, LXn)
            RHS = None
            s = self.steps
            with jax.named_scope("dedalus/step/dd.combine"):
                for j in range(s):
                    terms = [dd_mul(F_hist[j], c[j]),
                             dd_mul(MX_hist[j], dd_neg(a[j + 1])),
                             dd_mul(LX_hist[j], dd_neg(b[j + 1]))]
                    for term in terms:
                        RHS = term if RHS is None else dd_add(RHS, term)
            Xn = solve_ir(lhs, RHS)
            return Xn, F_hist, MX_hist, LX_hist

        def rk_step_body(X, t, dt, lhs_list, extra_dd):
            """One IMEX Runge-Kutta step in dd (mirrors the native
            RungeKuttaIMEX.step_body; tableau entries are exact dd
            constants closed over — they never change). lhs_list holds
            one factored LHS per stage (shared auxes alias upstream)."""
            scheme = self.scheme
            s = scheme.stages
            A = np.asarray(scheme.A, dtype=np.float64)
            H = np.asarray(scheme.H, dtype=np.float64)
            cvec = np.asarray(scheme.c, dtype=np.float64)
            MX0 = mx(M_planes, X)
            Fs, LXs = [], []
            Xi = X
            for i in range(1, s + 1):
                ti = dd_add(t, dd_mul(dt, _dd_scalar(cvec[i - 1])))
                # L X_j only where a later stage reads it (a zero column
                # of H below the diagonal: RK222's first)
                LXs.append(mx(L_planes, Xi) if H[i:, i - 1].any() else None)
                Fs.append(eval_F_dd(Xi, ti, extra_dd))
                RHS = MX0
                with jax.named_scope("dedalus/step/dd.combine"):
                    for j in range(i):
                        if A[i, j] != 0.0:
                            RHS = dd_add(RHS, dd_mul(
                                dd_mul(dt, _dd_scalar(A[i, j])), Fs[j]))
                        if H[i, j] != 0.0:
                            RHS = dd_sub(RHS, dd_mul(
                                dd_mul(dt, _dd_scalar(H[i, j])), LXs[j]))
                Xi = solve_ir(lhs_list[i - 1], RHS)
            return Xi

        def rk_factor(dts):
            """One factored LHS per UNIQUE implicit diagonal (dts: dd
            scalars dt*H[i,i] per unique diagonal)."""
            one = _dd_scalar(1.0)
            return [factor(one, dth) for dth in dts]

        def step_n_body(X, t, F_hist, MX_hist, LX_hist, lhs, a, b, c,
                        extra_dd, dt_dd, n):
            """n constant-dt multistep steps in ONE lax.scan dispatch
            (post-ramp: coefficients are scan-invariant)."""
            def body(carry, _):
                Xc, tc, F, MX, LX = carry
                Xn, F2, MX2, LX2 = step_body(Xc, tc, F, MX, LX, lhs,
                                             a, b, c, extra_dd)
                return (Xn, dd_add(tc, dt_dd), F2, MX2, LX2), None
            carry, _ = jax.lax.scan(
                body, (X, t, F_hist, MX_hist, LX_hist), None, length=n)
            return carry

        def rk_step_n_body(X, t, dt, lhs_list, extra_dd, n):
            def body(carry, _):
                Xc, tc = carry
                Xn = rk_step_body(Xc, tc, dt, lhs_list, extra_dd)
                return (Xn, dd_add(tc, dt)), None
            carry, _ = jax.lax.scan(body, (X, t), None, length=n)
            return carry

        self._factor = lifted_jit(factor)
        # the refined solve alone, after any number of sweeps: what the
        # accuracy checks call (tests/test_ddstep.py), no step does
        self._solve_ir = lifted_jit(solve_ir, static_argnums=(2,))
        self._step = lifted_jit(step_body)
        self._step_n = lifted_jit(step_n_body, static_argnums=(11,))
        self._rk_factor = lifted_jit(rk_factor)
        self._rk_step = lifted_jit(rk_step_body)
        self._rk_step_n = lifted_jit(rk_step_n_body, static_argnums=(5,))
        # validate the RHS tree's dd support NOW (abstract trace): an
        # unsupported node must surface at construction, where the
        # solver's auto-wiring can fall back to native f64 — not at the
        # first step's trace
        jax.eval_shape(eval_F_dd, self.X,
                       DD(jnp.float32(0.0), jnp.float32(0.0)),
                       self._extras_dd())

    # -------------------------------------------------------------- stepping

    def _refactor(self, key, dt, factor):
        """`self._lhs = factor()` unless `key` is the one held: the one
        place the dd route refactors (an f32 factorization and a plane
        slicing of A), inside the `step/factor` span. As on the float32
        route (timesteppers._ensure_lhs) the run's first factorization is
        cold start: waited for and booked as the build's `factor` phase."""
        if key == self._lhs_key:
            return
        first = self._lhs_key is None
        booked = self.solver.build_phases.scope("factor") if first \
            else contextlib.nullcontext()
        with tracing.span("step/factor", {"dt": float(dt)}), booked:
            self._lhs = factor()
            self._lhs_key = key
            if first:
                jax.block_until_ready(self._lhs)  # dedalus-lint: disable=DTL001

    def _launch(self, program, *args):
        """One step program on the device. The first one to run is
        counted while it is traced: its int8 plane products (a scan block
        traces one step) and the int8 planes it takes as arguments, the
        lifted constants and the factored A among them."""
        if self.program_counts is not None:
            return program(*args)
        dots_before = doubledouble.plane_dots_traced
        reads_before = self.f32_reads_traced
        out = program(*args)
        planes = {id(a): a.nbytes
                  for a in jax.tree.leaves((args, program.constants()))
                  if getattr(a, "dtype", None) == np.int8}
        self.program_counts = {
            "int8_dots_per_step":
                doubledouble.plane_dots_traced - dots_before,
            "plane_MB": round(sum(planes.values()) / 1e6, 1),
            "f32_stack_reads_per_step":
                self.f32_reads_traced - reads_before}
        return out

    def counters(self):
        """What `build_phases.record()` says of this route."""
        return dict({"slices": self.slices, "refine": self.refine,
                     "f32_solver": self.f32.solver_cls.__name__},
                    **(self.program_counts or {}))

    def _lhs_for(self, a0, b0, dt):
        """Factored LHS for a0*M + b0*L, cached on the rounded-coefficient
        key (native pattern, timesteppers.py: float noise in recomputed
        coefficients must not trigger spurious refactors)."""
        key = (round(float(a0), 14), round(float(b0), 14))
        self._refactor(key, dt, lambda: self._factor(_dd_scalar(a0),
                                                     _dd_scalar(b0)))
        return self._lhs

    def _t_dd(self):
        """Current sim_time as an exact dd scalar."""
        return DD(jnp.float32(self.sim_time),
                  jnp.float32(self.sim_time
                              - float(np.float32(self.sim_time))))

    def step(self, dt):
        dt = float(dt)
        if not np.isfinite(dt):
            raise ValueError("Invalid timestep.")
        if self.kind == "rk":
            return self._rk_advance(dt)
        self.dt_hist = ([dt] + self.dt_hist)[: self.steps]
        order = min(self.iteration + 1, self.steps)
        a, b, c = self.scheme.compute_coefficients(self.dt_hist, order)
        # startup ramp returns order-length arrays; pad to the full
        # stencil so the (static) history loop bounds stay fixed
        s = self.steps
        a = np.concatenate([np.asarray(a, float), np.zeros(s + 1 - len(a))])
        b = np.concatenate([np.asarray(b, float), np.zeros(s + 1 - len(b))])
        c = np.concatenate([np.asarray(c, float), np.zeros(s - len(c))])
        lhs = self._lhs_for(a[0], b[0], dt)
        self.X, self.F_hist, self.MX_hist, self.LX_hist = self._launch(
            self._step, self.X, self._t_dd(), self.F_hist, self.MX_hist,
            self.LX_hist, lhs, _dd_vector(a), _dd_vector(b), _dd_vector(c),
            self._extras_dd())
        self.sim_time += dt
        self.iteration += 1

    def step_many(self, n, dt):
        """Advance n constant-dt steps with ONE device dispatch per block
        (lax.scan; small problems are host-latency bound at one dispatch
        per step). Multistep startup-ramp steps run individually first."""
        n = int(n)
        dt = float(dt)
        if not np.isfinite(dt):
            raise ValueError("Invalid timestep.")
        if n <= 0:
            return
        if self.kind == "rk":
            lhs_list, t_dd = self._rk_prepare(dt)
            self.X, _ = self._launch(
                self._rk_step_n, self.X, t_dd, _dd_scalar(dt), lhs_list,
                self._extras_dd(), n)
            self.sim_time += n * dt
            self.iteration += n
            return
        # ramp to steady order, then scan
        while n > 0 and (self.iteration < self.steps
                         or self.dt_hist != [dt] * self.steps):
            self.step(dt)
            n -= 1
        if n <= 0:
            return
        a, b, c = self.scheme.compute_coefficients([dt] * self.steps,
                                                   self.steps)
        lhs = self._lhs_for(a[0], b[0], dt)
        carry = self._launch(
            self._step_n, self.X, self._t_dd(), self.F_hist, self.MX_hist,
            self.LX_hist, lhs, _dd_vector(np.asarray(a, float)),
            _dd_vector(np.asarray(b, float)),
            _dd_vector(np.asarray(c, float)), self._extras_dd(),
            _dd_scalar(dt), n)
        self.X, _, self.F_hist, self.MX_hist, self.LX_hist = carry
        self.sim_time += n * dt
        self.iteration += n

    def _rk_prepare(self, dt):
        scheme = self.scheme
        H_diag = [float(scheme.H[i, i]) for i in range(1, scheme.stages + 1)]
        uniq = sorted(set(H_diag))
        self._refactor(("rk", round(dt, 14)), dt, lambda: self._rk_factor(
            [_dd_scalar(dt * h) for h in uniq]))
        lhs_list = [self._lhs[uniq.index(h)] for h in H_diag]
        return lhs_list, self._t_dd()

    def _rk_advance(self, dt):
        lhs_list, t_dd = self._rk_prepare(dt)
        self.X = self._launch(self._rk_step, self.X, t_dd, _dd_scalar(dt),
                              lhs_list, self._extras_dd())
        self.sim_time += dt
        self.iteration += 1


def maybe_dd_runner(solver):
    """The dtype=np.float64-on-accelerator selection hook: the solver's
    auto-wired runner (InitialValueSolver constructs one when the backend
    is a TPU and [execution] EMULATED_F64 = auto), or a fresh DDIVPRunner
    under the same conditions, else None (including EMULATED_F64 = never
    and problems outside the dd-supported set)."""
    from ..tools.config import config
    existing = getattr(solver, "_dd", None)
    if existing is not None:
        return existing
    if config["execution"].get("EMULATED_F64", "auto").lower() == "never":
        return None
    if (np.dtype(solver.pencil_dtype) == np.dtype(np.float64)
            and jax.default_backend() == "tpu"):
        try:
            return DDIVPRunner(solver)
        except DDUnsupportedError:
            return None
    return None

"""
Deferred-evaluation expression nodes (reference: dedalus/core/future.py).

TPU-native redesign: instead of the reference's per-step interpreted
`evaluate()` walks with layout oscillation (core/evaluator.py:94-148), each
node implements `ev(ctx, layout)` — a pure jnp computation memoized per
(node, layout) within one trace. Whole expression trees therefore compile
into single XLA programs; duplicated transforms are shared via the memo and
XLA CSE.

Layout protocol: 'c' = full coefficient space (in the node's output bases,
including Jacobi derivative levels), 'g' = full grid space at dealias scales.
"""

import logging

import numpy as np
import jax
import jax.numpy as jnp

from .field import Operand, Field, transform_to_coeff, transform_to_grid

logger = logging.getLogger(__name__)


class CompiledWithFallback:
    """
    One jit-compiled evaluation over Field-atom inputs with a permanent
    eager fallback: untraceable user callbacks (GeneralFunction host code,
    backends without host callbacks) fail in arbitrary ways on the first
    compiled call, after which evaluation stays eager. Shared by
    Future.evaluate and the output handlers (evaluator.evaluate_tasks).
    """

    def __init__(self, fields, fn, eager, describe):
        from ..tools.jitlift import lifted_jit
        self.fields = fields
        self.fn = lifted_jit(fn)
        self.eager = eager
        self.describe = describe
        # "compiled" until the permanent fallback, "eager" after
        self.mode = "compiled"

    def __call__(self):
        if self.mode == "compiled":
            try:
                return self.fn([f.coeff_data() for f in self.fields])
            except Exception as exc:
                # once per instance, and loud: every later call walks the
                # expression op by op from the host
                logger.warning(f"{self.describe}: compiled evaluation "
                               f"failed ({exc!r}); falling back to eager "
                               "permanently.")
                self.mode = "eager"
        return self.eager()


class EvalContext:
    """Carries substitutions (Field -> traced coeff array) and the memo.
    `fusion` (set by the IVP's RHS evaluator) carries the solver's
    FusedEvalPlan so LinearOperator grid evaluations can route through
    precomposed composite GEMMs (core/fusedstep.py); None = generic."""

    fusion = None

    def __init__(self, subs=None):
        self.subs = subs or {}
        self.memo = {}

    def field_data(self, field, layout):
        key = (id(field), layout)
        if key in self.memo:
            return self.memo[key]
        if field in self.subs:
            coeff = self.subs[field]
        else:
            coeff = field.coeff_data()
        if layout == "c":
            out = coeff
        else:
            out = transform_to_grid(coeff, field.domain, field.domain.dealias,
                                    field.tdim, tensorsig=field.tensorsig)
        self.memo[key] = out
        return out


def ev(node, ctx, layout):
    """Evaluate an operand (Field, Future, or scalar) in the given layout."""
    if isinstance(node, Field):
        return ctx.field_data(node, layout)
    if isinstance(node, Future):
        return node.ev(ctx, layout)
    # plain number
    return node


class Future(Operand):
    """Expression-tree node base (reference: core/future.py:22 Future)."""

    name = "Future"
    natural_layout = "g"

    def __init__(self, *args):
        self.args = list(args)
        self.dist = self._find_dist(args)
        self._build_metadata()

    @staticmethod
    def _find_dist(args):
        for arg in args:
            if isinstance(arg, (Field, Future)):
                return arg.dist
        raise ValueError("Expression has no field operands.")

    def _build_metadata(self):
        """Subclasses set self.domain, self.tensorsig, self.dtype."""
        raise NotImplementedError

    @property
    def tshape(self):
        return tuple(cs.dim for cs in self.tensorsig)

    @property
    def tdim(self):
        return len(self.tensorsig)

    def __repr__(self):
        argstr = ", ".join(map(str, self.args))
        return f"{self.name}({argstr})"

    __str__ = __repr__

    # ------------------------------------------------------------ evaluation

    def ev(self, ctx, layout):
        key = (id(self), layout)
        if key in ctx.memo:
            return ctx.memo[key]
        if layout == self.natural_layout:
            out = self.ev_impl(ctx)
        elif layout == "g":
            out = transform_to_grid(self.ev(ctx, "c"), self.domain,
                                    self.domain.dealias, self.tdim,
                                    tensorsig=self.tensorsig)
        else:
            out = transform_to_coeff(self.ev(ctx, "g"), self.domain,
                                     self.domain.dealias, self.tdim,
                                     tensorsig=self.tensorsig)
        ctx.memo[key] = out
        return out

    def ev_impl(self, ctx):
        raise NotImplementedError

    def evaluate(self):
        """
        Host-facing evaluation: returns a new Field with this node's data.

        The whole expression tree compiles into one cached XLA program per
        node, with the current data of every Field atom passed as an input
        (so repeated evaluation picks up field updates without retracing).
        Nodes whose ev_impl cannot trace (e.g. a GeneralFunction running
        host code) fall back to eager evaluation permanently.
        """
        runner = getattr(self, "_evaluate_cache", None)
        if runner is None:
            fields = sorted(self.atoms(Field),
                            key=lambda f: (f.name or "", id(f)))

            def fn(arrays):
                ctx = EvalContext(dict(zip(fields, arrays)))
                return self.ev(ctx, "c")

            runner = self._evaluate_cache = CompiledWithFallback(
                fields, fn, lambda: self.ev(EvalContext(), "c"), repr(self))
        data = runner()
        out = Field(self.dist, bases=self.domain.bases, tensorsig=self.tensorsig,
                    dtype=self.dtype)
        out.preset_coeff(jnp.asarray(data))
        return out

    # --------------------------------------------------------- symbolic API

    def operand_args(self):
        return [a for a in self.args if isinstance(a, (Field, Future))]

    def atoms(self, *types):
        out = set()
        if not types or isinstance(self, types):
            out.add(self)
        for arg in self.operand_args():
            if isinstance(arg, Future):
                out |= arg.atoms(*types)
            elif not types or isinstance(arg, types):
                out.add(arg)
        return out

    def has(self, *operands):
        for op in operands:
            if self is op:
                return True
            if isinstance(op, type) and isinstance(self, op):
                return True
        return any(isinstance(a, (Field, Future)) and _has(a, operands)
                   for a in self.args)

    def replace(self, old, new):
        if self is old:
            return new
        if isinstance(old, type) and isinstance(self, old):
            return new
        new_args = [a.replace(old, new) if isinstance(a, (Field, Future)) else a
                    for a in self.args]
        return self.rebuild(new_args)

    def rebuild(self, new_args):
        return type(self)(*new_args)

    def frechet_differential(self, variables, perturbations):
        """
        Symbolic derivative d/de [self with vars -> vars + e*perts] at e=0
        (reference: core/field.py:259). Linear nodes: differential passes
        through; nonlinear nodes override.
        """
        out = 0
        for i, arg in enumerate(self.args):
            if isinstance(arg, (Field, Future)):
                d_arg = arg.frechet_differential(variables, perturbations)
                if not (np.isscalar(d_arg) and d_arg == 0):
                    new_args = list(self.args)
                    new_args[i] = d_arg
                    out = out + self.rebuild(new_args)
        return out

    # -------------------------------------------------- matrix construction

    def expression_matrices(self, subproblem, vars, **kw):
        """Sparse matrices mapping each var's pencil to this node's pencil
        (reference: core/operators.py:739 expression_matrices)."""
        raise NotImplementedError(f"{type(self).__name__} has no matrix form.")


def _has(operand, operands):
    if isinstance(operand, Future):
        return operand.has(*operands)
    return any(operand is op for op in operands
               if not isinstance(op, type))

"""
Batched pencil matrix solvers (reference: dedalus/libraries/matsolvers.py).

The reference solves each pencil serially with SuperLU/UMFPACK on CPU
(libraries/matsolvers.py:71-285). Here the pencil index is a batch
dimension: factorizations and solves are batched dense LU on device (MXU),
with a banded/block-tridiagonal path as the large-N perf option.

Functional API so factorizations flow through jit as pytrees:
    aux = Solver.factor(matrices)   # (G, S, S) -> pytree of arrays
    x   = Solver.solve(aux, rhs)    # (G, S) -> (G, S)
"""

import jax
import jax.numpy as jnp
import jax.scipy.linalg as jsl

from ..tools.metrics import scoped as _scoped

matsolvers = {}


def add_solver(cls):
    """Register a solver class by lowercase name (reference:
    libraries/matsolvers.py:11 add_solver), phase-labeling its factor/solve
    entry points for profiler traces."""
    for meth in ("factor", "solve", "solve_multi"):
        raw = cls.__dict__.get(meth)
        label = f"dedalus/matsolve/{cls.__name__}.{meth}"
        if isinstance(raw, staticmethod):
            setattr(cls, meth, staticmethod(_scoped(raw.__func__, label)))
        elif isinstance(raw, classmethod):
            setattr(cls, meth, classmethod(_scoped(raw.__func__, label)))
    matsolvers[cls.__name__.lower()] = cls
    return cls


def batched_matvec(A, x):
    """(G, S, S) . (G, S) -> (G, S) in the operands' own dtype, said as
    an elementwise multiply and a sum over the contracted axis, not as a
    dot. A matrix-vector product reads every entry of A once and has no
    reuse to offer the MXU. The TPU keeps a (G, S, S) stack pencil-minor
    (`{0,2,1:T(8,128)}`: the group axis in the lanes), a dot wants it
    `{2,1,0}`, and layout assignment then copies the whole stack in
    front of the dot on EVERY call: at RB 256x64 (G=128, S=526, 142 MB a
    stack) 0.47 ms each, `unscoped/copy.605/641/643/653` in the single
    step and `copy.1214` in the scan body (PERF_LEDGER.jsonl, PR 30:
    56% and 27% of `device_ms_per_step`). The multiply-reduce is what
    XLA's own algebraic simplifier turned most of these dots into; it
    reads the stack as it lies. Same arithmetic: f32 products summed in
    f32, where the dot at `highest` emulates f32 in six bf16 passes.
    Still said as dots: `BatchedInverseRefined`'s four `einsum`s. The
    float64 route's sweeps ran them until PR 36 (42 stack reads a step
    of rb256x64-f64) and take the plain `BatchedInverse` now
    (core/ddstep._inner_ops); what is left to them, XLA's software
    float64 (`EMULATED_F64 = never`) and the `[precision]` ladder, no
    cell runs."""
    return jnp.sum(A * x[:, None, :], axis=-1)


@add_solver
class BatchedLUFactorized:
    """Batched dense LU with partial pivoting (default; the TPU analogue of
    the reference's SuperluColamdFactorizedTranspose default)."""

    @staticmethod
    def factor(matrices):
        return jsl.lu_factor(matrices)

    @staticmethod
    def solve(aux, rhs):
        return jsl.lu_solve(aux, rhs[..., None])[..., 0]

    @staticmethod
    def solve_multi(aux, rhs):
        return jsl.lu_solve(aux, rhs)


@add_solver
class BatchedInverse:
    """Precomputed batched inverse: each solve is one batched product, one
    read of the stored inverse (reference SparseInverse/DenseInverse,
    libraries/matsolvers.py:223). Fastest per-step for moderate S;
    factorization cost is ~3x LU."""

    @staticmethod
    def factor(matrices):
        return jnp.linalg.inv(matrices)

    @staticmethod
    def solve(inv, rhs):
        return batched_matvec(inv, rhs)

    @staticmethod
    def solve_multi(inv, rhs):
        return jnp.matmul(inv, rhs)


@add_solver
class BatchedInverseRefined:
    """
    Mixed-precision solver for 64-bit problems on TPU: TPU LuDecomposition
    only implements F32/C64, so the inverse is computed in the low dtype
    and each solve is polished by iterative refinement with 64-bit
    residual matvecs (supported via emulation). The sweep count and the
    residual tolerance are CLASS attributes bound per solver build
    (`refined_ladder` below / `get_solver`) from the `[precision]` config
    — resolved at build time, never read inside traced code — and the
    refinement runs as a fixed-trip `lax.fori_loop` with
    tolerance-masked updates, so programs stay retrace-free while
    converged groups freeze. `residual()` is the telemetry probe
    (achieved relative residual per group).
    """

    iterations = 3        # overridden per build via refined_ladder()
    tol = 0.0             # 0: apply every sweep (the legacy behavior)
    low_name = "f32"      # 'f32' or 'bf16' (libraries/solvecomp.py)

    @classmethod
    def _low(cls, dtype):
        from .solvecomp import low_dtype
        return low_dtype(cls.low_name, dtype)

    @classmethod
    def factor(cls, matrices):
        inv_low = jnp.linalg.inv(matrices.astype(cls._low(matrices.dtype)))
        return (matrices, inv_low)

    @classmethod
    def solve(cls, aux, rhs):
        A, inv_low = aux
        low = cls._low(rhs.dtype)
        x = jnp.einsum("gij,gj->gi", inv_low,
                       rhs.astype(low)).astype(rhs.dtype)
        tol = cls.tol

        def sweep(_, x):
            r = rhs - jnp.einsum("gij,gj->gi", A, x)
            dx = jnp.einsum("gij,gj->gi", inv_low,
                            r.astype(low)).astype(rhs.dtype)
            if tol > 0.0:
                rn = jnp.max(jnp.abs(r), axis=-1, keepdims=True)
                bn = jnp.max(jnp.abs(rhs), axis=-1, keepdims=True)
                return jnp.where(rn > tol * bn, x + dx, x)
            return x + dx

        if cls.iterations > 0:
            # static bounds: lowers as a fixed-length loop (retrace-free
            # and reverse-mode differentiable through the adjoint funnel)
            x = jax.lax.fori_loop(0, cls.iterations, sweep, x)
        return x

    @classmethod
    def residual(cls, aux, x, rhs):
        """Achieved relative residual per group (device values; the
        `precision` telemetry/benchmark probe — off the step path)."""
        A, _ = aux
        r = rhs - jnp.einsum("gij,gj->gi", A, x)
        bn = jnp.max(jnp.abs(rhs), axis=-1)
        return jnp.max(jnp.abs(r), axis=-1) / jnp.where(bn == 0, 1.0, bn)


def refined_ladder(plan):
    """A per-build BatchedInverseRefined subclass bound to the resolved
    `[precision]` plan (libraries/solvecomp.SolvePlan): the dense arm of
    the precision ladder. Class attributes carry the schedule so the
    traced factor/solve bodies never read config (DTL008)."""
    low = plan.dtype if plan.dtype != "native" else "f32"
    sweeps = plan.sweeps if plan.sweeps is not None \
        else BatchedInverseRefined.iterations
    return type("BatchedInverseLadder", (BatchedInverseRefined,),
                {"iterations": int(sweeps), "tol": float(plan.tol),
                 "low_name": low})


@add_solver
class BatchedDenseSolve:
    """Factor-per-solve (reference ScipyDenseLU analogue); aux = matrices."""

    @staticmethod
    def factor(matrices):
        return matrices

    @staticmethod
    def solve(matrices, rhs):
        return jnp.linalg.solve(matrices, rhs[..., None])[..., 0]

    @staticmethod
    def solve_multi(matrices, rhs):
        return jnp.linalg.solve(matrices, rhs)


@add_solver
class DummySolver:
    """Testing solver returning zeros (reference: libraries/matsolvers.py:32)."""

    @staticmethod
    def factor(matrices):
        return matrices

    @staticmethod
    def solve(aux, rhs):
        return jnp.zeros_like(rhs)


def get_solver(spec):
    if spec is None:
        spec = "BatchedLUFactorized"
    cls = matsolvers[spec.lower()] if isinstance(spec, str) else spec
    if cls is BatchedInverseRefined:
        # bind the [precision] refinement schedule at build time (the
        # sweep count used to be a hardcoded class attribute): get_solver
        # runs in ops construction, before any program traces
        from .solvecomp import resolve_solve_plan
        return refined_ladder(resolve_solve_plan())
    return cls

"""
Fused spectral step: transform -> solve -> transform without intermediate
round-trips (ROADMAP item 2; TurboFNO in PAPERS.md shows the shape of the
win for FFT->GEMM->iFFT chains).

Profile-driven design. The PR-1 phase timers on the CPU headline rank the
step's traffic (rb256x64, RK222, banded, f64, 2 host cores):

    matsolve   141.7 ms/stage   (~91% of the step)
    rhs_eval    16.3 ms/stage   (transforms 4.7 ms of it)

and inside matsolve, the blocked banded substitution dominates: each of
the NB sequential scan steps dispatches a batched `solve_triangular`
custom call that costs ~19x an equivalent batched matmul at these shapes
((G, q, q) x (G, q, 1): 876 us vs 47 us measured). The highest-traffic
"pair" is therefore the RHS-assembly GEMM feeding the banded
substitution, not the transform pair — so the measured default fuses the
solve side, and the MMT composition targets the accelerator backends
where matmul transforms are the architecture win (the same reasoning
that picked BatchedInverse for the TPU dense path).

Fusion layers (config section [fusion], resolved once per solver build):

  FUSED_SOLVE     — at `factor_lincomb` time the banded panel factors are
                    precomposed into explicit inverses (L1^-1, U11^-1,
                    last-block A^-1, Woodbury capacitance^-1), so every
                    substitution scan step and the Woodbury correction
                    run as batched GEMMs instead of triangular-solve /
                    pivoted-LU custom calls (libraries/pencilops.py).
                    Factor-time cost, amortized over the step loop; LBVP/
                    NLBVP/EVP `factor()` keeps the backward-stable
                    substitution (one factor, one solve — nothing to
                    amortize).
  FUSED_MATVEC    — M@X and L@X in one pass: shared permute/pad, and one
                    scan over row tiles whose body reads its tile of both
                    band stores against one window of the padded operand
                    (`BandedOps.matvec_pair`, `_band_mv`); row by row the
                    float operations of the separate matvecs.
  FUSED_TRANSFORMS— RHS linear-operator chains precomposed host-side into
                    single batched GEMMs: dealias-scaled backward MMT @
                    (conversion/derivative matrices) on the coupled
                    Jacobi axis, so `grad`/`lap`/`Lift` chains evaluate
                    grid-ward with no intermediate coefficient layout
                    (FusedEvalPlan below; composites are cached through
                    the PR-5 assembly cache under a fusion-keyed entry).
  DONATE_STEP     — the multistep fused step program donates its history
                    buffers (F/MX/LX) so XLA writes the rolled histories
                    in place. Consumers that hold cross-step references
                    (resilience snapshot ring, async checkpoint capture,
                    phase-probe caches) copy when
                    `timestepper.donates_histories` is set.

Every fused solve still routes through `pencilops.AdjointSolveOps.solve`
(the custom_vjp funnel), so `DifferentiableIVP` adjoints keep working;
the composite GEMMs are plain jnp matmuls (natively differentiable) and
compose under vmap (EnsembleSolver) and shard_map (distributed pencils)
with zero post-warmup retraces — see tests/test_fusion.py.
"""

import hashlib
import logging

import numpy as np
import jax
import jax.numpy as jnp

from ..tools.config import config

logger = logging.getLogger(__name__)

__all__ = ["FusionPlan", "resolve_fusion", "cache_token", "FusedEvalPlan",
           "guard_histories"]


def guard_histories(ts, hists=None):
    """The donation contract in ONE place: a DONATE_STEP program aliases
    its multistep history inputs (F/MX/LX) to outputs, so any cross-step
    reference holder — the resilience snapshot ring, SDC replay restore,
    async sharded-checkpoint capture, the phase-probe cache — must own
    device-side copies or it reads donated (deleted) arrays after the
    next step. Returns (F_hist, MX_hist, LX_hist) — the timestepper's
    live buffers by default — copied iff `ts` donates. The copies are
    async device dispatches; no host sync."""
    if hists is None:
        hists = (ts.F_hist, ts.MX_hist, ts.LX_hist)
    if getattr(ts, "donates_histories", False):
        hists = tuple(jnp.array(h, copy=True) for h in hists)
    return hists


class FusionPlan:
    """Resolved fusion switches (immutable per solver build)."""

    __slots__ = ("solve", "matvec", "transforms", "donate")

    def __init__(self, solve, matvec, transforms, donate):
        self.solve = bool(solve)
        self.matvec = bool(matvec)
        self.transforms = bool(transforms)
        self.donate = bool(donate)

    def token(self):
        """Stable content token for cache keys (tools/assembly_cache.py):
        the RESOLVED composition structure, so an `auto` that lands
        differently on another backend keys differently too."""
        return ("fusion-v1", self.solve, self.matvec, self.transforms)

    def __repr__(self):
        on = [k for k in ("solve", "matvec", "transforms", "donate")
              if getattr(self, k)]
        return f"FusionPlan({'+'.join(on) or 'off'})"


def _flag(section, key, default, auto_value):
    raw = section.get(key, default).strip().lower() if section else default
    if raw in ("on", "true", "1", "yes"):
        return True
    if raw in ("off", "false", "0", "no", ""):
        return False
    if raw != "auto":
        # a typo'd flag must not SILENTLY resolve to auto: the fused and
        # unfused solves sit in different tolerance classes, so a user
        # who wrote `offf` would compare against the wrong baseline
        raise ValueError(
            f"[fusion] {key} = {raw!r} is not a recognized value "
            f"(on/off/auto)")
    return auto_value


def _refuse_removed_options():
    """A user file that still sets an option PR 30 removed must not be
    silently ignored: the build it asked for (a tuned plan, the Pallas
    substitution kernel) is not the build it would get."""
    removed = []
    if config.has_section("autotune"):
        removed += [f"[autotune] {key}" for key in config["autotune"]] \
            or ["[autotune]"]
    if config.has_option("fusion", "PALLAS"):
        removed.append("[fusion] PALLAS")
    if removed:
        raise ValueError(
            f"{', '.join(removed)}: removed in PR 30 with the autotuner and "
            f"the Pallas substitution kernel (every build runs the one plan "
            f"`auto` resolves); delete the setting from your dedalus_tpu.cfg")


def resolve_fusion():
    """Resolve the [fusion] config against the active backend. `auto`
    semantics are profile-driven (module docstring): solve/matvec/donate
    fuse everywhere; transform composition defaults on only where MMT
    GEMMs beat the DCT/FFT fast paths (accelerator backends)."""
    _refuse_removed_options()
    section = config["fusion"] if config.has_section("fusion") else None
    accel = jax.default_backend() == "tpu"
    return FusionPlan(
        solve=_flag(section, "FUSED_SOLVE", "auto", True),
        matvec=_flag(section, "FUSED_MATVEC", "auto", True),
        transforms=_flag(section, "FUSED_TRANSFORMS", "auto", accel),
        donate=_flag(section, "DONATE_STEP", "auto", True),
    )


def cache_token():
    """The fusion component of assembly-cache content keys: a flag flip
    (or an `auto` resolving differently) can never serve a payload whose
    precomposed composites were built under another composition."""
    return resolve_fusion().token()


# ------------------------------------------------- composite transform GEMMs
#
# The RHS evaluator's linear-operator chains on the coupled Jacobi axis
# currently evaluate as: operand coeff -> per-axis operator matrices
# (conversion/derivative, coeff layout) -> backward transform (DCT chain
# or MMT) -> grid. Each arrow materializes a full intermediate. The
# composite folds the whole chain into ONE host-precomposed
# (Ng, N) GEMM per term: dealias-scaled backward MMT of the node's
# OUTPUT basis @ the term's coupled-axis matrix, applied directly to the
# operand's coefficients. Separable-axis factors ("blocks": Fourier
# derivative 2x2s) stay in coefficient space ahead of it — they are
# group-diagonal and exact — and the remaining separable axes transform
# after the (already summed) terms, so the whole node costs one GEMM +
# one FFT pass instead of per-term transform chains.

def _foldable_terms(node):
    """[(tensor_factor, blocks_descrs, folded_axis, fold_mat_or_None)] for
    a LinearOperator whose every term couples at most ONE 1-D Jacobi axis
    via a "full" matrix (+ any "blocks" on separable axes), or None when
    the node is outside the foldable set (curvilinear group stacks,
    multi-axis coupling, tensor-shape changes without factors...)."""
    from .basis import Jacobi
    domain = node.domain
    try:
        terms = node.device_terms()
    except Exception:
        return None
    jac_axes = [axis for axis, basis in enumerate(domain.bases)
                if isinstance(basis, Jacobi) and basis.dim == 1]
    if len(jac_axes) != 1:
        return None
    folded_axis = jac_axes[0]
    out = []
    for tensor_factor, descrs in terms:
        blocks = [None] * len(descrs)
        fold_mat = None
        for axis, descr in enumerate(descrs):
            if descr is None:
                continue
            kind = descr[0]
            if axis == folded_axis and kind == "full":
                fold_mat = descr[1]
            elif kind == "blocks" and domain.bases[axis] is not None \
                    and domain.bases[axis].separable:
                blocks[axis] = descr[1]
            else:
                return None
        if tensor_factor is None \
                and tuple(node.operand.tshape) != tuple(node.tshape):
            return None
        out.append((tensor_factor, blocks, folded_axis, fold_mat))
    return out or None


def _fold_spec(node, fold_mat):
    """(plan, fold_mat, shape) for the composite of `node`'s coupled-axis
    term: the node's output-basis backward MMT at dealias scale, folded
    with the term's matrix. The shape is known WITHOUT running the fold,
    so a warm build can validate and adopt cached composites before any
    host GEMM runs (the fold itself happens in FusedEvalPlan._fold, only
    on a cache miss)."""
    axis = None
    from .basis import Jacobi
    for ax, basis in enumerate(node.domain.bases):
        if isinstance(basis, Jacobi) and basis.dim == 1:
            axis = ax
            break
    basis = node.domain.bases[axis]
    scale = node.domain.dealias[axis]
    plan = basis.transform_plan(scale, library="matrix")
    Bshape = np.shape(plan.backward_mat)
    ncols = Bshape[1] if fold_mat is None else int(fold_mat.shape[1])
    return plan, fold_mat, (int(Bshape[0]), int(ncols))


class FusedEvalPlan:
    """
    Per-solver registry of fused RHS linear-operator evaluations.

    Built in two stages so warm builds actually skip the folds: the
    construction walk only records fold SPECS (plan, matrix, composite
    shape — all derivable without folding), the caller consults the
    assembly cache, and `finalize(payload)` either adopts the cached
    composites or runs the host folds fresh. `EvalContext.fusion`
    carries the plan into the traced evaluator; `LinearOperator.ev`
    consults it for grid-layout evaluations.
    """

    def __init__(self, solver, exprs):
        from .operators import LinearOperator
        # optional low-precision composite GEMMs ([precision] MMT_DTYPE,
        # libraries/solvecomp.py): resolved on the solver's build-start
        # plan — grid_eval casts the operand around the contraction
        # (apply_matrix_jax matches the matrix to the operand dtype)
        splan = getattr(solver, "_solve_plan", None)
        self._mmt_dtype = splan.mmt_dtype if splan is not None else "native"
        self.nodes = {}        # id(node) -> [(factor, blocks, axis, comp)]
        self._walk_order = []  # deterministic node order for cache payload
        # id(node) -> [(factor, blocks, axis, plan, fold_mat, shape)];
        # holding plan/fold_mat here pins their ids for _fold's intern
        # (Lift columns are built fresh per device_terms() call, so an
        # unpinned id could be reused by a DIFFERENT matrix and alias)
        self._pending = {}
        seen = set()

        def walk(expr):
            from .future import Future
            if not isinstance(expr, Future) or id(expr) in seen:
                return
            seen.add(id(expr))
            if isinstance(expr, LinearOperator):
                folded = _foldable_terms(expr)
                if folded is not None:
                    entries = []
                    for factor, blocks, axis, fold_mat in folded:
                        plan, mat, shape = _fold_spec(expr, fold_mat)
                        entries.append((factor, blocks, axis,
                                        plan, mat, shape))
                    self._pending[id(expr)] = entries
                    self._walk_order.append(expr)
            for arg in expr.args:
                walk(arg)

        for expr in exprs:
            walk(expr)

        # composition signature, from spec shapes only (no folds): the
        # same bytes whether computed before or after finalize
        h = hashlib.blake2b(digest_size=16)
        for node in self._walk_order:
            for factor, blocks, axis, _plan, _mat, shape \
                    in self._pending[id(node)]:
                h.update(type(node).__name__.encode())
                h.update(repr((np.shape(factor) if factor is not None
                               else None,
                               [np.shape(b) if b is not None else None
                                for b in blocks],
                               axis, tuple(shape))).encode())
        self._signature = h.hexdigest()

    def __len__(self):
        return len(self._walk_order)

    def finalize(self, payload=None):
        """Make the plan evaluable: adopt the cached composites when the
        payload validates against the fresh walk's specs (shape + kind +
        signature — a mismatch is a clean miss, never a wrong GEMM; this
        is the warm path, NO folds run), else fold fresh. Returns True on
        a cache install."""
        installed = payload is not None and self._install(payload)
        if not installed:
            self._fold()
        self._pending = None
        return installed

    def _install(self, payload):
        try:
            meta, arrays = payload["meta"], payload["arrays"]
        except Exception:
            return False
        if meta.get("kind") != "fused_composites" \
                or meta.get("signature") != self.signature():
            return False
        nodes = {}
        for i, node in enumerate(self._walk_order):
            entries = []
            for j, (factor, blocks, axis, _plan, _mat, shape) \
                    in enumerate(self._pending[id(node)]):
                cached = arrays.get(f"comp_{i}_{j}")
                if cached is None or tuple(cached.shape) != tuple(shape):
                    return False
                entries.append((factor, blocks, axis,
                                np.ascontiguousarray(cached)))
            nodes[id(node)] = entries
        self.nodes = nodes
        return True

    def _fold(self):
        """Run the host folds (cache miss): one B @ T per distinct
        (plan, matrix) pair — ids are stable while _pending pins the
        sources — interned so shared chains lift one device copy."""
        interned = {}
        for node in self._walk_order:
            entries = []
            for factor, blocks, axis, plan, fold_mat, _shape \
                    in self._pending[id(node)]:
                key = (id(plan),
                       id(fold_mat) if fold_mat is not None else None)
                comp = interned.get(key)
                if comp is None:
                    B = np.asarray(plan.backward_mat, dtype=np.float64)
                    if fold_mat is None:
                        comp = np.ascontiguousarray(B)
                    else:
                        T = fold_mat.toarray() \
                            if hasattr(fold_mat, "toarray") \
                            else np.asarray(fold_mat)
                        comp = np.ascontiguousarray(B @ T)
                    interned[key] = comp
                entries.append((factor, blocks, axis, comp))
            self.nodes[id(node)] = entries

    # ------------------------------------------------------- traced eval

    def grid_eval(self, node, ctx):
        """Fused grid-layout evaluation of a registered node, or None.
        Falls back (None) under an active transform mesh: the composite
        replaces the coupled-axis backward inside the sharded layout
        walk, whose transpose constraints the generic path owns."""
        entries = self.nodes.get(id(node))
        if entries is None:
            return None
        from .field import _active_mesh
        mesh, _ = _active_mesh(node.domain)
        if mesh is not None:
            return None
        from .future import ev
        from .operators import (apply_axis_blocks, apply_tensor_factor)
        from ..tools.array import apply_matrix_jax
        data = ev(node.operand, ctx, "c")
        tdim_in = node.operand.tdim
        total = None
        folded_axis = entries[0][2]
        with jax.named_scope("dedalus/transform/fused_composite"):
            for factor, blocks, axis, comp in entries:
                term = data
                for bax, blk in enumerate(blocks):
                    if blk is not None:
                        term = apply_axis_blocks(term, blk, tdim_in + bax)
                # the composite GEMM: coupled-axis operator chain +
                # dealiased backward transform in one contraction
                # (optionally in the [precision] MMT dtype — the matrix
                # follows the operand via the match_precision funnel,
                # the result is cast back to the working precision)
                if self._mmt_dtype != "native":
                    from ..libraries.solvecomp import low_dtype
                    wide = term.dtype
                    term = apply_matrix_jax(
                        comp, term.astype(low_dtype(self._mmt_dtype, wide)),
                        tdim_in + axis).astype(wide)
                else:
                    term = apply_matrix_jax(comp, term, tdim_in + axis)
                if factor is not None:
                    term = apply_tensor_factor(
                        term, factor, node.operand.tshape, node.tshape)
                total = term if total is None else total + term
            # remaining axes walk grid-ward in transform_to_grid order
            # (last axis first), the folded axis already in grid layout
            tdim = node.tdim
            domain = node.domain
            for bax in range(domain.dim - 1, -1, -1):
                basis = domain.bases[bax]
                if basis is None or bax == folded_axis:
                    continue
                total = basis.backward_transform(
                    total, tdim + bax, domain.dealias[bax],
                    tensorsig=node.tensorsig, sub_axis=bax - basis.first_axis)
        return total

    # ------------------------------------------------- assembly-cache IO

    def signature(self):
        """Composition-structure signature: per-node composite shapes and
        term layout, hashed into the cache entry key so a drifted problem
        or fold set can never alias. Computed from the walk's specs at
        construction — available before (and unchanged by) finalize."""
        return self._signature

    def cache_key(self, solver):
        base = getattr(solver, "assembly_key", None)
        if base is None or not self._walk_order:
            return None
        plan = getattr(solver, "_fusion_plan", None)
        token = plan.token() if plan is not None else cache_token()
        h = hashlib.blake2b(digest_size=20)
        h.update(b"fused-composites")
        h.update(base.encode())
        h.update(repr(token).encode())
        h.update(self.signature().encode())
        return h.hexdigest()

    def store(self, solver, cache):
        """Persist the precomposed composites (meta + arrays)."""
        key = self.cache_key(solver)
        if cache is None or key is None:
            return None
        arrays = {}
        for i, node in enumerate(self._walk_order):
            for j, (_, _, _, comp) in enumerate(self.nodes[id(node)]):
                arrays[f"comp_{i}_{j}"] = comp
        meta = {"kind": "fused_composites", "signature": self.signature(),
                "n_nodes": len(self._walk_order)}
        try:
            cache.store(key, meta, arrays)
        except Exception as exc:
            logger.warning(f"fused-composite cache store failed: {exc!r}")
        return key

def build_eval_plan(solver):
    """FusedEvalPlan over the solver's RHS `F` expressions (None when
    transform fusion is off or nothing folds), persisted through the
    assembly cache: on a warm hit `finalize` adopts the cached arrays
    and the host folds are skipped entirely."""
    plan = getattr(solver, "_fusion_plan", None) or resolve_fusion()
    if not plan.transforms:
        return None
    from .field import Field
    from .future import Future
    exprs = []
    for eq in solver.equations:
        for member, _cond in eq["members"]:
            expr = member.get("F")
            if isinstance(expr, (Field, Future)):
                exprs.append(expr)
    eval_plan = FusedEvalPlan(solver, exprs)
    if not len(eval_plan):
        return None
    from ..tools import assembly_cache
    cache = assembly_cache.resolve() if solver.cache_ok else None
    key = eval_plan.cache_key(solver)
    payload = cache.load(key) if (cache is not None and key is not None) \
        else None
    if eval_plan.finalize(payload):
        logger.info(f"fused composites: assembly cache hit "
                    f"({len(eval_plan)} node(s), key {key[:12]})")
    elif cache is not None and key is not None:
        if payload is not None:
            # parseable but mismatched/corrupt: quarantine, fresh folds
            cache.discard(key)
        eval_plan.store(solver, cache)
    return eval_plan

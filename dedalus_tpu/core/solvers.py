"""
Solvers (reference: dedalus/core/solvers.py).

  InitialValueSolver        — IMEX timestepping, one jitted device step
  LinearBoundaryValueSolver — batched pencil solve of L.X = F
  NonlinearBoundaryValueSolver — Newton-Kantorovich iteration
  EigenvalueSolver          — dense/sparse generalized eigensolves per pencil

TPU-native design: the solver holds the state as ONE device array X of shape
(G, S) (all pencils batched); fields are synchronized at step boundaries so
user code sees reference-like Field semantics while the hot loop stays on
device (reference hot loop anatomy: core/solvers.py:683-711 + SURVEY.md §3.2).
"""

import os
import pathlib
import time as time_mod
import weakref
import logging
import numpy as np
import scipy.linalg
import jax
import jax.numpy as jnp

from .subsystems import (PencilLayout, build_subproblems, build_matrices,
                         assemble_group_coos, MatrixStructure,
                         build_banded_arrays, gather_state, scatter_state,
                         row_valid_masks, merge_conditional_equations,
                         active_member, state_key)
from .curvilinear import gblocks_snapshot, gblocks_tally
from .future import EvalContext, ev
from . import timesteppers as timesteppers_mod
from ..libraries import pencilops
from ..tools import assembly_cache
from ..tools import health as health_mod
from ..tools import metrics as metrics_mod
from ..tools import tracing
from ..tools import retrace as retrace_mod
from ..tools.config import config
from ..tools.general import is_complex_dtype

logger = logging.getLogger(__name__)


class SolverBase:
    """Shared setup: pencil layout, subproblems, device matrices
    (reference: core/solvers.py:31 SolverBase)."""

    matrices = ("L",)
    lazy_ok = False   # EVP: per-group on-demand assembly at large sizes
    cache_ok = True   # NLBVP: Jacobian rebuilds churn the persistent cache

    @metrics_mod.timed_init
    def __init__(self, problem, matsolver=None, ncc_cutoff=None,
                 matrix_coupling=None, **kw):
        self.problem = problem
        self.dist = problem.dist
        self.variables = self.matrix_variables(problem)
        if matsolver is None:
            matsolver = config["linear algebra"].get("MATRIX_SOLVER", "auto")
        self.matsolver = matsolver
        # API-parity kwarg (reference: solvers accept ncc_cutoff for
        # Clenshaw truncation). NCC matrices here are quadrature-built and
        # sparsified at fixed tolerances (arithmetic.NCC_ANGULAR_CUTOFF,
        # sparsify defaults), so the value is accepted but currently
        # unused.
        self.ncc_cutoff = ncc_cutoff
        with self.build_phases.scope("layout"):
            self.layout = PencilLayout(self.dist, self.variables,
                                       problem.equations,
                                       matrix_coupling=matrix_coupling)
            self.equations = merge_conditional_equations(
                problem.equations, self.dist, self.layout)
            self.subproblems = build_subproblems(self.layout)
        self._lazy = False
        # cold-start accounting (tools/metrics.BuildPhases): `timed_init`
        # made `self.build_phases` and times this whole `__init__`
        self._build_pencil_system()
        with self.build_phases.scope("layout"):
            self.valid_row_mask = row_valid_masks(self.layout,
                                                  self.equations)

    def _build_pencil_system(self):
        """
        Assemble the pencil matrices and pick the device representation:
        dense (G, S, S) for small systems, banded-interior + Schur border
        for large single-coupled-axis systems (reference: ScipyBanded +
        Woodbury, libraries/matsolvers.py:186-194,285-316). Sets
        self._matrices (host arrays), self.ops, self.structure.

        Assembly itself goes through the group-batched kron-term path
        (core/batched_assembly.py) whenever the expression tree supports
        it — O(1) tree walks instead of O(G) — falling back to the
        per-group scipy walk otherwise.
        """
        names = self.matrices
        # resolve the [fusion] composition ONCE, before anything keys on
        # or compiles under it: solver_key's fusion token, BandedOps'
        # switches, the timestepper's donation contract and the eval plan
        # all read THIS plan, so a config mutation mid-build (tests and
        # benchmarks flip flags in-process) can never split one solver
        # across two compositions
        from . import fusedstep
        self._fusion_plan = fusedstep.resolve_fusion()
        # resolve the [distributed] transpose chunking ONCE too, for the
        # same reason: the chunk structure shapes every compiled sharded
        # walk, and solver_key/pool_key token it so pooled compiled
        # programs can never alias across chunk configs (a bad config
        # value fails the build here, not mid-trace)
        from ..parallel.transposes import resolve_transpose_chunks
        self._transpose_chunks = resolve_transpose_chunks()
        # resolve the solve composition + precision ladder ONCE as well
        # ([fusion] SOLVE_COMPOSITION/SPIKE_CHUNKS + the [precision]
        # section, libraries/solvecomp.py): the composition restructures
        # the compiled substitution and the ladder changes the factor
        # store dtype, so both token the assembly/pool keys; a bad
        # config value fails the build here, not mid-trace
        from ..libraries import solvecomp
        self._solve_plan = solvecomp.resolve_solve_plan()
        # provenance: how THIS build's plan was chosen, stamped into
        # plan_provenance() so every results row names its selector
        self._plan_source = ("config" if solvecomp.solve_knobs_pinned()
                             else "default")
        G, S = self.pencil_shape
        dense_bytes = G * S * S * np.dtype(self.pencil_dtype).itemsize
        lazy_bytes = int(config["linear algebra"].get(
            "EVP_LAZY_BYTES", str(1 << 28)))
        if self.lazy_ok and dense_bytes > lazy_bytes:
            # EVP at scale (e.g. ell-coupled rotating convection): skip the
            # full (G, S, S) batched store entirely; solve_dense/solve_sparse
            # assemble the requested group on demand, sparse end-to-end
            # (reference: per-subproblem sparse assembly + SuperLU,
            # core/solvers.py:225 solve_sparse)
            logger.info(
                f"EVP pencil system: lazy per-group assembly "
                f"(G={G}, S={S}; dense store would be "
                f"{dense_bytes / 1e9:.2f} GB)")
            self._lazy = True
            self._batched = None
            self._matrices = None
            self.structure = None
            self.ops = None
            return
        # persistent assembly cache (tools/assembly_cache.py): on a hit the
        # symbolic walk, scipy kron folds and banded structural analysis are
        # all skipped — the COO/banded stores load from disk
        cache = assembly_cache.resolve() if self.cache_ok else None
        ckey = None
        if cache is not None:
            with self.build_phases.scope("assembly_cache"):
                ckey = assembly_cache.solver_key(self, names)
        # content identity of this pencil system, stashed for consumers
        # that key on it after the build (the warm-pool service's
        # assembly_cache.pool_key); None when the cache is disabled or
        # the graph is unfingerprintable — pool_key then recomputes
        self.assembly_key = ckey
        if ckey is not None:
            with self.build_phases.scope("assembly_cache"):
                installed = self._cache_install(cache, ckey, names)
            self.build_phases.cache = "hit" if installed else "miss"
            if installed:
                return
        self._assemble_batched(names)
        spec = self.matsolver if isinstance(self.matsolver, str) else ""
        forced = spec.lower() if spec.lower() in ("banded", "dense") else None
        cutoff_bytes = int(config["linear algebra"].get(
            "BANDED_CUTOFF_BYTES", str(1 << 30)))
        # An explicitly named dense matsolver (or solver class) is always
        # honored; only 'auto' lets the size heuristic pick the banded path.
        auto = isinstance(self.matsolver, str) and spec.lower() == "auto"
        try_banded = (forced == "banded"
                      or (auto and dense_bytes > cutoff_bytes))
        self.structure = None
        if try_banded:
            result = self._try_banded(names, S)
            if result is True:
                self._cache_store(cache, ckey, names)
                return
            if forced == "banded":
                raise ValueError("Banded solve forced but not applicable: "
                                 f"{self._banded_reason}")
            msg = (f"Banded path not applicable ({self._banded_reason}); "
                   f"using dense ({dense_bytes / 1e9:.2f} GB)")
            if dense_bytes > 4 * cutoff_bytes:
                # e.g. a Chebyshev x Chebyshev problem (two coupled axes):
                # O(G S^2) memory and O(G S^3) factor work with no banded
                # escape hatch yet — make the scale cost loud (reference
                # handles arbitrary coupled sets with sparse LU,
                # core/subsystems.py:493-598)
                logger.warning(
                    msg + " — this exceeds the banded cutoff 4x; consider "
                    "lowering the coupled-axis resolution or making more "
                    "axes separable (Fourier).")
            else:
                logger.info(msg)
            # reuse the already-assembled COO matrices for the dense fallback
            with self.build_phases.scope("host_assembly"):
                self._matrices = self._densify_coo_store(result, names, S)
        elif self._batched is not None:
            with self.build_phases.scope("host_assembly"):
                self._matrices = self._dense_from_batched(names)
        else:
            with self.build_phases.scope("host_assembly"):
                self._matrices = build_matrices(
                    self.subproblems, self.equations, self.variables,
                    names=names)
        self.ops = pencilops.DenseOps(
            self._dense_matsolver(),
            solve_plan=getattr(self, "_solve_plan", None))
        self._cache_store(cache, ckey, names)

    def _cache_install(self, cache, ckey, names):
        """Load the cached pencil system and install it (the dense scatter
        of a cached COO store with it); False on a miss."""
        payload = cache.load(ckey)
        if payload is None:
            return False
        try:
            installed = assembly_cache.install_payload(self, names, payload)
        except Exception as exc:
            # parseable but internally inconsistent (missing array,
            # drifted structure state): quarantine and assemble fresh —
            # same contract as load-time corruption, which must never
            # abort solver builds
            installed = False
            logger.warning(
                f"assembly cache payload {ckey[:12]} failed to "
                f"install ({exc!r}); quarantined, assembling fresh")
            cache.discard(ckey)
        if installed:
            logger.info(
                f"Pencil system: assembly cache hit "
                f"({payload['meta']['kind']}, key {ckey[:12]})")
        return bool(installed)

    def _cache_store(self, cache, ckey, names):
        """Persist the freshly built pencil system (miss path only)."""
        if cache is None or ckey is None:
            return
        with self.build_phases.scope("assembly_cache"):
            try:
                exported = assembly_cache.export_payload(self, names)
                if exported is not None:
                    cache.store(ckey, *exported)
            except Exception as exc:
                logger.warning(f"assembly cache store failed: {exc!r}")

    def _assemble_batched(self, names):
        """Attempt group-batched assembly; sets self._batched to the shared
        COO pattern result (rows, cols, {name: (G, nnz) vals}, row_valid,
        col_valid) or None when the expression tree requires the per-group
        walk. Runs in PARTIAL mode (per-expression fallback onto the
        shared pattern) so a single unbatchable expression never forces
        the whole system onto the per-group walk."""
        from .batched_assembly import batched_system_coos, BatchUnsupported
        with self.build_phases.scope("host_assembly"):
            # PARTIAL mode directly: with zero per-expression fallbacks it
            # produces the full-mode output, and a system with one
            # unbatchable term late in the tree would otherwise pay full
            # assembly of every preceding expression twice (once in a
            # doomed non-partial pass, again in the retry)
            try:
                self._batched = batched_system_coos(
                    self.layout, self.equations, self.variables, names,
                    subproblems=self.subproblems, partial=True)
            except BatchUnsupported as exc:
                logger.debug(f"Batched assembly unavailable ({exc}); "
                             "using per-group assembly.")
                self._batched = None

    def _dense_from_batched(self, names):
        """Scatter the shared-pattern COO store into dense (G, S, S) arrays
        with the enumeration-order validity closure on the last name."""
        pr, pc, vals, row_valid, col_valid = self._batched
        G, S = self.pencil_shape
        out = {}
        for name in names:
            dense = np.zeros((G, S, S), dtype=vals[name].dtype)
            dense[:, pr, pc] = vals[name]
            out[name] = dense
        last = names[-1]
        for g in range(G):
            inv_rows = np.flatnonzero(~row_valid[g])
            inv_cols = np.flatnonzero(~col_valid[g])
            out[last][g, inv_rows, inv_cols] = 1.0
        return out

    def _densify_coo_store(self, store, names, S):
        """Scatter (coo_store, masks) from a failed banded attempt into the
        dense (G, S, S) arrays, applying the enumeration-order closure the
        dense path uses."""
        coo_store, masks = store
        cplx = any(is_complex_dtype(v.dtype) for v in self.variables)
        dtype = np.complex128 if cplx else np.float64
        G = len(coo_store)
        out = {name: np.zeros((G, S, S), dtype=dtype) for name in names}
        for g, (coos, (row_valid, col_valid)) in enumerate(zip(coo_store, masks)):
            for name in names:
                rows, cols, vals = coos[name]
                out[name][g][rows, cols] = vals
            inv_rows = np.flatnonzero(~row_valid)
            inv_cols = np.flatnonzero(~col_valid)
            out[names[-1]][g][inv_rows, inv_cols] = 1.0
        return out

    def _try_banded(self, names, S):
        """
        Attempt the banded + pinned representation: assemble real
        (pre-closure) entries per group, run the structural analysis, place
        the validity closure on the matched diagonal, and extract banded
        storage. Returns True on success (with self._matrices and self.ops
        set), else (coo_store, masks) for the dense fallback, with
        self._banded_reason set.
        """
        from .subsystems import PatternAccumulator, compute_group_closure
        # Relative drop tolerance for the PATTERN only (band detection /
        # matching); stored matrix values are never filtered, so the banded
        # and dense paths solve the same operator up to sub-tol out-of-band
        # entries dropped at fill time.
        tol = float(config["linear algebra"].get("BAND_DETECT_CUTOFF", "1e-14"))
        equations = self.equations
        coo_store = []
        masks = []
        acc = PatternAccumulator(S)
        scale = 0.0
        if self._batched is not None:
            pr, pc, bvals, row_valid_b, col_valid_b = self._batched
            with self.build_phases.scope("pattern"):
                for g in range(len(self.subproblems)):
                    coo_store.append({name: (pr, pc, bvals[name][g])
                                      for name in names})
                    masks.append((row_valid_b[g], col_valid_b[g]))
                scale = max((np.abs(bvals[name]).max()
                             if bvals[name].size else 0.0) for name in names)
        else:
            from .subsystems import map_groups
            with self.build_phases.scope("host_assembly"):
                results = map_groups(
                    lambda sp: assemble_group_coos(
                        sp, equations, self.variables, names, closure=False),
                    self.subproblems)
            for coos, row_valid, col_valid in results:
                coo_store.append(coos)
                masks.append((row_valid, col_valid))
                scale = max(scale, max((np.abs(v).max() if len(v) else 0.0
                                        for _, _, v in coos.values()), default=0.0))
        tol_abs = tol * (scale or 1.0)
        # Per-ROW relative significance, scaled to the pencil precision:
        # f32-sourced data breaks exact cancellations at ~eps32-relative
        # levels, leaving junk far below its row's real structure yet
        # above the GLOBAL cutoff when one term (e.g. a Rayleigh-scaled
        # buoyancy) inflates the global scale. Row-relative filtering
        # separates the two cleanly in both precisions.
        eps_p = np.finfo(self.real_dtype).eps
        row_frac = max(tol, 10.0 * eps_p)
        with self.build_phases.scope("structure"):
            for coos, (row_valid, col_valid) in zip(coo_store, masks):
                rowmax = np.zeros(S)
                for r, c, v in coos.values():
                    if len(r):
                        np.maximum.at(rowmax, r, np.abs(v))
                pat = {}
                for k, (r, c, v) in coos.items():
                    # row-significant AND above the global assembly-dirt
                    # floor (dirt-only rows would otherwise self-certify)
                    keep = (np.abs(v) >= row_frac * rowmax[r]) \
                        & (np.abs(v) > tol_abs)
                    pat[k] = (r[keep], c[keep], v[keep])
                acc.add_group(pat, row_valid, col_valid)
            structure = MatrixStructure(self.layout, self.variables,
                                        equations)
            row_valid_all = np.array([m[0] for m in masks])
            col_valid_all = np.array([m[1] for m in masks])
            spec = self.matsolver if isinstance(self.matsolver, str) else ""
            structure.finalize(acc.union, acc.qualified(), row_valid_all,
                               col_valid_all, vmax=acc.vmax,
                               allow_uneconomic=(spec.lower() == "banded"))
            if not structure.ok:
                self._banded_reason = structure.reason
                return (coo_store, masks)
            # validity closure aligned with the matching (passed separately
            # to build_banded_arrays so the shared COO pattern stays shared
            # and the scatter can vectorize over the whole group batch)
            closures = []
            for coos, (row_valid, col_valid) in zip(coo_store, masks):
                closure = compute_group_closure(structure, row_valid,
                                                col_valid)
                if closure is None:
                    self._banded_reason = \
                        "validity closure misaligned with matching"
                    return (coo_store, masks)
                closures.append(closure)
        host_dtype = (np.complex128 if is_complex_dtype(self.pencil_dtype)
                      else np.float64)
        try:
            with self.build_phases.scope("host_assembly"):
                self._matrices = build_banded_arrays(
                    coo_store, structure, names, host_dtype,
                    drop_tol=max(tol_abs, row_frac * (scale or 1.0)),
                    closures=closures)
        except ValueError as exc:
            self._banded_reason = str(exc)
            return (coo_store, masks)
        self.structure = structure
        self.ops = pencilops.BandedOps(
            structure, fusion=getattr(self, "_fusion_plan", None),
            solve_plan=getattr(self, "_solve_plan", None))
        logger.info(
            f"Pencil system: banded path (S={structure.S}, "
            f"pins={structure.t_pins}, kl={structure.kl}, "
            f"ku={structure.ku}, q={structure.q})")
        return True

    def _dense_matsolver(self):
        """Resolve the dense batched matsolver name (config MATRIX_SOLVER)."""
        spec = self.matsolver
        if not isinstance(spec, str) or spec.lower() not in ("auto", "banded", "dense"):
            return spec
        # TPU: triangular solves are sequential (slow); a precomputed
        # batched inverse makes every solve one MXU matmul (~65x faster
        # on v5e). TPU LuDecomposition only implements F32/C64, so
        # 64-bit problems factor in 32-bit + iterative refinement: that
        # is what steps XLA's software float64 (EMULATED_F64 = never, or
        # a problem the dd runner refuses). The double-double route
        # refines by itself and takes the plain inverse for its inner
        # float32 solves (core/ddstep._inner_ops).
        # Elsewhere (CPU/GPU): LU is accurate and fast.
        if jax.default_backend() == "tpu":
            small = all(np.dtype(v.dtype) in (np.dtype(np.float32),
                                              np.dtype(np.complex64))
                        for v in self.variables)
            return "BatchedInverse" if small else "BatchedInverseRefined"
        return "BatchedLUFactorized"

    def matrix_variables(self, problem):
        return problem.variables

    @property
    def pencil_shape(self):
        S = sum(self.layout.slot_size(v.domain, v.tensorsig) for v in self.variables)
        return (self.layout.n_groups, S)

    @property
    def subproblems_by_group(self):
        """Subproblems keyed by their group tuple (reference:
        core/solvers.py SolverBase.subproblems_by_group)."""
        return {sp.group: sp for sp in self.subproblems}

    @property
    def pencil_dtype(self):
        """Device working dtype: 32-bit when every variable is 32-bit."""
        cplx = any(is_complex_dtype(v.dtype) for v in self.variables)
        bits32 = all(np.dtype(v.dtype) in (np.dtype(np.float32), np.dtype(np.complex64))
                     for v in self.variables)
        if cplx:
            return np.dtype(np.complex64) if bits32 else np.dtype(np.complex128)
        return np.dtype(np.float32) if bits32 else np.dtype(np.float64)

    @property
    def real_dtype(self):
        return np.dtype(np.float32) if self.pencil_dtype in (np.dtype(np.float32), np.dtype(np.complex64)) else np.dtype(np.float64)

    @property
    def state(self):
        return self.problem.variables

    # ---------------------------------------------------------------- fields

    def _state_program(self, cache, fields, body):
        """`body(layout, fields, arg)` — `gather_state` or `scatter_state` —
        as one jitted program per field set, memoized on the solver under
        the attribute `cache`: launched op by op from the host, the
        reshape/transpose/slice chain costs ~0.5 s of every cold start
        (gather) and was 48 dispatches, 15-27 ms of every handler read on
        the chip (scatter of RB's 8 variables), while a single traced
        program is one dispatch AND lands in the persistent XLA cache for
        the next process."""
        key = tuple(state_key(v) for v in fields)
        programs = self.__dict__.setdefault(cache, {})
        fn = programs.get(key)
        if fn is None:
            from ..tools.jitlift import lifted_jit
            layout, fields = self.layout, list(fields)
            # memoized in `programs` just above (cache-subscript guard the
            # static pass cannot see)
            fn = programs[key] = lifted_jit(  # dedalus-lint: disable=DTL003
                lambda arg: body(layout, fields, arg))
        return fn

    def gather_fields(self, fields=None):
        """The fields' coefficients as the (G, S) state vector, through the
        memoized gather program."""
        fields = fields or self.variables
        arrays = {state_key(v): v.coeff_data() for v in fields}
        return self._state_program("_gather_programs", fields,
                                   gather_state)(arrays)

    def _scatter_program(self, fields):
        """The twin of `gather_fields`' program: X -> {state_key: coeffs}."""
        return self._state_program("_scatter_programs", fields,
                                   scatter_state)

    def scatter_fields(self, X, fields=None):
        """Scatter X into the fields now, through the memoized scatter
        program: counts as a mutation so a co-resident IVP solver's dirty
        tracking re-gathers this data."""
        fields = fields or self.variables
        arrays = self._scatter_program(fields)(X)
        for v in fields:
            v.preset_coeff(arrays[state_key(v)])
            v.mark_modified()

    def defer_scatter(self, X):
        """
        Install lazy pulls: the first field of this state that is accessed
        launches the scatter program once, for all variables; a state
        nobody reads launches nothing (keeps the no-IO stepping loop free
        of per-step scatter work). X is not donated.
        """
        def fetch():
            # one launch per state that is read
            with tracing.span("state/scatter"):
                return self._scatter_program(self.variables)(X)

        self._install_pulls(fetch)

    def _install_pulls(self, fetch):
        """Lazy pulls on every variable: the first one read calls
        `fetch()` -> {state_key: coefficient data}, once for all."""
        cache = {}

        def make_pull(var):
            def pull():
                if "arrays" not in cache:
                    cache["arrays"] = fetch()
                var.preset_coeff(cache["arrays"][state_key(var)])
            return pull

        for v in self.variables:
            v.install_pull(make_pull(v))

    def snapshot_versions(self):
        self._field_versions = {v.name: v._version for v in self.variables}

    def fields_dirty(self):
        versions = getattr(self, "_field_versions", None)
        if versions is None:
            return True
        return any(v._version != versions.get(v.name) for v in self.variables)

    # ------------------------------------------------------------------ RHS

    def _member_masks(self):
        """Per-block, per-member group-activity masks (None when always
        active); computed once — conditions are static per problem."""
        if getattr(self, "_member_masks_cache", None) is None:
            groups = list(self.layout.groups())
            out = []
            for eq in self.equations:
                out.append([None if cond is None
                            else np.array([float(cond(g)) for g in groups])
                            for _, cond in eq["members"]])
            self._member_masks_cache = out
        return self._member_masks_cache

    def build_rhs_evaluator(self, key="F", time_field=None, get_expr=None):
        """
        Build `eval_F(X, t=None, extra_arrays=None) -> (G, S)` evaluating the
        per-equation expressions selected by `get_expr` (default: the member's
        `key` entry). X=None skips the variable scatter (residual-style
        evaluation over non-variable fields only).
        """
        problem = self.problem
        layout = self.layout
        variables = self.variables
        equations = self.equations
        dim = self.dist.dim
        dtype = self.pencil_dtype
        if get_expr is None:
            get_expr = lambda member: member.get(key)

        # per-block member selection masks for conditioned equations
        member_masks = self._member_masks()

        # Non-variable fields feeding the RHS (parameters, forcings) become
        # explicit inputs of the compiled evaluator, so callers that thread
        # `extra_arrays` (see rhs_extra) pick up user updates to those fields
        # without retracing; a None leaves them baked as trace-time constants.
        from .field import Field as _Field
        from .future import Future as _Future
        extra = set()
        for eq in equations:
            for member, cond in eq["members"]:
                expr = get_expr(member)
                if isinstance(expr, (_Field, _Future)):
                    extra |= expr.atoms(_Field)
        extra -= set(variables)
        if time_field is not None:
            extra.discard(time_field)
        extra_fields = sorted(extra, key=lambda f: (f.name or "", id(f)))

        def eval_F(X, t=None, extra_arrays=None):
            from .field import mesh_transforms
            with mesh_transforms(self.dist.mesh,
                                 chunks=self._transpose_chunks):
                return eval_F_body(X, t, extra_arrays)

        def eval_F_body(X, t=None, extra_arrays=None):
            with metrics_mod.trace_scope("evaluator", "rhs"):
                return eval_F_inner(X, t, extra_arrays)

        def eval_F_inner(X, t=None, extra_arrays=None):
            subs = {}
            if X is not None:
                arrays = scatter_state(layout, variables, X)
                subs = {var: arrays[state_key(var)] for var in variables}
            if time_field is not None:
                subs[time_field] = jnp.reshape(jnp.asarray(t, dtype=self.real_dtype),
                                               (1,) * dim)
            if extra_arrays is not None:
                subs.update(zip(extra_fields, extra_arrays))
            ctx = EvalContext(subs)
            # fused operator-chain composites ride into the traced
            # evaluator (read per trace: the plan is built after this
            # evaluator, at solver construction)
            ctx.fusion = getattr(self, "_fused_eval_plan", None)
            parts = []
            for eq, masks in zip(equations, member_masks):
                size = layout.slot_size(eq["domain"], eq["tensorsig"])
                total = None
                for (member, cond), mask in zip(eq["members"], masks):
                    expr = get_expr(member)
                    if expr is None:
                        continue
                    data = ev(expr, ctx, "c")
                    part = layout.gather(data, eq["domain"], eq["tensorsig"])
                    if mask is not None:
                        part = part * jnp.asarray(mask, dtype=self.real_dtype)[:, None]
                    total = part if total is None else total + part
                if total is None:
                    total = jnp.zeros((layout.n_groups, size), dtype=dtype)
                parts.append(total)
            return jnp.concatenate(parts, axis=1).astype(dtype)

        eval_F.extra_fields = extra_fields
        return eval_F

    def rhs_extra(self):
        """Current data of the RHS's non-variable field inputs (ordered to
        match eval_F.extra_fields)."""
        return [f.coeff_data() for f in self.eval_F.extra_fields]


class InitialValueSolver(SolverBase):
    """IVP solver (reference: core/solvers.py:503 InitialValueSolver)."""

    matrices = ("M", "L")

    @metrics_mod.timed_init
    def __init__(self, problem, timestepper, matsolver=None,
                 enforce_real_cadence=100, warmup_iterations=10,
                 profile=None, profile_directory=None, metrics=None,
                 metrics_file=None, sample_cadence=None, health=None,
                 health_cadence=None, postmortem_dir=None, **kw):
        init_t0 = time_mod.time()
        super().__init__(problem, matsolver=matsolver, **kw)
        with self.build_phases.scope("factor"):
            self.M_mat = self.ops.to_device(self._matrices["M"],
                                            self.pencil_dtype)
            self.L_mat = self.ops.to_device(self._matrices["L"],
                                            self.pencil_dtype)
        with self.build_phases.scope("plans"):
            self.eval_F = self.build_rhs_evaluator(
                "F", time_field=problem.time)
        # fused RHS operator chains (core/fusedstep.py FUSED_TRANSFORMS):
        # foldable linear-operator nodes get host-precomposed
        # backward-MMT @ operator composite GEMMs, persisted through the
        # assembly cache; None when transform fusion is off or nothing
        # folds. Read at trace time via EvalContext.fusion.
        from . import fusedstep
        with self.build_phases.scope("plans"):
            self._fused_eval_plan = fusedstep.build_eval_plan(self)
        # timestepping state
        self.sim_time = 0.0
        self.initial_sim_time = 0.0
        self.iteration = 0
        self.initial_iteration = 0
        self.stop_sim_time = np.inf
        self.stop_wall_time = np.inf
        self.stop_iteration = np.inf
        self.warmup_iterations = warmup_iterations
        self.enforce_real_cadence = enforce_real_cadence
        self.start_time = self.init_time = time_mod.time()
        self.warmup_time = None
        self.X = self.gather_fields()
        if isinstance(timestepper, str):
            timestepper = timesteppers_mod.schemes[timestepper]
        self.timestepper = timestepper(self)
        from .evaluator import Evaluator
        self.evaluator = Evaluator(self)
        self.dt = None
        self._project_state = None
        # float64 on an accelerator: route stepping through the emulated-
        # f64 (double-double) path where the problem is supported — XLA's
        # native software f64 has no MXU path, so the dd runner's int8
        # Ozaki matmuls + f32-factor/dd-refined solves are the fast f64
        # (config [execution] EMULATED_F64 = auto|never; core/ddstep.py)
        self._dd = None
        if (np.dtype(self.pencil_dtype) == np.dtype(np.float64)
                and jax.default_backend() == "tpu"
                and config["execution"].get(
                    "EMULATED_F64", "auto").lower() != "never"):
            from .ddstep import DDIVPRunner, DDUnsupportedError
            try:
                with self.build_phases.scope("dd_prepare"):
                    self._dd = DDIVPRunner(self)
                logger.info("float64 on accelerator: emulated-f64 "
                            "(double-double) step path active")
            except DDUnsupportedError as exc:
                logger.info(f"float64 on accelerator: dd path unavailable "
                            f"({exc}); stepping in native XLA f64")
        # which route keeps a float64 problem's guarantee, for the records
        if self._dd is not None:
            self.build_phases.f64_route = "dd"
            self.build_phases.dd = weakref.WeakMethod(self._dd.counters)
        elif np.dtype(self.pencil_dtype) in (np.float64, np.complex128):
            self.build_phases.f64_route = "xla_f64"
        # Profiling (reference: core/solvers.py:546-561,780-806 cProfile
        # phases; here a jax.profiler trace of the run phase + per-phase
        # wall times dumped at log_stats)
        if profile is None:
            profile = config["profiling"].getboolean("PROFILE_DEFAULT",
                                                     fallback=False)
        self.profile = bool(profile)
        self.profile_directory = pathlib.Path(
            profile_directory
            or config["profiling"].get("PROFILE_DIRECTORY", "profiles"))
        # Step-loop metrics (tools/metrics.py): counters + sampled phase
        # timers + memory watermark; default-on per [profiling] config,
        # cadence-gated so off-cadence steps never sync the device.
        self.metrics = metrics_mod.resolve(
            metrics, sink=metrics_file, cadence=sample_cadence,
            meta={"backend": jax.default_backend(),
                  "dtype": str(np.dtype(self.pencil_dtype)),
                  "pencil_shape": list(self.pencil_shape)})
        self._metrics_warm_pending = False
        # Abnormal-exit telemetry: an interrupted run (exception, SIGTERM)
        # still flushes one complete results.jsonl record (atexit + the
        # chaining signal hook; tools/metrics.py)
        metrics_mod.register_exit_flush(self)
        # Retrace sentinel (tools/retrace.py): armed at warmup end; a
        # post-warmup recompile of any step program warns and bumps the
        # dedalus/retrace counter on this metrics instance.
        retrace_mod.sentinel.subscribe(self.metrics)
        # Numerical-health monitor (tools/health.py): cadence-gated fused
        # NaN/growth/tail-energy probe + divergence flight recorder.
        # Default-on per [health] config; a disabled monitor compiles
        # nothing (zero-overhead path) but keeps the structured
        # invalid-dt error path available.
        self.health = health_mod.resolve(
            health, solver=self, cadence=health_cadence,
            postmortem_dir=postmortem_dir)
        self._health_error = None
        self._setup_time = time_mod.time() - init_t0
        self._trace_active = False

    @property
    def health_error(self):
        """The SolverHealthError that halted the run (None while healthy)."""
        return self._health_error

    @property
    def proceed(self):
        """Whether to keep iterating (reference: core/solvers.py:618)."""
        if self._health_error is not None:
            # logged once at detection (health monitor); graceful halt
            return False
        if self.sim_time >= self.stop_sim_time:
            logger.info("Simulation stop time reached.")
            return False
        if self.iteration >= self.stop_iteration:
            logger.info("Simulation stop iteration reached.")
            return False
        if (time_mod.time() - self.start_time) >= self.stop_wall_time:
            logger.info("Simulation stop wall time reached.")
            return False
        return True

    def enforce_hermitian_symmetry(self):
        """
        Re-project the state through a dealiased grid roundtrip
        (reference: core/solvers.py:675-692 enforce_hermitian_symmetry).
        Real-dtype storage makes Hermitian drift structurally impossible
        here (RealFourier keeps real arrays end-to-end), but the roundtrip
        still projects accumulated drift out of non-representable modes
        (curvilinear triangular truncation, Nyquist slots).
        """
        self.X = self._ensure_project()(self.X)

    def _ensure_project(self):
        """The jitted dealiased-roundtrip projection of the state (shared
        by enforce_hermitian_symmetry and the transform phase probe)."""
        if self._project_state is None:
            from .field import (transform_to_grid, transform_to_coeff,
                                mesh_transforms)
            layout, variables = self.layout, self.variables

            from ..tools.jitlift import lifted_jit

            def project(X):
                with mesh_transforms(self.dist.mesh,
                                     chunks=self._transpose_chunks):
                    arrays = scatter_state(layout, variables, X)
                    out = {}
                    for v in variables:
                        scales = tuple(v.domain.dealias)
                        tdim = len(v.tensorsig)
                        g = transform_to_grid(arrays[state_key(v)], v.domain,
                                              scales,
                                              tdim, tensorsig=v.tensorsig)
                        out[state_key(v)] = transform_to_coeff(g, v.domain, scales,
                                                         tdim,
                                                         tensorsig=v.tensorsig)
                    return gather_state(layout, variables, out)

            # ensemble hook: the raw projection body (core/ensemble.py
            # vmaps it over the member axis for the fleet's Hermitian/
            # valid-mode re-projection cadence)
            self._project_body = project
            self._project_state = lifted_jit(project)
        return self._project_state

    def _dd_advance(self, n, dt):
        """Advance n steps on the emulated-f64 (double-double) path, inside
        the same `step` / `step_many` span and with the same cold-start
        booking as the float32 route: sync user field edits into the dd
        state, step, then the shared host bookkeeping. The f32 Hermitian
        re-projection cadence is skipped here — a f32 grid roundtrip would
        truncate the dd state (the dd-supported problem set is Cartesian
        real-storage, which has no Hermitian drift to project out)."""
        dd = self._dd
        first = self._first_advance()
        attrs = {"iteration": self.iteration, "route": "dd"}
        if n > 1:
            attrs["n"] = n
        with tracing.span("step_many" if n > 1 else "step", attrs):
            if self.fields_dirty():
                # user edit or checkpoint restart: re-gather state AND
                # restart the multistep ramp from the solver's clock
                # (histories predate the new state; load_state also resets
                # sim_time/iteration)
                dd.sync_state()
                dd.reset_history(self.sim_time)
            elif dd.sim_time != self.sim_time:
                dd.sim_time = self.sim_time
            if n > 1:
                dd.step_many(n, dt)   # one lax.scan dispatch per block
            else:
                dd.step(dt)
            self.X = dd.X.hi   # f32 view: finite checks, health probe
            self.sim_time = dd.sim_time
            self._book_group_stacks(first)
            self._after_advance(n, dt)

    def _dd_defer_pull(self, Xdd):
        """The dd route's `defer_scatter`: the first field of this state
        that is read pulls hi and lo and sums them in float64 on the host,
        for all variables (the `dd/pull` span); a state nobody reads pulls
        nothing."""
        def fetch():
            with tracing.span("dd/pull"):
                his = scatter_state(self.layout, self.variables, Xdd.hi)
                los = scatter_state(self.layout, self.variables, Xdd.lo)
                return {k: jnp.asarray(np.asarray(his[k], np.float64)
                                       + np.asarray(los[k], np.float64))
                        for k in his}

        self._install_pulls(fetch)

    def _stop_trace(self):
        if self._trace_active:
            jax.profiler.stop_trace()
            self._trace_active = False
            logger.info(f"Profiler trace written to {self.profile_directory}")

    def _end_warmup(self):
        """Record warmup completion; start the profiler trace if enabled."""
        # Compile + first-run the phase probes BEFORE stamping warmup_time:
        # probe compilation stays out of the run window (log_stats rate) and
        # out of any externally measured post-warmup block. step_many-only
        # drivers hit this before the first block has factored the LHS
        # (no probes yet): defer the warm sample — and the loop-window
        # anchor — past that first, compile-bearing block.
        self._metrics_warm_pending = False
        if self.metrics.sampling and self._dd is None:
            if not self._try_sample_phases():
                self._metrics_warm_pending = self.metrics.sampling
        # health probe compiles here too (one baseline record), keeping
        # its compile out of measured windows like the phase probes
        self.health.warm(self.X)
        self.metrics.reset_loop()
        self.warmup_time = time_mod.time()
        # warmup compiled (or deferred-compiles) every step program; any
        # later retrace is a hygiene regression worth a structured warning
        retrace_mod.sentinel.arm()
        if self.profile and not self._trace_active:
            import atexit
            os.makedirs(self.profile_directory, exist_ok=True)
            jax.profiler.start_trace(str(self.profile_directory))
            self._trace_active = True
            # the trace must be closed even if the run dies before
            # log_stats (exception, NaN abort) — stop_trace is global
            # profiler state and a leaked session poisons later runs
            atexit.register(self._stop_trace)

    def _gather_state(self):
        """gather_fields placed like the stepped state. On a distributed
        solver the re-gathered X lands unsharded while every step output
        is pinned to the pencil sharding, and an array's mesh is part of
        its traced type: without the placement the step and projection
        programs each trace and compile twice (once per placement), the
        second projection trace after warmup."""
        X = self.gather_fields()
        mesh = self.dist.mesh
        if mesh is not None \
                and X.shape[0] % mesh.shape[mesh.axis_names[0]] == 0:
            from ..parallel.sharding import pencil_sharding
            X = jax.device_put(X, pencil_sharding(mesh, X.ndim))
        return X

    def step(self, dt, wall_time=None):
        """Advance the system by one timestep (reference: core/solvers.py:683)."""
        dt = float(dt)
        if not np.isfinite(dt):
            # structured health-error path: names iteration/sim_time and
            # dumps the flight recorder, so a CFL-produced NaN timestep
            # leaves the same post-mortem evidence as a NaN state
            raise self.health.invalid_dt(dt)
        if self.iteration == self.warmup_iterations:
            self._end_warmup()
        if self._dd is not None:
            self._dd_advance(1, dt)
            return
        # pick up user modifications of the state fields (version-tracked)
        if self.fields_dirty():
            self.X = self._gather_state()
        # Hermitian/valid-mode re-projection cadence (reference:
        # core/solvers.py:688-692 — enforced for timestepper.steps
        # consecutive iterations so the multistep history stays consistent)
        if self.enforce_real_cadence:
            if self.iteration % self.enforce_real_cadence < self.timestepper.steps:
                self.enforce_hermitian_symmetry()
        first = self._first_advance()
        # the whole host side of one iteration, not only the launch
        with tracing.span("step", {"iteration": self.iteration}):
            self.timestepper.step(dt)
            self._book_group_stacks(first)
            self._after_advance(1, dt)

    def _first_advance(self):
        """Entering an advance: this solver's build phases become the
        thread's current ones (the set-up ledger books the programs first
        called from here to it: `compile_sec`). Returns the `gblocks`
        applications traced so far before the run's first advance, None
        before any later one."""
        phases = self.build_phases
        phases.enter()
        return gblocks_snapshot() if phases.group_stacks is None else None

    def _book_group_stacks(self, first):
        """After the first advance: which way the step program applies
        its `gblocks` stacks (curvilinear.gblocks_tally)."""
        if first is not None:
            self.build_phases.group_stacks = gblocks_tally(since=first)

    def step_many(self, n, dt):
        """
        Advance n constant-dt steps with ONE device dispatch (lax.scan over
        the jitted step). Small problems are host-latency bound at one
        dispatch per step; blocking amortizes it. Scheduled handlers are
        evaluated once at the END of the block, so per-step output cadences
        inside a block coarsen to the block boundary; the Hermitian
        re-projection runs at the block start when the block crosses its
        cadence. Use step() when per-step cadences or adaptive dt matter.
        """
        n = int(n)
        dt = float(dt)
        if not np.isfinite(dt):
            raise self.health.invalid_dt(dt)
        if n <= 0:
            return
        if self.iteration <= self.warmup_iterations < self.iteration + n:
            self._end_warmup()
        if self._dd is not None:
            self._dd_advance(n, dt)   # blocked via DDIVPRunner.step_many
            return
        if self.fields_dirty():
            self.X = self._gather_state()
        cadence = self.enforce_real_cadence
        if cadence:
            r = self.iteration % cadence
            if (n >= cadence or r < self.timestepper.steps
                    or (cadence - r) < n):
                self.enforce_hermitian_symmetry()
        first = self._first_advance()
        with tracing.span("step_many", {"iteration": self.iteration, "n": n}):
            self.timestepper.step_many(n, dt)
            self._book_group_stacks(first)
            self.metrics.inc("step_many_blocks")
            self._after_advance(n, dt)

    def _after_advance(self, n, dt):
        """Host bookkeeping after the timestepper advanced n iterations:
        counters, the cadence-gated probes (the phase sampler sits out the
        dd route; the health probe reads its f32 view), scheduled
        handlers."""
        if self._dd is not None:
            self._dd_defer_pull(self._dd.X)
        else:
            self.defer_scatter(self.X)
        self.snapshot_versions()
        self.problem.sim_time = self.sim_time
        self.iteration += n
        self.dt = dt
        self._metrics_tick(n)
        self.health.tick(n)
        if self._health_error is None:
            # a poisoned step must not flow into scheduled outputs (no
            # NaN-filled checkpoint written as a "good" write)
            self.evaluator.evaluate_scheduled(
                iteration=self.iteration,
                wall_time=time_mod.time() - self.start_time,
                sim_time=self.sim_time, timestep=dt)

    # -------------------------------------------------------------- metrics

    def _metrics_tick(self, n):
        """Per-step metrics hook: count iterations (non-blocking) and run
        the cadence-gated phase sample (the only point that syncs the
        device, and only every SAMPLE_CADENCE-th post-warmup iteration)."""
        m = self.metrics
        if not m.enabled:
            return
        m.observe_steps(n)
        if not (m.sampling and self._dd is None
                and self.warmup_time is not None):
            return
        if getattr(self, "_metrics_warm_pending", False):
            # deferred warm compile (step_many-only driver): sample now and
            # re-anchor the loop window — the block just finished carried
            # the step jit compile and must stay out of per-step rates
            self._metrics_warm_pending = False
            self._try_sample_phases()
            m.reset_loop()
            return
        if m.due():
            self._try_sample_phases()

    def _try_sample_phases(self):
        """_sample_phases with a telemetry firewall: probe failure disables
        sampling (with a warning) instead of killing the simulation.
        Returns whether a sample was recorded."""
        try:
            return self._sample_phases()
        except Exception as exc:
            logger.warning(f"metrics phase sampling disabled: {exc}")
            self.metrics.sampling = False
            return False

    def _sample_phases(self):
        """
        One phase sample: drain outstanding dispatches, then wall-time the
        already-compiled step pieces (timestepper phase probes + the
        dealiased transform roundtrip) on the current state, bracketing
        `block_until_ready`. The transform share of the RHS evaluation is
        measured by the roundtrip probe and subtracted out so
        transform/evaluator/matsolve/transpose sum to ~one step. On fused
        multi-device steps the all_to_all collectives execute inside the
        eval/solve probes, so their cost rides in evaluator/matsolve and
        `transpose` stays 0 — profiler traces (dedalus/transpose/...)
        are the per-collective attribution tool there. Returns True when
        a sample was recorded (False: probes not available yet).
        """
        m = self.metrics
        probes = self.timestepper.phase_probes()
        if probes is None:
            return False
        # the wait for the steps still queued is theirs, not the sampler's
        with tracing.span("metrics/drain"):
            jax.block_until_ready(self.X)
        with tracing.span("metrics/sample"):
            scale = float(getattr(self.timestepper, "stages", 1) or 1)
            proj = self._ensure_project()
            times = {name: m.time_thunk(name, thunk) * s
                     for name, (thunk, s) in probes.items()}
            trans = m.time_thunk("transform", lambda: proj(self.X)) * scale
            rhs = times.get("rhs_eval", 0.0)
            trans = min(trans, rhs) if rhs else trans
            sample = {
                "transform": trans,
                "evaluator": max(rhs - trans, 0.0),
                "matsolve": times.get("matsolve", 0.0),
                "transpose": times.get("transpose", 0.0),
            }
            if "fused_step" in times:
                # the whole fused step program re-measured as its own row:
                # an ALTERNATIVE whole-step attribution that OVERLAPS the
                # split rows above, so metrics excludes it from the phase
                # sum (SUM_PHASES) — fused < sum(split) is the fusion win
                sample["fused"] = times["fused_step"]
            m.add_phase_sample(sample)
        return True

    def flush_metrics(self, extra=None):
        """Block on the state (so the loop window covers the device tail of
        the final dispatch) and flush one telemetry record — appended to
        the JSONL sink when one is configured. Health summary (checks,
        warnings, ok/failed) rides along under the `health` key. Returns
        the record dict."""
        try:
            jax.block_until_ready(self.X)
        except Exception:
            pass
        health_summary = self.health.summary()
        extra = dict(extra or {})
        if health_summary is not None:
            extra.setdefault("health", health_summary)
        resilience = getattr(self, "resilience", None)
        if resilience is not None:
            extra.setdefault("resilience", resilience.summary())
        # retrace-sentinel verdict rides in every telemetry record so the
        # perf trajectory shows compile-hygiene regressions in place
        extra.setdefault("retraces_post_warmup",
                         retrace_mod.sentinel.post_arm_retraces)
        # cold-start phase split (host_assembly/structure/factor/compile
        # seconds + assembly-cache verdict)
        extra.setdefault("build_phases", self.build_phases.record())
        # non-default solve composition / precision ladder: record the
        # resolved plan + the achieved residual of one probe solve (a
        # flush-time dispatch, off the step loop) so every telemetry
        # record carries the accuracy its speedup was bought at
        plan = getattr(self, "_solve_plan", None)
        if plan is not None and (plan.dtype != "native"
                                 or plan.composition != "sequential"):
            extra.setdefault("precision", self._precision_summary())
        # resolved plan provenance: every flushed record names the plan
        # that produced its numbers (ROADMAP item 2; `report` renders
        # pre-provenance rows as plan=unversioned)
        extra.setdefault("plan", self.plan_provenance())
        return self.metrics.flush(extra=extra)

    def plan_provenance(self):
        """The resolved execution plan this solver was built under, as one
        flat telemetry block: fusion composition, solve composition +
        precision ladder, transpose chunking, and the content identity
        the warm pool keys on. Everything here was resolved ONCE in
        `_build_pencil_system`, so the block names the plan the compiled
        programs actually run — not whatever the config says now."""
        block = {"plan_version": 1}
        fusion = getattr(self, "_fusion_plan", None)
        if fusion is not None:
            block["fusion"] = {
                "solve": fusion.solve, "matvec": fusion.matvec,
                "transforms": fusion.transforms, "donate": fusion.donate}
        solve = getattr(self, "_solve_plan", None)
        if solve is not None:
            block["solve_composition"] = solve.composition
            block["solve_dtype"] = solve.dtype
            block["refine_sweeps"] = solve.sweeps
            block["spike_chunks"] = solve.spike_chunks
        chunks = getattr(self, "_transpose_chunks", None)
        if chunks is not None:
            block["transpose_chunks"] = int(chunks)
        key = getattr(self, "assembly_key", None)
        if key:
            block["solver_key"] = str(key)[:16]
        # how the plan was chosen: `config` (user-pinned solve knobs) or
        # `default` (what `auto` resolves to)
        block["plan_source"] = getattr(self, "_plan_source", "default")
        return block

    def _precision_summary(self):
        """The `precision` telemetry block: the resolved solve plan and
        the achieved relative residual of a probe solve against the
        current LHS factorization (None until the first factor)."""
        plan = self._solve_plan
        block = {
            "solve_dtype": plan.dtype,
            "composition": plan.composition,
            "refine_sweeps": plan.sweeps if plan.sweeps is not None
            else getattr(self.ops, "refine", None),
            "refine_tol": plan.tol,
        }
        ts = getattr(self, "timestepper", None)
        aux = getattr(ts, "_lhs_aux", None)
        if aux is None or not hasattr(self.ops, "solve_report"):
            return block
        aux0 = aux[0] if isinstance(aux, list) else aux
        try:
            _, rel = self.ops.solve_report(
                aux0, self.X, mats=(self.M_mat, self.L_mat))
            if rel is not None:
                block["achieved_residual"] = float(np.asarray(rel))
        except Exception:
            pass
        return block

    def evolve_resilient(self, timestep_function=None, dt=None,
                         log_cadence=100, **kw):
        """
        Run the main loop under the resilient driver
        (tools/resilience.ResilientLoop): rolling state-snapshot ring,
        automatic rewind + dt backoff on SolverHealthError, SIGTERM/
        SIGINT-safe durable checkpointing with validated resume, and
        transient-IO retry around checkpoint/telemetry writes. Keyword
        arguments (snapshot_cadence, max_retries, dt_backoff,
        checkpoint_dir, resume, chaos, ...) configure the loop; defaults
        come from the [resilience] config section. Returns the loop's
        summary dict (also attached to flushed telemetry records).
        """
        from ..tools.resilience import ResilientLoop
        loop = ResilientLoop(self, timestep_function=timestep_function,
                             dt=dt, **kw)
        try:
            return loop.run(log_cadence=log_cadence)
        finally:
            self.log_stats()

    def ensemble(self, members, **kw):
        """Build an EnsembleSolver over this (built, undistributed) IVP:
        one compiled, vmapped + mesh-sharded step advancing `members`
        independent copies with per-member initial conditions, RHS
        parameters, and (RK schemes) per-member dt (core/ensemble.py)."""
        from .ensemble import EnsembleSolver
        return EnsembleSolver(self, members, **kw)

    def differentiable(self, wrt=("initial_state",), loss=None,
                       checkpoint_segments=None, **kw):
        """Build a DifferentiableIVP over this (built, undistributed)
        IVP: compiled `jax.grad`-able value-and-grad programs of a
        scalar `loss` of the final state over n constant-dt steps, with
        adjoint pencil solves against the cached LHS factorization and
        `jax.checkpoint`-bounded backprop memory (core/adjoint.py,
        docs/differentiable.md)."""
        from .adjoint import DifferentiableIVP
        return DifferentiableIVP(self, wrt=wrt, loss=loss,
                                 checkpoint_segments=checkpoint_segments,
                                 **kw)

    def evolve(self, timestep_function=None, log_cadence=100):
        """Run the main loop to completion (reference: core/solvers.py:713)."""
        try:
            while self.proceed:
                dt = timestep_function() if timestep_function else self.dt
                if dt is None:
                    raise ValueError(
                        "evolve() requires a timestep_function, or a prior "
                        "solver.step(dt) to set the timestep.")
                self.step(dt)
                if self.iteration % log_cadence == 0:
                    logger.info(f"Iteration={self.iteration}, Time={self.sim_time:.6e}, dt={dt:.6e}")
            if self._health_error is not None:
                logger.error(
                    f"Main loop halted by health monitor: "
                    f"{self._health_error.reason} (error available as "
                    f"solver.health_error)")
        except Exception:
            logger.error("Exception raised, triggering end of main loop.")
            raise
        finally:
            self.log_stats()

    def print_subproblem_ranks(self, max_groups=16, **kw):
        """Rank/conditioning diagnostic of the first `max_groups` pencil
        matrices (reference: solver debug helper). Densifies per group on
        the host — O(S^3) each, so the group count is bounded by default
        (pass max_groups=None for all groups)."""
        subproblems = self.subproblems
        if max_groups is not None and len(subproblems) > max_groups:
            print(f"(showing {max_groups} of {len(subproblems)} groups; "
                  "pass max_groups=None for all)")
            subproblems = subproblems[:max_groups]
        for sp in subproblems:
            L = self.ops.densify_host(self._matrices["L"], sp.index)
            M = self.ops.densify_host(self._matrices["M"], sp.index)
            A = M + L
            print(f"group {sp.group}: rank={np.linalg.matrix_rank(A)}/{A.shape[0]}, "
                  f"cond={np.linalg.cond(A):.2e}")

    def load_state(self, path, index=-1, allow_missing=False,
                   fallback=False):
        """Restore state from an HDF5 checkpoint
        (reference: core/solvers.py:632 load_state).

        Hardened against truncated/corrupt files: failures raise a
        structured `CheckpointError` naming the file and write index
        instead of a raw h5py traceback. With `fallback=True`, a corrupt
        write falls back to the previous writes in the same file (newest
        surviving write wins); `tools.resilience.resume_latest` extends
        the fallback across set files.
        """
        import h5py
        from ..tools.exceptions import CheckpointError
        try:
            f = h5py.File(path, "r")
        except OSError as exc:
            raise CheckpointError(
                f"checkpoint {path} unreadable (truncated or corrupt): "
                f"{exc}", path=path) from exc
        with f:
            try:
                n_writes = len(f["scales/write_number"])
            except KeyError as exc:
                raise CheckpointError(
                    f"checkpoint {path} has no scales/write_number "
                    f"(not a handler file?)", path=path) from exc
            if n_writes == 0:
                raise CheckpointError(
                    f"checkpoint {path} has an empty write index",
                    path=path)
            start = index if index >= 0 else n_writes + index
            if not 0 <= start < n_writes:
                raise CheckpointError(
                    f"checkpoint {path}: write index {index} out of range "
                    f"({n_writes} writes)", path=path, index=index)
            candidates = range(start, -1, -1) if fallback else (start,)
            failures = []
            for idx in candidates:
                try:
                    self._load_write(f, path, idx, allow_missing)
                except CheckpointError as exc:
                    if not fallback:
                        raise
                    failures.append(str(exc))
                    logger.warning(f"checkpoint write unusable, "
                                   f"falling back: {exc}")
                    continue
                if failures:
                    logger.info(f"loaded write {idx} of {path} after "
                                f"{len(failures)} fallback(s)")
                write = int(np.asarray(f["scales/write_number"])[idx])
                break
            else:
                raise CheckpointError(
                    f"checkpoint {path}: no loadable write at or before "
                    f"index {index} ({'; '.join(failures)})",
                    path=path, index=index)
        self.X = self.gather_fields()
        return write, self.dt

    def _load_write(self, f, path, idx, allow_missing):
        """Load ONE write of an open checkpoint file into the solver,
        wrapping data-level corruption (h5py OSError/ValueError on torn
        datasets) as CheckpointError. Scalar clocks are restored last-
        writer-wins only after every field read back cleanly."""
        from ..tools.exceptions import CheckpointError
        try:
            sim_time = float(np.asarray(f["scales/sim_time"])[idx])
            iteration = int(np.asarray(f["scales/iteration"])[idx])
            dt = float(np.asarray(f["scales/timestep"])[idx]) \
                if "scales/timestep" in f else None
            tasks = f["tasks"]
            data = {}
            for var in self.state:
                if var.name not in tasks:
                    if allow_missing:
                        continue
                    raise KeyError(
                        f"State variable {var.name} not found in {path}")
                ds = tasks[var.name]
                if len(ds) <= idx:
                    raise CheckpointError(
                        f"checkpoint {path} write {idx}: task "
                        f"'{var.name}' has only {len(ds)} write(s) "
                        f"(torn write)", path=path, index=idx)
                layout = ds.attrs.get("layout", "g")
                if isinstance(layout, bytes):
                    layout = layout.decode()
                data[var.name] = (layout, np.asarray(ds[idx]))
        except CheckpointError:
            raise
        except (OSError, ValueError, IndexError) as exc:
            raise CheckpointError(
                f"checkpoint {path} write {idx} unreadable: {exc}",
                path=path, index=idx) from exc
        for var in self.state:
            if var.name in data:
                layout, arr = data[var.name]
                var[layout if layout in ("c", "g") else "g"] = arr
        self.sim_time = self.initial_sim_time = sim_time
        self.iteration = self.initial_iteration = iteration
        self.dt = dt
        logger.info(f"Loading iteration: {iteration} (write index {idx})")

    def log_stats(self, format=".4g"):
        """Log run statistics including the reference's throughput metric
        (reference: core/solvers.py:755-778 log_stats, modes-stages/cpu-sec),
        and dump profile artifacts when enabled (reference:
        core/solvers.py:780-806 dump_profiles)."""
        log_time = time_mod.time()
        total = log_time - self.init_time
        self._stop_trace()
        logger.info(f"Final iteration: {self.iteration}")
        logger.info(f"Final sim time: {self.sim_time}")
        logger.info(f"Setup time (init - iter 0): {self.start_time - self.init_time:{format}} sec")
        bp = self.build_phases.record()
        for line in metrics_mod.format_build_phases(bp, format):
            logger.info(line)
        phases = {"setup": self._setup_time,
                  "total": total}
        if self.iteration > self.warmup_iterations and self.warmup_time:
            warmup = self.warmup_time - self.start_time
            run = log_time - self.warmup_time
            iters = self.iteration - self.warmup_iterations
            logger.info(f"Warmup time (iter 0-{self.warmup_iterations}): {warmup:{format}} sec")
            logger.info(f"Run time (iter {self.warmup_iterations}-end): {run:{format}} sec")
            G, S = self.pencil_shape
            modes = G * S
            stages = self.timestepper.stages if hasattr(self.timestepper, "stages") else 1
            rate = modes * stages * iters / run if run > 0 else 0.0
            logger.info(f"Speed: {rate:.2e} mode-stages/sec")
            phases.update({"warmup": warmup, "run": run, "run_iterations": iters,
                           "mode_stages_per_sec": rate})
        else:
            logger.info(f"Total time: {total:{format}} sec")
        record = None
        if self.metrics.enabled:
            record = self.flush_metrics()
            if record and record.get("phase_samples"):
                for line in metrics_mod.format_phase_table(record):
                    logger.info(line)
        health_summary = self.health.summary()
        if health_summary is not None:
            status = "ok" if health_summary.get("ok") else \
                f"FAILED ({health_summary.get('reason')})"
            logger.info(f"Health: {status}, "
                        f"{health_summary.get('checks', 0)} checks, "
                        f"{health_summary.get('warnings', 0)} warnings")
        if self.profile:
            import json
            os.makedirs(self.profile_directory, exist_ok=True)
            if record:
                phases["step_metrics"] = record
            with open(self.profile_directory / "phase_times.json", "w") as f:
                json.dump(phases, f, indent=2)


class LinearBoundaryValueSolver(SolverBase):
    """LBVP solver (reference: core/solvers.py:324)."""

    matrices = ("L",)

    @metrics_mod.timed_init
    def __init__(self, problem, matsolver=None, **kw):
        super().__init__(problem, matsolver=matsolver, **kw)
        with self.build_phases.scope("factor"):
            self.L_mat = self.ops.to_device(self._matrices["L"],
                                            self.pencil_dtype)
            self._aux = self.ops.factor(self.L_mat)
        # RHS-evaluator construction is expression compilation, not
        # factorization: outside the factor scope so factor_sec stays
        # comparable across solver types (IVP builds eval_F unscoped too)
        self.eval_F = self.build_rhs_evaluator("F")
        from ..tools.jitlift import lifted_jit, device_constant
        mask_np, rd = self.valid_row_mask, self.real_dtype
        eval_F, ops = self.eval_F, self.ops

        def _rhs_solve(aux, X0, extra):
            mask = device_constant(mask_np, dtype=rd)
            return ops.solve(aux, eval_F(X0, extra_arrays=extra) * mask)

        self._rhs_solve = lifted_jit(_rhs_solve)
        self.iteration = 0

    def solve(self):
        """Solve L.X = F with current NCC/RHS fields
        (reference: core/solvers.py:369)."""
        self.build_phases.enter()
        X0 = self.gather_fields()
        X = self._rhs_solve(self._aux, X0, self.rhs_extra())
        self.scatter_fields(X)
        self.iteration += 1
        return self.state


class NonlinearBoundaryValueSolver(SolverBase):
    """Newton-Kantorovich NLBVP solver (reference: core/solvers.py:418)."""

    matrices = ("L",)
    # Jacobians rebuild around the moving state every Newton iteration;
    # persisting each one would churn the on-disk cache for zero reuse.
    cache_ok = False

    @metrics_mod.timed_init
    def __init__(self, problem, matsolver=None, **kw):
        # Matrices are in terms of the perturbation variables.
        self._problem_ref = problem
        super().__init__(problem, matsolver=matsolver, **kw)
        self.iteration = 0
        # residual expressions converted to equation-block domains
        self._residual_exprs = {}
        for block in self.equations:
            for member, cond in block["members"]:
                if member.get("residual") is not None:
                    self._residual_exprs[id(member)] = problem._wrap(
                        member["residual"], block["domain"])

    def matrix_variables(self, problem):
        return problem.perturbations

    @property
    def state(self):
        return self.problem.variables

    def _eval_residual(self):
        cache = getattr(self, "_residual_cache", None)
        if cache is None:
            exprs = self._residual_exprs
            eval_R = self.build_rhs_evaluator(
                get_expr=lambda member: exprs.get(id(member)))
            from ..tools.jitlift import lifted_jit, device_constant
            mask_np, rd = self.valid_row_mask, self.real_dtype
            # memoized via _residual_cache just below (hand-rolled guard
            # the static pass cannot see)
            fn = lifted_jit(  # dedalus-lint: disable=DTL003
                lambda extra: eval_R(None, extra_arrays=extra)
                * device_constant(mask_np, dtype=rd))
            cache = self._residual_cache = (eval_R.extra_fields, fn)
        fields, fn = cache
        return fn([f.coeff_data() for f in fields])

    def newton_iteration(self, damping=1.0):
        """One Newton step: solve dG.dX = -G, update variables
        (reference: core/solvers.py:470)."""
        # Rebuild Jacobian matrices around the current state (NCC data moves;
        # the structural path is re-selected since the pattern can change).
        self.build_phases.enter()
        self._build_pencil_system()
        L = self.ops.to_device(self._matrices["L"], self.pencil_dtype)
        aux = self.ops.factor(L)
        F = -self._eval_residual()
        dX = self.ops.solve(aux, F)
        self._last_perturbation = dX
        arrays = scatter_state(self.layout, self.variables, dX)
        for var, pert in zip(self.problem.variables, self.variables):
            var.preset_coeff(var.coeff_data() + damping * arrays[state_key(pert)])
            var.mark_modified()
        self.iteration += 1

    def perturbation_norm(self, order=2):
        """Norm of the last Newton update dX (reference convergence metric)."""
        if getattr(self, "_last_perturbation", None) is None:
            return np.inf
        dX = np.asarray(self._last_perturbation)
        if order == np.inf:
            return np.max(np.abs(dX))
        return np.sum(np.abs(dX) ** order) ** (1.0 / order)

    def residual_norm(self, order=2):
        data = np.asarray(self._eval_residual())
        return np.sum(np.abs(data) ** order) ** (1.0 / order)


class EigenvalueSolver(SolverBase):
    """EVP solver: lam*M.X + L.X = 0 (reference: core/solvers.py:134)."""

    matrices = ("M", "L")
    lazy_ok = True

    @metrics_mod.timed_init
    def __init__(self, problem, matsolver=None, **kw):
        super().__init__(problem, matsolver=matsolver, **kw)
        self.eigenvalues = None
        self.eigenvectors = None
        self.eigenvalue_subproblem = None

    def _group_csr(self, subproblem):
        """
        {name: scipy CSR} of one subproblem's pencil matrices, sparse
        end-to-end: lazy mode assembles the single group on demand; the
        batched shared-pattern store scatters directly to CSR; only the
        banded/dense device stores densify (reference: sparse per-
        subproblem matrices, core/subsystems.py:493-598).
        """
        import scipy.sparse as sps
        names = self.matrices
        G, S = self.pencil_shape
        if self._lazy:
            cache = getattr(self, "_lazy_cache", None)
            if cache is not None and cache[0] == subproblem.index:
                return cache[1]
            coos, _, _ = assemble_group_coos(
                subproblem, self.equations, self.variables, names)
            out = {name: sps.csr_matrix(
                (vals, (rows, cols)), shape=(S, S))
                for name, (rows, cols, vals) in coos.items()}
            self._lazy_cache = (subproblem.index, out)
            return out
        if self._batched is not None:
            pr, pc, vals, row_valid, col_valid = self._batched
            g = subproblem.index
            out = {}
            for name in names:
                mat = sps.csr_matrix((vals[name][g], (pr, pc)), shape=(S, S))
                out[name] = mat
            inv_rows = np.flatnonzero(~row_valid[g])
            inv_cols = np.flatnonzero(~col_valid[g])
            if len(inv_rows):
                closure = sps.csr_matrix(
                    (np.ones(len(inv_rows)), (inv_rows, inv_cols)),
                    shape=(S, S))
                out[names[-1]] = out[names[-1]] + closure
            return out
        return {name: sps.csr_matrix(
            self.ops.densify_host(self._matrices[name], subproblem.index))
            for name in names}

    def solve_dense(self, subproblem, left=False, normalize_left=True,
                    rebuild_matrices=False, **kw):
        """Dense generalized eigensolve for one pencil
        (reference: core/solvers.py:180 solve_dense). `rebuild_matrices`
        reassembles M/L around the current NCC field data (parameter
        continuation, e.g. the Mathieu example's q sweep)."""
        self.build_phases.enter()
        if rebuild_matrices:
            # parameter-continuation rebuilds change the NCC data every
            # call: each would hash to a never-reloaded fresh cache key,
            # churning the persistent store and LRU-evicting useful
            # entries — so rebuilds opt out (same rationale as NLBVP)
            self.cache_ok = False
            if self._lazy:
                self._lazy_cache = None
            else:
                self._build_pencil_system()
        mats = self._group_csr(subproblem)
        L = mats["L"].toarray()
        M = mats["M"].toarray()
        out = scipy.linalg.eig(L, b=-M, left=left, **kw)
        if left:
            evals, evecs_left, evecs = out
        else:
            evals, evecs = out
        # drop infinite eigenvalues from identity-closure/tau rows
        finite = np.isfinite(evals)
        self.eigenvalues = evals[finite]
        self.eigenvectors = evecs[:, finite]
        if left:
            self.left_eigenvectors = evecs_left[:, finite]
            if normalize_left:
                norms = np.einsum("ij,ij->j", np.conj(self.left_eigenvectors),
                                  -M @ self.eigenvectors)
                safe = np.where(np.abs(norms) > 0, norms, 1.0)
                self.left_eigenvectors = self.left_eigenvectors / np.conj(safe)
        self.eigenvalue_subproblem = subproblem
        return self.eigenvalues

    def solve_sparse(self, subproblem, N, target, left=False,
                     rebuild_matrices=False, **kw):
        """Sparse shift-invert eigensolve around `target`
        (reference: core/solvers.py:225 solve_sparse)."""
        self.build_phases.enter()
        from ..tools.array import scipy_sparse_eigs
        if rebuild_matrices:
            # see solve_dense: continuation rebuilds must not churn the
            # persistent assembly cache
            self.cache_ok = False
            if self._lazy:
                self._lazy_cache = None
            else:
                self._build_pencil_system()
        mats = self._group_csr(subproblem)
        L, M = mats["L"], mats["M"]
        out = scipy_sparse_eigs(A=L, B=-M, N=N, target=target, left=left, **kw)
        if left:
            self.eigenvalues, self.eigenvectors, self.left_eigenvalues, \
                self.left_eigenvectors = out
        else:
            self.eigenvalues, self.eigenvectors = out
        self.eigenvalue_subproblem = subproblem
        return self.eigenvalues

    def set_state(self, index, subproblem=None):
        """Load eigenvector `index` into the state fields
        (reference: core/solvers.py:296 set_state)."""
        subproblem = subproblem or self.eigenvalue_subproblem
        G, S = self.pencil_shape
        X = np.zeros((G, S), dtype=np.complex128)
        X[subproblem.index] = self.eigenvectors[:, index]
        arrays = scatter_state(self.layout, self.variables, jnp.asarray(X))
        for var in self.variables:
            data = arrays[state_key(var)]
            if not np.iscomplexobj(np.asarray(var.data)):
                data = data.real
            var.preset_coeff(jnp.asarray(data).astype(var.data.dtype))
            var.mark_modified()

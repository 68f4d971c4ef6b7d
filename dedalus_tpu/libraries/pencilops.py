"""
Structured batched pencil operators: the device-side representation of the
per-group LHS matrices and their factorization/solve algorithms.

The reference solves each pencil's sparse matrix with pivoted SuperLU on the
host (reference: dedalus/libraries/matsolvers.py:126-194, ScipyBanded :187,
Woodbury :285). The TPU-native equivalents here treat the pencil index G as
an MXU batch dimension and exploit structure instead of general sparsity:

  DenseOps  — (G, S, S) dense matrices; factor/solve delegate to the
              registered batched matsolvers (inverse / LU / refined).
  BandedOps — the mode-interleaved, matching-aligned permutation
              (core/subsystems.MatrixStructure) makes every true row
              banded; dense rows (BCs, gauges) are replaced by identity
              "pin" rows and restored by a rank-t Woodbury correction
              (reference Woodbury: libraries/matsolvers.py:285-316).
              Storage is (G, D, n) diagonals plus the pinned-row block
              Vt (G, t, n). The banded factorization is a blocked
              windowed-partial-pivoting LU (the batched analogue of
              LAPACK dgbtrf, reference matsolver ScipyBanded) over
              q-wide blocks via lax.scan; solves are two block
              substitution scans plus the t x t capacitance solve.
              Optional iterative-refinement sweeps polish the result
              using cheap banded matvecs.

All methods are pure jnp functions safe to trace inside jit; the structure
metadata (permutations, band offsets, block size, pin positions) is
host-static.
"""

import logging
import threading

import numpy as np
import jax
import jax.numpy as jnp
import jax.scipy.linalg as jsl
from jax.sharding import PartitionSpec

from . import solvecomp
from .matsolvers import (BatchedInverseRefined, batched_matvec, get_solver,
                         refined_ladder)
from ..tools.compat import shard_map
from ..tools.config import config
from ..tools.array import zeropad
from ..tools import tracing

logger = logging.getLogger(__name__)

# ------------------------------------------------------- pencil-mesh routing
#
# XLA's SPMD partitioner cannot partition the pivoted-LU custom calls
# (lu_solve's pivot gather/scatter loop, triangular_solve): with the pencil
# batch sharded over a mesh, a plain jitted factor/solve lowers as
# all-gather + replicated full-batch solve — the exact failure local_fft
# (core/meshctx.py) guards against for ffts. The step bodies publish the
# active pencil mesh here at trace time; the batched dense factor/solve
# funnels below then run inside shard_map so each device factors/solves
# only its own group block. EnsembleSolver (core/ensemble.py) reuses the
# same routing with its member axis as the leading batch dimension.

_PENCIL_MESH = threading.local()


class pencil_mesh:
    """Trace-time context: batched factor/solve calls under this context
    run inside shard_map over the leading batch axis of `mesh`'s first
    axis (or `axis_name`). `mesh=None` INHERITS any active context (so
    an undistributed solver's factor/solve bodies traced inside an outer
    pencil context — the 2-D batch x pencil fleet, core/ensemble.py —
    keep the outer routing); with no outer context it is a no-op and
    unsharded traces compile identically to before."""

    def __init__(self, mesh, axis_name=None):
        self.inherit = mesh is None
        self.state = None if mesh is None else \
            (mesh, axis_name or mesh.axis_names[0])

    def __enter__(self):
        self.prev = getattr(_PENCIL_MESH, "state", None)
        if not self.inherit:
            _PENCIL_MESH.state = self.state
        return getattr(_PENCIL_MESH, "state", None)

    def __exit__(self, *exc):
        _PENCIL_MESH.state = self.prev


def active_pencil_mesh():
    return getattr(_PENCIL_MESH, "state", None)


# -------------------------------------------------- adjoint solve funnel
#
# The batched pivoted-LU solves are opaque to JAX's autodiff at the
# factorization boundary: the factors (aux) are precomputed OUTSIDE the
# differentiated program (they are value-dependent host dispatches), and
# letting autodiff transpose the solve's internals op-by-op would drag
# the substitution scans through linearization for no reason. The
# mathematical fact is simpler: x = A^-1 f is LINEAR in f, and the vjp
# of a linear solve is one more linear solve against the SAME matrix,
# transposed. Every ops.solve therefore routes through one
# jax.custom_vjp whose backward pass is `solve_transpose` — an adjoint
# solve reusing the cached LHS factors (core/adjoint.py is the
# consumer; the primal lowering is unchanged, so forward-only stepping
# compiles exactly as before).
#
# Factors and matrices receive ZERO cotangents: gradients w.r.t. the
# M/L assembly data are not implemented (the factorization is outside
# the trace; see docs/differentiable.md for the contract).

def _zeros_like_tree(tree):
    return jax.tree.map(jnp.zeros_like, tree)


def _adjoint_solve_primal(ops, aux, rhs, mats):
    return ops._solve_impl(aux, rhs, mats)


_adjoint_solve = jax.custom_vjp(_adjoint_solve_primal, nondiff_argnums=(0,))


def _adjoint_solve_fwd(ops, aux, rhs, mats):
    # residuals are references to the already-resident factor buffers,
    # never copies — the backward solve reuses them in place
    return ops._solve_impl(aux, rhs, mats), (aux, mats)


def _adjoint_solve_bwd(ops, res, ct):
    aux, mats = res
    ct_rhs = ops.solve_transpose(aux, ct, mats=mats)
    return (_zeros_like_tree(aux), ct_rhs, _zeros_like_tree(mats))


_adjoint_solve.defvjp(_adjoint_solve_fwd, _adjoint_solve_bwd)


class AdjointSolveOps:
    """Shared solve surface of the pencil-ops classes: the public `solve`
    is the custom-VJP funnel above; `solve_transpose` is its backward
    pass (and a public API in its own right — data assimilation codes
    want A^T solves against the forward factorization)."""

    def solve(self, aux, rhs, mats=None):
        """Solve A x = rhs against the cached factorization. Linear in
        `rhs` with a registered custom VJP: the backward pass is
        `solve_transpose` against the same factors, and aux/mats get
        zero cotangents (M/L data is not differentiable)."""
        return _adjoint_solve(self, aux, rhs, mats)

    def solve_transpose(self, aux, rhs, mats=None):
        """Solve A^T x = rhs against the SAME factorization: the solve
        is linear in its RHS, so its transpose re-expresses the compiled
        substitution chain transposed — triangular solves against the
        transposed factors, run in reverse order, plus the transposed
        Woodbury/refinement corrections — without ever refactoring (the
        adjoint of a linear solve is a linear solve with the same
        matrix). Routed through jax.vjp rather than jax.linear_transpose
        because raw `lax.scan` equations (the blocked banded
        substitutions) carry no linearity flags for the direct transpose
        rule; linearizing first marks them. The linearization point is
        zeros, so every primal-side value is a DCE-able constant and the
        compiled backward contains just the transposed solve."""
        with jax.named_scope(f"dedalus/matsolve/{self.kind}.solve_T"):
            _, f_vjp = jax.vjp(
                lambda r: self._solve_impl(aux, r, mats),
                jnp.zeros_like(rhs))
            (out,) = f_vjp(rhs)
            return out


def shard_groups(fn, G, *args):
    """
    Run `fn(*args)` with the length-G leading batch axis sharded over the
    active pencil mesh (each device computes its local block; zero
    collectives inside). Falls back to a direct call when no mesh context
    is active, G does not divide the mesh axis, or any array leaf does not
    lead with the batch axis (e.g. the chunked banded factor slabs, whose
    leading dim is the chunk count — those rely on GSPMD propagation).
    Scalar leaves ride along replicated.
    """
    state = active_pencil_mesh()
    if state is None:
        return fn(*args)
    mesh, name = state
    if G % mesh.shape[name]:
        return fn(*args)
    spec = PartitionSpec(name)

    def spec_of(leaf):
        ndim = getattr(leaf, "ndim", 0)
        if ndim == 0:
            return PartitionSpec()
        return spec if leaf.shape[0] == G else None

    in_specs = jax.tree.map(spec_of, args)
    if any(s is None for s in jax.tree.leaves(
            in_specs, is_leaf=lambda x: x is None)):
        return fn(*args)
    return shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=spec)(*args)


class DenseOps(AdjointSolveOps):
    """Dense (G, S, S) pencil operators (small problems / fallback)."""

    kind = "dense"

    def __init__(self, matsolver=None, solve_plan=None):
        # solve-composition/precision plan: callers in a solver build
        # pass the plan the solver resolved ONCE (solver._solve_plan);
        # standalone constructions resolve fresh. The scan compositions
        # are inert on the dense path (there is no substitution scan to
        # restructure — accepted as no-ops so one [fusion] config drives
        # mixed dense/banded fleets); the precision ladder routes the
        # solve through the refined low-dtype inverse + f64 residual
        # polish (matsolvers.refined_ladder).
        if solve_plan is None:
            solve_plan = solvecomp.resolve_solve_plan()
        self._solve_plan = solve_plan
        self._composition = "sequential"
        if solve_plan.dtype != "native":
            self.solver_cls = refined_ladder(solve_plan)
        else:
            self.solver_cls = get_solver(matsolver)

    def to_device(self, host_mat, dtype):
        return jnp.asarray(host_mat, dtype=dtype)

    def matvec(self, A, X):
        with jax.named_scope("dedalus/matsolve/dense.matvec"):
            return batched_matvec(A, X)

    def matvec_pair(self, M, L, X):
        """(M @ X, L @ X) — the fused-step pair surface (core/fusedstep).
        Dense matvecs share nothing to factor out, so this is the two
        products (bitwise identical to separate calls by construction)."""
        with jax.named_scope("dedalus/matsolve/dense.matvec_pair"):
            return batched_matvec(M, X), batched_matvec(L, X)

    def lincomb(self, a, A, b, B):
        return a * A + b * B

    def scale(self, a, A):
        return a * A

    def factor(self, A):
        with jax.named_scope("dedalus/matsolve/dense.factor"):
            return shard_groups(self.solver_cls.factor, A.shape[0], A)

    def factor_lincomb(self, a, A, b, B):
        return self.factor(self.lincomb(a, A, b, B))

    def _solve_impl(self, aux, rhs, mats=None):
        with jax.named_scope("dedalus/matsolve/dense.solve"):
            return shard_groups(self.solver_cls.solve, rhs.shape[0],
                                aux, rhs)

    def solve_report(self, aux, rhs, mats=None):
        """Diagnostic solve + achieved relative residual as a device
        scalar (None when this aux carries no reconstructible matrix) —
        the flush-time `precision` telemetry probe and the benchmark
        accuracy rows. Never called on the step path."""
        x = self.solve(aux, rhs, mats=mats)
        if not (isinstance(self.solver_cls, type)
                and issubclass(self.solver_cls, BatchedInverseRefined)):
            return x, None
        return x, jnp.max(self.solver_cls.residual(aux, x, rhs))

    def densify_host(self, host_mat, g):
        return np.asarray(host_mat[g])


def device_memory_bytes():
    """Memory of the default device, where the backend reports a limit
    (a TPU does, the CPU does not: None)."""
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("bytes_limit")


@jax.tree_util.register_pytree_node_class
class BandedMatrix:
    """
    One pencil matrix in trimmed banded + pinned-row storage: only the
    structurally nonzero diagonals are kept (`dsel` maps stored rows to the
    shared 0..nd-1 diagonal lattice), and an all-zero pinned-row block is
    dropped entirely. The mass matrix M typically occupies a few diagonals
    of the lattice the stiffness L defines, so trimming cuts both the
    storage and what a band product reads: `BandedOps._band_mv` streams
    every STORED diagonal once, zeros included (a stored diagonal's zero
    runs are not trimmed).
    """

    def __init__(self, bands, Vt, dsel):
        self.bands = bands    # (G, len(dsel), n_store) — ASSEMBLED width
        self.Vt = Vt          # (G, t, n_store) or None
        self.dsel = tuple(int(d) for d in dsel)

    def tree_flatten(self):
        return (self.bands, self.Vt), self.dsel

    @classmethod
    def tree_unflatten(cls, dsel, children):
        bands, Vt = children
        return cls(bands, Vt, dsel)


class BandedOps(AdjointSolveOps):
    """
    Banded + pinned-row pencil operators.

    Host representation per matrix name (core/subsystems.build_banded_arrays):
        bands : (G, D, n_store)  diagonals of the matched (true-banded)
                rows, offsets -kl..ku; bands[g, d, p] = A'[g, p, p+d-kl].
                n_store is the ASSEMBLED width (structural NB*q); factor
                transients and solves run at the re-blocked width n_pad
                >= n_store when BANDED_MIN_Q raises q.
        Vt    : (G, t, n_store)  true content of the pinned rows

    with A' the row/column-permuted matrix. The represented matrix is
    A' = B + sum_i e_{p_i} Vt_i^T where B carries zero rows at the pin
    positions. Factorization pins those rows (B~ = B + sum_i e_{p_i}
    e_{p_i}^T, well-conditioned: pins constrain the coefficients the
    boundary rows would otherwise leave free) and applies Woodbury:
        A'^-1 = B~^-1 - B~^-1 E (I + (Vt - E^T) B~^-1 E)^-1 (Vt - E^T) B~^-1
    """

    kind = "banded"

    def __init__(self, structure, refine=1, fusion=None, solve_plan=None):
        st = structure
        # Structures arrive either freshly finalized or rehydrated from
        # the persistent assembly cache (MatrixStructure.from_state);
        # validate the contract HERE so a drifted/hand-edited cache
        # payload fails with a clear message instead of an AttributeError
        # deep inside a factorization scan.
        missing = [attr for attr in
                   ("S", "NB", "q", "t_pins", "kl", "ku", "row_perm",
                    "col_perm", "pinned_positions")
                   if getattr(st, attr, None) is None]
        if missing:
            raise ValueError(
                f"BandedOps: structure is missing {missing} (corrupt or "
                f"stale assembly-cache payload?)")
        self.st = st
        self.refine = int(refine)
        # fused-step switches: callers in a solver build pass the plan
        # the solver resolved ONCE (solver._fusion_plan) so mid-build
        # config edits can never split one solver across two
        # compositions; standalone constructions resolve fresh.
        # FUSED_SOLVE engages on the factor_lincomb paths only (the IVP
        # step loop, where the factor-time inversion cost is amortized);
        # plain factor() keeps the backward-stable pivoted substitution
        # for the one-factor-one-solve solver classes.
        if fusion is None:
            from ..core.fusedstep import resolve_fusion
            fusion = resolve_fusion()
        plan = fusion
        self._fused_solve = plan.solve
        # `auto` (not an explicit `on`) yields to the device's memory at
        # first factor, when the group count is known (_ensure_fused_fits)
        self._fused_solve_auto = plan.solve and (
            not config.has_section("fusion")
            or config["fusion"].get("FUSED_SOLVE", "auto").strip().lower()
            == "auto")
        self._fused_matvec = plan.matvec
        # solve-composition/precision plan (libraries/solvecomp.py):
        # like `fusion`, resolved once per solver build and passed in so
        # a mid-build config edit can never split one solver across two
        # compositions; the plan token rides the assembly/pool keys.
        if solve_plan is None:
            solve_plan = solvecomp.resolve_solve_plan()
        self._solve_plan = solve_plan
        if solve_plan.composition != "sequential" and not plan.solve:
            raise ValueError(
                f"[fusion] SOLVE_COMPOSITION = {solve_plan.composition} "
                "requires FUSED_SOLVE: the restructured sweeps run over "
                "the precomposed FwdOp/BwdOp GEMM operators")
        self._composition = solve_plan.composition if plan.solve \
            else "sequential"
        self._spike_chunks_cfg = solve_plan.spike_chunks
        self._ladder = solve_plan.dtype != "native"
        # refinement schedule: explicit [precision] sweeps win; None
        # defers to the legacy `refine` count (the PR-12 fused tolerance
        # class is calibrated against it)
        self._refine_sweeps = solve_plan.sweeps
        self._refine_tol = solve_plan.tol
        # pencil-batch chunking (lax.map over G-chunks): bounds the
        # factorization's HLO temp footprint AND forces the scan-stacked
        # factor outputs into flat (Gc, 2q*q) layouts that tile (8, 128)
        # cleanly — full-G factors otherwise materialize as 4-D
        # (NB, G, 2q, q) buffers whose q-sized minor dims pad 2-4x on TPU.
        # Chosen at factor time (needs G and the dtype); solve re-derives
        # the count from the aux's shapes — this attr is diagnostic only.
        self._g_chunks = 1
        # (rows per tile, tiles) of the band product's scan as last
        # traced (_band_tiles reads them off the stores' shapes) —
        # diagnostic, as _g_chunks is
        self._band_tiling = None
        # Re-blocking: BANDED_MIN_Q = <integer> re-blocks the SAME banded
        # lattice with a larger q (fewer, fatter scan steps). The band
        # STORAGE keeps its assembled width (n_store); factor transients
        # pad to the re-blocked width. q only has to satisfy kl, ku <= q,
        # which growing q preserves. `0` and `auto` keep the structural q:
        # until PR 33 `auto` doubled q on a TPU while the factor slab fit
        # BANDED_Q_BUDGET_GB, written when a scan body cost 88 us. At the
        # microsecond a body costs now, the sphere shallow-water pencils
        # (256 groups of 1,536, q = 7) ran 8.5 times slower re-blocked to
        # q = 224 on a v5e, each body reading its 7 q^2 operator (PERF.md,
        # PR 33), and float32 went wrong at a re-blocked q = 64 (PR 29).
        min_q = config["linear algebra"].get(
            "BANDED_MIN_Q", "0").strip().lower()
        self.n = st.S                  # true system size
        self.n_store = st.NB * st.q    # band-array width as assembled
        self.t = st.t_pins
        self.kl = st.kl
        self.ku = st.ku
        self.nd = st.kl + st.ku + 1    # number of stored diagonals
        # static permutation index arrays
        self.row_perm = np.asarray(st.row_perm)   # permuted pos -> orig index
        self.col_perm = np.asarray(st.col_perm)
        self.pos_col = np.argsort(self.col_perm)  # orig index -> permuted pos
        self.pin_pos = np.asarray(st.pinned_positions)
        self._set_q(st.q if min_q in ("0", "auto", "")
                    else max(st.q, int(min_q)))

    def _set_q(self, q):
        """(Re)derive the blocking-dependent geometry for block size q."""
        self.q = int(q)
        self.n_pad = -(-self.n_store // self.q) * self.q
        self.NB = self.n_pad // self.q
        # static block-gather indices: block[o][ri, ci] reads
        # bands[:, o*q + ci - ri + kl, block_row*q + ri]
        ri = np.arange(self.q)[:, None]
        ci = np.arange(self.q)[None, :]
        self._blk_idx = {}
        for o in (-1, 0, 1):
            d = o * self.q + ci - ri + self.kl       # (q, q)
            valid = (d >= 0) & (d < self.nd)
            self._blk_idx[o] = (np.where(valid, d, 0), valid)

    def _ensure_fused_fits(self, G, itemsize):
        """[fusion] FUSED_SOLVE = auto, decided against the device the
        factors will live on: the precomposed substitution operators are
        7q^2 numbers per block row (FwdOp 4q^2 + BwdOp 3q^2) against the
        packed pivoted factors' 4q^2 (_factor_interior). Where the
        operators, the two band stores and the Woodbury blocks together
        pass three quarters of the device's memory, the factors stay
        packed and the solve is the pivoted substitution. RB 2048x1024 f32
        on a 16 GB v5e: 8.6 GB of operators + 4.4 of bands + 1.1 of
        Woodbury blocks = 14.1 GB
        resident, and a stage solve's own temporaries are 3.5 GB (compiled
        for a described v5e, PR 28); packed, 4.5 + 4.4 + 1.1 = 10.0 GB. A
        quarter of the device is what the step's temporaries take there.
        An explicit `on` or a restructured composition (it consumes the
        operators) is never overridden; a backend that reports no limit
        (the CPU) keeps the operators."""
        if not self._fused_solve_auto or self._composition != "sequential":
            return
        limit = device_memory_bytes()
        if not limit:
            return
        q = self.q
        nb = -(-self.n_store // q)
        bands = 2 * G * self.nd * self.n_store
        woodbury = 3 * G * self.t * nb * q
        operators = 7 * G * nb * q * q
        if (bands + woodbury + operators) * itemsize > 0.75 * limit:
            self._fused_solve = self._fused_solve_auto = False

    # ------------------------------------------------------------ host side

    def to_device(self, host_arrs, dtype):
        """Host band store -> trimmed BandedMatrix. Accepts pre-trimmed
        storage (a "dsel" key, the assembly fast path) or a full
        (G, nd, n_pad) lattice, trimmed here."""
        bands = host_arrs["bands"]
        Vt = host_arrs["Vt"]
        if "dsel" in host_arrs:
            dsel = list(host_arrs["dsel"])
            trimmed = jnp.asarray(bands, dtype=dtype)
        else:
            dsel = [d for d in range(self.nd) if np.any(bands[:, d, :])]
            if not dsel:
                dsel = [self.kl]
            # fancy-index slice is already a fresh contiguous array
            trimmed = jnp.asarray(bands[:, dsel, :], dtype=dtype)
        Vt_dev = None
        if self.t and np.any(Vt):
            Vt_dev = jnp.asarray(Vt, dtype=dtype)
        rows, tiles = self._band_tiles(trimmed)
        logger.info(
            f"Banded store on the device: {len(dsel)} of {self.nd} "
            f"diagonals x {trimmed.shape[-1]} rows x {trimmed.shape[0]} "
            f"groups ({trimmed.nbytes / 1e6:.1f} MB), band product in "
            f"{tiles} tile(s) of {rows} rows")
        return BandedMatrix(trimmed, Vt_dev, dsel)

    def densify_host(self, host_arrs, g):
        """Reconstruct the original-ordering dense (S, S) matrix (host)."""
        S = self.n
        W = host_arrs["bands"].shape[-1]
        Ap = np.zeros((W, W), dtype=host_arrs["bands"].dtype)
        bands = host_arrs["bands"][g]
        dsel = host_arrs.get("dsel", range(self.nd))
        for i, d in enumerate(dsel):
            off = d - self.kl
            rr = np.arange(max(0, -off), min(W, W - off))
            Ap[rr, rr + off] = bands[i, rr]
        if self.t:
            Ap[self.pin_pos, :] += host_arrs["Vt"][g]
        Ap = Ap[:S, :S]
        # un-permute: Ap[i, j] = A[row_perm[i], col_perm[j]]
        A = np.zeros_like(Ap)
        A[np.ix_(self.row_perm, self.col_perm)] = Ap
        return A

    # ----------------------------------------------------------- device ops

    def expand(self, A, a=1.0):
        """Trimmed BandedMatrix -> full-lattice (bands (G, nd, n_pad),
        Vt (G, t, n_pad)) scaled by `a` (factorization transient)."""
        G = A.bands.shape[0]
        dtype = A.bands.dtype
        full = jnp.zeros((G, self.nd, self.n_pad), dtype=dtype)
        full = full.at[:, np.asarray(A.dsel), :self.n_store].set(a * A.bands)
        Vt = jnp.zeros((G, self.t, self.n_pad), dtype=dtype)
        if self.t and A.Vt is not None:
            Vt = Vt.at[:, :, :self.n_store].set(a * A.Vt)
        return full, Vt

    # bytes of ONE store's tile a body of the band product's scan reads:
    # at a v5e's bandwidth 80 us a body, against the 3 us a body costs to
    # launch (PERF.md, PR 29), and far under what a step's programs have
    # to spare beside 10 GB of resident factors
    _BAND_TILE_BYTES = 64 * 2 ** 20

    def _band_tiles(self, *stores):
        """(rows per tile, tiles) of the band product over band stores
        (G, D, width) of one width, read off their shapes: the fewest
        tiles whose slab of the widest store (every stored diagonal, every
        group) stays under _BAND_TILE_BYTES, the rows spread evenly over
        them and rounded up to the 8 sublanes of a float32 tile, so that
        a tile starts on a tile boundary of the group-minor layout a TPU
        keeps the stores in. A store that fits one tile is one tile."""
        G, _, width = stores[0].shape
        row_bytes = max(1, G * max(b.shape[1] * b.dtype.itemsize
                                   for b in stores))
        tiles = -(-width * row_bytes // self._BAND_TILE_BYTES)
        if tiles <= 1:
            return width, 1
        rows = -(-(-(-width // tiles)) // 8) * 8
        return rows, -(-width // rows)

    def _band_mv(self, mats, x):
        """For every (bands, dsel) of `mats`, stores of one width:
            y[g, p] = sum_i bands[g, i, p] * x[g, p + dsel[i] - kl]
        over ONE zero-padded x; width follows the band ARRAY (assembled
        storage, not the re-blocked factor width).

        Each store is read once, where it lies: a scan over tiles of rows
        (`_band_tiles`) whose body takes a `dynamic_slice` of the bands
        (every stored diagonal, `rows` rows, every group) and of the
        rows + kl + ku window of the padded x they meet, and sums the
        shifted multiply-adds inside the body, i ascending: on every row
        the float operations of a loop over whole diagonals, in its order.
        That loop, which this replaces, reached a v5e as copies of 50 of
        54 diagonals out of the store and 19 shifted copies of x: 8 GB
        moved for 1.8 GB of bands (PERF.md, PR 38). A store that fits one
        tile takes the body once, which is that loop's program again.

        The last tile's start is clamped to the stored width, so it
        rewrites rows it shares with its neighbour, with the same values,
        and a store is never padded or copied to fit. The results are
        carried TRANSPOSED, (width, G): a TPU keeps the stores and x
        group-minor and gives a loop's carry of no other user the default
        layout, so a (G, width) carry came out row-minor and every body
        transposed 54 slabs to meet it (146 MB of temporaries a product);
        transposed, the default layout IS group-minor and both `.T` are
        bitcasts there; on a CPU they transpose one result, 1/D of the
        bytes. The group axis is not tiled: a pencil mesh shards it as
        before. Scan indices are int32 (`_shard_chunked`)."""
        width = mats[0][0].shape[-1]
        rows, tiles = self._band_tiling = self._band_tiles(
            *(bands for bands, _ in mats))
        xpad = zeropad(x, ((0, 0), (self.kl, self.ku)))
        window = rows + self.kl + self.ku

        def tile(start):
            """Rows [start, start + rows) of every product."""
            xw = jax.lax.dynamic_slice_in_dim(xpad, start, window, axis=1)
            ys = []
            for bands, dsel in mats:
                b = jax.lax.dynamic_slice_in_dim(bands, start, rows, axis=2)
                y = jnp.zeros_like(xw[:, :rows])
                for i, d in enumerate(dsel):
                    y = y + b[:, i, :] * jax.lax.slice_in_dim(
                        xw, d, d + rows, axis=1)
                ys.append(y)
            return ys

        if tiles == 1:
            return tile(0)

        def body(yTs, k):
            start = jnp.minimum(k * rows, width - rows)
            return [jax.lax.dynamic_update_slice_in_dim(yT, y.T, start, 0)
                    for yT, y in zip(yTs, tile(start))], None

        yTs, _ = jax.lax.scan(body, [jnp.zeros_like(x).T for _ in mats],
                              jnp.arange(tiles, dtype=jnp.int32))
        return [yT.T for yT in yTs]

    def _matvecs(self, mats, X):
        """[A @ X for A in mats] in the ORIGINAL slot ordering, X (G, S):
        one column permutation, one padded X and one scan over row tiles
        for all of `mats`; each matrix its own pinned rows and scatter."""
        xp = X[:, self.col_perm]
        xp = zeropad(xp, ((0, 0), (0, mats[0].bands.shape[-1] - self.n)))
        outs = []
        for A, yp in zip(mats, self._band_mv(
                [(A.bands, A.dsel) for A in mats], xp)):
            if self.t and A.Vt is not None:
                pin_vals = jnp.einsum("gtn,gn->gt", A.Vt, xp)
                yp = yp.at[:, self.pin_pos].add(pin_vals)
            # yp[p] = (A @ X)[row_perm[p]]
            out = jnp.zeros_like(X)
            outs.append(out.at[:, self.row_perm].set(yp[:, :self.n]))
        return outs

    def matvec(self, A, X):
        """Full A @ X in the ORIGINAL slot ordering; X (G, S)."""
        with jax.named_scope("dedalus/matsolve/banded.matvec"):
            return self._matvecs([A], X)[0]

    def matvec_pair(self, M, L, X):
        """(M @ X, L @ X) in ONE pass over the operand: the fused-step
        pair surface (core/fusedstep.py). The column permutation, the pads
        and ONE scan over row tiles (`_band_mv`) are shared: a body reads
        its tile of both stores against one window of the padded X, each
        matrix summing its own trimmed diagonals in `matvec`'s order, so
        every row of both outputs is the float operations of a separate
        `matvec` call."""
        with jax.named_scope("dedalus/matsolve/banded.matvec_pair"):
            return tuple(self._matvecs([M, L], X))

    def _chunk_blocks(self, chunk):
        """One block-row's (G, D, q) band chunk -> (diag, left, right) blocks
        ((i, i), (i, i-1), (i, i+1)); avoids materializing the full block
        tridiagonal (3 extra (G, NB, q, q) arrays) during factorization."""
        q = self.q
        ri = np.broadcast_to(np.arange(q)[:, None], (q, q))
        out = {}
        for o in (-1, 0, 1):
            d, valid = self._blk_idx[o]                      # (q, q)
            blk = chunk[:, d, ri] * jnp.asarray(valid, dtype=chunk.dtype)
            out[o] = blk
        return out[0], out[-1], out[1]

    @staticmethod
    def _triangle_inverses(top):
        """A panel's (G, q, q) LU top — L1 below the diagonal (unit lower),
        U11 on and above it — with both triangles inverted in place: the
        strict lower triangle of L1^-1 (unit lower stays unit lower) and
        the upper triangle of U11^-1 (upper stays upper).

        Each inverse gets one Newton step, X + X (I - A X), in plain
        multiply-adds (no dot: on the TPU a dot is MXU passes). What
        `solve_triangular` against the identity returns there is not good
        enough to be STORED: an error in an entry of the inverse reaches
        the solution multiplied by the block's condition number. RB
        2048x1024 on the v5e, float32, with the inverses as returned: dense
        residual check 4.8e-6, wall values off by 0.06 after ten steps and
        0.26 after fifty; with the step: 2.0e-8 and 1.0e-6, which is what
        the substitution they replace read (2.0e-8, 8.8e-7; my chip runs,
        PR 29). On the CPU the step moves nothing (1e-8 either way)."""
        eye = jnp.eye(top.shape[-1], dtype=top.dtype)
        eyes = jnp.broadcast_to(eye, top.shape)
        L1, U11 = jnp.tril(top, -1) + eye, jnp.triu(top)
        L1inv = jsl.solve_triangular(L1, eyes, lower=True, unit_diagonal=True)
        U11inv = jsl.solve_triangular(U11, eyes, lower=False)

        def product(A, B):
            return (A[..., :, :, None] * B[..., None, :, :]).sum(axis=-2)

        def polished(A, X):
            return X + product(X, eye - product(A, X))

        return (jnp.tril(polished(L1, L1inv), -1)
                + jnp.triu(polished(U11, U11inv)))

    def _factor_interior(self, bands):
        """
        Blocked banded LU with windowed partial pivoting (the batched-TPU
        analogue of LAPACK dgbtrf, reference matsolver ScipyBanded:
        libraries/matsolvers.py:187): at block column i the (2q x q) panel
        [S_i; Lo_i] is factored with row pivoting (pivots confined to the
        window, exactly LAPACK's banded pivot range for kl <= q), the
        permutation + elimination are applied to the (2q x 2q) trailing
        window, and the upper fill (bandwidth ku + kl <= 2q) is stored in
        a (q x 2q) U12 block per step. Unconditionally stable where the
        no-pivot block elimination breaks on constraint rows.

        Factors are stored packed, one (2q x q) panel per block row, in
        LAPACK's slots but not with LAPACK's contents: the top q x q holds
        the INVERSES of the panel's triangles — the strict lower triangle
        of L1^-1 (unit lower stays unit lower, its diagonal implied) and
        the upper triangle of U11^-1 (upper stays upper) — and the bottom
        q x q holds L2 as factored. The triangles never change between
        factorizations, so they are inverted here, once per block row, and
        every substitution body multiplies (`_solve_interior`); on the TPU
        `solve_triangular` at q = 32 is "invert the block, then multiply"
        anyway, so the sweep does the arithmetic it always did, without
        redoing the inversion in each of its 16,384 bodies a step (RB
        2048x1024, PR 29; `_triangle_inverses` says what a stored inverse
        needs that a used-at-once one did not). U12 itself stays a
        `solve_triangular` against L1: formed as L1^-1 @ T on the CPU it
        cost RB 8x512 in float32 a factor 17 in the continuity residual.
        Still 4q^2 numbers + 2q pivots a block row. The last block (one
        per solve, no scan body) keeps its plain LAPACK LU.

        Returns aux tuple (perms, panelLU, U12, lastP, lastLU), stacked
        (steps, G, flat) with panels and U12 flattened row-major.
        """
        G = bands.shape[0]
        q, NB = self.q, self.NB
        dtype = bands.dtype
        if NB == 1:
            Dg0, _, _ = self._chunk_blocks(bands)
            lu, _, perm = jax.lax.linalg.lu(Dg0)
            return (None, None, None, perm, lu)

        eye_q = jnp.eye(q, dtype=dtype)
        zero_qq = jnp.zeros((G, q, q), dtype=dtype)

        # All arrays entering/leaving the scan are flattened to (G, flat):
        # TPU tiles the two minor dims to (8, 128), so stacked (steps, G, q,
        # q)-shaped arrays with q ~ 32 pay 4-8x padding; (steps, G, q*q)
        # tiles cleanly. The scan consumes the band storage directly as
        # per-block-row chunks (one (G, D, q) slab per step) instead of a
        # pre-materialized block tridiagonal.
        nd = self.nd

        def step(carry, chunk_flat):
            A11, A12 = carry              # (G,q,q), (G,q,2q): cols i+1, i+2
            D_n, Lo_i, Up_n = self._chunk_blocks(
                chunk_flat.reshape(G, nd, q))
            panel = jnp.concatenate([A11, Lo_i], axis=1)          # (G,2q,q)
            lu, _, perm = jax.lax.linalg.lu(panel)
            top, L2 = lu[:, :q, :], lu[:, q:, :]                  # (G,q,q)
            T = jnp.concatenate(
                [A12, jnp.concatenate([D_n, Up_n], axis=2)], axis=1)  # (G,2q,2q)
            T = jnp.take_along_axis(T, perm[:, :, None], axis=1)
            # a substitution where the backend has one (the CPU); on the
            # TPU the product by L1^-1, whose inversion its compiler
            # shares with the one in _triangle_inverses
            U12 = jsl.solve_triangular(jnp.tril(top, -1) + eye_q,
                                       T[:, :q, :], lower=True,
                                       unit_diagonal=True)        # (G,q,2q)
            Tn = T[:, q:, :] - L2 @ U12                           # (G,q,2q)
            carry = (Tn[:, :, :q],
                     jnp.concatenate([Tn[:, :, q:], zero_qq], axis=2))
            packed = jnp.concatenate(
                [self._triangle_inverses(top), L2], axis=1)
            return carry, (perm, packed.reshape(G, 2 * q * q),
                           U12.reshape(G, 2 * q * q))

        chunks = jnp.moveaxis(bands.reshape(G, nd, NB, q), 2, 0)  # (NB,G,nd,q)
        chunks = chunks.reshape(NB, G, nd * q)
        Dg0, _, Up0 = self._chunk_blocks(chunks[0].reshape(G, nd, q))
        A12_0 = jnp.concatenate([Up0, zero_qq], axis=2)
        (A11_f, _), (perms, panelLU, U12) = jax.lax.scan(
            step, (Dg0, A12_0), chunks[1:])
        lu, _, lastP = jax.lax.linalg.lu(A11_f)
        return (perms, panelLU, U12, lastP, lu)

    def _batch_minor(self, interior):
        """The packed pivoted factors as they are kept and as the scan
        bodies of `_solve_interior` read them: GROUP axis last and each
        block TRANSPOSED — perms (steps, 2q, G); the panel (steps, q*2q,
        G), a (q cols, 2q rows, G) view whose first q rows hold the two
        triangular inverses and whose last q hold L2 (`_factor_interior`);
        U12 (steps, 2q*q, G), a (2q cols, q rows, G) view. A block product
        is then a sum over the view's LEADING axis of (rows, G) slabs
        scaled by a row of the right-hand side: groups in the lanes, block
        rows in the sublanes, nothing moved. The substitution's block
        operations are batched over groups on blocks far smaller than an
        MXU tile, and the TPU's layout assignment wants the batch in the
        lanes for them; handed (steps, G, flat) it re-lays-out the WHOLE
        stacked store before the scan (RB 2048x1024: `copy` of
        f32[16,256,64,2048] into {2,3,1,0:T(8,128)}, 4.0 GB with the lane
        padding, "Used 16.49G of 15.75G hbm": compiled for a described
        v5e, PR 28). Stored this way the resident layout is the one the
        scan body reads."""
        perms, panelLU, U12, lastP, lastLU = interior
        if panelLU is None:
            return interior
        q = self.q
        steps, G = panelLU.shape[:2]

        def blocks_T(a, rows, cols):
            a = jnp.transpose(a.reshape(steps, G, rows, cols), (0, 3, 2, 1))
            return a.reshape(steps, cols * rows, G)

        return (jnp.swapaxes(perms, 1, 2), blocks_T(panelLU, 2 * q, q),
                blocks_T(U12, q, 2 * q), lastP, lastLU)

    def _precompose_subst(self, interior):
        """Precomposed matmul-substitution operators (FUSED_SOLVE,
        core/fusedstep.py). At factor time each panel's inverted
        unit-lower and upper blocks (the packed panel holds the inverses:
        `_factor_interior`; the last block's are taken here) are FOLDED
        with the window permutation and the elimination update into
        per-step GEMM operators:

            fwd:  [y_i; w_next] = FwdOp_i @ [w; f_{i+1}]
                  FwdOp_i = [[L1inv P_top], [P_bot - L2 L1inv P_top]]
            bwd:  x_i = BwdOp_i @ [y_i; x_{i+1}; x_{i+2}]
                  BwdOp_i = [U11inv | -U11inv U12]
            last: x = lastOp @ w,  lastOp = U^-1 L^-1 P

        so every substitution scan step is ONE batched (2q, 2q)-class
        matmul — no triangular-solve custom calls, no gathers, no
        separate elimination update (measured ~19x per triangular solve
        and ~2x per scan step in op overhead on CPU; the TPU dense
        path's BatchedInverse principle applied to the banded factors).
        The substitution result moves off the backward-stable sweep by
        ~eps*cond(block); the refinement polish (refine >= 1) drives the
        final residual back to the unfused level — the documented
        fused-vs-unfused tolerance (tests/test_fusion.py)."""
        perms, panelLU, U12, lastP, lastLU = interior
        q = self.q
        dtype = lastLU.dtype
        eye = jnp.eye(q, dtype=dtype)

        def inv_lower(lu):
            L1 = jnp.tril(lu, -1) + eye
            return jsl.solve_triangular(
                L1, jnp.broadcast_to(eye, L1.shape), lower=True,
                unit_diagonal=True)

        def inv_upper(lu):
            return jsl.solve_triangular(
                jnp.triu(lu), jnp.broadcast_to(eye, lu.shape), lower=False)

        # last block: A^-1 P = U^-1 L^-1 P composed once (perm folded)
        lastPmat = jax.nn.one_hot(lastP, q, dtype=dtype, axis=-1)
        fsub = {"lastOp": inv_upper(lastLU) @ inv_lower(lastLU) @ lastPmat}
        if panelLU is not None:
            steps, G = panelLU.shape[:2]
            # the panel's top already holds both inverses (_factor_interior)
            lu = panelLU.reshape(steps * G, 2 * q, q)
            L1inv = jnp.tril(lu[:, :q, :], -1) + eye
            U11inv = jnp.triu(lu[:, :q, :])
            Pmat = jax.nn.one_hot(perms.reshape(steps * G, 2 * q), 2 * q,
                                  dtype=dtype, axis=-1)
            top = L1inv @ Pmat[:, :q, :]                      # (., q, 2q)
            bot = Pmat[:, q:, :] - lu[:, q:, :] @ top
            fwd_op = jnp.concatenate([top, bot], axis=1)      # (., 2q, 2q)
            bwd_op = jnp.concatenate(
                [U11inv, -(U11inv @ U12.reshape(steps * G, q, 2 * q))],
                axis=2)                                       # (., q, 3q)
            fsub["FwdOp"] = fwd_op.reshape(steps, G, 4 * q * q)
            fsub["BwdOp"] = bwd_op.reshape(steps, G, 3 * q * q)
        return fsub

    # ------------------------------- restructured substitutions (solvecomp)
    #
    # Both precomposed sweeps are affine recurrences over factor-time
    # operators: forward w_{i+1} = A_i w_i + B_i f_{i+1} with outputs
    # y_i = C_i w_i + D_i f_{i+1} ((A|B; C|D) = blocks of FwdOp), and
    # backward z_i = A'_i z_{i+1} + B'_i y_i over the stacked pair
    # z_i = [x_i; x_{i+1}] (A', B' built from BwdOp = [Y | P]:
    # x_i = Y_i y_i + P_i z_{i+1}). The [fusion] SOLVE_COMPOSITION knob
    # swaps the O(N)-depth lax.scan over these recurrences for the
    # log-depth parallel prefix (ascan) or the chunk-partitioned SPIKE
    # program (libraries/solvecomp.py has the depth/flops model).

    def _subst_fwd_system(self, fsub):
        """(A, B, C, D) of the forward sweep from the precomposed
        FwdOp blocks; state/input/output widths all q."""
        q = self.q
        steps, G = fsub["FwdOp"].shape[:2]
        op = fsub["FwdOp"].reshape(steps, G, 2 * q, 2 * q)
        return (op[:, :, q:, :q], op[:, :, q:, q:],
                op[:, :, :q, :q], op[:, :, :q, q:])

    def _subst_bwd_system(self, fsub):
        """(A', B', C', D') of the backward sweep, step-reversed into a
        forward recurrence over v_j = z_{NB-2-j}; state width 2q,
        input/output width q. The output row extracts x_i = z_i[:q]
        (the post-step state's top block: C' = P, D' = Y)."""
        q = self.q
        steps, G = fsub["BwdOp"].shape[:2]
        op = fsub["BwdOp"].reshape(steps, G, q, 3 * q)
        Yb = op[..., :q]                                  # acts on y_i
        Pb = op[..., q:]                                  # acts on z_{i+1}
        shift = jnp.broadcast_to(
            jnp.concatenate([jnp.eye(q, dtype=op.dtype),
                             jnp.zeros((q, q), dtype=op.dtype)], axis=1),
            (steps, G, q, 2 * q))                         # x_{i+1} carry row
        A = jnp.concatenate([Pb, shift], axis=2)[::-1]
        B = jnp.concatenate([Yb, jnp.zeros_like(Yb)], axis=2)[::-1]
        return A, B, Pb[::-1], Yb[::-1]

    def _attach_spike(self, fsub):
        """Factor-time SPIKE precomposition: fold the within-chunk
        transfer products of both sweeps into dense per-chunk GEMM
        operators (solvecomp.spike_precompose) and DROP FwdOp/BwdOp —
        the spike solve consumes only the chunk operators, so keeping
        the step-stacked forms would double the persistent factor
        store. Degenerate step counts (too few steps to chunk) keep the
        sequential operators untouched."""
        n_steps = fsub["FwdOp"].shape[0]
        chunks = solvecomp.spike_chunk_count(n_steps, self._spike_chunks_cfg)
        if chunks <= 1:
            return
        fsub["spikeF"] = solvecomp.spike_precompose(
            *self._subst_fwd_system(fsub), chunks)
        fsub["spikeB"] = solvecomp.spike_precompose(
            *self._subst_bwd_system(fsub), chunks)
        del fsub["FwdOp"], fsub["BwdOp"]

    def _solve_interior_ascan(self, f, fsub):
        """Solve B~ x = f with both substitution sweeps as parallel
        prefixes over (A, b) pairs (lax.associative_scan, matmul
        combine): O(log NB) depth, no sequential scan in the lowered
        program (the DTP106 contract's ascan branch)."""
        G, _, k = f.shape
        q, NB = self.q, self.NB
        fb = jnp.moveaxis(f.reshape(G, NB, q, k), 1, 0)   # (NB, G, q, k)
        ys, w_f = solvecomp.ascan_apply(
            *self._subst_fwd_system(fsub), fb[1:], fb[0])
        x_last = fsub["lastOp"] @ w_f
        z0 = jnp.concatenate([x_last, jnp.zeros_like(x_last)], axis=1)
        outs, _ = solvecomp.ascan_apply(
            *self._subst_bwd_system(fsub), ys[::-1], z0)
        x = jnp.concatenate([outs[::-1], x_last[None]], axis=0)
        return jnp.moveaxis(x, 0, 1).reshape(G, self.n_pad, k)

    def _solve_interior_spike(self, f, fsub):
        """Solve B~ x = f against the factor-time SPIKE operators: each
        sweep is two batched GEMMs over all chunks plus the C-step
        reduced coupling scan (the DTP106 contract's spike branch)."""
        G, _, k = f.shape
        q, NB = self.q, self.NB
        fb = jnp.moveaxis(f.reshape(G, NB, q, k), 1, 0)
        ys, w_f = solvecomp.spike_apply(fsub["spikeF"], fb[1:], fb[0])
        x_last = fsub["lastOp"] @ w_f
        z0 = jnp.concatenate([x_last, jnp.zeros_like(x_last)], axis=1)
        outs, _ = solvecomp.spike_apply(fsub["spikeB"], ys[::-1], z0)
        x = jnp.concatenate([outs[::-1], x_last[None]], axis=0)
        return jnp.moveaxis(x, 0, 1).reshape(G, self.n_pad, k)

    def _solve_interior_fused(self, interior_aux, f, fsub):
        """Solve B~ x = f via the precomposed substitution operators: the
        same blocked sweeps as `_solve_interior`, each scan step one
        batched GEMM against the factor-time FwdOp/BwdOp."""
        G, _, k = f.shape
        q, NB = self.q, self.NB
        lastOp = fsub["lastOp"]
        fb = jnp.moveaxis(f.reshape(G, NB, q, k), 1, 0).reshape(NB, G, q * k)
        if NB == 1:
            x = lastOp @ fb[0].reshape(G, q, k)
            return jnp.moveaxis(x[None], 0, 1).reshape(G, self.n_pad, k)
        # restructured compositions (resolved once per build): spike
        # factors carry their chunk operators in the aux; ascan slices
        # the step-stacked operators at solve time
        if "spikeF" in fsub:
            return self._solve_interior_spike(f, fsub)
        if self._composition == "ascan":
            return self._solve_interior_ascan(f, fsub)

        def fwd(w_cur, xs):
            f_next, op_flat = xs
            wf = jnp.concatenate([w_cur, f_next.reshape(G, q, k)], axis=1)
            yw = op_flat.reshape(G, 2 * q, 2 * q) @ wf
            return yw[:, q:], yw[:, :q].reshape(G, q * k)

        with jax.named_scope("dedalus/matsolve/banded.fwd"):
            w_f, ys = jax.lax.scan(fwd, fb[0].reshape(G, q, k),
                                   (fb[1:], fsub["FwdOp"]))
        x_last = lastOp @ w_f
        zero = jnp.zeros_like(x_last)

        def bwd(carry, xs):
            x1, x2 = carry
            y_flat, op_flat = xs
            z = jnp.concatenate([y_flat.reshape(G, q, k), x1, x2], axis=1)
            x = op_flat.reshape(G, q, 3 * q) @ z
            return (x, x1), x.reshape(G, q * k)

        with jax.named_scope("dedalus/matsolve/banded.bwd"):
            _, xs_rev = jax.lax.scan(bwd, (x_last, zero),
                                     (ys, fsub["BwdOp"]), reverse=True)
        x = jnp.concatenate([xs_rev.reshape(NB - 1, G, q, k),
                             x_last[None]], axis=0)
        return jnp.moveaxis(x, 0, 1).reshape(G, self.n_pad, k)

    def _solve_interior(self, interior_aux, f, fsub=None):
        """Solve B~ x = f for f (G, n_pad, k) via the pivoted block factors
        as `_batch_minor` keeps them. Both block-row scans run group-minor
        from end to end: a block row of the right-hand side is (k, q, G),
        and a scan body is selects, multiplies and sums over (rows, G)
        slabs of the stored blocks — the window permutation a select
        against an iota, the triangular solves products by the inverses
        `_factor_interior` stored — so that the TPU program of a body holds
        no gather, no custom call and no re-laid-out copy of a factor
        slice (tests/test_chip_compile.py asks the compiler)."""
        if fsub is not None:
            return self._solve_interior_fused(interior_aux, f, fsub)
        perms, panelLU, U12, lastP, lastLU = interior_aux
        G, _, k = f.shape
        q, NB = self.q, self.NB
        eye_q = jnp.eye(q, dtype=f.dtype)

        def last_solve(w):
            w = jnp.take_along_axis(w, lastP[:, :, None], axis=1)
            y = jsl.solve_triangular(jnp.tril(lastLU, -1) + eye_q, w,
                                     lower=True, unit_diagonal=True)
            return jsl.solve_triangular(jnp.triu(lastLU), y, lower=False)

        if NB == 1:
            return last_solve(f)

        def apply(blockT, x):
            """block @ x for a block kept transposed, (cols, rows, G), and
            x (k, cols, G): the sum over cols of a (rows, G) slab times a
            row of x."""
            return (blockT[None] * x[:, :, None, :]).sum(axis=1)

        def permute(w, perm):
            """w[:, perm[i, g], g] for w (k, 2q, G), perm (2q, G): per row
            exactly one term of the sum is selected (bit for bit the
            gathered value; `where`, so no inf or nan crosses rows)."""
            j = jax.lax.broadcasted_iota(perm.dtype, (2 * q, 1, 1), 0)
            return jnp.where(perm[None] == j, w[:, :, None, :], 0).sum(axis=1)

        col = jax.lax.broadcasted_iota(jnp.int32, (q, q, 1), 0)
        row = jax.lax.broadcasted_iota(jnp.int32, (q, q, 1), 1)
        # right-hand side block rows, flattened (steps, k*q, G): see the
        # layout note of _factor_interior
        fb = jnp.transpose(f.reshape(G, NB, q, k), (1, 3, 2, 0))
        fb = fb.reshape(NB, k * q, G)

        # forward: eliminate with pivots; carry the updated next block
        def fwd(w_cur, xs):
            f_next, perm, panel = xs
            panel = panel.reshape(q, 2 * q, G)
            w = permute(jnp.concatenate(
                [w_cur, f_next.reshape(k, q, G)], axis=1), perm)
            L1inv_strict = jnp.where(row > col, panel[:, :q], 0)
            y = w[:, :q] + apply(L1inv_strict, w[:, :q])
            w_next = w[:, q:] - apply(panel[:, q:], y)
            return w_next, y.reshape(k * q, G)

        with jax.named_scope("dedalus/matsolve/banded.fwd"):
            w_f, ys = jax.lax.scan(fwd, fb[0].reshape(k, q, G),
                                   (fb[1:], perms, panelLU))
        x_last = last_solve(jnp.transpose(w_f, (2, 1, 0)))        # (G,q,k)
        x_last = jnp.transpose(x_last, (2, 1, 0))                 # (k,q,G)

        # backward: x_i = U11_i^-1 (y_i - U12_i @ [x_{i+1}; x_{i+2}])
        def bwd(carry, xs):
            x1, x2 = carry                                        # x_{i+1}, x_{i+2}
            y_flat, panel, U12_i = xs
            rhs = y_flat.reshape(k, q, G) - apply(
                U12_i.reshape(2 * q, q, G),
                jnp.concatenate([x1, x2], axis=1))
            U11inv = jnp.where(
                row <= col, panel.reshape(q, 2 * q, G)[:, :q], 0)
            x = apply(U11inv, rhs)
            return (x, x1), x.reshape(k * q, G)

        with jax.named_scope("dedalus/matsolve/banded.bwd"):
            _, xs_rev = jax.lax.scan(bwd, (x_last, jnp.zeros_like(x_last)),
                                     (ys, panelLU, U12), reverse=True)
        x = jnp.concatenate([xs_rev.reshape(NB - 1, k, q, G),
                             x_last[None]], axis=0)
        return jnp.transpose(x, (3, 0, 2, 1)).reshape(G, self.n_pad, k)

    def _pick_chunks(self, G, itemsize):
        """(C, Gc): chunk count and width for the G-chunked factorization,
        keeping a chunk's persistent factor slab (panelLU + U12) under
        BANDED_CHUNK_MB (the observed XLA temp footprint is a small
        multiple of that slab). When C*Gc > G (e.g. prime G) the batch is
        edge-padded with copies of the last group — factoring a duplicate
        is well-conditioned and its results are trimmed — so divisibility
        never degenerates chunking to size-1 sequential chunks. (When one
        group's factor slab alone exceeds the target, Gc still clamps to 1
        and factorization proceeds group-at-a-time: the target is a soft
        bound, exceeded only by indivisible per-group slabs.)"""
        target = float(config["linear algebra"].get(
            "BANDED_CHUNK_MB", "256")) * 1e6
        per_g = self.NB * (2 * self.q * self.q) * 2 * itemsize
        Gc = int(max(1, min(G, target // max(per_g, 1))))
        C = -(-G // Gc)
        if C <= 1:
            return 1, G
        Gc = -(-G // C)  # rebalance: padding stays below one chunk width
        # The chunk width is a whole number of TPU tiles of the stacked
        # factors, so that they are resident in the layout the block-row
        # scans slice. Precomposed operators are (C, steps, Gc, flat): Gc
        # is the sublane dim, a multiple of 8 (for Gc = 57 the TPU makes
        # `steps` the sublane dim instead, {3,1,2,0:T(8,128)}, and every
        # solve starts by copying the WHOLE store back to {3,2,1,0}). The
        # packed pivoted factors are (C, steps, flat, Gc) (_batch_minor):
        # Gc is the lane dim, a multiple of 128 (64 would pad to 128 and
        # double the store). Compiled for a described v5e, PR 28.
        tile = 8 if self._fused_solve else 128
        if G > tile and Gc % tile:
            Gc = -(-Gc // tile) * tile
            C = -(-G // Gc)
        return C, Gc

    @staticmethod
    def _pad_groups(arr, G_pad):
        """Edge-pad the leading (group) axis to G_pad."""
        pad = G_pad - arr.shape[0]
        if pad <= 0:
            return arr
        widths = [(0, pad)] + [(0, 0)] * (arr.ndim - 1)
        return jnp.pad(arr, widths, mode="edge")

    def _factor_core(self, bands, Vt, fused=False):
        """Factor one full-lattice band slab (any leading batch size).
        Returns (interior, Vt, YbT, CapLU, fsub) — a pytree safe to
        lax.map. `fused` additionally precomposes the matmul-substitution
        inverses (FUSED_SOLVE; the Woodbury E-solve below already runs on
        them, so fused factors are cheaper too)."""
        G = bands.shape[0]
        dtype = bands.dtype
        # identity pins at the pinned rows + padded diagonal
        ones = jnp.ones((G, len(self.pin_pos)), dtype=dtype)
        bands = bands.at[:, self.kl, self.pin_pos].set(ones)
        if self.n_pad > self.n:
            tail = jnp.ones((G, self.n_pad - self.n), dtype=dtype)
            bands = bands.at[:, self.kl, self.n:].set(tail)
        interior = self._factor_interior(bands)
        fsub = self._precompose_subst(interior) if fused else None
        if not fused:
            interior = self._batch_minor(interior)
        if fused:
            # the fused solve consumes only fsub — dropping the pivoted
            # factors here (not just from the host-side aux) keeps the
            # incremental path's donated stores from materializing ~5q^2
            # of dead factors per step next to the ~7q^2 live operators
            interior = None
            if self._composition == "spike" and "FwdOp" in fsub:
                # BEFORE the Woodbury E-solve below: the E columns then
                # solve through the same restructured program
                self._attach_spike(fsub)
        YbT = CapLU = None
        if self.t:
            # Y = B~^-1 E  (E = one-hot columns at the pin positions)
            E = jnp.zeros((G, self.n_pad, self.t), dtype=dtype)
            E = E.at[:, self.pin_pos, jnp.arange(self.t)].set(1.0)
            Yb = self._solve_interior(interior, E, fsub=fsub)     # (G, n_pad, t)
            # capacitance I + (Vt - E^T) Y = Vt Y: the pinned rows of B~
            # are unit rows, so E^T Y = I exactly. Summed as written, the
            # two identities cancel against Vt Y, whose rows carry the
            # implicit coefficient b = dt*gamma: at dt = 5e-4 float32 kept
            # two digits of it (RB 64x32 against float64: 2.8e-2; 3.1e-4
            # since; CPU, PR 28). For the same reason _solve_core subtracts
            # the right-hand side's own pin values, which y[pins] equals.
            Cap = jnp.einsum("gtn,gnk->gtk", Vt, Yb)
            # stored (G, t, n_pad): a trailing dim of t ~ 16 pads 8x under
            # TPU (8, 128) tiling; n_pad-minor tiles cleanly
            YbT = jnp.swapaxes(Yb, 1, 2)
            if fused:
                # the t x t capacitance solve becomes one GEMM too
                fsub["CapInv"] = jnp.linalg.inv(Cap)
            else:
                CapLU = jsl.lu_factor(Cap)
        if fused and self._ladder:
            # precision ladder (libraries/solvecomp.py): the whole
            # A'-solve — substitution operators AND Woodbury correction
            # — is stored and run in the low dtype (also halving the
            # persistent factor store); everything above computed at
            # native precision first so the low operators are rounded
            # versions of well-conditioned f64 factors. The f64
            # residual-matvec refinement in _solve_impl polishes each
            # solve back (sweep count scaled to the dtype gap).
            low = solvecomp.low_dtype(self._solve_plan.dtype, bands.dtype)
            fsub = jax.tree.map(lambda a: a.astype(low), fsub)
            Vt = Vt.astype(low)
            if YbT is not None:
                YbT = YbT.astype(low)
        return (interior, Vt, YbT, CapLU, fsub)

    def _aux_from_core(self, core, refine_aux):
        interior, Vt, YbT, CapLU, fsub = core
        # fused solves consume only the precomposed operators — dropping
        # the pivoted factors from the persistent aux frees ~4q^2 of the
        # 7q^2 per-step factor storage (they were transients of fsub)
        aux = {"Vt": Vt}
        if fsub is None:
            aux["interior"] = interior
        else:
            aux["fsub"] = fsub
        if YbT is not None:
            aux["YbT"] = YbT
        if CapLU is not None:
            aux["Cap"] = CapLU
        aux.update(refine_aux)
        return aux

    def _factor_impl(self, bands, Vt, refine_aux):
        """Shared factorization body; refine_aux supplies the residual
        matvec without persisting a combined matrix."""
        with jax.named_scope("dedalus/matsolve/banded.factor"):
            G = bands.shape[0]
            C, Gc = self._pick_chunks(G, bands.dtype.itemsize)
            self._g_chunks = C
            if C == 1:
                core = self._factor_core(bands, Vt)
            else:
                bands_c = self._pad_groups(bands, C * Gc).reshape(
                    C, Gc, self.nd, self.n_pad)
                Vt_c = self._pad_groups(Vt, C * Gc).reshape(
                    C, Gc, Vt.shape[1], self.n_pad)
                core = jax.lax.map(lambda xs: self._factor_core(*xs),
                                   (bands_c, Vt_c))
            return self._aux_from_core(core, refine_aux)

    def _combine_ml(self, mb, lb, mv, lv, g, a, b, dM, dL, dtype):
        """a*M + b*L as a full-lattice (bands, Vt) pair at the re-blocked
        factor width (the SINGLE implementation shared by the fused and
        incremental factor paths; inputs are assembled-width slabs)."""
        ns = self.n_store
        bands = jnp.zeros((g, self.nd, self.n_pad), dtype=dtype)
        bands = bands.at[:, dM, :ns].add(a * mb)
        bands = bands.at[:, dL, :ns].add(b * lb)
        Vt = jnp.zeros((g, self.t, self.n_pad), dtype=dtype)
        if mv is not None:
            Vt = Vt.at[:, :, :ns].add(a * mv)
        if lv is not None:
            Vt = Vt.at[:, :, :ns].add(b * lv)
        return bands, Vt

    def factor(self, A):
        """Factor a matrix already resident in banded storage."""
        self._ensure_fused_fits(A.bands.shape[0], A.bands.dtype.itemsize)
        bands, Vt = self.expand(A)
        return self._factor_impl(bands, Vt, {"A": A})

    def factor_lincomb(self, a, M, b, L):
        """Factor a*M + b*L WITHOUT persisting the combined bands: the
        combination is a transient of the factorization (built per G-chunk
        when chunking is active), and the refinement residual uses matvecs
        of the already-resident trimmed M and L (saves one full band store
        at large S)."""
        G = M.bands.shape[0]
        dtype = M.bands.dtype
        self._ensure_fused_fits(G, dtype.itemsize)
        C, Gc = self._pick_chunks(G, dtype.itemsize)
        self._g_chunks = C
        dM = np.asarray(M.dsel)
        dL = np.asarray(L.dsel)

        ns = self.n_store

        def combine(mb, lb, mv, lv, g):
            return self._combine_ml(mb, lb, mv, lv, g, a, b, dM, dL, dtype)

        # M and L themselves are NOT stored in the aux: the jitted factor
        # would return copies of both full band stores; the refinement
        # matvec receives them via solve(..., mats=(M, L))
        fused = self._fused_solve
        if C == 1:
            bands, Vt = combine(M.bands, L.bands, M.Vt, L.Vt, G)
            core = self._factor_core(bands, Vt, fused=fused)
        else:
            G_pad = C * Gc
            has_mv = M.Vt is not None
            has_lv = L.Vt is not None
            xs = [self._pad_groups(M.bands, G_pad).reshape(C, Gc, -1, ns),
                  self._pad_groups(L.bands, G_pad).reshape(C, Gc, -1, ns)]
            if has_mv:
                xs.append(self._pad_groups(M.Vt, G_pad).reshape(
                    C, Gc, self.t, ns))
            if has_lv:
                xs.append(self._pad_groups(L.Vt, G_pad).reshape(
                    C, Gc, self.t, ns))

            def one(xs):
                mb, lb = xs[0], xs[1]
                i = 2
                mv = xs[i] if has_mv else None
                i += has_mv
                lv = xs[i] if has_lv else None
                bands, Vt = combine(mb, lb, mv, lv, Gc)
                return self._factor_core(bands, Vt, fused=fused)

            if active_pencil_mesh() is not None:
                # distributed factor: XLA's SPMD partitioner miscompiles
                # the chunk-level lax.map (s64/s32 index mismatch in the
                # scan's dynamic_update_slice under x64 — the 2048x1024
                # north-star regime), and the factor outputs' group dims
                # vary per leaf so a manual shard_map reassembly is
                # ambiguous. C is static and small: unroll the chunk
                # loop into C chunk programs instead (the memory bound
                # lax.map provided is preserved by XLA's serial
                # scheduling of the independent chunk subgraphs).
                cores = [one(jax.tree.map(lambda s, _i=i: s[_i],
                                          tuple(xs)))
                         for i in range(C)]
                core = jax.tree.map(lambda *ls: jnp.stack(ls), *cores)
            else:
                core = jax.lax.map(one, tuple(xs))
        return self._aux_from_core(core, {"ab": (a, b)})

    # ------------------------------------------------ incremental factor

    def use_incremental_factor(self, G, itemsize):
        """Whether to factor chunk-by-chunk in SEPARATE device dispatches
        with donated accumulation (caps the transient HBM peak at roughly
        store + M/L + one chunk, vs the fused program's store + M/L + all
        scan temps). Engaged automatically when the factor output alone
        exceeds BANDED_INCREMENTAL_GB (the RB 2048x1024 regime: ~5.5 GB of
        factors on a 16 GB chip)."""
        self._ensure_fused_fits(G, itemsize)
        mode = config["linear algebra"].get(
            "BANDED_FACTOR_MODE", "auto").lower()
        if mode in ("fused", "incremental"):
            return mode == "incremental"
        C, Gc = self._pick_chunks(G, itemsize)
        if C <= 1:
            return False
        thresh = float(config["linear algebra"].get(
            "BANDED_INCREMENTAL_GB", "2.0")) * 1e9
        out_bytes = G * self.NB * (2 * self.q * self.q) * 2 * itemsize
        return out_bytes > thresh

    def incremental_chunk_program(self, M, L):
        """The ONE device program of the incremental factorization and what
        it is fed: `(write, store_shapes, C, Gc)`. `write(store, i, mb, lb,
        mv, lv, a, b)` combines and factors chunk i's (Gc, ...) slabs of M
        and L and writes the result into the donated (C, Gc, ...) `store`;
        `store_shapes` is that store as a pytree of ShapeDtypeStructs. M
        and L may be abstract (only shapes, dtypes, `dsel` and the
        presence of `Vt` are read), so the fit check compiles the program
        for a described chip with no array anywhere
        (tests/test_chip_compile.py)."""
        import functools
        G = M.bands.shape[0]
        dtype = M.bands.dtype
        self._ensure_fused_fits(G, dtype.itemsize)
        C, Gc = self._pick_chunks(G, dtype.itemsize)
        if C < 2:  # incremental mode implies chunked aux layout
            C, Gc = 2, -(-G // 2)
        dM = np.asarray(M.dsel)
        dL = np.asarray(L.dsel)
        has_mv = M.Vt is not None
        has_lv = L.Vt is not None
        rd = np.dtype(dtype)
        ns = self.n_store

        def chunk_core(mb, lb, mv, lv, a, b):
            bands, Vt = self._combine_ml(mb, lb, mv, lv, Gc, a, b,
                                         dM, dL, dtype)
            return self._factor_core(bands, Vt, fused=self._fused_solve)

        shapes = jax.eval_shape(
            chunk_core,
            jax.ShapeDtypeStruct((Gc, len(dM), ns), dtype),
            jax.ShapeDtypeStruct((Gc, len(dL), ns), dtype),
            jax.ShapeDtypeStruct((Gc, self.t, ns), dtype)
            if has_mv else None,
            jax.ShapeDtypeStruct((Gc, self.t, ns), dtype)
            if has_lv else None,
            jax.ShapeDtypeStruct((), rd), jax.ShapeDtypeStruct((), rd))
        store_shapes = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((C,) + s.shape, s.dtype), shapes)

        @functools.partial(jax.jit, donate_argnums=0)
        def write(store, i, mb, lb, mv, lv, a, b):
            with jax.named_scope("dedalus/matsolve/banded.factor"):
                core = chunk_core(mb, lb, mv, lv, a, b)
                return jax.tree.map(
                    lambda s, c: jax.lax.dynamic_update_index_in_dim(
                        s, c, i, 0),
                    store, core)

        return write, store_shapes, C, Gc

    def factor_lincomb_incremental(self, a, M, L, b_scale):
        """factor_lincomb(a, M, b, L) as C separate device dispatches: each
        chunk is combined + factored by a small jitted program whose result
        is written into donated (C, Gc, ...) stores, so the full-batch scan
        temps never coexist with the finished factors. Returns the same
        chunked aux `solve` already consumes. Host-level: call OUTSIDE jit.
        Each dispatch is a `factor/chunk` span (attrs `chunk`, `chunks`)."""
        if b_scale is None:
            raise ValueError("factor_lincomb_incremental requires b_scale "
                             "(the coefficient multiplying L).")
        G = M.bands.shape[0]
        rd = np.dtype(M.bands.dtype)
        write, store_shapes, C, Gc = self.incremental_chunk_program(M, L)
        self._g_chunks = C
        a = jnp.asarray(a, dtype=rd)
        b = jnp.asarray(b_scale, dtype=rd)
        store = jax.tree.map(
            lambda s: jnp.zeros(s.shape, dtype=s.dtype), store_shapes)

        def chunk_of(arr, i):
            if arr is None:
                return None
            lo = i * Gc
            hi = min(lo + Gc, G)
            sl = arr[lo:hi]
            if hi - lo < Gc:
                sl = self._pad_groups(sl, Gc)  # edge-pad the final chunk
            return sl

        for i in range(C):
            with tracing.span("factor/chunk", {"chunk": i, "chunks": C}):
                store = write(store, i,
                              chunk_of(M.bands, i), chunk_of(L.bands, i),
                              chunk_of(M.Vt, i), chunk_of(L.Vt, i), a, b)
        jax.block_until_ready(store)
        return self._aux_from_core(store, {"ab": (a, b)})

    def _aux_matvec(self, aux, x, mats):
        if "A" in aux:
            return self.matvec(aux["A"], x)
        a, b = aux["ab"]
        M, L = mats
        if self._fused_matvec:
            # one-pass pair (bitwise-identical components): the
            # refinement residual's two matvecs share permute/pad/scatter
            MX, LX = self.matvec_pair(M, L, x)
            return a * MX + b * LX
        return a * self.matvec(M, x) + b * self.matvec(L, x)

    def _solve_core(self, auxc, fp):
        fsub = auxc.get("fsub")
        if fsub is not None and fsub["lastOp"].dtype != fp.dtype:
            # precision ladder: the factors are stored low — run the
            # whole inner solve low; _solve_once casts the result back
            # and _solve_impl refines against the f64 M/L matvec
            fp = fp.astype(fsub["lastOp"].dtype)
        y = self._solve_interior(auxc.get("interior"), fp[..., None],
                                 fsub=fsub)[..., 0]
        if self.t:
            with jax.named_scope("dedalus/matsolve/banded.woodbury"):
                Vy = (jnp.einsum("gtn,gn->gt", auxc["Vt"], y)
                      - fp[:, self.pin_pos])
                if fsub is not None and "CapInv" in fsub:
                    z = jnp.einsum("gij,gj->gi", fsub["CapInv"], Vy)
                else:
                    z = jsl.lu_solve(auxc["Cap"], Vy)
                y = y - jnp.einsum("gtn,gt->gn", auxc["YbT"], z)
        return y

    def _solve_once(self, aux, rhs):
        G = rhs.shape[0]
        fp = rhs[:, self.row_perm]
        fp = zeropad(fp, ((0, 0), (0, self.n_pad - self.n)))
        # chunking is read off the aux's own stacked shapes ((G, q, q)
        # unchunked, (C, Gc, q, q) chunked) — instance state would go
        # stale across auxes factored under different configs
        probe = (aux["fsub"]["lastOp"] if "fsub" in aux
                 else aux["interior"][-1])
        C = probe.shape[0] if probe.ndim == 4 else 1
        if C == 1:
            y = self._solve_core(aux, fp)
        else:
            Gc = probe.shape[1]
            fp = self._pad_groups(fp, C * Gc)   # match factor-time padding
            auxc = {k: aux[k] for k in ("interior", "Vt", "YbT", "Cap",
                                        "fsub")
                    if k in aux}
            fpr = fp.reshape(C, Gc, self.n_pad)

            def chunked_solve(auxc, fpr):
                return jax.lax.map(
                    lambda xs: self._solve_core(xs[0], xs[1]),
                    (auxc, fpr))

            y = self._shard_chunked(chunked_solve, (auxc, fpr), Gc)
            y = y.reshape(-1, self.n_pad)[:G]
        xp = y[:, :self.n]
        out = xp[:, self.pos_col]
        if out.dtype != rhs.dtype:
            out = out.astype(rhs.dtype)   # ladder: back to the rhs dtype
        return out

    def _shard_chunked(self, fn, args, Gc):
        """Run a chunk-mapped factor/solve (`fn(*args)`, every traced
        leaf a (C, Gc, ...) slab) with the per-chunk GROUP axis (dim 1)
        sharded over the active pencil mesh, inside manual shard_map.
        Two reasons: the t x t capacitance LU custom calls stay
        device-local (GSPMD cannot partition them), and XLA's SPMD
        partitioner miscompiles the chunk scan's dynamic_update_slice
        under x64 (s64/s32 index mismatch, verifier failure after
        spmd-partitioning — observed on the 2048x1024 north-star banded
        step). Falls back to the plain GSPMD call when no mesh context
        is active, the chunk width does not tile the mesh, or any leaf
        does not carry the (C, Gc, ...) layout."""
        state = active_pencil_mesh()
        if state is not None:
            mesh, name = state
            n = mesh.shape[name]
            spec = PartitionSpec(None, name)

            def spec_of(leaf):
                ndim = getattr(leaf, "ndim", 0)
                if ndim == 0:
                    return PartitionSpec()
                if ndim >= 2 and leaf.shape[1] == Gc:
                    return spec
                return None

            in_specs = jax.tree.map(spec_of, args)
            if Gc % n == 0 and not any(
                    s is None for s in jax.tree.leaves(
                        in_specs, is_leaf=lambda x: x is None)):
                return shard_map(fn, mesh=mesh, in_specs=in_specs,
                                 out_specs=spec)(*args)
        return fn(*args)

    def _solve_impl(self, aux, rhs, mats=None):
        with jax.named_scope("dedalus/matsolve/banded.solve"):
            x = self._solve_once(aux, rhs)
            if mats is None and "A" not in aux:
                return x  # lincomb factor without mats: no refinement possible
            sweeps = self._refine_sweeps if self._refine_sweeps is not None \
                else self.refine
            if sweeps <= 0:
                return x
            tol = self._refine_tol

            def sweep(x, _):
                # f64 residual matvec against the assembled M/L (never
                # the low-dtype factors) — the correction solve runs in
                # the solve dtype, the polish at native precision
                # (`banded.refine` is the sweep's own arithmetic; its
                # matvecs and correction solve keep their inner scopes)
                with jax.named_scope("dedalus/matsolve/banded.refine"):
                    r = rhs - self._aux_matvec(aux, x, mats)
                dx = self._solve_once(aux, r)
                with jax.named_scope("dedalus/matsolve/banded.refine"):
                    if tol > 0.0:
                        # tolerance-terminated: converged groups freeze
                        # (masked update — fixed trip count, retrace-free)
                        rn = jnp.max(jnp.abs(r), axis=1, keepdims=True)
                        bn = jnp.max(jnp.abs(rhs), axis=1, keepdims=True)
                        return jnp.where(rn > tol * bn, x + dx, x), None
                    return x + dx, None

            x, _ = jax.lax.scan(sweep, x, None, length=sweeps)
            return x

    def solve_report(self, aux, rhs, mats=None):
        """Diagnostic solve + achieved relative residual as a device
        scalar (None when the aux carries no residual matvec) — the
        flush-time `precision` telemetry probe and the benchmark
        accuracy rows. Never called on the step path."""
        x = self.solve(aux, rhs, mats=mats)
        if mats is None and "A" not in aux:
            return x, None
        r = rhs - self._aux_matvec(aux, x, mats)
        scale = jnp.max(jnp.abs(rhs))
        rel = jnp.max(jnp.abs(r)) / jnp.where(scale == 0, 1.0, scale)
        return x, rel

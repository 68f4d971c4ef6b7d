"""
Solve compositions: log-depth restructurings of the banded substitution
recurrences, and the mixed-precision solve ladder (ROADMAP item 5's
precision half; JAXMg in PAPERS.md is the XLA-native precedent for
restructuring a structured solve into batched matmuls).

The PR-12 fused substitution made every scan STEP one batched GEMM, but
the scan itself still runs NB-1 *sequential* steps per sweep — O(N)
dependent dispatches that serialize exactly the dimension an MXU wants
to batch, and that per-step fusion cannot hide (the measured remaining
floor of the rb256x64 step). Both sweeps are affine recurrences over
factor-time-constant operators:

    forward:   w_{i+1} = A_i @ w_i + B_i @ f_{i+1}
               y_i     = C_i @ w_i + D_i @ f_{i+1}
    backward:  z_i     = A'_i @ z_{i+1} + B'_i @ y_i     (z = [x_i; x_{i+1}])

where (A, B, C, D) are slices of the precomposed FwdOp/BwdOp GEMM
operators (libraries/pencilops.BandedOps._precompose_subst). Two
restructurings of that recurrence live here, selected by
`[fusion] SOLVE_COMPOSITION` (resolved ONCE per solver build, folded
into the assembly-cache/pool keys like every PR-12/13 knob):

  ascan — the textbook parallel prefix: `lax.associative_scan` over
          (A, b) pairs with the matmul combine
          (A2, b2) o (A1, b1) = (A2 @ A1, A2 @ b1 + b2).
          Depth O(log N); flops O(N log N * s^3) because the combine
          multiplies s x s operator blocks — the composition wins where
          depth is the cost (latency-bound accelerators), and loses
          where flops are (CPU). No `lax.scan` survives in the lowered
          program at all.

  spike — the chunk-partitioned SPIKE analogue: the step axis splits
          into C chunks whose within-chunk transfer operators are
          PRECOMPOSED AT FACTOR TIME into dense block-triangular
          per-chunk GEMM operators, so the solve is
              outs_c = Y_c @ f_c + YH_c @ v_in_c        (batched GEMMs)
              v_in_{c+1} = T_c @ v_in_c + P_c @ f_c     (C-step reduced scan)
          — one batched GEMM program over all chunks at once, coupled
          through a C-length reduced recurrence. Sequential depth C
          (~sqrt(N) by default), flops ~(N/C) x the sequential sweep's,
          amortized into large GEMMs instead of N tiny scan steps.

The precision ladder (`[precision] SOLVE_DTYPE = f32|bf16`) casts the
factor-time substitution/Woodbury operators to the low dtype so every
solve GEMM runs low, then polishes with the existing f64
residual-matvec refinement loop (fixed trip count, residual-tolerance
masked — retrace-free) back to a configurable tolerance. `REFINE_SWEEPS
= auto` scales the sweep count to the dtype gap; accuracy is recorded
in the `precision` telemetry block.

Everything here is pure jnp, traced inside the existing
`AdjointSolveOps.solve` custom_vjp funnel (so adjoints transpose the
SAME restructured linear algebra via jax.vjp), and composes under vmap
(EnsembleSolver) and shard_map. Config is read only in the resolve_*
functions, at solver-build time — never on the step path (DTL008).
"""

import numpy as np
import jax
import jax.numpy as jnp

from ..tools.config import config

__all__ = ["SolvePlan", "resolve_solve_plan", "solve_plan_token",
           "solve_knobs_pinned", "low_dtype", "spike_chunk_count",
           "ascan_apply", "spike_precompose", "spike_apply",
           "COMPOSITIONS", "SOLVE_DTYPES"]

COMPOSITIONS = ("sequential", "ascan", "spike")
SOLVE_DTYPES = ("native", "f32", "bf16")

# refinement sweeps per solve dtype when REFINE_SWEEPS = auto; None =
# defer to the ops' own default polish (BandedOps.refine — the PR-12
# fused tolerance class is calibrated against exactly that count).
# f32: 2 sweeps measured to hold the rb256x64 trajectory at the f64
# class (state err ~1e-14, probe residual ~1e-12) while keeping the
# ladder's speedup; raise REFINE_TOL/REFINE_SWEEPS for stiffer
# operators. bf16's weaker per-sweep contraction (~eps_bf16 * cond)
# needs the deeper schedule.
_AUTO_SWEEPS = {"native": None, "f32": 2, "bf16": 6}


class SolvePlan:
    """Resolved solve composition + precision ladder (immutable per
    solver build; the `[fusion]`/`[precision]` analogue of FusionPlan).
    `sweeps=None` means "keep the ops' own refinement count"."""

    __slots__ = ("composition", "spike_chunks", "dtype", "sweeps", "tol",
                 "mmt_dtype")

    def __init__(self, composition="sequential", spike_chunks=0,
                 dtype="native", sweeps=None, tol=0.0, mmt_dtype="native"):
        self.composition = composition
        self.spike_chunks = int(spike_chunks)
        self.dtype = dtype
        self.sweeps = sweeps
        self.tol = float(tol)
        self.mmt_dtype = mmt_dtype

    def token(self):
        """Stable content token for the assembly-cache solver key (and
        through it the serving pool key): the RESOLVED composition and
        ladder, so a knob flip can never alias a compiled program built
        under another composition/precision."""
        return ("solve-v1", self.composition, self.spike_chunks,
                self.dtype, self.sweeps, self.tol, self.mmt_dtype)

    def __repr__(self):
        bits = [self.composition]
        if self.dtype != "native":
            bits.append(f"{self.dtype}+refine")
        return f"SolvePlan({'+'.join(bits)})"


def _choice(section, key, default, allowed):
    raw = config[section].get(key, default) \
        if config.has_section(section) else default
    val = raw.strip().lower()
    if val not in allowed:
        # unknown values must FAIL the build, not silently resolve to
        # auto: the compositions sit in different tolerance classes and
        # different depth contracts (the PR-12 config discipline)
        raise ValueError(
            f"[{section}] {key} = {raw!r} is not a recognized value "
            f"({'/'.join(allowed)})")
    return val


def solve_knobs_pinned():
    """True when any solve knob carries an explicit (non-auto) value:
    the build's `plan_source` is then `config`, else `default`
    (core/solvers.plan_provenance)."""
    for section, key in (("fusion", "SOLVE_COMPOSITION"),
                         ("fusion", "SPIKE_CHUNKS"),
                         ("precision", "SOLVE_DTYPE"),
                         ("precision", "REFINE_SWEEPS")):
        raw = config[section].get(key, "auto") \
            if config.has_section(section) else "auto"
        if raw.strip().lower() not in ("auto", ""):
            return True
    return False


def resolve_solve_plan():
    """Resolve `[fusion] SOLVE_COMPOSITION`/`SPIKE_CHUNKS` and the
    `[precision]` section against the active backend. Called once per
    solver build (core/solvers._build_pencil_system) BEFORE
    assembly_cache.solver_key seals the result into the cache/pool keys.
    `auto` semantics: composition stays `sequential` (the measured
    default), SOLVE_DTYPE stays native, REFINE_SWEEPS scales to
    the dtype gap, REFINE_TOL 0 (fixed sweeps, always applied)."""
    comp = _choice("fusion", "SOLVE_COMPOSITION", "auto",
                   ("auto",) + COMPOSITIONS)
    if comp == "auto":
        comp = "sequential"
    raw_chunks = config["fusion"].get("SPIKE_CHUNKS", "auto") \
        if config.has_section("fusion") else "auto"
    raw_chunks = raw_chunks.strip().lower()
    if raw_chunks in ("auto", ""):
        spike_chunks = 0
    else:
        try:
            spike_chunks = int(raw_chunks)
        except ValueError:
            raise ValueError(
                f"[fusion] SPIKE_CHUNKS = {raw_chunks!r} is not a "
                "recognized value (auto or an integer >= 2)")
        if spike_chunks < 2:
            raise ValueError(
                f"[fusion] SPIKE_CHUNKS = {spike_chunks} must be >= 2 "
                "(1 chunk is the sequential composition)")
    dtype = _choice("precision", "SOLVE_DTYPE", "auto",
                    ("auto", "f64") + SOLVE_DTYPES)
    if dtype in ("auto", "f64"):
        dtype = "native"
    raw_sweeps = config["precision"].get("REFINE_SWEEPS", "auto") \
        if config.has_section("precision") else "auto"
    raw_sweeps = raw_sweeps.strip().lower()
    if raw_sweeps in ("auto", ""):
        sweeps = _AUTO_SWEEPS[dtype]
    else:
        try:
            sweeps = int(raw_sweeps)
        except ValueError:
            raise ValueError(
                f"[precision] REFINE_SWEEPS = {raw_sweeps!r} is not a "
                "recognized value (auto or an integer >= 0)")
        if sweeps < 0:
            raise ValueError(
                f"[precision] REFINE_SWEEPS = {sweeps} must be >= 0")
    raw_tol = config["precision"].get("REFINE_TOL", "auto") \
        if config.has_section("precision") else "auto"
    raw_tol = raw_tol.strip().lower()
    if raw_tol in ("auto", ""):
        tol = 0.0
    else:
        try:
            tol = float(raw_tol)
        except ValueError:
            raise ValueError(
                f"[precision] REFINE_TOL = {raw_tol!r} is not a "
                "recognized value (auto or a float >= 0)")
        if tol < 0.0:
            raise ValueError(
                f"[precision] REFINE_TOL = {tol} must be >= 0")
    mmt = _choice("precision", "MMT_DTYPE", "auto",
                  ("auto",) + SOLVE_DTYPES)
    if mmt == "auto":
        mmt = "native"
    return SolvePlan(composition=comp, spike_chunks=spike_chunks,
                     dtype=dtype, sweeps=sweeps, tol=tol, mmt_dtype=mmt)


def solve_plan_token():
    """The solve-plan component of assembly-cache content keys (used
    when the solver carries no resolved plan — standalone builds)."""
    return resolve_solve_plan().token()


def low_dtype(name, native):
    """The storage dtype for ladder operators: `name` ('native'/'f32'/
    'bf16') applied to the problem's native pencil dtype. Complex
    problems map f32 -> complex64; bf16 has no complex variant and
    raises (at factor time — still inside the solver build)."""
    native = np.dtype(native)
    if name == "native":
        return native
    complex_ = np.issubdtype(native, np.complexfloating)
    if name == "f32":
        return np.dtype(np.complex64) if complex_ else np.dtype(np.float32)
    if name == "bf16":
        if complex_:
            raise ValueError(
                "[precision] SOLVE_DTYPE = bf16 has no complex variant; "
                "use f32 for complex pencil systems")
        return jnp.bfloat16
    raise ValueError(f"unknown solve dtype {name!r}")


def spike_chunk_count(m, configured):
    """Chunk count for a SPIKE partition of m recurrence steps:
    `configured` (from [fusion] SPIKE_CHUNKS; 0 = auto) clamped to the
    step count; auto targets sqrt(m) — the depth/flops balance point
    (depth C + GEMMs of size (m/C); both ~sqrt at the optimum)."""
    if m < 4:
        return 1        # degenerate: the sequential sweep is already flat
    if configured:
        return max(2, min(int(configured), m))
    return max(2, min(int(round(np.sqrt(m))), m))


# --------------------------------------------------------- parallel prefix

def ascan_apply(A, B, C, D, u, v0):
    """Solve the affine recurrence/output system

        v_{j+1} = A_j @ v_j + B_j @ u_j,   v_0 = v0
        out_j   = C_j @ v_j + D_j @ u_j            (v_j = PRE-step state)

    for all j = 0..m-1 as a parallel prefix over (A, b) pairs via
    `lax.associative_scan` with the matmul combine — O(log m) sequential
    depth, no `lax.scan` in the lowered program. Shapes: A (m, G, s, s),
    B (m, G, s, kin), C (m, G, o, s), D (m, G, o, kin), u (m, G, kin, k),
    v0 (G, s, k). Returns (outs (m, G, o, k), v_final (G, s, k))."""
    b = B @ u                                   # (m, G, s, k)
    # fold v0 into the first element so prefix b-components ARE the states
    b = jnp.concatenate([(A[0] @ v0 + b[0])[None], b[1:]], axis=0)

    def combine(prev, nxt):
        A1, b1 = prev
        A2, b2 = nxt
        return A2 @ A1, A2 @ b1 + b2

    _, states = jax.lax.associative_scan(combine, (A, b), axis=0)
    # states[j] = v_{j+1}; outputs consume the PRE-step states v_0..v_{m-1}
    v_pre = jnp.concatenate([v0[None], states[:-1]], axis=0)
    return C @ v_pre + D @ u, states[-1]


# ------------------------------------------------------------------- SPIKE

def spike_precompose(A, B, C, D, n_chunks):
    """Factor-time SPIKE operators for the affine system of
    `ascan_apply`: the m steps split into C chunks of L = ceil(m/C)
    (identity-padded), and the within-chunk transfer products fold into
    dense per-chunk GEMM operators

        Y  (C, G, L*o, L*kin)  block-lower-triangular input->output map
        YH (C, G, L*o, s)      chunk-inflow -> output correction
        P  (C, G, s, L*kin)    input -> chunk-end particular state
        T  (C, G, s, s)        chunk transfer (propagator product)

    so `spike_apply` solves all chunks as one batched GEMM program
    coupled through a C-step reduced recurrence. The builder is pure jnp
    (traced at factor time, vmap/chunk-map safe); cost O(L^2) batched
    (s x s) matmuls — factor-time, amortized over the step loop."""
    m, G = A.shape[:2]
    s = A.shape[2]
    kin = B.shape[3]
    o = C.shape[2]
    L = -(-m // n_chunks)
    m_pad = n_chunks * L
    dtype = A.dtype

    def pad(arr, fill_eye=False):
        if m_pad == m:
            return arr
        tail_shape = (m_pad - m, G) + arr.shape[2:]
        if fill_eye:
            tail = jnp.broadcast_to(jnp.eye(s, dtype=dtype), tail_shape)
        else:
            tail = jnp.zeros(tail_shape, dtype=dtype)
        return jnp.concatenate([arr, tail], axis=0)

    def chunked(arr):
        # (m_pad, G, r, c) -> (C, L, G, r, c): local step j = axis 1
        return arr.reshape((n_chunks, L, G) + arr.shape[2:])

    Ac = chunked(pad(A, fill_eye=True))
    Bc = chunked(pad(B))
    Cc = chunked(pad(C))
    Dc = chunked(pad(D))
    zero_blk = jnp.zeros((n_chunks, G, o, kin), dtype=dtype)
    rows = []
    yh = []
    carr = []   # carr[r] = (prod_{r < i <= j} A_i) @ B_r, per chunk/group
    H = jnp.broadcast_to(jnp.eye(s, dtype=dtype), (n_chunks, G, s, s))
    for j in range(L):
        Aj, Bj, Cj, Dj = Ac[:, j], Bc[:, j], Cc[:, j], Dc[:, j]
        row = [Cj @ c for c in carr] + [Dj] + [zero_blk] * (L - 1 - j)
        rows.append(jnp.concatenate(row, axis=-1))    # (C, G, o, L*kin)
        yh.append(Cj @ H)
        carr = [Aj @ c for c in carr] + [Bj]
        H = Aj @ H
    Y = jnp.stack(rows, axis=2).reshape(n_chunks, G, L * o, L * kin)
    YH = jnp.stack(yh, axis=2).reshape(n_chunks, G, L * o, s)
    P = jnp.concatenate(carr, axis=-1)                # (C, G, s, L*kin)
    return {"Y": Y, "YH": YH, "P": P, "T": H}


def spike_apply(ops, u, v0):
    """Solve the `ascan_apply` system against factor-time SPIKE
    operators: two batched GEMMs over all chunks plus the C-step reduced
    recurrence — the only sequential scan left, length C (the DTP106
    depth contract). u (m, G, kin, k), v0 (G, s, k); returns
    (outs (m, G, o, k), v_final (G, s, k))."""
    Y, YH, P, T = ops["Y"], ops["YH"], ops["P"], ops["T"]
    m, G, kin, k = u.shape
    n_chunks = Y.shape[0]
    s = T.shape[-1]
    L = P.shape[-1] // kin
    o = Y.shape[2] // L
    m_pad = n_chunks * L
    if m_pad > m:
        u = jnp.concatenate(
            [u, jnp.zeros((m_pad - m, G, kin, k), dtype=u.dtype)], axis=0)
    # (m_pad, G, kin, k) -> (C, G, L*kin, k) in local-step-major order
    uc = u.reshape(n_chunks, L, G, kin, k).transpose(0, 2, 1, 3, 4)
    uc = uc.reshape(n_chunks, G, L * kin, k)
    pend = P @ uc                                     # (C, G, s, k)

    def body(v, xs):
        Tc, pc = xs
        return Tc @ v + pc, v                         # emit chunk INFLOW

    v_final, v_in = jax.lax.scan(body, v0.astype(u.dtype), (T, pend))
    outs = Y @ uc + YH @ v_in                         # (C, G, L*o, k)
    outs = outs.reshape(n_chunks, G, L, o, k).transpose(0, 2, 1, 3, 4)
    outs = outs.reshape(m_pad, G, o, k)[:m]
    return outs, v_final

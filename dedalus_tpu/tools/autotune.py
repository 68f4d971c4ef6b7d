"""
Empirical per-backend plan autotuner: measure-once, cache-forever
fast-path selection (ROADMAP item 2; TurboFNO and the M2L-operators
paper in PAPERS.md are the precedents — fused-kernel and operator-form
wins are architecture-specific, so the right composition is *selected by
measurement per architecture*, not hard-coded).

The config exposes a genuine tuning space — `SOLVE_COMPOSITION` x
`SOLVE_DTYPE` x `REFINE_SWEEPS` x `SPIKE_CHUNKS` (plus the PALLAS
substitution kernel and the `FUSED_TRANSFORMS`/`TRANSPOSE_CHUNKS`
auto picks) — whose optimum is backend- and shape-dependent: the PR-15
CPU sweep measured sequential/f32+2-sweep at 1.166x while ascan ran
0.40x (a depth play priced for accelerators). This module replaces the
hand-coded `auto` heuristics with empirical selection:

  * at first solver build on a (backend, device_kind, problem-shape
    signature), `consult` microbenches the candidate plan cells at the
    OPS level — candidate BandedOps built over the solver's own
    assembled matrices, timed on repeated factor+solve probes with an
    accuracy guard against the sequential/native reference, so an
    inaccurate cell can never win;
  * the decision persists in the content-addressed assembly cache as a
    `tuning` payload (validate-on-install + corrupt-entry quarantine,
    like every other payload kind), keyed by the shape signature — the
    cache is cross-process, so one replica's tuning warms the whole
    serving fleet;
  * warm builds load the decision and perform ZERO microbench probes
    (`probe_count()` is the machine-checked witness, mirroring the
    PR-12 lazy-composite drive);
  * the chosen plan and its measured evidence ride
    `Solver.plan_provenance()` (`plan_source: tuned|config|default`),
    so every results.jsonl row names how its plan was chosen.

`python -m dedalus_tpu tune` runs the OFFLINE harness instead: the
per-cell sweep machinery extracted from benchmarks/fusion.py
`run_solve_sweep` (`measure_build`: warmup trajectory, scanned-block
medians, state-error + residual guards), measuring real end-to-end
steps/s per cell and warming the same cache.

Config discipline (DTL008): config is read ONLY in `resolve_autotune`
and the cell-pinning helpers, at solver-build/CLI time — never on the
step path, and the consulted decision is resolved ONCE per build before
`assembly_cache.solver_key` seals the plan into the cache/pool keys.
User-pinned knobs always win: any non-auto `SOLVE_COMPOSITION`/
`SOLVE_DTYPE`/`REFINE_SWEEPS`/`SPIKE_CHUNKS` disables the tuned path
for that build (`plan_source: config`).
"""

import hashlib
import logging
import time

import numpy as np

from .config import config

logger = logging.getLogger(__name__)

__all__ = ["AutotunePlan", "Decision", "resolve_autotune", "consult",
           "solver_signature", "candidate_cells", "measure_build",
           "probe_solve_residual", "set_solve_config", "pick_winner",
           "tune_offline", "run_tune", "store_decision", "load_decision",
           "seed_decision", "ops_decision", "probe_count", "clear_memo",
           "MODES", "ACCURACY_BAR"]

MODES = ("off", "cached", "force")

TUNING_VERSION = 1

# f64-class accuracy bar for a candidate cell vs the sequential/native
# reference (the PR-15 ladder bar): a fast-but-wrong cell can never win.
# Scaled up for low-precision native dtypes (f32 problems measure their
# candidates against an f32 reference).
ACCURACY_BAR = 1e-10

# backends where the Pallas substitution lowers natively: none today.
# The TPU compiler refuses its (1, n_pad) blocks (ROADMAP D4;
# tests/test_chip_compile.py holds the strict xfail) and elsewhere the
# kernel only runs in interpret mode (a tested emulation, not a
# candidate worth a tuning budget), so the cell records as skipped
# everywhere. Add "tpu" here the day the kernel lowers.
_PALLAS_BACKENDS = ()

# in-process decision memo: signature -> Decision (cross-process
# persistence rides the assembly cache)
_MEMO = {}

# coarse ops-level registry: (ops_kind, system_size) -> Decision, so
# bare BandedOps/DenseOps constructions (no solver threading a plan)
# resolve the SAME plan a tuned solver build picked for that shape
_OPS_DECISIONS = {}

# microbench probe counter: incremented once per measured cell, never on
# a warm (cached-decision) build — tests assert exact zeros against it
_PROBES = [0]

# reentrancy guard: candidate probes build ops/solvers themselves; a
# probe-in-progress must never consult the tuner again
_TUNING = [False]


def probe_count():
    """Total microbench probes performed by this process (one per
    measured candidate cell). A warm build must not move this."""
    return _PROBES[0]


def _count_probe():
    _PROBES[0] += 1


def clear_memo():
    """Drop the in-process decision memo + ops registry (tests)."""
    _MEMO.clear()
    _OPS_DECISIONS.clear()


# ------------------------------------------------------------- resolution

class AutotunePlan:
    """Resolved [autotune] budget knobs (immutable per build)."""

    __slots__ = ("mode", "tune_steps", "budget_sec")

    def __init__(self, mode="off", tune_steps=12, budget_sec=120.0):
        self.mode = mode
        self.tune_steps = int(tune_steps)
        self.budget_sec = float(budget_sec)

    def __repr__(self):
        return (f"AutotunePlan({self.mode}, steps={self.tune_steps}, "
                f"budget={self.budget_sec}s)")


def resolve_autotune():
    """Resolve the [autotune] section. Called once per solver build (and
    per tune CLI run); unknown values raise ValueError AT BUILD — the
    modes gate real measurement budgets and must not silently degrade."""
    section = config["autotune"] if config.has_section("autotune") else {}
    raw = (section.get("MODE", "off") or "off").strip().lower()
    if raw not in MODES:
        raise ValueError(
            f"[autotune] MODE = {raw!r} is not a recognized value "
            f"({'/'.join(MODES)})")
    mode = raw
    raw_steps = (section.get("TUNE_STEPS", "12") or "12").strip().lower()
    try:
        tune_steps = int(raw_steps)
    except ValueError:
        raise ValueError(
            f"[autotune] TUNE_STEPS = {raw_steps!r} is not a recognized "
            "value (an integer >= 1)")
    if tune_steps < 1:
        raise ValueError(
            f"[autotune] TUNE_STEPS = {tune_steps} must be >= 1")
    raw_budget = (section.get("TUNE_BUDGET_SEC", "120") or "120").strip()
    try:
        budget = float(raw_budget)
    except ValueError:
        raise ValueError(
            f"[autotune] TUNE_BUDGET_SEC = {raw_budget!r} is not a "
            "recognized value (a positive number of seconds)")
    if budget <= 0:
        raise ValueError(
            f"[autotune] TUNE_BUDGET_SEC = {budget} must be > 0")
    return AutotunePlan(mode=mode, tune_steps=tune_steps, budget_sec=budget)


# -------------------------------------------------------------- decisions

class Decision:
    """One persisted tuning decision: the chosen plan cell plus the
    measured evidence it was selected on."""

    __slots__ = ("signature", "cell", "evidence", "backend", "device_kind",
                 "evidence_kind", "wall_sec", "margin", "mode", "created",
                 "cache_verdict")

    def __init__(self, signature, cell, evidence=(), backend="?",
                 device_kind="?", evidence_kind="ops_probe", wall_sec=0.0,
                 margin=None, mode="cached", created=None,
                 cache_verdict="fresh"):
        self.signature = signature
        self.cell = dict(cell)
        self.evidence = [dict(c) for c in evidence]
        self.backend = backend
        self.device_kind = device_kind
        self.evidence_kind = evidence_kind
        self.wall_sec = float(wall_sec)
        self.margin = margin
        self.mode = mode
        self.created = float(created) if created is not None \
            else time.time()
        self.cache_verdict = cache_verdict

    def to_record(self):
        return {"tuning_version": TUNING_VERSION,
                "signature": self.signature,
                "cell": dict(self.cell),
                "cells": [dict(c) for c in self.evidence],
                "backend": self.backend,
                "device_kind": self.device_kind,
                "evidence_kind": self.evidence_kind,
                "wall_sec": round(self.wall_sec, 3),
                "margin": self.margin,
                "mode": self.mode,
                "created": self.created}

    @classmethod
    def from_record(cls, record, signature=None):
        """Validated Decision from a cache record, or None on any
        structural/semantic drift (the caller quarantines)."""
        from ..libraries.solvecomp import COMPOSITIONS, SOLVE_DTYPES
        if not isinstance(record, dict):
            return None
        if record.get("tuning_version") != TUNING_VERSION:
            return None
        sig = record.get("signature")
        if not isinstance(sig, str) or \
                (signature is not None and sig != signature):
            return None
        cell = record.get("cell")
        if not isinstance(cell, dict):
            return None
        if cell.get("composition") not in COMPOSITIONS:
            return None
        if cell.get("solve_dtype") not in SOLVE_DTYPES:
            return None
        sweeps = cell.get("refine_sweeps")
        if sweeps is not None and (not isinstance(sweeps, int)
                                   or isinstance(sweeps, bool)
                                   or sweeps < 0):
            return None
        chunks = cell.get("spike_chunks", 0)
        if not isinstance(chunks, int) or isinstance(chunks, bool) \
                or chunks < 0:
            return None
        if not isinstance(cell.get("pallas", False), bool):
            return None
        tchunks = cell.get("transpose_chunks")
        if tchunks is not None and (not isinstance(tchunks, int)
                                    or isinstance(tchunks, bool)
                                    or tchunks < 1):
            return None
        ftrans = cell.get("fused_transforms")
        if ftrans is not None and not isinstance(ftrans, bool):
            return None
        cells = record.get("cells")
        if not isinstance(cells, list):
            return None
        return cls(sig, cell, evidence=[c for c in cells
                                        if isinstance(c, dict)],
                   backend=str(record.get("backend", "?")),
                   device_kind=str(record.get("device_kind", "?")),
                   evidence_kind=str(record.get("evidence_kind", "?")),
                   wall_sec=record.get("wall_sec", 0.0) or 0.0,
                   margin=record.get("margin"),
                   mode=str(record.get("mode", "cached")),
                   created=record.get("created"))

    def provenance(self):
        """The `tuning` block of plan_provenance(): chosen cell plus the
        evidence summary, compact enough for every telemetry row."""
        return {"signature": str(self.signature)[:16],
                "mode": self.mode,
                "evidence_kind": self.evidence_kind,
                "wall_sec": round(self.wall_sec, 3),
                "cache": self.cache_verdict,
                "margin": self.margin,
                "chosen": dict(self.cell),
                "cells": [dict(c) for c in self.evidence]}

    def __repr__(self):
        c = self.cell
        tag = f"{c.get('composition')}/{c.get('solve_dtype')}"
        if c.get("pallas"):
            tag += "+pallas"
        return f"Decision({tag}, sig {str(self.signature)[:8]})"


def cell_label(cell):
    """Human-readable tag for one candidate/chosen cell."""
    tag = f"{cell.get('composition', '?')}/{cell.get('solve_dtype', '?')}"
    if cell.get("pallas"):
        tag += "+pallas"
    sweeps = cell.get("refine_sweeps")
    if sweeps:
        tag += f"+{sweeps}sw"
    return tag


# ------------------------------------------------------------- signatures

def _device_kind():
    try:
        import jax
        return getattr(jax.devices()[0], "device_kind", "?") or "?"
    except Exception:
        return "?"


def solver_signature(solver):
    """Content key of a tuning decision: everything shape- and
    architecture-relevant that is known BEFORE plan resolution (the
    decision must be consultable before `solver_key` seals the plan).
    None when the solver cannot be fingerprinted."""
    try:
        import jax
        G, S = solver.pencil_shape
        spec = solver.matsolver
        spec = spec if isinstance(spec, str) else getattr(
            spec, "__name__", type(spec).__name__)
        h = hashlib.blake2b(digest_size=20)
        for part in ("autotune-v%d" % TUNING_VERSION,
                     jax.default_backend(), _device_kind(),
                     len(jax.devices()), type(solver).__name__,
                     str(spec).lower(), int(G), int(S),
                     np.dtype(solver.pencil_dtype).str):
            h.update(repr(part).encode())
            h.update(b"\x00")
        return h.hexdigest()
    except Exception as exc:
        logger.debug(f"autotune: unfingerprintable solver ({exc!r})")
        return None


# ------------------------------------------------------- cache round-trip

def store_decision(cache, decision):
    """Persist one decision as a `tuning` assembly-cache payload."""
    from . import assembly_cache
    return assembly_cache.store_tuning(cache, decision.signature,
                                       decision.to_record())


def load_decision(cache, signature):
    """Load + validate a persisted decision; any corruption or semantic
    drift quarantines the entry and reports a miss (fresh tune next)."""
    from . import assembly_cache
    record = assembly_cache.load_tuning(cache, signature)
    if record is None:
        return None
    decision = Decision.from_record(record, signature=signature)
    if decision is None:
        logger.warning(
            f"autotune: tuning record {str(signature)[:12]} failed "
            "validation; quarantined, will re-tune")
        cache.discard(signature)
        return None
    return decision


def seed_decision(signature, cell, evidence=(), cache=None, mode="cached",
                  **kw):
    """Install a ready-made decision (tests, progcheck census, warm-cache
    priming): memo + ops registry, and optionally the persistent cache."""
    decision = Decision(signature, cell, evidence=evidence, mode=mode, **kw)
    _MEMO[signature] = decision
    if cache is not None:
        store_decision(cache, decision)
    return decision


def _register_ops(decision, sizes):
    """Expose a solver-level decision to bare-ops constructions of the
    same system size (libraries/pencilops.py fallback paths)."""
    for kind in ("banded", "dense"):
        for n in sizes:
            _OPS_DECISIONS[(kind, int(n))] = decision


def ops_decision(kind, n):
    """The registered decision for a bare-ops construction of `n`-sized
    systems, or None. In-process only: bare ops carry no problem
    fingerprint, so the registry is seeded by tuned SOLVER builds."""
    try:
        return _OPS_DECISIONS.get((kind, int(n)))
    except (TypeError, ValueError):
        return None


# ------------------------------------------------------------- candidates

def candidate_cells(backend=None):
    """The tuning grid: the PR-15 sweep cells (composition x ladder
    dtype) plus the Pallas substitution as a first-class candidate on
    backends that lower it natively (_PALLAS_BACKENDS). The
    sequential/native reference is ALWAYS first — every other cell's
    accuracy is measured against it."""
    if backend is None:
        import jax
        backend = jax.default_backend()
    cells = [
        {"composition": "sequential", "solve_dtype": "native",
         "pallas": False, "reference": True},
        {"composition": "sequential", "solve_dtype": "f32", "pallas": False},
        {"composition": "ascan", "solve_dtype": "native", "pallas": False},
        {"composition": "ascan", "solve_dtype": "f32", "pallas": False},
        {"composition": "spike", "solve_dtype": "native", "pallas": False},
        {"composition": "spike", "solve_dtype": "f32", "pallas": False},
    ]
    pallas = {"composition": "sequential", "solve_dtype": "native",
              "pallas": True}
    if backend not in _PALLAS_BACKENDS:
        pallas["skipped"] = (f"backend {backend!r} cannot lower the "
                             "pallas substitution natively")
    cells.append(pallas)
    return cells


def _accuracy_bar(native_dtype):
    """The per-problem accuracy bar: f64-class for f64 problems, scaled
    to the native precision otherwise (an f32 problem's reference is
    itself f32)."""
    real = np.finfo(np.dtype(native_dtype)).eps \
        if np.dtype(native_dtype).kind in "fc" else np.finfo(float).eps
    return max(ACCURACY_BAR, 1e4 * float(real))


def pick_winner(evidence, bar, rate_key):
    """(winner_cell, margin) from measured evidence: the fastest finite
    cell within the accuracy bar — an inaccurate cell can NEVER win, so
    the reference (rel_err 0) is always eligible. Margin is the
    winner's rate over the runner-up's (None with < 2 eligible)."""
    eligible = []
    for cell in evidence:
        if cell.get("skipped") or cell.get("error"):
            continue
        rate = cell.get(rate_key)
        if not isinstance(rate, (int, float)) or rate <= 0:
            continue
        if cell.get("finite") is False:
            continue
        err = cell.get("rel_err", cell.get("state_rel_err"))
        if err is None or not np.isfinite(err) or err > bar:
            continue
        eligible.append(cell)
    if not eligible:
        return None, None
    ordered = sorted(eligible, key=lambda c: c[rate_key], reverse=True)
    winner = ordered[0]
    margin = None
    if len(ordered) > 1 and ordered[1][rate_key] > 0:
        margin = round(winner[rate_key] / ordered[1][rate_key], 3)
    return winner, margin


def _decision_cell(measured, resolved_sweeps=None, spike_chunks=0):
    """The persisted plan cell for a winning measured cell."""
    return {"composition": measured["composition"],
            "solve_dtype": "native" if measured["solve_dtype"]
            in ("native", "f64") else measured["solve_dtype"],
            "refine_sweeps": resolved_sweeps,
            "spike_chunks": int(spike_chunks),
            "pallas": bool(measured.get("pallas")),
            "fused_transforms": None,
            "transpose_chunks": None}


# ----------------------------------------------------------- the consult

def consult(solver, plan=None, cache=None):
    """The build-time entry point (core/solvers._build_pencil_system):
    the tuned decision for this solver's shape signature, or None when
    the tuner is off, the knobs are user-pinned (`plan_source: config`),
    the problem is out of scope, or tuning is already in progress.

    Warm path (memo/disk hit): ZERO microbench probes. Cold path with
    MODE=cached|force: a bounded in-build ops-level tune, persisted for
    every later process/replica."""
    if plan is None:
        plan = resolve_autotune()
    if plan.mode == "off" or _TUNING[0]:
        return None
    from ..libraries import solvecomp
    if solvecomp.solve_knobs_pinned():
        return None         # explicit config wins: plan_source "config"
    names = tuple(getattr(solver, "matrices", ()) or ())
    if not {"M", "L"}.issubset(set(names)):
        return None         # the tuning space targets the IVP step loop
    sig = solver_signature(solver)
    if sig is None:
        return None
    if plan.mode != "force":
        hit = _MEMO.get(sig)
        if hit is not None:
            hit.cache_verdict = "memo"
            return hit
        if cache is None:
            from . import assembly_cache
            cache = assembly_cache.resolve()
        if cache is not None:
            hit = load_decision(cache, sig)
            if hit is not None:
                hit.cache_verdict = "hit"
                _MEMO[sig] = hit
                _register_ops(hit, solver.pencil_shape[1:])
                logger.info(
                    f"autotune: cached decision {hit!r} "
                    f"(sig {sig[:12]})")
                return hit
    else:
        if cache is None:
            from . import assembly_cache
            cache = assembly_cache.resolve()
    decision = _tune_in_build(solver, plan, sig)
    if decision is None:
        return None
    _MEMO[sig] = decision
    _register_ops(decision, solver.pencil_shape[1:])
    if cache is not None and store_decision(cache, decision):
        decision.cache_verdict = "stored"
    return decision


def _will_go_banded(solver, names):
    """Mirror of the main build's banded-vs-dense choice (the in-build
    probe must measure the representation the build will actually
    compile)."""
    spec = solver.matsolver if isinstance(solver.matsolver, str) else ""
    forced = spec.lower() if spec.lower() in ("banded", "dense") else None
    if forced == "banded":
        return True
    if forced == "dense" or not (isinstance(solver.matsolver, str)
                                 and spec.lower() == "auto"):
        return False
    G, S = solver.pencil_shape
    dense_bytes = G * S * S * np.dtype(solver.pencil_dtype).itemsize
    cutoff = int(config["linear algebra"].get(
        "BANDED_CUTOFF_BYTES", str(1 << 30)))
    return dense_bytes > cutoff


def _tune_in_build(solver, plan, sig):
    """Cold in-build tune: assemble the solver's own matrices (the
    assembly output is plan-independent), run the banded structural
    analysis, and microbench candidate BandedOps cells on repeated
    factor+solve probes. Returns a Decision or None (out of scope /
    probe failure — the build then proceeds untuned)."""
    names = list(solver.matrices)
    try:
        if not _will_go_banded(solver, names):
            return None     # dense path: compositions are inert there
    except Exception:
        return None
    import jax
    t0 = time.perf_counter()
    _TUNING[0] = True
    try:
        solver._assemble_batched(names)
        G, S = solver.pencil_shape
        result = solver._try_banded(names, S)
        if result is not True:
            return None
        structure = solver.structure
        stores = solver._matrices
        evidence = _probe_ops_cells(
            structure, stores, np.dtype(solver.pencil_dtype), plan, t0)
    except Exception as exc:
        logger.warning(f"autotune: in-build tune failed ({exc!r}); "
                       "build proceeds untuned")
        return None
    finally:
        _TUNING[0] = False
    bar = _accuracy_bar(solver.pencil_dtype)
    winner, margin = pick_winner(evidence, bar, "solves_per_sec")
    if winner is None:
        return None
    from ..libraries.solvecomp import _AUTO_SWEEPS
    cell = _decision_cell(winner,
                          resolved_sweeps=winner.get("refine_sweeps"),
                          spike_chunks=0)
    if cell["refine_sweeps"] is None:
        cell["refine_sweeps"] = _AUTO_SWEEPS.get(cell["solve_dtype"])
    wall = time.perf_counter() - t0
    decision = Decision(sig, cell, evidence=evidence,
                        backend=jax.default_backend(),
                        device_kind=_device_kind(),
                        evidence_kind="ops_probe", wall_sec=wall,
                        margin=margin, mode=plan.mode)
    logger.info(f"autotune: tuned {decision!r} in {wall:.1f}s "
                f"(margin {margin}, sig {sig[:12]})")
    return decision


def _probe_ops_cells(structure, stores, dtype, plan, t0):
    """Measure every candidate cell at the ops level: candidate
    BandedOps over the already-assembled band stores, timed on repeated
    jitted solves against one factored a*M + b*L (matsolve is the
    measured ~91% of the step, so solves/s ranks compositions the way
    steps/s does), each compared against the sequential/native
    reference solution. Budget-bounded: cells past TUNE_BUDGET_SEC
    record as skipped rather than silently vanishing."""
    import jax
    backend = jax.default_backend()
    evidence = []
    ref = None
    for cell in candidate_cells(backend):
        entry = {k: cell[k] for k in ("composition", "solve_dtype",
                                      "pallas")}
        if cell.get("skipped"):
            entry["skipped"] = cell["skipped"]
            evidence.append(entry)
            continue
        if ref is not None and \
                time.perf_counter() - t0 > plan.budget_sec:
            entry["skipped"] = (f"tuning budget "
                                f"({plan.budget_sec}s) exhausted")
            evidence.append(entry)
            continue
        try:
            probe = _probe_ops_cell(structure, stores, dtype, cell,
                                    plan.tune_steps,
                                    ref["x"] if ref else None)
        except Exception as exc:
            entry["error"] = repr(exc)
            evidence.append(entry)
            continue
        entry.update({k: probe[k] for k in ("solves_per_sec", "rel_err",
                                            "finite", "refine_sweeps")})
        if cell.get("reference"):
            entry["reference"] = True
            ref = probe
        evidence.append(entry)
    return evidence


def _probe_ops_cell(structure, stores, dtype, cell, tune_steps, ref_x):
    """One cell's microbench: build candidate ops, factor a*M + b*L
    once, then time `tune_steps` jitted solves (median of 3 passes).
    Returns solves/s + accuracy vs the reference solution. Counts one
    probe."""
    import jax
    import jax.numpy as jnp
    from ..core.fusedstep import FusionPlan
    from ..libraries import pencilops
    from ..libraries.solvecomp import SolvePlan, _AUTO_SWEEPS
    _count_probe()
    sdtype = "native" if cell["solve_dtype"] in ("native", "f64") \
        else cell["solve_dtype"]
    sweeps = _AUTO_SWEEPS.get(sdtype)
    splan = SolvePlan(composition=cell["composition"], spike_chunks=0,
                      dtype=sdtype, sweeps=sweeps)
    fplan = FusionPlan(solve=True, matvec=True, transforms=False,
                       donate=False, pallas=bool(cell.get("pallas")))
    ops = pencilops.BandedOps(structure, fusion=fplan, solve_plan=splan)
    M = ops.to_device(stores["M"], dtype)
    L = ops.to_device(stores["L"], dtype)
    G = int(np.asarray(stores["M"]["bands"]).shape[0])
    n = int(structure.S)
    rng = np.random.default_rng(8)
    if np.dtype(dtype).kind == "c":
        rhs_host = (rng.standard_normal((G, n))
                    + 1j * rng.standard_normal((G, n)))
    else:
        rhs_host = rng.standard_normal((G, n))
    rhs = jnp.asarray(rhs_host, dtype=dtype)
    aux = ops.factor_lincomb(1.0, M, 1e-3, L)

    def _solve_probe(a, r):
        return ops.solve(a, r, mats=(M, L))

    # one-shot probe program: built once per measured cell, timed, then
    # dropped — there is no retrace-per-call hazard to hoist away
    solve_jit = jax.jit(_solve_probe)  # dedalus-lint: disable=DTL003 (one-shot tuning probe)
    out = solve_jit(aux, rhs)
    x = np.asarray(out)             # deliberate host sync + accuracy copy
    times = []
    for _ in range(3):
        start = time.perf_counter()
        for _ in range(max(1, int(tune_steps))):
            out = solve_jit(aux, rhs)
        tail = np.asarray(out)      # deliberate host sync
        times.append(time.perf_counter() - start)
    del tail
    rate = max(1, int(tune_steps)) / float(np.median(times))
    finite = bool(np.isfinite(x).all())
    if ref_x is None:
        rel = 0.0
    else:
        scale = float(np.max(np.abs(ref_x))) or 1.0
        rel = float(np.max(np.abs(x - ref_x)) / scale)
    return {"solves_per_sec": round(float(rate), 3),
            "rel_err": rel, "finite": finite,
            "refine_sweeps": sweeps, "x": x}


# -------------------------------------------- offline (step-level) harness

def set_solve_config(composition="auto", solve_dtype="auto", sweeps="auto",
                     spike_chunks="auto", pallas=None):
    """Pin the solve composition + precision ladder for the next build
    (the [fusion]/[precision] knobs of the solve-composition sweep;
    extracted from benchmarks/fusion.py so the benchmark and the tuner
    pin cells identically). `pallas=None` leaves the flag untouched."""
    for section in ("fusion", "precision"):
        if not config.has_section(section):
            config.add_section(section)
    config["fusion"]["SOLVE_COMPOSITION"] = composition
    config["fusion"]["SPIKE_CHUNKS"] = spike_chunks
    config["precision"]["SOLVE_DTYPE"] = solve_dtype
    config["precision"]["REFINE_SWEEPS"] = sweeps
    if pallas is not None:
        config["fusion"]["PALLAS"] = pallas


class _cell_config:
    """Pin one candidate cell's config for a measured build, restored on
    exit. MODE is pinned off so the measured builds can never recurse
    into the tuner."""

    _KEYS = (("fusion", "SOLVE_COMPOSITION"), ("fusion", "SPIKE_CHUNKS"),
             ("fusion", "PALLAS"), ("fusion", "FUSED_SOLVE"),
             ("precision", "SOLVE_DTYPE"), ("precision", "REFINE_SWEEPS"),
             ("autotune", "MODE"))

    def __init__(self, cell):
        self.cell = cell

    def __enter__(self):
        for section in {s for s, _ in self._KEYS}:
            if not config.has_section(section):
                config.add_section(section)
        self.saved = {(s, k): config[s].get(k) for s, k in self._KEYS}
        cell = self.cell
        sdtype = cell.get("solve_dtype", "native")
        set_solve_config(
            composition=cell.get("composition", "auto"),
            solve_dtype="auto" if sdtype in ("native", "f64") else sdtype,
            sweeps="auto", spike_chunks="auto",
            pallas="on" if cell.get("pallas") else "off")
        config["fusion"]["FUSED_SOLVE"] = "on"
        config["autotune"]["MODE"] = "off"
        return self

    def __exit__(self, *exc):
        for (s, k), val in self.saved.items():
            if val is None:
                config[s].pop(k, None)
            else:
                config[s][k] = val


def measure_build(build, n_steps, block, blocks, solver_out=None):
    """Build, advance `n_steps` (trajectory warmup; single steps so only
    one scanned block size compiles below), then measure median steps/s
    over `blocks` scanned step_many blocks — the per-cell sweep
    machinery extracted from benchmarks/fusion.py run_solve_sweep.
    `solver_out` (a list) receives the live solver for post-measurement
    probes. Counts one microbench probe. Returns (result dict,
    post-warmup host state)."""
    _count_probe()
    solver, dt = build()
    if solver_out is not None:
        solver_out.append(solver)
    for _ in range(n_steps):
        solver.step(dt)
    x = solver.X
    state = np.asarray(x).copy()    # deliberate host sync + snapshot
    solver.step_many(block, dt)     # compile the block program
    x = solver.X
    np.asarray(x)                   # deliberate host sync
    rates = []
    for _ in range(blocks):
        start = time.perf_counter()
        solver.step_many(block, dt)
        x = solver.X
        tail = np.asarray(x)        # deliberate host sync (timed edge)
        rates.append(block / (time.perf_counter() - start))
    finite = bool(np.isfinite(tail).all())
    return {
        "steps_per_sec": round(float(np.median(rates)), 3),
        "steps_per_sec_iqr": round(float(np.percentile(rates, 75)
                                         - np.percentile(rates, 25)), 3),
        "finite": finite,
    }, state


def probe_solve_residual(solver):
    """Achieved relative residual of one probe solve against the live
    LHS factorization (the ladder accuracy record), or None."""
    import jax.numpy as jnp
    ts = getattr(solver, "timestepper", None)
    aux = getattr(ts, "_lhs_aux", None)
    if aux is None or not hasattr(solver.ops, "solve_report"):
        return None
    aux0 = aux[0] if isinstance(aux, list) else aux
    try:
        _, rel = solver.ops.solve_report(
            aux0, jnp.asarray(solver.X),
            mats=(solver.M_mat, solver.L_mat))
    except Exception:
        return None
    return None if rel is None else float(np.asarray(rel))


def tune_offline(build, plan=None, label="", n_steps=12, block=20,
                 blocks=5):
    """The offline (CLI / pre-tuning) harness: measure every candidate
    cell END TO END — real solver builds, real steps/s — under the
    state-error + residual guards, and return (Decision, evidence).
    Budget-bounded like the in-build probe; the decision's signature is
    taken from the reference build, so it warms exactly the builds
    `consult` will serve."""
    import jax
    if plan is None:
        plan = resolve_autotune()
    backend = jax.default_backend()
    t0 = time.perf_counter()
    evidence = []
    ref_state = None
    signature = None
    native_dtype = None
    for cell in candidate_cells(backend):
        entry = {k: cell[k] for k in ("composition", "solve_dtype",
                                      "pallas")}
        if cell.get("skipped"):
            entry["skipped"] = cell["skipped"]
            evidence.append(entry)
            continue
        if ref_state is not None and \
                time.perf_counter() - t0 > plan.budget_sec:
            entry["skipped"] = (f"tuning budget "
                                f"({plan.budget_sec}s) exhausted")
            evidence.append(entry)
            continue
        holder = []
        try:
            with _cell_config(cell):
                result, state = measure_build(
                    build, n_steps, block, blocks, solver_out=holder)
        except Exception as exc:
            entry["error"] = repr(exc)
            evidence.append(entry)
            continue
        solver = holder[0]
        splan = getattr(solver, "_solve_plan", None)
        entry.update(result)
        entry["refine_sweeps"] = None if splan is None else splan.sweeps
        entry["achieved_residual"] = probe_solve_residual(solver)
        if ref_state is None:
            entry["reference"] = True
            entry["rel_err"] = 0.0
            ref_state = state
            signature = solver_signature(solver)
            native_dtype = np.dtype(solver.pencil_dtype)
        else:
            scale = float(np.max(np.abs(ref_state))) or 1.0
            entry["rel_err"] = float(
                np.max(np.abs(state - ref_state)) / scale)
        evidence.append(entry)
    if signature is None:
        return None, evidence
    bar = _accuracy_bar(native_dtype)
    winner, margin = pick_winner(evidence, bar, "steps_per_sec")
    if winner is None:
        return None, evidence
    cell = _decision_cell(winner,
                          resolved_sweeps=winner.get("refine_sweeps"))
    decision = Decision(signature, cell, evidence=evidence,
                        backend=backend, device_kind=_device_kind(),
                        evidence_kind="step_sweep",
                        wall_sec=time.perf_counter() - t0,
                        margin=margin, mode=plan.mode)
    return decision, evidence


# ------------------------------------------------------------ the tune CLI

_PROBLEMS = ("rb256x64", "rb64x32", "diffusion64")


def _problem_build(name, dtype):
    from ..extras.bench_problems import (build_diffusion_solver,
                                         build_rb_solver)
    if name == "rb256x64":
        return lambda: (build_rb_solver(256, 64, dtype,
                                        matsolver="banded")[0], 0.01)
    if name == "rb64x32":
        return lambda: (build_rb_solver(64, 32, dtype,
                                        matsolver="banded")[0], 0.01)
    if name == "diffusion64":
        return lambda: (build_diffusion_solver(64, dtype), 1e-3)
    raise ValueError(f"unknown tune problem {name!r} "
                     f"(one of {', '.join(_PROBLEMS)})")


def run_tune(problem="rb256x64", force=False, quick=False, as_json=False,
             record=True, steps=None, budget=None, out=print):
    """`python -m dedalus_tpu tune`: pre-tune one benchmark problem
    offline, persist the decision (warming every later build/replica on
    this cache), and append a `kind: autotune` evidence row to
    benchmarks/results.jsonl. Returns a process exit code."""
    import json as json_mod
    import jax
    from . import assembly_cache
    try:
        plan = resolve_autotune()
    except ValueError as exc:
        out(f"tune: {exc}")
        return 2
    if steps is not None:
        plan.tune_steps = int(steps)
    if budget is not None:
        plan.budget_sec = float(budget)
    dtype = np.float64 if jax.default_backend() == "cpu" else np.float32
    try:
        build = _problem_build(problem, dtype)
    except ValueError as exc:
        out(f"tune: {exc}")
        return 2
    cache = assembly_cache.resolve()
    if not force and cache is not None:
        # a measured decision may already exist: probe it via one cheap
        # reference build signature
        pass
    n_steps, block, blocks = (4, 8, 2) if quick else (12, 20, 5)
    decision, evidence = tune_offline(build, plan=plan, label=problem,
                                      n_steps=n_steps, block=block,
                                      blocks=blocks)
    if decision is None:
        out(f"tune: {problem}: no accurate candidate cell survived "
            "(see cells below)")
        for cell in evidence:
            out(f"  {cell_label(cell)}: "
                f"{cell.get('skipped') or cell.get('error') or cell}")
        return 1
    decision.mode = "force" if force else plan.mode
    stored = False
    if cache is not None:
        stored = store_decision(cache, decision)
        decision.cache_verdict = "stored" if stored else "store-failed"
    else:
        decision.cache_verdict = "cache-disabled"
    _MEMO[decision.signature] = decision
    row = {
        "kind": "autotune",
        "config": problem,
        "backend": decision.backend,
        "device_kind": decision.device_kind,
        "signature": decision.signature,
        "evidence_kind": decision.evidence_kind,
        "mode": decision.mode,
        "forced": bool(force),
        "chosen": dict(decision.cell),
        "chosen_label": cell_label(decision.cell),
        "margin": decision.margin,
        "tuning_wall_sec": round(decision.wall_sec, 3),
        "cache": decision.cache_verdict,
        "cells": [dict(c) for c in evidence],
        "trajectory_steps": n_steps,
        "quick": bool(quick),
        "ts": round(time.time(), 1),
    }
    if record and not quick:
        try:
            from __graft_entry__ import _append_result
            _append_result(row)
        except Exception as exc:
            logger.warning(f"tune: could not record results row ({exc!r})")
    if as_json:
        out(json_mod.dumps(row, indent=2, default=str))
        return 0
    out(f"tune {problem} [{decision.backend}/{decision.device_kind}]: "
        f"chosen {cell_label(decision.cell)} "
        f"(margin {decision.margin or '?'}x over runner-up, "
        f"wall {decision.wall_sec:.1f}s, cache {decision.cache_verdict})")
    for cell in evidence:
        if cell.get("skipped"):
            out(f"  {cell_label(cell)}: skipped ({cell['skipped']})")
        elif cell.get("error"):
            out(f"  {cell_label(cell)}: ERROR {cell['error']}")
        else:
            tag = " (reference)" if cell.get("reference") else ""
            out(f"  {cell_label(cell)}: "
                f"{cell.get('steps_per_sec', '?')} steps/s, "
                f"err {cell.get('rel_err', '?'):.1e}{tag}")
    return 0

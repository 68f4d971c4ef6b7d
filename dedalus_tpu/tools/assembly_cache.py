"""
Persistent pencil-matrix assembly cache (the on-disk tier of tools/cache.py).

Cold starts pay a host-side symbolic walk (`expression_matrices` + scipy
kron folds) plus the banded structural analysis for every solver build,
even when the problem is byte-identical to the last run. This module
content-addresses the OUTPUTS of `core.solvers.SolverBase.
_build_pencil_system` — the shared-pattern COO store, or the banded
arrays + permutations + Woodbury pin data — under a key derived from
everything that determines them:

  * the equation expression TREES (class names, scalars, operator
    parameters — not just the equation strings, which would alias
    different parameter values),
  * non-variable (NCC/background) field DATA feeding the LHS matrices
    (hashed bytes, so parameter continuation and Newton rebuilds can
    never alias),
  * variable names/dtypes/tensor signatures and per-basis specs
    (class, size, bounds/radii, dealias, k, ...),
  * the solver class, matrix names, matsolver spec and the [linear
    algebra] knobs that steer the structural path,
  * the package version and a cache format version.

Entries are single `.npb` array bundles (magic + JSON meta line + raw
`np.save` members — no zip/CRC pass, which dominated warm load time;
`allow_pickle=False` end-to-end) under `[caching] ASSEMBLY_CACHE`,
mirroring the persistent XLA cache layout next door. Writes are atomic
(tmp file + `os.replace`, fsync'd) following the torn-file discipline of
tools/resilience.py; loads validate the payload (format/key/shape
checks, full parse) and fall back to fresh assembly on ANY corruption,
quarantining the bad entry. Eviction is LRU by mtime under
`ASSEMBLY_CACHE_MAX_MB` (hits touch their entry).
"""

import hashlib
import json
import logging
import os
import pathlib
import tempfile

import numpy as np
import scipy.sparse as sp

from .config import config

logger = logging.getLogger(__name__)

__all__ = ["AssemblyCache", "pool_key", "resolve", "solver_key", "clear"]

FORMAT_VERSION = 2

# Config keys (outside [caching]) whose values steer which representation
# is assembled; they ride into the key so a knob flip cannot alias.
_KEYED_CONFIG = (
    ("linear algebra", "MATRIX_SOLVER"),
    ("linear algebra", "BANDED_CUTOFF_BYTES"),
    ("linear algebra", "BAND_DETECT_CUTOFF"),
    ("linear algebra", "BANDED_MAX_DIAGS"),
)


# ------------------------------------------------------------ fingerprints

class Unfingerprintable(Exception):
    """Expression/field graph contains something we cannot hash safely."""


def _fp_update(h, *parts):
    for part in parts:
        h.update(repr(part).encode())
        h.update(b"\x00")


# Constructor-parameter attributes that define a basis. An explicit
# allowlist, NOT the whole __dict__: interned bases grow lazily-cached
# attributes over a session (CachedAttribute materializes on first
# access), which would make the fingerprint depend on what OTHER code
# already touched the basis.
_BASIS_ATTRS = (
    "size", "shape", "bounds", "radii", "radius", "dealias", "a", "b",
    "a0", "b0", "k", "alpha", "dtype", "library", "colatitude_library",
    "radius_library", "kappa", "rho", "length", "dR", "Lmax", "Nr",
    "Ntheta", "ell_separable", "complex",
)


def _fp_basis(h, basis, seen):
    if basis is None:
        _fp_update(h, "basis:None")
        return
    if id(basis) in seen:
        _fp_update(h, "basis-ref", seen[id(basis)])
        return
    seen[id(basis)] = len(seen)
    _fp_update(h, "basis", type(basis).__name__)
    for key in _BASIS_ATTRS:
        val = basis.__dict__.get(key)
        if val is None:
            continue
        if isinstance(val, (int, float, complex, str, bool, np.integer,
                            np.floating)):
            _fp_update(h, key, val)
        elif isinstance(val, tuple) and all(
                isinstance(v, (int, float, str, bool)) for v in val):
            _fp_update(h, key, val)
        elif isinstance(val, np.dtype):
            _fp_update(h, key, val.str)
        elif isinstance(val, type):
            _fp_update(h, key, val.__name__)
    coord = getattr(basis, "coord", None) or getattr(basis, "coordsystem",
                                                     None)
    _fp_update(h, "first_axis", basis.first_axis, "dim", basis.dim,
               "coord", getattr(coord, "name", None))
    # derived size invariants, in case a basis class stores a shape
    # parameter under a name outside the allowlist
    try:
        _fp_update(h, "sizes", tuple(int(basis.coeff_size(sub))
                                     for sub in range(basis.dim)))
    except Exception:
        pass


def _fp_domain(h, domain, seen):
    _fp_update(h, "domain", len(domain.bases))
    for basis in domain.bases:
        _fp_basis(h, basis, seen)


def _fp_field(h, field, variables, seen):
    from ..core.subsystems import state_key
    _fp_update(h, "field", field.name, np.dtype(field.dtype).str,
               tuple(type(cs).__name__ for cs in field.tensorsig),
               tuple(cs.dim for cs in field.tensorsig))
    _fp_domain(h, field.domain, seen)
    if field in variables:
        # variables enter symbolically: identified by position/name only
        _fp_update(h, "variable", [state_key(v) for v in variables].index(
            state_key(field)))
    else:
        # NCC / parameter field: the DATA is baked into the matrices
        data = np.asarray(field.coeff_data())
        _fp_update(h, "data", data.shape, data.dtype.str)
        h.update(np.ascontiguousarray(data).tobytes())


def _fp_expr(h, expr, variables, seen):
    from ..core.field import Field
    from ..core.future import Future
    from ..core.coords import CoordinateSystem
    from ..core.basis import Basis
    if expr is None:
        _fp_update(h, "none")
        return
    if np.isscalar(expr):
        _fp_update(h, "scalar", expr)
        return
    if isinstance(expr, CoordinateSystem):
        # operator parameters (Differentiate's coordinate, Gradient's cs):
        # the interning token names the coordsystem + distributor axes
        _fp_update(h, "coords", type(expr).__name__, expr._cache_token)
        return
    if isinstance(expr, Basis):
        # Lift/Convert target bases in args
        _fp_basis(h, expr, seen)
        return
    if isinstance(expr, Field):
        _fp_field(h, expr, variables, seen)
        return
    if not isinstance(expr, Future):
        raise Unfingerprintable(f"unhashable node {type(expr).__name__}")
    _fp_update(h, "op", type(expr).__name__)
    # Operator parameters living outside .args: Lift/Convert TARGET BASES
    # (`basis`, `basis_in`, `target_bases`), interpolation positions,
    # scalar multipliers, coordinate systems, component indices, ... —
    # anything of an unrecognized type FAILS CLOSED (Unfingerprintable ->
    # no caching) rather than silently dropping out of the key, which
    # would let distinct problems collide on one cache entry.
    for key in sorted(expr.__dict__):
        if key in ("args", "domain", "tensorsig", "dtype", "dist") or \
                key.startswith("_"):
            continue
        _fp_value(h, key, expr.__dict__[key], variables, seen)
    for arg in expr.args:
        _fp_expr(h, arg, variables, seen)
    _fp_update(h, "end")


def _fp_value(h, key, val, variables, seen):
    """Fingerprint one operator attribute/parameter value (fails closed
    on unrecognized types)."""
    from ..core.field import Field
    from ..core.future import Future
    from ..core.coords import CoordinateSystem
    from ..core.basis import Basis
    if val is None or isinstance(val, (int, float, complex, str, bool,
                                       np.integer, np.floating)):
        _fp_update(h, key, val)
    elif isinstance(val, np.dtype):
        _fp_update(h, key, val.str)
    elif isinstance(val, Basis):
        _fp_update(h, key)
        _fp_basis(h, val, seen)
    elif isinstance(val, CoordinateSystem):
        _fp_update(h, key, type(val).__name__, val._cache_token)
    elif isinstance(val, (Field, Future)):
        _fp_update(h, key)
        _fp_expr(h, val, variables, seen)
    elif isinstance(val, np.ndarray):
        _fp_update(h, key, val.shape, val.dtype.str)
        h.update(np.ascontiguousarray(val).tobytes())
    elif isinstance(val, (tuple, list)):
        _fp_update(h, key, len(val))
        for i, item in enumerate(val):
            _fp_value(h, f"{key}[{i}]", item, variables, seen)
    else:
        raise Unfingerprintable(
            f"operator attribute {key} of type {type(val).__name__}")


def solver_key(solver, names):
    """Content hash for one solver's pencil system, or None when the
    problem graph cannot be fingerprinted safely."""
    from .. import __version__
    try:
        h = hashlib.blake2b(digest_size=20)
        _fp_update(h, "format", FORMAT_VERSION, "version", __version__,
                   "solver", type(solver).__name__, "names", tuple(names))
        for section, key in _KEYED_CONFIG:
            _fp_update(h, key, config[section].get(key, ""))
        # fused-step composition (core/fusedstep.py): the RESOLVED fusion
        # token rides into the key so a [fusion] flag flip (or an `auto`
        # landing differently on another backend) can never serve a
        # payload whose precomposed fused matrices were built under
        # another composition. The host-assembly matrices themselves are
        # fusion-independent, but this key seeds pool_key — the serving
        # warm pool holds COMPILED step programs, which do depend on the
        # composition — and the fused-composite entries, so a flip
        # invalidates all three together. Cost: a rare flag flip re-runs
        # host assembly once; the safe direction. The solver's
        # build-start plan is preferred so the key always tokens the
        # composition the build actually compiles under.
        plan = getattr(solver, "_fusion_plan", None)
        if plan is None:
            from ..core.fusedstep import cache_token
            _fp_update(h, "fusion", cache_token())
        else:
            _fp_update(h, "fusion", plan.token())
        # resolved [distributed] transpose chunking: the chunk structure
        # shapes every compiled sharded walk, and this key seeds
        # pool_key — pooled entries hold COMPILED step programs, so two
        # chunk configs must never alias one warm entry (the host
        # matrices themselves are chunk-independent; same safe-direction
        # trade as the fusion token above)
        chunks = getattr(solver, "_transpose_chunks", None)
        if chunks is None:
            from ..parallel.transposes import resolve_transpose_chunks
            chunks = resolve_transpose_chunks()
        _fp_update(h, "transpose_chunks", int(chunks))
        # resolved solve composition + precision ladder (libraries/
        # solvecomp.py): the composition restructures the compiled
        # substitution programs and the ladder changes the factor-store
        # dtype — pooled compiled solvers and fused-composite payloads
        # must never alias across either (same safe-direction trade as
        # the fusion/chunk tokens above)
        splan = getattr(solver, "_solve_plan", None)
        if splan is None:
            from ..libraries.solvecomp import solve_plan_token
            _fp_update(h, "solve_plan", solve_plan_token())
        else:
            _fp_update(h, "solve_plan", splan.token())
        spec = solver.matsolver
        _fp_update(h, "matsolver",
                   spec if isinstance(spec, str) else getattr(
                       spec, "__name__", type(spec).__name__))
        # layout coupling: a matrix_coupling override (or NCC forcing)
        # changes which axes are separable without changing the equation
        # trees — equal-sized alternate couplings must not collide on one
        # entry
        layout = solver.layout
        _fp_update(h, "coupled_axes", tuple(layout.coupled_axes),
                   "sep_widths", tuple(sorted(layout.sep_widths.items())))
        seen = {}
        variables = list(solver.variables)
        _fp_update(h, "nvars", len(variables))
        for v in variables:
            _fp_field(h, v, variables, seen)
        _fp_update(h, "neqs", len(solver.equations))
        for eq in solver.equations:
            members = eq["members"] if "members" in eq else [(eq, None)]
            _fp_update(h, "block", len(members))
            _fp_domain(h, eq["domain"], seen)
            _fp_update(h, "tsig", tuple(cs.dim for cs in eq["tensorsig"]))
            for member, _cond in members:
                _fp_update(h, "cond", member.get("condition"))
                for name in names:
                    _fp_expr(h, member.get(name), variables, seen)
        return h.hexdigest()
    except Unfingerprintable as exc:
        logger.debug(f"assembly cache: unfingerprintable problem ({exc})")
        return None
    except Exception as exc:
        logger.debug(f"assembly cache: fingerprint failed ({exc!r})")
        return None


def pool_key(solver):
    """Warm-pool identity of a BUILT solver — the key the service tier
    (dedalus_tpu/service/pool.py) stores live compiled solvers under.

    It is the assembly-cache content key (reusing the key stashed at
    build time as `solver.assembly_key` when the persistent cache
    computed one, recomputing otherwise) composed with everything else
    that makes two LIVE solvers interchangeable but that the assembly
    key deliberately excludes (M/L matrices are scheme-independent, so
    cached matrices shard across these):

      * the timestepper scheme — the compiled step programs and
        factorizations a pooled entry holds are scheme-specific;
      * the run-behavior knobs (`warmup_iterations`,
        `enforce_real_cadence`) — two specs that build identical
        matrices but different Hermitian-projection cadences would
        produce DIFFERENT trajectories from one shared entry.

    Returns None when the problem graph cannot be fingerprinted; the
    pool then falls back to its normalized-spec digest."""
    key = getattr(solver, "assembly_key", None)
    if key is None:
        key = solver_key(solver, solver.matrices)
    if key is None:
        return None
    ts = getattr(solver, "timestepper", None)
    h = hashlib.blake2b(digest_size=20)
    _fp_update(h, "pool", key,
               "scheme", type(ts).__name__ if ts is not None else None,
               "warmup", getattr(solver, "warmup_iterations", None),
               "enforce_real", getattr(solver, "enforce_real_cadence",
                                       None))
    return h.hexdigest()


# ------------------------------------------------------------- disk store

class AssemblyCache:
    """One on-disk cache directory of raw array-bundle payloads.

    Entry format (`.npb`): a magic line, one JSON meta line (which names
    the arrays in order), then each array appended via `np.save` — NOT a
    zip/npz, whose per-member CRC pass costs ~0.3 s on a warm RB 256x64
    load and would eat most of the cache's win."""

    MAGIC = b"DTASM\n"

    def __init__(self, directory, max_mb=2048):
        self.directory = pathlib.Path(os.path.expanduser(str(directory)))
        self.max_bytes = int(float(max_mb) * 1e6)

    def _path(self, key):
        return self.directory / f"asm-{key}.npb"

    def load(self, key):
        """Validated payload {meta: dict, arrays: dict} or None. Any
        corruption (torn write, truncation, stale format) quarantines the
        entry and reports a miss."""
        path = self._path(key)
        if not path.exists():
            return None
        try:
            with open(path, "rb") as f:
                if f.readline() != self.MAGIC:
                    raise ValueError("bad magic")
                meta = json.loads(f.readline().decode())
                if meta.get("format") != FORMAT_VERSION:
                    raise ValueError(f"format {meta.get('format')}")
                if meta.get("key") != key:
                    raise ValueError("key mismatch")
                arrays = {name: np.load(f, allow_pickle=False)
                          for name in meta["array_names"]}
                if f.read(1):
                    raise ValueError("trailing bytes")
        except OSError as exc:
            # transient access failure (EIO/EINTR, NFS hiccup): the entry
            # on disk may be intact — report a miss but do NOT quarantine
            logger.warning(
                f"assembly cache entry {path.name} unreadable "
                f"({exc!r}); falling back to fresh assembly")
            return None
        except Exception as exc:
            logger.warning(
                f"assembly cache entry {path.name} unusable "
                f"({exc!r}); falling back to fresh assembly")
            try:
                os.remove(path)
            except OSError:
                pass
            return None
        try:
            os.utime(path)   # LRU touch
        except OSError:
            # read-only cache dir (shared prebuilt warm cache): the entry
            # parsed cleanly, so it is a hit — only the recency stamp is
            # lost
            pass
        return {"meta": meta, "arrays": arrays}

    def discard(self, key):
        """Quarantine one entry (best-effort removal: a payload that
        parsed but failed to install must not poison every future build)."""
        try:
            os.remove(self._path(key))
        except OSError:
            pass

    def store(self, key, meta, arrays):
        """Atomic write (tmp + replace): a crash mid-write can never leave
        a half-visible entry, only an orphaned tmp file."""
        meta = dict(meta)
        meta["format"] = FORMAT_VERSION
        meta["key"] = key
        meta["array_names"] = sorted(arrays)
        path = self._path(key)
        nbytes = sum(np.asarray(a).nbytes for a in arrays.values())
        if nbytes > self.max_bytes:
            # an entry over the whole budget would be written, synced and
            # evicted at once: RB 2048x1024 wrote 8.4 GB per build that way
            # (v5e host, PR 28: 52.7 GiB of disk writes in six runs)
            logger.info(
                f"assembly cache: entry {str(key)[:12]} of "
                f"{nbytes / 1e6:.0f} MB passes ASSEMBLY_CACHE_MAX_MB "
                f"({self.max_bytes / 1e6:.0f}); not stored")
            return False
        try:
            self.directory.mkdir(parents=True, exist_ok=True)
            fd, tmp = tempfile.mkstemp(dir=str(self.directory),
                                       prefix=".asm-tmp-")
            try:
                with os.fdopen(fd, "wb") as f:
                    f.write(self.MAGIC)
                    f.write(json.dumps(meta).encode() + b"\n")
                    for name in meta["array_names"]:
                        np.save(f, np.asarray(arrays[name]),
                                allow_pickle=False)
                    f.flush()
                    os.fsync(f.fileno())
                os.replace(tmp, path)
            except BaseException:
                try:
                    os.remove(tmp)
                except OSError:
                    pass
                raise
            self._evict()
            return True
        except OSError as exc:
            logger.warning(f"assembly cache write failed: {exc}")
            return False

    def _evict(self):
        """Drop oldest entries (mtime LRU) above the size budget."""
        try:
            paths = list(self.directory.glob("asm-*.np[bz]"))
        except OSError:
            return
        entries = []
        for p in paths:
            try:
                st = p.stat()
            except OSError:
                # concurrently removed by another process: skip it, keep
                # enforcing the budget over the rest
                continue
            entries.append((st.st_mtime, st.st_size, p))
        total = sum(size for _, size, _ in entries)
        if total <= self.max_bytes:
            return
        for _, size, path in sorted(entries):
            try:
                os.remove(path)
                total -= size
            except OSError:
                pass
            if total <= self.max_bytes:
                break

    def clear(self):
        for path in self.directory.glob("asm-*.np[bz]"):
            try:
                os.remove(path)
            except OSError:
                pass


def resolve():
    """The configured cache, or None when disabled. The
    DEDALUS_TPU_ASSEMBLY_CACHE environment variable overrides the
    [caching] ASSEMBLY_CACHE directory ('' disables), so subprocesses
    (tests, benchmarks) can redirect it without a config file."""
    directory = os.environ.get("DEDALUS_TPU_ASSEMBLY_CACHE")
    if directory is None:
        directory = config["caching"].get("ASSEMBLY_CACHE", "").strip() \
            if config.has_section("caching") else ""
    if not directory:
        return None
    max_mb = config["caching"].getfloat("ASSEMBLY_CACHE_MAX_MB",
                                        fallback=2048.0) \
        if config.has_section("caching") else 2048.0
    return AssemblyCache(directory, max_mb=max_mb)


def clear():
    cache = resolve()
    if cache is not None:
        cache.clear()


# -------------------------------------------------- solver payload codecs

def export_payload(solver, names):
    """(meta, arrays) snapshot of a freshly built pencil system, or None
    when the representation is not worth persisting."""
    G, S = solver.pencil_shape
    meta = {"kind": None, "names": list(names), "G": int(G), "S": int(S)}
    arrays = {}
    if solver.structure is not None:
        st = solver.structure
        meta["kind"] = "banded"
        meta["structure"] = {
            "S": int(st.S), "NB": int(st.NB), "q": int(st.q),
            "kl": int(st.kl), "ku": int(st.ku), "t_pins": int(st.t_pins),
            "n_modes": int(getattr(st, "n_modes", 0)),
            "n_caxes": int(getattr(st, "n_caxes", 1)),
        }
        for attr in ("row_perm", "col_perm", "row_pos", "pinned_rows",
                     "pinned_positions"):
            arrays[f"st_{attr}"] = np.asarray(getattr(st, attr))
        for name in names:
            store = solver._matrices[name]
            arrays[f"bands_{name}"] = store["bands"]
            arrays[f"Vt_{name}"] = store["Vt"]
            if "dsel" in store:
                arrays[f"dsel_{name}"] = np.asarray(store["dsel"], dtype=int)
        return meta, arrays
    if solver._batched is not None:
        pr, pc, vals, row_valid, col_valid = solver._batched
        meta["kind"] = "coo"
        arrays["pattern_rows"] = np.asarray(pr)
        arrays["pattern_cols"] = np.asarray(pc)
        arrays["row_valid"] = np.asarray(row_valid)
        arrays["col_valid"] = np.asarray(col_valid)
        for name in names:
            arrays[f"vals_{name}"] = np.asarray(vals[name])
        return meta, arrays
    # per-group dense fallback: persist the dense store below a size cap
    # (rare path: unbatchable expression trees with small G)
    total = sum(solver._matrices[name].nbytes for name in names)
    if total > 256e6:
        return None
    meta["kind"] = "dense"
    for name in names:
        arrays[f"dense_{name}"] = solver._matrices[name]
    return meta, arrays


def install_payload(solver, names, payload):
    """Rebuild solver._matrices/structure/ops from a cache payload.
    Returns True on success; False (clean miss) on any inconsistency."""
    from ..core.subsystems import MatrixStructure
    from ..libraries import pencilops
    meta, arrays = payload["meta"], payload["arrays"]
    G, S = solver.pencil_shape
    if (meta.get("names") != list(names) or meta.get("G") != G
            or meta.get("S") != S):
        return False
    kind = meta.get("kind")
    if kind == "banded":
        state = {k: int(v) for k, v in meta["structure"].items()}
        for attr in ("row_perm", "col_perm", "row_pos", "pinned_rows",
                     "pinned_positions"):
            state[attr] = arrays[f"st_{attr}"]
        state["n_interior"] = state["S"]
        st = MatrixStructure.from_state(state, solver.layout)
        mats = {}
        for name in names:
            store = {"bands": arrays[f"bands_{name}"],
                     "Vt": arrays[f"Vt_{name}"]}
            if f"dsel_{name}" in arrays:
                store["dsel"] = tuple(int(d) for d in arrays[f"dsel_{name}"])
            mats[name] = store
        solver._batched = None
        solver._matrices = mats
        solver.structure = st
        solver.ops = pencilops.BandedOps(
            st, fusion=getattr(solver, "_fusion_plan", None),
            solve_plan=getattr(solver, "_solve_plan", None))
        return True
    if kind == "coo":
        vals = {name: arrays[f"vals_{name}"] for name in names}
        solver._batched = (arrays["pattern_rows"], arrays["pattern_cols"],
                           vals, arrays["row_valid"], arrays["col_valid"])
        solver._matrices = solver._dense_from_batched(names)
        solver.structure = None
        solver.ops = pencilops.DenseOps(
            solver._dense_matsolver(),
            solve_plan=getattr(solver, "_solve_plan", None))
        return True
    if kind == "dense":
        solver._batched = None
        solver._matrices = {name: arrays[f"dense_{name}"] for name in names}
        solver.structure = None
        solver.ops = pencilops.DenseOps(
            solver._dense_matsolver(),
            solve_plan=getattr(solver, "_solve_plan", None))
        return True
    return False

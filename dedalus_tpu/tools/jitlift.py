"""
Device-constant lifting for compiled programs.

This JAX version inlines every non-splat array constant into the lowered
MLIR (verified: a 100 MB transform-matrix stack adds ~400 MB of program
text). Spectral kernels are built from exactly such constants — MMT
matrices, per-m SWSH/Zernike stacks, NCC matrices — so naive jit produces
multi-GB programs that overwhelm the TPU compiler (and remote-compile
transports). The reference never hits this because FFTW plans live outside
the compiler (libraries/fftw/fftw_wrappers.pyx); a TPU-native design needs
the matrices INSIDE the program boundary but OUTSIDE the program text.

`lifted_jit(fn)` compiles fn with every `device_constant(arr)` the trace
touches passed as a runtime ARGUMENT:

  1. discovery: `jax.eval_shape` traces fn abstractly; each
     `device_constant` call resolves to its concrete device array and
     records its registry index;
  2. execution: the recorded constants are bound as leading arguments of a
     wrapped `jax.jit`, inside which `device_constant` resolves to the
     traced argument value.

Producers keep returning plain numpy (host assembly reads them directly);
only device-use funnels (`tools.array.match_precision` and the transform
matmul helpers) route through `device_constant`.
"""

import logging
import threading

import numpy as np
import jax
import jax.numpy as jnp

from . import metrics as metrics_mod
from . import retrace as retrace_mod

__all__ = ["device_constant", "discovering", "lifted_jit", "tracing_active",
           "tracing_state_known"]


def _probe_public():
    """Public trace-state probe (jax.core has exported trace_state_clean
    across recent majors)."""
    from jax.core import trace_state_clean
    trace_state_clean()  # verify callable before committing to it
    return lambda: not trace_state_clean()


def _probe_private():
    """Legacy fallback on jax internals; kept only for JAX builds whose
    public surface predates/renames trace_state_clean."""
    # the one sanctioned private-API fallback, guarded by _resolve below
    from jax._src.core import trace_ctx, EvalTrace  # dedalus-lint: disable=DTL005
    isinstance(trace_ctx.trace, EvalTrace)  # verify the attributes exist
    return lambda: not isinstance(trace_ctx.trace, EvalTrace)


def _resolve_tracing_probe(candidates=(_probe_public, _probe_private)):
    """Resolve a () -> bool tracing probe, trying public JAX surfaces
    before private ones. When every candidate fails (API drift across a
    JAX upgrade), degrade to a constant-False probe with ONE warning
    instead of raising: callers lose the inline-instead-of-cache guard
    (device_value) and the eager GeneralFunction fast path, both safe
    fallbacks, rather than the whole import."""
    for candidate in candidates:
        try:
            return candidate()
        except Exception:
            continue
    logging.getLogger(__name__).warning(
        "jitlift: no usable JAX trace-state API (public and private probes "
        "both failed); assuming never-tracing. device_constant caching and "
        "GeneralFunction dispatch fall back to conservative behavior.")
    return _degraded_probe


def _degraded_probe():
    """Distinguished never-tracing probe: callers that need to know
    whether the answer is trustworthy check tracing_state_known()."""
    return False


_tracing_probe = None


def tracing_active():
    """True when called under a jax trace (jit/vmap/grad/eval_shape).
    Resolved lazily against the running JAX version; see
    _resolve_tracing_probe for the degradation contract."""
    global _tracing_probe
    if _tracing_probe is None:
        _tracing_probe = _resolve_tracing_probe()
    return _tracing_probe()


def tracing_state_known():
    """False when the trace-state probe degraded to constant-False (every
    candidate API failed): tracing_active() is then a guess, and callers
    with a safe conservative branch (e.g. GeneralFunction's io_callback
    path) should take it."""
    global _tracing_probe
    if _tracing_probe is None:
        _tracing_probe = _resolve_tracing_probe()
    return _tracing_probe is not _degraded_probe


# historical internal spelling (device_value below predates the public name)
_tracing_active = tracing_active


class _Registry:
    """
    Constants are interned by CONTENT (shape/dtype/digest), with a
    source-object-identity fast path that skips hashing for producer-cached
    arrays. Producers that rebuild equal arrays per trace therefore still
    dedupe correctly — they just pay a hash per call.
    """

    def __init__(self):
        self.arrays = []            # numpy or device arrays by index
        self.by_id = {}             # (id(src), dtype) -> index
        self.by_content = {}        # (shape, dtype, digest) -> index
        self.keepalive = {}         # id(src) -> src (guards id reuse)

    def intern(self, array, convert, dtype):
        import hashlib
        fast = (id(array), str(np.dtype(dtype)) if dtype is not None else None)
        idx = self.by_id.get(fast)
        if idx is not None:
            return idx
        # stored as NUMPY: device conversion must happen outside any trace
        # (under a trace jnp.asarray yields a tracer, which must never be
        # cached)
        converted = convert()
        digest = hashlib.blake2b(
            np.ascontiguousarray(converted).tobytes(),
            digest_size=16).digest()
        key = (converted.shape, str(converted.dtype), digest)
        idx = self.by_content.get(key)
        if idx is None:
            idx = len(self.arrays)
            self.arrays.append(converted)
            self.by_content[key] = idx
        self.by_id[fast] = idx
        self.keepalive[id(array)] = array
        return idx

    def device_value(self, idx):
        """The constant as a device array; caches the transfer only when
        called outside a trace."""
        val = self.arrays[idx]
        if isinstance(val, np.ndarray):
            # host -> device, once per constant (build phase `upload`)
            with metrics_mod.build_scope("upload"):
                converted = jnp.asarray(val)
            # never cache a tracer: belt (probe) AND suspenders (type
            # check), so a degraded never-tracing probe cannot poison the
            # process-global registry from inside a foreign trace
            if _tracing_active() or isinstance(converted, jax.core.Tracer):
                return converted   # foreign trace: inline, no cache
            val = self.arrays[idx] = converted
        return val


_registry = _Registry()
_local = threading.local()


def device_constant(array, dtype=None):
    """
    Mark a host array (numpy or scipy sparse) as a large device constant
    of compiled programs. Outside lifted tracing this returns the interned
    device array (eager use); during a lifted trace it resolves to the
    constant's traced argument (recording it during discovery).

    Interning is by the SOURCE object's identity: callers must pass cached
    host arrays (fresh per-call arrays defeat the lift and leak registry
    entries — the fallback below warns when that happens).
    """
    def convert():
        a = array.toarray() if hasattr(array, "toarray") else array
        if dtype is not None and np.dtype(dtype) != np.asarray(a).dtype:
            return np.asarray(a, dtype=dtype)
        return np.asarray(a)

    idx = _registry.intern(array, convert, dtype)
    mode = getattr(_local, "mode", None)
    if mode is None:
        return _registry.device_value(idx)
    if mode[0] == "discover":
        mode[1].add(idx)
        return _registry.arrays[idx]
    # substitution: traced argument values by index
    sub = mode[1].get(idx)
    if sub is not None:
        return sub
    # A constant first touched during the jit trace but not discovery:
    # the source object was rebuilt between traces (unstable identity),
    # so the lift silently degrades to inlining — make that visible.
    import logging
    logging.getLogger(__name__).warning(
        f"device_constant: unstable source identity for a "
        f"{np.shape(_registry.arrays[idx])} constant; inlining into the "
        "program (the producer should cache this array).")
    return _registry.arrays[idx]


def discovering():
    """True inside a lifted program's discovery pass: the abstract trace
    that finds its constants, after which the program is traced once more.
    Trace-time tallies skip it so that each traced operation counts once."""
    mode = getattr(_local, "mode", None)
    return mode is not None and mode[0] == "discover"


class _Mode:
    def __init__(self, tag, payload):
        self.state = (tag, payload)

    def __enter__(self):
        self.prev = getattr(_local, "mode", None)
        _local.mode = self.state
        return self.state[1]

    def __exit__(self, *exc):
        _local.mode = self.prev


def _signature(tree):
    leaves, treedef = jax.tree.flatten(tree)
    sig = tuple((np.shape(l), str(getattr(l, "dtype", type(l))))
                for l in leaves)
    return (treedef, sig)


class lifted_jit:
    """jax.jit with device-constant lifting; supports static_argnums and
    donate_argnums (original-fn positions; the fused step programs donate
    their history buffers so XLA rolls them in place — callers own the
    invalidation contract for outstanding references, see
    core/fusedstep.py DONATE_STEP)."""

    def __init__(self, fn, static_argnums=(), donate_argnums=()):
        self.fn = fn
        self.static_argnums = tuple(static_argnums)
        self.donate_argnums = tuple(donate_argnums)
        overlap = set(self.static_argnums) & set(self.donate_argnums)
        if overlap:
            raise ValueError(f"cannot donate static argnums {overlap}")
        self._cache = {}
        # retrace sentinel: the jit bodies below note every trace of THIS
        # wrapper, so post-warmup recompiles surface as structured
        # warnings + the dedalus/retrace metric (tools/retrace.py)
        self._retrace_state = retrace_mod.TraceCount(
            getattr(fn, "__qualname__", None) or repr(fn))

    def _donate_positions(self, n_args):
        """Donated original positions -> wrapped positions (the consts
        list occupies wrapped slot 0; dynamic arg j sits at 1 + j)."""
        dyn_index = {}
        j = 0
        for i in range(n_args):
            if i not in self.static_argnums:
                dyn_index[i] = j
                j += 1
        return tuple(1 + dyn_index[i] for i in self.donate_argnums)

    def __call__(self, *args):
        static = tuple(args[i] for i in self.static_argnums)
        dynamic = [a for i, a in enumerate(args) if i not in self.static_argnums]
        key = (static, _signature(dynamic))
        entry = self._cache.get(key)
        if entry is None:
            return self._first_call(key, static, dynamic, len(args))
        idxs, jfn = entry
        return jfn([_registry.device_value(i) for i in idxs], *dynamic)

    def _first_call(self, key, static, dynamic, n_args):
        """The first call of a signature: discovery pass, the jit wrapper,
        the first launch (trace, lowering, compile or cache load),
        bracketed as ONE row of the set-up ledger (tools/retrace.py). The
        upload of the constants it found is the build phase `upload`
        (`_Registry.device_value`), which pauses the row."""
        row = retrace_mod.sentinel.open_row(
            self._retrace_state, metrics_mod.current_phases())
        try:
            touched = set()
            with _Mode("discover", touched):
                jax.eval_shape(lambda *d: self._call_fn(static, d), *dynamic)
            idxs = tuple(sorted(touched))

            def wrapped(consts, *d):
                # trace-time side effect: runs per (re)trace, not per call
                retrace_mod.sentinel.note(self._retrace_state)
                with _Mode("substitute", dict(zip(idxs, consts))):
                    return self._call_fn(static, d)

            donate = self._donate_positions(n_args) \
                if self.donate_argnums else ()
            # memoized in self._cache on the next line
            jfn = jax.jit(wrapped, donate_argnums=donate)  # dedalus-lint: disable=DTL003
            self._cache[key] = (idxs, jfn)
            row.discovered()
            return jfn([_registry.device_value(i) for i in idxs], *dynamic)
        finally:
            row.close()

    def _call_fn(self, static, dynamic):
        args = list(dynamic)
        for pos, val in sorted(zip(self.static_argnums, static)):
            args.insert(pos, val)
        return self.fn(*args)

    def constants(self):
        """The device constants the programs traced so far take as
        arguments (as interned: host or device arrays)."""
        return [_registry.arrays[i] for idxs, _ in self._cache.values()
                for i in idxs]

    def jaxpr(self, *args):
        """ClosedJaxpr of the lifted program body (device constants
        resolve to their interned device arrays, so they appear as jaxpr
        constants). Inspection surface for the program contract checker
        (tools/lint/progcheck.py): primitive-level contracts — forbidden
        solve/callback primitives, pads inside partial-auto shard_map
        regions — read the program from here."""
        static = tuple(args[i] for i in self.static_argnums)
        dynamic = [a for i, a in enumerate(args)
                   if i not in self.static_argnums]
        return jax.make_jaxpr(lambda *d: self._call_fn(static, d))(*dynamic)

    def lower(self, *args):
        """Lower the lifted program (for inspection/testing). The fresh
        jit carries the wrapper's donate_argnums, so inspection sees the
        SAME input_output_alias contract the executing program compiles
        with — the donation-honored program contract
        (tools/lint/progcheck.py) reads it from exactly this text, and a
        lower() that silently dropped donation would report every
        donating program as a regression (and, worse, hide a real one)."""
        static = tuple(args[i] for i in self.static_argnums)
        dynamic = [a for i, a in enumerate(args)
                   if i not in self.static_argnums]
        touched = set()
        with _Mode("discover", touched):
            jax.eval_shape(lambda *d: self._call_fn(static, d), *dynamic)
        idxs = tuple(sorted(touched))

        def wrapped(consts, *d):
            with _Mode("substitute", dict(zip(idxs, consts))):
                return self._call_fn(static, d)

        donate = self._donate_positions(len(args)) \
            if self.donate_argnums else ()
        # cold inspection path: a fresh jit per lower() is the point here
        return jax.jit(wrapped, donate_argnums=donate).lower(  # dedalus-lint: disable=DTL003
            [_registry.device_value(i) for i in idxs], *dynamic)

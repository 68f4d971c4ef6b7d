"""
Operands and Fields (reference: dedalus/core/field.py).

`Operand` is the arithmetic-overload base: `+ - * / ** @` and calls build
symbolic expression nodes (reference: core/field.py:39-327). `Field` is the
concrete distributed data container: an immutable-by-convention jnp array
plus a current layout tag ('c' coefficient / 'g' grid) and grid scales.

TPU-native design: user-facing Fields behave like the reference's (mutable
layout walked on access), but all data lives on device as jnp arrays; the
solver hot loop never touches Fields — it closes over pure pytrees of
coefficient arrays (see solvers.py).
"""

import functools

import numpy as np
import jax
import jax.numpy as jnp

from .domain import Domain
from ..tools.general import is_complex_dtype


# ------------------------------------------------------------------
# Transform pipeline: pure jnp, safe inside jit.
#
# Mesh-aware mode: inside `mesh_transforms(mesh)` the walk pins the
# intermediate shardings of the reference's layout chain
# (core/distributor.py:128-166: coeff keeps the first R axes distributed,
# transforming axis r first moves its blocks to axis r+1) via
# with_sharding_constraint, so GSPMD lowers the moves to all-to-all pencil
# transposes instead of gathering the full state (the reference's
# Alltoallv transposes, core/transposes.pyx:246). Host/setup paths run
# outside the context and are untouched.

import threading as _threading

from . import meshctx

_MESH_CTX = _threading.local()


class mesh_transforms:
    """Context manager activating sharded transform walks (trace-time).
    `mesh=None` INHERITS any active context instead of clearing it: an
    undistributed solver body traced inside an outer walk context (the
    2-D batch x pencil fleet, core/ensemble.py) keeps the outer mesh.
    `chunks` carries the solver's resolved transpose chunk count
    ([distributed] TRANSPOSE_CHUNKS) into the walk; None resolves from
    config at walk time."""

    def __init__(self, mesh, chunks=None):
        self.mesh = mesh
        self.chunks = chunks

    def __enter__(self):
        self.prev = getattr(_MESH_CTX, "mesh", None)
        self.prev_chunks = getattr(_MESH_CTX, "chunks", None)
        if self.mesh is not None:
            _MESH_CTX.mesh = self.mesh
            _MESH_CTX.chunks = self.chunks
        return getattr(_MESH_CTX, "mesh", None)

    def __exit__(self, *exc):
        _MESH_CTX.mesh = self.prev
        _MESH_CTX.chunks = self.prev_chunks


def _active_mesh(domain):
    """(mesh, axis_names) for the current transform walk, or (None, ()).
    Reserved ensemble batch axes are filtered out (meshctx.walk_axis_names):
    on a 2-D batch x pencil mesh the walk transposes over the pencil axes
    only."""
    mesh = getattr(_MESH_CTX, "mesh", None)
    if mesh is None:
        return None, ()
    names = meshctx.walk_axis_names(mesh)
    R = min(len(names), domain.dim - 1)
    if R < 1:
        return None, ()
    return mesh, names[:R]


def _active_chunks():
    """Transpose chunk count for the current walk: the solver's resolved
    value when its mesh_transforms context carried one, else resolved
    from [distributed] TRANSPOSE_CHUNKS."""
    chunks = getattr(_MESH_CTX, "chunks", None)
    if chunks is not None:
        return chunks
    from ..parallel.transposes import resolve_transpose_chunks
    return resolve_transpose_chunks()


def _constrain(data, mesh, layout):
    from jax.sharding import NamedSharding, PartitionSpec
    spec = [layout.get(d) for d in range(data.ndim)]
    return jax.lax.with_sharding_constraint(
        data, NamedSharding(mesh, PartitionSpec(*spec)))


def _walk_divisible(data, domain, scales, tdim, mesh, names):
    """Whether every stage of the sharded layout walk divides evenly: mesh
    axis r shards the coeff size of axis r and the grid size of axis r+1.
    Uneven stages (reduced tau fields, odd sizes) fall back to the plain
    global-view walk — correct, but GSPMD may gather; choose divisible
    resolutions for the distributed axes."""
    def size(axis, grid):
        basis = domain.bases[axis]
        if basis is None:
            return data.shape[tdim + axis]
        sub = axis - basis.first_axis
        if grid:
            return basis.sub_grid_size(sub, scales[axis])
        return basis.coeff_size(sub)

    for r, name in enumerate(names):
        n = mesh.shape[name]
        if size(r, grid=False) % n or size(r + 1, grid=True) % n:
            return False
    return True


def transform_to_coeff(data, domain, scales, tdim, library=None, tensorsig=()):
    """
    Full grid -> full coefficient transform. First axis first, so curvilinear
    azimuths are in coefficient (m) space before their m-dependent
    colatitude/radial transforms run (reference layout-walk direction:
    core/distributor.py:128-166).
    """
    def fwd(data, axis):
        basis = domain.bases[axis]
        if basis is None:
            return data
        return basis.forward_transform(data, tdim + axis, scales[axis],
                                       library, tensorsig=tensorsig,
                                       sub_axis=axis - basis.first_axis)

    mesh, names = _active_mesh(domain)
    if mesh is not None and not _walk_divisible(data, domain, scales, tdim,
                                                mesh, names):
        mesh = None
    if mesh is None:
        for axis in range(domain.dim):
            data = fwd(data, axis)
        return data
    R = len(names)
    chunks = _active_chunks()
    # grid layout: mesh axis r shards array dim r+1
    layout = {tdim + r + 1: names[r] for r in range(R)}
    prev = meshctx.set_walk(mesh, layout)
    try:
        data = _constrain(data, mesh, layout)
        for r in range(R):
            if data.shape[tdim + r + 1] % mesh.shape[names[r]] == 0:
                # overlapped chunked stage: transform + per-chunk
                # all_to_all interleaved inside one shard_map
                # (parallel/transposes.py; bit-identical to the
                # monolithic constraint-walk below). One chunk too: the
                # exchange is then an explicit all_to_all under
                # `dedalus/transpose/`, where the one GSPMD inserts for the
                # constraint carries its neighbouring transform's op_name
                # and no reader can see it (CPU, PR 40)
                from ..parallel.transposes import overlapped_to_coeff_stage
                del layout[tdim + r + 1]
                data = overlapped_to_coeff_stage(
                    data, lambda x, _r=r: fwd(x, _r),
                    tdim + r + 1, tdim + r, mesh, names[r],
                    layout=layout, chunks=chunks)
                layout[tdim + r] = names[r]
                meshctx.set_walk(mesh, layout)
                data = _constrain(data, mesh, layout)
                continue
            data = fwd(data, r)                 # axis r is local in grid layout
            del layout[tdim + r + 1]
            layout[tdim + r] = names[r]
            meshctx.set_walk(mesh, layout)
            data = _constrain(data, mesh, layout)  # all-to-all: dim r+1 -> dim r
        for axis in range(R, domain.dim):
            data = fwd(data, axis)
        return _constrain(data, mesh, layout)
    finally:
        meshctx.restore_walk(prev)


def transform_to_grid(data, domain, scales, tdim, library=None, tensorsig=()):
    """Full coefficient -> full grid transform: last axis first."""
    def bwd(data, axis):
        basis = domain.bases[axis]
        if basis is None:
            return data
        return basis.backward_transform(data, tdim + axis, scales[axis],
                                        library, tensorsig=tensorsig,
                                        sub_axis=axis - basis.first_axis)

    mesh, names = _active_mesh(domain)
    if mesh is not None and not _walk_divisible(data, domain, scales, tdim,
                                                mesh, names):
        mesh = None
    if mesh is None:
        for axis in range(domain.dim - 1, -1, -1):
            data = bwd(data, axis)
        return data
    R = len(names)
    chunks = _active_chunks()
    # coeff layout: mesh axis r shards array dim r
    layout = {tdim + r: names[r] for r in range(R)}
    prev = meshctx.set_walk(mesh, layout)
    try:
        data = _constrain(data, mesh, layout)
        for axis in range(domain.dim - 1, R - 1, -1):
            data = bwd(data, axis)
        for r in range(R - 1, -1, -1):
            n = mesh.shape[names[r]]
            if data.shape[tdim + r + 1] % n == 0 \
                    and data.shape[tdim + r] % n == 0:
                # overlapped chunked stage (parallel/transposes.py):
                # chunk k+1's all_to_all rides under chunk k's backward
                # transform; bit-identical to the monolithic walk below
                from ..parallel.transposes import overlapped_to_grid_stage
                del layout[tdim + r]
                data = overlapped_to_grid_stage(
                    data, lambda x, _r=r: bwd(x, _r),
                    tdim + r, tdim + r + 1, mesh, names[r],
                    layout=layout, chunks=chunks)
                layout[tdim + r + 1] = names[r]
                meshctx.set_walk(mesh, layout)
                data = _constrain(data, mesh, layout)
                continue
            del layout[tdim + r]
            layout[tdim + r + 1] = names[r]
            meshctx.set_walk(mesh, layout)
            data = _constrain(data, mesh, layout)  # all-to-all: dim r -> dim r+1
            data = bwd(data, r)                 # axis r now local
        return data
    finally:
        meshctx.restore_walk(prev)


def _compiled_transform(direction, domain, scales, tdim, tensorsig):
    """
    Jit-compiled whole-field transform, cached per static signature. All
    host-facing layout changes go through here: eager per-op dispatch is both
    slow and fragile on remote-compile TPU backends (each new op shape is a
    round-trip through the backend compiler). The cache lives on the domain
    object, so its compiled executables share the domain's lifetime instead
    of pinning every domain in a global table.
    """
    per_domain = domain.__dict__.setdefault("_compiled_transforms", {})
    key = (direction, scales, tdim, tensorsig)
    fn = per_domain.get(key)
    if fn is None:
        if direction == "c":
            def fn(data):
                return transform_to_coeff(data, domain, scales, tdim,
                                          tensorsig=tensorsig)
        else:
            def fn(data):
                return transform_to_grid(data, domain, scales, tdim,
                                         tensorsig=tensorsig)
        from ..tools.jitlift import lifted_jit
        fn = per_domain[key] = lifted_jit(fn)
    return fn


class _FieldDataView(np.ndarray):
    """
    Host ndarray tied to a Field layout: item assignment writes the whole
    array back into the field, emulating the reference's live data views
    (reference: core/field.py:561 __getitem__ returning self.data).
    """

    def __new__(cls, arr, field, layout):
        obj = np.asarray(arr).view(cls)
        obj._field = field
        obj._field_layout = layout
        # Shared mutable cell tracking the field data epoch this view
        # mirrors: all slices of this view share it, so sequential writes
        # through any of them stay valid while external data changes (user
        # mutation OR solver updates) invalidate all.
        obj._view_version = [field._data_epoch]
        return obj

    def __array_finalize__(self, obj):
        # Memory-sharing views (slices) keep the backref so
        # `u['g'][2][...] = v` lands in the field; fresh arrays produced by
        # ufuncs drop it so `w = u['g']*2; w[0] = ...` does not.
        self._field = None
        self._field_layout = None
        self._view_version = None
        if obj is not None and getattr(obj, "_field", None) is not None:
            try:
                shared = np.shares_memory(self, obj)
            except Exception:
                shared = False
            if shared:
                self._field = obj._field
                self._field_layout = obj._field_layout
                self._view_version = obj._view_version

    def _writeback(self):
        field, layout = self._field, self._field_layout
        if field is None:
            return
        if field._data_epoch != self._view_version[0]:
            raise RuntimeError(
                "Writing through a stale field data view: the field's data "
                "changed (user assignment or solver step) after this view "
                f"was taken. Re-read the data (field['{layout}']) and apply "
                "the mutation to the fresh view.")
        root = self
        while isinstance(root.base, np.ndarray):
            root = root.base
        field[layout] = np.asarray(root)
        self._view_version[0] = field._data_epoch

    def __setitem__(self, key, value):
        np.ndarray.__setitem__(self, key, value)
        self._writeback()


def _inplace_with_writeback(name):
    base_op = getattr(np.ndarray, name)

    def op(self, other):
        out = base_op(self, other)
        self._writeback()
        return out
    op.__name__ = name
    return op


for _name in ("__iadd__", "__isub__", "__imul__", "__itruediv__",
              "__ifloordiv__", "__imod__", "__ipow__", "__iand__",
              "__ior__", "__ixor__", "__ilshift__", "__irshift__"):
    setattr(_FieldDataView, _name, _inplace_with_writeback(_name))


class Operand:
    """Base class for everything that can appear in symbolic expressions."""

    __array_priority__ = 100.0  # win dispatch against numpy arrays

    # ---- arithmetic overloads (lazy imports avoid circular deps) ----

    def __add__(self, other):
        from .arithmetic import Add
        if np.isscalar(other) and other == 0:
            return self
        return Add(self, other)

    def __radd__(self, other):
        from .arithmetic import Add
        if np.isscalar(other) and other == 0:
            return self
        return Add(other, self)

    def __sub__(self, other):
        return self + (-1) * other

    def __rsub__(self, other):
        return other + (-1) * self

    def __neg__(self):
        return (-1) * self

    def __mul__(self, other):
        from .arithmetic import Multiply
        return Multiply(self, other)

    def __rmul__(self, other):
        from .arithmetic import Multiply
        return Multiply(other, self)

    def __truediv__(self, other):
        from .arithmetic import Multiply, Power
        if np.isscalar(other):
            return Multiply(1.0 / other, self)
        return Multiply(self, Power(other, -1))

    def __rtruediv__(self, other):
        from .arithmetic import Multiply, Power
        return Multiply(other, Power(self, -1))

    def __pow__(self, other):
        from .arithmetic import Power
        return Power(self, other)

    def __matmul__(self, other):
        from .arithmetic import DotProduct
        return DotProduct(self, other)

    def __rmatmul__(self, other):
        from .arithmetic import DotProduct
        return DotProduct(other, self)

    def __call__(self, **positions):
        """Interpolation: f(x=0.5) (reference: core/field.py API)."""
        from .operators import Interpolate
        out = self
        for name, position in positions.items():
            coord = self._lookup_coord(name)
            out = Interpolate(out, coord, position)
        return out

    def _lookup_coord(self, name):
        return self.dist.get_coord(name)

    def __array_ufunc__(self, ufunc, method, *inputs, **kw):
        """Dispatch numpy ufuncs on operands to symbolic nodes
        (reference: core/field.py:44)."""
        from .arithmetic import Add, Multiply, Power, DotProduct
        from .operators import UnaryGridFunction
        if method != "__call__":
            return NotImplemented
        binary = {np.add: Add, np.multiply: Multiply, np.matmul: DotProduct}
        if ufunc in binary and len(inputs) == 2:
            return binary[ufunc](*inputs)
        if ufunc is np.subtract and len(inputs) == 2:
            return inputs[0] - inputs[1]
        if ufunc is np.true_divide and len(inputs) == 2:
            a, b = inputs
            if isinstance(a, Operand):
                return a / b
            return a * Power(b, -1)
        if ufunc is np.power and len(inputs) == 2:
            return Power(*inputs)
        if ufunc is np.negative:
            return -inputs[0]
        if len(inputs) == 1:
            return UnaryGridFunction(ufunc, inputs[0])
        return NotImplemented

    # ---- symbolic tree API (overridden by Future) ----

    def atoms(self, *types):
        return set()

    def has(self, *operands):
        return any(self is op for op in operands)

    def replace(self, old, new):
        return new if self is old else self

    @staticmethod
    def cast(arg, dist):
        if isinstance(arg, Operand):
            return arg
        raise TypeError(f"Cannot cast {arg!r} to an Operand")


_zeros_cache = {}
_zeros_cache_bytes = 0
_zeros_cache_lock = _threading.Lock()
# device memory pinned by interned zeros is bounded in BYTES, not entry
# count: a resolution scan would otherwise accumulate dead large buffers
# (scarce HBM on TPU) for shapes no live field references
_ZEROS_CACHE_MAX_BYTES = 64 * 1024 * 1024


def _shared_zeros(shape, dtype):
    """Interned zero arrays for field initialization: jax arrays are
    immutable, so every field of one (shape, dtype) can alias a single
    zeros buffer — writes replace `field.data` wholesale. Saves one eager
    dispatch per field on cold starts (a dozen fields is ~0.2 s).
    Locked: fields are constructed from worker threads (ASSEMBLY_WORKERS),
    and the pop-reinsert recency refresh races without it."""
    global _zeros_cache_bytes
    key = (tuple(shape), np.dtype(dtype).str)
    with _zeros_cache_lock:
        out = _zeros_cache.get(key)
        if out is not None:
            # refresh recency: move the hit to the back of the eviction
            # order
            _zeros_cache[key] = _zeros_cache.pop(key)
            return out
        nbytes = int(np.prod(shape, dtype=np.int64)) * np.dtype(dtype).itemsize
        if nbytes > _ZEROS_CACHE_MAX_BYTES:
            return jnp.zeros(shape, dtype=dtype)   # too large to pin
        # evict least-recently-used (hits reinsert, so dict order is LRU)
        while _zeros_cache and \
                _zeros_cache_bytes + nbytes > _ZEROS_CACHE_MAX_BYTES:
            old = _zeros_cache.pop(next(iter(_zeros_cache)))
            _zeros_cache_bytes -= old.size * old.dtype.itemsize
        out = _zeros_cache[key] = jnp.zeros(shape, dtype=dtype)
        _zeros_cache_bytes += nbytes
    return out


class Field(Operand):
    """
    Distributed spectral field (reference: core/field.py:32 Field/ScalarField,
    with VectorField/TensorField as tensorsig variants).
    """

    def __init__(self, dist, bases=None, name=None, tensorsig=(), dtype=None):
        self.dist = dist
        self.name = name
        self.tensorsig = tuple(tensorsig)
        self.dtype = np.dtype(dtype or dist.dtype)
        self.domain = Domain(dist, dist.expand_bases(bases))
        if self.domain.coeff_dtype_is_complex and not is_complex_dtype(self.dtype):
            raise ValueError("ComplexFourier bases require a complex dtype.")
        self.scales = dist.remedy_scales(1)
        self.layout = "c"
        self.data = _shared_zeros(self.coeff_shape, self.coeff_dtype)
        # Solver synchronization: `_version` counts user mutations;
        # `_data_epoch` counts ALL data changes (including solver updates,
        # for data-view staleness detection); `_pull`
        # is a deferred fetch installed by solvers after a step so field data
        # is only scattered from the device state when actually accessed.
        self._version = 0
        self._data_epoch = 0
        self._pull = None

    def atoms(self, *types):
        if not types or isinstance(self, types):
            return {self}
        return set()

    # ---- shapes & dtypes ----

    @property
    def tshape(self):
        return tuple(cs.dim for cs in self.tensorsig)

    @property
    def tdim(self):
        return len(self.tshape)

    @property
    def coeff_dtype(self):
        return self.dtype

    @property
    def grid_dtype(self):
        return self.dtype

    @property
    def coeff_shape(self):
        return self.tshape + self.domain.coeff_shape

    def grid_shape(self, scales=None):
        scales = self.dist.remedy_scales(scales if scales is not None else self.scales)
        return self.tshape + self.domain.grid_shape(scales)

    def __repr__(self):
        return f"Field(name={self.name!r}, bases={self.domain.bases})"

    def __str__(self):
        return self.name or f"F{id(self)%10000}"

    # ---- layout management ----

    def _sync(self):
        if self._pull is not None:
            pull, self._pull = self._pull, None
            pull()

    def require_coeff_space(self):
        self._sync()
        if self.layout == "g":
            fn = _compiled_transform("c", self.domain, tuple(self.scales),
                                     self.tdim, self.tensorsig)
            self.data = fn(self.data)
            self.layout = "c"
        return self.data

    def require_grid_space(self, scales=None):
        self._sync()
        if scales is not None:
            self.change_scales(scales)
        if self.layout == "c":
            fn = _compiled_transform("g", self.domain, tuple(self.scales),
                                     self.tdim, self.tensorsig)
            self.data = fn(self.data)
            self.layout = "g"
        return self.data

    def change_scales(self, scales):
        scales = self.dist.remedy_scales(scales)
        if scales != self.scales:
            self.require_coeff_space()
            self.scales = scales

    def change_layout(self, layout):
        if layout in ("c", 0, "coeff"):
            self.require_coeff_space()
        else:
            self.require_grid_space()

    def __getitem__(self, layout):
        # Return a host view that writes back on item assignment, so the
        # reference idiom `u['g'][2] = ...` works (reference fields expose
        # their live buffers; here device arrays are immutable, so the view
        # pushes mutations back through __setitem__).
        if layout in ("c", 0, "coeff"):
            return _FieldDataView(np.array(self.require_coeff_space()),
                                  self, "c")
        elif layout in ("g", 1, "grid"):
            return _FieldDataView(np.array(self.require_grid_space()),
                                  self, "g")
        raise KeyError(f"Unknown layout: {layout}")

    def __setitem__(self, layout, value):
        if layout in ("c", 0, "coeff"):
            new_layout = "c"
            shape, dtype = self.coeff_shape, self.coeff_dtype
        elif layout in ("g", 1, "grid"):
            new_layout = "g"
            shape, dtype = self.grid_shape(), self.grid_dtype
        else:
            raise KeyError(f"Unknown layout: {layout}")
        data = jnp.broadcast_to(jnp.asarray(value, dtype=dtype), shape)
        # Only after validation: discard pending solver data, count mutation.
        self._pull = None
        self._version += 1
        self._data_epoch += 1
        self.layout = new_layout
        self.data = data

    # Solver-facing accessors -------------------------------------------------

    def coeff_data(self):
        """Device coefficient array (triggers transform if needed)."""
        return self.require_coeff_space()

    def preset_coeff(self, array):
        """Install device coefficient data directly (solver scatter).
        Does not count as a user mutation (no version bump, but existing
        data views become stale); the grid-scale selection is preserved
        (coefficient data is scale-independent)."""
        self.data = array
        self.layout = "c"
        self._data_epoch += 1

    def mark_modified(self):
        self._version += 1

    def install_pull(self, pull):
        """Install a lazy solver-data pull; any outstanding data views
        become stale immediately (the field's data is now solver-owned)."""
        self._pull = pull
        self._data_epoch += 1

    # ---- utilities ----

    def copy(self):
        self._sync()
        out = Field(self.dist, bases=self.domain.bases, name=self.name,
                    tensorsig=self.tensorsig, dtype=self.dtype)
        out.data = self.data
        out.layout = self.layout
        out.scales = self.scales
        return out

    def evaluate(self):
        return self

    def fill_random(self, layout="g", seed=None, distribution="normal", **kw):
        """
        Deterministic random fill (reference: core/field.py:847 fill_random).
        Uses a global-shape numpy RNG so results are independent of sharding
        (reference's ChunkedRandomArray guarantees the same property).
        """
        rng = np.random.default_rng(seed)
        if layout in ("g", 1, "grid"):
            shape, dtype = self.grid_shape(), self.grid_dtype
        else:
            shape, dtype = self.coeff_shape, self.coeff_dtype
        scale = kw.pop("scale", 1)
        if distribution in ("normal", "standard_normal"):
            data = rng.standard_normal(shape)
            if is_complex_dtype(dtype):
                data = data + 1j * rng.standard_normal(shape)
        elif distribution == "uniform":
            data = rng.uniform(size=shape, **{k: kw[k] for k in ("low", "high") if k in kw})
        else:
            data = getattr(rng, distribution)(size=shape)
        self[layout] = scale * data.astype(dtype)

    def low_pass_filter(self, shape=None, scales=None):
        """Zero coefficients above a per-axis mode cutoff
        (reference: core/field.py API). `scales` gives cutoffs as fractions
        of each axis size; `shape` gives them as mode counts."""
        from .basis import RealFourier, ComplexFourier
        if shape is None and scales is None:
            return self
        coeff_shape = self.domain.coeff_shape
        if shape is None:
            scales = self.dist.remedy_scales(scales)
            shape = [1 if b is None else int(s * n)
                     for b, s, n in zip(self.domain.bases, scales, coeff_shape)]
        data = np.asarray(self.require_coeff_space())
        mask = np.ones_like(data, dtype=bool)
        for axis, (basis, cutoff) in enumerate(zip(self.domain.bases, shape)):
            if basis is None:
                continue
            n = coeff_shape[axis]
            if isinstance(basis, RealFourier):
                # interleaved (cos, -sin) pairs: cutoff counts coefficients
                keep = np.arange(n) < cutoff
            elif isinstance(basis, ComplexFourier):
                # FFT ordering: keep |k| < cutoff/2 on both branches
                k = np.abs(np.fft.fftfreq(n, d=1.0 / n))
                keep = k < cutoff / 2
            else:
                keep = np.arange(n) < cutoff
            view = [np.newaxis] * data.ndim
            view[self.tdim + axis] = slice(None)
            mask = mask & keep[tuple(view)]
        self.data = jnp.asarray(data * mask)
        self._version += 1
        return self

    def allreduce_data_norm(self, layout="c", order=2):
        data = np.asarray(self[layout])
        if order == np.inf:
            return np.max(np.abs(data))
        return np.linalg.norm(data.ravel(), ord=order)

    def allgather_data(self, layout="g"):
        return np.asarray(self[layout])

    # Problem-layer helpers ---------------------------------------------------

    def frechet_differential(self, variables, perturbations):
        """
        Symbolic Frechet differential of this field viewed as an expression
        (trivial for a bare field; see Future.frechet_differential).
        """
        for var, pert in zip(variables, perturbations):
            if self is var:
                return pert
        return 0


def ScalarField(dist, *args, **kw):
    return dist.Field(*args, **kw)


def VectorField(dist, coordsys, *args, **kw):
    return dist.VectorField(coordsys, *args, **kw)


def TensorField(dist, coordsys, *args, **kw):
    return dist.TensorField(coordsys, *args, **kw)


class LockedField(Field):
    """Field with locked layout (reference: core/field.py:952)."""

    def lock_to_layouts(self, *layouts):
        self._locked = tuple(layouts)

    def lock_scales(self):
        pass

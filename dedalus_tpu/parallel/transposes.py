"""
Pencil redistribution via lax.all_to_all inside shard_map
(reference: dedalus/core/transposes.pyx:22 FFTWTranspose / :246
AlltoallvTranspose — the hand-written MPI pack/unpack loops become one XLA
collective; the pack/unpack reshapes fuse into neighboring ops).

A D-dimensional state on an R-dimensional device mesh keeps the first R
axes block-distributed in coefficient space. Transforming an axis requires
it to be device-local, so the layout walk alternates local transforms with
these all-to-all transposes — exactly the reference's Transform/Transpose
ladder (core/distributor.py:128-166), but compiled.

Overlapped chunking ([distributed] TRANSPOSE_CHUNKS): a monolithic
all_to_all leaves the device idle through the whole exchange before the
next axis's transform starts. Each transpose+transform stage is therefore
CHUNKED — the per-device destination block is split into
TRANSPOSE_CHUNKS sub-blocks, each issued as its own lax.all_to_all with
the already-arrived chunk's local transform running between issues, so
communication for chunk k+1 rides under compute for chunk k (the
AccFFT/DaggerFFT overlap structure; XLA's async collective scheduling
does the interleave on TPU ICI, and the dataflow graph carries no false
dependencies between chunks on any backend). The whole stage runs inside
ONE shard_map (explicit per-stage manual sharding, so GSPMD can never
degrade a stage to a gather), and the chunk extraction is STRIDED so
every chunk's all_to_all lands in canonical block order — reassembly is
a local reshape and the chunked stage is bit-identical data movement.
The interleaved transforms are the fft fast paths, which are
batch-slab-invariant bitwise; chunked walks therefore reproduce the
monolithic walk bit-for-bit (asserted in tests/test_distributed.py).
"""

from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from ..tools.compat import shard_map
from ..tools.config import cfg_get
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["all_to_all_transpose", "DistributedPencilPipeline",
           "resolve_transpose_chunks", "stage_chunks",
           "overlapped_to_grid_stage", "overlapped_to_coeff_stage"]

# 'auto' chunk counts, by backend class. Accelerators (async collectives
# on the ICI that genuinely run under compute): 4 sub-blocks, so the
# first chunk's transform starts after ~1/4 of the exchange while
# per-chunk collective latency stays amortized. CPU (collectives are
# thread-pool memcpys with nothing to hide under): 2 — the chunked walk
# must stay within the >=0.95x non-regression bar, and measured CPU cost
# is ~0.7% at 2 chunks vs ~4% at 4 (benchmarks/scaling.py rows). Every
# stage additionally clamps to a divisor of its per-device destination
# block (stage_chunks), so small problems degrade gracefully toward the
# monolithic walk.
AUTO_CHUNKS_ACCELERATOR = 4
AUTO_CHUNKS_CPU = 2
_ACCELERATOR_BACKENDS = ("tpu", "gpu", "cuda", "rocm")


def resolve_transpose_chunks(value=None):
    """
    Resolve the transpose chunk count ONCE (per solver build / pipeline
    construction): `[distributed] TRANSPOSE_CHUNKS` = 'auto' (backend
    heuristic documented at AUTO_CHUNKS_*) or a positive integer. The
    resolved value rides the assembly-cache solver key and the serving
    pool key (tools/assembly_cache.py) — pooled compiled programs depend
    on the chunk structure, so two chunk configs must never alias one
    entry. Raises ValueError on anything else.
    """
    if value is None:
        value = cfg_get("distributed", "TRANSPOSE_CHUNKS", "auto")
    if isinstance(value, str):
        text = value.strip().lower()
        if text == "auto":
            backend = jax.default_backend()
            return (AUTO_CHUNKS_ACCELERATOR
                    if backend in _ACCELERATOR_BACKENDS
                    else AUTO_CHUNKS_CPU)
        try:
            value = int(text)
        except ValueError:
            raise ValueError(
                f"[distributed] TRANSPOSE_CHUNKS must be 'auto' or a "
                f"positive integer, got {value!r}") from None
    if not isinstance(value, (int, np.integer)) or isinstance(value, bool):
        raise ValueError(
            f"[distributed] TRANSPOSE_CHUNKS must be 'auto' or a "
            f"positive integer, got {value!r}")
    if value < 1:
        raise ValueError(
            f"[distributed] TRANSPOSE_CHUNKS must be >= 1, got {value}")
    return int(value)


def stage_chunks(requested, block):
    """Largest chunk count <= `requested` dividing the per-device
    destination block `block` (>=1 always divides, so every stage has a
    legal chunking and small blocks fall back toward monolithic)."""
    block = int(block)
    c = max(1, min(int(requested), block))
    while block % c:
        c -= 1
    return c


def _validate_divisible(data, axis_in, axis_out, n, axis_name):
    """Both moving axes must divide the mesh axis: the sharded `axis_in`
    splits into n local blocks, and the tiled all_to_all splits `axis_out`
    n ways. A non-divisible axis_in used to sail through and produce a
    wrong-shaped tiled exchange; now each failure names its axis."""
    for which, axis in (("axis_in", axis_in), ("axis_out", axis_out)):
        if data.shape[axis] % n:
            raise ValueError(
                f"{which} {axis} (size {data.shape[axis]}) must be "
                f"divisible by mesh axis {axis_name!r} (size {n}); a "
                f"non-divisible {which} would mis-shape the tiled "
                f"all_to_all blocks.")


def all_to_all_transpose(data, axis_in, axis_out, mesh, axis_name,
                         layout=None):
    """
    Redistribute `data` from block-sharded along `axis_in` to block-sharded
    along `axis_out` (both global axis indices), preserving the global
    array. `layout` maps OTHER array dims to mesh axis names that stay
    sharded throughout (the multi-axis-mesh case: only `axis_name` moves —
    including the ensemble `batch` axis of the 2-D batch x pencil
    composition, which rides in `layout` untouched).

    Equivalent to the reference's pencil transpose
    (core/transposes.pyx:336-355 Alltoallv + split/combine loops over one
    mesh-axis subcommunicator, core/distributor.py:702-713).
    """
    layout = dict(layout or {})
    n = mesh.shape[axis_name]
    _validate_divisible(data, axis_in, axis_out, n, axis_name)
    in_spec = [layout.get(d) for d in range(data.ndim)]
    out_spec = list(in_spec)
    in_spec[axis_in] = axis_name
    out_spec[axis_out] = axis_name

    @partial(shard_map, mesh=mesh, in_specs=P(*in_spec), out_specs=P(*out_spec))
    def _transpose(block):
        return lax.all_to_all(block, axis_name, split_axis=axis_out,
                              concat_axis=axis_in, tiled=True)

    # phase label shared with the metrics timers (dedalus/transpose/...,
    # see tools/metrics.py) so profiler traces attribute the collective
    with jax.named_scope("dedalus/transpose/all_to_all"):
        return _transpose(data)


def _suspend_walk():
    """Deactivate the meshctx transform-walk inside a stage body: stage
    data is already device-local, so the per-chunk transforms must not
    re-route their ffts through a nested shard_map of their own."""
    from ..core import meshctx
    return meshctx


def _take_strided_chunk(block, axis, n, C, k):
    """Chunk k of the destination-block-strided split of `axis` (local
    view, full size n*B): rows {d*B + k*B/C + t} for every destination
    device d — so the chunk's all_to_all lands exactly in canonical block
    order and the final reassembly is a LOCAL concatenation."""
    shp = block.shape
    B = shp[axis] // n
    resh = block.reshape(shp[:axis] + (n, C, B // C) + shp[axis + 1:])
    piece = lax.index_in_dim(resh, k, axis=axis + 1, keepdims=False)
    return piece.reshape(shp[:axis] + (n * (B // C),) + shp[axis + 1:])


def overlapped_to_grid_stage(data, transform, axis_in, axis_out, mesh,
                             axis_name, layout=None, chunks=1):
    """
    One to_grid walk stage: all_to_all transpose (axis_in -> axis_out)
    followed by the local backward `transform` along axis_in, chunked so
    chunk k+1's collective is issued before chunk k's transform runs
    (double-buffered: exactly one arrived chunk is in flight through the
    transform while the next exchange proceeds). The chunk axis is the
    per-device DESTINATION block of axis_out; chunks are strided by
    destination device so the exchange is canonical-block-ordered data
    movement and the chunked stage output is bit-identical to the
    monolithic stage. Runs inside one shard_map: every chunk's sharding
    is explicit, so GSPMD cannot degrade any part of the stage to a
    gather.
    """
    layout = dict(layout or {})
    n = mesh.shape[axis_name]
    _validate_divisible(data, axis_in, axis_out, n, axis_name)
    C = stage_chunks(chunks, data.shape[axis_out] // n)
    in_spec = [layout.get(d) for d in range(data.ndim)]
    out_spec = list(in_spec)
    in_spec[axis_in] = axis_name
    out_spec[axis_out] = axis_name
    meshctx = _suspend_walk()

    def a2a(piece):
        return lax.all_to_all(piece, axis_name, split_axis=axis_out,
                              concat_axis=axis_in, tiled=True)

    @partial(shard_map, mesh=mesh, in_specs=P(*in_spec),
             out_specs=P(*out_spec))
    def _stage(block):
        prev = meshctx.set_walk(None, {})
        try:
            if C == 1:
                with jax.named_scope("dedalus/transpose/all_to_all"):
                    moved = a2a(block)
                return transform(moved)
            outs = []
            with jax.named_scope("dedalus/transpose/all_to_all"):
                arrived = a2a(_take_strided_chunk(block, axis_out, n, C, 0))
            for k in range(1, C):
                # comm for chunk k rides under compute for chunk k-1
                with jax.named_scope("dedalus/transpose/all_to_all"):
                    in_flight = a2a(
                        _take_strided_chunk(block, axis_out, n, C, k))
                outs.append(transform(arrived))
                arrived = in_flight
            outs.append(transform(arrived))
            return jnp.concatenate(outs, axis=axis_out)
        finally:
            meshctx.restore_walk(prev)

    with jax.named_scope("dedalus/transpose/overlapped_stage"):
        return _stage(data)


def overlapped_to_coeff_stage(data, transform, axis_in, axis_out, mesh,
                              axis_name, layout=None, chunks=1):
    """
    One to_coeff walk stage: local forward `transform` along axis_out
    followed by the all_to_all transpose (axis_in -> axis_out), chunked
    along the SOURCE per-device block of axis_in so each chunk's
    collective is issued while the NEXT chunk is still transforming.
    Received chunks arrive source-device-major; the final local reshape
    restores canonical global order, so the chunked stage is bit-identical
    data movement around batch-slab-invariant transforms. One shard_map,
    explicit sharding throughout.
    """
    layout = dict(layout or {})
    n = mesh.shape[axis_name]
    if data.shape[axis_in] % n:
        raise ValueError(
            f"axis_in {axis_in} (size {data.shape[axis_in]}) must be "
            f"divisible by mesh axis {axis_name!r} (size {n}); a "
            f"non-divisible axis_in would mis-shape the tiled "
            f"all_to_all blocks.")
    B = data.shape[axis_in] // n
    C = stage_chunks(chunks, B)
    in_spec = [layout.get(d) for d in range(data.ndim)]
    out_spec = list(in_spec)
    in_spec[axis_in] = axis_name
    out_spec[axis_out] = axis_name
    meshctx = _suspend_walk()

    def a2a(piece):
        # the transform ran first, so axis_out now carries the coeff
        # size: validate it divides before the exchange mis-shapes
        if piece.shape[axis_out] % n:
            raise ValueError(
                f"axis_out {axis_out} (transformed size "
                f"{piece.shape[axis_out]}) must be divisible by mesh "
                f"axis {axis_name!r} (size {n}); a non-divisible "
                f"axis_out would mis-shape the tiled all_to_all blocks.")
        return lax.all_to_all(piece, axis_name, split_axis=axis_out,
                              concat_axis=axis_in, tiled=True)

    @partial(shard_map, mesh=mesh, in_specs=P(*in_spec),
             out_specs=P(*out_spec))
    def _stage(block):
        prev = meshctx.set_walk(None, {})
        try:
            if C == 1:
                moved = transform(block)
                with jax.named_scope("dedalus/transpose/all_to_all"):
                    return a2a(moved)
            sub = B // C
            pieces = [lax.slice_in_dim(block, k * sub, (k + 1) * sub,
                                       axis=axis_in)
                      for k in range(C)]
            outs = []
            pending = transform(pieces[0])
            for k in range(1, C):
                # comm for chunk k-1 rides under compute for chunk k
                with jax.named_scope("dedalus/transpose/all_to_all"):
                    outs.append(a2a(pending))
                pending = transform(pieces[k])
            with jax.named_scope("dedalus/transpose/all_to_all"):
                outs.append(a2a(pending))
            # reassemble canonical order along axis_in: each chunk came
            # back source-device-major (n, sub); interleave chunks back
            # into each source block with one local reshape
            shp = outs[0].shape
            resh = [o.reshape(shp[:axis_in] + (n, sub) + shp[axis_in + 1:])
                    for o in outs]
            stacked = jnp.stack(resh, axis=axis_in + 1)   # (n, C, sub)
            return stacked.reshape(shp[:axis_in] + (n * C * sub,)
                                   + shp[axis_in + 1:])
        finally:
            meshctx.restore_walk(prev)

    with jax.named_scope("dedalus/transpose/overlapped_stage"):
        return _stage(data)


class DistributedPencilPipeline:
    """
    Distributed full-coefficient <-> full-grid transform pipeline for a
    D-dimensional domain over an R-dimensional device mesh (R < D): mesh
    axis r shards array dim r in coefficient space and array dim r+1 in
    grid space (the reference's block "pencil" decomposition,
    core/distributor.py:59-74).

    to_grid walk (mirroring the reference layout chain, :128-166):
      for axis = D-1 .. R:  local backward transform      [Transform]
      for r   = R-1 .. 0:   chunked all_to_all mesh axis r: dim r -> r+1
                            interleaved with the local backward transform
                            of dim r                [Transpose||Transform]
    to_coeff reverses the walk. Each transpose+transform stage is an
    overlapped chunked stage (see module docstring): `chunks` sub-block
    exchanges per stage, each riding under the neighboring chunk's
    transform, inside one shard_map per stage. `chunks=None` resolves
    `[distributed] TRANSPOSE_CHUNKS` once at construction; `chunks=1`
    reproduces the monolithic walk (and the chunked walk reproduces it
    bit-for-bit). Tensor components (leading dims) are never distributed.
    """

    def __init__(self, domain, mesh, axis_names=None, chunks=None):
        self.domain = domain
        self.mesh = mesh
        if isinstance(axis_names, str):
            axis_names = (axis_names,)
        self.axis_names = tuple(axis_names or mesh.axis_names)
        self.R = len(self.axis_names)
        self.D = domain.dim
        self.chunks = resolve_transpose_chunks(chunks)
        if self.R >= self.D:
            raise ValueError(f"Mesh rank {self.R} must be below the domain "
                             f"dimension {self.D}.")
        for axis in range(self.D):
            if domain.bases[axis] is None:
                raise ValueError("Pipeline requires a basis on every axis.")

    def _transform(self, data, axis, scales, tensorsig, forward):
        basis = self.domain.bases[axis]
        fn = basis.forward_transform if forward else basis.backward_transform
        return fn(data, len(tensorsig) + axis, scales[axis],
                  tensorsig=tensorsig, sub_axis=axis - basis.first_axis)

    def _constrain(self, data, layout):
        """Pin the stage sharding: fft ops are unpartitionable, so without
        explicit constraints GSPMD gathers at the first local transform
        after a transpose and the walk degrades to replicated."""
        spec = [layout.get(d) for d in range(data.ndim)]
        return jax.lax.with_sharding_constraint(
            data, NamedSharding(self.mesh, P(*spec)))

    def coeff_layout(self, tdim=0):
        """{array dim: mesh axis} for full-coefficient arrays."""
        return {tdim + r: self.axis_names[r] for r in range(self.R)}

    def grid_layout(self, tdim=0):
        """{array dim: mesh axis} for full-grid arrays."""
        return {tdim + r + 1: self.axis_names[r] for r in range(self.R)}

    def to_grid(self, cdata, scales=None, tensorsig=()):
        """Full coefficient -> full grid, sharded end-to-end. The current
        {dim: mesh axis} layout is published to core/meshctx so every
        local transform of the non-transposing phase routes its fft
        through shard_map (XLA cannot partition fft ops); each
        transpose+transform stage runs as one overlapped chunked
        shard_map with its sharding pinned on entry and exit."""
        from ..core import meshctx
        scales = scales or (1.0,) * self.D
        D, R = self.D, self.R
        tdim = len(tensorsig)
        layout = self.coeff_layout(tdim)
        prev = meshctx.set_walk(self.mesh, layout)
        try:
            out = self._constrain(cdata, layout)
            for axis in range(D - 1, R - 1, -1):
                out = self._transform(out, axis, scales, tensorsig,
                                      forward=False)
            for r in range(R - 1, -1, -1):
                del layout[tdim + r]
                out = overlapped_to_grid_stage(
                    out,
                    lambda x, _r=r: self._transform(x, _r, scales,
                                                    tensorsig,
                                                    forward=False),
                    tdim + r, tdim + r + 1, self.mesh, self.axis_names[r],
                    layout=layout, chunks=self.chunks)
                layout[tdim + r + 1] = self.axis_names[r]
                meshctx.set_walk(self.mesh, layout)
                out = self._constrain(out, layout)
            return out
        finally:
            meshctx.restore_walk(prev)

    def to_coeff(self, gdata, scales=None, tensorsig=()):
        """Full grid -> full coefficient, sharded end-to-end (see to_grid
        for the meshctx walk publication + per-stage pinning)."""
        from ..core import meshctx
        scales = scales or (1.0,) * self.D
        D, R = self.D, self.R
        tdim = len(tensorsig)
        layout = self.grid_layout(tdim)
        prev = meshctx.set_walk(self.mesh, layout)
        try:
            out = self._constrain(gdata, layout)
            for r in range(R):
                del layout[tdim + r + 1]
                out = overlapped_to_coeff_stage(
                    out,
                    lambda x, _r=r: self._transform(x, _r, scales,
                                                    tensorsig,
                                                    forward=True),
                    tdim + r + 1, tdim + r, self.mesh, self.axis_names[r],
                    layout=layout, chunks=self.chunks)
                layout[tdim + r] = self.axis_names[r]
                meshctx.set_walk(self.mesh, layout)
                out = self._constrain(out, layout)
            for axis in range(R, D):
                out = self._transform(out, axis, scales, tensorsig,
                                      forward=True)
            return self._constrain(out, layout)
        finally:
            meshctx.restore_walk(prev)

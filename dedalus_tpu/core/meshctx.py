"""
Mesh context for sharded transform walks.

XLA's SPMD partitioner cannot partition `fft` ops: a batched FFT whose
batch dims are sharded is lowered as all-gather + replicated full-size FFT
(observed on the compiled sharded step), which destroys both memory and
scaling at large sizes. The transform walk therefore publishes the current
{array dim: mesh axis} layout here, and the Fourier/DCT plans route their
FFT calls through `local_fft`, which runs the op inside shard_map so each
device transforms only its own batch block — the compiled program then
contains only the walk's intended all-to-all pencil transposes
(reference counterpart: FFTW transforms are always rank-local,
dedalus/core/transposes.pyx moves data so that stays true).
"""

import threading
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec

from ..libraries.matsolvers import in_member_fleet
from ..tools.compat import shard_map

_CTX = threading.local()

# Mesh axis names reserved for ENSEMBLE member batching (core/ensemble.py):
# a transform walk never transposes over them — on a 2-D batch x pencil
# mesh the walk distributes the pencil axes only, while the member axis
# stays manual (shard_map) around the whole fleet program.
BATCH_AXIS_NAMES = frozenset({"batch"})


def walk_axis_names(mesh):
    """Mesh axes that participate in transform-walk distribution: every
    axis except the reserved ensemble batch axes. The 2-D batch x pencil
    composition publishes the SAME mesh for walks and fleet sharding;
    this filter is what keeps the walk's transposes on the pencil axes
    while members ride the batch axis untouched."""
    return tuple(n for n in mesh.axis_names if n not in BATCH_AXIS_NAMES)


def surviving_devices(mesh, lost_indices):
    """Devices of a 1-D `mesh` left after losing `lost_indices` (local
    device indices; out-of-range entries ignored), in their original
    order. The single filter rule behind device-loss recovery — the mesh
    built from it (surviving_mesh) and the member re-padding derived
    from its length (core/ensemble.py) must never disagree."""
    if len(mesh.axis_names) != 1:
        raise ValueError("surviving_devices supports 1-D meshes only")
    devices = list(mesh.devices.flat)
    lost = {i for i in lost_indices if 0 <= i < len(devices)}
    return [dev for i, dev in enumerate(devices) if i not in lost]


def surviving_mesh(mesh, lost_indices):
    """
    The 1-D mesh left after losing `lost_indices` of a 1-D `mesh`: same
    axis name, surviving devices in their original order. Returns None
    when a single device survives — a single-device layout needs no
    mesh — and raises when nothing survives. The device-loss recovery
    path (core/ensemble.py) reshards onto this.
    """
    survivors = surviving_devices(mesh, lost_indices)
    if not survivors:
        raise RuntimeError("no surviving devices to build a mesh from")
    if len(survivors) < 2:
        return None
    return Mesh(np.array(survivors), mesh.axis_names)


def set_walk(mesh, layout):
    """Activate (mesh, {absolute data dim: mesh axis name}) for subsequent
    transform calls; returns the previous state for restoration."""
    prev = getattr(_CTX, "state", None)
    _CTX.state = (mesh, dict(layout)) if mesh is not None else None
    return prev


def restore_walk(prev):
    _CTX.state = prev


def active():
    return getattr(_CTX, "state", None)


def gathered_apply(fn, data, mesh, axis_name, dim=0):
    """
    Apply `fn` (a local whole-array function) to `data` whose `dim` is
    block-sharded over `axis_name`: all_gather the axis inside shard_map,
    apply `fn` to the replicated copy, and slice this device's block back
    out. The escape hatch for arrays too low-dimensional to layout-walk —
    a 1-D tau field's transform roundtrip under the 2-D batch x pencil
    fleet (core/ensemble.py): its only axis is the sharded one, so there
    is no free axis to keep local, and an unrouted fft on a
    manual-subgroup-sharded array hard-crashes the SPMD partitioner.
    `fn` must preserve the size of `dim`. Falls back to a direct call
    when the dim does not divide the mesh axis.
    """
    n = mesh.shape[axis_name]
    if data.shape[dim] % n:
        return fn(data)
    spec = PartitionSpec(*[axis_name if d == dim else None
                           for d in range(data.ndim)])

    def local(block):
        full = jax.lax.all_gather(block, axis_name, axis=dim, tiled=True)
        out = fn(full)
        idx = jax.lax.axis_index(axis_name)
        blk = out.shape[dim] // n
        return jax.lax.dynamic_slice_in_dim(out, idx * blk, blk, axis=dim)

    return shard_map(local, mesh=mesh, in_specs=spec, out_specs=spec)(data)


# XLA:TPU's float32 FFTs are wrong past some number of rows: irfft of
# length 384 over (1024, 96) rows (a 1,024-member fleet's RB 256x64 grid)
# returns 0.32 of the result's largest entry as error, over (512, 96) rows
# 2e-7, as every rfft / irfft / fft / ifft of length 96 to 384 does up to
# 81,920 rows of a 2-D operand (v5e, the JAX of this package's
# installation). A fleet's FFTs carry the member axis on top of a
# member's own rows, so inside a fleet program they go in member chunks
# of at most this many rows. Only the fleets that keep the FFT reach this:
# under `matsolvers.fleet_contraction`'s member count, or a basis with no
# 'matrix' plan (from that count a fleet's transforms are matrix
# products, core/transforms.py).
FLEET_FFT_ROWS = 65536


def _member_chunked(fn):
    """`fn` whose batching rule applies it to the member axis in chunks of
    at most `FLEET_FFT_ROWS` rows (an FFT along the last axis does not mix
    rows: any split of them is the same transform)."""

    @jax.custom_batching.custom_vmap
    def chunked(a):
        return fn(a)

    @chunked.def_vmap
    def _vmapped(members, in_batched, a):
        (batched,) = in_batched
        if not batched:
            return fn(a), False
        rows = members * int(np.prod(a.shape[1:-1], dtype=np.int64))
        chunks = -(-rows // FLEET_FFT_ROWS)
        if chunks <= 1:
            return fn(a), True
        # the fewest chunks that divide the members evenly, else the
        # fewest that hold them (a shorter last chunk)
        chunks = next((c for c in range(chunks, members + 1)
                       if members % c == 0 and c <= 4 * chunks), chunks)
        size = -(-members // chunks)
        # a static unroll: the chunks are few, and a `lax.map` here would
        # sit in the manual regions of a sharded walk (lint DTL009)
        return jnp.concatenate([fn(a[i:i + size])
                                for i in range(0, members, size)]), True

    return chunked


def local_fft(fn, data, orig_axis):
    """
    Apply `fn` (an FFT-like op along the LAST axis of `data`, where `data`
    is the walk-level array with `orig_axis` moved to the end) per-device:
    inside shard_map each device runs the FFT on its local batch block.
    Falls back to the global-view call (which GSPMD will gather) when no
    walk is active, nothing is sharded, or a sharded dim does not divide
    the mesh axis. Inside a fleet's program (`matsolvers.member_fleet`)
    the member axis goes in chunks (`FLEET_FFT_ROWS`).
    """
    if in_member_fleet():
        fn = _member_chunked(fn)
    state = active()
    if state is None or orig_axis is None:
        return fn(data)
    mesh, layout = state
    # moveaxis(orig_axis -> -1): dims before orig_axis keep their index,
    # dims after shift down one, the transformed axis lands last
    moved = {}
    for dim, name in layout.items():
        if name is None:
            continue
        if dim == orig_axis:
            # the walk must have localized the transform axis already
            return fn(data)
        moved[dim if dim < orig_axis else dim - 1] = name
    if not moved:
        return fn(data)
    for dim, name in moved.items():
        if data.shape[dim] % mesh.shape[name]:
            return fn(data)  # uneven block: let GSPMD handle it
    spec = PartitionSpec(*[moved.get(d) for d in range(data.ndim)])

    def local(block):
        # collapse batch dims to 2D around the FFT: XLA:CPU's fft thunk
        # requires a dim0-major operand layout, which fusion inside the
        # shard_map body does not always produce for high-rank operands;
        # the reshape forces a standard-layout copy when needed
        shp = block.shape
        flat = block.reshape((-1, shp[-1]))
        out = fn(flat)
        return out.reshape(shp[:-1] + out.shape[-1:])

    return partial(shard_map, mesh=mesh, in_specs=spec,
                   out_specs=spec)(local)(data)

"""
Solver distribution over a device mesh
(reference: dedalus/core/distributor.py:35 Distributor process-mesh setup;
the per-rank pencil ownership becomes a NamedSharding of the batched pencil
arrays, and GSPMD inserts the reference's transpose/gather collectives
inside the jitted step).
"""

import weakref

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P


def pencil_sharding(mesh, ndim=1, axis_name=None):
    """NamedSharding placing the leading (pencil-group) axis on the mesh."""
    axis_name = axis_name or mesh.axis_names[0]
    spec = [axis_name] + [None] * (ndim - 1)
    return NamedSharding(mesh, P(*spec))


def group_sharded(ops, tree, mesh, axis_name):
    """`tree` (band stores, a factorization's aux) placed with every leaf
    split on the axis `ops.group_axes` says it keeps the groups on (-1:
    replicated)."""
    def place(a, axis):
        spec = [None] * a.ndim
        if axis >= 0:
            spec[axis] = axis_name
        return jax.device_put(a, NamedSharding(mesh, P(*spec)))
    return jax.tree.map(place, tree, ops.group_axes(tree))


def distribute_solver(solver, mesh=None, axis_name=None):
    """
    Shard an InitialValueSolver's device state over the mesh: the pencil
    batch (group) dimension is the data-parallel axis — every group's
    implicit solve is independent (reference: core/timesteppers.py:160-172
    per-pencil factorizations), and the RHS transforms inside the jitted
    step trigger GSPMD all-to-alls exactly where the reference placed MPI
    transposes.

    Returns the solver (modified in place).
    """
    mesh = mesh or solver.dist.mesh
    if mesh is None:
        return solver
    if getattr(solver, "_dd", None) is not None:
        raise ValueError(
            "distribute_solver requires the native step path: the "
            "emulated-f64 (double-double) runner (core/ddstep.py) steps "
            "a single-process dd state the mesh sharding would bypass. "
            "Build with [execution] EMULATED_F64 = never to distribute "
            "f64 solves.")
    # record on the distributor: the compiled transform walks read it to
    # pin intermediate shardings (field.mesh_transforms)
    solver.dist.mesh = mesh
    axis_name = axis_name or mesh.axis_names[0]
    G = solver.pencil_shape[0]
    n = mesh.shape[axis_name]
    if G % n:
        raise ValueError(
            f"Mesh axis {axis_name!r} (size {n}) does not divide pencil "
            f"count {G}; choose resolutions with G % n == 0.")
    s2 = pencil_sharding(mesh, 2, axis_name)
    hist_sharding = NamedSharding(mesh, P(None, axis_name, None))
    with solver.build_phases.scope("shard"):
        solver.X = jax.device_put(solver.X, s2)
        ops = solver.ops
        if hasattr(ops, "shard_over"):
            # banded pencils: every program of the operators runs as each
            # chip's own over its groups (pencilops.BandedOps.shard_over),
            # and every leaf is placed on the axis it keeps the groups on
            ops.shard_over(mesh, axis_name)
            solver.M_mat = group_sharded(ops, solver.M_mat, mesh, axis_name)
            solver.L_mat = group_sharded(ops, solver.L_mat, mesh, axis_name)
            ops_ref = weakref.ref(ops)

            def pencils():
                held = ops_ref()
                return {"chips": n, "groups_per_chip": G // n,
                        "Gc_per_chip": getattr(held, "_chunk_width", None)}
            solver.build_phases.pencils = pencils
        else:
            # dense (G, S, S) stacks lead with the pencil-group axis
            shard_leaf = lambda a: jax.device_put(
                a, pencil_sharding(mesh, a.ndim, axis_name))
            solver.M_mat = jax.tree.map(shard_leaf, solver.M_mat)
            solver.L_mat = jax.tree.map(shard_leaf, solver.L_mat)
    ts = solver.timestepper
    for name in ("F_hist", "MX_hist", "LX_hist"):
        if hasattr(ts, name):
            setattr(ts, name, jax.device_put(getattr(ts, name), hist_sharding))
    # invalidate any cached LHS factorization built pre-sharding
    if hasattr(ts, "_lhs_key"):
        ts._lhs_key = None
        ts._lhs_aux = None
    return solver

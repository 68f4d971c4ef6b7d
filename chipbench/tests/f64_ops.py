"""Which operation of the double-double route loses digits on the chip
(PR 35): the pieces of one dd step, each on the configuration's own
matrices and stepped state, against NumPy long double on the host. One
process, which holds the chip:

    python3 chipbench/tests/f64_ops.py

The CPU reads 1e-14 for the ten reference steps with this same code, the
chip 2.3e-10 whatever the number of refinement sweeps (f64_digits.py), so
the loss is in the chip's own arithmetic of some piece: this names it.
Prints one JSON line per piece: the largest error over what the piece's
inputs allow (a row's 1-norm times the largest unknown for a product,
the entry for an elementwise operation). A diagnosis, not a metric."""

import json
import pathlib
import sys

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
LD = np.longdouble


def say(piece, **numbers):
    print(json.dumps(dict(piece=piece, **{k: float(v) for k, v
                                          in numbers.items()})), flush=True)


def main():
    import jax
    import jax.numpy as jnp
    from chipbench.manifest import load_module
    from dedalus_tpu.core.ddstep import _dd_scalar
    from dedalus_tpu.libraries import doubledouble as ddl
    from dedalus_tpu.libraries.doubledouble import DD
    cfg = load_module(ROOT / "chipbench" / "configs" / "rb256x64-f64.py")
    dep = cfg.build(0)
    solver, dt = dep.solver, cfg.SPEC["fixed_dt"]
    runner = solver._dd
    for _ in range(3):
        solver.step(dt)
    X = runner.state_f64()                       # (G, S) float64, host
    M, L = runner.M_host, runner.L_host
    G = [0, 1, 2, 64, 127]                       # pencil groups compared
    gamma = float(solver.timestepper.H[1, 1])
    A = M[G].astype(LD) + LD(dt * gamma) * L[G].astype(LD)
    rng = np.random.default_rng(0)

    # the value-space bridge and the elementwise operations
    x = rng.standard_normal(4096) * 10.0 ** rng.uniform(-8, 2, 4096)
    y = rng.standard_normal(4096) * 10.0 ** rng.uniform(-8, 2, 4096)
    a, b = ddl.dd_from_f64(x), ddl.dd_from_f64(y)
    x2, y2 = ddl.dd_to_f64(a).astype(LD), ddl.dd_to_f64(b).astype(LD)
    rel = lambda got, want: np.max(np.abs(got.astype(LD) - want)  # noqa: E731
                                   / np.abs(want))
    say("roundtrip _from64(_to64(x))", rel=rel(ddl.dd_to_f64(jax.jit(
        lambda v: ddl._from64(ddl._to64(v)))(a)), x2))
    say("dd_add", rel=np.max(np.abs(ddl.dd_to_f64(jax.jit(ddl.dd_add)(a, b))
                                    .astype(LD) - (x2 + y2))
                             / (np.abs(x2) + np.abs(y2))))
    say("dd_mul", rel=rel(ddl.dd_to_f64(jax.jit(ddl.dd_mul)(a, b)), x2 * y2))
    s = _dd_scalar(dt * gamma)
    s_ld = LD(float(s.hi)) + LD(float(s.lo))
    say("dd_mul by a dd scalar", rel=rel(ddl.dd_to_f64(
        jax.jit(ddl.dd_mul)(a, s)), x2 * s_ld))
    ints = rng.integers(-2 ** 24, 2 ** 24, 4096, dtype=np.int32)
    say("int32 -> f64 -> pair", abs=np.max(np.abs(ddl.dd_to_f64(jax.jit(
        lambda i: ddl._from64(i.astype(jnp.float64)))(jnp.asarray(ints)))
        - ints)))

    # the factor program's A: its planes against M + dt gamma L
    lhs = runner._rk_factor([s])[0]
    planes = np.asarray(lhs["planes"])[:, G].astype(LD)
    inv = np.asarray(lhs["inv"])[G].astype(LD)
    A_planes = inv * sum(planes[p] * LD(2.0) ** (-7 * (p + 1))
                         for p in range(planes.shape[0]))
    row = np.abs(A).max(axis=-1, keepdims=True)
    say("A from its planes, over the row's largest entry",
        rel=np.max(np.abs(A_planes - A) / row))

    # one pencil product: M X on the chip against long double
    Xdd = ddl.dd_from_f64(X)
    mp, minv = ddl.dd_slices_from_f64(M)
    product = jax.jit(lambda p, i, v: ddl.dd_matmul(
        None, DD(v.hi[..., None], v.lo[..., None]), a_planes=(p, i)))
    MX = ddl.dd_to_f64(product(jnp.asarray(mp), jnp.asarray(minv), Xdd))
    want = np.einsum("gij,gj->gi", M[G].astype(LD), X[G].astype(LD))
    # normwise, as an Ozaki product promises: a row's 1-norm times the
    # pencil's largest unknown (one exponent per sliced line)
    scale = np.abs(M[G]).sum(axis=-1) * np.abs(X[G]).max(axis=-1)[:, None]
    say("M X, over |M_i|_1 |X|_inf",
        rel=np.max(np.abs(MX[G, :, 0].astype(LD) - want)
                   / np.where(scale > 0, scale, 1)))

    # every pencil, three successive states: the device-side slicing of
    # the state rebuilt from its planes, and M X and L X, worst row of all
    slicer = jax.jit(lambda v: ddl._dd_slices(
        DD(v.hi[..., None], v.lo[..., None]), axis=-2, slices=8))
    lp, linv = ddl.dd_slices_from_f64(L)
    for state in range(3):
        solver.step(dt)
        Xs = runner.state_f64()
        Xs_dd = ddl.dd_from_f64(Xs)
        planes_x, inv_x = slicer(Xs_dd)
        planes_x = np.asarray(planes_x)[..., 0].astype(LD)
        rebuilt = np.asarray(inv_x)[..., 0].astype(LD) * sum(
            planes_x[k] * LD(2.0) ** (-7 * (k + 1)) for k in range(8))
        top = np.abs(Xs).max(axis=-1, keepdims=True)
        say(f"state {state}: X from its device-sliced planes, over |X|_inf",
            rel=np.max(np.abs(rebuilt - Xs) / np.where(top > 0, top, 1)))
        for name, mat, pl, iv in (("M", M, mp, minv), ("L", L, lp, linv)):
            got = ddl.dd_to_f64(product(jnp.asarray(pl), jnp.asarray(iv),
                                        Xs_dd))[..., 0].astype(LD)
            want = np.einsum("gij,gj->gi", mat.astype(LD), Xs.astype(LD))
            scale = np.abs(mat).sum(axis=-1) * top
            err = np.abs(got - want) / np.where(scale > 0, scale, 1)
            g, i = np.unravel_index(err.argmax(), err.shape)
            say(f"state {state}: {name} X, every pencil, over |row|_1 |X|_inf",
                rel=err.max(), group=g, row=i,
                rows_over_1e_12=(err > 1e-12).sum())

    # the refined solve of A x = r, as solve_ir does it, eagerly
    X = runner.state_f64()                       # the state by now
    r = np.einsum("gij,gj->gi", M, X)            # a right-hand side, f64
    rdd = ddl.dd_from_f64(r)
    ops = solver.ops
    for sweeps in (0, 1, 2, 3):
        x32 = ops.solve(lhs["aux32"], rdd.hi)
        sol = DD(x32, jnp.zeros_like(x32))
        for _ in range(sweeps):
            Ax = product(lhs["planes"], lhs["inv"], sol)
            res = ddl.dd_sub(rdd, DD(Ax.hi[..., 0], Ax.lo[..., 0]))
            dx = ops.solve(lhs["aux32"], res.hi)
            sol = ddl.dd_add(sol, DD(dx, jnp.zeros_like(dx)))
        got = ddl.dd_to_f64(sol)
        # every pencil (A in float64 here: numpy's solve is the yardstick)
        want = np.linalg.solve(M + dt * gamma * L, r[..., None])[..., 0]
        each = np.linalg.norm(got - want, axis=-1) \
            / np.linalg.norm(want, axis=-1)
        say(f"solve_ir, {sweeps} sweeps, against numpy.linalg.solve",
            rel_l2=np.linalg.norm(got - want) / np.linalg.norm(want),
            worst_group=each.max(), which=each.argmax(),
            groups_over_1e_10=(each > 1e-10).sum(),
            largest_entry_error_over_X_inf=np.max(
                np.abs(got - want) / np.abs(want).max(axis=-1,
                                                      keepdims=True)))
    # the explicit half's transforms: each axis of b and of w = u_z, there
    # and back, as the interpreter applies them (dd_apply_matrix on the
    # basis's own matrices), against long double; per line of the field
    from dedalus_tpu.core.ddstep import dd_apply_matrix
    from dedalus_tpu.tools.jitlift import lifted_jit
    fields = {"b": np.asarray(dep.fields["b"]["c"], dtype=np.float64),
              "w": np.asarray(dep.fields["u"]["c"], dtype=np.float64)[1]}
    bases = dep.fields["b"].domain.bases
    for name, coeff in fields.items():
        data = coeff
        for axis in (1, 0, 0, 1):                # z, x to the grid; back
            forward = data.shape[axis] != coeff.shape[axis]
            plan = bases[axis].transform_plan(
                bases[axis].dealias[0] if hasattr(bases[axis].dealias,
                                                  "__len__")
                else bases[axis].dealias, library="matrix")
            mat = plan.forward_mat if forward else plan.backward_mat
            mat = np.asarray(mat.toarray() if hasattr(mat, "toarray")
                             else mat, dtype=np.float64)
            got = ddl.dd_to_f64(lifted_jit(
                lambda d, m=mat, ax=axis: dd_apply_matrix(m, d, ax))(
                    ddl.dd_from_f64(data)))
            want = np.moveaxis(np.tensordot(
                mat.astype(LD), data.astype(LD), axes=(1, axis)), 0, axis)
            line = np.abs(want).max(axis=axis, keepdims=True)
            err = np.abs(got.astype(LD) - want) / np.where(line > 0, line, 1)
            say(f"{name}: axis {axis} {'forward' if forward else 'backward'}"
                ", over the line's largest value", rel=err.max(),
                lines_over_1e_12=(err.max(axis=axis) > 1e-12).sum(),
                overall=np.linalg.norm((got - want).astype(np.float64))
                / np.linalg.norm(want.astype(np.float64)))
            # the two halves of that product, apart: the operand as
            # dd_apply_matrix lays it out, (k, n), sliced on the device
            # line by line and rebuilt from its planes; and the int8
            # products of every plane pair against integers on the host
            B = np.moveaxis(data, axis, -1).reshape(-1, data.shape[axis]).T
            Bdd = ddl.dd_from_f64(B)
            pb, ib = jax.jit(lambda v: ddl._dd_slices(v, axis=-2, slices=8))(
                Bdd)
            pb = np.asarray(pb)
            rebuilt = np.asarray(ib).astype(LD) * sum(
                pb[k].astype(LD) * LD(2.0) ** (-7 * (k + 1))
                for k in range(8))
            top = np.abs(B).max(axis=0, keepdims=True)
            off = np.abs(rebuilt - B) / np.where(top > 0, top, 1)
            pa, _ = ddl.dd_slices_from_f64(mat)
            dims = (((1,), (0,)), ((), ()))
            wrong = 0
            for i in range(8):
                for j in range(8 - i):
                    dev = np.asarray(ddl._plane_dot(
                        jnp.asarray(pa[i]), jnp.asarray(pb[j]), dims))
                    wrong += int((dev != pa[i].astype(np.int64)
                                  @ pb[j].astype(np.int64)).sum())
            say(f"{name}: axis {axis}: operand from its device-sliced planes"
                ", over the line's largest value; int8 products",
                rel=off.max(), lines_over_1e_12=(off.max(axis=0) > 1e-12).sum(),
                plane_max=np.abs(pb).max(), wrong_int32_sums=wrong)
            data = np.asarray(want, dtype=np.float64)
    say("platform " + jax.devices()[0].platform, ok=1)


if __name__ == "__main__":
    main()

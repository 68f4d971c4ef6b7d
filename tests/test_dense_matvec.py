"""
The dense pencil product (libraries/matsolvers.batched_matvec) and its
three users — `DenseOps.matvec`, `DenseOps.matvec_pair` and
`BatchedInverse.solve` — against float64 NumPy: the product is an
elementwise multiply and a sum over the contracted axis (the form the
TPU reads without re-laying-out the stack; tests/test_chip_compile.py
asks the chip's compiler), and that is the same arithmetic as the dot it
replaced, not a cheaper one. Sizes: RB 256x64's stack (G=128, S=526),
shear-flow-like many small pencils, and one pencil alone.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import dedalus_tpu.public as d3  # noqa: F401  (x64 + matmul precision set)
from dedalus_tpu.libraries.matsolvers import BatchedInverse, batched_matvec
from dedalus_tpu.libraries.pencilops import DenseOps

cases = pytest.mark.parametrize("dtype", [np.float32, np.float64],
                                ids=["f32", "f64"])
sizes = pytest.mark.parametrize("G,S", [(128, 526), (4096, 20), (1, 64)])


def _system(G, S, dtype):
    """Well-conditioned pencils (I + a random part of norm ~1/2) and
    vectors, as `dtype` arrays plus their exact float64 values."""
    rng = np.random.default_rng(1000 * G + S)
    A = (np.eye(S) + rng.standard_normal((G, S, S)) / (4 * np.sqrt(S)))
    x = rng.standard_normal((G, S))
    A, x = A.astype(dtype), x.astype(dtype)
    return A, x, A.astype(np.float64), x.astype(np.float64)


def _row_bound(A64, x64, dtype):
    """4 S eps |A||x| per row: the textbook bound on a length-S inner
    product in `dtype` (S eps), with room for the float64 reference's
    own rounding where dtype is float64."""
    S = A64.shape[-1]
    return 4 * S * np.finfo(dtype).eps * np.einsum(
        "gij,gj->gi", np.abs(A64), np.abs(x64))


@cases
@sizes
def test_matvec_within_the_inner_product_bound(G, S, dtype):
    A, x, A64, x64 = _system(G, S, dtype)
    ref = np.einsum("gij,gj->gi", A64, x64)
    bound = _row_bound(A64, x64, dtype)
    ops = DenseOps(matsolver="BatchedInverse")
    Aj, xj = jnp.asarray(A), jnp.asarray(x)
    out = batched_matvec(Aj, xj)
    assert out.dtype == dtype and out.shape == (G, S)
    assert np.all(np.abs(np.asarray(out, np.float64) - ref) <= bound)
    # the three call sites are the helper, bit for bit
    for other in (ops.matvec(Aj, xj), *ops.matvec_pair(Aj, Aj, xj),
                  BatchedInverse.solve(Aj, xj)):
        assert np.array_equal(np.asarray(other), np.asarray(out))


@cases
@pytest.mark.parametrize("G,S", [(4, 526), (4096, 20), (1, 64)])
def test_stored_inverse_recovers_x(G, S, dtype):
    """Four pencils of 526 and not 128: XLA:CPU's batched inverse at the
    full stack takes minutes beside five other xdist workers, and the
    product's contraction length is what this holds."""
    A, x, A64, x64 = _system(G, S, dtype)
    rhs = np.einsum("gij,gj->gi", A64, x64).astype(dtype)
    inv = BatchedInverse.factor(jnp.asarray(A))
    got = np.asarray(BatchedInverse.solve(inv, jnp.asarray(rhs)), np.float64)
    # cond(A) < 3 by construction: forward error of inverse-times-rhs
    err = np.max(np.abs(got - x64)) / np.max(np.abs(x64))
    assert err <= 16 * S * np.finfo(dtype).eps


@cases
@sizes
def test_grad_through_the_adjoint_funnel(G, S, dtype):
    """Reverse mode through DenseOps.solve (AdjointSolveOps: a jax.vjp of
    the helper) is the transposed product with the same stored inverse."""
    A, w, A64, w64 = _system(G, S, dtype)
    ops = DenseOps(matsolver="BatchedInverse")
    aux = jnp.asarray(A)            # stands for a stored inverse
    grad = jax.grad(lambda r: jnp.sum(jnp.asarray(w) * ops.solve(aux, r)))(
        jnp.zeros((G, S), dtype))
    At64 = A64.transpose(0, 2, 1)
    ref = np.einsum("gij,gj->gi", At64, w64)
    assert grad.dtype == dtype
    assert np.all(np.abs(np.asarray(grad, np.float64) - ref)
                  <= _row_bound(At64, w64, dtype))
    direct = ops.solve_transpose(aux, jnp.asarray(w))
    assert np.array_equal(np.asarray(direct), np.asarray(grad))


@cases
@sizes
def test_vmap_over_members(G, S, dtype):
    """The ensemble's use (core/ensemble.py vmaps the step body with M and
    L unbatched, X per member)."""
    A, _, A64, _ = _system(G, S, dtype)
    rng = np.random.default_rng(7)
    X = rng.standard_normal((3, G, S)).astype(dtype)
    out = jax.vmap(batched_matvec, in_axes=(None, 0))(
        jnp.asarray(A), jnp.asarray(X))
    assert out.shape == (3, G, S) and out.dtype == dtype
    for n in range(3):
        x64 = X[n].astype(np.float64)
        ref = np.einsum("gij,gj->gi", A64, x64)
        assert np.all(np.abs(np.asarray(out[n], np.float64) - ref)
                      <= _row_bound(A64, x64, dtype))

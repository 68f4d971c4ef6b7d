"""
Double-double (emulated f64) arithmetic: exactness and precision oracles.

Every check compares the f32-pair result against numpy float64 reference
arithmetic; tolerances reflect dd's ~49-bit significand (eps ~ 2^-49 ~
1.8e-15) vs f64's 53 bits. Reference parity target: the reference
framework runs float64 end-to-end (SURVEY.md §7 hard part 7); this is the
TPU-native equivalent compute path.
"""

import numpy as np
import pytest

from dedalus_tpu.libraries import doubledouble as dd


@pytest.fixture
def rng():
    return np.random.default_rng(7)


def rel_err(approx, exact):
    scale = np.max(np.abs(exact)) + 1e-300
    return np.max(np.abs(approx - exact)) / scale


def test_roundtrip_precision(rng):
    # a dd pair carries ~49 significand bits (24 + 24 + implicit overlap
    # headroom) vs f64's 53: roundtrip is accurate to ~2^-49 relative,
    # not exact
    x = rng.standard_normal(1000) * 10.0 ** rng.integers(-8, 8, 1000)
    a = dd.dd_from_f64(x)
    err = np.abs(dd.dd_to_f64(a) - x) / np.abs(x)
    assert err.max() < 2.0 ** -48


def test_two_sum_exact(rng):
    a = np.float32(1.0)
    b = np.float32(1e-8)
    s, e = dd.two_sum(a, b)
    assert float(s) + float(e) == pytest.approx(1.0 + 1e-8, abs=0)
    # exactness: s + e == a + b in f64
    assert np.float64(s) + np.float64(e) == np.float64(a) + np.float64(b)


def test_two_prod_exact(rng):
    a = rng.standard_normal(200).astype(np.float32)
    b = rng.standard_normal(200).astype(np.float32)
    p, e = dd.two_prod(np.asarray(a), np.asarray(b))
    exact = a.astype(np.float64) * b.astype(np.float64)
    got = np.asarray(p, dtype=np.float64) + np.asarray(e, dtype=np.float64)
    assert np.array_equal(got, exact)


def test_add_mul_div_precision(rng):
    x = rng.standard_normal(500)
    y = rng.standard_normal(500) * 3.7
    ax, ay = dd.dd_from_f64(x), dd.dd_from_f64(y)
    assert rel_err(dd.dd_to_f64(dd.dd_add(ax, ay)), x + y) < 2e-14
    assert rel_err(dd.dd_to_f64(dd.dd_mul(ax, ay)), x * y) < 2e-14
    assert rel_err(dd.dd_to_f64(dd.dd_div(ax, ay)), x / y) < 2e-14
    assert rel_err(dd.dd_to_f64(dd.dd_mul_f32(ax, np.float32(1.5))),
                   x * 1.5) < 2e-14


def test_accumulated_sum_precision(rng):
    # f32 would drift at ~1e-7 over 10^4 additions; dd must hold ~1e-14
    x = rng.standard_normal(10000)
    a = dd.dd_zeros(())
    for chunk in x.reshape(100, 100):
        c = dd.dd_from_f64(chunk)
        # tree-reduce the chunk then accumulate
        s = dd.DD(c.hi.sum(), c.lo.sum())  # f32 partial: deliberately crude
        a = dd.dd_add(a, s)
    crude = float(dd.dd_to_f64(a))
    exact = x.sum()
    # even with crude f32 chunk sums the dd accumulator stays ~1e-11;
    # this guards the accumulator itself, not the chunk reduction
    assert abs(crude - exact) < 1e-4
    # full-precision path: element-wise dd accumulate of one chunk
    c = dd.dd_from_f64(x[:100])
    tot = dd.dd_zeros(())
    for i in range(100):
        tot = dd.dd_add(tot, c[i])
    assert abs(float(dd.dd_to_f64(tot)) - x[:100].sum()) < 1e-13


def test_matmul_precision(rng):
    A = rng.standard_normal((100, 80))
    B = rng.standard_normal((80, 60))
    C = dd.dd_matmul(dd.dd_from_f64(A), dd.dd_from_f64(B))
    exact = A @ B
    assert rel_err(dd.dd_to_f64(C), exact) < 1e-13


def test_matmul_batched(rng):
    A = rng.standard_normal((5, 32, 48))
    B = rng.standard_normal((5, 48, 16))
    C = dd.dd_matmul(dd.dd_from_f64(A), dd.dd_from_f64(B))
    exact = A @ B
    assert rel_err(dd.dd_to_f64(C), exact) < 1e-13


def test_matmul_presliced(rng):
    # static-operand fast path: the transform-matrix use case
    M = rng.standard_normal((64, 64))
    X = rng.standard_normal((64, 24))
    planes, inv = dd.dd_slices_from_f64(M, axis=-1)
    import jax.numpy as jnp
    pl = (jnp.asarray(planes), jnp.asarray(inv))
    C = dd.dd_matmul(None, dd.dd_from_f64(X), a_planes=pl)
    assert rel_err(dd.dd_to_f64(C), M @ X) < 1e-13


def test_matmul_wild_scales(rng):
    # rows/cols spanning ~24 orders of magnitude: per-line exponent
    # normalization must keep relative precision. (Range is bounded by
    # f32's exponent field — dd(f32) covers ~1e+/-38 magnitudes, so
    # products stay below ~1e30 here; beyond that is a documented
    # limitation of f32-pair emulation, not a precision loss.)
    A = rng.standard_normal((40, 50)) * 10.0 ** rng.integers(-12, 12, (40, 1))
    B = rng.standard_normal((50, 30)) * 10.0 ** rng.integers(-12, 12, (1, 30))
    C = dd.dd_matmul(dd.dd_from_f64(A), dd.dd_from_f64(B))
    exact = A @ B
    # compare per-element relative to the row/col scale product
    scale = np.abs(A).max(axis=1)[:, None] * np.abs(B).max(axis=0)[None, :]
    err = np.abs(dd.dd_to_f64(C) - exact) / (scale * A.shape[1])
    assert err.max() < 1e-13


def test_matmul_under_jit(rng):
    import jax
    A = rng.standard_normal((32, 32))
    B = rng.standard_normal((32, 32))
    f = jax.jit(lambda a, b: dd.dd_matmul(a, b))
    C = f(dd.dd_from_f64(A), dd.dd_from_f64(B))
    assert rel_err(dd.dd_to_f64(C), A @ B) < 1e-13


def test_mass_conservation_grade(rng):
    # the KdV oracle scale: sum of ~1000 coefficients must be stable to
    # ~1e-14 relative over repeated add/sub cycles
    x = rng.standard_normal(1024)
    a = dd.dd_from_f64(x)
    b = a
    for _ in range(50):
        b = dd.dd_add(b, a)
        b = dd.dd_sub(b, a)
    assert rel_err(dd.dd_to_f64(b), x) < 1e-13


@pytest.mark.parametrize("plane", [0, 3, 6])
def test_slices_survive_a_rint_that_is_a_unit_off(rng, monkeypatch, plane):
    """A TPU's f64 is a float32 pair and its rint is now and then a whole
    unit off (v5e, PR 35): the next plane's value is then 128 or more,
    which int8 cannot hold, and the line lost its lower planes. The
    slicing rounds a second time on what the first left, so the line is
    still rebuilt to the last plane's size. Emulated here by a rint that
    rounds one entry of one plane's FIRST rounding a unit too low."""
    import jax.numpy as jnp
    x = rng.standard_normal((6, 5))
    rint, calls = jnp.rint, []

    def unit_off(v):
        q = rint(v)
        calls.append(None)
        if len(calls) - 1 == 2 * plane:      # two roundings a plane
            q = q.at[2, 1].add(jnp.where(q[2, 1] > 0, -1.0, 1.0))
        return q

    monkeypatch.setattr(jnp, "rint", unit_off)
    planes, inv = dd._dd_slices(dd.dd_from_f64(x), axis=-2, slices=8)
    planes = np.asarray(planes).astype(np.float64)
    assert np.abs(planes).max() <= 65
    rebuilt = np.asarray(inv, np.float64) * sum(
        planes[p] * 2.0 ** (-7 * (p + 1)) for p in range(8))
    top = np.abs(x).max(axis=0, keepdims=True)
    assert (np.abs(rebuilt - x) / top).max() < 2.0 ** -47

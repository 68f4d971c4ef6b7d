"""
Transform tests (reference: dedalus/tests/test_transforms.py).

The reference's dual-implementation oracle pattern: every fast transform
library is checked against the 'matrix' MMT implementation of the same
basis, plus grid<->coeff roundtrips with random data.
"""

import numpy as np
import pytest
import jax.numpy as jnp

import dedalus_tpu.public as d3
from dedalus_tpu.core.field import transform_to_coeff, transform_to_grid

N_range = [8, 16, 32]
dealias_range = [1, 3/2]


@pytest.mark.parametrize("N", N_range)
@pytest.mark.parametrize("dealias", dealias_range)
@pytest.mark.parametrize("library", ["fft"])
def test_real_fourier_libraries(N, dealias, library, rng):
    """Fast library forward/backward vs matrix MMT
    (reference: test_transforms.py:22)."""
    xc = d3.Coordinate("x")
    dist = d3.Distributor(xc, dtype=np.float64)
    xb = d3.RealFourier(xc, size=N, bounds=(0, 2.3), dealias=dealias)
    gdata = rng.standard_normal(xb.grid_size(dealias))
    c_fast = np.asarray(xb.forward_transform(jnp.asarray(gdata), 0, dealias, library))
    c_mat = np.asarray(xb.forward_transform(jnp.asarray(gdata), 0, dealias, "matrix"))
    assert np.allclose(c_fast, c_mat)
    g_fast = np.asarray(xb.backward_transform(jnp.asarray(c_mat), 0, dealias, library))
    g_mat = np.asarray(xb.backward_transform(jnp.asarray(c_mat), 0, dealias, "matrix"))
    assert np.allclose(g_fast, g_mat)


@pytest.mark.parametrize("N", N_range)
@pytest.mark.parametrize("library", ["fft"])
def test_complex_fourier_libraries(N, library, rng):
    xc = d3.Coordinate("x")
    dist = d3.Distributor(xc, dtype=np.complex128)
    xb = d3.ComplexFourier(xc, size=N, bounds=(0, 1.7))
    gdata = rng.standard_normal(N) + 1j * rng.standard_normal(N)
    c_fast = np.asarray(xb.forward_transform(jnp.asarray(gdata), 0, 1.0, library))
    c_mat = np.asarray(xb.forward_transform(jnp.asarray(gdata), 0, 1.0, "matrix"))
    assert np.allclose(c_fast, c_mat)
    g_fast = np.asarray(xb.backward_transform(jnp.asarray(c_mat), 0, 1.0, library))
    g_mat = np.asarray(xb.backward_transform(jnp.asarray(c_mat), 0, 1.0, "matrix"))
    assert np.allclose(g_fast, g_mat)


@pytest.mark.parametrize("N", N_range)
@pytest.mark.parametrize("basis_fn", [d3.ChebyshevT, d3.Legendre,
                                      lambda c, **kw: d3.Jacobi(c, a=1.0, b=0.5, **kw)])
def test_jacobi_roundtrip(N, basis_fn, rng):
    """Band-limited roundtrip is exact (reference: test_transforms.py
    roundtrip suites)."""
    zc = d3.Coordinate("z")
    dist = d3.Distributor(zc, dtype=np.float64)
    zb = basis_fn(zc, size=N, bounds=(-0.7, 1.3))
    coeffs = rng.standard_normal(N)
    g = np.asarray(zb.backward_transform(jnp.asarray(coeffs), 0, 1.0))
    c2 = np.asarray(zb.forward_transform(jnp.asarray(g), 0, 1.0))
    assert np.allclose(c2, coeffs)


@pytest.mark.parametrize("N", [16, 32])
@pytest.mark.parametrize("dealias", dealias_range)
def test_2d_field_roundtrip(N, dealias, rng):
    """Full-field grid->coeff->grid roundtrip in 2D."""
    coords = d3.CartesianCoordinates("x", "z")
    dist = d3.Distributor(coords, dtype=np.float64)
    xb = d3.RealFourier(coords["x"], size=N, bounds=(0, 2), dealias=dealias)
    zb = d3.ChebyshevT(coords["z"], size=N, bounds=(0, 1), dealias=dealias)
    u = dist.Field(name="u", bases=(xb, zb))
    x, z = dist.local_grids(xb, zb)
    u["g"] = np.sin(np.pi * x) * z**3
    g0 = u["g"].copy()
    _ = u["c"]
    assert np.allclose(u["g"], g0)


def test_scale_change(rng):
    """Dealias pad/truncate through coefficient space."""
    xc = d3.Coordinate("x")
    dist = d3.Distributor(xc, dtype=np.float64)
    xb = d3.RealFourier(xc, size=16, bounds=(0, 2 * np.pi))
    u = dist.Field(name="u", bases=xb)
    x = dist.local_grid(xb)
    u["g"] = np.sin(3 * x)
    u.change_scales(3 / 2)
    x2 = dist.local_grid(xb, scale=3 / 2)
    assert np.allclose(u["g"], np.sin(3 * x2.ravel()))
    u.change_scales(1)
    assert np.allclose(u["g"], np.sin(3 * x.ravel()))


def test_jacobi_derivative_level_transforms(rng):
    """Transforms at derivative levels k>0 (ultraspherical conversion)."""
    zc = d3.Coordinate("z")
    dist = d3.Distributor(zc, dtype=np.float64)
    zb = d3.ChebyshevT(zc, size=24, bounds=(0, 1))
    zb2 = zb.derivative_basis(2)
    z = dist.local_grid(zb).ravel()
    f = z**4 - 2 * z
    c = np.asarray(zb2.forward_transform(jnp.asarray(f), 0, 1.0))
    g = np.asarray(zb2.backward_transform(jnp.asarray(c), 0, 1.0))
    assert np.allclose(g, f)


@pytest.mark.parametrize("N", [8, 64, 256])
@pytest.mark.parametrize("k", [0, 1, 2])
@pytest.mark.parametrize("scale", [1.0, 1.5])
def test_fast_chebyshev_vs_mmt(N, k, scale, rng):
    """DCT fast path vs the MMT oracle (reference pattern:
    tests/test_transforms.py fast-vs-matrix checks; math reference:
    core/transforms.py:801-890 FastChebyshevTransform)."""
    import dedalus_tpu.public as d3
    from dedalus_tpu.core import transforms as tr
    coords = d3.CartesianCoordinates("z")
    d3.Distributor(coords, dtype=np.float64)
    zb = d3.ChebyshevT(coords["z"], size=N, bounds=(0, 1)).derivative_basis(k)
    mmt = tr.get_plan(zb, scale, "matrix")
    fft = tr.get_plan(zb, scale, "fft")
    assert fft._mmt is None  # really the DCT path
    Ng = zb.grid_size(scale)
    g = rng.standard_normal((3, Ng))
    cm = np.asarray(mmt.forward(jnp.asarray(g), 1))
    cf = np.asarray(fft.forward(jnp.asarray(g), 1))
    assert np.abs(cm - cf).max() < 1e-11 * max(1, np.abs(cm).max())
    c = rng.standard_normal((3, N))
    gm = np.asarray(mmt.backward(jnp.asarray(c), 1))
    gf = np.asarray(fft.backward(jnp.asarray(c), 1))
    assert np.abs(gm - gf).max() < 1e-11 * max(1, np.abs(gm).max())


def test_legendre_fft_falls_back_to_mmt(rng):
    """Non-Chebyshev Jacobi grids have no DCT; the fft plan must still be
    correct by falling back to the MMT."""
    import dedalus_tpu.public as d3
    from dedalus_tpu.core import transforms as tr
    coords = d3.CartesianCoordinates("z")
    d3.Distributor(coords, dtype=np.float64)
    zb = d3.Legendre(coords["z"], size=32, bounds=(0, 1))
    fft = tr.get_plan(zb, 1.0, "fft")
    assert fft._mmt is not None
    g = rng.standard_normal(32)
    c = np.asarray(fft.forward(jnp.asarray(g), 0))
    g2 = np.asarray(fft.backward(jnp.asarray(c), 0))
    assert np.abs(g - g2).max() < 1e-12


def test_fast_chebyshev_complex_and_coarse(rng):
    """Complex data must survive the DCT path (real/imag split), and
    coarse scales (Ng < N) must route to the rectangular MMT."""
    import dedalus_tpu.public as d3
    from dedalus_tpu.core import transforms as tr
    coords = d3.CartesianCoordinates("z")
    d3.Distributor(coords, dtype=np.complex128)
    zb = d3.ChebyshevT(coords["z"], size=16, bounds=(0, 1))
    mmt = tr.get_plan(zb, 1.0, "matrix")
    fft = tr.get_plan(zb, 1.0, "fft")
    g = rng.standard_normal(16) + 1j * rng.standard_normal(16)
    cm = np.asarray(mmt.forward(jnp.asarray(g), 0))
    cf = np.asarray(fft.forward(jnp.asarray(g), 0))
    assert np.abs(cm - cf).max() < 1e-13
    gm = np.asarray(mmt.backward(jnp.asarray(cm), 0))
    gf = np.asarray(fft.backward(jnp.asarray(cm), 0))
    assert np.abs(gm - gf).max() < 1e-13
    coarse = tr.get_plan(zb, 0.5, "fft")
    assert coarse._mmt is not None
    c = rng.standard_normal(16)
    out = np.asarray(coarse.backward(jnp.asarray(c), 0))
    assert out.shape == (8,)


# ---------------------------------------------------------------------------
# Extended roundtrip coverage (reference: tests/test_transforms.py parametrizes
# every basis x dtype x dealias x rank against the matrix oracle, 742 LoC)

@pytest.mark.parametrize("basis_fn", [
    lambda c, N, d: d3.ChebyshevU(c, size=N, bounds=(-1, 2), dealias=d),
    lambda c, N, d: d3.ChebyshevV(c, size=N, bounds=(0, 1), dealias=d),
    lambda c, N, d: d3.Ultraspherical(c, size=N, bounds=(0, 3), alpha=1.5,
                                      dealias=d),
    lambda c, N, d: d3.Legendre(c, size=N, bounds=(-2, -1), dealias=d),
])
@pytest.mark.parametrize("dealias", [1, 3 / 2])
def test_jacobi_family_roundtrips(basis_fn, dealias, rng):
    N = 24
    c = d3.Coordinate("x")
    dist = d3.Distributor(c, dtype=np.float64)
    b = basis_fn(c, N, dealias)
    f = dist.Field(name="f", bases=b)
    f["c"] = rng.standard_normal(N)
    c0 = np.asarray(f["c"]).copy()
    f.change_scales(dealias)
    _ = f["g"]
    assert np.allclose(np.asarray(f["c"]), c0, atol=1e-12)


@pytest.mark.parametrize("rank", [1, 2])
def test_tensor_field_roundtrip(rank, rng):
    """Vector/tensor fields roundtrip with tensor axes leading."""
    coords = d3.CartesianCoordinates("x", "z")
    dist = d3.Distributor(coords, dtype=np.float64)
    xb = d3.RealFourier(coords["x"], size=12, bounds=(0, 1), dealias=3 / 2)
    zb = d3.ChebyshevT(coords["z"], size=10, bounds=(0, 1), dealias=3 / 2)
    sig = (coords,) * rank
    f = dist.TensorField(sig, name="f", bases=(xb, zb))
    shape = np.asarray(f["c"]).shape
    f["c"] = rng.standard_normal(shape)
    # one roundtrip first: random coefficients include invalid slots
    # (RealFourier -sin0/Nyquist) that project away
    _ = f["g"]
    c0 = np.asarray(f["c"]).copy()
    _ = f["g"]
    assert np.allclose(np.asarray(f["c"]), c0, atol=1e-12)


def test_complex_fourier_matrix_vs_fft_forward(rng):
    """Forward coefficients agree between MMT oracle and FFT library."""
    c = d3.Coordinate("x")
    dist = d3.Distributor(c, dtype=np.complex128)
    N = 16
    g = rng.standard_normal(N) + 1j * rng.standard_normal(N)
    coeffs = {}
    for lib in ("matrix", "fft"):
        b = d3.ComplexFourier(c, size=N, bounds=(0, 2 * np.pi), library=lib)
        f = dist.Field(name="f", bases=b)
        f["g"] = g
        coeffs[lib] = np.asarray(f["c"]).copy()
    assert np.allclose(coeffs["matrix"], coeffs["fft"], atol=1e-12)


def test_real_fourier_matrix_vs_fft_forward(rng):
    c = d3.Coordinate("x")
    dist = d3.Distributor(c, dtype=np.float64)
    N = 16
    g = rng.standard_normal(N)
    coeffs = {}
    for lib in ("matrix", "fft"):
        b = d3.RealFourier(c, size=N, bounds=(0, 2 * np.pi), library=lib)
        f = dist.Field(name="f", bases=b)
        f["g"] = g
        coeffs[lib] = np.asarray(f["c"]).copy()
    assert np.allclose(coeffs["matrix"], coeffs["fft"], atol=1e-12)


@pytest.mark.parametrize("Ng_scale", [1, 2, 3 / 2])
def test_chebyshev_known_function(Ng_scale):
    """T_3(x) has exactly one coefficient in the ChebyshevT expansion."""
    c = d3.Coordinate("x")
    dist = d3.Distributor(c, dtype=np.float64)
    b = d3.ChebyshevT(c, size=8, bounds=(-1, 1), dealias=Ng_scale)
    f = dist.Field(name="f", bases=b)
    f.change_scales(Ng_scale)
    x = b.global_grid(Ng_scale)
    f["g"] = 4 * x ** 3 - 3 * x   # T_3
    coeffs = np.asarray(f["c"])
    # orthonormal normalization: only mode 3 nonzero
    mask = np.zeros(8, dtype=bool)
    mask[3] = True
    assert np.abs(coeffs[~mask]).max() < 1e-13
    assert np.abs(coeffs[3]) > 0.1


def test_degenerate_sizes(rng):
    """Size-1 and size-2 bases roundtrip (reference degenerate-size tests)."""
    c = d3.Coordinate("x")
    dist = d3.Distributor(c, dtype=np.float64)
    for N in (1, 2, 3):
        b = d3.ChebyshevT(c, size=N, bounds=(0, 1))
        f = dist.Field(name="f", bases=b)
        f["c"] = rng.standard_normal(N)
        c0 = np.asarray(f["c"]).copy()
        _ = f["g"]
        assert np.allclose(np.asarray(f["c"]), c0, atol=1e-12), N


# members of a fleet from which its transforms, like its pencil products,
# take the contraction (`matsolvers.fleet_contraction`)
FLEET = 16


@pytest.mark.parametrize("scale", [1, 3 / 2])
@pytest.mark.parametrize("direction", ["forward", "backward"])
@pytest.mark.parametrize("basis", ["RealFourier", "ChebyshevT",
                                   "ChebyshevT-k2"])
def test_fleet_transforms_are_matrix_contractions(basis, direction, scale,
                                                  rng):
    """Inside a fleet program (`matsolvers.member_fleet`), vmapped over
    FLEET members, an `fft` plan applies its basis's matrix plan along the
    axis (RealFourier along an inner axis, ChebyshevT along the last): the
    FFT path batched to 1e-6 in float32, with no `fft` in the jaxpr. Under
    FLEET members it is the FFT path vmapped, and outside a fleet the
    plan's jaxpr is its class's own."""
    import jax
    from dedalus_tpu.core import transforms as tr
    from dedalus_tpu.libraries import matsolvers
    c = d3.Coordinate("x")
    d3.Distributor(c, dtype=np.float64)
    if basis == "RealFourier":
        b, axis = d3.RealFourier(c, size=16, bounds=(0, 2.3)), 1
    else:
        b = d3.ChebyshevT(c, size=16, bounds=(0, 1))
        b, axis = (b.derivative_basis(2) if basis.endswith("k2") else b), 2
    plan = tr.get_plan(b, scale, "fft")
    own = getattr(type(plan)(b, scale), direction)
    n = b.grid_size(scale) if direction == "forward" else b.size
    shape = [3, 4, 5]
    shape[axis] = n
    x = jnp.asarray(rng.standard_normal([FLEET] + shape), jnp.float32)
    member = lambda a: getattr(plan, direction)(a, axis)  # noqa: E731

    def fleet(a):
        with matsolvers.member_fleet():
            return jax.vmap(member)(a)

    def ffts(fn, *args):
        return str(jax.make_jaxpr(fn)(*args)).count(" fft[")

    want = np.asarray(jax.jit(jax.vmap(lambda a: own(a, axis)))(x),
                      np.float64)
    got = np.asarray(jax.jit(fleet)(x), np.float64)
    assert np.abs(got - want).max() <= 1e-6 * np.abs(want).max()
    assert ffts(fleet, x) == 0
    assert ffts(fleet, x[:FLEET // 2]) == ffts(jax.vmap(member), x) > 0
    assert str(jax.make_jaxpr(member)(x[0])) == \
        str(jax.make_jaxpr(lambda a: own(a, axis))(x[0]))

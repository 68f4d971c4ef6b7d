"""
Spectral transform plans (reference: dedalus/core/transforms.py).

Each plan converts one axis of an N-d array between coefficient and grid
representations. Plans are registered per (basis class, library name) like
the reference's `@register_transform` registry (core/transforms.py:27-32):

  * 'matrix' — dense matrix-multiply transform (MMT). The test oracle, and
    on TPU a genuinely fast path: an MMT is one batched matmul on the MXU.
  * 'fft'    — jnp.fft fast path for Fourier bases; FFT-based DCT for
    Chebyshev.

All plan methods are pure jnp functions of their array argument (safe under
jit/vmap); the transform matrices are host-built numpy constants closed over
by the jitted step.

Inside a fleet's programs (`matsolvers.member_fleet`, the member-vmapped
bodies of core/ensemble.py) every plan whose basis has a 'matrix' plan
applies that plan's matrix instead, as ONE contraction over the
transformed axis for every member at once (`_contract_axis`), where the
fleet's pencil products take their contraction (`fleet_contraction`: from
16 members). A fleet's transform carries the member count times a member's
rows, and the FFT gains nothing from that batch (v5e, RB 256x64: 0.27 ms a
member in a 1,024-member fleet against 0.22 ms alone), where a product with
the shared matrix is one MXU-shaped contraction with members x rows rows.
Outside a fleet every plan traces the program it always has.
"""

import collections
import threading

import numpy as np
import jax
import jax.numpy as jnp

from . import meshctx
from ..tools.array import zeropad

from ..tools.array import apply_matrix_jax, match_precision
from ..tools import metrics as metrics_mod
from ..tools.metrics import scoped as _scoped
from ..libraries.matsolvers import fleet_contraction, in_member_fleet

# Registry: {(basis_class_name, library): plan_class}
transform_registry = {}


def register_transform(basis_cls_name, name):
    def wrapper(cls):
        transform_registry[(basis_cls_name, name)] = cls
        cls.library = name
        return cls
    return wrapper


def _registered(basis, library):
    """The plan class registered for `basis` under `library`, looked up
    through its base classes (e.g. ChebyshevT -> Jacobi), or None."""
    for klass in type(basis).__mro__:
        cls = transform_registry.get((klass.__name__, library))
        if cls is not None:
            return cls
    return None


@metrics_mod.in_build_scope("plans")
def get_plan(basis, scale, library=None):
    """Build a transform plan. Callers go through Basis.transform_plan
    (@CachedMethod), so plans — and the host matrices they own, which the
    device-constant registry interns by object identity — are built once
    per (basis, scale, library)."""
    lib = library or basis.library
    cls = _registered(basis, lib)
    if cls is None:
        raise KeyError(
            f"No transform plan registered for {(type(basis).__name__, lib)}")
    plan = cls(basis, scale)
    # single choke point for transform trace annotation: every plan built
    # through the registry gets phase-labeled forward/backward methods,
    # which inside a fleet program take the fleet form
    fleet = _registered(basis, "matrix") is not None
    for method, tag in (("forward", "fwd"), ("backward", "bwd")):
        own = _scoped(getattr(plan, method), f"dedalus/transform/"
                      f"{type(basis).__name__}.{cls.library}.{tag}")
        setattr(plan, method, _fleet_switch(own, basis, scale, tag, fleet))
    return plan


# ------------------------------------------------- the fleet's transforms

_COUNTING = threading.local()


class counting_fleet_transforms:
    """Trace-time context: the transform calls vmapped over a fleet's
    members while it is open, as {(form, basis class): calls} in `counts`;
    form "matrix" (`_contract_axis`) or "fft" (the plan's own path, vmapped:
    a fleet under `fleet_contraction`'s member count, or a basis with no
    'matrix' plan)."""

    def __enter__(self):
        self.prev = getattr(_COUNTING, "counts", None)
        self.counts = _COUNTING.counts = collections.Counter()
        return self

    def __exit__(self, *exc):
        _COUNTING.counts = self.prev

    def record(self):
        """{"matrix": n, "fft": n, "by_basis": {class: {"matrix": n,
        "fft": n}}}: the `fleet_transforms` entry of
        `BuildPhases.record()`."""
        out = {"matrix": 0, "fft": 0, "by_basis": {}}
        for (form, name), n in sorted(self.counts.items()):
            out[form] += n
            out["by_basis"].setdefault(name, {"matrix": 0, "fft": 0})
            out["by_basis"][name][form] += n
        return out


def _contract_axis(matrix, data, axis):
    """`matrix` (m_out, m_in) applied along `axis` of `data` as ONE
    contraction over that axis at 'highest' precision, the result's axis
    where `data`'s was. Not `apply_matrix_jax`: its moveaxis of the operand
    is a layout copy of a fleet's whole grid on a TPU, where a dot over the
    axis in place leaves the layouts to the compiler (a v5e compile of RB
    256x64 at 1,024 members: no copy under the RealFourier scopes)."""
    out = jnp.tensordot(data, match_precision(matrix, data.dtype),
                        axes=(axis, 1), precision=jax.lax.Precision.HIGHEST)
    return jnp.moveaxis(out, -1, axis)


def _fleet_switch(own, basis, scale, tag, fleet):
    """A plan's `forward` / `backward` (`tag` fwd / bwd): `own` outside a
    fleet program. Inside one (`matsolvers.member_fleet`), vmapped over
    the members, it takes the rule of the fleet's pencil products
    (`matsolvers.fleet_contraction`): where the basis has a 'matrix' plan
    (`fleet`) and the members are enough, that plan's matrix for the same
    scale, contracted over the transformed axis for every member at once
    under `dedalus/transform/<Basis>.fleet_matrix.<tag>`; else `own`
    vmapped, the program and rounding of a member's solo run (a served
    micro-batch's members are bit-identical to direct solves)."""
    name = type(basis).__name__
    label = f"dedalus/transform/{name}.fleet_matrix.{tag}"

    def method(data, axis):
        if not in_member_fleet():
            return own(data, axis)

        @jax.custom_batching.custom_vmap
        def transform(a):
            return own(a, axis)

        @transform.def_vmap
        def _vmapped(members, in_batched, a):
            (batched,) = in_batched
            if not batched:
                return own(a, axis), False
            matrix = fleet and fleet_contraction(members,
                                                 jax.default_backend())
            counts = getattr(_COUNTING, "counts", None)
            if counts is not None:
                counts["matrix" if matrix else "fft", name] += 1
            if not matrix:
                return jax.vmap(lambda m: own(m, axis))(a), True
            twin = basis.transform_plan(scale, library="matrix")
            with jax.named_scope(label):
                return _contract_axis(
                    twin.forward_mat if tag == "fwd" else twin.backward_mat,
                    a, axis + 1), True

        return transform(data)

    method.__name__ = getattr(own, "__name__", tag)
    return method


class TransformPlan:
    """Base transform plan for one axis at one grid scale."""

    def __init__(self, basis, scale):
        self.basis = basis
        self.scale = scale
        self.N = basis.size
        self.Ng = basis.grid_size(scale)


class MatrixTransform(TransformPlan):
    """Generic MMT plan: subclasses provide forward/backward matrices."""

    def __init__(self, basis, scale):
        super().__init__(basis, scale)
        self.forward_mat = self.build_forward(basis, scale)    # (N, Ng)
        self.backward_mat = self.build_backward(basis, scale)  # (Ng, N)

    def forward(self, gdata, axis):
        return apply_matrix_jax(self.forward_mat, gdata, axis)

    def backward(self, cdata, axis):
        return apply_matrix_jax(self.backward_mat, cdata, axis)


@register_transform("Jacobi", "matrix")
class JacobiMMT(MatrixTransform):
    """
    Jacobi MMT (reference: core/transforms.py:115 JacobiMMT).

    Grid is always the (a0, b0) Gauss grid of the basis family; forward
    projects onto (a0, b0) then applies the ultraspherical-style conversion
    to the basis's derivative level (a, b) = (a0+k, b0+k).
    """

    @staticmethod
    def build_forward(basis, scale):
        from ..tools import jacobi
        Ng = basis.grid_size(scale)
        F = jacobi.forward_matrix(basis.size, basis.a0, basis.b0, Ng)
        if basis.k > 0:
            C = jacobi.conversion_matrix(basis.size, basis.a0, basis.b0, basis.k, basis.k)
            F = C @ F
        return F

    @staticmethod
    def build_backward(basis, scale):
        from ..tools import jacobi
        Ng = basis.grid_size(scale)
        x = jacobi.build_grid(Ng, basis.a0, basis.b0)
        return jacobi.build_polynomials(basis.size, basis.a, basis.b, x).T


def _dct2(x, orig_axis=None):
    """
    Unnormalized DCT-II along the last axis with explicit dtype control:
    y_n = 2 sum_j x_j cos(pi n (2j+1) / (2N)), via Makhoul's single
    length-N FFT of the even/odd reordering. jax.scipy.fft.dct is avoided
    because its internal padding promotes f32 inputs to f64 under x64,
    and TPU backends have no f64 FFT kernels.
    """
    if jnp.iscomplexobj(x):
        # Makhoul's Re() identity only holds for real input: transform the
        # real and imaginary parts separately
        return _dct2(x.real, orig_axis) + 1j * _dct2(x.imag, orig_axis)
    N = x.shape[-1]
    cdt = jnp.complex64 if x.dtype == jnp.float32 else jnp.complex128
    v = jnp.concatenate([x[..., 0::2], x[..., 1::2][..., ::-1]], axis=-1)
    V = meshctx.local_fft(lambda a: jnp.fft.fft(a, axis=-1), v.astype(cdt),
                          orig_axis)
    n = np.arange(N)
    phase = jnp.asarray(np.exp(-1j * np.pi * n / (2 * N)), dtype=cdt)
    return 2.0 * (phase * V).real.astype(x.dtype)


def _idct2(y, orig_axis=None):
    """
    Inverse of _dct2 (up to the factor 2N): x_j such that
    _dct2(x) = y; equivalently a DCT-III evaluation
    x_j = y_0/(2N) + (1/N) sum_{n>=1} y_n cos(pi n (2j+1)/(2N)).
    """
    if jnp.iscomplexobj(y):
        return _idct2(y.real, orig_axis) + 1j * _idct2(y.imag, orig_axis)
    N = y.shape[-1]
    cdt = jnp.complex64 if y.dtype == jnp.float32 else jnp.complex128
    n = np.arange(N)
    phase = jnp.asarray(np.exp(1j * np.pi * n / (2 * N)) / 2, dtype=cdt)
    yrev = jnp.concatenate([jnp.zeros_like(y[..., :1]), y[..., 1:][..., ::-1]],
                           axis=-1)
    W = phase * (y.astype(cdt) - 1j * yrev.astype(cdt))
    v = meshctx.local_fft(lambda a: jnp.fft.ifft(a, axis=-1), W,
                          orig_axis).real.astype(y.dtype)
    half = (N + 1) // 2
    x = jnp.zeros_like(v)
    x = x.at[..., 0::2].set(v[..., :half])
    x = x.at[..., 1::2].set(v[..., half:][..., ::-1])
    return x


@register_transform("Jacobi", "fft")
class FastChebyshevTransform(TransformPlan):
    """
    O(N log N) Chebyshev transform via DCT with ultraspherical conversion
    (reference: core/transforms.py:801-890 FastChebyshevTransform).

    Applies to the Chebyshev grid family (a0 = b0 = -1/2):
      forward : flip grid -> DCT-II -> classical->orthonormal rescale ->
                truncate -> banded conversion to level k (vectorized
                diagonal shifts, offsets 0, 2, .., 2k)
      backward: inverse conversion k -> 0 solved level-by-level; each
                2-diagonal upper-triangular level telescopes into a
                strided reversed CUMSUM (no sequential scan on device) ->
                rescale -> zero-pad -> DCT-III -> flip.
    The cumsum chain weights are prefix products of the conversion
    diagonal ratios, checked at build time for overflow; non-Chebyshev
    families (no DCT grid) and unstable chains fall back to the MMT,
    which is itself MXU-native.
    """

    def __init__(self, basis, scale):
        super().__init__(basis, scale)
        self.cheb = (basis.a0 == -0.5 and basis.b0 == -0.5)
        self._mmt = None
        # no DCT grid for non-Chebyshev families; coarse scales (Ng < N)
        # need the rectangular MMT
        if not self.cheb or self.Ng < self.N:
            self._mmt = JacobiMMT(basis, scale)
            return
        from ..tools import jacobi as jt
        N, Ng, k = self.N, self.Ng, basis.k
        self.k = k
        # orthonormal P_n = r_n * cos(n theta): r_0 = 1/sqrt(pi), else sqrt(2/pi)
        r = np.full(N, np.sqrt(2.0 / np.pi))
        r[0] = 1.0 / np.sqrt(np.pi)
        self.rescale = r
        # per-level conversion diagonals (a0+l, b0+l) -> (a0+l+1, b0+l+1)
        self.levels = []
        stable = True
        for l in range(k):
            C = np.asarray(jt.conversion_matrix(N, basis.a0 + l, basis.b0 + l, 1, 1))
            d0 = np.diagonal(C).copy()
            d2 = np.zeros(N)
            d2[:N - 2] = np.diagonal(C, 2)
            # chain prefix products H_n (parity-strided) for the cumsum
            # inverse: u_n = (1/H_n) * revcumsum_parity(H * v/d0), with
            # H_{n+2} = H_n * (-d2_n / d0_n)
            rho = -d2 / d0
            H = np.ones(N)
            for n in range(2, N):
                H[n] = H[n - 2] * rho[n - 2]
            if not np.all(np.isfinite(H)) or np.abs(H).max() > 1e280 or \
                    np.abs(H[H != 0]).min() < 1e-280:
                stable = False
            self.levels.append((d0, d2, H))
        if not stable:
            self._mmt = JacobiMMT(basis, scale)

    @staticmethod
    def _revcumsum_parity(x):
        """Reversed cumulative sum along the last axis within each parity
        chain (stride-2): out[n] = sum_{m >= n, m = n mod 2} x[m]."""
        n = x.shape[-1]
        if n % 2:
            x = zeropad(x, [(0, 0)] * (x.ndim - 1) + [(0, 1)])
        pairs = x.reshape(x.shape[:-1] + (-1, 2))
        acc = jnp.cumsum(pairs[..., ::-1, :], axis=-2)[..., ::-1, :]
        return acc.reshape(x.shape[:-1] + (-1,))[..., :n]

    def forward(self, gdata, axis):
        if self._mmt is not None:
            return self._mmt.forward(gdata, axis)
        N, Ng = self.N, self.Ng
        data = jnp.moveaxis(gdata, axis, -1)[..., ::-1]
        dt = data.dtype
        y = _dct2(data, axis)                          # y_n = 2 sum g cos(n th)
        chat = y / Ng
        chat = chat.at[..., 0].divide(2.0)
        # constants cast to the data dtype: f32 data must not promote to
        # f64 (TPU backends have no f64 FFT kernels)
        u = chat[..., :N] / jnp.asarray(self.rescale, dtype=dt)
        for d0, d2, H in self.levels:
            v = jnp.asarray(d0, dtype=dt) * u
            v = v.at[..., :N - 2].add(jnp.asarray(d2[:N - 2], dtype=dt)
                                      * u[..., 2:])
            u = v
        return jnp.moveaxis(u, -1, axis)

    def backward(self, cdata, axis):
        if self._mmt is not None:
            return self._mmt.backward(cdata, axis)
        N, Ng = self.N, self.Ng
        u = jnp.moveaxis(cdata, axis, -1)
        dt = u.dtype
        for d0, d2, H in reversed(self.levels):
            Hj = jnp.asarray(H, dtype=dt)
            u = self._revcumsum_parity(Hj * u / jnp.asarray(d0, dtype=dt)) / Hj
        chat = u * jnp.asarray(self.rescale, dtype=dt)
        chat = zeropad(chat, [(0, 0)] * (chat.ndim - 1) + [(0, Ng - N)])
        # _idct2(y)_j = y_0/(2Ng) + (1/Ng) sum_n y_n cos(n th_j)
        chat = chat.at[..., 0].multiply(2.0)
        g = _idct2(chat * Ng, axis)
        return jnp.moveaxis(g[..., ::-1], -1, axis)


@register_transform("RealFourier", "matrix")
class RealFourierMMT(MatrixTransform):
    """
    Real Fourier MMT oracle (reference: core/transforms.py:388 RealFourierMMT).

    Coefficient layout matches the reference's interleaved (cos, -sin) pairs:
    c[2g] = cos-amplitude, c[2g+1] = minus-sin-amplitude of mode g
    (reference: core/basis.py:1108 RealFourier, group_shape=(2,)).
    """

    @staticmethod
    def build_forward(basis, scale):
        Ng = basis.grid_size(scale)
        N = basis.size
        theta = 2 * np.pi * np.arange(Ng) / Ng
        g = np.arange(N // 2)
        F = np.zeros((N, Ng))
        cosrows = np.cos(np.outer(g, theta)) * 2.0 / Ng
        cosrows[0] /= 2.0
        sinrows = -np.sin(np.outer(g, theta)) * 2.0 / Ng
        sinrows[0] *= 0.0  # -sin(0x) mode is invalid
        F[0::2] = cosrows
        F[1::2] = sinrows
        return F

    @staticmethod
    def build_backward(basis, scale):
        Ng = basis.grid_size(scale)
        N = basis.size
        theta = 2 * np.pi * np.arange(Ng) / Ng
        g = np.arange(N // 2)
        B = np.zeros((Ng, N))
        B[:, 0::2] = np.cos(np.outer(theta, g))
        B[:, 1::2] = -np.sin(np.outer(theta, g))
        B[:, 1] = 0.0
        return B


@register_transform("RealFourier", "fft")
class RealFourierFFT(TransformPlan):
    """
    Real Fourier fast path via jnp.fft.rfft/irfft
    (reference: core/transforms.py:513 ScipyRealFFT / :538 FFTWRealFFT).
    """

    def forward(self, gdata, axis):
        N, Ng = self.N, self.Ng
        data = jnp.moveaxis(gdata, axis, -1)
        F = meshctx.local_fft(lambda a: jnp.fft.rfft(a, axis=-1), data,
                              axis) / Ng
        K = N // 2
        F = F[..., :K]
        cos = 2.0 * F.real
        cos = cos.at[..., 0].divide(2.0)
        msin = 2.0 * F.imag
        msin = msin.at[..., 0].set(0.0)
        out = jnp.stack([cos, msin], axis=-1).reshape(data.shape[:-1] + (N,))
        return jnp.moveaxis(out, -1, axis)

    def backward(self, cdata, axis):
        N, Ng = self.N, self.Ng
        data = jnp.moveaxis(cdata, axis, -1)
        K = N // 2
        pairs = data.reshape(data.shape[:-1] + (K, 2))
        cos = pairs[..., 0]
        msin = pairs[..., 1].at[..., 0].set(0.0)
        F = (cos + 1j * msin) / 2.0
        F = F.at[..., 0].multiply(2.0)
        # pad spectrum to the grid's rfft length
        pad = Ng // 2 + 1 - K
        F = jnp.concatenate([F, jnp.zeros(F.shape[:-1] + (pad,), dtype=F.dtype)], axis=-1)
        out = meshctx.local_fft(
            lambda a: jnp.fft.irfft(a, n=Ng, axis=-1), F * Ng, axis)
        return jnp.moveaxis(out, -1, axis)


@register_transform("ComplexFourier", "matrix")
class ComplexFourierMMT(MatrixTransform):
    """
    Complex Fourier MMT oracle (reference: core/transforms.py:212).
    Coefficients ordered by FFT wavenumber layout [0..K, (nyquist), -K..-1];
    the Nyquist slot is invalid and masked to zero.
    """

    @staticmethod
    def _wavenumbers(N):
        return np.fft.fftfreq(N, d=1.0 / N).astype(int)

    @staticmethod
    def build_forward(basis, scale):
        Ng = basis.grid_size(scale)
        N = basis.size
        theta = 2 * np.pi * np.arange(Ng) / Ng
        k = ComplexFourierMMT._wavenumbers(N)
        F = np.exp(-1j * np.outer(k, theta)) / Ng
        F[N // 2] = 0.0  # Nyquist mode invalid
        return F

    @staticmethod
    def build_backward(basis, scale):
        Ng = basis.grid_size(scale)
        N = basis.size
        theta = 2 * np.pi * np.arange(Ng) / Ng
        k = ComplexFourierMMT._wavenumbers(N)
        B = np.exp(1j * np.outer(theta, k))
        B[:, N // 2] = 0.0
        return B


@register_transform("ComplexFourier", "fft")
class ComplexFourierFFT(TransformPlan):
    """Complex Fourier fast path via jnp.fft (reference: core/transforms.py:271)."""

    def forward(self, gdata, axis):
        N, Ng = self.N, self.Ng
        data = jnp.moveaxis(gdata, axis, -1)
        F = meshctx.local_fft(lambda a: jnp.fft.fft(a, axis=-1), data,
                              axis) / Ng
        K = N // 2
        # keep modes [0..K-1] and [-K..-1], zero the Nyquist slot
        out = jnp.concatenate([F[..., :K],
                               jnp.zeros(F.shape[:-1] + (1,), F.dtype),
                               F[..., Ng - K + 1:]], axis=-1)
        return jnp.moveaxis(out, -1, axis)

    def backward(self, cdata, axis):
        N, Ng = self.N, self.Ng
        data = jnp.moveaxis(cdata, axis, -1)
        K = N // 2
        pos = data[..., :K]
        neg = data[..., K + 1:]
        mid = jnp.zeros(data.shape[:-1] + (Ng - N + 1,), data.dtype)
        F = jnp.concatenate([pos, mid, neg], axis=-1)
        out = meshctx.local_fft(
            lambda a: jnp.fft.ifft(a, axis=-1), F * Ng, axis)
        return jnp.moveaxis(out, -1, axis)

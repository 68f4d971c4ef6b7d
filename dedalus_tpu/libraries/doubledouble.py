"""
Double-double (f32 x 2) arithmetic for emulated float64 on TPU.

The reference framework is float64/complex128 end-to-end (reference:
dedalus/tools/config.py dtype defaults; SURVEY.md §7 hard part 7). TPU
hardware has no f64 matrix unit — XLA:TPU emulates f64 on the scalar/
vector path at a large slowdown, and the MXU only speaks bf16/int8 — so
`dtype=np.float64` problems route their pencil compute through this
module: values travel as unevaluated sums hi + lo of two float32s
(~49 mantissa bits), elementwise operations evaluate in (emulated) f64
VALUE space, and matrix products run on the MXU via an Ozaki-style int8
slice decomposition with exact int32 accumulation.

Representation: a `DD` pytree holding (hi, lo) f32 arrays with
|lo| <= ulp(hi)/2. All functions are pure jnp and safe under jit/vmap/scan.

Design note — why value-space f64 instead of error-free transformations:
the classical EFT formulations (Knuth two-sum, Dekker split/product) are
algebraically-exact cancellation patterns, and this XLA backend breaks
them under jit: optimization barriers are stripped, producers are
rematerialized into consumer fusions with different contraction, and
mixed f32/f64 convert chains are excess-precision-folded — each of which
silently zeroes the captured rounding term (observed: a hard 3.7e-8
error floor on scalar-operand dd_mul, identical across three EFT
variants). Computing each elementwise op as

    v = f64(a.hi) + f64(a.lo) (exact)  ->  op in f64  ->  split back
    hi = f32(v), lo = f32(v - f64(hi))

has no fragile cancellation: one f64 rounding per op (2^-53, below the
pair's 2^-49 capacity) and the split is compiler-stable (verified under
jit against scalar, splat, and array operands). The pair format is kept
as the storage/interchange type because the matmul path needs it.

dd_matmul — C = A @ B in ~f64 precision: each operand is row/column
exponent-normalized and sliced into SLICES signed-7-bit int8 planes
(slice p carries bits [7p, 7p+7)); slice-pair products run as int8
dot_generals with int32 accumulation (exact for k <= 2^16), and the
int32 partial sums are recombined in f64 with per-level power-of-two
scales. MXU cost: SLICES*(SLICES+1)/2 int8 matmuls.

References (public literature): Dekker 1971; Hida, Li & Bailey 2001 (qd);
Ozaki et al. 2012 / Ootomo & Yokota 2022 (error-free matmul slicing on
low-precision units).
"""

import numpy as np
import jax
import jax.numpy as jnp

from ..tools.jitlift import discovering

__all__ = [
    "DD", "dd_from_f64", "dd_to_f64", "dd_zeros",
    "two_sum", "quick_two_sum", "two_prod",
    "dd_add", "dd_sub", "dd_neg", "dd_mul", "dd_scale", "dd_div",
    "dd_add_f32", "dd_mul_f32", "dd_abs_hi",
    "dd_matmul", "dd_slices_from_f64",
]

_F32 = jnp.float32
# this library IS the f64 emulation layer: the wide dtype is its subject,
# not a precision-funnel bypass
_F64 = jnp.float64  # dedalus-lint: disable=DTL004


@jax.tree_util.register_pytree_node_class
class DD:
    """Unevaluated f32 sum hi + lo (|lo| <= ulp(hi)/2 when normalized)."""

    __slots__ = ("hi", "lo")

    def __init__(self, hi, lo):
        self.hi = hi
        self.lo = lo

    @property
    def shape(self):
        return jnp.shape(self.hi)

    @property
    def ndim(self):
        return jnp.ndim(self.hi)

    def __getitem__(self, idx):
        return DD(self.hi[idx], self.lo[idx])

    def reshape(self, *shape):
        return DD(jnp.reshape(self.hi, shape), jnp.reshape(self.lo, shape))

    def tree_flatten(self):
        return (self.hi, self.lo), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    def __repr__(self):
        return f"DD(hi={self.hi!r}, lo={self.lo!r})"


# ------------------------------------------------------ value-space bridge

def _to64(a):
    """DD -> f64 value (exact: both components are f32)."""
    return jnp.asarray(a.hi, _F64) + jnp.asarray(a.lo, _F64)


def _from64(v):
    """f64 value -> normalized DD (exact two-term split)."""
    hi = v.astype(_F32)
    lo = (v - hi.astype(_F64)).astype(_F32)
    return DD(hi, lo)


def dd_split_host(x):
    """Host float64 numpy -> (hi, lo) f32 NUMPY pair (exact split). The
    single implementation of the split convention — device-array callers
    use dd_from_f64/_from64, which share it semantically."""
    x = np.asarray(x, dtype=np.float64)
    hi = x.astype(np.float32)
    lo = (x - hi.astype(np.float64)).astype(np.float32)
    return hi, lo


def dd_from_f64(x):
    """Host float64 numpy -> DD of f32 pairs (exact 2-term split)."""
    hi, lo = dd_split_host(x)
    return DD(jnp.asarray(hi), jnp.asarray(lo))


def dd_to_f64(a):
    """DD -> host float64 numpy (for verification / output)."""
    return (np.asarray(a.hi, dtype=np.float64)
            + np.asarray(a.lo, dtype=np.float64))


def dd_zeros(shape):
    z = jnp.zeros(shape, dtype=_F32)
    return DD(z, z)


# ------------------------------------------------------------ error-free ops
# Kept for compatibility/tests; implemented through the f64 bridge (the
# returned (s, e) pair represents a+b / a*b to f64 accuracy).

def two_sum(a, b):
    """a + b = s + e (s = f32 round, e = the f64-exact remainder)."""
    v = jnp.asarray(a, _F64) + jnp.asarray(b, _F64)
    s = v.astype(_F32)
    e = (v - s.astype(_F64)).astype(_F32)
    return s, e


quick_two_sum = two_sum


def two_prod(a, b):
    """a * b = p + e exactly (f32 products are exact in f64)."""
    v = jnp.asarray(a, _F64) * jnp.asarray(b, _F64)
    p = v.astype(_F32)
    e = (v - p.astype(_F64)).astype(_F32)
    return p, e


# --------------------------------------------------------------- dd algebra

def dd_add(a, b):
    return _from64(_to64(a) + _to64(b))


def dd_neg(a):
    return DD(-a.hi, -a.lo)


def dd_sub(a, b):
    return _from64(_to64(a) - _to64(b))


def dd_add_f32(a, b):
    """DD + f32 array/scalar."""
    return _from64(_to64(a) + jnp.asarray(b, _F64))


def dd_mul(a, b):
    """DD * DD."""
    return _from64(_to64(a) * _to64(b))


def dd_mul_f32(a, b):
    """DD * f32 array/scalar."""
    return _from64(_to64(a) * jnp.asarray(b, _F64))


def dd_scale(a, pow2):
    """DD * exact power of two (exact; no renormalization needed)."""
    return DD(a.hi * pow2, a.lo * pow2)


def dd_div(a, b):
    """DD / DD."""
    return _from64(_to64(a) / _to64(b))


def dd_abs_hi(a):
    return jnp.abs(a.hi)


# --------------------------------------------------- Ozaki int8 slice matmul

SLICE_BITS = 7          # signed slice width: values in [-64, 64]
DEFAULT_SLICES = 8      # 8 * 7 = 56 bits >= f64's 53


def _exact_pow2(n):
    """2^n as f32 for integer array n in [-126, 127], EXACTLY — via the
    exponent bit field. (jnp.exp2 is a polynomial approximation and is
    NOT exact even at integer arguments; an inexact scale here breaks
    the error-free slice decomposition.)"""
    n = jnp.clip(n, -126, 127)
    return jax.lax.bitcast_convert_type(
        ((n + 127) << 23).astype(jnp.int32), jnp.float32)


def _exponent_scale(mag):
    """For f64 mag = max |value| along the contraction axis: returns an
    exact power-of-two f64 s with s * mag <= 1/2 (1 where mag == 0).
    Lines whose magnitude exceeds the f32-representable scale range
    (|v| >= 2^125, where the needed s would clip) poison to NaN so a
    blown-up state reads as non-finite instead of int8-wrapped garbage."""
    # exponent read from the f32 rounding of mag: frexp of an f64 is a
    # 64-bit bitcast, which the TPU's x64 rewriter does not implement.
    # Round-to-nearest keeps mag <= 2^e (a mag that rounds up to 2^e reads
    # one exponent higher — one slice bit lost, the scale still exact)
    _, e = jnp.frexp(mag.astype(_F32))
    s = _exact_pow2(-(e + 1)).astype(_F64)
    s = jnp.where(mag >= 2.0 ** 125, jnp.float64(np.nan), s)  # dedalus-lint: disable=DTL004
    return jnp.where(mag > 0, s, jnp.float64(1.0))  # dedalus-lint: disable=DTL004


def _dd_slices(x, axis, slices):
    """Exponent-normalize DD `x` along `axis` and slice into int8 planes.

    Returns (planes, inv_scale): planes int8 (slices,) + x.shape with
    plane p holding rint(R_p * 2^(7(p+1))) for the running remainder R,
    and inv_scale f32 per-line factor such that
        value = inv_scale * sum_p planes[p] * 2^-(7(p+1)).
    The extraction runs in f64 value space (exact: power-of-two scales,
    integer-valued subtractions; |R_p| <= 2^-(7p+1))."""
    v = _to64(x)
    mag = jnp.max(jnp.abs(v), axis=axis, keepdims=True)
    s = _exponent_scale(mag)
    r = v * s                                # exact pow2 scale, |r| <= 1/2
    planes = []
    for p in range(slices):
        sc = np.float64(2.0 ** (SLICE_BITS * (p + 1)))
        q = jnp.rint(r * sc)                 # |q| <= 64
        # ... where rint is exact. A TPU's f64 is a float32 pair and its
        # rint of a value of this size is now and then a whole unit off
        # (v5e, PR 35): the remainder is then a unit of this plane, the
        # NEXT q is 128 to 192, and the int8 plane saturates at 127 while
        # the remainder goes on as if it held the rest — that line keeps
        # only the planes above (errors of 1e-11 to 1e-4 of the line).
        # Rounded once more on what is left, where the value is of order
        # one and rint has nothing to get wrong; adds 0 where it was right
        q = q + jnp.rint((r - q / sc) * sc)  # |q| <= 65
        planes.append(q.astype(jnp.int8))
        r = r - q / sc                       # exact
    planes = jnp.stack(planes)
    return planes, (1.0 / s).astype(_F32)


def dd_slices_from_f64(M, slices=DEFAULT_SLICES, axis=-1):
    """HOST-side exact slice decomposition of a float64 numpy matrix for
    reuse across many dd_matmul calls (e.g. cached transform matrices).

    Returns (planes int8 (slices,)+M.shape, inv_scale f32 per-line).
    Normalization is along `axis` (the contraction axis of the intended
    product)."""
    M = np.asarray(M, dtype=np.float64)
    mag = np.max(np.abs(M), axis=axis, keepdims=True)
    with np.errstate(divide="ignore"):
        e = np.ceil(np.log2(mag, where=mag > 0,
                            out=np.zeros_like(mag))) + 1
    s = np.where(mag > 0, 2.0 ** -e, 1.0)
    # ensure s*mag <= 1/2 despite log2 edge cases (mag an exact pow2)
    bad = s * mag > 0.5
    s = np.where(bad, s / 2, s)
    r = M * s
    planes = np.empty((slices,) + M.shape, dtype=np.int8)
    for p in range(slices):
        sc = 2.0 ** (SLICE_BITS * (p + 1))
        q = np.rint(r * sc)
        planes[p] = q.astype(np.int8)
        r = r - q / sc
    return planes, (1.0 / s).astype(np.float32)


plane_dots_traced = 0   # int8 plane products traced in this process


def _plane_dot(ap, bp, dims):
    global plane_dots_traced
    # a lifted program's discovery pass traces everything a second time
    if not discovering():
        plane_dots_traced += 1
    return jax.lax.dot_general(ap, bp, dims,
                               preferred_element_type=jnp.int32)


def dd_matmul(A, B, slices=DEFAULT_SLICES, b_planes=None, a_planes=None):
    """C = A @ B in ~f64 precision. A: DD (..., m, k), B: DD (..., k, n)
    — 2-D or batched 3-D with matching leading dims.

    Either operand may be pre-sliced (pass (planes, inv_scale) from
    `dd_slices_from_f64` via a_planes/b_planes; planes must already be
    device arrays or lifted constants). Exactness budget: int32
    accumulation is exact for k <= 2^16 with 7-bit slices; levels
    p+q >= `slices` are dropped (below 2^-(7*slices) relative).
    """
    nd = A.ndim if a_planes is None else a_planes[0].ndim - 1
    if a_planes is None:
        ap, a_inv = _dd_slices(A, axis=-1, slices=slices)
    else:
        ap, a_inv = a_planes
    if b_planes is None:
        bp, b_inv = _dd_slices(B, axis=-2, slices=slices)
    else:
        bp, b_inv = b_planes
    batch = tuple(range(nd - 2))
    # contraction over k: A (..., m, k) x B (..., k, n); planes prepend a
    # slice axis which we index in python (static small loop)
    dims = (((nd - 1,), (nd - 2,)), (batch, batch))
    # sum int32 plane products per level (exact), recombine in f64 from
    # the lowest-order level up so small terms are absorbed first
    level_terms = {}
    for p in range(slices):
        for q in range(slices - p):
            d = _plane_dot(ap[p], bp[q], dims)
            level_terms.setdefault(p + q, []).append(d)
    C = None
    for lev in sorted(level_terms, reverse=True):
        tot = level_terms[lev][0]
        for extra in level_terms[lev][1:]:
            tot = tot + extra              # int32 adds: exact
        term = tot.astype(_F64) * np.float64(2.0 ** (-SLICE_BITS * (lev + 2)))
        C = term if C is None else C + term
    # undo the per-line normalizations: rows of A (axis -2 of C), cols of B
    a_inv_c = jnp.squeeze(jnp.asarray(a_inv, _F64), axis=-1)[..., :, None]
    b_inv_c = jnp.squeeze(jnp.asarray(b_inv, _F64), axis=-2)[..., None, :]
    return _from64(C * a_inv_c * b_inv_c)

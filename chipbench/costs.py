"""
Operations and bytes from shapes: the numerators of the roofline metrics.
Computed, not measured; what the algorithm has to do, not what a kernel
happens to do (a kernel that reads a matrix twice still needs it once).
"""

import math


def dense_matvec(G, S, itemsize):
    """Batched dense (G, S, S) @ (G, S): the pencil matvec, and the pencil
    solve where the factor is a stored inverse (DenseOps/BatchedInverse).
    Reads the matrix and the vector once, writes the result once."""
    return {"flops": 2 * G * S * S,
            "bytes": G * (S * S + 2 * S) * itemsize}


def rk_dense_step(G, S, stages, itemsize):
    """One IMEX Runge-Kutta step on dense pencils as
    core/timesteppers.RungeKuttaIMEX.step_body does it: M @ X0 once, then
    per stage L @ Xi and one solve with the stored inverse."""
    one = dense_matvec(G, S, itemsize)
    calls = 1 + 2 * stages
    return {"flops": calls * one["flops"], "bytes": calls * one["bytes"],
            "matrix_reads": calls}


def real_fft(n, batch, itemsize):
    """`batch` real-to-complex (or complex-to-real) FFTs of length n:
    2.5 n log2 n flops each (half a complex FFT's 5 n log2 n); reads n
    reals, writes n/2+1 complex numbers (or the reverse)."""
    return {"flops": 2.5 * n * math.log2(n) * batch,
            "bytes": (n + 2 * (n // 2 + 1)) * batch * itemsize}


def dct_fft(n, batch, itemsize):
    """`batch` DCTs of length n through one real FFT of the same length
    plus a twiddle pass (the 'fft' transform library): n reals in, n out."""
    return {"flops": (2.5 * n * math.log2(n) + 4 * n) * batch,
            "bytes": 2 * n * batch * itemsize}


def matrix_transform(n_grid, n_coeff, batch, itemsize):
    """`batch` transforms as a dense (n_grid, n_coeff) matrix product (the
    'matrix' library and the fused composites): the matrix is read once."""
    return {"flops": 2 * n_grid * n_coeff * batch,
            "bytes": (n_grid * n_coeff
                      + (n_grid + n_coeff) * batch) * itemsize}


def least_seconds(cost, peaks, flops_key="bf16_flops_per_s"):
    """The roofline bound and which side sets it."""
    by_flops = cost["flops"] / peaks[flops_key]
    by_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), \
        ("bandwidth" if by_bytes >= by_flops else "compute")

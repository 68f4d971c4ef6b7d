"""
Dedalus-TPU: a TPU-native spectral PDE framework.

A from-scratch JAX/XLA re-design of the capabilities of Dedalus v3
(reference: kburns/dedalus, surveyed in SURVEY.md): global spectral methods
for PDEs on Cartesian and curvilinear domains, symbolic vector equations,
IMEX initial value problems, boundary/eigenvalue problems — with the hot
path (transforms, pencil solves, distributed transposes) compiled by XLA
onto TPU (MXU matmuls, fused elementwise, mesh collectives) instead of
FFTW/MPI/SuperLU.

Architecture notes:
  * Symbolic problem layer runs on host (numpy/scipy), like the reference's
    (reference: dedalus/core/problems.py, operators.py).
  * The IVP step is ONE jitted function: spectral<->grid transforms,
    pointwise nonlinearities, and a batched dense/banded LU solve over all
    pencils (pencil index = batch dimension on the MXU).
  * Distribution uses jax.sharding.Mesh + named shardings; the reference's
    MPI Alltoallv pencil transposes (dedalus/core/transposes.pyx) become
    XLA-inserted all-to-alls.
"""

__version__ = "0.1.0"

# Double precision is the house dtype of spectral methods (the reference is
# float64/complex128 end-to-end). Enable x64 before any jax import users run.
import jax

jax.config.update("jax_enable_x64", True)
# A TPU multiplies f32 matrices in ONE bf16 pass unless told otherwise:
# measured on the v5e (PR 22), the default left RB 256x64's boundary
# conditions at 4e-3 and the banded substitution NaN by step 10. Spectral
# transforms and pencil solves are matmuls whose errors the condition
# number amplifies, so working precision means working precision. (Explicit
# low-precision operands — [precision] SOLVE_DTYPE, [fusion] MMT_DTYPE —
# are cast by hand and are not affected.)
jax.config.update("jax_default_matmul_precision", "highest")

from .tools.logging import setup_logging

setup_logging()


def _setup_compilation_cache():
    """Place the persistent XLA compilation cache (config [compilation]).

    Compiled step/factor programs are reused across runs and processes,
    cutting time-to-first-step on warm builds. Where the environment sets
    JAX_COMPILATION_CACHE_DIR, JAX reads it itself and the package sets no
    directory at all; otherwise `[compilation] CACHE_DIR` names it (a
    relative path is taken from the checkout root — the path is part of
    the cache key, so it must not move between processes)."""
    import os
    import pathlib
    from .tools.config import config
    section = config["compilation"]
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      section.getfloat("CACHE_MIN_COMPILE_SECS",
                                       fallback=1.0))
    # cache regardless of entry size (large factor programs are the
    # expensive ones)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    cache_dir = section.get("CACHE_DIR", "").strip()
    if not cache_dir:
        return
    root = pathlib.Path(__file__).resolve().parent.parent
    jax.config.update("jax_compilation_cache_dir",
                      str(root / pathlib.Path(cache_dir).expanduser()))


_setup_compilation_cache()

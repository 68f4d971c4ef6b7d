"""
Persistent assembly-cache tests (tools/assembly_cache.py): hit/miss/
invalidation semantics of the content-addressed key, corruption fallback,
cross-process reuse, and the bit-identical cached-vs-fresh guarantee on
both a Cartesian (RB) and a curvilinear (annulus, m-coupled NCC) problem.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import dedalus_tpu.public as d3
from dedalus_tpu.tools import assembly_cache
from dedalus_tpu.tools.config import config


@pytest.fixture()
def cache_dir(tmp_path, monkeypatch):
    d = tmp_path / "assembly"
    monkeypatch.setenv("DEDALUS_TPU_ASSEMBLY_CACHE", str(d))
    return d


def build_rb(Nx=32, Nz=8, dtype=np.float64, kappa=1.0, matsolver=None):
    from dedalus_tpu.extras.bench_problems import build_rb_solver
    old = config["linear algebra"].get("MATRIX_SOLVER", "auto")
    if matsolver is not None:
        config["linear algebra"]["MATRIX_SOLVER"] = matsolver
    try:
        if kappa == 1.0:
            solver, b = build_rb_solver(Nx, Nz, dtype)
            return solver
        # variant problem: same SHAPE, different diffusivity scalar — the
        # equation STRING is identical, only the baked coefficient differs
        coords = d3.CartesianCoordinates("x", "z")
        dist = d3.Distributor(coords, dtype=dtype)
        xb = d3.RealFourier(coords["x"], size=Nx, bounds=(0, 4), dealias=3 / 2)
        zb = d3.ChebyshevT(coords["z"], size=Nz, bounds=(0, 1), dealias=3 / 2)
        u = dist.Field(name="u", bases=(xb, zb))
        problem = d3.IVP([u], namespace=locals())
        problem.add_equation("dt(u) - kappa*lap(u) = 0")
        return problem.build_solver(d3.RK222)
    finally:
        config["linear algebra"]["MATRIX_SOLVER"] = old


def mats_equal(a, b):
    if isinstance(a, dict):
        keys = set(a) | set(b)
        for k in keys - {"dsel"}:
            if not np.array_equal(np.asarray(a[k]), np.asarray(b[k])):
                return False
        return a.get("dsel") == b.get("dsel")
    return np.array_equal(a, b)


def test_miss_then_hit_bit_identical_rb(cache_dir):
    fresh = build_rb()
    assert fresh.build_phases.cache == "miss"
    assert list(cache_dir.glob("asm-*.npb"))
    cached = build_rb()
    assert cached.build_phases.cache == "hit"
    for name in ("M", "L"):
        assert mats_equal(fresh._matrices[name], cached._matrices[name])
    # the cached solver must actually run
    cached.step(1e-3)
    assert np.isfinite(np.asarray(cached.X)).all()


def test_banded_store_bit_identical(cache_dir):
    fresh = build_rb(64, 16, matsolver="banded")
    assert fresh.build_phases.cache == "miss"
    assert fresh.structure is not None
    cached = build_rb(64, 16, matsolver="banded")
    assert cached.build_phases.cache == "hit"
    assert cached.structure is not None
    for name in ("M", "L"):
        assert mats_equal(fresh._matrices[name], cached._matrices[name])
    for attr in ("row_perm", "col_perm", "pinned_positions"):
        assert np.array_equal(getattr(fresh.structure, attr),
                              getattr(cached.structure, attr))
    assert (fresh.structure.kl, fresh.structure.ku, fresh.structure.q) == \
        (cached.structure.kl, cached.structure.ku, cached.structure.q)
    cached.step(1e-3)
    assert np.isfinite(np.asarray(cached.X)).all()


def _annulus_lbvp(Nphi=8, Nr=6, eps=0.3):
    coords = d3.PolarCoordinates("phi", "r")
    dist = d3.Distributor(coords, dtype=np.float64)
    ann = d3.AnnulusBasis(coords, shape=(Nphi, Nr), dtype=np.float64,
                          radii=(0.7, 1.8), dealias=2)
    phi, r = dist.local_grids(ann)
    w = dist.Field(name="w", bases=ann)
    w["g"] = 1.0 + eps * np.cos(phi) * r
    u = dist.Field(name="u", bases=ann)
    tau1 = dist.Field(name="tau1", bases=ann.edge)
    tau2 = dist.Field(name="tau2", bases=ann.edge)
    lift_basis = ann.derivative_basis(2)
    lift = lambda A, n: d3.Lift(A, lift_basis, n)  # noqa: E731
    g = dist.Field(name="g", bases=ann)
    g["g"] = 1.0
    problem = d3.LBVP([u, tau1, tau2], namespace=locals())
    problem.add_equation("w*u - lap(u) + lift(tau1,-1) + lift(tau2,-2) = g")
    problem.add_equation("u(r=0.7) = 0")
    problem.add_equation("u(r=1.8) = 0")
    return problem.build_solver()


def test_curvilinear_hit_and_ncc_data_invalidation(cache_dir):
    fresh = _annulus_lbvp()
    assert fresh.build_phases.cache == "miss"
    cached = _annulus_lbvp()
    assert cached.build_phases.cache == "hit"
    assert mats_equal(fresh._matrices["L"], cached._matrices["L"])
    cached.solve()
    # identical equation TEXT but different NCC field data must MISS:
    # the data is baked into the matrices
    other = _annulus_lbvp(eps=0.4)
    assert other.build_phases.cache == "miss"
    assert not mats_equal(fresh._matrices["L"], other._matrices["L"])


def test_invalidation_axes(cache_dir):
    base = build_rb()
    assert base.build_phases.cache == "miss"
    # resolution
    assert build_rb(Nx=64).build_phases.cache == "miss"
    # dtype
    assert build_rb(dtype=np.float32).build_phases.cache == "miss"
    # equation coefficient (identical string, different baked scalar)
    assert build_rb(kappa=2.0).build_phases.cache == "miss"
    assert build_rb(kappa=2.0).build_phases.cache == "hit"
    # package version bump (scoped patch: monkeypatch.undo() would also
    # revert the cache_dir fixture's env var)
    import dedalus_tpu
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(dedalus_tpu, "__version__", "999.0-test")
        assert build_rb().build_phases.cache == "miss"
    # original problem still hits afterwards
    assert build_rb().build_phases.cache == "hit"


def test_corrupted_entry_falls_back_to_fresh(cache_dir):
    fresh = build_rb()
    assert fresh.build_phases.cache == "miss"
    entries = list(cache_dir.glob("asm-*.npb"))
    assert entries
    # torn write: truncate the entry mid-file
    data = entries[0].read_bytes()
    entries[0].write_bytes(data[:len(data) // 3])
    rebuilt = build_rb()
    # corruption is a clean miss (quarantined + fresh assembly + restore)
    assert rebuilt.build_phases.cache == "miss"
    for name in ("M", "L"):
        assert mats_equal(fresh._matrices[name], rebuilt._matrices[name])
    # garbage entry (valid zip magic absent entirely)
    entries = list(cache_dir.glob("asm-*.npb"))
    entries[0].write_bytes(b"not a cache bundle at all")
    again = build_rb()
    assert again.build_phases.cache == "miss"
    assert build_rb().build_phases.cache == "hit"


@pytest.mark.parametrize("max_mb, stored", [(1.0, True), (0.5, False)])
def test_entry_over_the_whole_budget_is_not_written(tmp_path, max_mb,
                                                    stored):
    """An entry larger than ASSEMBLY_CACHE_MAX_MB used to be written,
    synced and evicted at once (RB 2048x1024: 8.4 GB of disk writes per
    build); it is refused before the first byte."""
    cache = assembly_cache.AssemblyCache(tmp_path / "asm", max_mb=max_mb)
    arrays = {"bands": np.zeros(100_000)}              # 0.8 MB
    assert cache.store("k" * 40, {"kind": "banded"}, arrays) is stored
    files = list((tmp_path / "asm").glob("*")) \
        if (tmp_path / "asm").is_dir() else []
    assert bool(files) == stored
    assert (cache.load("k" * 40) is not None) == stored


def test_key_stability_and_resolve(cache_dir, monkeypatch):
    solver = build_rb()
    key1 = assembly_cache.solver_key(solver, ("M", "L"))
    key2 = assembly_cache.solver_key(solver, ("M", "L"))
    assert key1 == key2 and key1 is not None
    assert assembly_cache.solver_key(solver, ("L",)) != key1
    monkeypatch.setenv("DEDALUS_TPU_ASSEMBLY_CACHE", "")
    assert assembly_cache.resolve() is None


def test_cross_process_reuse(cache_dir):
    code = (
        "import numpy as np, json\n"
        "import dedalus_tpu.public\n"
        "from dedalus_tpu.extras.bench_problems import build_rb_solver\n"
        "solver, b = build_rb_solver(32, 8, np.float64)\n"
        "print(json.dumps(solver.build_phases.record()))\n"
    )
    env = dict(os.environ)
    env["DEDALUS_TPU_ASSEMBLY_CACHE"] = str(cache_dir)
    env.setdefault("JAX_PLATFORMS", "cpu")

    def run():
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             stdout=subprocess.PIPE, text=True, timeout=600)
        assert out.returncode == 0, out.stdout
        line = [ln for ln in out.stdout.splitlines()
                if ln.startswith("{")][-1]
        return json.loads(line)

    first = run()
    assert first["assembly_cache"] == "miss"
    second = run()
    assert second["assembly_cache"] == "hit"


def test_build_phases_in_telemetry(cache_dir):
    solver = build_rb()
    solver.step(1e-3)
    record = solver.flush_metrics()
    phases = record["build_phases"]
    for key in ("host_assembly_sec", "structure_sec", "factor_sec",
                "compile_sec"):
        assert key in phases
    assert phases["compile_sec"] > 0.0
    assert phases["assembly_cache"] in ("hit", "miss")


# ------------------------------------------- XLA compile cache placement
# (the sibling cache: [compilation] CACHE_DIR, dedalus_tpu/__init__.py)

def _xla_cache_dir(env_overrides, cwd):
    """jax.config.jax_compilation_cache_dir after `import dedalus_tpu` in
    a fresh process started from `cwd`."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(env_overrides)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo
    code = ("import dedalus_tpu, jax; "
            "print('DIR=' + str(jax.config.jax_compilation_cache_dir))")
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=str(cwd),
                         stdout=subprocess.PIPE, text=True, timeout=300)
    assert out.returncode == 0, out.stdout
    (line,) = [ln for ln in out.stdout.splitlines() if ln.startswith("DIR=")]
    return line[len("DIR="):]


def test_xla_cache_dir_from_environment_is_left_to_jax(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: JAX reads it itself and the package
    sets no directory in code, so the variable's value is what holds."""
    assert _xla_cache_dir({"JAX_COMPILATION_CACHE_DIR": "/x"},
                          tmp_path) == "/x"


def test_xla_cache_dir_default_is_one_fixed_path_in_the_checkout(tmp_path):
    """Unset: one fixed directory inside the checkout — never under ~, a
    temporary name, a pid or a time (the path is part of the cache key) —
    identical across two processes with different HOMEs and cwds."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    (tmp_path / "a").mkdir()
    (tmp_path / "b").mkdir()
    first = _xla_cache_dir({"HOME": str(tmp_path / "a")}, tmp_path / "a")
    second = _xla_cache_dir({"HOME": str(tmp_path / "b")}, tmp_path / "b")
    assert first == second == os.path.join(repo, ".cache", "xla")

"""
rb256x64-ens at its rehearsal size (RB 64 x 16, 32 members: a count that
takes the fleet product on this backend), through the configuration's own
file, chipbench/configs/rb256x64-ens.py: what the cell
`rb256x64-ens.block10` relies on, checked where it costs no chip time. The
builds ask for the stored inverse (`BatchedInverse`, what a TPU takes), so
that the fleet's solves are fleet products too.

  (a) the fleet's sampled members after ten fixed-dt steps agree with the
      configuration's plain float64 reference (one solver, no fleet) within
      the file's limit, and every invariant holds;
  (b) the comparison bites: two members' initial conditions swapped fail
      it; a fleet collapsed onto member 0 fails `members_distinct`; a
      poisoned member is dropped by the fleet's health probe and fails
      `members_dropped`;
  (c) the fleet is a solver a time loop can run: `sim_time`, `health_error`,
      `ops`, the `step` / `step_many` spans, the health probe under
      `health/check`, the `ensemble` and `factor` phases and the fleet
      programs' rows in its template's ledger.
"""

import contextlib
import pathlib

import numpy as np
import pytest

from dedalus_tpu.tools import tracing
from dedalus_tpu.tools.config import config

ROOT = pathlib.Path(__file__).resolve().parents[1]
SIZE = {"Nx": 64, "Nz": 16, "members": 32, "matsolver": "BatchedInverse"}
DT, STEPS = 0.005, 10


@pytest.fixture(scope="module")
def cfg():
    from chipbench.manifest import load_module
    return load_module(ROOT / "chipbench" / "configs" / "rb256x64-ens.py")


@contextlib.contextmanager
def plain_path():
    """chipbench/reference.py's settings, as its child sets them."""
    from chipbench.reference import PLAIN_PATH
    was = {(s, k): config[s].get(k) for s, keys in PLAIN_PATH.items()
           for k in keys}
    for section, keys in PLAIN_PATH.items():
        config[section].update(keys)
    try:
        yield
    finally:
        for (section, key), value in was.items():
            if value is None:
                config.remove_option(section, key)
            else:
                config[section][key] = value


def rel(a, b):
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def limit(cfg, name):
    return cfg.SPEC["tolerances"][name]["value"]


@pytest.fixture(scope="module")
def reference(cfg):
    with plain_path():
        dep = cfg.build(7, dtype=np.float64,
                        size=dict(SIZE, matsolver="dense"))
        assert not hasattr(dep.solver, "active_host")   # no fleet
        for _ in range(STEPS):
            dep.solver.step(DT)
        return dep.compared()


@pytest.fixture(scope="module")
def runs(cfg):
    """One fleet, read after ten steps, then re-run from its initial
    conditions with two members swapped, collapsed and poisoned."""
    dep = cfg.build(7, size=SIZE)
    fleet = dep.solver
    X0 = np.asarray(fleet.X)
    out = {"dep": dep, "members": dep.members}
    tracing.enable()
    try:
        fleet.step_many(STEPS, DT)
        out["spans"] = [s.name for s in tracing.recorder().spans()]
        out["fleet"] = dep.compared()
        out["sim_time"] = fleet.sim_time
        out["invariants"] = dep.invariants()
        with tracing.span("probe-test"):
            fleet.check_health()
        out["probe_spans"] = [(s.name, s.parent_id)
                              for s in tracing.recorder().spans()]
    finally:
        tracing.disable()
    out["record"] = fleet.build_phases.record()
    a, b = dep.members[1], dep.members[-1]
    swapped = X0.copy()
    swapped[[a, b]] = X0[[b, a]]
    fleet.set_states(swapped)
    fleet.step_many(STEPS, DT)
    out["swapped"] = dep.compared()
    # the member-level invariants alone: rb256x64's per-state checks are
    # (a)'s, and would only cost their evaluation again here
    dep.base.invariants = lambda: {"wall_bc": (0.0, 1.0),
                                   "continuity": (0.0, 1.0)}
    fleet.set_states(np.broadcast_to(X0[0], X0.shape))
    out["collapsed"] = dep.invariants()
    poisoned = X0.copy()
    victim = next(m for m in range(X0.shape[0]) if m not in dep.members)
    poisoned[victim] = np.nan
    fleet.set_states(poisoned)
    fleet.check_health()
    out["events"] = list(fleet.dropped)
    out["poisoned"] = dep.invariants()
    out["victim"] = victim
    return out


def test_the_sampled_members(runs):
    assert runs["members"] == [0, 1, 9, 10, 15, 16, 26, 31]


def test_fleet_against_the_plain_float64_reference(cfg, runs, reference):
    assert rel(runs["fleet"], reference) <= limit(cfg, "ref_rel_l2")


def test_invariants_hold(cfg, runs):
    for name, (value, bound) in runs["invariants"].items():
        assert value <= bound, (name, value, bound)
    assert runs["invariants"]["members_dropped"][0] == 0


def test_swapped_initial_conditions_fail(cfg, runs, reference):
    # two of the eight members' flows exchanged: sqrt(2 / 8) of sqrt(2)
    assert rel(runs["swapped"], reference) > 40 * limit(cfg, "ref_rel_l2")


def test_a_collapsed_fleet_fails_members_distinct(runs):
    value, bound = runs["collapsed"]["members_distinct"]
    assert value == 0.0 and value > bound
    healthy, _ = runs["invariants"]["members_distinct"]
    assert healthy < -1.0    # independent realisations: near -sqrt(2)


def test_a_poisoned_member_is_dropped_and_fails(runs):
    assert [e["member"] for e in runs["events"]] == [runs["victim"]]
    assert runs["events"][0]["outcome"] == "dropped"
    value, bound = runs["poisoned"]["members_dropped"]
    assert value == 1.0 and value > bound
    fleet = runs["dep"].solver
    assert fleet.health_error is None and fleet.n_active == 31


def test_the_fleet_answers_as_a_solver(runs):
    fleet = runs["dep"].solver
    assert runs["sim_time"] == pytest.approx(STEPS * DT)
    assert type(fleet.ops).__name__ == "DenseOps"
    assert fleet.pencil_shape == fleet.solver.pencil_shape
    assert fleet.pencil_dtype == fleet.solver.pencil_dtype
    fleet.sim_times[runs["members"][0]] += 1.0
    with pytest.raises(ValueError, match="disagree"):
        fleet.sim_time
    fleet.sim_times[runs["members"][0]] -= 1.0


def test_spans_and_the_ledger(runs):
    assert "step_many" in runs["spans"] and "step/factor" in runs["spans"]
    # the fleet's health probe is a `health/check` span of its own
    spans = runs["probe_spans"]
    assert ("health/check" in [name for name, _ in spans])
    record = runs["record"]
    assert record["ensemble_sec"] > 0 and record["factor_sec"] > 0
    labels = [r["label"] for r in record["programs"]["rows"]]
    assert "ensemble/fleet_step[10]" in labels


def test_the_fleet_transforms_are_matrix_products(runs):
    """`build_phases.record()["fleet_transforms"]`: every RealFourier and
    ChebyshevT (class Jacobi) call of the fleet's step program takes its
    basis's matrix (core/transforms.py), none the FFT; five of each a
    right-hand side, two of those a RK222 step."""
    assert runs["record"]["fleet_transforms"] == {
        "matrix": 20, "fft": 0,
        "by_basis": {"Jacobi": {"matrix": 10, "fft": 0},
                     "RealFourier": {"matrix": 10, "fft": 0}}}


def test_the_cells_readers(runs):
    """The three new per-layer readers: `ensemble_init_s` reads the
    template's `ensemble` phase; the fleet-product readers read the
    fleet scopes of a reduced trace, and the roofline share is the
    costs from shapes over the measured time, under 100%."""
    from chipbench.manifest import Manifest
    from chipbench import costs_fleet
    manifest = Manifest()
    read = manifest.layer_reader
    ctx = {"build_phases": runs["record"]}
    assert read("ensemble_init_s")(ctx) == runs["record"]["ensemble_sec"]
    assert read("ensemble_init_s")({}) is None
    G, S, N, stages = 128, 526, 1024, 2
    cost = costs_fleet.rk_fleet_step(G, S, stages, N, 4)
    assert cost["products"] == 5
    assert cost["flops"] == 5 * 2 * N * G * S * S          # 363 GFLOP
    assert cost["bytes"] == 5 * (G * S * S + 2 * N * G * S) * 4
    peaks = manifest.peaks("TPU v5 lite")
    # 10 fleet steps, the products taking 20 ms a step
    reduced = {"scopes": {costs_fleet.PRODUCT_SCOPES[0]: 0.12,
                          costs_fleet.PRODUCT_SCOPES[1]: 0.08,
                          "dedalus/transform/RealFourier.fft.bwd": 0.3}}
    ctx = {"reduced": reduced, "iterations": 10, "peaks": peaks,
           "facts": {"G": G, "S": S, "rk_stages": stages, "itemsize": 4}}
    assert read("fleet_product_ms_per_step")(ctx) == pytest.approx(20.0)
    share = read("fleet_product_roofline")(ctx)
    # at 105 flop a byte the least time is the bytes' (the one-pass ridge
    # is 240): 3.47 GB a step at 819 GB/s
    least = cost["bytes"] / peaks["hbm_bytes_per_s"]
    assert least > cost["flops"] / peaks["bf16_flops_per_s"]
    assert share == pytest.approx(100 * least / 0.020)
    # six bf16 passes take 6 flops / peak at the least: the share's ceiling
    ceiling = 100 * least / (6 * cost["flops"] / peaks["bf16_flops_per_s"])
    assert 0 < share < ceiling < 40
    assert read("fleet_product_roofline")(dict(ctx, peaks=None)) is None
    empty = dict(ctx, reduced={"scopes": {}})
    assert read("fleet_product_ms_per_step")(empty) is None
    assert read("fleet_product_roofline")(empty) is None

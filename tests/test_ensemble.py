"""
EnsembleSolver (core/ensemble.py): vmapped + mesh-sharded fleet stepping.

The contract under test is the acceptance bar of the ensemble PR:
  * fleet results BIT-match a serial run of each member with identical
    parameters (same step bodies, same factorization — vmap only adds
    the member axis), on both the unsharded path and the 8-device
    virtual mesh;
  * a chaos-poisoned member drops out (or rewinds with a per-member dt
    backoff) WITHOUT stopping the batch, with zero post-warmup retraces
    from the PR-3 sentinel;
  * the telemetry record carries the `ensemble` block and `python -m
    dedalus_tpu report` renders it.

All CPU, deterministic, tier-1.
"""

import json
import pathlib
import subprocess
import sys

import numpy as np
import pytest

import dedalus_tpu.public as d3
from dedalus_tpu.tools import chaos as chaos_mod
from dedalus_tpu.tools import retrace as retrace_mod

REPO = pathlib.Path(__file__).parent.parent

# module-wide ensemble marker: tier-1 by default, and covered by the
# conftest hard watchdog (a hung reshard/collective must fail ITS test,
# not eat the tier-1 budget)
pytestmark = pytest.mark.ensemble

AMPS = [0.1, 0.5, 1.0, 2.0, 0.3, 0.7, 1.5, 0.05]
KS = [1, 2, 3, 4, 1, 2, 3, 4]


def build_heat_solver(scheme="RK222", **kw):
    """1-D forced heat IVP with a parameter field `a` riding as an RHS
    extra operand — so member batching covers parameters, not just ICs."""
    xc = d3.Coordinate("x")
    dist = d3.Distributor(xc, dtype=np.float64)
    xb = d3.RealFourier(xc, size=32, bounds=(0, 2 * np.pi))
    u = dist.Field(name="u", bases=xb)
    a = dist.Field(name="a", bases=xb)
    problem = d3.IVP([u], namespace={"u": u, "a": a, "lap": d3.lap})
    problem.add_equation("dt(u) - lap(u) = a*u")
    solver = problem.build_solver(getattr(d3, scheme),
                                  warmup_iterations=2,
                                  enforce_real_cadence=10, **kw)
    x = dist.local_grid(xb)

    def member_init(i):
        u["g"] = np.sin(KS[i] * x)
        a["g"] = AMPS[i] * np.cos(x)

    return solver, member_init


_SERIAL = {}


def serial_states(scheme, n, dt, members=8, dts=None):
    """Reference: each member stepped on its own solver. The states depend
    on the arguments alone, so each set is computed once a module (the
    bit-match cases of both mesh parametrisations share it)."""
    key = (scheme, n, dt, members, None if dts is None else tuple(dts))
    if key not in _SERIAL:
        _SERIAL[key] = _serial_states(scheme, n, dt, members, dts)
    return _SERIAL[key]


def _serial_states(scheme, n, dt, members, dts):
    outs = []
    for i in range(members):
        solver, member_init = build_heat_solver(scheme)
        member_init(i)
        solver.step_many(n, dts[i] if dts is not None else dt)
        outs.append(np.asarray(solver.X))
    return outs


# ------------------------------------------------------------- bit-match

@pytest.mark.parametrize("scheme", ["SBDF2", "RK222"])
@pytest.mark.parametrize("mesh", [None, "auto"])
def test_fleet_bitmatches_serial(scheme, mesh):
    """Acceptance: fleet members == their serial runs (<= 1e-12 for f64;
    in practice identical), sharded and unsharded, both scheme families."""
    solver, member_init = build_heat_solver(scheme)
    ens = solver.ensemble(8, mesh=mesh)
    ens.init_members(member_init)
    ens.step_many(25, 1e-3)
    serial = serial_states(scheme, 25, 1e-3)
    for i in range(8):
        err = np.max(np.abs(np.asarray(ens.X[i]) - serial[i]))
        assert err <= 1e-12, (i, err)
    assert np.allclose(ens.sim_times[:8], 25e-3)


def test_heterogeneous_member_dts_bitmatch():
    """per_member_dt: members advance with genuinely different dts inside
    ONE compiled program (vmapped factorization) and still bit-match
    their own serial runs."""
    dts = np.array([1e-3, 5e-4, 2e-3, 1e-3, 7e-4, 1e-3, 1.5e-3, 9e-4])
    solver, member_init = build_heat_solver("RK222")
    ens = solver.ensemble(8, mesh="auto", per_member_dt=True)
    ens.init_members(member_init)
    ens.set_member_dts(dts)
    ens.step_many(20)
    serial = serial_states("RK222", 20, None, dts=dts)
    for i in range(8):
        err = np.max(np.abs(np.asarray(ens.X[i]) - serial[i]))
        assert err <= 1e-12, (i, err)
    assert np.allclose(ens.sim_times[:8], 20 * dts)


def test_member_io_roundtrip():
    """set_states/member_arrays/load_member move per-member state in and
    out of the fleet without loss."""
    solver, member_init = build_heat_solver("RK222")
    ens = solver.ensemble(3, mesh=None)
    G, S = solver.pencil_shape
    rng = np.random.default_rng(7)
    X = rng.normal(size=(3, G, S)).astype(solver.pencil_dtype)
    ens.set_states(X)
    assert np.array_equal(np.asarray(ens.X[:3]), X.astype(ens.X.dtype))
    arrays = ens.member_arrays(1)
    (key, arr), = arrays.items()
    state = ens.load_member(2)
    got = solver.gather_fields()
    assert np.array_equal(np.asarray(got), X[2].astype(ens.X.dtype))
    assert state is solver.state
    with pytest.raises(IndexError):
        ens.member_arrays(3)


# ------------------------------------------------------- construction API

def test_constructor_validation():
    solver, _ = build_heat_solver("SBDF2")
    with pytest.raises(ValueError, match="Runge-Kutta"):
        solver.ensemble(4, per_member_dt=True)
    with pytest.raises(ValueError, match="policy"):
        solver.ensemble(4, policy="explode")
    with pytest.raises(ValueError, match="per_member_dt"):
        solver.ensemble(4, policy="rewind")
    rk, _ = build_heat_solver("RK222")
    with pytest.raises(ValueError, match="per-member dt"):
        rk.ensemble(4, per_member_dt=False).set_member_dts([1e-3] * 4)


# --------------------------------------------------- chaos: drop + rewind

@pytest.mark.chaos
def test_chaos_member_poison_drops_without_stopping(tmp_path):
    """Acceptance: chaos NaN-poisons ONE member mid-run; the batch keeps
    going, the survivors finish bit-matching their serial runs, the
    dropped member is recorded (telemetry + report CLI), and the PR-3
    sentinel reports zero post-warmup retraces."""
    sink = tmp_path / "metrics.jsonl"
    retraces_before = retrace_mod.sentinel.post_arm_retraces
    solver, member_init = build_heat_solver("SBDF2")
    ens = solver.ensemble(8, mesh="auto", policy="drop", health_cadence=4,
                          snapshot_cadence=8,
                          metrics_file=str(sink))
    ens.init_members(member_init)
    injector = chaos_mod.ChaosInjector(nan_field="u", nan_iteration=20,
                                       nan_member=3)
    summary = ens.evolve(dt=1e-3, stop_iteration=60, block=4,
                         chaos=injector)
    assert ens.iteration == 60
    assert [f["kind"] for f in injector.fired] == ["nan"]
    # the poisoned member dropped; everyone else finished
    assert summary["dropped"] == 1
    assert summary["dropped_members"] == [3]
    assert summary["active"] == 7
    assert ens.dropped[0]["member"] == 3
    assert ens.dropped[0]["outcome"] == "dropped"
    # the dropped member froze at its newest finite snapshot
    assert np.all(np.isfinite(np.asarray(ens.X[3])))
    # survivors bit-match serial runs of the full 60 steps
    serial = serial_states("SBDF2", 60, 1e-3)
    for i in [0, 1, 2, 4, 5, 6, 7]:
        err = np.max(np.abs(np.asarray(ens.X[i]) - serial[i]))
        assert err <= 1e-12, (i, err)
    # zero post-warmup retraces: the drop was a value change, not a shape
    # (counted from this test's start: the sentinel is the process's, and
    # a worker runs other files' tests in the same process before this)
    assert retrace_mod.sentinel.post_arm_retraces == retraces_before
    # telemetry: ensemble block + counters in the flushed record
    record = ens.flush_metrics()
    assert record["ensemble"]["members"] == 8
    assert record["ensemble"]["active"] == 7
    assert record["ensemble"]["dropped"] == 1
    assert record["ensemble"]["dropped_members"] == [3]
    assert record["ensemble"]["ensemble_steps_per_sec"] > 0
    assert record["counters"]["ensemble/dropped"] == 1
    assert record["retraces_post_warmup"] == retraces_before
    # report CLI round-trip: the ensemble columns render
    out = subprocess.run(
        [sys.executable, "-m", "dedalus_tpu", "report", str(sink)],
        cwd=REPO, capture_output=True, text=True)
    assert out.returncode == 0, out.stderr
    assert "ensemble: 8 members, 7 active, 1 dropped" in out.stdout
    assert "member-steps/s" in out.stdout
    assert "dropped members: [3]" in out.stdout


@pytest.mark.chaos
def test_chaos_member_poison_rewinds_with_backoff():
    """policy='rewind': the poisoned member restores from its snapshot
    slot with its dt halved; the rest of the fleet never notices, and
    the member stays ACTIVE to completion."""
    retraces_before = retrace_mod.sentinel.post_arm_retraces
    solver, member_init = build_heat_solver("RK222")
    ens = solver.ensemble(8, mesh="auto", policy="rewind",
                          per_member_dt=True, health_cadence=4,
                          snapshot_cadence=8, dt_backoff=0.5,
                          max_member_retries=3)
    ens.init_members(member_init)
    injector = chaos_mod.ChaosInjector(nan_field="u", nan_iteration=20,
                                       nan_member=5)
    summary = ens.evolve(dt=1e-3, stop_iteration=60, block=4,
                         chaos=injector)
    assert ens.iteration == 60
    assert summary["dropped"] == 0
    assert summary["active"] == 8
    assert summary["rewinds"] >= 1
    event = ens.rewound[0]
    assert event["member"] == 5
    assert event["outcome"] == "rewound"
    assert event["rewind_iteration"] <= 20
    assert ens.dts[5] == pytest.approx(0.5e-3)
    assert np.all(np.isfinite(np.asarray(ens.X)))
    # the rewound member lost sim-time relative to the fleet (backed-off
    # dt from the snapshot onward)
    assert ens.sim_times[5] < ens.sim_times[0]
    assert retrace_mod.sentinel.post_arm_retraces == retraces_before


@pytest.mark.chaos
def test_rewind_backoff_survives_scalar_dt_driving():
    """A per-step driving loop re-passes the same scalar dt every call;
    that must NOT undo a rewound member's backed-off dt (or rewind
    degenerates to drop-with-extra-work)."""
    solver, member_init = build_heat_solver("RK222")
    ens = solver.ensemble(8, mesh=None, policy="rewind",
                          per_member_dt=True, health_cadence=2,
                          snapshot_cadence=4)
    ens.init_members(member_init)
    ens.snapshot()
    injector = chaos_mod.ChaosInjector(nan_field="u", nan_iteration=6,
                                       nan_member=5)
    for _ in range(30):
        ens.step(1e-3)
        injector.after_step(ens)
    assert len(ens.rewound) == 1
    assert ens.dts[5] == pytest.approx(0.5e-3)
    assert ens.n_active == 8
    assert np.all(np.isfinite(np.asarray(ens.X)))


# ------------------------------------------------ chaos: device loss

@pytest.mark.chaos
def test_chaos_device_loss_reshards_onto_survivors(tmp_path):
    """Acceptance: chaos kills one of the 8 virtual mesh devices mid-run
    (its member block poisoned + loss notification). The fleet re-shards
    onto the 7 survivors before the next dispatch, the lost device's
    member restores from the snapshot ring, the run completes with every
    member ACTIVE — and the final states bit-match fault-free serial
    references (survivors: the full 60 steps; the restored member: its
    snapshot iteration 16 plus the remaining 40 = 56 steps). Zero
    post-warmup retraces: rebuilt programs are fresh wrappers, each
    tracing once."""
    sink = tmp_path / "metrics.jsonl"
    solver, member_init = build_heat_solver("SBDF2")
    ens = solver.ensemble(8, mesh="auto", snapshot_cadence=8,
                          health_cadence=4, metrics_file=str(sink))
    ens.init_members(member_init)
    retrace_mod.sentinel.reset()
    injector = chaos_mod.ChaosInjector(lose_device=2, lose_iteration=20)
    summary = ens.evolve(dt=1e-3, stop_iteration=60, block=4,
                         chaos=injector, log_cadence=0)
    assert [f["kind"] for f in injector.fired] == ["lose_device"]
    assert ens.iteration == 60
    assert summary["reshards"] == 1
    assert summary["devices"] == 7
    assert summary["active"] == 8 and summary["dropped"] == 0
    event = ens.reshard_events[0]
    assert event["lost_devices"] == [2]
    assert [r["source"] for r in event["restored"]] == ["ring"]
    affected = [r["member"] for r in event["restored"]]
    assert affected == injector.fired[0]["members"]
    restored_iter = event["restored"][0]["iteration"]
    assert restored_iter == 16          # newest pre-loss snapshot
    # bit-identity against fault-free references: the restored member
    # plus two survivors (one per side of the lost block) — each
    # reference is a full serial build+run, so spot-checking keeps this
    # inside the tier-1 budget without weakening the claim
    steps_for = lambda i: (restored_iter + (60 - 20)) if i in affected \
        else 60
    for i in sorted(set(affected) | {0, 7}):
        ref_solver, ref_init = build_heat_solver("SBDF2")
        ref_init(i)
        ref_solver.step_many(steps_for(i), 1e-3)
        err = np.max(np.abs(np.asarray(ens.X[i]) - np.asarray(ref_solver.X)))
        assert err <= 1e-12, (i, err)
    assert retrace_mod.sentinel.post_arm_retraces == 0
    # telemetry: reshard count in the flushed block and the report CLI
    record = ens.flush_metrics()
    assert record["ensemble"]["reshards"] == 1
    assert record["counters"]["ensemble/reshards"] == 1
    out = subprocess.run(
        [sys.executable, "-m", "dedalus_tpu", "report", str(sink)],
        cwd=REPO, capture_output=True, text=True)
    assert out.returncode == 0, out.stderr
    assert "1 reshards" in out.stdout


@pytest.mark.chaos
def test_device_loss_restores_from_durable_checkpoint(tmp_path):
    """With the snapshot ring unusable (a real device loss destroys its
    slices too), the lost members restore from the last durable sharded
    checkpoint — and the post-fault run still bit-matches the fault-free
    reference (checkpoint iteration + remaining steps)."""
    solver, member_init = build_heat_solver("SBDF2")
    ens = solver.ensemble(8, mesh="auto", snapshot_cadence=1000,
                          health_cadence=4)
    ens.init_members(member_init)
    ens.init_checkpoints(tmp_path / "fleet")
    ens.snapshot()
    ens.step_many(16, 1e-3)
    ens.write_checkpoint()              # durable at iteration 16
    ens.step_many(4)
    # a REAL loss kills the ring slices with the device; model that
    ens.ring.clear()
    injector = chaos_mod.ChaosInjector(lose_device=2, lose_iteration=20)
    injector.after_step(ens)            # poison + notify at iteration 20
    ens.step_many(40)                   # reshard happens on entry
    assert ens.iteration == 60
    event = ens.reshard_events[0]
    assert [r["source"] for r in event["restored"]] == ["checkpoint"]
    assert event["restored"][0]["iteration"] == 16
    assert ens.n_active == 8
    affected = [r["member"] for r in event["restored"]]
    for i in sorted(set(affected) | {0, 7}):
        n = 16 + 40 if i in affected else 60
        ref_solver, ref_init = build_heat_solver("SBDF2")
        ref_init(i)
        ref_solver.step_many(n, 1e-3)
        err = np.max(np.abs(np.asarray(ens.X[i]) - np.asarray(ref_solver.X)))
        assert err <= 1e-12, (i, err)


@pytest.mark.chaos
def test_device_loss_without_any_source_drops_members(tmp_path):
    """No finite ring slot AND no durable checkpoint: the lost device's
    members drop (recorded, masked out) and the rest of the fleet
    completes untouched."""
    solver, member_init = build_heat_solver("SBDF2")
    ens = solver.ensemble(8, mesh="auto", snapshot_cadence=1000,
                          health_cadence=4)
    ens.init_members(member_init)
    ens.step_many(20, 1e-3)
    ens.ring.clear()
    injector = chaos_mod.ChaosInjector(lose_device=3, lose_iteration=20)
    injector.after_step(ens)
    ens.step_many(40)
    assert ens.iteration == 60
    event = ens.reshard_events[0]
    assert event["restored"] == []
    assert event["dropped"] == [3]
    assert ens.n_active == 7
    assert ens.dropped[0]["member"] == 3
    for i in (0, 4, 7):     # spot-check survivors (tier-1 budget)
        ref_solver, ref_init = build_heat_solver("SBDF2")
        ref_init(i)
        ref_solver.step_many(60, 1e-3)
        err = np.max(np.abs(np.asarray(ens.X[i]) - np.asarray(ref_solver.X)))
        assert err <= 1e-12, (i, err)


def test_notify_device_loss_without_mesh_raises():
    solver, member_init = build_heat_solver("SBDF2")
    ens = solver.ensemble(2, mesh=None)
    ens.init_members(member_init)
    ens.notify_device_loss(0)
    with pytest.raises(RuntimeError, match="without a device mesh"):
        ens.step_many(1, 1e-3)


@pytest.mark.chaos
def test_unrecoverable_member_drops_after_retries():
    """A member whose physics (not a transient) diverges exhausts its
    rewind retries and drops — the fleet still completes."""
    xc = d3.Coordinate("x")
    dist = d3.Distributor(xc, dtype=np.float64)
    xb = d3.RealFourier(xc, size=16, bounds=(0, 2 * np.pi))
    s = dist.Field(name="s", bases=xb)
    problem = d3.IVP([s], namespace={})
    problem.add_equation((d3.dt(s), s * s))
    solver = problem.build_solver(d3.RK222, warmup_iterations=2,
                                  enforce_real_cadence=0)
    ens = solver.ensemble(4, mesh=None, policy="rewind",
                          per_member_dt=True, health_cadence=2,
                          snapshot_cadence=4, max_member_retries=2)

    def member_init(i):
        # member 2 blows up at any dt; the others decay harmlessly
        s["g"] = 40.0 if i == 2 else -0.5

    ens.init_members(member_init)
    ens.evolve(dt=0.2, stop_iteration=40, block=2, log_cadence=0)
    assert ens.iteration == 40
    assert [e["member"] for e in ens.dropped] == [2]
    assert ens.dropped[0]["outcome"] == "dropped"
    # it was retried (rewound) before giving up
    assert len([e for e in ens.rewound if e["member"] == 2]) == 2
    assert ens.n_active == 3
    finite = [np.all(np.isfinite(np.asarray(ens.X[i]))) for i in range(3)]
    assert all(finite)

"""
Empirical plan-autotuner tests (tools/autotune.py wired through
core/solvers, libraries/solvecomp, and the assembly cache): config
validation fails loud at build, the winner selection is deterministic
under the accuracy bar (a fast-but-wrong cell can never win), decisions
round-trip the content-addressed cache with corrupt-record quarantine,
warm builds perform ZERO microbench probes (`probe_count()` is the
machine-checked witness), a decision change re-keys solver_key, bare-ops
constructions resolve the same tuned plan via the ops registry, and
`plan_provenance()` names its selector (`plan_source: tuned|config|
default`). The in-build microbench itself is monkeypatched to rigged
rates so the selection logic is exercised deterministically and fast.
"""

import json

import numpy as np
import jax
import pytest

from dedalus_tpu.libraries import solvecomp
from dedalus_tpu.tools import assembly_cache, autotune
from dedalus_tpu.tools.config import config

pytestmark = pytest.mark.autotune

# every config key a test may mutate, saved/restored by the fixture
CFG_KEYS = (("autotune", "MODE"), ("autotune", "TUNE_STEPS"),
            ("autotune", "TUNE_BUDGET_SEC"),
            ("fusion", "SOLVE_COMPOSITION"), ("fusion", "SPIKE_CHUNKS"),
            ("fusion", "FUSED_SOLVE"), ("fusion", "PALLAS"),
            ("precision", "SOLVE_DTYPE"), ("precision", "REFINE_SWEEPS"))


@pytest.fixture
def tune_cfg(tmp_path, monkeypatch):
    """Isolated tuner state: config keys restored, in-process memo/ops
    registry cleared, and the assembly cache redirected to a tmp dir so
    tests never read or warm the user's real cache."""
    monkeypatch.setenv("DEDALUS_TPU_ASSEMBLY_CACHE",
                       str(tmp_path / "assembly"))
    for section in {s for s, _ in CFG_KEYS}:
        if not config.has_section(section):
            config.add_section(section)
    saved = {(s, k): config[s].get(k) for s, k in CFG_KEYS}
    autotune.clear_memo()

    def set_cfg(**kw):
        for (s, k) in CFG_KEYS:
            if k in kw:
                config[s][k] = str(kw[k])

    yield set_cfg
    for (s, k), val in saved.items():
        if val is None:
            config[s].pop(k, None)
        else:
            config[s][k] = val
    autotune.clear_memo()


def build_rb(Nx=16, Nz=32):
    from dedalus_tpu.extras.bench_problems import build_rb_solver
    solver, b = build_rb_solver(Nx, Nz, np.float64, matsolver="banded")
    return solver


GOOD_CELL = {"composition": "ascan", "solve_dtype": "f32",
             "refine_sweeps": 2, "spike_chunks": 0, "pallas": False,
             "fused_transforms": None, "transpose_chunks": None}


# --------------------------------------------------- config validation

def test_resolve_autotune_defaults(tune_cfg):
    plan = autotune.resolve_autotune()
    assert plan.mode == "off"
    assert plan.tune_steps >= 1
    assert plan.budget_sec > 0


@pytest.mark.parametrize("key,value,fragment", [
    ("MODE", "always", "MODE"),
    ("MODE", "ON", "not a recognized value"),
    ("TUNE_STEPS", "fast", "TUNE_STEPS"),
    ("TUNE_STEPS", "0", "must be >= 1"),
    ("TUNE_BUDGET_SEC", "forever", "TUNE_BUDGET_SEC"),
    ("TUNE_BUDGET_SEC", "-3", "must be > 0"),
])
def test_bad_autotune_config_fails_loud(tune_cfg, key, value, fragment):
    tune_cfg(**{key: value})
    with pytest.raises(ValueError, match=fragment):
        autotune.resolve_autotune()


def test_bad_mode_fails_the_build_even_when_tuning_off(tune_cfg):
    # [autotune] is validated at EVERY build (core/solvers resolves it
    # unconditionally), so a typo cannot silently disable tuning
    tune_cfg(MODE="bogus")
    with pytest.raises(ValueError, match="MODE"):
        build_rb()


# ----------------------------------------------------- winner selection

def test_candidate_grid_reference_first_and_pallas_gating():
    cells = autotune.candidate_cells(backend="cpu")
    assert cells[0].get("reference") is True
    assert cells[0]["composition"] == "sequential"
    assert cells[0]["solve_dtype"] == "native"
    (pallas,) = [c for c in cells if c.get("pallas")]
    assert "skipped" in pallas          # cpu cannot lower it natively
    (tpu_pallas,) = [c for c in autotune.candidate_cells(backend="tpu")
                     if c.get("pallas")]
    # the TPU compiler refuses the kernel today (ROADMAP D4): skipped
    # with its reason everywhere until a backend lists it again
    assert "skipped" in tpu_pallas


def test_pick_winner_accuracy_bar_beats_speed():
    evidence = [
        {"composition": "sequential", "solve_dtype": "native",
         "solves_per_sec": 100.0, "rel_err": 0.0, "finite": True},
        # fastest cell, but inaccurate: can NEVER win
        {"composition": "ascan", "solve_dtype": "f32",
         "solves_per_sec": 1000.0, "rel_err": 1e-3, "finite": True},
        {"composition": "spike", "solve_dtype": "f32",
         "solves_per_sec": 500.0, "rel_err": 1e-12, "finite": True},
        # fast but non-finite / errored / skipped: all ineligible
        {"composition": "spike", "solve_dtype": "native",
         "solves_per_sec": 900.0, "rel_err": 0.0, "finite": False},
        {"composition": "ascan", "solve_dtype": "native",
         "error": "boom"},
        {"composition": "sequential", "solve_dtype": "f32",
         "skipped": "budget"},
    ]
    winner, margin = autotune.pick_winner(evidence, 1e-10,
                                          "solves_per_sec")
    assert (winner["composition"], winner["solve_dtype"]) == \
        ("spike", "f32")
    assert margin == pytest.approx(5.0)     # 500 over the 100 runner-up


def test_pick_winner_degenerate_cases():
    assert autotune.pick_winner([], 1e-10, "solves_per_sec") == \
        (None, None)
    solo = [{"composition": "sequential", "solve_dtype": "native",
             "solves_per_sec": 10.0, "rel_err": 0.0, "finite": True}]
    winner, margin = autotune.pick_winner(solo, 1e-10, "solves_per_sec")
    assert winner is solo[0] and margin is None


# ------------------------------------------------- decision round-trip

def test_decision_record_round_trip():
    d = autotune.Decision("sig" * 10, GOOD_CELL, evidence=[{"a": 1}],
                          backend="cpu", device_kind="cpu",
                          wall_sec=1.5, margin=2.0)
    back = autotune.Decision.from_record(d.to_record(),
                                         signature="sig" * 10)
    assert back is not None
    assert back.cell == GOOD_CELL
    assert back.margin == 2.0
    assert back.evidence == [{"a": 1}]


@pytest.mark.parametrize("mutate", [
    lambda r: r.update(tuning_version=99),
    lambda r: r.update(signature=None),
    lambda r: r["cell"].update(composition="warp"),
    lambda r: r["cell"].update(solve_dtype="f8"),
    lambda r: r["cell"].update(refine_sweeps=True),    # bool is not int
    lambda r: r["cell"].update(refine_sweeps=-1),
    lambda r: r["cell"].update(spike_chunks="two"),
    lambda r: r["cell"].update(pallas="yes"),
    lambda r: r["cell"].update(transpose_chunks=0),
    lambda r: r.update(cells="not-a-list"),
])
def test_decision_rejects_drifted_records(mutate):
    record = autotune.Decision("s" * 40, GOOD_CELL).to_record()
    mutate(record)
    assert autotune.Decision.from_record(record, "s" * 40) is None


def test_decision_rejects_signature_mismatch():
    record = autotune.Decision("s" * 40, GOOD_CELL).to_record()
    assert autotune.Decision.from_record(record, "x" * 40) is None


def test_corrupt_cached_record_is_quarantined(tune_cfg, tmp_path):
    cache = assembly_cache.AssemblyCache(str(tmp_path / "quarantine"))
    sig = "f" * 40
    # structurally valid JSON, semantically drifted (bad version):
    # load_decision must report a miss AND discard the entry
    assert assembly_cache.store_tuning(cache, sig, {"tuning_version": 99})
    assert autotune.load_decision(cache, sig) is None
    assert assembly_cache.load_tuning(cache, sig) is None   # quarantined
    # a valid record survives the round trip
    good = autotune.Decision(sig, GOOD_CELL, backend="cpu")
    assert autotune.store_decision(cache, good)
    loaded = autotune.load_decision(cache, sig)
    assert loaded is not None and loaded.cell == GOOD_CELL


# ------------------------------------- in-build tuning (rigged probes)

RIGGED_RATES = {("sequential", "native"): 100.0,
                ("sequential", "f32"): 50.0,
                ("ascan", "native"): 40.0,
                ("ascan", "f32"): 1000.0,       # fastest but inaccurate
                ("spike", "native"): 30.0,
                ("spike", "f32"): 500.0}        # fastest ACCURATE cell
RIGGED_ERRS = {("ascan", "f32"): 1e-3}


def rigged_probe(structure, stores, dtype, cell, tune_steps, ref_x):
    autotune._count_probe()
    key = (cell["composition"], cell["solve_dtype"])
    return {"solves_per_sec": RIGGED_RATES[key],
            "rel_err": 0.0 if ref_x is None else RIGGED_ERRS.get(key,
                                                                 1e-13),
            "finite": True,
            "refine_sweeps": 2 if cell["solve_dtype"] == "f32" else None,
            "x": np.zeros(4)}


@pytest.mark.skipif(jax.default_backend() != "cpu",
                    reason="rigged grid assumes the cpu candidate set")
def test_cold_tune_warm_hit_and_quarantine_retune(tune_cfg, monkeypatch):
    """The consult life cycle end to end: cold build measures every
    candidate once and the accurate winner (not the fast-but-wrong one)
    lands in the plan; a warm build after a memo wipe loads the decision
    from disk with ZERO probes; corrupting the cached record quarantines
    it and triggers exactly one fresh tune."""
    monkeypatch.setattr(autotune, "_probe_ops_cell", rigged_probe)
    tune_cfg(MODE="cached", TUNE_STEPS="2", TUNE_BUDGET_SEC="600")
    p0 = autotune.probe_count()
    solver = build_rb()
    assert autotune.probe_count() - p0 == 6     # pallas skipped on cpu
    assert solver._plan_source == "tuned"
    plan = solver._solve_plan
    assert (plan.composition, plan.dtype, plan.sweeps) == \
        ("spike", "f32", 2)
    prov = solver.plan_provenance()
    assert prov["plan_source"] == "tuned"
    tuning = prov["tuning"]
    assert tuning["cache"] == "stored"
    assert tuning["evidence_kind"] == "ops_probe"
    assert tuning["margin"] == pytest.approx(5.0)
    assert len(tuning["cells"]) == 7            # 6 measured + 1 skipped
    sig = autotune.solver_signature(solver)
    key_tuned = assembly_cache.solver_key(solver, list(solver.matrices))

    # warm build: decision from DISK (memo wiped), zero probes
    autotune.clear_memo()
    p1 = autotune.probe_count()
    warm = build_rb()
    assert autotune.probe_count() == p1         # the tentpole invariant
    assert warm._plan_source == "tuned"
    assert warm._tuning["cache"] == "hit"
    assert warm._solve_plan.composition == "spike"
    # identical decision -> identical content key as the tuning build
    assert assembly_cache.solver_key(warm, list(warm.matrices)) == \
        key_tuned

    # corrupt the persisted record: next cold build quarantines + re-tunes
    cache = assembly_cache.resolve()
    assert assembly_cache.store_tuning(cache, sig, {"tuning_version": 99})
    autotune.clear_memo()
    p2 = autotune.probe_count()
    retuned = build_rb()
    assert autotune.probe_count() - p2 == 6     # fresh tune, not a crash
    assert retuned._plan_source == "tuned"
    assert retuned._tuning["cache"] == "stored"


@pytest.mark.skipif(jax.default_backend() != "cpu",
                    reason="rigged grid assumes the cpu candidate set")
def test_plan_source_and_rekey(tune_cfg, monkeypatch):
    """plan_source names the selector: `default` untuned, `tuned` with a
    (seeded) decision — which re-keys solver_key/pool_key — and `config`
    when any explicit knob pins the plan (explicit config always wins:
    zero probes even under MODE=force)."""
    # default: tuner off, heuristic plan
    solver = build_rb()
    assert solver._plan_source == "default"
    prov = solver.plan_provenance()
    assert prov["plan_source"] == "default"
    assert "tuning" not in prov
    key_default = assembly_cache.solver_key(solver, list(solver.matrices))
    pool_default = assembly_cache.pool_key(solver)
    sig = autotune.solver_signature(solver)

    # tuned: a seeded ascan/f32 decision flips the whole plan stack and
    # therefore the assembly/pool content keys, with zero probes
    autotune.seed_decision(sig, GOOD_CELL, evidence_kind="seeded")
    tune_cfg(MODE="cached")
    p0 = autotune.probe_count()
    tuned = build_rb()
    assert autotune.probe_count() == p0
    assert tuned._plan_source == "tuned"
    assert (tuned._solve_plan.composition, tuned._solve_plan.dtype,
            tuned._solve_plan.sweeps) == ("ascan", "f32", 2)
    assert assembly_cache.solver_key(tuned, list(tuned.matrices)) != \
        key_default
    assert assembly_cache.pool_key(tuned) != pool_default

    # config: one pinned knob beats the seeded decision, probes stay 0
    monkeypatch.setattr(autotune, "_probe_ops_cell", rigged_probe)
    tune_cfg(MODE="force", SOLVE_COMPOSITION="sequential")
    pinned = build_rb()
    assert autotune.probe_count() == p0
    assert pinned._plan_source == "config"
    assert pinned._solve_plan.composition == "sequential"
    assert pinned.plan_provenance()["plan_source"] == "config"


# ------------------------------------------------- bare-ops consistency

def test_bare_ops_resolve_the_registered_decision(tune_cfg):
    """libraries/pencilops.py fallback paths (BandedOps/DenseOps built
    with no solver threading a plan) must resolve the SAME plan a tuned
    solver build registered for that system size."""
    decision = autotune.Decision("d" * 40, GOOD_CELL)
    autotune._register_ops(decision, [48])
    assert autotune.ops_decision("banded", 48) is decision
    assert autotune.ops_decision("dense", 48) is decision
    assert autotune.ops_decision("banded", 49) is None
    assert autotune.ops_decision("banded", None) is None
    plan = solvecomp.resolve_solve_plan_for_ops("banded", 48)
    assert (plan.composition, plan.dtype, plan.sweeps) == \
        ("ascan", "f32", 2)
    # unregistered size: plain heuristics
    plan = solvecomp.resolve_solve_plan_for_ops("banded", 49)
    assert plan.composition == "sequential"
    # pinned config wins over the registry too
    tune_cfg(SOLVE_COMPOSITION="spike", SPIKE_CHUNKS="4")
    plan = solvecomp.resolve_solve_plan_for_ops("banded", 48)
    assert (plan.composition, plan.spike_chunks) == ("spike", 4)


def test_apply_decision_layers_cell_over_plan():
    base = solvecomp.SolvePlan(composition="sequential", spike_chunks=0,
                               dtype="native", sweeps=None, tol=0.0,
                               mmt_dtype="native")
    plan = solvecomp.apply_decision(base, GOOD_CELL)
    assert (plan.composition, plan.dtype, plan.sweeps) == \
        ("ascan", "f32", 2)
    assert plan.tol == base.tol and plan.mmt_dtype == base.mmt_dtype
    # sweeps fall back to the dtype's auto schedule when the cell is
    # silent, and f64 normalizes to native
    cell = {"composition": "spike", "solve_dtype": "f32",
            "refine_sweeps": None}
    assert solvecomp.apply_decision(base, cell).sweeps == \
        solvecomp._AUTO_SWEEPS["f32"]
    assert solvecomp.apply_decision(
        base, {"solve_dtype": "f64"}).dtype == "native"


def test_solve_knobs_pinned(tune_cfg):
    assert not solvecomp.solve_knobs_pinned()
    tune_cfg(REFINE_SWEEPS="3")
    assert solvecomp.solve_knobs_pinned()
    tune_cfg(REFINE_SWEEPS="auto")
    assert not solvecomp.solve_knobs_pinned()


# --------------------------------------------------------- the tune CLI

def test_run_tune_rejects_bad_inputs(tune_cfg):
    lines = []
    assert autotune.run_tune(problem="nosuch", out=lines.append) == 2
    assert any("unknown tune problem" in ln for ln in lines)
    tune_cfg(MODE="bogus")
    lines.clear()
    assert autotune.run_tune(out=lines.append) == 2
    assert any("MODE" in ln for ln in lines)


def rigged_offline(build, plan=None, label="", n_steps=12, block=20,
                   blocks=5):
    evidence = [
        {"composition": "sequential", "solve_dtype": "native",
         "pallas": False, "steps_per_sec": 8.0, "rel_err": 0.0,
         "finite": True, "refine_sweeps": None, "reference": True},
        {"composition": "sequential", "solve_dtype": "f32",
         "pallas": False, "steps_per_sec": 9.5, "rel_err": 1e-13,
         "finite": True, "refine_sweeps": 2},
        {"composition": "ascan", "solve_dtype": "native", "pallas": False,
         "skipped": "budget"},
    ]
    cell = {"composition": "sequential", "solve_dtype": "f32",
            "refine_sweeps": 2, "spike_chunks": 0, "pallas": False,
            "fused_transforms": None, "transpose_chunks": None}
    decision = autotune.Decision("a" * 40, cell, evidence=evidence,
                                 backend="cpu", device_kind="cpu",
                                 evidence_kind="step_sweep",
                                 wall_sec=4.2, margin=1.188)
    return decision, evidence


def test_run_tune_reports_and_persists(tune_cfg, monkeypatch):
    monkeypatch.setattr(autotune, "tune_offline", rigged_offline)
    lines = []
    rc = autotune.run_tune(problem="rb64x32", quick=True, as_json=True,
                           record=False, out=lines.append)
    assert rc == 0
    row = json.loads("\n".join(lines))
    assert row["kind"] == "autotune"
    assert row["chosen_label"] == "sequential/f32+2sw"
    assert row["evidence_kind"] == "step_sweep"
    assert row["cache"] == "stored"
    assert len(row["cells"]) == 3
    # the decision reached the (tmp) persistent cache AND the memo
    cache = assembly_cache.resolve()
    assert autotune.load_decision(cache, "a" * 40) is not None
    assert autotune._MEMO["a" * 40].cell["solve_dtype"] == "f32"
    # human rendering names the winner and the per-cell evidence
    lines.clear()
    rc = autotune.run_tune(problem="rb64x32", quick=True, record=False,
                           out=lines.append)
    assert rc == 0
    assert "chosen sequential/f32+2sw" in lines[0]
    assert any("(reference)" in ln for ln in lines)
    assert any("skipped" in ln for ln in lines)


def test_run_tune_no_accurate_winner(tune_cfg, monkeypatch):
    def no_winner(build, **kw):
        return None, [{"composition": "ascan", "solve_dtype": "f32",
                       "pallas": False, "error": "Exception('nan')"}]
    monkeypatch.setattr(autotune, "tune_offline", no_winner)
    lines = []
    assert autotune.run_tune(problem="rb64x32", quick=True, record=False,
                             out=lines.append) == 1
    assert any("no accurate candidate" in ln for ln in lines)

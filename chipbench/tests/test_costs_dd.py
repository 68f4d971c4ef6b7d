"""The float64 route's pencil-product bytes against the same count by hand
at a size a person can check and at the published one; the five readers
this configuration adds on a trace that has their scopes, and on one that
has none: nothing to read, no number; the cell's entries."""

import json
import pathlib

import pytest

from chipbench import costs_dd
from chipbench.manifest import Manifest

SPEC = json.loads((pathlib.Path(__file__).resolve().parents[1] / "configs"
                   / "rb256x64-f64.json").read_text())
CELL = "rb256x64-f64.block10"
NEW = ["dd_matvec_ms_per_step", "dd_refine_ms_per_step",
       "dd_rhs_ms_per_step", "dd_transform_ms_per_step",
       "dd_matvec_roofline"]
FACTS = {"ops": "DenseOps", "G": 128, "S": 526, "itemsize": 8,
         "rk_stages": 2}


def test_products_of_a_step_are_the_scheme_s():
    shapes = SPEC["dd_shapes"]
    # RK222 at two sweeps: M X0, L X1 (no stage reads L X0: the first
    # column of its H is zero), and A x for 2 stages x 2 sweeps
    assert costs_dd.products_per_step(shapes, stages=2) == 1 + 1 + 4 == 6
    assert costs_dd.products_per_step(dict(shapes, sweeps=1), 3) == 5


def test_bytes_by_hand_for_2_pencils_of_3():
    cost = costs_dd.rk_dd_step(SPEC["dd_shapes"], 2, 3, stages=2)
    # one product: 2 matrices of 3 x 3 entries of 8 bytes
    assert cost["bytes_per_product"] == 2 * 9 * 8 == 144
    assert cost == {"bytes": 6 * 144, "products": 6,
                    "bytes_per_product": 144}


def test_bytes_at_the_published_size():
    cost = costs_dd.rk_dd_step(SPEC["dd_shapes"], 128, 526, stages=2)
    # 128 * 526^2 = 35,414,528 entries: 283.3 MB a product as 8 planes of
    # int8 or as a float32 pair, whichever the program keeps
    assert cost["bytes_per_product"] == 8 * 35_414_528 == 283_316_224
    assert cost["bytes"] == 1_699_897_344
    # at a v5e's 819 GB/s: 2.076 ms a step
    assert cost["bytes"] / 819e9 == pytest.approx(2.0756e-3, rel=1e-3)
    # the Ozaki product as first written reads 36 planes a product where
    # 8 would do: 4.5 times these bytes
    assert 36 * 35_414_528 / cost["bytes_per_product"] == 4.5


@pytest.mark.parametrize("name", NEW)
def test_reader_reads_nothing_where_the_program_has_no_such_scope(name):
    """A tree before PR 35, a float32 cell, or the other float64 route
    (the driver lays these files over the parent): the metric is left out,
    nothing raises."""
    read = Manifest().layer_reader(name)
    assert read({}) is None
    old = {"dedalus/evaluator/rhs": 3.0, "dedalus/matsolve/dense.solve": 1.0,
           "dedalus/matsolve/BatchedInverseRefined.solve": 1.0,
           "dedalus/transform/RealFourier.matrix.bwd": 1.0}
    ctx = {"reduced": {"scopes": old}, "iterations": 10, "facts": FACTS,
           "peaks": {"hbm_bytes_per_s": 819e9}}
    assert read(ctx) is None


def test_readers_on_a_trace_that_has_the_scopes():
    scopes = {
        "dedalus/matsolve/dd.matvec": 0.10,
        "dedalus/matsolve/dd.residual": 0.20,
        "dedalus/matsolve/dd.refine": 0.03,
        "dedalus/matsolve/dense.solve": 0.01,
        "dedalus/matsolve/BatchedInverseRefined.solve": 0.06,
        "dedalus/matsolve/dd.factor": 9.0,              # no metric's
        "dedalus/matsolve/dense.factor": 9.0,
        "dedalus/transform/RealFourier.dd.bwd": 0.04,
        "dedalus/transform/Jacobi.dd.fwd": 0.02,
        "dedalus/transform/RealFourier.matrix.bwd": 9.0,   # not dd
        "dedalus/evaluator/dd.rhs": 0.05,
        "dedalus/evaluator/rhs": 9.0,
        "dedalus/step/dd.combine": 0.01}
    ctx = {"reduced": {"scopes": scopes}, "iterations": 10, "facts": FACTS,
           "peaks": {"hbm_bytes_per_s": 819e9}}
    read = Manifest().layer_reader
    assert read("dd_matvec_ms_per_step")(ctx) == pytest.approx(30.0)
    assert read("dd_refine_ms_per_step")(ctx) == pytest.approx(10.0)
    assert read("dd_rhs_ms_per_step")(ctx) == pytest.approx(5.0)
    assert read("dd_transform_ms_per_step")(ctx) == pytest.approx(6.0)
    assert read("dd_matvec_roofline")(ctx) == pytest.approx(
        100 * 10 * 1_699_897_344 / 819e9 / 0.30)
    # no share of a roofline without a Runge-Kutta scheme's stages
    assert read("dd_matvec_roofline")(
        dict(ctx, facts=dict(FACTS, rk_stages=0))) is None


def test_manifest_lists_the_cell_for_every_reader_it_reports():
    manifest = Manifest()
    cell = manifest.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("rb256x64-f64", "block10", 1)
    assert manifest.workload(cell)["expect"] == {"ops": "DenseOps"}
    names = {m["name"] for m in manifest.metrics("per_layer", CELL)}
    assert set(NEW) <= names
    assert {"step_dispatch_ms_per_step", "probe_ms_per_step",
            "device_ms_per_step", "transform_ms_per_step",
            "solve_ms_per_step", "ref_rel_l2", "peak_hbm_MB",
            "device_idle_pct"} <= names
    assert not {"solve_roofline", "banded_solve_roofline",
                "swsh_mmt_roofline"} & names
    for name in NEW:
        entry = next(m for m in manifest.data["per_layer"]
                     if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "steps_per_s"
    config = next(c for c in manifest.data["configs"]
                  if c["name"] == "rb256x64-f64")
    assert config["reduced"] == [] == SPEC["reduced"]

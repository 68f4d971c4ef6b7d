"""The banded layer's byte count against the same count by hand, at the
shapes of rb2048x1024 (G=1024 pencils of S=8206 unknowns, q=32, 54
diagonals each of M and L, 16 pinned rows, float32)."""

import json
import pathlib

import pytest

from chipbench import costs_banded

SPEC = json.loads((pathlib.Path(__file__).resolve().parents[1] / "configs"
                   / "rb2048x1024.json").read_text())


def test_shapes_at_the_published_size():
    shape = costs_banded.shapes(SPEC, 1024, 8206)
    assert shape["NB"] == 257 and shape["n_pad"] == 8224 == 257 * 32
    assert (shape["diagonals_M"], shape["diagonals_L"]) == (54, 54)
    assert (shape["pin_rows_M"], shape["pin_rows_L"]) == (0, 16)
    # the rehearsal's pencils have the same structure, fewer block rows
    small = costs_banded.shapes(SPEC, 32, 270)
    assert small["NB"] == 9 and small["n_pad"] == 288


def test_rk222_step_bytes_by_hand():
    shape = costs_banded.shapes(SPEC, 1024, 8206)
    cost = costs_banded.rk_banded_step(shape, stages=2, itemsize=4)
    by_hand = {
        # M @ X0: 54 diagonals of 8224 numbers per pencil, no pinned row
        "M": 1024 * 54 * 8224 * 4,
        # L @ Xi, twice: 54 diagonals and 16 pinned rows
        "L": 2 * 1024 * (54 + 16) * 8224 * 4,
        # two solves: 256 block rows of 4 * 32^2 numbers and 64 pivots,
        # and the last block's 32^2 numbers and 32 pivots
        "factors": 2 * 1024 * (256 * (4096 * 4 + 64 * 4) + 1024 * 4 + 128),
        # two solves: V^T and Y^T (16 x 8224 each) and a 16 x 16 matrix
        "woodbury": 2 * 1024 * (2 * 16 * 8224 + 256) * 4,
    }
    assert cost["parts"] == by_hand
    assert cost["bytes"] == sum(by_hand.values()) == 17_425_760_256
    # at a v5e's 819 GB/s: 21.3 ms, so no step rate above 47/s exists
    assert cost["bytes"] / 819e9 == pytest.approx(0.02128, rel=1e-3)


def test_reader_reads_nothing_without_a_banded_trace():
    from chipbench.manifest import Manifest
    read = Manifest().layer_reader("banded_solve_roofline")
    assert read({}) is None
    facts = {"ops": "DenseOps", "G": 128, "S": 526, "itemsize": 4,
             "rk_stages": 2}
    assert read({"reduced": {"scopes": {}}, "iterations": 10,
                 "facts": facts}) is None
    facts = dict(facts, ops="BandedOps", G=1024, S=8206)
    ctx = {"reduced": {"scopes": {}}, "iterations": 10, "facts": facts,
           "peaks": {"hbm_bytes_per_s": 819e9}}
    assert read(ctx) is None          # no banded scope in the trace
    ctx["reduced"]["scopes"] = {
        "dedalus/matsolve/banded.fwd": 1.0,
        "dedalus/matsolve/banded.matvec_pair": 1.0,
        "dedalus/matsolve/banded.factor": 5.0,      # left out
        "dedalus/matsolve/dense.solve": 7.0}        # not this layer
    # ten steps' least time over two measured seconds
    assert read(ctx) == pytest.approx(
        100 * 10 * 17_425_760_256 / 819e9 / 2.0)

"""Shapes -> bytes and flops, against values worked out by hand for the
RB 256x64 pencil system (G=128 groups, S=526 unknowns, float32)."""

import json
import pathlib

from chipbench import costs

G, S = 128, 526


def test_dense_matvec_by_hand():
    c = costs.dense_matvec(G, S, 4)
    # 2 flops per matrix entry: 2 * 128 * 526^2
    assert c["flops"] == 70_829_056
    # matrix 128*526*526 + vector in + vector out (128*526 each), 4 B each
    assert c["bytes"] == (35_414_528 + 2 * 67_328) * 4 == 142_196_736


def test_rk222_dense_step_by_hand():
    c = costs.rk_dense_step(G, S, stages=2, itemsize=4)
    # M@X0, then per stage L@Xi and one stored-inverse solve: 5 calls
    assert c["matrix_reads"] == 5
    assert c["bytes"] == 5 * 142_196_736 == 710_983_680
    assert c["flops"] == 5 * 70_829_056


def test_least_seconds_is_bandwidth_bound_on_v5e():
    peaks = json.loads((pathlib.Path(costs.__file__).parent
                        / "peaks.json").read_text())["TPU v5 lite"]
    least, side = costs.least_seconds(
        costs.rk_dense_step(G, S, 2, 4), peaks)
    assert side == "bandwidth"
    # 710,983,680 B / 819e9 B/s
    assert abs(least - 8.6811e-4) < 1e-7


def test_transform_costs_by_hand():
    # 384 real FFTs of length 384 (RB's dealiased x axis, one z column each)
    c = costs.real_fft(384, 96, 4)
    assert c["bytes"] == (384 + 2 * 193) * 96 * 4
    assert abs(c["flops"] - 2.5 * 384 * 8.584962500721156 * 96) < 1
    m = costs.matrix_transform(96, 64, 384, 4)
    assert m["flops"] == 2 * 96 * 64 * 384
    assert m["bytes"] == (96 * 64 + (96 + 64) * 384) * 4
    d = costs.dct_fft(96, 384, 4)
    assert d["bytes"] == 2 * 96 * 384 * 4

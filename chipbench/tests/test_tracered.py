"""The trace reduction: on hand-made events whose answer is known, and on
a small recorded trace of the chip kept beside this file."""

import pathlib

import pytest

from chipbench import tracered
from chipbench.tracered import Trace, flatten, reduce, scope_of

RECORDED = pathlib.Path(__file__).parent / "data"


def test_flatten_nested_events_are_not_counted_twice():
    # a while [0, 100) holding two body ops, then a lone op
    events = [(0, 100, "while"), (10, 30, "a"), (40, 90, "b"),
              (120, 150, "c")]
    segs = flatten(events)
    assert segs == [(0, 10, "while"), (10, 30, "a"), (30, 40, "while"),
                    (40, 90, "b"), (90, 100, "while"), (120, 150, "c")]
    assert sum(e - s for s, e, _ in segs) == 130        # the busy union
    assert sum(e - s for s, e, _ in events) == 200      # counted twice


def test_flatten_three_levels_and_partial_overlap():
    segs = flatten([(0, 50, "outer"), (5, 45, "mid"), (10, 20, "leaf")])
    own = {}
    for s, e, label in segs:
        own[label] = own.get(label, 0) + e - s
    assert own == {"outer": 10, "mid": 30, "leaf": 10}
    # a child that outlives its parent never makes time twice
    segs = flatten([(0, 10, "p"), (5, 15, "c")])
    assert sum(e - s for s, e, _ in segs) == 15


def test_scope_of_takes_the_innermost_dedalus_scope():
    path = ("jit(step_body)/dedalus/step/stage1/dedalus/matsolve/"
            "dense.solve/dot_general")
    assert scope_of(path) == "dedalus/matsolve/dense.solve"
    assert scope_of("jit(f)/dedalus/step/stage2/add") \
        == "dedalus/step/stage2"
    assert scope_of("jit(f)/jit(main)/mul") is None
    assert scope_of("") is None


def hand_made():
    op = "jit(s)/dedalus/step/stage1/dedalus/"
    device = {"/device:TPU:0": [
        (1000, 9000, "while.1", ""),
        (1000, 3000, "fusion.1", op + "matsolve/dense.solve/dot_general"),
        (3000, 4000, "fft.1", op + "transform/RealFourier.fft.fwd/fft"),
        (5000, 8000, "fusion.2", op + "matsolve/dense.matvec/dot_general"),
        (12000, 13000, "copy.3", "jit(s)/dedalus/step/stage1/add"),
    ]}
    spans = [(0, 20000, "chipbench/window"),
             (0, 9500, "chipbench/block"),
             (9500, 20000, "chipbench/block"),
             (10000, 11000, "chipbench/handlers")]
    return Trace(device, spans)


def test_reduce_hand_made_trace():
    r = reduce(hand_made())
    assert r["window_s"] == pytest.approx(20000e-9)
    # union: while [1000, 9000) + copy [12000, 13000)
    assert r["busy_s"] == pytest.approx(9000e-9)
    assert r["summed_durations_s"] == pytest.approx(15000e-9)
    assert tracered.scope_seconds(r, "dedalus/matsolve/") \
        == pytest.approx(5000e-9)
    assert tracered.scope_seconds(r, "dedalus/transform/") \
        == pytest.approx(1000e-9)
    # the while's own time (gaps between its body ops) has no scope
    assert r["unscoped_s"] == pytest.approx(2000e-9)
    assert sum(v for _, v in r["device_ops"]) == pytest.approx(r["busy_s"])
    # idle: [0,1000) and [9000,9500) under the first block; [9500,12000)
    # and [13000,20000) under the second, 1000 of it inside handlers
    idle = dict(r["idle_gaps"])
    assert idle["chipbench/handlers"] == pytest.approx(1000e-9)
    assert idle["chipbench/block"] == pytest.approx(10000e-9)
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_reduce_averages_over_chips_and_knows_an_empty_trace():
    t = hand_made()
    t.device["/device:TPU:1"] = [(2000, 4000, "fusion.9", "")]
    r = reduce(t)
    assert r["busy_s"] == pytest.approx((9000e-9 + 2000e-9) / 2)
    assert reduce(Trace({}, t.spans)) is None


# ---- a recorded trace: one step_many(50) block of rb256x64.block on a
# TPU v5 lite (PR 23, first chip call), cut to the XLA Ops line and the
# harness's span, long metadata dropped. 433 KB.

ONE_BLOCK = RECORDED / "rb256x64.block.one-block.xplane.pb"
STEPS = 50


def merged_length(intervals):
    """Union of intervals the plain way: sort, merge, add."""
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


@pytest.fixture(scope="module")
def recorded():
    return Trace.from_file(ONE_BLOCK)


def test_wire_reader_agrees_with_profiledata(recorded):
    """chipbench/xplane.py decodes the file by hand; jax's own reader sees
    the same events at the same times (and none of the metadata stats the
    hand reader is there for)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(ONE_BLOCK))
    plane = data.find_plane_with_name("/device:TPU:0")
    (line,) = [ln for ln in plane.lines if ln.name == "XLA Ops"]
    theirs = [(e.start_ns, e.duration_ns) for e in line.events]
    ours = recorded.device["/device:TPU:0"]
    assert len(ours) == len(theirs) == 21675
    for (s, e, _, _), (ts, td) in zip(ours[::97], theirs[::97]):
        assert abs(s - ts) <= 1 and abs((e - s) - td) <= 1     # ns
    assert recorded.spans == [(43724156.0, 131297808.0, "chipbench/block")]
    assert sum(1 for ev in ours if "dedalus/" in ev[3]) > 15000


def test_recorded_trace_busy_union_scopes_and_per_step(recorded):
    r = reduce(recorded)
    events = recorded.device["/device:TPU:0"]
    # no chipbench/window span in the cut: the window is the events' extent
    assert r["window_s"] == pytest.approx(0.087092481, rel=1e-6)
    union = merged_length((s, e) for s, e, _, _ in events) * 1e-9
    assert r["busy_s"] == pytest.approx(union, rel=1e-9)
    assert r["busy_s"] == pytest.approx(0.085249714, rel=1e-6)
    # the scan's `while` holds its body: durations as they stand count
    # everything inside it twice; self times do not
    (loop,) = [ev for ev in events if ev[2].startswith("while")]
    assert (loop[1] - loop[0]) * 1e-9 == pytest.approx(0.085164969, rel=1e-6)
    assert r["summed_durations_s"] / r["busy_s"] == pytest.approx(1.998,
                                                                  abs=2e-3)
    attributed = sum(r["scopes"].values()) + r["unscoped_s"]
    assert attributed == pytest.approx(r["busy_s"], rel=1e-9)
    assert sum(v for _, v in r["device_ops"]) \
        == pytest.approx(r["busy_s"], rel=1e-9)
    solve = tracered.scope_seconds(r, "dedalus/matsolve/")
    transform = tracered.scope_seconds(r, "dedalus/transform/")
    assert solve == pytest.approx(0.050038540, rel=1e-6)
    assert transform == pytest.approx(0.011031057, rel=1e-6)
    assert r["scopes"]["dedalus/matsolve/dense.matvec"] \
        == pytest.approx(0.031137075, rel=1e-6)
    # per step: 50 steps in the block
    assert 1e3 * r["busy_s"] / STEPS == pytest.approx(1.70499, abs=1e-4)
    assert 1e3 * solve / STEPS == pytest.approx(1.00077, abs=1e-4)
    assert r["device_ops"][1][0] == "unscoped/copy.1214"


def test_layer_readers_on_the_recorded_trace(recorded):
    """The per-layer reader files, fed the recorded block as the harness
    feeds them, with the v5e's peaks and RB's shapes."""
    from chipbench.manifest import Manifest
    manifest = Manifest()
    ctx = {"reduced": reduce(recorded), "iterations": STEPS,
           "peaks": manifest.peaks("TPU v5 lite"),
           "facts": {"ops": "DenseOps", "G": 128, "S": 526, "itemsize": 4,
                     "rk_stages": 2}}
    value = lambda name: manifest.layer_reader(name)(ctx)  # noqa: E731
    assert value("device_ms_per_step") == pytest.approx(1.70499, abs=1e-4)
    assert value("solve_ms_per_step") == pytest.approx(1.00077, abs=1e-4)
    assert value("transform_ms_per_step") == pytest.approx(0.22062, abs=1e-4)
    assert value("device_idle_pct") == pytest.approx(2.1159, abs=1e-3)
    assert value("host_gap_ms_per_step") == pytest.approx(0.036855, abs=1e-5)
    # least time 710,983,680 B / 819e9 B/s = 0.86811 ms of 1.00077 measured
    assert value("solve_roofline") == pytest.approx(86.744, abs=1e-2)
    assert manifest.layer_reader("solve_roofline")(
        dict(ctx, facts=dict(ctx["facts"], ops="BandedOps"))) is None
    assert manifest.layer_reader("device_ms_per_step")(
        dict(ctx, reduced=None)) is None

"""The trace reduction: on a trace written out by hand, and on a cut of a
trace recorded on the chip, against values counted by hand.

``data/t4_solves.xspace.txt`` is three back-to-back 1-D solves
(``repro.solve("cubic", dim=1, particles=131072, iters=200)``) from a
profiler trace taken on one TPU v5e: every ``XLA Ops`` event of chip 0
between the first ``solve`` span and the third ``result`` span, with its
HLO instruction head as its name, and those host spans, with a
``bench.window`` over them. Its values below were counted by a sweep
over the events' start and end edges, apart from ``xtrace``."""
import os

import pytest

import xtrace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _plane(pid, name, line, events, names):
    evs = "\n".join(
        f"    events {{ metadata_id: {m} offset_ps: {s * 1000} "
        f"duration_ps: {(e - s) * 1000} }}" for m, s, e in events)
    meta = "\n".join(f'  event_metadata {{ key: {k} value {{ id: {k} '
                     f'name: "{n}" }} }}' for k, n in names.items())
    return (f'planes {{\n  id: {pid}\n  name: "{name}"\n  lines {{\n'
            f'    id: 1\n    name: "{line}"\n    timestamp_ns: 0\n{evs}\n'
            f'  }}\n{meta}\n}}\n')


HAND = (
    # chip 0: a kernel [1000, 3000) overlapping a fusion [2000, 4000), then
    # a copy [6000, 7000): busy 3000 + 1000 ns
    _plane(1, "/device:TPU:0", "XLA Ops",
           [(1, 1000, 3000), (2, 2000, 4000), (3, 6000, 7000)],
           {1: "%cupso_fused_queue_lock.1 = (f32[8,512]) custom-call()",
            2: "%fusion.3 = f32[8] fusion()", 3: "%copy.1 = f32[8] copy()"})
    # chip 1: busy through the whole window
    + _plane(2, "/device:TPU:1", "XLA Ops", [(1, 0, 10000)],
             {1: "%fusion.7 = f32[8] fusion()"})
    # the host: the window, a solve span and a result span
    + _plane(3, "/host:CPU", "main",
             [(1, 0, 10000), (2, 0, 5000), (3, 5000, 9000)],
             {1: "bench.window", 2: "solve", 3: "result"}))


@pytest.fixture(scope="module")
def hand():
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(HAND)


def test_union_busy_and_idle_one_chip(hand):
    s = xtrace.summarize(hand, chips=1)
    assert s.window_s == pytest.approx(10000e-9)
    assert s.busy_s == pytest.approx(4000e-9)      # overlap counted once
    assert s.kernel_s == pytest.approx(2000e-9)
    assert 1 - s.busy_s / s.window_s == pytest.approx(0.6)


def test_busy_is_averaged_over_chips(hand):
    s = xtrace.summarize(hand, chips=2)
    assert s.busy_s == pytest.approx((4000e-9 + 10000e-9) / 2)


def test_breakdown_ops_and_gaps(hand):
    b = xtrace.summarize(hand, chips=1).breakdown()
    ops = dict(b["device_ops"])
    assert ops == pytest.approx({"cupso_fused_queue_lock": 2000e-9,
                                 "fusion": 2000e-9, "copy": 1000e-9})
    # gaps [0,1000) under solve; [4000,6000) and [7000,10000) under result
    assert b["idle_gaps"] == [["result", pytest.approx(3000e-9)],
                              ["result", pytest.approx(2000e-9)],
                              ["solve", pytest.approx(1000e-9)]]


def test_interval_helpers():
    assert xtrace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert xtrace.gaps([(2, 3), (5, 6)], 0, 10) == [(0, 2), (3, 5), (6, 10)]
    assert xtrace.clip((0, 10), 3, 5) == (3, 5)
    assert xtrace.clip((0, 2), 3, 5) is None
    assert xtrace.short_name("%cupso_x.12 = (f32[1]) custom-call()") \
        == "cupso_x"


@pytest.fixture(scope="module")
def chip():
    from jax.profiler import ProfileData
    with open(os.path.join(DATA, "t4_solves.xspace.txt")) as f:
        return ProfileData.from_text_proto(f.read())


def test_recorded_solves(chip):
    s = xtrace.summarize(chip, chips=1)
    assert s.window_s == pytest.approx(181_537_641e-9, abs=1e-9)
    assert s.busy_s == pytest.approx(50_172_075e-9, abs=1e-9)
    # three fused queue-lock kernels, one a solve
    assert s.kernel_s == pytest.approx(49_564_390e-9, abs=1e-9)
    assert 1 - s.busy_s / s.window_s == pytest.approx(0.72363, abs=1e-5)
    b = s.breakdown()
    assert b["device_ops"][0] == ["cupso_fused_queue_lock",
                                  pytest.approx(49_564_390e-9, abs=1e-9)]
    assert len(b["device_ops"]) == xtrace.TOP
    # the longest device-idle stretch falls inside repro.solve's host work
    assert b["idle_gaps"][0] == ["solve", pytest.approx(5_482_863e-9,
                                                        abs=1e-9)]

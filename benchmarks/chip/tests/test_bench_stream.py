"""The kernels' stream in a traced window (``xstream``) and its two
readers, ``kernel.stream_share`` and ``kernel.step_us``: on a trace
written out by hand, on a synthetic context and on the recorded chip trace
of ``test_bench_trace.py``, against values counted by hand."""
import os
import sys
import types

import pytest

import spec
import xstream
from test_bench_trace import DATA, _plane

KERNEL = "cupso_fused_queue_lock"
SHARE = spec.load_reader("kernel.stream_share")
STEP = spec.load_reader("kernel.step_us")

# chip 0: a warm-up launch [0, 500) before the window [1000, 10000), two
# launches inside it, one that starts inside and ends after it, and other
# device operations that are no accounted kernel.
HAND = (
    _plane(1, "/device:TPU:0", "XLA Ops",
           [(1, 0, 500), (2, 600, 900), (1, 2000, 4000), (3, 4000, 4500),
            (1, 5000, 6000), (4, 7000, 8000), (1, 9500, 10500)],
           {1: f"%{KERNEL}.1 = (f32[8,512]) custom-call()",
            2: "%fusion.3 = f32[8] fusion()", 3: "%copy.1 = f32[8] copy()",
            4: "%cupso_other.2 = (f32[8,512]) custom-call()"})
    + _plane(2, "/host:CPU", "main", [(1, 1000, 10000)],
             {1: "bench.window"}))


def _ctx(streams, launches, peak=1e12):
    return {"streams": streams, "launches": launches,
            "peak": {"hbm_bytes_per_s": peak}, "log": lambda msg: None}


def test_launches_count_only_the_window():
    from jax.profiler import ProfileData
    got = xstream.launches(ProfileData.from_text_proto(HAND),
                           {KERNEL, "cupso_other"})
    # the warm-up launch is out; the launch across the end is in, whole
    assert got == {KERNEL: (3, pytest.approx((2000 + 1000 + 1000) * 1e-9)),
                   "cupso_other": (1, pytest.approx(1000e-9))}


def test_readers_on_a_synthetic_window():
    # 3 launches of 100 grid steps and 1e6 bytes each in 4 us of device
    # time, and an unaccounted kernel that does not count
    ctx = _ctx({KERNEL: (100, 1_000_000)},
               {KERNEL: (3, 4e-6), "cupso_other": (5, 1.0)})
    assert SHARE.read(ctx) == pytest.approx(100 * 3e6 / 4e-6 / 1e12)
    assert STEP.read(ctx) == pytest.approx(1e6 * 4e-6 / 300)


@pytest.mark.parametrize("streams,launches", [
    ({}, {KERNEL: (3, 4e-6)}),                 # no kernel accounted
    ({KERNEL: (100, 10**6)}, {}),              # no launch in the window
    ({KERNEL: (100, 10**6)}, {KERNEL: (0, 0.0)}),
    ({KERNEL: (100, 10**6)}, {"cupso_other": (5, 1.0)}),
], ids=["no-account", "no-launches", "zero-launches", "other-kernel"])
def test_readers_read_nothing_without_accounted_launches(streams, launches):
    ctx = _ctx(streams, launches)
    assert SHARE.read(ctx) is None and STEP.read(ctx) is None


def test_a_program_without_the_account_reads_nothing(monkeypatch):
    # an older program: repro.telemetry without kernel_streams
    monkeypatch.setitem(sys.modules, "repro.telemetry",
                        types.ModuleType("repro.telemetry"))
    ctx = {"launches": {KERNEL: (3, 4e-6)}, "log": lambda msg: None}
    assert xstream.accounts(ctx) is None
    assert SHARE.read(ctx) is None and STEP.read(ctx) is None


def test_recorded_chip_trace_reads_the_t4_kernel():
    """Three t4 solves on one TPU v5e: each launch streams 256 blocks of
    [8, 512] over 200 iterations, 51,200 grid steps of 2 x 51,200 bytes,
    plus the gbest column [8, 1] in and out."""
    from jax.profiler import ProfileData
    with open(os.path.join(DATA, "t4_solves.xspace.txt")) as f:
        pd = ProfileData.from_text_proto(f.read())
    launches = xstream.launches(pd, {KERNEL})
    assert launches[KERNEL][0] == 3
    steps = 200 * 256
    per = 2 * (steps * (3 * 8 + 1) * 512 * 4 + 8 * 4)
    ctx = _ctx({KERNEL: (steps, per)}, launches, peak=8.19e11)
    seconds = launches[KERNEL][1]
    assert STEP.read(ctx) == pytest.approx(1e6 * seconds / (3 * steps))
    assert SHARE.read(ctx) == pytest.approx(
        100 * 3 * per / seconds / 8.19e11)
    # about 0.32 us a step and 39% of the HBM bandwidth
    assert 0.3 < STEP.read(ctx) < 0.35 and 35 < SHARE.read(ctx) < 42

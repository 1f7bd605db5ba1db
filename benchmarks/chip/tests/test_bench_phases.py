"""The program's spans in a traced window (``xphases``) and the readers
built on them, on a trace written out by hand and on the recorded chip
trace of ``test_bench_trace.py``, against values counted by hand."""
import os

import pytest

import spec
import xphases
import xtrace
from test_bench_trace import DATA, _plane

# The program's spans (``repro.*``) nested in the benchmark's: chip 0 busy
# [2000, 3000) and [6000, 7000) in the window [1000, 10000), so its idle
# gaps are [1000, 2000), [3000, 6000) and [7000, 10000).
PROGRAM = (
    _plane(1, "/device:TPU:0", "XLA Ops", [(1, 2000, 3000), (2, 6000, 7000)],
           {1: "%cupso_fused_queue_lock.1 = (f32[8,512]) custom-call()",
            2: "%copy.1 = f32[8] copy()"})
    + _plane(2, "/host:CPU", "python",
             [(1, 1000, 10000), (2, 1550, 8100), (3, 8100, 10600),
              (4, 500, 1500),      # the last call's init, across the start
              (5, 1600, 8000), (4, 1700, 2500), (6, 2500, 6500),
              (7, 8200, 8600), (7, 8400, 8800),   # overlapping: union
              (7, 9800, 10500)],                  # across the end
             {1: "bench.window", 2: "solve", 3: "result", 4: "repro.init",
              5: "repro.solve", 6: "repro.launch", 7: "repro.readback"}))


@pytest.fixture(scope="module")
def program_pd():
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(PROGRAM)


@pytest.fixture(scope="module")
def program(program_pd):
    return xphases.reduce(program_pd)


def test_phases_count_wall_and_idle(program):
    ph = {k: (n, pytest.approx(w * 1e-9), pytest.approx(i * 1e-9))
          for k, (n, w, i) in {
              # one call: its gaps [1600, 2000), [3000, 6000), [7000, 8000)
              "repro.solve": (1, 6400, 400 + 3000 + 1000),
              # the straddler counts in wall and idle, not in count
              "repro.init": (1, 500 + 800, 500 + 300),
              "repro.launch": (1, 4000, 3000),
              # wall sums every event; idle takes their union once
              "repro.readback": (3, 400 + 400 + 200, 600 + 200),
          }.items()}
    assert program.by_name == ph
    assert program.window_s == pytest.approx(9000e-9)


def test_idle_under_program_spans(program):
    # idle 1000 + 3000 + 3000; program spans cover [1000, 1500),
    # [1600, 2000), [3000, 6000), [7000, 8000), [8200, 8800), [9800, 10000)
    assert program.idle_s == pytest.approx(7000e-9)
    assert program.under_program_s == pytest.approx(
        (500 + 400 + 3000 + 1000 + 600 + 200) * 1e-9)
    lines = xphases.log_lines(program)
    assert lines[0].startswith("program spans: ")
    assert any(line.startswith("program span repro.init: count 1")
               for line in lines)


def test_longest_gaps_named_by_innermost_program_span(program_pd, program):
    assert program.idle_gaps == [
        ("repro.launch", pytest.approx(3000e-9)),    # inside solve
        ("repro.readback", pytest.approx(3000e-9)),  # inside result
        ("idle", pytest.approx(1000e-9))]            # middle 1500: none
    # the benchmark's own breakdown names them as before
    b = xtrace.summarize(program_pd, chips=1).breakdown()
    assert [n for n, _ in b["idle_gaps"]] == ["solve", "result", "idle"]


def test_attribute_prefers_the_program_on_a_tie():
    for spans in ([("solve", 0, 100), ("repro.solve", 0, 100)],
                  [("repro.solve", 0, 100), ("solve", 0, 100)]):
        assert xphases.attribute((10, 20), spans) == "repro.solve"
    assert xphases.attribute((10, 20), [("solve", 0, 100),
                                        ("repro.solve", 0, 200)]) == "solve"
    assert xphases.attribute((10, 20), [("repro.init", 30, 40)]) == "idle"


def test_covered_sweep():
    free = [(0, 10), (20, 30), (40, 50)]
    assert xphases.covered([(5, 45)], free) == 5 + 10 + 5
    assert xphases.covered([(25, 27), (5, 8), (26, 29)], free) == 3 + 4
    assert xphases.covered([(10, 20), (60, 70)], free) == 0
    assert xphases.covered([], free) == 0


def test_recorded_trace_has_no_program_spans():
    from jax.profiler import ProfileData
    with open(os.path.join(DATA, "t4_solves.xspace.txt")) as f:
        pd = ProfileData.from_text_proto(f.read())
    ph, s = xphases.reduce(pd), xtrace.summarize(pd, chips=1)
    assert ph.by_name == {} and ph.under_program_s == 0.0
    assert ph.window_s == s.window_s
    assert ph.idle_s == pytest.approx(s.window_s - s.busy_s, abs=1e-12)
    # without program spans the gaps are named as the benchmark names them
    assert [list(g) for g in ph.idle_gaps] == s.breakdown()["idle_gaps"]
    ctx = {"phases": ph, "trace": s}
    for name in ("engine.init_ms", "engine.launch_ms", "engine.readback_ms",
                 "sched.host_ms"):
        assert spec.load_reader(name).read(ctx) is None


def test_of_reads_the_newest_trace_once(tmp_path, program_pd):
    from jax.profiler import ProfileData
    assert xphases.of({}, root=str(tmp_path)) is None       # no trace
    cell_dir = tmp_path / "some.cell" / "plugins"
    cell_dir.mkdir(parents=True)
    (cell_dir / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(PROGRAM))
    summary = xtrace.summarize(program_pd, chips=1)
    logged = []
    ctx = {"trace": summary, "log": logged.append}
    ph = xphases.of(ctx, root=str(tmp_path))
    assert ph.by_name["repro.launch"][2] == pytest.approx(3000e-9)
    assert logged == xphases.log_lines(ph)
    assert xphases.of(ctx, root=str(tmp_path)) is ph       # read once
    assert len(logged) == len(xphases.log_lines(ph))
    # a trace of another window is not this run's
    other = xtrace.Summary(window_s=1.0, busy_s=0.5, kernel_s=0.0, ops={},
                           idle_gaps=[])
    assert xphases.of({"trace": other}, root=str(tmp_path)) is None


def _phases(by_name):
    return xphases.Phases(window_s=1.0, idle_s=0.5, under_program_s=0.5,
                          by_name=by_name, idle_gaps=[])


def test_engine_readers(program_pd, program):
    ctx = {"phases": program, "trace": xtrace.summarize(program_pd, chips=1)}
    init = spec.load_reader("engine.init_ms").read(ctx)
    launch = spec.load_reader("engine.launch_ms").read(ctx)
    readback = spec.load_reader("engine.readback_ms").read(ctx)
    assert init == pytest.approx(800e-6)            # ms, one call
    assert launch == pytest.approx(3000e-6)         # no repro.prepare
    assert readback == pytest.approx(800e-6)        # no repro.rows
    t = ctx["trace"]
    assert init + launch + readback <= 1e3 * (t.window_s - t.busy_s) / 1


def test_engine_readers_per_call_and_absent():
    two = _phases({"repro.solve_many": (2, 3.0, 0.5),
                   "repro.rows": (2, 0.2, 0.1),
                   "repro.readback": (250, 0.3, 0.3),
                   "repro.prepare": (4, 0.01, 0.01)})
    ctx = {"phases": two}
    assert spec.load_reader("engine.readback_ms").read(ctx) \
        == pytest.approx(1e3 * 0.4 / 2)
    assert spec.load_reader("engine.launch_ms").read(ctx) \
        == pytest.approx(1e3 * 0.01 / 2)
    assert spec.load_reader("engine.init_ms").read(ctx) is None
    for name in ("engine.init_ms", "engine.launch_ms", "engine.readback_ms",
                 "sched.host_ms"):
        assert spec.load_reader(name).read({"phases": _phases({})}) is None
        assert spec.load_reader(name).read({"phases": None}) is None


def test_sched_host_reader():
    ctx = {"phases": _phases({"repro.sched.step": (10, 0.030, 0.02),
                              "repro.sched.dispatch": (10, 0.013, 0.0),
                              "repro.sched.admit": (3, 0.002, 0.002)})}
    assert spec.load_reader("sched.host_ms").read(ctx) == pytest.approx(1.7)
    no_dispatch = {"phases": _phases({"repro.sched.step": (4, 0.004, 0.0)})}
    assert spec.load_reader("sched.host_ms").read(no_dispatch) \
        == pytest.approx(1.0)

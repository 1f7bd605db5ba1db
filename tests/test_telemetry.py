"""Telemetry: in-kernel contention counters vs the eager oracle, the
universal convergence traces, and the Perfetto/Prometheus exporters.

The counter pins here are exact event counts, not tolerances: the
kernels and the ref.py oracles count at the same program points, so any
drift in either is a semantic change. ``block_improvements`` counts
per BLOCK-INVOCATION (one event per (iteration, block) where any lane
improved its pbest), so it scales with ``block_n`` — the pinned shape
uses ``block_n=64`` (two blocks of 128 particles) throughout.
"""
import json

import numpy as np
import pytest

import repro
from repro.api import History, Method, solve, solve_many
from repro.core import PSOConfig, batch_row, init_batch, init_swarm
from repro.kernels import ops, ref
from repro.serving.metrics import LatencyStat, ServingMetrics
from repro.telemetry import (COUNTER_NAMES, SLOTS_PER_SWARM, KernelCounters,
                             TraceWriter, prometheus_text, zero_counts)

# the pinned validation shape: dim=2 cubic, 128 particles, two blocks
DIM, N, BN, ITERS, SEED = 2, 128, 64, 12, 5
PINNED = {"queue_updates": 1, "publications": 1, "block_improvements": 11}


def _cfg(fitness="cubic", dim=DIM):
    return PSOConfig(dim=dim, particle_cnt=N, fitness=fitness).resolved()


def _oracle_kwargs(cfg, dim):
    kw = ops._cfg_kwargs(cfg)
    kw["d_real"] = dim
    return kw


def _oracle_counts(cfg, s, iters, *, sync_every=None):
    """Eager-oracle event counts for the same run."""
    dim = s.pos.shape[1]
    scal, pos, vel, pbp, pbf, gp, gf = ops.state_to_kernel(s, dim)
    kw = _oracle_kwargs(cfg, dim)
    fitness = kw.pop("fitness")
    cnt = {}
    if sync_every is None:
        ref.run_fused_oracle(int(s.seed), int(s.iteration), pos, vel, pbp,
                             pbf, gp, gf, iters, BN, fitness=fitness,
                             counters=cnt, **kw)
    else:
        ref.run_fused_async_oracle(int(s.seed), int(s.iteration), pos, vel,
                                   pbp, pbf, gp, float(gf[0]), iters, BN,
                                   sync_every, fitness=fitness,
                                   counters=cnt, **kw)
    return {k: cnt.get(k, 0) for k in COUNTER_NAMES}


# ---------------------------------------------------------------- counters

def test_sync_kernel_counters_match_oracle():
    cfg = _cfg()
    s = init_swarm(cfg, SEED)
    _, cnt = ops.run_queue_lock_fused(cfg, s, iters=ITERS, block_n=BN,
                                      telemetry=True)
    got = KernelCounters.from_array(cnt).as_dict()
    assert got == _oracle_counts(cfg, s, ITERS) == PINNED


def test_async_kernel_counters_match_oracle():
    cfg = _cfg()
    s = init_swarm(cfg, SEED)
    _, cnt = ops.run_queue_lock_fused_async(cfg, s, iters=ITERS,
                                            sync_every=4, block_n=BN,
                                            telemetry=True)
    got = KernelCounters.from_array(cnt).as_dict()
    assert got == _oracle_counts(cfg, s, ITERS, sync_every=4)


def test_batched_counters_match_standalone():
    """Row s of the batched counter buffer == the standalone run's."""
    cfg = _cfg()
    b = init_batch(cfg, (5, 6, 7))
    _, cnt = ops.run_queue_lock_fused_batch(cfg, b, iters=ITERS, block_n=BN,
                                            telemetry=True)
    rows = KernelCounters.rows(cnt)
    assert len(rows) == 3 and cnt.size == 3 * SLOTS_PER_SWARM
    for i in (0, 1, 2):
        _, c1 = ops.run_queue_lock_fused(cfg, batch_row(b, i), iters=ITERS,
                                         block_n=BN, telemetry=True)
        assert rows[i] == KernelCounters.from_array(c1)


def test_counters_disabled_by_default():
    cfg = _cfg()
    s = init_swarm(cfg, SEED)
    out = ops.run_queue_lock_fused(cfg, s, iters=2, block_n=BN)
    assert hasattr(out, "gbest_fit")        # the state itself, not a pair


def test_counters_additive_across_chunks():
    """Chunked launches sum to the uninterrupted run's counts."""
    cfg = _cfg()
    s = init_swarm(cfg, SEED)
    tot = None
    for k in (5, 4, 3):
        s, cnt = ops.run_queue_lock_fused(cfg, s, iters=k, block_n=BN,
                                          telemetry=True)
        c = KernelCounters.from_array(cnt)
        tot = c if tot is None else tot + c
    assert tot.as_dict() == PINNED


def test_kernel_counters_helpers():
    z = zero_counts(2)
    assert z.shape == (2 * SLOTS_PER_SWARM,) and int(z.sum()) == 0
    c = KernelCounters(queue_updates=1, publications=2,
                       block_improvements=3)
    assert (c + c).as_dict() == {"queue_updates": 2, "publications": 4,
                                 "block_improvements": 6}
    with pytest.raises(ValueError):
        KernelCounters.from_array(np.zeros(4, np.int32))


# ------------------------------------------------------------- api surface

def test_result_telemetry():
    r = solve("cubic", dim=DIM, particles=N, iters=ITERS, seed=SEED,
              variant="queue_lock", backend="kernel", block_n=BN,
              telemetry=True)
    assert isinstance(r.telemetry, KernelCounters)
    assert r.telemetry.as_dict() == PINNED
    # off by default: no counter plumbing in the result
    r0 = solve("cubic", dim=DIM, particles=N, iters=ITERS, seed=SEED,
               variant="queue_lock", backend="kernel", block_n=BN)
    assert r0.telemetry is None
    assert float(r0.state.gbest_fit) == float(r.state.gbest_fit)


def test_telemetry_method_validation():
    with pytest.raises(ValueError, match="telemetry"):
        Method(variant="queue_lock", backend="jnp", telemetry=True)
    with pytest.raises(ValueError, match="telemetry"):
        Method(variant="queue", telemetry=True)   # no queue kernel
    with pytest.raises(ValueError, match="telemetry"):
        Method(variant="queue_lock", islands=2, telemetry=True)
    # telemetry alone resolves to the kernel backend
    m = Method(variant="queue_lock", telemetry=True)
    assert m.resolve_backend(repro.resolve_problem("cubic")) \
        == "kernel"


def test_record_history_on_kernel_backend():
    """The former ValueError combo: history via chunk-boundary readback."""
    r = solve("cubic", dim=DIM, particles=N, iters=ITERS, seed=SEED,
              variant="queue_lock", backend="kernel", block_n=BN,
              record_history=True, telemetry=True)
    h = r.history
    assert isinstance(h, History) and len(h) == ITERS
    assert h.iteration[-1] == ITERS
    assert float(h.gbest_fit[-1]) == float(r.state.gbest_fit)
    assert np.all(np.diff(h.gbest_fit) >= 0)      # gbest is monotone
    assert r.telemetry.as_dict() == PINNED        # counters ride along
    # async kernel: sampled at sync_every publication boundaries
    ra = solve("cubic", dim=DIM, particles=N, iters=ITERS, seed=SEED,
               variant="async", backend="kernel", block_n=BN, sync_every=4,
               record_history=True)
    assert list(ra.history.iteration) == [4, 8, 12]
    assert float(ra.history.gbest_fit[-1]) == float(ra.state.gbest_fit)


def test_record_history_islands_still_precise_error():
    with pytest.raises(ValueError, match="single-device"):
        Method(variant="queue", islands=2, record_history=True)


def test_solve_many_row_histories():
    seeds = (5, 6, 7)
    res = solve_many("cubic", seeds, dim=DIM, particles=N, iters=ITERS,
                     variant="queue_lock", backend="kernel", block_n=BN,
                     record_history=True, telemetry=True)
    assert len(res) == 3
    for i, r in enumerate(res):
        single = solve("cubic", dim=DIM, particles=N, iters=ITERS,
                       seed=seeds[i], variant="queue_lock",
                       backend="kernel", block_n=BN, record_history=True,
                       telemetry=True)
        assert float(r.history.gbest_fit[-1]) == float(r.state.gbest_fit)
        assert r.history.iteration[-1] == ITERS
        assert r.telemetry == single.telemetry
        np.testing.assert_array_equal(r.history.gbest_fit,
                                      single.history.gbest_fit)


def test_solve_many_hetero_histories():
    res = solve_many(problems=["cubic", "sphere", "rastrigin"],
                     seeds=(5, 6, 7), dim=DIM, particles=N, iters=ITERS,
                     variant="queue_lock", backend="kernel", block_n=BN,
                     record_history=True, telemetry=True)
    assert len(res) == 3
    pins = [PINNED,
            {"queue_updates": 3, "publications": 3,
             "block_improvements": 24},
            {"queue_updates": 4, "publications": 4,
             "block_improvements": 24}]
    for r, pin in zip(res, pins):
        assert r.telemetry.as_dict() == pin
        assert float(r.history.gbest_fit[-1]) == float(r.state.gbest_fit)


# ---------------------------------------------------------------- exporters

def test_trace_writer_schema(tmp_path):
    tw = TraceWriter()
    tw.complete("chunk", 100.0, 50.0, process="solver", thread="chunks",
                cat="solve", args={"iters": 4})
    tw.instant("admit t0", 120.0, process="serving", thread="lane 0")
    tw.counter("lane 0 fill", 130.0, {"active": 3, "idle": 1})
    p = tmp_path / "trace.json"
    tw.write(str(p))
    doc = json.load(open(p))
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    evs = doc["traceEvents"]
    phs = {e["ph"] for e in evs}
    assert {"M", "X", "i", "C"} <= phs
    for e in evs:
        assert {"name", "ph", "pid"} <= set(e)
        if e["ph"] == "X":
            assert e["dur"] == 50.0 and "ts" in e
    # ts rebased: earliest non-meta event sits at 0
    tss = [e["ts"] for e in evs if "ts" in e]
    assert min(tss) == 0.0


def test_prometheus_exposition():
    m = ServingMetrics()
    m.inc("completed", 3)
    m.observe("e2e_us", 100.0)
    m.observe("e2e_us", 300.0)
    text = m.prometheus(kernel_counters=PINNED)
    lines = text.splitlines()
    assert any(l.startswith("repro_completed_total 3") for l in lines)
    assert "# TYPE repro_completed_total counter" in lines
    assert "# TYPE repro_uptime_seconds gauge" in lines
    assert any('repro_span_latency_microseconds{span="e2e_us",quantile='
               in l for l in lines)
    assert 'repro_span_latency_microseconds_count{span="e2e_us"} 2' in lines
    assert "repro_kernel_publications_total 1" in lines
    assert "repro_kernel_block_improvements_total 11" in lines
    # bare-function path with a custom prefix
    t2 = prometheus_text(m.snapshot(), prefix="pso")
    assert any(l.startswith("pso_completed_total") for l in t2.splitlines())


def test_solve_stream_trace_and_history(tmp_path):
    from repro.api import solve_stream
    from repro.launch.serve import SolveRequest
    reqs = [SolveRequest(fitness="cubic", dim=DIM, particle_cnt=N,
                         iters=12, seed=5, variant="async", sync_every=4),
            SolveRequest(fitness="sphere", dim=3, particle_cnt=N,
                         iters=16, seed=6, variant="async", sync_every=4),
            SolveRequest(fitness="cubic", dim=DIM, particle_cnt=N,
                         iters=12, seed=9, variant="queue")]
    p = tmp_path / "trace.json"
    res = solve_stream(reqs, lane_width=4, record_history=True,
                       trace_path=str(p))
    for r in res[:2]:        # lane-riding async rows get histories
        h = r.history
        assert h is not None and h.iteration[-1] == r.request.iters
        assert float(h.gbest_fit[-1]) == pytest.approx(r.gbest_fit)
    assert res[2].history is None           # standalone fallback: no lane
    doc = json.load(open(p))
    evs = doc["traceEvents"]
    names = {e["name"] for e in evs}
    assert any(n.startswith("admit t") for n in names)
    assert any(n.startswith("chunk ") for n in names)
    assert any(n.startswith("request t") for n in names)
    assert any(n.startswith("standalone t") for n in names)
    for e in evs:
        assert {"name", "ph", "pid"} <= set(e)


# ------------------------------------------------------------ LatencyStat

def test_latency_stat_percentile_edges():
    st = LatencyStat()
    assert st.percentile(0) == 0.0 and st.percentile(100) == 0.0  # empty
    st.add(42.0)
    for q in (0, 50, 100):
        assert st.percentile(q) == 42.0                      # single sample
    st.add(10.0)
    assert st.percentile(0) == 10.0 and st.percentile(100) == 42.0


def test_latency_stat_merge_from_overflow():
    """Both reservoirs past cap: exact count/total, sane percentiles."""
    cap = 8
    a, b = ServingMetrics(span_cap=cap), ServingMetrics(span_cap=cap)
    for i in range(20):
        a.observe("x_us", 100.0)
    for i in range(30):
        b.observe("x_us", 200.0)
    a.merge_from(b)
    st = a.span("x_us")
    assert st.count == 50                                    # exact
    assert st.total_us == pytest.approx(20 * 100.0 + 30 * 200.0)
    assert st.mean_us == pytest.approx(160.0)
    assert len(st._samples) <= 2 * cap
    assert 100.0 <= st.p50_us <= 200.0 and 100.0 <= st.p99_us <= 200.0
    a.merge_from(None)                                       # no-op
    assert a.span("x_us").count == 50


def test_profiler_session_raises_when_trace_cannot_start(monkeypatch,
                                                         tmp_path):
    """Asking for a trace and not getting one is an error, not a silent
    run without it; no logdir stays a no-op."""
    from jax import profiler

    from repro.telemetry import profiler_session

    def refuse(logdir, *a, **k):
        raise RuntimeError("profiler unavailable")
    monkeypatch.setattr(profiler, "start_trace", refuse)
    with pytest.raises(RuntimeError, match="profiler unavailable"):
        with profiler_session(str(tmp_path)):
            pass
    with profiler_session(None) as started:
        assert started is False


# ------------------------------------------------------------ profiler spans

def _profiled_spans(logdir):
    """``{name: [(start_ns, end_ns), ...]}`` of the ``repro.*`` host events
    of the one profile under ``logdir``."""
    import glob
    import os

    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(files) == 1
    out = {}
    for p in ProfileData.from_file(files[0]).planes:
        for line in p.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    out.setdefault(ev.name, []).append(
                        (int(ev.start_ns), int(ev.end_ns)))
    return out


def _inside(spans, inner, *outer):
    """Every ``inner`` span lies inside some span named in ``outer``."""
    around = [iv for name in outer for iv in spans.get(name, ())]
    return all(any(s0 <= s and e <= e0 for s0, e0 in around)
               for s, e in spans[inner])


def _span_paths():
    """A kernel and a jnp solve, a hetero ``solve_many`` and three
    scheduler steps (a row finishing at a chunk boundary, a row ejected
    with a remainder); returns every answer they gave."""
    from repro.launch.serve import SolveRequest
    from repro.serving import ContinuousScheduler
    out = []
    for backend in ("kernel", "jnp"):
        r = solve("cubic", dim=DIM, particles=N, iters=4, seed=SEED,
                  variant="queue_lock", backend=backend)
        out += [r.gbest_fit, r.best_fit, r.best_pos]
    for r in solve_many(problems=["sphere", "rastrigin"], seeds=(1, 2),
                        dim=DIM, particles=N, iters=4, variant="async",
                        backend="jnp", sync_every=2):
        out += [r.best_fit, r.best_pos]
    sched = ContinuousScheduler(lane_width=4)
    for iters, seed in ((8, 1), (6, 2)):
        sched.submit(SolveRequest(fitness="sphere", dim=DIM, particle_cnt=N,
                                  iters=iters, seed=seed, variant="async",
                                  sync_every=4))
    for _ in range(3):
        out += [r.gbest_fit for r in sched.step().values()]
    assert not sched.busy
    return out


def test_profiler_spans_nest_on_the_host_plane(tmp_path):
    import jax
    from repro.telemetry import SPAN_NAMES
    with jax.profiler.trace(str(tmp_path)):
        traced = _span_paths()
    spans = _profiled_spans(str(tmp_path))
    names = SPAN_NAMES
    taken = {names.solve, names.solve_many, names.prepare, names.init,
             names.launch, names.rows, names.readback, names.sched_step,
             names.sched_admit, names.sched_dispatch, names.sched_eject,
             names.sched_finish}
    assert set(spans) == taken
    assert len(spans[names.solve]) == 2 and len(spans[names.solve_many]) == 1
    assert len(spans[names.sched_step]) == 3
    for inner in (names.prepare, names.init, names.launch):
        assert _inside(spans, inner, names.solve, names.solve_many), inner
    assert _inside(spans, names.rows, names.solve_many)
    for inner in (names.sched_admit, names.sched_dispatch, names.sched_eject,
                  names.sched_finish):
        assert _inside(spans, inner, names.sched_step)
    # readbacks come after the entry calls, not inside them
    assert not any(_inside({"r": [iv], **spans}, "r", names.solve,
                           names.solve_many)
                   for iv in spans[names.readback])
    # without a session the same calls give the same answers, and an
    # empty session afterwards holds nothing of them
    untraced = _span_paths()
    for a, b in zip(traced, untraced):
        np.testing.assert_array_equal(a, b)
    empty = tmp_path / "empty"
    with jax.profiler.trace(str(empty)):
        pass
    assert _profiled_spans(str(empty)) == {}


def test_span_feeds_its_sinks_from_one_reading():
    from repro.telemetry import SPAN_NAMES, span
    m, tw = ServingMetrics(), TraceWriter()
    with span(SPAN_NAMES.sched_dispatch, metrics=m, metric="dispatch_us",
              trace=tw, event=lambda: ("chunk 1", {"process": "serving"})
              ) as sp:
        sum(range(1000))
    assert m.span("dispatch_us").total_us == sp.dur_us > 0
    (ev,) = [e for e in tw.to_dict()["traceEvents"] if e["ph"] == "X"]
    assert ev["name"] == "chunk 1" and ev["dur"] == sp.dur_us
    with span(SPAN_NAMES.chunk, trace=tw):
        pass
    assert [e["name"] for e in tw.to_dict()["traceEvents"]
            if e["ph"] == "X"] == ["chunk 1", SPAN_NAMES.chunk]
    with pytest.raises(ValueError):      # a region that raised: no sample
        with span(SPAN_NAMES.sched_dispatch, metrics=m, metric="dispatch_us"):
            raise ValueError("boom")
    assert m.span("dispatch_us").count == 1
    with span(SPAN_NAMES.init) as bare:  # no sink: no clock reads
        pass
    assert bare.t0_us is None and bare.dur_us is None


def test_span_names_are_the_programs_only_spans():
    """Every span the program opens names a ``SPAN_NAMES`` field, and every
    name starts with the prefix the benchmark's trace reduction reads."""
    import os
    import re
    import repro as pkg
    from repro.telemetry import SPAN_NAMES
    assert all(n.startswith("repro.") for n in SPAN_NAMES)
    assert len(set(SPAN_NAMES)) == len(SPAN_NAMES)
    root = os.path.dirname(pkg.__file__)
    used = set()
    for d, _, files in os.walk(root):
        for f in files:
            if f.endswith(".py"):
                src = open(os.path.join(d, f)).read()
                assert "TraceAnnotation(" not in src or f == "trace.py", f
                used |= set(re.findall(r"span\(SPAN_NAMES\.(\w+)", src))
    assert used == set(SPAN_NAMES._fields)

"""The serving subsystem: continuous-batching scheduler, persistent AOT
compile cache, metrics — plus the flush server's failure isolation and
bucket-ladder edge cases (repro.serving, repro.launch.serve).

The load-bearing assertions:

* every result out of ``ContinuousScheduler`` agrees with the standalone
  ``solve(cfg, seed, T, "async", sync_every)`` of that request under the
  contract of tests/agreement.py (a lane program and a standalone solve
  are two compiled programs) — across heterogeneous lanes, row swaps
  mid-flight, tail ejections (sub-chunk remainders) and sub-chunk
  standalone fallbacks;
* a second ``CompileCache`` over the same directory serves the same
  trace from deserialized ``jax.export`` blobs with ``trace_events ==
  0`` (the zero-recompile restart story) and bitwise-equal results.
"""
from __future__ import annotations

import numpy as np
import pytest

import agreement
from repro.core.pso import PSOConfig, solve
from repro.launch.serve import SolveRequest

NAMES = ("cubic", "sphere", "rastrigin", "ackley", "griewank", "rosenbrock")
DIM, N, SE = 10, 128, 8


def _req(k, iters, fitness=None, variant="async"):
    return SolveRequest(dim=DIM, particle_cnt=N,
                        fitness=fitness or NAMES[k % len(NAMES)],
                        seed=k, iters=iters, variant=variant, sync_every=SE)


def _standalone(r):
    cfg = PSOConfig(dim=r.dim, particle_cnt=r.particle_cnt,
                    fitness=r.fitness, dtype=r.dtype)
    return solve(cfg, r.seed, r.iters, r.variant, r.sync_every)


def _assert_bit_exact(results, reqs):
    for res, r in zip(results, reqs):
        st = _standalone(r)
        assert res.ok
        assert res.gbest_fit == float(st.gbest_fit), (r.fitness, r.iters)
        np.testing.assert_array_equal(res.gbest_pos,
                                      np.asarray(st.gbest_pos))


def _assert_agree(results, reqs):
    for res, r in zip(results, reqs):
        st = _standalone(r)
        assert res.ok
        msg = f"{r.fitness} {r.iters}"
        agreement.assert_fit_close(res.gbest_fit, st.gbest_fit, msg)
        cfg = PSOConfig(dim=r.dim, particle_cnt=r.particle_cnt,
                        fitness=r.fitness, dtype=r.dtype)
        agreement.assert_swarm_close(res.gbest_pos, st.gbest_pos,
                                     agreement.box_span(cfg), msg)


# -- the tentpole: chunk-boundary admission, under the contract ------------

def test_scheduler_bit_exact_vs_standalone_mixed_trace():
    """11 mixed requests through one hetero lane of width 8: multiples of
    sync_every (pure lane rides), a non-multiple (tail ejection), a
    sub-chunk budget (standalone fallback), and more requests than slots
    (row swaps at chunk boundaries). Every answer must agree with its
    standalone solve."""
    from repro.serving import ContinuousScheduler
    reqs = [_req(k, iters) for k, iters in
            enumerate((16, 8, 24, 16, 8, 16, 24, 8, 16))]
    reqs.append(_req(9, 20))      # 2 chunks + remainder 4 -> ejection
    reqs.append(_req(10, 4))      # < sync_every -> standalone
    sched = ContinuousScheduler(lane_width=8)
    results = sched.run(reqs)
    _assert_agree(results, reqs)
    m = sched.metrics
    assert m.get("completed") == len(reqs)
    assert m.get("row_swaps") >= 1        # a freed slot was re-admitted
    assert m.get("tail_ejections") == 1
    assert m.get("standalone_solves") == 1
    assert 0.0 < m.batch_fill <= 1.0
    snap = sched.snapshot()
    assert snap["lanes"] and snap["lanes"][0]["active"] == 0


def test_scheduler_sync_variant_runs_standalone():
    """Synchronous variants have no chunk boundary to preempt at: they
    bypass the lanes entirely and still agree."""
    from repro.serving import ContinuousScheduler
    reqs = [_req(0, 12, variant="queue"), _req(1, 16)]
    sched = ContinuousScheduler()
    results = sched.run(reqs)
    _assert_agree(results, reqs)
    assert sched.metrics.get("standalone_solves") == 1


def test_scheduler_homogeneous_lane_for_custom_problem():
    """A custom Problem is not hetero-eligible: it gets its own
    content-keyed lane, same bit-exactness contract."""
    import jax.numpy as jnp

    from repro.core.problem import Problem
    from repro.serving import ContinuousScheduler
    prob = Problem(name="serving_quad",
                   fn=lambda x: -jnp.sum((x - 1.0) ** 2, axis=-1),
                   lo=-5.0, hi=5.0)
    reqs = [_req(k, 16, fitness=prob) for k in range(3)]
    results = ContinuousScheduler(lane_width=8).run(reqs)
    _assert_bit_exact(results, reqs)


# -- a dispatch runs until the lane's next row leaves ----------------------

def _chunk_counts(trace):
    """The chunk count of every lane dispatch a ``TraceWriter`` saw."""
    return [e["args"]["chunks"] for e in trace.to_dict()["traceEvents"]
            if e.get("cat") == "dispatch"]


def test_scheduler_dispatch_runs_until_next_row_leaves():
    """One lane of 4 holding rows with different remaining budgets: each
    dispatch carries the fewest whole chunks any row has left. A row is
    admitted mid-flight into the slot a finished row freed, a row leaves
    with its remainder, a sub-chunk request runs standalone, and every
    answer agrees with its standalone solve."""
    from repro.serving import ContinuousScheduler
    from repro.telemetry import TraceWriter
    first = [_req(0, 40), _req(1, 16), _req(2, 28), _req(3, 4)]
    late = _req(4, 24)
    tw = TraceWriter()
    sched = ContinuousScheduler(lane_width=4, trace=tw)
    tickets = [sched.submit(r) for r in first]
    sched.step()             # 5, 2 and 3 chunks left: 2 run, row 1 leaves
    tickets.append(sched.submit(late))
    done = sched.drain()     # then 1 chunk (row 2 reaches its remainder), 2
    _assert_agree([done[t] for t in tickets], first + [late])
    m = sched.metrics
    assert _chunk_counts(tw) == [2, 1, 2]
    assert m.get("dispatches") == 3 and m.get("chunks") == 5
    assert m.get("lane_slots") == 5 * 4            # counted per chunk
    assert m.get("lane_active_slots") == 2 * 3 + 1 * 3 + 2 * 2
    assert m.get("row_swaps") == 1
    assert m.get("tail_ejections") == 1
    assert m.get("standalone_solves") == 1
    assert sched.snapshot()["lanes"][0]["chunks"] == 5


@pytest.mark.parametrize("iters,chunks,ejections",
                         [(SE, 1, 0), (5 * SE, 5, 0), (5 * SE + 4, 5, 1)])
def test_scheduler_lone_request_one_dispatch(iters, chunks, ejections):
    """A lone request of n whole chunks is served in one dispatch of n
    chunks; a remainder then leaves the lane on its own."""
    from repro.serving import ContinuousScheduler
    req = _req(0, iters)
    sched = ContinuousScheduler(lane_width=8)
    _assert_agree(sched.run([req]), [req])
    m = sched.metrics
    assert m.get("dispatches") == 1 and m.get("chunks") == chunks
    assert m.get("tail_ejections") == ejections


def test_scheduler_history_samples_every_chunk_boundary():
    """With ``record_history`` a dispatch carries one chunk, so every row
    keeps a sample at each of its chunk boundaries (and one at its
    ejection), the last one its answer."""
    from repro.serving import ContinuousScheduler
    reqs = [_req(0, 24), _req(1, 40), _req(2, 28)]
    sched = ContinuousScheduler(lane_width=4, record_history=True)
    results = sched.run(reqs)
    _assert_agree(results, reqs)
    want = ([8, 16, 24], [8, 16, 24, 32, 40], [8, 16, 24, 28])
    for res, its in zip(results, want):
        assert list(res.history.iteration) == its
        assert float(res.history.gbest_fit[-1]) == res.gbest_fit
    m = sched.metrics
    assert m.get("dispatches") == m.get("chunks") == 5


# -- the restart story: persistent AOT compile cache -----------------------

def test_compile_cache_restart_zero_retrace_bit_exact(tmp_path):
    """Process A traces + exports the lane program; 'process' B (a fresh
    CompileCache over the same directory — empty memo, so resolution goes
    through the serialized blob) prewarms and serves the same trace with
    ZERO trace events and bitwise-equal results (one program)."""
    from repro.serving import CompileCache, ContinuousScheduler
    reqs = [_req(k, 16) for k in range(4)]

    cold = CompileCache(str(tmp_path))
    a = ContinuousScheduler(lane_width=8, compile_cache=cold).run(reqs)
    assert cold.aot_misses == 1 and cold.trace_events == 1

    warm = CompileCache(str(tmp_path))
    assert warm.prewarm() == 1
    sched = ContinuousScheduler(lane_width=8, compile_cache=warm)
    b = sched.run(reqs)
    assert warm.aot_hits == 1 and warm.aot_misses == 0
    assert warm.trace_events == 0          # the acceptance criterion
    for ra, rb in zip(a, b):
        assert ra.gbest_fit == rb.gbest_fit
        np.testing.assert_array_equal(ra.gbest_pos, rb.gbest_pos)
    _assert_agree(b, reqs)
    assert sched.snapshot()["compile_cache"]["trace_events"] == 0


def test_compile_cache_one_lane_program_for_every_chunk_count(tmp_path):
    """The chunk count is an operand: one traced lane program serves
    dispatches of 1 and of 3 chunks, and a restarted replica serves the
    same dispatches from the exported blob with no trace at all."""
    from repro.serving import CompileCache, ContinuousScheduler
    from repro.telemetry import TraceWriter
    reqs = [_req(0, 16), _req(1, 8), _req(2, 40)]

    def serve(cc):
        tw = TraceWriter()
        out = ContinuousScheduler(lane_width=4, compile_cache=cc,
                                  trace=tw).run(reqs)
        assert _chunk_counts(tw) == [1, 1, 3]
        return out

    cold = CompileCache(str(tmp_path))
    a = serve(cold)
    assert cold.aot_misses == 1 and cold.trace_events == 1
    warm = CompileCache(str(tmp_path))
    assert warm.prewarm() == 1
    b = serve(warm)
    assert warm.aot_misses == 0 and warm.trace_events == 0
    for ra, rb in zip(a, b):
        assert ra.gbest_fit == rb.gbest_fit
        np.testing.assert_array_equal(ra.gbest_pos, rb.gbest_pos)
    _assert_agree(b, reqs)


def test_compile_cache_memory_only_dedup():
    """No path, no env: the cache still memoizes within the process."""
    import jax.numpy as jnp

    from repro.serving import CompileCache
    cc = CompileCache(path="")
    calls = []

    def build(x):
        calls.append(1)
        return x * 2.0
    spec = jnp.ones((3,))
    f1 = cc.get("k", build, spec)
    f2 = cc.get("k", build, spec)
    assert f1 is f2
    assert cc.aot_misses == 1 and cc.aot_hits == 1
    assert cc.trace_events == 1 and len(calls) == 1
    np.testing.assert_allclose(np.asarray(f1(spec)), 2.0 * np.ones((3,)))


def test_compile_cache_manifest_fingerprint_mismatch(tmp_path):
    """A manifest from another jax/backend is ignored: the cache rebuilds
    rather than replaying an incompatible blob."""
    import json
    import os

    from repro.serving import CompileCache
    os.makedirs(tmp_path, exist_ok=True)
    with open(tmp_path / "manifest.json", "w") as f:
        json.dump({"fingerprint": {"jax": "0.0.0", "backend": "vaporware"},
                   "entries": {"deadbeef": {"key": "k", "file": "x.jaxexport",
                                            "bytes": 1}}}, f)
    cc = CompileCache(str(tmp_path))
    assert cc.prewarm() == 0               # incompatible manifest dropped


def test_solve_stream_facade(tmp_path):
    """repro.solve_stream: dict requests + a directory path for the
    compile cache, results in submit order."""
    import repro
    reqs = [dict(dim=DIM, particle_cnt=N, fitness=NAMES[k], seed=k,
                 iters=16, variant="async", sync_every=SE)
            for k in range(3)]
    results = repro.solve_stream(reqs, compile_cache=str(tmp_path))
    _assert_agree(results, [SolveRequest(**r) for r in reqs])


# -- metrics ---------------------------------------------------------------

def test_latency_stat_percentiles_and_reservoir():
    from repro.serving import LatencyStat
    st = LatencyStat(cap=8)
    for v in (10.0, 20.0, 30.0, 40.0):
        st.add(v)
    assert st.mean_us == 25.0
    assert st.p50_us == 30.0               # nearest-rank over 4 samples
    assert st.p99_us == 40.0
    for v in range(100):                   # wrap the reservoir
        st.add(float(v))
    assert st.count == 104
    assert len(st._samples) == 8
    snap = st.snapshot()
    assert snap["count"] == 104 and snap["p99_us"] <= 99.0


def test_serving_metrics_snapshot_and_fill():
    from repro.serving import ServingMetrics
    m = ServingMetrics()
    assert m.batch_fill == 0.0             # no dispatched slots yet
    m.inc("lane_slots", 16)
    m.inc("lane_active_slots", 12)
    m.observe("e2e_us", 100.0)
    snap = m.snapshot()
    assert snap["batch_fill"] == 0.75
    assert snap["spans"]["e2e_us"]["count"] == 1
    m2 = ServingMetrics()
    m2.merge_from(m)
    assert m2.batch_fill == 0.75


# -- satellites: flush-server hardening ------------------------------------

def test_flush_partial_failure_isolated():
    """A poisoned custom objective fails ITS group only: other groups in
    the same flush return normally, the offending tickets carry the
    error, and ``objective`` refuses to report garbage."""
    from repro.core.problem import Problem
    from repro.launch.serve import SolveServer

    def poison(x):
        raise RuntimeError("poisoned objective")

    bad = Problem(name="serving_poison", fn=poison, lo=-1.0, hi=1.0)
    good = [_req(k, 16, variant="queue") for k in range(2)]
    reqs = [good[0], _req(2, 16, fitness=bad, variant="queue"), good[1]]
    srv = SolveServer()
    results = srv.solve_all(reqs)
    assert not results[1].ok
    assert isinstance(results[1].error, RuntimeError)
    with pytest.raises(RuntimeError, match="request failed"):
        results[1].objective
    for res, r in ((results[0], good[0]), (results[2], good[1])):
        assert res.ok
        st = _standalone(r)
        agreement.assert_fit_close(res.gbest_fit, st.gbest_fit)
    assert srv.stats.failed == 1
    assert srv.stats.requests == 2         # only the successful ones


def test_serve_stats_batch_fill_zero_flushes():
    from repro.launch.serve import ServeStats
    s = ServeStats()
    assert s.batch_fill == 0.0             # no dispatches: no div-by-zero
    d = s.as_dict()
    assert d["batch_fill"] == 0.0 and d["failed"] == 0


def test_bucket_size_edges():
    from repro.launch.serve import BUCKETS, _MIN_BUCKET, bucket_size
    assert bucket_size(1) == _MIN_BUCKET   # below the smallest rung
    assert bucket_size(_MIN_BUCKET) == _MIN_BUCKET
    assert bucket_size(5) == 8             # rounds up to the next rung
    assert bucket_size(BUCKETS[-1]) == BUCKETS[-1]
    assert bucket_size(10 ** 6) == BUCKETS[-1]    # capped at the top
    # max_batch below a ladder rung caps the pick
    assert bucket_size(100, max_batch=16) == 16
    assert bucket_size(3, max_batch=4) == 4
    # a custom (autotune-pruned) ladder is honored
    assert bucket_size(5, max_batch=64, buckets=(4, 32)) == 32
    assert bucket_size(40, max_batch=64, buckets=(4, 32)) == 64


def test_buckets_for_autotune_ladder_memoized():
    from repro.launch.serve import _MIN_BUCKET, SolveServer
    srv = SolveServer(max_batch=16, autotune=True)
    r = _req(0, 32, variant="queue")
    ladder = srv._buckets_for(r)
    assert ladder and ladder[0] >= _MIN_BUCKET
    assert all(b <= 16 for b in ladder)
    assert sorted(ladder) == list(ladder)
    assert srv._buckets_for(r) is ladder   # memoized per shape


# -- update-rule / topology plumbing + per-request rejection ---------------

def test_serve_rejects_invalid_requests_per_request():
    """Unknown variant/rule/topology fail the REQUEST, not the flush:
    valid requests in the same generation return normally on both front
    ends, and the bad ticket carries the enumerating error."""
    from repro.launch.serve import SolveServer
    from repro.serving import ContinuousScheduler
    good = [_req(0, 16, variant="queue"), _req(1, 16)]
    bad = [
        SolveRequest(dim=DIM, particle_cnt=N, fitness=NAMES[0], seed=7,
                     iters=16, variant="warp"),
        SolveRequest(dim=DIM, particle_cnt=N, fitness=NAMES[1], seed=8,
                     iters=16, variant="queue", rule="warp_speed"),
        SolveRequest(dim=DIM, particle_cnt=N, fitness=NAMES[2], seed=9,
                     iters=16, variant="async", sync_every=SE,
                     topology="hypercube"),
    ]
    reqs = [good[0]] + bad + [good[1]]
    for front_end in (SolveServer().solve_all,
                      ContinuousScheduler(lane_width=8).run):
        results = front_end(list(reqs))
        for res, want in zip(results[1:4], ("variant", "rule", "topology")):
            assert not res.ok
            assert want in str(res.error)
            assert np.isnan(res.gbest_fit)
        for res, r in ((results[0], good[0]), (results[4], good[1])):
            assert res.ok
            st = _standalone(r)
            agreement.assert_fit_close(res.gbest_fit, st.gbest_fit)


def test_serve_rule_topology_thread_to_engine():
    """``rule=`` / ``topology=`` on a request reach the engine: each
    group's answers match the standalone solve with the same PSOConfig
    (distinct rules/topologies must never share a compiled group)."""
    from repro.launch.serve import SolveServer
    combos = [("sso", "gbest"), ("lowcost", "ring"), ("pso", "vonneumann")]
    reqs = [SolveRequest(dim=DIM, particle_cnt=N, fitness=NAMES[k], seed=k,
                         iters=16, variant="async", sync_every=SE,
                         rule=rule, topology=topo)
            for k, (rule, topo) in enumerate(combos)]
    srv = SolveServer()
    results = srv.solve_all(list(reqs))
    for res, r in zip(results, reqs):
        assert res.ok
        cfg = PSOConfig(dim=r.dim, particle_cnt=r.particle_cnt,
                        fitness=r.fitness, dtype=r.dtype,
                        update_rule=r.rule, topology=r.topology)
        st = solve(cfg, r.seed, r.iters, r.variant, r.sync_every)
        agreement.assert_fit_close(res.gbest_fit, st.gbest_fit)
    # one dispatch per (rule, topology): the compile key split them
    assert srv.stats.dispatches == len(combos)


_CACHE_KNOBS = ("jax_compilation_cache_dir",
                "jax_persistent_cache_min_compile_time_secs",
                "jax_persistent_cache_min_entry_size_bytes")


def test_persistent_cache_dir_fixed_in_checkout(monkeypatch):
    """Unset JAX_COMPILATION_CACHE_DIR: the cache is <checkout>/.jax_cache,
    never a temp or per-process name."""
    import os

    import jax
    from repro.serving import use_persistent_cache
    from repro.serving.compile_cache import XLA_CACHE_ENV
    monkeypatch.delenv(XLA_CACHE_ENV, raising=False)
    prev = {k: getattr(jax.config, k) for k in _CACHE_KNOBS}
    try:
        path = use_persistent_cache()
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert path == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        # every program persists, however fast it compiled
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
        assert jax.config.jax_persistent_cache_min_entry_size_bytes == -1
        assert use_persistent_cache() == path        # stable across calls
    finally:
        for k, v in prev.items():
            jax.config.update(k, v)


def test_persistent_cache_dir_env_left_to_jax(monkeypatch, tmp_path):
    """Set JAX_COMPILATION_CACHE_DIR: JAX's own reading stands and no code
    sets another directory."""
    import jax
    from repro.serving import CompileCache, use_persistent_cache
    from repro.serving.compile_cache import XLA_CACHE_ENV
    monkeypatch.setenv(XLA_CACHE_ENV, str(tmp_path / "xla"))
    prev = jax.config.jax_compilation_cache_dir
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a, **k: updates.append(a))
    assert use_persistent_cache() == str(tmp_path / "xla")
    CompileCache(str(tmp_path / "aot"))
    # the thresholds are still set (they choose no directory); the
    # directory is not
    assert {a[0] for a in updates} == {
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes"}
    assert ("jax_persistent_cache_min_compile_time_secs", 0.0) in updates
    assert ("jax_persistent_cache_min_entry_size_bytes", -1) in updates
    assert jax.config.jax_compilation_cache_dir == prev


def test_serve_main_exits_nonzero_on_failed_group(monkeypatch, capsys):
    """A group that raises is isolated into error results by flush(), but
    the CLI run as a whole must report failure."""
    import sys

    import repro.serving
    from repro.launch import serve

    def boom(self, reqs):
        raise RuntimeError("injected group failure")

    monkeypatch.setattr(repro.serving, "use_persistent_cache", lambda: None)
    monkeypatch.setattr(serve.SolveServer, "_solve_group", boom)
    monkeypatch.setattr(sys, "argv", ["serve", "--requests", "4",
                                      "--iters", "4"])
    assert serve.main() == 1
    assert "injected group failure" in capsys.readouterr().err

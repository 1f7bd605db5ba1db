"""Benchmark harness — one function per paper table/figure.

  table3  — 1D problem: execution time of the five implementations
            (CPU serial, Reduction, Loop-unrolled*, Queue, Queue-Lock)
            across particle counts (paper Table 3 / Fig. 3).
  table4  — 1D speedup of Queue-Lock vs CPU serial (paper Table 4).
  table5  — 120D speedup of Queue vs CPU serial (paper Table 5).
  multi_swarm — batched engine: S independent solves via ONE solve_many
            device program vs a Python loop of solve() (swarms/sec).
  mixed_traffic — serving-layer registry coalescing: a mixed trace of
            built-in objectives at one solve shape, heterogeneous batches
            (launch/serve.py, lax.switch row dispatch) vs the legacy
            content-hash grouping — batch fill, dispatches, flush p50/p99.
  serving — continuous-batching scheduler (repro.serving) vs the flush
            server on ONE mixed trace (six built-ins x four iteration
            budgets, staggered waves): requests/s, e2e p50/p99, batch
            fill per leg (benchmarks/loadgen.py; steady-state pass).
            Hard-gated in CI (promoted from warn-only after a cycle of
            baseline-refresh history).
  async_sweep — the enhanced (asynchronous) queue-lock: per-iteration cost
            and solution quality vs the synchronous kernel across
            sync_every ∈ {1, 4, 16, 64}. Fewer chunk boundaries = fewer
            cross-block synchronization points = fewer grid steps; the
            per-iteration cost must fall monotonically as sync_every grows.
  islands_ring — distributed exchange cost: the async island ring
            (neighbor ppermute pushes, core.distributed) vs the barrier
            ``_pmax_best`` collective at the same exchange cadence.
  custom_objective — Problem-API adapter overhead: a user-written cubic
            lowered by the generic d-major adapter vs the hand-tuned
            kernel form, through the fused queue-lock kernel.
  constrained — constraint-handling cost: penalty vs projection us/iter
            on the sphere-on-simplex built-in (repro.core.constraints),
            with final gbest + violation as quality columns.
  autotune — roofline schedule autotuner: auto-picked (variant, backend,
            block_n, sync_every) vs the fixed default schedule per suite
            shape, plus the measured-optima cache-hit check. Warn-only in
            compare.py until it accumulates noise-floor history.
  portfolio — update-rule portfolio: solution quality at EQUAL WALL-CLOCK
            across the registered rules (pso / sso / lowcost) on one
            landscape — per-rule us/iter plus final gbest when each rule
            spends the default rule's time budget. Warn-only in
            compare.py until it accumulates noise-floor history.
  telemetry — in-kernel contention-counter overhead: the fused
            queue-lock kernel with counters off (A/A control; CI asserts
            the disabled ratio ≤ 1.05) vs counters on (real enabled
            ratio + counter totals). Warn-only in compare.py until it
            accumulates noise-floor history; docs/observability.md.
  lm_bench— LM substrate micro-bench (tokens/s on the smoke configs).

Cross-PR trend: ``compare.py OLD.json NEW.json`` diffs two artifacts
(per-record us/call delta; nonzero exit above --threshold). CI runs it
warning-only against the committed benchmarks/BENCH_pso.json baseline.

This container is CPU-only, so the "GPU" columns run the same JAX
algorithms on the CPU backend, jit-compiled, and the Pallas kernels run in
interpret mode (which measures *semantics*, not TPU silicon). Relative
orderings therefore reflect algorithmic work (the paper's claim), while
absolute numbers are CPU numbers — EXPERIMENTS.md §Benchmarks discusses
the mapping onto the paper's GTX-1080Ti results.

*Loop-unrolled on TPU: the CUDA unrolling trick has no TPU counterpart
(DESIGN.md §2); the reduction variant is its closest analogue and is
reported once.

Output: ``name,us_per_call,derived`` CSV rows on stdout, plus a
machine-readable ``BENCH_pso.json`` (``--out``) with the same records and
backend/interpret metadata, so the perf trajectory is tracked across PRs.
``--smoke`` shrinks every benchmark to CI-sized iteration counts and skips
the LM substrate.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import numpy as np

ITERS_1D = 2000           # paper uses 100k; scaled for CPU wall-time — the
REPEATS = 3               # us/iter metric is iteration-count invariant

# Machine-readable result records: [{"name": ..., "us_per_call": ...,
# <derived k/v>}, ...], dumped to BENCH_pso.json by main().
RESULTS = []


def emit(name: str, us_per_call: float, **derived) -> None:
    """Print the CSV row and record it for the JSON dump."""
    tail = ",".join(f"{k}={v:.6g}" if isinstance(v, float) else f"{k}={v}"
                    for k, v in derived.items())
    print(f"{name},{us_per_call:.3f}" + ("," + tail if tail else ""))
    RESULTS.append({"name": name, "us_per_call": float(us_per_call),
                    **{k: (float(v) if isinstance(v, (int, float, np.floating))
                           and not isinstance(v, bool) else v)
                       for k, v in derived.items()}})


def _time(fn, repeats=REPEATS):
    fn()                                  # warmup / compile
    ts = []
    for _ in range(repeats + 2):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    if len(ts) > 2:
        ts = sorted(ts)[1:-1]             # drop min/max (paper §6.1)
    return float(np.mean(ts))


def _pso_variants(dim: int, particles: int, iters: int):
    from repro.core import PSOConfig, init_swarm, run, run_serial_fast
    from repro.kernels.ops import run_queue_lock_fused
    cfg = PSOConfig(dim=dim, particle_cnt=particles,
                    fitness="cubic").resolved()
    s0 = init_swarm(cfg, 0)
    out = {}
    out["cpu_serial"] = _time(lambda: run_serial_fast(cfg, 0, iters),
                              repeats=1)
    for variant in ("reduction", "queue", "queue_lock"):
        out[variant] = _time(lambda v=variant: jax.block_until_ready(
            run(cfg, s0, iters, v).gbest_fit))
    # fused Pallas queue-lock kernel (interpret mode: semantics on CPU)
    kiters = min(iters, 50)               # interpret mode is a python loop
    t = _time(lambda: jax.block_until_ready(
        run_queue_lock_fused(cfg, s0, iters=kiters).gbest_fit), repeats=1)
    out["queue_lock_pallas_interp"] = t * (iters / kiters)
    return out


def table3(smoke=False) -> None:
    """1D problem across particle counts (paper Table 3)."""
    iters = 200 if smoke else ITERS_1D
    sweep = (64, 256) if smoke else (32, 64, 128, 256, 512, 1024, 2048)
    for particles in sweep:
        res = _pso_variants(1, particles, iters)
        base = res["cpu_serial"]
        for name, t in res.items():
            emit(f"table3/p{particles}/{name}", 1e6 * t / iters,
                 speedup_vs_serial=base / t)


def table4(smoke=False) -> None:
    """Queue-Lock speedup scaling, 1D (paper Table 4)."""
    from repro.core import PSOConfig, init_swarm, run, run_serial_fast
    iters = 100 if smoke else ITERS_1D // 2
    sweep = (128, 2048) if smoke else (128, 512, 2048, 8192, 32768, 131072)
    for particles in sweep:
        cfg = PSOConfig(dim=1, particle_cnt=particles).resolved()
        s0 = init_swarm(cfg, 0)
        t_cpu = _time(lambda: run_serial_fast(cfg, 0, iters), repeats=1)
        t_ql = _time(lambda: jax.block_until_ready(
            run(cfg, s0, iters, "queue_lock").gbest_fit))
        emit(f"table4/p{particles}/queue_lock", 1e6 * t_ql / iters,
             speedup=t_cpu / t_ql)


def table5(smoke=False) -> None:
    """Queue speedup scaling, 120D (paper Table 5)."""
    from repro.core import PSOConfig, init_swarm, run, run_serial_fast
    sweep = (((128, 50), (1024, 25)) if smoke else
             ((128, 200), (1024, 150), (8192, 100), (32768, 50)))
    for particles, iters in sweep:
        cfg = PSOConfig(dim=120, particle_cnt=particles).resolved()
        s0 = init_swarm(cfg, 0)
        t_cpu = _time(lambda: run_serial_fast(cfg, 0, iters), repeats=1)
        t_q = _time(lambda: jax.block_until_ready(
            run(cfg, s0, iters, "queue").gbest_fit))
        emit(f"table5/p{particles}/queue", 1e6 * t_q / iters,
             speedup=t_cpu / t_q)


def convergence_equivalence(smoke=False) -> None:
    """The queue variants must match reduction's answer (paper §4.1) —
    report final gbest per variant on the paper's two problems."""
    from repro.core import PSOConfig, solve
    sweep = ((1, 200),) if smoke else ((1, 1000), (120, 500))
    for dim, iters in sweep:
        vals = {}
        for v in ("reduction", "queue", "queue_lock"):
            s = solve(PSOConfig(dim=dim, particle_cnt=1024), seed=0,
                      iters=iters, variant=v)
            vals[v] = float(s.gbest_fit)
        spread = max(vals.values()) - min(vals.values())
        emit(f"equiv/{dim}d/gbest_spread", spread, gbest=vals["queue"])


def async_sweep(smoke=False) -> None:
    """Async queue-lock: cost and quality vs sync across sync_every.

    Kernel leg (interpret mode): the grid has ``blocks * iters/sync_every``
    steps, so per-iteration cost measures exactly what the async algorithm
    removes — cross-block synchronization points (on TPU silicon: the
    serialized gbest publication + state round-trips; in interpret mode:
    the per-grid-step machinery standing in for them). It must fall
    monotonically as sync_every grows. Timing protocol: the K values are
    sampled round-robin (interleaved) and the per-K minimum is kept, so
    shared-machine scheduling drift hits every K equally instead of
    whichever K ran last. Library leg: final gbest quality of the relaxed
    semantics vs the synchronous queue_lock on the same seed.
    """
    from repro.core import PSOConfig, init_swarm, run, run_async
    from repro.kernels.ops import (run_queue_lock_fused,
                                   run_queue_lock_fused_async)
    dim, particles, block_n = 1, 4096, 64     # 64 particle blocks
    iters = 128                                # long calls: stable us/iter
    rounds = 6 if smoke else 10
    sweep = (1, 4, 16, 64)
    cfg = PSOConfig(dim=dim, particle_cnt=particles,
                    fitness="rastrigin").resolved()
    s0 = init_swarm(cfg, 0)

    def async_call(k):
        return run_queue_lock_fused_async(cfg, s0, iters=iters,
                                          sync_every=k, block_n=block_n)

    def sync_call():
        return run_queue_lock_fused(cfg, s0, iters=iters, block_n=block_n)

    fns = {k: (lambda k=k: jax.block_until_ready(async_call(k).gbest_fit))
           for k in sweep}
    fns["sync"] = lambda: jax.block_until_ready(sync_call().gbest_fit)
    # warmup/compile; the calls are deterministic, so the warmup results
    # double as the quality numbers (no re-execution after timing)
    gbest = {k: float(fn()) for k, fn in fns.items()}
    best = {k: float("inf") for k in fns}
    for _ in range(rounds):                   # interleaved, keep the min
        for k, fn in fns.items():
            t0 = time.perf_counter()
            fn()
            best[k] = min(best[k], time.perf_counter() - t0)
    tag = f"async_sweep/d{dim}_n{particles}_b{block_n}"
    emit(f"{tag}/sync_kernel", 1e6 * best["sync"] / iters,
         gbest_fit=gbest["sync"])
    for k in sweep:
        emit(f"{tag}/sync_every_{k}", 1e6 * best[k] / iters,
             speedup_vs_sync=best["sync"] / best[k],
             gbest_fit=gbest[k],
             gbest_gap_vs_sync=gbest["sync"] - gbest[k])
    # library (jnp) leg: relaxed-consistency quality at production iteration
    # counts — the async answer must stay in the sync answer's neighborhood.
    qcfg = PSOConfig(dim=8, particle_cnt=256, fitness="rastrigin").resolved()
    q0 = init_swarm(qcfg, 0)
    jiters = 100 if smoke else 400
    gf_ql = float(run(qcfg, q0, jiters, "queue_lock").gbest_fit)
    for k in sweep:
        st = run_async(qcfg, q0, jiters, sync_every=k, n_blocks=4)
        emit(f"async_sweep/jnp_d8_n256/sync_every_{k}",
             0.0, gbest_fit=float(st.gbest_fit),
             gbest_gap_vs_queue_lock=gf_ql - float(st.gbest_fit))


def islands_ring(smoke=False) -> None:
    """Async island ring vs barrier exchange (core.distributed).

    Same island layout and exchange cadence; the sync leg pays the
    ``_pmax_best`` barrier collective per exchange, the async leg a
    neighbor-only ring push (plus the run_async local loop). On this
    container the mesh is 1-device, so absolute numbers measure program
    overhead rather than network latency — the record exists to track the
    ring path's cost trajectory and its convergence quality (the final
    gbest must equal max(pbest): the final-flush invariant).
    """
    import jax
    from repro.core import PSOConfig
    from repro.core.distributed import init_sharded_swarm, make_distributed_run
    dim, particles = 8, 2048
    iters = 64 if smoke else 256
    exchange = 16
    cfg = PSOConfig(dim=dim, particle_cnt=particles,
                    fitness="rastrigin").resolved()
    mesh = jax.make_mesh((len(jax.devices()),), ("data",))
    s0 = init_sharded_swarm(cfg, 0, mesh)
    legs = {
        "barrier": make_distributed_run(cfg, mesh, iters=iters,
                                        variant="queue",
                                        exchange_interval=exchange),
        "ring_async": make_distributed_run(cfg, mesh, iters=iters,
                                           variant="async",
                                           exchange_interval=exchange,
                                           sync_every=8),
    }
    tag = f"islands_ring/d{dim}_n{particles}_x{exchange}"
    times, quality = {}, {}
    for name, fn in legs.items():
        quality[name] = float(jax.block_until_ready(fn(s0).gbest_fit))
    for _ in range(3 if smoke else 6):        # interleaved, keep the min
        for name, fn in legs.items():
            t0 = time.perf_counter()
            jax.block_until_ready(fn(s0).gbest_fit)
            dt = time.perf_counter() - t0
            times[name] = min(times.get(name, float("inf")), dt)
    for name in legs:
        emit(f"{tag}/{name}", 1e6 * times[name] / iters,
             gbest_fit=quality[name],
             speedup_vs_barrier=times["barrier"] / times[name])


def multi_swarm(smoke=False) -> None:
    """Batched multi-swarm engine vs loop-of-solve (swarms/sec).

    The loop baseline compiles once (cfg/iters static) and pays per-solve
    dispatch + eager init; solve_many pays one dispatch for the whole batch.
    Note the 1D/tiny-swarm regime can favor the loop on CPU: vmap turns the
    queue variant's rare-improvement ``cond`` into an always-both-branches
    ``select``, so batching wins where per-dispatch overhead and vector
    width dominate (realistic dims / particle counts), not on toy shapes.
    """
    import jax
    from repro.core import PSOConfig, solve, solve_many
    sweep = (((10, 256, 8, 50),) if smoke else
             ((10, 256, 8, 200), (10, 256, 16, 200), (10, 1024, 32, 100)))
    for dim, particles, s_cnt, iters in sweep:
        cfg = PSOConfig(dim=dim, particle_cnt=particles, fitness="rastrigin")
        seeds = list(range(s_cnt))
        t_loop = _time(lambda: [jax.block_until_ready(
            solve(cfg, sd, iters, "queue").gbest_fit) for sd in seeds],
            repeats=1)
        t_batch = _time(lambda: jax.block_until_ready(
            solve_many(cfg, seeds, iters, "queue").gbest_fit), repeats=1)
        tag = f"multi_swarm/d{dim}_n{particles}_s{s_cnt}"
        emit(f"{tag}/loop_of_solve", 1e6 * t_loop,
             swarms_per_s=s_cnt / t_loop)
        emit(f"{tag}/solve_many", 1e6 * t_batch,
             swarms_per_s=s_cnt / t_batch,
             speedup_vs_loop=t_loop / t_batch)


def mixed_traffic(smoke=False) -> None:
    """Serving-layer registry coalescing (launch/serve.py): a stream of
    requests cycling through the six built-in objectives at ONE solve
    shape, flushed in waves. With ``coalesce_registry`` every wave is a
    single heterogeneous dispatch (one compiled program for the whole
    mix); the legacy content-hash grouping pays one dispatch — and one
    compiled program — per distinct objective. ``first_flush_us`` carries
    the compile cost of each mode; later flushes are steady-state, so the
    p50/p99 columns are the serving-latency claim. ``fill_vs_content_hash``
    (real rows per dispatch, ratio of the two modes) is the coalescing
    payoff — 6 distinct objectives per wave means a >=2x floor."""
    from repro.launch.serve import SolveRequest, SolveServer
    names = ("cubic", "sphere", "rosenbrock", "griewank", "rastrigin",
             "ackley")
    dim, n, iters = 10, 128, (20 if smoke else 100)
    waves, per_wave = (3, 6) if smoke else (6, 12)
    stats, flushes = {}, {}
    for label, coalesce in (("hetero", True), ("content_hash", False)):
        srv = SolveServer(coalesce_registry=coalesce)
        lat = []
        k = 0
        for _ in range(waves):
            for _ in range(per_wave):
                srv.submit(SolveRequest(
                    dim=dim, particle_cnt=n, fitness=names[k % len(names)],
                    seed=k, iters=iters, variant="queue"))
                k += 1
            t0 = time.perf_counter()
            srv.flush()
            lat.append(1e6 * (time.perf_counter() - t0))
        stats[label], flushes[label] = srv.stats, lat
    for label in ("hetero", "content_hash"):
        s, lat = stats[label], flushes[label]
        steady = lat[1:] or lat
        kv = dict(first_flush_us=lat[0],
                  p50_us=float(np.percentile(steady, 50)),
                  p99_us=float(np.percentile(steady, 99)),
                  dispatches=s.dispatches, batch_fill=s.batch_fill,
                  padded_rows=s.padded_rows)
        if label == "hetero":
            kv["fill_vs_content_hash"] = (
                s.batch_fill / stats["content_hash"].batch_fill)
        emit(f"mixed_traffic/d{dim}_n{n}/{label}", float(np.mean(steady)),
             **kv)


def serving_bench(smoke=False) -> None:
    """Continuous batching vs flush batching on the mixed-traffic stream
    (benchmarks/loadgen.py): six built-ins crossed with four iteration
    budgets at one solve shape, arriving in waves. Flush group keys
    include ``iters`` so every wave fragments into padded groups; the
    continuous scheduler's lane keys drop ``iters`` and admit at chunk
    boundaries, so the same trace rides one full lane. Both legs are
    steady-state (warmup pass untimed) and the per-request results are
    cross-checked bitwise (``gbest_agree`` must be True). The continuous
    leg's ``speedup_vs_flush`` (steady-state requests/s ratio) is the
    serving claim; ``batch_fill`` is the mechanism."""
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import loadgen
    rep = loadgen.run_loadgen(smoke=smoke)
    tag = "serving/mixed_d6_n64"
    for leg in ("flush", "continuous"):
        s = rep[leg]
        kv = dict(requests_per_s=s["requests_per_s"], p50_us=s["p50_us"],
                  p99_us=s["p99_us"], batch_fill=s["batch_fill"],
                  dispatches=s["dispatches"],
                  first_pass_s=rep[f"{leg}_first_pass_s"])
        if leg == "continuous":
            kv["speedup_vs_flush"] = rep["speedup_vs_flush"]
            kv["gbest_agree"] = rep["gbest_agree"]
            sc = rep["continuous_snapshot"]["counters"]
            kv["row_swaps"] = int(sc.get("row_swaps", 0))
            kv["tail_ejections"] = int(sc.get("tail_ejections", 0))
        emit(f"{tag}/{leg}", s["us_per_request"], **kv)


def custom_objective(smoke=False) -> None:
    """Problem-API adapter overhead: the generic d-major adapter
    (``repro.kernels.pso_step.dmajor_adapter`` — transpose + sliced user
    fn + hoisted consts + pinned advance) vs the hand-tuned ``cubic``
    kernel form, same landscape, same fused queue-lock kernel. The
    ``overhead_vs_hand_tuned`` ratio is the price of a user-defined
    objective on the kernel path; the gbest gap must be ~0 (identical
    landscape, same seed)."""
    import jax.numpy as jnp
    from repro.core import PSOConfig, init_swarm
    from repro.core.problem import Problem
    from repro.kernels.ops import run_queue_lock_fused

    def cubic_user(x):      # the paper's Eq. 3, as a user would write it
        return jnp.sum(x * x * x - 0.8 * (x * x) - 1000.0 * x + 8000.0,
                       axis=-1)

    custom = Problem(name="cubic_user", fn=cubic_user, lo=-100.0, hi=100.0)
    dim, particles, iters = 8, 1024, (10 if smoke else 40)
    results = {}
    for label, fitness in (("hand_tuned", "cubic"), ("adapter", custom)):
        cfg = PSOConfig(dim=dim, particle_cnt=particles,
                        fitness=fitness).resolved()
        s0 = init_swarm(cfg, 0)
        last = {}

        def call(cfg=cfg, s0=s0, last=last):
            out = run_queue_lock_fused(cfg, s0, iters=iters)
            last["gbest"] = float(jax.block_until_ready(out.gbest_fit))

        t = _time(call, repeats=1)  # deterministic: timed runs = quality run
        results[label] = (t, last["gbest"])
    tag = f"custom_objective/d{dim}_n{particles}"
    t_hand, g_hand = results["hand_tuned"]
    t_adpt, g_adpt = results["adapter"]
    emit(f"{tag}/hand_tuned", 1e6 * t_hand / iters, gbest_fit=g_hand)
    emit(f"{tag}/adapter", 1e6 * t_adpt / iters,
         overhead_vs_hand_tuned=t_adpt / t_hand,
         gbest_fit=g_adpt, gbest_gap_vs_hand_tuned=g_hand - g_adpt)


def constrained(smoke=False) -> None:
    """Constrained-optimization subsystem: penalty vs projection us/iter on
    the sphere-on-simplex built-in (repro.core.constraints), through the
    jnp queue-lock engine. Penalty pays one extra objective-sized violation
    evaluation per fitness call; projection pays a sort-based simplex
    projection per advance. Both records carry the final gbest (user sense:
    minimized, optimum 1/D) and its violation so constraint-handling
    quality is tracked alongside cost."""
    from repro.core import PSOConfig, init_swarm, run
    from repro.core.problem import get_problem
    dim, particles = 8, 1024
    iters = 50 if smoke else 200
    for label, name in (("penalty", "sphere_simplex_pen"),
                        ("projection", "sphere_simplex")):
        prob = get_problem(name)
        cfg = PSOConfig(dim=dim, particle_cnt=particles, fitness=prob,
                        w=0.7).resolved()
        s0 = init_swarm(cfg, 0)
        last = {}

        def call(cfg=cfg, s0=s0, last=last):
            out = run(cfg, s0, iters, "queue_lock")
            jax.block_until_ready(out.gbest_fit)
            last["out"] = out

        t = _time(call)  # deterministic: timed runs = quality run
        out = last["out"]
        viol = prob.violation_at(out.gbest_pos)
        emit(f"constrained/d{dim}_n{particles}/{label}", 1e6 * t / iters,
             best=float(prob.user_value(out.gbest_fit)),
             violation=float(viol), feasible=bool(viol <= 0.0))


def autotune_bench(smoke=False) -> None:
    """Roofline schedule autotuner (repro.core.autotune): auto-picked
    ``(variant, backend, block_n, sync_every)`` vs the fixed schedule a
    user would pin, across built-in suite shapes.

    Each shape carries the variant a fixed-schedule user plausibly
    requests — ``queue`` (the repo default) on some, the paper's
    GPU-winning ``queue_lock``/``async`` on others. The fixed leg honors
    that pin exactly (``Method(variant=...)``); the auto leg is
    ``schedule="auto"``, where the variant is a preference the tuner may
    override — on hosts whose roofline disagrees with the paper's GPU
    (this CPU container), walking a pinned fused/async variant back to
    the cheapest engine is precisely the tuner's job.

    Both legs are timed with the tuner's own micro-run harness so the
    comparison is apples-to-apples; when the tuner picks exactly the fixed
    schedule the fixed timing is REUSED (ratio exactly 1.0) — the measured
    fallback always includes the default fixed schedule as a candidate, so
    auto is never worse than the default rule by construction. ``cache_hit``
    records that the second resolve of each shape was served from the
    measured-optima cache (no re-measurement) — the serving-layer latency
    guarantee."""
    from repro.core import autotune as at
    shapes = ([("sphere", 4, 256, "queue"),
               ("rastrigin", 8, 512, "async"),
               ("cubic", 1, 2048, "async")] if smoke else
              [("sphere", 4, 256, "queue"),
               ("rastrigin", 8, 512, "async"),
               ("cubic", 1, 2048, "async"),
               ("ackley", 16, 1024, "async"),
               ("griewank", 2, 64, "queue"),
               ("rosenbrock", 32, 4096, "async")])
    iters = 40 if smoke else 120
    cache = at.AutotuneCache()
    for prob, d, n, req_variant in shapes:
        fixed = at.fixed_schedule(variant=req_variant)
        tuned = at.resolve_schedule(prob, d, n, iters, cache=cache)
        hit = at.resolve_schedule(prob, d, n, iters, cache=cache)
        same = (tuned.variant == fixed.variant
                and tuned.backend == fixed.backend
                and tuned.block_n == fixed.block_n
                and (tuned.variant != "async"
                     or tuned.sync_every == fixed.sync_every))
        t_fixed = at.measure_schedule(fixed, prob, d, n, iters=iters,
                                      repeats=3)
        t_auto = t_fixed if same else at.measure_schedule(
            tuned, prob, d, n, iters=iters, repeats=3)
        tag = f"autotune/{prob}_d{d}_n{n}"
        emit(f"{tag}/fixed", t_fixed, variant=fixed.variant,
             backend=fixed.backend, requested=req_variant)
        emit(f"{tag}/auto", t_auto, speedup_vs_fixed=t_fixed / t_auto,
             variant=tuned.variant, backend=tuned.backend,
             block_n=tuned.block_n, sync_every=tuned.sync_every,
             source=tuned.source, cache_hit=bool(hit.source == "cache"))


def portfolio(smoke=False) -> None:
    """Update-rule portfolio: quality at equal wall-clock.

    Rules trade per-iteration cost against per-iteration progress (sso
    has no velocity chain, lowcost drops the stochastic multiplies), so
    comparing them at equal ITERATION counts is the wrong frame for a
    serving deployment. This suite times each registered rule's us/iter
    on the jnp queue-lock engine, then reruns each rule with the
    iteration count that fits the DEFAULT rule's wall-clock budget —
    ``gbest_fit`` is the quality-at-equal-time column and
    ``gbest_gap_vs_pso`` the portfolio signal (positive = the canonical
    rule is ahead at this budget on this landscape)."""
    from repro.core import PSOConfig, init_swarm, run
    from repro.core.update_rules import rule_names
    dim, particles = 8, 512
    base_iters = 60 if smoke else 300
    rules = rule_names()
    cfgs = {r: PSOConfig(dim=dim, particle_cnt=particles,
                         fitness="rastrigin", update_rule=r).resolved()
            for r in rules}
    s0 = {r: init_swarm(cfgs[r], 0) for r in rules}
    t = {r: _time(lambda r=r: jax.block_until_ready(
        run(cfgs[r], s0[r], base_iters, "queue_lock").gbest_fit))
        for r in rules}
    budget = t["pso"]                     # the default rule's wall-clock
    tag = f"portfolio/rastrigin_d{dim}_n{particles}"
    quality = {}
    iters_at = {}
    for r in rules:
        iters_at[r] = max(1, int(round(base_iters * budget / t[r])))
        quality[r] = float(jax.block_until_ready(
            run(cfgs[r], s0[r], iters_at[r], "queue_lock").gbest_fit))
    for r in rules:
        emit(f"{tag}/{r}", 1e6 * t[r] / base_iters,
             iters_at_budget=iters_at[r], gbest_fit=quality[r],
             gbest_gap_vs_pso=quality["pso"] - quality[r])


def telemetry_bench(smoke=False) -> None:
    """Telemetry overhead: the in-kernel contention-counter plumbing.

    ``telemetry/.../off`` times the fused queue-lock kernel with
    counters disabled. The disabled program lowers bit-identically to
    the pre-telemetry kernel (digest-pinned in tests/test_kernels.py),
    so the ``disabled_ratio`` derived column is an A/A control — the
    same program timed twice — and its value is the runner's timing
    noise floor. CI asserts it stays ≤ 1.05 (the ≤5% budget for the
    disabled path; the digest pin is the structural zero-overhead
    guarantee). ``.../on`` times the counter-instrumented program and
    reports the real ``enabled_ratio`` plus the counter totals.
    Warn-only in compare.py until it accumulates noise-floor history.
    """
    from repro.core import PSOConfig, init_swarm
    from repro.kernels.ops import run_queue_lock_fused
    from repro.telemetry import KernelCounters
    dim, particles = 8, 512
    iters = 8 if smoke else 32
    cfg = PSOConfig(dim=dim, particle_cnt=particles,
                    fitness="rastrigin").resolved()
    s0 = init_swarm(cfg, 0)
    t_off = _time(lambda: jax.block_until_ready(
        run_queue_lock_fused(cfg, s0, iters=iters).gbest_fit))
    t_off2 = _time(lambda: jax.block_until_ready(
        run_queue_lock_fused(cfg, s0, iters=iters).gbest_fit))
    t_on = _time(lambda: jax.block_until_ready(
        run_queue_lock_fused(cfg, s0, iters=iters,
                             telemetry=True)[0].gbest_fit))
    _, cnt = run_queue_lock_fused(cfg, s0, iters=iters, telemetry=True)
    c = KernelCounters.from_array(cnt)
    tag = f"telemetry/queue_lock_d{dim}_n{particles}"
    emit(f"{tag}/off", 1e6 * t_off / iters,
         disabled_ratio=t_off2 / t_off)
    emit(f"{tag}/on", 1e6 * t_on / iters,
         enabled_ratio=t_on / t_off, **c.as_dict())


def lm_bench() -> None:
    """LM substrate: smoke-config train-step tokens/s per arch family."""
    from repro.configs import get_arch
    from repro.launch.steps import make_train_step
    from repro.models import zoo
    for arch in ("stablelm-3b", "phi3.5-moe-42b-a6.6b", "hymba-1.5b",
                 "xlstm-350m", "whisper-small"):
        cfg = get_arch(arch).smoke()
        params = zoo.init_params(cfg, jax.random.key(0))
        step, opt_init = make_train_step(cfg)
        opt = opt_init(params)
        jstep = jax.jit(step)
        b, s = 4, 128
        batch = zoo.make_batch(cfg, "train_4k", b, s, jax.random.key(1))
        t = _time(lambda: jax.block_until_ready(
            jstep(params, opt, batch)[2]["loss"]))
        toks = b * s
        emit(f"lm/{arch}/train_step", 1e6 * t, tokens_per_s=toks / t)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized iteration counts; skips the LM substrate")
    ap.add_argument("--out", default="BENCH_pso.json",
                    help="machine-readable results path ('' disables)")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    convergence_equivalence(args.smoke)
    table3(args.smoke)
    table4(args.smoke)
    table5(args.smoke)
    multi_swarm(args.smoke)
    mixed_traffic(args.smoke)
    serving_bench(args.smoke)
    async_sweep(args.smoke)
    islands_ring(args.smoke)
    custom_objective(args.smoke)
    constrained(args.smoke)
    autotune_bench(args.smoke)
    portfolio(args.smoke)
    telemetry_bench(args.smoke)
    if not args.smoke:
        lm_bench()
    if args.out:
        import platform
        from repro.kernels.pso_step import default_interpret
        doc = {
            "meta": {
                "backend": jax.default_backend(),
                "jax_version": jax.__version__,
                # how the kernels ran (the builders decide from the
                # platform), so interpret-mode and TPU-compiled timings can
                # never be silently compared
                "pallas_interpret": default_interpret(),
                "smoke": bool(args.smoke),
                # recorded so compare.py can tell same-runner A/Bs (where
                # the hard gate is meaningful) from cross-machine diffs.
                # BENCH_HOST_ID overrides the hostname for fleets of
                # interchangeable machines (CI sets it to the runner class:
                # GitHub-hosted VMs get a fresh hostname per job, which
                # would otherwise disarm the gate on every run)
                "host": os.environ.get("BENCH_HOST_ID") or platform.node(),
                # host fingerprint for the roofline calibration fit
                # (repro.roofline.pso_cost.fit_calibration): model fits
                # must never mix hosts, and hostname alone is too weak
                # (CI runner classes share BENCH_HOST_ID across VM sizes)
                "cpu_count": os.cpu_count(),
                "device_kind": jax.devices()[0].device_kind,
            },
            "benchmarks": RESULTS,
        }
        with open(args.out, "w") as f:
            json.dump(doc, f, indent=2)
            f.write("\n")
        print(f"# wrote {len(RESULTS)} records to {args.out}")


if __name__ == "__main__":
    main()

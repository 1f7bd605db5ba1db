#!/usr/bin/env python3
"""Bring-up smoke run: drive the solver's main paths once on a TPU.

    python3 chip_smoke.py            # one chip: phases a-e
    python3 chip_smoke.py --chips 4  # four chips: the island phase only

Everything runs in this one process, through the entry points a user calls
(``repro.solve``, ``solve_many``, ``solve_stream``, ``SolveServer``), at the
paper's sizes with a fixed seed. Phases on one chip:

  a. ``solve``: 1-D ``cubic`` at 131,072 particles (the paper's Table 4).
  b. ``solve``: 120-D ``rastrigin`` at 32,768 particles (Table 5).
     a and b run ``queue_lock`` and ``async`` on the kernel and jnp backends.
     b-ring: the async kernel with ``topology="ring"`` at b's size.
  c. ``solve_many`` over 16 seeds on the batched and the heterogeneous
     (per-row problem) kernels, against the jnp engine.
  d. ``solve_stream``: 24 mixed requests through the continuous scheduler.
  e. ``SolveServer(backend="kernel")``: one flush of mixed requests.

With ``--chips 4`` it runs only ``Method(islands=4)`` with the async ring
and the barrier ``queue`` variant, each against a one-chip jnp solve of the
same swarm, and checks that the island state is sharded over all four chips.

Checks (a failed check fails the run). Gaps are relative to the largest
magnitude among the two answers and the initial best, so an optimum at 0
gives no vanishing denominator. On an 8-iteration horizon:
  * every kernel at its multi-block size (phases a, b, b-ring, c at 2,048
    particles, e at up to 1,024) against the eager ``ref.py`` oracle of
    that kernel, which takes the kernel's own block-by-block steps op by
    op under XLA. Only f32 rounding differs (Mosaic and XLA contract the
    velocity chain differently), which 8 iterations of the swarm's
    feedback amplify from ulps. Bound: ``SAME_STEPS_RTOL``, on the gbest
    and, where the solve returns the swarm, on the relative L1 distance of
    all velocities, which a stale block revisit or a lost write-back moves
    even where the gbest sits on a bound (0.258 for one lost block of
    four at 1-D on the CPU).
  * kernel against the jnp engine.
    - Swarms of one particle block (phases c and e, at most 512
      particles): the two take the same steps. Bound: ``SAME_STEPS_RTOL``.
    - The paper's swarms (phases a and b) span many blocks. The fused
      kernel then publishes block by block inside an iteration (later
      blocks already steer toward what earlier blocks found) and the async
      kernel runs its blocks one after another, while the jnp engine
      publishes once per iteration or chunk: different schedules of the
      same descent. Bound: ``SCHEDULE_RTOL``, above the 4% spread seen
      across seeds on the CPU at 4,096 particles and 120-D.
  * every reported gbest is the objective at the reported position: jnp
    evaluates the problem at ``gbest_pos`` and must agree with ``gbest_fit``
    within ``EVAL_RTOL`` (f32 sums in a different order).
  * full-length runs: gbest finite, its position inside the bounds, and
    better than the best of the initial swarm (every problem here improves
    within its budget; a kernel that does not step would not).
  * zero failed requests on the serving paths.

Each phase prints JSON lines with its first-call seconds (trace, compile
and one run), its warm-call seconds (a second identical call) and their
difference as ``compile_s``. The last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Without a TPU, or outside a checkout (``src/`` next to this file), it exits
non-zero and prints no result line.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import math
import os
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

SEED = 0
SCHEDULE_RTOL = 0.10
SAME_STEPS_RTOL = 1e-3
EVAL_RTOL = 1e-5
HORIZON = 8
FULL_ITERS = 200
ONE_BLOCK = 512         # particles: pick_block_n gives one block up to here


FAILURES = []      # every failed check and phase error of this run


def _emit(**rec):
    print(json.dumps(rec, sort_keys=True), flush=True)


def _check(cond, what):
    """Record a failed check and go on: one run reports every failure."""
    if not cond:
        FAILURES.append(what)
        _emit(check_failed=what)
    return bool(cond)


def _timed(fn):
    """(result, first-call s, warm-call s): two identical calls, each
    waited on; the first traces and compiles."""
    import jax
    t0 = time.perf_counter()
    jax.block_until_ready(fn())
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    warm = time.perf_counter() - t0
    return out, first, warm


def _times(first, warm):
    return dict(first_call_s=round(first, 4), run_s=round(warm, 4),
                compile_s=round(max(0.0, first - warm), 4))


def _bounds(prob, dim):
    import numpy as np
    lo = np.broadcast_to(np.asarray(prob.lo, np.float32), (dim,))
    hi = np.broadcast_to(np.asarray(prob.hi, np.float32), (dim,))
    return lo, hi


def _init_best(prob, dim, particles, seed):
    from repro.core.pso import PSOConfig, init_swarm
    cfg = PSOConfig(dim=dim, particle_cnt=particles, fitness=prob).resolved()
    return float(init_swarm(cfg, seed).gbest_fit)


def _check_answer(tag, prob, dim, gbest_fit, gbest_pos, init_fit):
    """Full-length checks on one answer (canonical, maximized fitness)."""
    import numpy as np
    import jax.numpy as jnp
    pos = np.asarray(gbest_pos, np.float32)
    lo, hi = _bounds(prob, dim)
    if not _check(math.isfinite(gbest_fit) and np.all(np.isfinite(pos)),
                  f"{tag}: gbest not finite ({gbest_fit})"):
        return
    _check(np.all(pos >= lo) and np.all(pos <= hi),
           f"{tag}: gbest position {pos[:4]} outside the bounds")
    _check(gbest_fit > init_fit,
           f"{tag}: gbest {gbest_fit} no better than at init {init_fit}")
    evald = float(prob.max_fn(jnp.asarray(pos)))
    _check(abs(evald - gbest_fit) <= EVAL_RTOL * abs(gbest_fit) + 1e-3,
           f"{tag}: objective at gbest_pos {pos[:4]} is {evald}, reported "
           f"{gbest_fit}")


def _check_band(tag, fit, ref_fit, init_fit, bound):
    """Relative gap between two gbests (``tag`` names the two)."""
    scale = max(abs(fit), abs(ref_fit), abs(init_fit), 1e-12)
    gap = abs(fit - ref_fit) / scale
    _check(gap <= bound, f"{tag}: {fit} vs {ref_fit} (relative gap "
           f"{gap:.3g} > {bound})")
    return gap


@contextlib.contextmanager
def _no_cache_writes():
    """Keep the oracles' compiles out of the persistent cache: eager
    execution compiles hundreds of one-op programs. In a chip run that
    wrote them, every phase after the first oracle compiled slower (phase
    c's kernels 4x, phases d and e 6-8x in wall time)."""
    import jax
    key = "jax_persistent_cache_min_compile_time_secs"
    prev = getattr(jax.config, key)
    jax.config.update(key, 1e9)
    try:
        yield
    finally:
        jax.config.update(key, prev)


def _oracle_fit(prob, dim, particles, seed, variant, horizon,
                sync_every=None, topology="gbest"):
    """(gbest, velocities ``[N, D]``, seconds) after ``horizon``
    iterations of the eager ``ref.py`` oracle of the kernel the facade runs
    at this shape: the kernel's own block-by-block schedule, op by op under
    XLA."""
    from repro.core import ASYNC_SYNC_EVERY
    from repro.core.pso import PSOConfig, init_swarm
    from repro.kernels import ops, ref
    import numpy as np
    t0 = time.perf_counter()
    cfg = PSOConfig(dim=dim, particle_cnt=particles, fitness=prob,
                    topology=topology).resolved()
    s0 = init_swarm(cfg, seed)
    _, pos, vel, pbp, pbf, gp, gf = ops.state_to_kernel(s0, dim)
    kw = dict(ops._cfg_kwargs(cfg), d_real=dim)
    args = (int(s0.seed), 0, pos, vel, pbp, pbf, gp, float(gf[0]), horizon,
            ops._resolve_block(particles, None))
    with _no_cache_writes():
        if variant == "async":
            out = ref.run_fused_async_oracle(
                *args, sync_every or ASYNC_SYNC_EVERY, topology=topology,
                **kw)
        else:
            out = ref.run_fused_oracle(*args, **kw)
    return (float(out[5]), np.asarray(out[1])[:dim].T,
            time.perf_counter() - t0)


def _check_oracle(tag, state, oracle, init_fit):
    """Kernel state against ``_oracle_fit``: the gbest, and the whole
    swarm's velocities as a relative L1 distance. The velocities carry
    every step of every particle and never saturate, so a block whose steps
    went astray shows even where the gbest and the bests sit on a bound
    (phase a's cubic). Returns (gbest gap, velocity distance)."""
    import numpy as np
    tag = f"{tag}/kernel-vs-oracle"
    gap = _check_band(tag, float(state.gbest_fit), oracle[0], init_fit,
                      SAME_STEPS_RTOL)
    vk, vo = np.asarray(state.vel, np.float64), oracle[1].astype(np.float64)
    dist = float(np.abs(vk - vo).sum() / max(np.abs(vo).sum(), 1e-30))
    _check(dist <= SAME_STEPS_RTOL,
           f"{tag}: velocities {dist:.3g} apart (relative L1) > "
           f"{SAME_STEPS_RTOL}")
    return gap, dist


# --------------------------------------------------------------------------
# Phases on one chip
# --------------------------------------------------------------------------

def phase_solve(phase, problem, dim, particles, iters=FULL_ITERS,
                horizon=HORIZON, seed=SEED):
    """a/b: ``repro.solve`` per (variant, backend), full length and at the
    short horizon."""
    import repro
    prob = repro.resolve_problem(problem)
    init = _init_best(prob, dim, particles, seed)
    for variant in ("queue_lock", "async"):
        short = {}
        for backend in ("kernel", "jnp"):
            m = repro.Method(variant=variant, backend=backend)

            def run(k, m=m):
                return repro.solve(prob, dim=dim, particles=particles,
                                   iters=k, seed=seed, method=m).state

            st, first, warm = _timed(lambda: run(iters))
            tag = f"{phase}/{variant}/{backend}"
            _check_answer(tag, prob, dim, float(st.gbest_fit),
                          st.gbest_pos, init)
            short[backend] = run(horizon)
            _emit(phase=phase, problem=prob.name, dim=dim,
                  particles=particles, iters=iters, variant=variant,
                  backend=backend, gbest_fit=float(st.gbest_fit),
                  init_fit=init, **_times(first, warm))
        kernel, jnp_ = (float(short[b].gbest_fit) for b in ("kernel", "jnp"))
        gap = _check_band(f"{phase}/{variant}/kernel-vs-jnp", kernel, jnp_,
                          init, SCHEDULE_RTOL)
        _emit(phase=phase, check="kernel_vs_jnp", variant=variant,
              horizon=horizon, kernel_fit=kernel, jnp_fit=jnp_, rel_gap=gap,
              bound=SCHEDULE_RTOL)
        oracle = _oracle_fit(prob, dim, particles, seed, variant, horizon)
        gap, dist = _check_oracle(f"{phase}/{variant}", short["kernel"],
                                  oracle, init)
        _emit(phase=phase, check="kernel_vs_oracle", variant=variant,
              horizon=horizon, kernel_fit=kernel, oracle_fit=oracle[0],
              rel_gap=gap, vel_rel_l1=dist, bound=SAME_STEPS_RTOL,
              oracle_s=round(oracle[2], 4))


def phase_ring(phase="b-ring", problem="rastrigin", dim=120,
               particles=32768, sync_every=2, horizon=HORIZON, seed=SEED):
    """The async kernel with ``topology="ring"``: each block's chunk entry
    reads its neighbours' local bests by a leading-axis index. Several
    chunks per block, so the neighbours' values differ when they are read;
    against the oracle of the same stencil."""
    import repro
    prob = repro.resolve_problem(problem)
    init = _init_best(prob, dim, particles, seed)
    m = repro.Method(variant="async", backend="kernel", topology="ring",
                     sync_every=sync_every)
    st, first, warm = _timed(lambda: repro.solve(
        prob, dim=dim, particles=particles, iters=horizon, seed=seed,
        method=m).state)
    _check_answer(phase, prob, dim, float(st.gbest_fit), st.gbest_pos, init)
    oracle = _oracle_fit(prob, dim, particles, seed, "async", horizon,
                         sync_every=sync_every, topology="ring")
    gap, dist = _check_oracle(phase, st, oracle, init)
    _emit(phase=phase, check="kernel_vs_oracle", topology="ring", dim=dim,
          particles=particles, sync_every=sync_every, horizon=horizon,
          kernel_fit=float(st.gbest_fit), oracle_fit=oracle[0], rel_gap=gap,
          vel_rel_l1=dist, bound=SAME_STEPS_RTOL,
          oracle_s=round(oracle[2], 4), **_times(first, warm))


def phase_solve_many(phase="c", dim=10, particles=2048, swarms=16,
                     iters=FULL_ITERS, horizon=HORIZON, seed=SEED,
                     short_particles=ONE_BLOCK):
    """c: ``solve_many`` over 16 seeds, homogeneous (batched kernels) and
    one problem per row (hetero kernels): full length at ``particles``
    (several blocks per swarm); at the short horizon against the oracle
    per row at ``particles`` and against the jnp engine at
    ``short_particles`` (one block per swarm)."""
    import repro
    seeds = [seed + i for i in range(swarms)]
    mix = ("rastrigin", "sphere", "ackley", "griewank")
    cases = {"batched": dict(problem="rastrigin"),
             "hetero": dict(problems=[mix[i % len(mix)]
                                      for i in range(swarms)])}
    for kind, pk in cases.items():
        probs = [repro.resolve_problem(p) for p in
                 pk.get("problems", [pk.get("problem")] * swarms)]
        inits = [_init_best(p, dim, particles, s)
                 for p, s in zip(probs, seeds)]
        short_inits = [_init_best(p, dim, short_particles, s)
                       for p, s in zip(probs, seeds)]
        for variant in ("queue_lock", "async"):
            short = {}
            for backend in ("kernel", "jnp"):
                m = repro.Method(variant=variant, backend=backend)

                def run(k, n, m=m):
                    rs = repro.solve_many(seeds=seeds, dim=dim,
                                          particles=n, iters=k,
                                          method=m, **pk)
                    return [r.state for r in rs]

                states, first, warm = _timed(lambda: run(iters, particles))
                for i, (p, st) in enumerate(zip(probs, states)):
                    _check_answer(f"{phase}/{kind}/{variant}/{backend}/{i}",
                                  p, dim, float(st.gbest_fit),
                                  st.gbest_pos, inits[i])
                short[backend] = [float(st.gbest_fit)
                                  for st in run(horizon, short_particles)]
                if backend == "kernel":
                    multi = run(horizon, particles)
                _emit(phase=phase, kind=kind, swarms=swarms, dim=dim,
                      particles=particles, iters=iters, variant=variant,
                      backend=backend,
                      gbest_fit=[float(st.gbest_fit) for st in states],
                      **_times(first, warm))
            tag = f"{phase}/{kind}/{variant}"
            gaps = [_check_band(f"{tag}/row{i}/kernel-vs-jnp", k, j,
                                short_inits[i], SAME_STEPS_RTOL)
                    for i, (k, j) in enumerate(zip(short["kernel"],
                                                   short["jnp"]))]
            _emit(phase=phase, check="kernel_vs_jnp", kind=kind,
                  variant=variant, horizon=horizon,
                  particles=short_particles, max_rel_gap=max(gaps),
                  bound=SAME_STEPS_RTOL)
            gaps, secs = [], 0.0
            for i, (p, sd) in enumerate(zip(probs, seeds)):
                oracle = _oracle_fit(p, dim, particles, sd, variant, horizon)
                secs += oracle[2]
                gaps.append(_check_oracle(f"{tag}/row{i}", multi[i], oracle,
                                          inits[i]))
            _emit(phase=phase, check="kernel_vs_oracle", kind=kind,
                  variant=variant, horizon=horizon, particles=particles,
                  max_rel_gap=max(g for g, _ in gaps),
                  max_vel_rel_l1=max(d for _, d in gaps),
                  bound=SAME_STEPS_RTOL, oracle_s=round(secs, 4))


def _mixed_requests(n, variant, budgets, seed):
    """A serving mix: four built-ins over two solve shapes, the budgets
    taken in turn."""
    from repro.launch.serve import SolveRequest
    shapes = (("rastrigin", 10, 1024), ("ackley", 10, 1024),
              ("sphere", 2, 256), ("griewank", 2, 256))
    return [SolveRequest(dim=d, particle_cnt=p, fitness=f, seed=seed + i,
                         iters=budgets[i % len(budgets)], variant=variant)
            for i, (f, d, p) in ((i, shapes[i % len(shapes)])
                                 for i in range(n))]


def _request_init(q):
    import repro
    return _init_best(repro.resolve_problem(q.fitness), q.dim,
                      q.particle_cnt, q.seed)


def _check_results(tag, results, answers=True):
    """Zero failed requests; with ``answers``, the full-length checks on
    every result."""
    import repro
    failed = [r for r in results if not r.ok]
    if not _check(not failed, f"{tag}: {len(failed)} failed request(s): "
                  f"{[repr(r.error) for r in failed][:3]}"):
        return len(failed)
    for i, r in enumerate(results if answers else ()):
        q = r.request
        _check_answer(f"{tag}/{i}", repro.resolve_problem(q.fitness), q.dim,
                      float(r.gbest_fit), r.gbest_pos, _request_init(q))
    return len(failed)


def phase_stream(phase="d", n=24, iters=64, seed=SEED):
    """d: ``solve_stream`` through the continuous scheduler (jnp lanes;
    no kernel runs on this path)."""
    import repro
    reqs = _mixed_requests(n, "async", (iters, iters // 2, iters // 4),
                           seed)
    metrics = repro.ServingMetrics()
    t0 = time.perf_counter()
    res = repro.solve_stream(reqs, metrics=metrics)
    wall = time.perf_counter() - t0
    failed = _check_results(phase, res)
    snap = metrics.snapshot()
    _emit(phase=phase, requests=n, failed=failed, wall_s=round(wall, 4),
          counters=snap.get("counters", {}))


def phase_server(phase="e", n=16, iters=FULL_ITERS, horizon=HORIZON,
                 seed=SEED):
    """e: one ``SolveServer(backend="kernel")`` flush of mixed queue_lock
    and async requests; the same requests at the short horizon, one block
    per swarm, against the jnp server, and at their own sizes against the
    oracle."""
    import repro
    from repro.launch.serve import SolveServer
    reqs = (_mixed_requests(n // 2, "queue_lock", (iters,), seed)
            + _mixed_requests(n // 2, "async", (iters,), seed + n))
    srv = SolveServer(backend="kernel")
    t0 = time.perf_counter()
    res = srv.solve_all(reqs)
    wall = time.perf_counter() - t0
    failed = _check_results(phase, res)
    _emit(phase=phase, requests=n, failed=failed, wall_s=round(wall, 4),
          stats=srv.stats.as_dict())
    short = [dataclasses.replace(r, iters=horizon, particle_cnt=min(
        r.particle_cnt, ONE_BLOCK)) for r in reqs]
    k = SolveServer(backend="kernel").solve_all(short)
    j = SolveServer(backend="jnp").solve_all(short)
    _check_results(f"{phase}/short-kernel", k, answers=False)
    _check_results(f"{phase}/short-jnp", j, answers=False)
    gaps = [_check_band(f"{phase}/req{i}/kernel-vs-jnp", a.gbest_fit,
                        b.gbest_fit, _request_init(a.request),
                        SAME_STEPS_RTOL)
            for i, (a, b) in enumerate(zip(k, j))]
    _emit(phase=phase, check="kernel_vs_jnp", horizon=horizon,
          max_rel_gap=max(gaps), bound=SAME_STEPS_RTOL)
    # the requests at their own sizes (up to two blocks), per request
    # against the oracle
    full = SolveServer(backend="kernel").solve_all(
        [dataclasses.replace(r, iters=horizon) for r in reqs])
    _check_results(f"{phase}/short-full", full, answers=False)
    gaps, secs = [], 0.0
    for i, r in enumerate(full):
        q = r.request
        oracle, _, t = _oracle_fit(repro.resolve_problem(q.fitness), q.dim,
                                   q.particle_cnt, q.seed, q.variant,
                                   horizon, sync_every=q.sync_every)
        secs += t
        gaps.append(_check_band(f"{phase}/req{i}/kernel-vs-oracle",
                                r.gbest_fit, oracle, _request_init(q),
                                SAME_STEPS_RTOL))
    _emit(phase=phase, check="kernel_vs_oracle", horizon=horizon,
          max_rel_gap=max(gaps), bound=SAME_STEPS_RTOL,
          oracle_s=round(secs, 4))


# --------------------------------------------------------------------------
# Four chips: islands
# --------------------------------------------------------------------------

def phase_islands(phase="islands", islands=4, dim=120, particles=32768,
                  iters=FULL_ITERS, horizon=HORIZON, seed=SEED):
    """``Method(islands=4)``: the async ring and the barrier ``queue``
    variant, each against a one-chip jnp solve of the same swarm."""
    import repro
    prob = repro.resolve_problem("rastrigin")
    init = _init_best(prob, dim, particles, seed)
    # At ``horizon == sync_every == exchange_interval`` no best crosses a
    # block or an island before the last iteration, so the async ring
    # takes the one-chip async engine's steps (2e-7 apart on four virtual
    # CPU devices at 4,096 particles): the same-steps bound. The barrier
    # variant's islands draw random numbers by island-local particle index
    # and pool a best every iteration: a different descent, so the
    # schedule band.
    cases = (("queue", dict(exchange_interval=1), SCHEDULE_RTOL),
             ("async", dict(exchange_interval=horizon, sync_every=horizon),
              SAME_STEPS_RTOL))
    for variant, kw, bound in cases:
        short = {}
        for where, m in (
                ("islands", repro.Method(variant=variant, islands=islands,
                                         **kw)),
                ("one_chip", repro.Method(variant=variant, backend="jnp",
                                          **kw))):

            def run(k, m=m):
                return repro.solve(prob, dim=dim, particles=particles,
                                   iters=k, seed=seed, method=m).state

            st, first, warm = _timed(lambda: run(iters))
            tag = f"{phase}/{variant}/{where}"
            _check_answer(tag, prob, dim, float(st.gbest_fit),
                          st.gbest_pos, init)
            devices = len(st.pos.sharding.device_set)
            if m.islands:
                _check(devices == islands,
                       f"{tag}: state spans {devices} device(s), "
                       f"not {islands}")
            short[where] = float(run(horizon).gbest_fit)
            _emit(phase=phase, variant=variant, where=where, dim=dim,
                  particles=particles, iters=iters, state_devices=devices,
                  gbest_fit=float(st.gbest_fit), init_fit=init,
                  **_times(first, warm))
        gap = _check_band(f"{phase}/{variant}/islands-vs-one-chip",
                          short["islands"], short["one_chip"], init, bound)
        _emit(phase=phase, check="islands_vs_one_chip", variant=variant,
              horizon=horizon, islands_fit=short["islands"],
              one_chip_fit=short["one_chip"], rel_gap=gap, bound=bound)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases a-e on one chip; 4: the island phase")
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (default platform "
              f"{platform!r}); this run needs the chip and does not fall "
              f"back", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX finds "
              f"{len(devs)} TPU device(s)", file=sys.stderr)
        return 2
    try:
        import repro  # noqa: F401
    except ImportError as e:
        print(f"chip_smoke: cannot import the solver from {HERE}/src "
              f"({e}); run it from a checkout", file=sys.stderr)
        return 2
    from repro.serving import use_persistent_cache
    _emit(phase="setup", xla_cache_dir=use_persistent_cache(),
          xla_cache_max_size=jax.config.jax_compilation_cache_max_size,
          platform=platform, kind=devs[0].device_kind, count=len(devs))

    if args.chips == 4:
        phases = [lambda: phase_islands()]
    else:
        phases = [lambda: phase_solve("a", "cubic", 1, 131072),
                  lambda: phase_solve("b", "rastrigin", 120, 32768),
                  lambda: phase_ring(),
                  lambda: phase_solve_many(),
                  lambda: phase_stream(),
                  lambda: phase_server()]
    t0 = time.perf_counter()
    for phase in phases:
        try:
            phase()
        except Exception as e:   # a phase that raises fails the run; the
            # others still run, so one chip call reports every failure
            traceback.print_exc()
            _check(False, f"phase raised {type(e).__name__}: {e}"[:2000])
    _emit(phase="done", wall_s=round(time.perf_counter() - t0, 4),
          failures=len(FAILURES))
    if FAILURES:
        print(f"chip_smoke: {len(FAILURES)} check(s) failed; first: "
              f"{FAILURES[0]}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The compiled swarm init: one cached XLA program per config and shape.

``init_swarm`` / ``init_swarm_async`` / ``init_batch`` run as compiled
programs keyed on the static config and shape, with the seed as a uint32
operand (``repro.core.pso._init_program``, ``multi_swarm.init_batch``).
What these tests hold:

* a batch row, an async-locals init and a heterogeneous row are their
  standalone init bit for bit, in every field;
* the integer part (seed, iteration) and the draws scaled into the box
  (positions and velocities) are the op-by-op body's bits under
  ``jax.disable_jit()``: the products are rounded on their own, so no
  program contracts them into an FMA;
* a new seed never retraces, whatever its integer type, and a new mix of
  problems in a heterogeneous batch of the same size compiles nothing
  (the trace counter ``repro.telemetry.init_traces``).
"""
from __future__ import annotations

import jax
import numpy as np
import pytest

import repro.telemetry
from repro.core.multi_swarm import batch_row, init_batch, problem_rows
from repro.core.pso import (PSOConfig, init_async_locals, init_swarm,
                            init_swarm_async, seed_operand)

FIELDS = ("pos", "vel", "fit", "pbest_pos", "pbest_fit", "gbest_pos",
          "gbest_fit", "iteration", "seed")
CEC = ("sphere", "rosenbrock", "griewank", "ackley", "rastrigin")


def _assert_same(a, b, fields=FIELDS, msg=""):
    for f in fields:
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)),
                                      err_msg=f"{msg}: {f}")


@pytest.mark.parametrize("dim,n,fitness", [
    (1, 256, "cubic"), (3, 64, "sphere"), (10, 40, "rastrigin"),
    (10, 128, "ackley"), (5, 64, "griewank"), (2, 96, "rosenbrock")])
def test_batch_rows_equal_standalone_init(dim, n, fitness):
    cfg = PSOConfig(dim=dim, particle_cnt=n, fitness=fitness)
    seeds = [3, 17, 2 ** 31 - 5, 40_000, 0]
    batch = init_batch(cfg, seeds)
    for s, sd in enumerate(seeds):
        _assert_same(batch_row(batch, s), init_swarm(cfg, sd),
                     msg=f"row {s}")


@pytest.mark.parametrize("n_blocks", [None, 4])
def test_async_init_is_init_plus_seeded_locals(n_blocks):
    cfg = PSOConfig(dim=5, particle_cnt=64, fitness="sphere")
    a = init_swarm_async(cfg, 11, n_blocks=n_blocks)
    s = init_swarm(cfg, 11)
    _assert_same(a, s)
    lbp, lbf = init_async_locals(s, n_blocks or a.lbest_fit.shape[0])
    np.testing.assert_array_equal(np.asarray(a.lbest_pos), np.asarray(lbp))
    np.testing.assert_array_equal(np.asarray(a.lbest_fit), np.asarray(lbf))


@pytest.mark.parametrize("dim,n", [(10, 40), (3, 64), (1, 64)])
def test_hetero_rows_equal_their_standalone_init(dim, n):
    """Every field, fitness included: a mixed row is initialized by its own
    problem's program."""
    probs = list(CEC) + ["cubic", "sphere", "ackley"]
    seeds = list(range(100, 100 + len(probs)))
    rows, table = problem_rows(probs, dim)
    batch = init_batch(PSOConfig(dim=dim, particle_cnt=n).resolved(), seeds,
                       rows=rows, table=table)
    for s, (p, sd) in enumerate(zip(probs, seeds)):
        ref = init_swarm(PSOConfig(dim=dim, particle_cnt=n, fitness=p), sd)
        _assert_same(batch_row(batch, s), ref, msg=f"row {s} ({p})")


def test_hetero_batch_programs_keyed_on_problem_and_size():
    """Any mix of the same problems at the same S reuses the programs: one
    per problem and S, and one merge per shape."""
    cfg = PSOConfig(dim=4, particle_cnt=24).resolved()
    mixes = [["sphere"] * 5 + ["ackley"] * 3,
             ["ackley"] * 7 + ["sphere"],
             ["sphere", "ackley"] * 4,
             ["ackley"] * 2 + ["sphere"] * 6]
    rows, table = problem_rows(mixes[0], 4)
    init_batch(cfg, list(range(8)), rows=rows, table=table)
    t0 = repro.telemetry.init_traces()
    for k, mix in enumerate(mixes):
        rows, table = problem_rows(mix, 4)
        batch = init_batch(cfg, [10 * k + i for i in range(8)], rows=rows,
                           table=table)
        for s, p in enumerate(mix):
            ref = init_swarm(PSOConfig(dim=4, particle_cnt=24, fitness=p),
                             10 * k + s)
            _assert_same(batch_row(batch, s), ref, msg=f"mix {k} row {s}")
    assert repro.telemetry.init_traces() - t0 == 2   # the standalone refs


def test_op_by_op_init_counts_no_trace():
    cfg = PSOConfig(dim=2, particle_cnt=16, fitness="sphere")
    t0 = repro.telemetry.init_traces()
    with jax.disable_jit():
        for sd in range(3):
            init_swarm(cfg, sd)
    assert repro.telemetry.init_traces() == t0


@pytest.mark.parametrize("dim,n,fitness", [
    (1, 512, "cubic"), (10, 40, "ackley"), (3, 64, "rastrigin"),
    (4, 32, "griewank")])
def test_draws_equal_op_by_op_body(dim, n, fitness):
    cfg = PSOConfig(dim=dim, particle_cnt=n, fitness=fitness)
    compiled = init_swarm(cfg, 2 ** 31 + 7)
    with jax.disable_jit():
        eager = init_swarm(cfg, 2 ** 31 + 7)
    _assert_same(compiled, eager, fields=("pos", "vel", "pbest_pos",
                                          "iteration", "seed"))


def test_seed_operand_is_the_rng_key():
    for sd in (0, 7, 2 ** 31 - 1, -1, np.int64(2 ** 33 + 5), np.uint32(9)):
        want = np.asarray(jax.numpy.asarray(np.int64(sd) & 0xFFFFFFFF,
                                            np.uint32))
        got = seed_operand(sd)
        assert got.dtype == np.uint32 and got == want, sd
    assert seed_operand([1, -1]).tolist() == [1, 2 ** 32 - 1]


def test_new_seeds_cost_no_trace():
    """50 seeds as int, np.int64 and np.uint32: one trace per shape."""
    cfg = PSOConfig(dim=7, particle_cnt=24, fitness="griewank")
    t0 = repro.telemetry.init_traces()
    seeds = range(50)
    for sd in seeds:
        init_swarm(cfg, sd)
        init_swarm(cfg, np.int64(sd))
        init_swarm(cfg, np.uint32(sd))
    assert repro.telemetry.init_traces() - t0 == 1
    for sd in seeds:
        init_swarm_async(cfg, sd)
    assert repro.telemetry.init_traces() - t0 == 2
    for k in range(5):
        init_batch(cfg, [k, k + 1, k + 2])
        init_batch(cfg, np.arange(3, dtype=np.int64) + k)
    assert repro.telemetry.init_traces() - t0 == 3


def test_solve_facade_traces_init_once():
    t0 = repro.telemetry.init_traces()
    for sd in range(6):
        repro.solve("rastrigin", dim=3, particles=72, iters=2, seed=sd)
    assert repro.telemetry.init_traces() - t0 == 1


def test_hetero_lane_admissions_trace_once_per_problem():
    """Eight admissions of CEC problems into one hetero lane trace one init
    program per problem, and the next eight (new seeds) trace none."""
    from repro.launch.serve import SolveRequest
    from repro.serving import ContinuousScheduler

    def reqs(base):
        return [SolveRequest(dim=6, particle_cnt=40, fitness=CEC[k % 5],
                             seed=base + k, iters=16, variant="async",
                             sync_every=8)
                for k in range(8)]

    sched = ContinuousScheduler(lane_width=8)
    t0 = repro.telemetry.init_traces()
    first = sched.run(reqs(0))
    assert repro.telemetry.init_traces() - t0 == len(CEC)
    assert len(sched.snapshot()["lanes"]) == 1
    t1 = repro.telemetry.init_traces()
    sched.run(reqs(500))
    assert repro.telemetry.init_traces() == t1
    for r in first:
        assert r.error is None

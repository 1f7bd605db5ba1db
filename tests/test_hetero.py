"""Heterogeneous multi-problem batching: per-row ``lax.switch`` dispatch
through the jnp engine (``solve_many(problems=...)``), the batched fused
Pallas kernels (``fids``/``table``), the ``repro.solve_many`` facade and
the serving layer's registry coalescing.

A hetero row and the standalone solve of its problem are two compiled
programs (the box as runtime columns, the objective behind a switch), so
rows are held to their standalone solves under the contract of
tests/agreement.py (the heterogeneous-dispatch notes in
``repro.core.pso``), not bit for bit.
"""
import numpy as np
import pytest

import agreement
import repro
from repro.core import PSOConfig, init_swarm, solve
from repro.core.multi_swarm import (batch_row, hetero_fid, init_batch,
                                    problem_rows, solve_many)
from repro.core.problem import Problem, resolve_problem
from repro.kernels import ops

ALL_BUILTINS = ["cubic", "sphere", "rosenbrock", "griewank", "rastrigin",
                "ackley"]
SEEDS = list(range(len(ALL_BUILTINS)))


def _cfg(dim, n, fitness="cubic"):
    return PSOConfig(dim=dim, particle_cnt=n, fitness=fitness).resolved()


def _agree(row, ref, ck, fields, msg):
    agreement.assert_states_agree(row, ref, agreement.box_span(ck), fields,
                                  msg=msg)


def test_hetero_fid_eligibility():
    assert hetero_fid("sphere") is not None
    assert hetero_fid(resolve_problem("rastrigin")) is not None
    custom = Problem(name="mine", fn=lambda x: -(x * x).sum(-1),
                     lo=-1.0, hi=1.0)
    assert hetero_fid(custom) is None
    # a lookalike re-built sphere is NOT the registered instance
    sphere = resolve_problem("sphere")
    lookalike = Problem(name="sphere", fn=lambda x: -(x * x).sum(-1),
                        lo=sphere.lo, hi=sphere.hi)
    assert hetero_fid(lookalike) is None


def test_problem_rows_bounds_match_standalone_configs():
    rows, table = problem_rows(ALL_BUILTINS, 3, "float32")
    for s, nm in enumerate(ALL_BUILTINS):
        r = PSOConfig(dim=3, fitness=nm).resolved()
        np.testing.assert_array_equal(np.asarray(rows.lo[s]),
                                      np.full(3, r.min_pos, np.float32))
        np.testing.assert_array_equal(np.asarray(rows.hi[s]),
                                      np.full(3, r.max_pos, np.float32))
        np.testing.assert_array_equal(np.asarray(rows.mv[s]),
                                      np.full(3, r.max_v, np.float32))
        assert table[int(rows.fid[s])] == resolve_problem(nm)


def test_problem_rows_rejects_non_table_and_hooked_members():
    custom = Problem(name="mine", fn=lambda x: -(x * x).sum(-1),
                     lo=-1.0, hi=1.0)
    with pytest.raises(ValueError, match="dispatch table"):
        problem_rows(["sphere", custom], 2, "float32")
    proj = resolve_problem("sphere_simplex")    # mode="projection"
    assert proj.projection_fn is not None
    with pytest.raises(ValueError, match="projection/repair"):
        problem_rows([proj], 2, "float32", table=(proj,))


def test_hetero_init_rows_bit_identical_to_standalone():
    cfg = _cfg(10, 128)
    rows, table = problem_rows(ALL_BUILTINS, 10, cfg.dtype)
    batch = init_batch(cfg, SEEDS, rows=rows, table=table)
    for s, (nm, sd) in enumerate(zip(ALL_BUILTINS, SEEDS)):
        ref = init_swarm(_cfg(10, 128, nm), sd)
        row = batch_row(batch, s)
        for f in ("pos", "vel", "gbest_pos"):
            np.testing.assert_array_equal(np.asarray(getattr(row, f)),
                                          np.asarray(getattr(ref, f)),
                                          err_msg=f"row {s} ({nm}): {f}")
        # the objective behind the switch: fitness values under the contract
        for f in agreement.FIT_FIELDS:
            agreement.assert_fit_close(getattr(row, f), getattr(ref, f),
                                       f"row {s} ({nm}): {f}")


@pytest.mark.parametrize("variant", ["reduction", "queue", "queue_lock",
                                     "async"])
def test_jnp_switch_dispatch_parity_all_builtins(variant):
    """All six built-ins in ONE batch: every row agrees with the
    standalone solve of its problem."""
    cfg = PSOConfig(dim=10, particle_cnt=128)
    out = solve_many(cfg, SEEDS, iters=20, variant=variant,
                     problems=ALL_BUILTINS)
    for s, (nm, sd) in enumerate(zip(ALL_BUILTINS, SEEDS)):
        ck = _cfg(10, 128, nm)
        ref = solve(ck, seed=sd, iters=20, variant=variant)
        _agree(batch_row(out, s), ref, ck,
               agreement.STATE_FIELDS,
               f"row {s} ({nm})")


def test_kernel_sync_hetero_batch_parity():
    """Kernel 3h (scalar-fid conditional dispatch) vs per-row standalone
    fused kernel runs."""
    cfg = _cfg(10, 128)
    rows, table = problem_rows(ALL_BUILTINS, 10, cfg.dtype)
    batch = init_batch(cfg, SEEDS, rows=rows, table=table)
    out = ops.run_queue_lock_fused_batch(cfg, batch, iters=8,
                                         fids=rows.fid, table=table)
    for s, (nm, sd) in enumerate(zip(ALL_BUILTINS, SEEDS)):
        ck = _cfg(10, 128, nm)
        ref = ops.run_queue_lock_fused(ck, init_swarm(ck, sd), iters=8)
        _agree(batch_row(out, s), ref, ck,
               ("pos", "vel", "pbest_pos", "gbest_pos", "gbest_fit"),
               f"row {s} ({nm})")


def test_kernel_async_hetero_batch_parity():
    """Kernel 4h vs per-row standalone async kernel runs."""
    cfg = _cfg(10, 128)
    rows, table = problem_rows(ALL_BUILTINS, 10, cfg.dtype)
    batch = init_batch(cfg, SEEDS, rows=rows, table=table)
    out = ops.run_queue_lock_fused_async_batch(cfg, batch, iters=8,
                                               sync_every=4,
                                               fids=rows.fid, table=table)
    for s, (nm, sd) in enumerate(zip(ALL_BUILTINS, SEEDS)):
        ck = _cfg(10, 128, nm)
        ref = ops.run_queue_lock_fused_async(ck, init_swarm(ck, sd),
                                             iters=8, sync_every=4)
        _agree(batch_row(out, s), ref, ck,
               ("pos", "vel", "pbest_pos", "pbest_fit", "gbest_pos",
                "gbest_fit"), f"row {s} ({nm})")


def test_core_solve_many_problems_validation():
    with pytest.raises(ValueError, match="bounds"):
        solve_many(PSOConfig(dim=2, min_pos=-1.0, max_pos=1.0), [0, 1],
                   problems=["sphere", "cubic"])
    with pytest.raises(ValueError, match="problems for"):
        solve_many(PSOConfig(dim=2), [0, 1, 2],
                   problems=["sphere", "cubic"])


def test_facade_solve_many_problems():
    res = repro.solve_many(problems=ALL_BUILTINS, seeds=SEEDS, dim=10,
                           particles=128, iters=10, variant="queue")
    assert [r.problem.name for r in res] == ALL_BUILTINS
    for r, nm, sd in zip(res, ALL_BUILTINS, SEEDS):
        ref = repro.solve(nm, dim=10, particles=128, iters=10, seed=sd,
                          variant="queue")
        _agree(r.state, ref.state, ref.config, ("gbest_pos", "gbest_fit"),
               nm)
        # per-row Result accessors report in the row problem's own sense
        agreement.assert_fit_close(r.best_fit, ref.best_fit, nm)
        assert r.config.fitness == resolve_problem(nm)


def test_campaign_shape_rows_agree_with_standalone():
    """A campaign at the batch cell's own shape (``cec05-d10``: the five
    CEC 2005 functions at D = 10, 40 particles, w = 1, c1 = c2 = 2, the
    asynchronous variant publishing every 8 of 2,500 iterations), two runs
    each, through ``repro.solve_many`` as the benchmark calls it: every
    row reaches the answer of its standalone ``repro.solve`` (the outcome
    form of the contract; with w = 1 the swarm never settles, so a row is
    held to its solve, not to a separately written program)."""
    kw = dict(dim=10, particles=40, iters=2500, w=1.0, c1=2.0, c2=2.0,
              dtype="float32",
              method=repro.Method(variant="async", sync_every=8))
    probs = [f for f in ("sphere", "rosenbrock", "griewank", "ackley",
                         "rastrigin") for _ in range(2)]
    seeds = [2 ** 31 + 99 + k for k in range(len(probs))]
    res = repro.solve_many(problems=probs, seeds=seeds, **kw)
    for r, nm, sd in zip(res, probs, seeds):
        ref = repro.solve(nm, seed=sd, **kw)
        assert int(r.state.seed) == sd and int(r.state.iteration) == 2500
        agreement.assert_outcome(r.state.gbest_fit, r.state.gbest_pos,
                                 ref.state.gbest_fit,
                                 ref.config.fitness_fn, msg=f"{nm} {sd}")


def test_facade_solve_many_problems_validation():
    with pytest.raises(ValueError, match="exactly one"):
        repro.solve_many("sphere", [0, 1], problems=["sphere", "cubic"])
    with pytest.raises(ValueError, match="exactly one"):
        repro.solve_many(seeds=[0, 1])
    with pytest.raises(ValueError, match="problems for"):
        repro.solve_many(problems=["sphere"], seeds=[0, 1])
    with pytest.raises(ValueError, match="bounds"):
        repro.solve_many(problems=["sphere", "cubic"], seeds=[0, 1],
                         min_pos=-1.0)


def test_facade_solve_many_problems_kernel_backend():
    res = repro.solve_many(problems=["sphere", "rastrigin", "ackley"],
                           seeds=[0, 1, 2], dim=2, particles=128, iters=6,
                           backend="kernel", variant="queue_lock")
    for r, nm, sd in zip(res, ["sphere", "rastrigin", "ackley"], [0, 1, 2]):
        ck = _cfg(2, 128, nm)
        ref = ops.run_queue_lock_fused(ck, init_swarm(ck, sd), iters=6)
        _agree(r.state, ref, ck, ("gbest_pos", "gbest_fit"), nm)


# --------------------------------------------------------------------------
# Serving: registry coalescing
# --------------------------------------------------------------------------

def test_serve_mixed_builtin_trace_coalesces_to_one_dispatch():
    from repro.launch.serve import SolveRequest, SolveServer
    reqs = [SolveRequest(dim=10, particle_cnt=128, fitness=nm, seed=i,
                         iters=20, variant="queue")
            for i, nm in enumerate(ALL_BUILTINS)]
    srv = SolveServer()
    res = srv.solve_all(reqs)
    assert srv.stats.dispatches == 1
    assert srv.stats.hetero_dispatches == 1
    assert srv.stats.batch_fill == len(reqs)
    for r in res:
        ck = _cfg(10, 128, r.request.fitness)
        ref = solve(ck, seed=r.request.seed, iters=20, variant="queue")
        agreement.assert_fit_close(r.gbest_fit, ref.gbest_fit,
                                   r.request.fitness)
        agreement.assert_swarm_close(r.gbest_pos, ref.gbest_pos,
                                     agreement.box_span(ck),
                                     r.request.fitness)


def test_serve_coalesce_off_restores_content_hash_grouping():
    from repro.launch.serve import SolveRequest, SolveServer
    reqs = [SolveRequest(dim=3, particle_cnt=64, fitness=nm, seed=i,
                         iters=10, variant="queue")
            for i, nm in enumerate(["sphere", "cubic", "rastrigin"])]
    srv = SolveServer(coalesce_registry=False)
    srv.solve_all(reqs)
    assert srv.stats.dispatches == 3       # one per problem (legacy keys)
    assert srv.stats.hetero_dispatches == 0
    srv2 = SolveServer()
    srv2.solve_all(reqs)
    assert srv2.stats.dispatches == 1
    assert srv2.stats.batch_fill >= 2 * srv.stats.batch_fill


def test_serve_custom_problem_keeps_content_hash_isolation():
    from repro.launch.serve import SolveRequest, SolveServer
    custom = Problem(name="mine", fn=lambda x: -(x * x).sum(-1),
                     lo=-1.0, hi=1.0)
    reqs = [SolveRequest(dim=3, particle_cnt=64, fitness="sphere", seed=0,
                         iters=10, variant="queue"),
            SolveRequest(dim=3, particle_cnt=64, fitness=custom, seed=1,
                         iters=10, variant="queue")]
    assert reqs[0].hetero_eligible and not reqs[1].hetero_eligible
    srv = SolveServer()
    res = srv.solve_all(reqs)
    assert srv.stats.dispatches == 2       # custom cannot join the mix
    assert srv.stats.hetero_dispatches == 1
    ref = solve(PSOConfig(dim=3, particle_cnt=64, fitness=custom).resolved(),
                seed=1, iters=10, variant="queue")
    agreement.assert_fit_close(res[1].gbest_fit, ref.gbest_fit)


def test_serve_kernel_backend_hetero_dispatch():
    from repro.launch.serve import SolveRequest, SolveServer
    names = ["sphere", "rastrigin", "ackley"]
    for variant in ("queue_lock", "async"):
        reqs = [SolveRequest(dim=2, particle_cnt=128, fitness=nm, seed=i,
                             iters=6, variant=variant)
                for i, nm in enumerate(names)]
        srv = SolveServer(backend="kernel")
        res = srv.solve_all(reqs)
        assert srv.stats.dispatches == 1
        for r in res:
            ck = _cfg(2, 128, r.request.fitness)
            st = init_swarm(ck, r.request.seed)
            if variant == "queue_lock":
                ref = ops.run_queue_lock_fused(ck, st, iters=6)
            else:
                ref = ops.run_queue_lock_fused_async(ck, st, iters=6)
            agreement.assert_swarm_close(r.gbest_pos, ref.gbest_pos,
                                         agreement.box_span(ck), variant)
            agreement.assert_fit_close(r.gbest_fit, ref.gbest_fit, variant)

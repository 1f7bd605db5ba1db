"""Batched multi-swarm engine tests (repro.core.multi_swarm + the batched
fused Pallas kernel + the request-batching front end).

The load-bearing invariant: batching is a *scheduling* transform, never a
semantic one — row s of any batch computes the corresponding standalone
single-swarm solve (same seed, same variant, same block size). Checks that
compare a batch of other sizes, or the flush server's rows, with a
standalone solve use the contract of tests/agreement.py (two programs).
"""
import jax.numpy as jnp
import numpy as np
import pytest

import agreement
from repro.core import (PSOConfig, batch_row, best_of_batch, init_batch,
                        init_swarm, run_many, solve, solve_many)
from repro.core.tuner import PSOTuner, PSO_COEFF_DIMS, make_solve_many_fitness
from repro.kernels import ops

# >= 8 heterogeneous seeds (acceptance criterion), spread over the u32 range
SEEDS = [0, 1, 7, 42, 99, 123, 100000, 2 ** 31 - 5]


@pytest.mark.parametrize("variant", ["reduction", "queue", "queue_lock"])
def test_solve_many_rows_bit_identical_to_solve(variant):
    cfg = PSOConfig(dim=3, particle_cnt=64, fitness="rastrigin")
    b = solve_many(cfg, SEEDS, iters=25, variant=variant)
    for i, sd in enumerate(SEEDS):
        s = solve(cfg, seed=sd, iters=25, variant=variant)
        # exact: same RNG counters, same arithmetic, vmap only reschedules
        assert np.asarray(b.gbest_fit)[i] == np.asarray(s.gbest_fit)
        np.testing.assert_array_equal(np.asarray(b.pos[i]),
                                      np.asarray(s.pos))
        np.testing.assert_array_equal(np.asarray(b.pbest_fit[i]),
                                      np.asarray(s.pbest_fit))
        np.testing.assert_array_equal(np.asarray(b.gbest_pos[i]),
                                      np.asarray(s.gbest_pos))
    assert int(b.iteration[0]) == 25


def test_batched_fused_kernel_bit_identical_to_single():
    """Kernel path: batched pallas_call row s == standalone fused call."""
    cfg = PSOConfig(dim=7, particle_cnt=256, fitness="cubic")
    b = init_batch(cfg, SEEDS[:4])
    out = ops.run_queue_lock_fused_batch(cfg, b, iters=4, block_n=128)
    for s in range(4):
        single = ops.run_queue_lock_fused(cfg, batch_row(b, s), iters=4,
                                          block_n=128)
        np.testing.assert_array_equal(np.asarray(out.pos[s]),
                                      np.asarray(single.pos))
        np.testing.assert_array_equal(np.asarray(out.gbest_fit)[s],
                                      np.asarray(single.gbest_fit))
        np.testing.assert_array_equal(np.asarray(out.gbest_pos[s]),
                                      np.asarray(single.gbest_pos))
        np.testing.assert_array_equal(np.asarray(out.pbest_fit[s]),
                                      np.asarray(single.pbest_fit))


def test_batched_fused_kernel_matches_vmapped_jnp_path():
    """Single-block regime: the kernel's in-iteration gbest freshness
    coincides with synchronous queue-lock, so the batched kernel and the
    vmapped jnp path must agree swarm-for-swarm."""
    cfg = PSOConfig(dim=2, particle_cnt=128, fitness="cubic")
    b = init_batch(cfg, SEEDS[:4])
    k = ops.run_queue_lock_fused_batch(cfg, b, iters=5, block_n=128)
    j = run_many(cfg, b, 5, "queue_lock")
    np.testing.assert_allclose(np.asarray(k.gbest_fit),
                               np.asarray(j.gbest_fit), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(k.pos), np.asarray(j.pos),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("fitness,dim,ok", [
    ("cubic", 1, lambda gf: np.all(np.abs(gf - 900000.0) < 900.0)),
    ("rastrigin", 3, lambda gf: np.all(gf > -5.0)),   # optimum 0
])
def test_mixed_seed_batch_converges(fitness, dim, ok):
    cfg = PSOConfig(dim=dim, particle_cnt=128, fitness=fitness, w=0.7)
    b = solve_many(cfg, SEEDS, iters=150, variant="queue")
    gf = np.asarray(b.gbest_fit)
    assert gf.shape == (len(SEEDS),)
    assert ok(gf), gf


def test_per_swarm_coeffs():
    """Uniform coeffs == the config's own floats reproduce the default path;
    heterogeneous coeffs actually change per-swarm trajectories."""
    cfg = PSOConfig(dim=4, particle_cnt=64, fitness="sphere").resolved()
    s_cnt = 4
    seeds = SEEDS[:s_cnt]
    uniform = (jnp.full(s_cnt, cfg.w), jnp.full(s_cnt, cfg.c1),
               jnp.full(s_cnt, cfg.c2))
    a = solve_many(cfg, seeds, iters=10, coeffs=uniform)
    bb = solve_many(cfg, seeds, iters=10)
    # allclose, not exact: traced coeffs vs trace-time-folded floats are
    # different compiled programs (only seed-batching is exact-by-contract),
    # and ulp-level differences compound over the 10 chaotic iterations
    np.testing.assert_allclose(np.asarray(a.pos), np.asarray(bb.pos),
                               rtol=2e-3, atol=2e-3)
    hetero = (jnp.asarray([0.3, 0.5, 0.7, 0.9]), uniform[1], uniform[2])
    c = solve_many(cfg, seeds, iters=10, coeffs=hetero)
    assert not np.array_equal(np.asarray(c.pos), np.asarray(a.pos))


def test_best_of_batch():
    cfg = PSOConfig(dim=1, particle_cnt=64)
    b = solve_many(cfg, SEEDS, iters=50)
    fit, pos, idx = best_of_batch(b)
    gf = np.asarray(b.gbest_fit)
    assert float(fit) == gf.max()
    np.testing.assert_array_equal(np.asarray(pos),
                                  np.asarray(b.gbest_pos[int(idx)]))


def test_tuner_batched_evaluation_on_solve_many():
    """PSOTuner with make_solve_many_fitness: the whole population x probe
    grid runs as one batched device program per tuner iteration."""
    cfg = PSOConfig(dim=5, particle_cnt=64, fitness="rastrigin")
    bf = make_solve_many_fitness(cfg, seeds=[0, 1], iters=25)
    tuner = PSOTuner(PSO_COEFF_DIMS, particles=6, seed=0)
    res = tuner.run(batch_fitness=bf, iters=2)
    assert res.evaluations == 6 * 2
    assert np.isfinite(res.best_fitness)
    assert set(res.best_params) == {"w", "c1", "c2"}
    # batched scores must match scoring one candidate alone (row identity)
    one = bf([res.best_params])
    np.testing.assert_allclose(one[0], res.best_fitness, rtol=1e-6)


def test_tuner_rejects_ambiguous_fitness_args():
    tuner = PSOTuner(PSO_COEFF_DIMS, particles=4)
    with pytest.raises(ValueError):
        tuner.run()
    with pytest.raises(ValueError):
        tuner.run(lambda p: 0.0, batch_fitness=lambda pop: [0.0] * len(pop))


def test_solve_server_batches_and_matches_direct_solve():
    from repro.launch.serve import SolveRequest, SolveServer
    reqs = [SolveRequest(dim=1, particle_cnt=64, fitness="cubic",
                         seed=i, iters=30) for i in range(5)]
    reqs += [SolveRequest(dim=3, particle_cnt=64, fitness="rastrigin",
                          seed=i, iters=30) for i in range(3)]
    srv = SolveServer(max_batch=16)
    results = srv.solve_all(reqs)
    assert len(results) == 8
    # two compilation groups -> two dispatches; 5 requests pad to bucket 8,
    # 3 requests to the restored minimum bucket 4
    assert srv.stats.dispatches == 2
    assert srv.stats.padded_rows == (8 - 5) + (4 - 3)
    for r in results:
        cfg = r.request.config()
        direct = solve(cfg, seed=r.request.seed, iters=r.request.iters,
                       variant=r.request.variant)
        # a batched row and a standalone solve: the contract
        agreement.assert_fit_close(r.gbest_fit, direct.gbest_fit)
        agreement.assert_swarm_close(r.gbest_pos, direct.gbest_pos,
                                     agreement.box_span(cfg))


def test_solve_server_rejects_sub_bucket_max_batch():
    from repro.launch.serve import SolveServer
    SolveServer(max_batch=4)       # the smallest bucket
    with pytest.raises(ValueError):
        SolveServer(max_batch=2)   # below the smallest bucket
    with pytest.raises(ValueError):
        SolveServer(backend="bogus")


@pytest.mark.parametrize("s_cnt", [1, 3, 4, 8])
def test_bucket4_row_identity_regression(s_cnt):
    """Rows of a batch of any size against their standalone solves, at the
    shape (dim=3, n=64, sphere) whose S=4 program XLA:CPU contracts an ulp
    off the standalone program's velocity chain. The batch runs as it is,
    no row added, and every row agrees with its solve under the contract
    (tests/agreement.py): 100 chaotic iterations, so the outcome form."""
    from repro.launch.serve import SolveRequest, SolveServer
    cfg = PSOConfig(dim=3, particle_cnt=64, fitness="sphere")
    seeds = list(range(s_cnt))
    b = run_many(cfg, init_batch(cfg, seeds), 100, "queue")
    assert b.swarm_cnt == s_cnt
    for i, sd in enumerate(seeds):
        s = solve(cfg, seed=sd, iters=100, variant="queue")
        # the integer fields stay exact: a swapped or mis-seeded row shows
        # here even where the gbest values sit near sphere's optimum 0
        assert int(b.seed[i]) == sd and int(b.iteration[i]) == 100
        agreement.assert_outcome(b.gbest_fit[i], b.gbest_pos[i],
                                 s.gbest_fit, cfg.fitness_fn,
                                 msg=f"row {i}")
    if s_cnt != 4:
        return
    # serving level: a 4-request flush rides bucket 4
    reqs = [SolveRequest(dim=3, particle_cnt=64, fitness="sphere", seed=i,
                         iters=100, variant="queue") for i in seeds]
    srv = SolveServer(max_batch=64)
    for r in srv.solve_all(reqs):
        direct = solve(cfg, seed=r.request.seed, iters=100, variant="queue")
        assert r.batch_size == 4
        agreement.assert_outcome(r.gbest_fit, r.gbest_pos,
                                 direct.gbest_fit, cfg.fitness_fn,
                                 msg=f"seed {r.request.seed}")


def test_solve_server_kernel_backend():
    from repro.launch.serve import SolveRequest, SolveServer
    reqs = [SolveRequest(dim=2, particle_cnt=128, fitness="cubic", seed=i,
                         iters=4, variant="queue_lock") for i in range(3)]
    srv = SolveServer(max_batch=8, backend="kernel", block_n=128)
    results = srv.solve_all(reqs)
    for r in results:
        cfg = r.request.config().resolved()
        direct = ops.run_queue_lock_fused(
            cfg, init_swarm(cfg, r.request.seed), iters=4, block_n=128)
        assert r.gbest_fit == float(direct.gbest_fit)

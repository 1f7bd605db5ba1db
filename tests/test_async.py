"""Async queue-lock validation: the paper's enhanced variant across all
four layers — Pallas kernel vs bit-exact oracle, sync_every=1 / single-block
identity with the synchronous fused kernel, batched row identity, the jnp
fallback's staleness bound and convergence quality, and the serving path.
"""
import jax.numpy as jnp
import numpy as np
import pytest

import agreement
from repro.core import (PSOConfig, batch_row, init_async_locals, init_batch,
                        init_swarm, publish_async_locals, run, run_async,
                        solve, solve_many, step_async)
from repro.kernels import ops, ref

SEEDS = [0, 1, 7, 42, 99, 123, 100000, 2 ** 31 - 5]


def _oracle_kwargs(cfg, dim):
    kw = ops._cfg_kwargs(cfg)
    kw["d_real"] = dim
    return kw


# --------------------------------------------------------------------------
# Kernel: the sync fused kernel is a special case of the async one.
# --------------------------------------------------------------------------

@pytest.mark.parametrize("sync_every", [1, 4])
def test_async_kernel_single_block_bit_identical_to_fused(sync_every):
    """With one particle block the block-local best IS the global best, so
    the async kernel — through an entirely different grid (block-major,
    chunked, fori-loop body, local-best carry) — must reproduce the
    synchronous fused kernel bit-for-bit for EVERY sync_every. This is the
    acceptance identity: run_queue_lock_fused_async(sync_every=1) ==
    run_queue_lock_fused."""
    cfg = PSOConfig(dim=3, particle_cnt=128, fitness="cubic")
    s = init_swarm(cfg, 7)
    a = ops.run_queue_lock_fused_async(cfg, s, iters=8,
                                       sync_every=sync_every, block_n=128)
    f = ops.run_queue_lock_fused(cfg, s, iters=8, block_n=128)
    for name in ("pos", "vel", "pbest_pos", "pbest_fit",
                 "gbest_pos", "gbest_fit"):
        np.testing.assert_array_equal(np.asarray(getattr(a, name)),
                                      np.asarray(getattr(f, name)),
                                      err_msg=name)
    assert int(a.iteration) == int(f.iteration) == 8


ASYNC_SWEEP = [
    # (dim, n, block_n, iters, sync_every) — multi-block relaxed schedules,
    # including a remainder split (10 % 4) and the paper's 120D regime.
    (1, 128, 64, 8, 2),
    (2, 256, 64, 10, 4),
    (7, 256, 128, 8, 8),
    (120, 256, 128, 6, 3),
    pytest.param(33, 384, 128, 9, 4, marks=pytest.mark.slow),
]


@pytest.mark.parametrize("dim,n,bn,iters,k", ASYNC_SWEEP)
def test_async_kernel_vs_oracle(dim, n, bn, iters, k):
    """Multi-block async kernel vs the eager oracle that mirrors the
    block-major publication order bit-exactly."""
    cfg = PSOConfig(dim=dim, particle_cnt=n, fitness="cubic").resolved()
    s = init_swarm(cfg, 42)
    out = ops.run_queue_lock_fused_async(cfg, s, iters=iters, sync_every=k,
                                         block_n=bn)
    scal, pos, vel, pbp, pbf, gp, gf = ops.state_to_kernel(s, dim)
    kw = _oracle_kwargs(cfg, dim)
    fitness_name = kw.pop("fitness")
    o = ref.run_fused_async_oracle(
        int(s.seed), int(s.iteration), pos, vel, pbp, pbf, gp,
        float(gf[0]), iters, bn, k, fitness=fitness_name, **kw)
    # atol: the kernel's compiled fori-loop chunk body may FMA-contract one
    # ulp differently from the oracle's eager per-iteration loop; chaotic
    # dynamics amplify it (~1e-5 -> ~1e-3 over these spans on [-100, 100])
    np.testing.assert_allclose(np.asarray(ops.pack_dmajor(out.pos, dim)),
                               np.asarray(o[0]), rtol=1e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(out.pbest_fit),
                               np.asarray(o[3])[0], rtol=1e-4, atol=0.5)
    np.testing.assert_allclose(float(out.gbest_fit), float(o[5]), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(out.gbest_pos),
                               np.asarray(o[4])[:dim, 0],
                               rtol=1e-4, atol=1e-4)


def test_async_kernel_iteration_counter_chains():
    """Two async calls of k iters == one call of 2k iters (RNG continuity)
    in the single-block regime where the schedule is call-split invariant."""
    cfg = PSOConfig(dim=9, particle_cnt=128, fitness="sphere")
    s = init_swarm(cfg, 13)
    a = ops.run_queue_lock_fused_async(cfg, s, iters=4, sync_every=2,
                                       block_n=128)
    a = ops.run_queue_lock_fused_async(cfg, a, iters=4, sync_every=2,
                                       block_n=128)
    b = ops.run_queue_lock_fused_async(cfg, s, iters=8, sync_every=2,
                                       block_n=128)
    np.testing.assert_allclose(np.asarray(a.pos), np.asarray(b.pos),
                               rtol=1e-5, atol=1e-5)
    assert int(a.iteration) == int(b.iteration) == 8


def test_async_batch_rows_bit_identical_to_single():
    """Batched async kernel row s == standalone async kernel (exact)."""
    cfg = PSOConfig(dim=7, particle_cnt=256, fitness="cubic")
    b = init_batch(cfg, SEEDS[:4])
    out = ops.run_queue_lock_fused_async_batch(cfg, b, iters=10,
                                               sync_every=4, block_n=64)
    for s in range(4):
        single = ops.run_queue_lock_fused_async(
            cfg, batch_row(b, s), iters=10, sync_every=4, block_n=64)
        np.testing.assert_array_equal(np.asarray(out.pos[s]),
                                      np.asarray(single.pos))
        np.testing.assert_array_equal(np.asarray(out.gbest_fit)[s],
                                      np.asarray(single.gbest_fit))
        np.testing.assert_array_equal(np.asarray(out.gbest_pos[s]),
                                      np.asarray(single.gbest_pos))
        np.testing.assert_array_equal(np.asarray(out.pbest_fit[s]),
                                      np.asarray(single.pbest_fit))


# --------------------------------------------------------------------------
# Library fallback: relaxed-consistency semantics.
# --------------------------------------------------------------------------

def test_async_staleness_bound():
    """The consistency contract: every block's local best is never below
    the shared gbest of the last sync point (staleness <= sync_every), and
    at each sync point the shared gbest equals the true swarm-wide best."""
    cfg = PSOConfig(dim=4, particle_cnt=128, fitness="rastrigin").resolved()
    k, nb = 4, 4
    s = init_swarm(cfg, 5)
    local = init_async_locals(s, nb)
    last_sync_gbest = float(s.gbest_fit)
    for t in range(1, 3 * k + 1):
        s, local = step_async(cfg, s, local)
        lbf = np.asarray(local[1])
        # between syncs: no block has forgotten the last synced best
        assert np.all(lbf >= last_sync_gbest - 0.0)
        # shared gbest is untouched (stale) between syncs
        if t % k:
            assert float(s.gbest_fit) == last_sync_gbest
        else:
            s, local = publish_async_locals(s, local)
            # sync point: shared best == true best over everything seen
            true_best = max(float(np.max(np.asarray(s.pbest_fit))),
                            last_sync_gbest)
            assert float(s.gbest_fit) == true_best
            # pull: every block now sees the fresh shared best
            np.testing.assert_array_equal(
                np.asarray(local[1]),
                np.full(nb, float(s.gbest_fit), np.float32))
            last_sync_gbest = float(s.gbest_fit)


def test_run_async_final_flush():
    """run_async always ends on a sync: gbest_fit == max(pbest_fit), for
    multiple-of-sync_every and remainder iteration counts alike."""
    cfg = PSOConfig(dim=2, particle_cnt=256, fitness="cubic")
    s = init_swarm(cfg, 3)
    for iters in (8, 11):                  # 11 = 2 chunks of 4 + rem 3
        out = run_async(cfg, s, iters, sync_every=4, n_blocks=4)
        assert float(out.gbest_fit) == float(jnp.max(out.pbest_fit))
        assert int(out.iteration) == iters


@pytest.mark.parametrize("fitness,dim,tol", [
    ("cubic", 1, 0.01),        # fraction of the optimum's magnitude
    ("sphere", 3, 0.02),
    ("rastrigin", 3, 0.02),
])
def test_async_convergence_quality_vs_sync(fitness, dim, tol):
    """Relaxed consistency must not cost convergence: async final gbest
    within a small tolerance of synchronous queue_lock (both near-optimal).
    Tolerance is relative to the optimum magnitude / search-span scale."""
    cfg = PSOConfig(dim=dim, particle_cnt=256, fitness=fitness,
                    w=0.7).resolved()
    s = init_swarm(cfg, 0)
    sync = run(cfg, s, 200, "queue_lock")
    a = run_async(cfg, s, 200, sync_every=16, n_blocks=4)
    scale = max(abs(float(sync.gbest_fit)), 1.0)
    gap = float(sync.gbest_fit) - float(a.gbest_fit)
    assert gap <= tol * scale, (float(a.gbest_fit), float(sync.gbest_fit))


def test_solve_many_async_rows_bit_identical_to_solve():
    """variant="async" through the batched engine: vmapped run_async row s
    is bit-identical to the standalone solve (the engine's contract)."""
    cfg = PSOConfig(dim=3, particle_cnt=64, fitness="rastrigin")
    b = solve_many(cfg, SEEDS, iters=25, variant="async")
    for i, sd in enumerate(SEEDS):
        s = solve(cfg, seed=sd, iters=25, variant="async")
        assert np.asarray(b.gbest_fit)[i] == np.asarray(s.gbest_fit)
        np.testing.assert_array_equal(np.asarray(b.pos[i]),
                                      np.asarray(s.pos))
        np.testing.assert_array_equal(np.asarray(b.pbest_fit[i]),
                                      np.asarray(s.pbest_fit))
    assert int(b.iteration[0]) == 25


def test_run_variant_async_dispatch():
    """run()/solve() accept variant="async" and actually relax: sync_every
    changes the trajectory (different consistency => different dynamics).
    particle_cnt=1024 so the default block picker yields > 1 block — with a
    single block the async schedule degenerates to the synchronous one and
    sync_every would be a no-op."""
    cfg = PSOConfig(dim=2, particle_cnt=1024, fitness="rastrigin")
    s = init_swarm(cfg, 1)
    a1 = run(cfg, s, 12, "async", sync_every=1)
    a8 = run(cfg, s, 12, "async", sync_every=8)
    assert a1.pos.shape == a8.pos.shape
    assert not np.array_equal(np.asarray(a1.pos), np.asarray(a8.pos))


# --------------------------------------------------------------------------
# Serving surface.
# --------------------------------------------------------------------------

def test_solve_server_async_variant_both_backends():
    from repro.launch.serve import SolveRequest, SolveServer
    reqs = [SolveRequest(dim=2, particle_cnt=128, fitness="cubic", seed=i,
                         iters=8, variant="async", sync_every=4)
            for i in range(3)]
    # jnp backend == solve_many(variant="async") == standalone run_async:
    # three requests ride bucket 4, a batch program of 4 rows against a
    # standalone one, so under the contract (tests/agreement.py)
    jnp_srv = SolveServer(max_batch=8, backend="jnp")
    for r in jnp_srv.solve_all(reqs):
        cfg = r.request.config().resolved()
        direct = run_async(cfg, init_swarm(cfg, r.request.seed), 8,
                           sync_every=4)
        agreement.assert_fit_close(r.gbest_fit, direct.gbest_fit)
    # kernel backend routes through the batched async pallas_call
    k_srv = SolveServer(max_batch=8, backend="kernel", block_n=64)
    for r in k_srv.solve_all(reqs):
        cfg = r.request.config().resolved()
        direct = ops.run_queue_lock_fused_async(
            cfg, init_swarm(cfg, r.request.seed), iters=8, sync_every=4,
            block_n=64)
        assert r.gbest_fit == float(direct.gbest_fit)


def test_sync_every_is_part_of_compile_key_for_async_only():
    from repro.launch.serve import SolveRequest
    a = SolveRequest(variant="async", sync_every=4)
    b = SolveRequest(variant="async", sync_every=16)
    assert a.batch_key != b.batch_key
    # sync variants ignore sync_every — keying on it would split
    # otherwise-identical requests into separate batches
    c = SolveRequest(variant="queue_lock", sync_every=4)
    d = SolveRequest(variant="queue_lock", sync_every=16)
    assert c.batch_key == d.batch_key


def test_async_kernel_externalizes_and_resumes_local_bests():
    """The async kernel wrappers surface the block-local best buffers in
    SwarmState.lbest_* and resume from them, so a chunked kernel run keeps
    the staleness window across calls (checkpoint/resume parity with the
    jnp path). In the single-block regime this must stay bit-identical to
    one long call (the call-split-invariant schedule)."""
    cfg = PSOConfig(dim=5, particle_cnt=128, fitness="cubic")
    s = init_swarm(cfg, 11)
    a = ops.run_queue_lock_fused_async(cfg, s, iters=4, sync_every=2,
                                       block_n=128)
    assert a.lbest_fit is not None and a.lbest_fit.shape == (1,)
    assert a.lbest_pos.shape == (1, 5)
    b = ops.run_queue_lock_fused_async(cfg, a, iters=4, sync_every=2,
                                       block_n=128)
    one = ops.run_queue_lock_fused_async(cfg, s, iters=8, sync_every=2,
                                         block_n=128)
    for name in ("pos", "vel", "pbest_fit", "gbest_pos", "gbest_fit",
                 "lbest_pos", "lbest_fit"):
        np.testing.assert_array_equal(np.asarray(getattr(b, name)),
                                      np.asarray(getattr(one, name)),
                                      err_msg=name)
    # multi-block: the buffers match what the eager oracle tracks
    cfg2 = PSOConfig(dim=2, particle_cnt=256, fitness="cubic").resolved()
    s2 = init_swarm(cfg2, 42)
    out = ops.run_queue_lock_fused_async(cfg2, s2, iters=8, sync_every=4,
                                         block_n=64)
    scal, pos, vel, pbp, pbf, gp, gf = ops.state_to_kernel(s2, 2)
    kw = _oracle_kwargs(cfg2, 2)
    fitness = kw.pop("fitness")
    o = ref.run_fused_async_oracle(
        int(s2.seed), int(s2.iteration), pos, vel, pbp, pbf, gp,
        float(gf[0]), 8, 64, 4, fitness=fitness, **kw)
    np.testing.assert_allclose(np.asarray(out.lbest_fit),
                               np.asarray(o[7]), rtol=1e-4, atol=1e-3)


def test_async_kernel_degenerate_inputs_clamp_like_jnp():
    """sync_every <= 0 / > iters and iters == 0 must not crash the kernel
    wrapper (clamped exactly like run_async)."""
    cfg = PSOConfig(dim=2, particle_cnt=128, fitness="cubic")
    s = init_swarm(cfg, 0)
    zero = ops.run_queue_lock_fused_async(cfg, s, iters=0, sync_every=0)
    assert int(zero.iteration) == 0
    np.testing.assert_array_equal(np.asarray(zero.pos), np.asarray(s.pos))
    a = ops.run_queue_lock_fused_async(cfg, s, iters=4, sync_every=0,
                                       block_n=128)
    b = ops.run_queue_lock_fused_async(cfg, s, iters=4, sync_every=1,
                                       block_n=128)
    np.testing.assert_array_equal(np.asarray(a.pos), np.asarray(b.pos))
    big = ops.run_queue_lock_fused_async(cfg, s, iters=4, sync_every=99,
                                         block_n=128)
    np.testing.assert_array_equal(
        np.asarray(big.pos),
        np.asarray(ops.run_queue_lock_fused_async(cfg, s, iters=4,
                                                  sync_every=4,
                                                  block_n=128).pos))

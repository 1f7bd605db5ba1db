"""The async multi-device island ring (core.distributed) + the exchange
primitives' contracts.

Collective property tests run in-process under ``jax.vmap(axis_name=...)``
(vmap implements pmax/pmin/psum/ppermute over the named axis without
needing devices); the true >= 4-device mesh runs in a subprocess with
``--xla_force_host_platform_device_count`` (the main process deliberately
keeps the real single CPU device — see conftest).
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import agreement
from repro.core import PSOConfig, init_swarm, run, run_async
from repro.core.distributed import (_pmax_best, init_sharded_swarm,
                                    make_distributed_run, ring_exchange)
from repro.kernels.ref import run_islands_ring_oracle

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mesh():
    return jax.make_mesh((1,), ("data",))


def _spmd(fn, *args):
    """Run a collective-using fn over a vmap named axis 's'."""
    return jax.vmap(fn, axis_name="s")(*args)


# --------------------------------------------------------------------------
# _pmax_best properties (ties, ±inf, NaN) — the barrier primitive.
# --------------------------------------------------------------------------

def _pm(fit, pos):
    return _spmd(lambda f, p: _pmax_best(f, p, ("s",)),
                 jnp.asarray(fit, jnp.float32),
                 jnp.asarray(pos, jnp.float32))


def test_pmax_best_matches_dense_argmax_oracle():
    rng = np.random.default_rng(0)
    for _ in range(20):
        n = int(rng.integers(2, 9))
        fit = rng.normal(size=n).astype(np.float32)
        pos = rng.normal(size=(n, 3)).astype(np.float32)
        gf, gp = _pm(fit, pos)
        w = int(np.argmax(fit))                   # dense oracle
        np.testing.assert_array_equal(np.asarray(gf), np.full(n, fit[w]))
        for s in range(n):
            np.testing.assert_array_equal(np.asarray(gp)[s], pos[w])


def test_pmax_best_tie_lowest_index_owns_broadcast():
    fit = [2.0, 5.0, 5.0, 5.0]
    pos = [[0.0], [10.0], [20.0], [30.0]]
    gf, gp = _pm(fit, pos)
    np.testing.assert_array_equal(np.asarray(gf), np.full(4, 5.0))
    # every shard gets shard 1's position — the lowest tied index
    np.testing.assert_array_equal(np.asarray(gp), np.full((4, 1), 10.0))


def test_pmax_best_inf_fits():
    gf, gp = _pm([-np.inf, 1.0, np.inf, np.inf], [[0.], [1.], [2.], [3.]])
    np.testing.assert_array_equal(np.asarray(gf), np.full(4, np.inf))
    np.testing.assert_array_equal(np.asarray(gp), np.full((4, 1), 2.0))
    # an all -inf swarm elects shard 0 deterministically
    gf, gp = _pm([-np.inf] * 4, [[0.], [1.], [2.], [3.]])
    np.testing.assert_array_equal(np.asarray(gf), np.full(4, -np.inf))
    np.testing.assert_array_equal(np.asarray(gp), np.zeros((4, 1)))


def test_pmax_best_nan_guard():
    # NaN never owns the broadcast (treated as -inf)...
    gf, gp = _pm([np.nan, 3.0, np.nan, 1.0], [[9.], [1.], [9.], [3.]])
    np.testing.assert_array_equal(np.asarray(gf), np.full(4, 3.0))
    np.testing.assert_array_equal(np.asarray(gp), np.full((4, 1), 1.0))
    # ...and an all-NaN swarm degrades to -inf + shard 0's pos, never a
    # garbage zero-sum position
    gf, gp = _pm([np.nan] * 4, [[7.], [1.], [2.], [3.]])
    np.testing.assert_array_equal(np.asarray(gf), np.full(4, -np.inf))
    np.testing.assert_array_equal(np.asarray(gp), np.full((4, 1), 7.0))


# --------------------------------------------------------------------------
# ring_exchange properties — the async primitive.
# --------------------------------------------------------------------------

def _hop(f, p, o, n):
    return _spmd(lambda a, b, c: ring_exchange(a, b, c, "s", n), f, p, o)


def test_ring_propagates_one_hop_per_round():
    n = 5
    f = jnp.asarray([9.0, 1.0, 2.0, 3.0, 4.0])
    p = jnp.arange(n, dtype=jnp.float32)[:, None]
    o = jnp.arange(n, dtype=jnp.int32)
    for hop in range(1, n):
        f, p, o = _hop(f, p, o, n)
        know = np.asarray(f) == 9.0
        # after h hops, shards 0..h know the best (one ring step per hop)
        np.testing.assert_array_equal(know, np.arange(n) <= hop)
    # n-1 hops: everyone knows, and owns the winner's pos + owner id
    np.testing.assert_array_equal(np.asarray(p), np.zeros((n, 1)))
    np.testing.assert_array_equal(np.asarray(o), np.zeros(n, np.int32))


def test_ring_tie_break_converges_to_lowest_owner():
    n = 4
    f = jnp.full(n, 5.0)                          # a pure fit tie
    p = jnp.arange(n, dtype=jnp.float32)[:, None]
    o = jnp.asarray([2, 1, 3, 0], jnp.int32)      # distinct originators
    for _ in range(n - 1):
        f, p, o = _hop(f, p, o, n)
    np.testing.assert_array_equal(np.asarray(o), np.zeros(n, np.int32))
    # every shard converged to the lowest-owner candidate's position
    # (owner 0's payload started on shard 3)
    np.testing.assert_array_equal(np.asarray(p), np.full((n, 1), 3.0))


def test_ring_nan_never_propagates():
    n = 4
    f = jnp.asarray([np.nan, 1.0, np.nan, 2.0])
    p = jnp.arange(n, dtype=jnp.float32)[:, None]
    o = jnp.arange(n, dtype=jnp.int32)
    for _ in range(n - 1):
        f, p, o = _hop(f, p, o, n)
    np.testing.assert_array_equal(np.asarray(f), np.full(n, 2.0))
    np.testing.assert_array_equal(np.asarray(p), np.full((n, 1), 3.0))


# --------------------------------------------------------------------------
# One-shard ring == single-chip run_async, under the contract.
# --------------------------------------------------------------------------

@pytest.mark.parametrize("iters,exchange,sync", [(24, 8, 4), (20, 5, 5),
                                                 (23, 8, 4)])
def test_one_shard_ring_bit_identical_to_run_async(iters, exchange, sync):
    """The acceptance identity: with one shard the ring path (shard_map,
    ppermute self-hop, fold, drain) reproduces run_async — including
    remainder-tail iteration counts (23 % 8 != 0). Two compiled programs,
    so under the contract (tests/agreement.py); the integer fields and the
    final-flush invariant exactly."""
    cfg = PSOConfig(dim=4, particle_cnt=128, fitness="rastrigin").resolved()
    mesh = _mesh()
    st = init_sharded_swarm(cfg, 7, mesh)
    runner = make_distributed_run(cfg, mesh, iters=iters, variant="async",
                                  exchange_interval=exchange,
                                  sync_every=sync)
    out = runner(st)
    ref = run_async(cfg, init_swarm(cfg, 7), iters, sync_every=sync,
                    n_blocks=out.lbest_fit.shape[0])
    # lbest differs only when a non-scheduled tail flush ran (the ring pulls
    # the published best back into the blocks, plain run_async does not)
    skip = ("lbest_pos", "lbest_fit") if iters % exchange else ()
    fields = tuple(f for f in out._fields
                   if f not in skip + ("iteration", "seed"))
    agreement.assert_states_agree(out, ref, agreement.box_span(cfg), fields)
    for f in ("iteration", "seed"):
        np.testing.assert_array_equal(np.asarray(getattr(out, f)),
                                      np.asarray(getattr(ref, f)),
                                      err_msg=f)
    assert float(out.gbest_fit) == float(np.max(np.asarray(out.pbest_fit)))


def test_async_ring_rejects_bad_sync_exchange_combo():
    cfg = PSOConfig(dim=2, particle_cnt=64, fitness="cubic").resolved()
    with pytest.raises(ValueError, match="divide"):
        make_distributed_run(cfg, _mesh(), iters=12, variant="async",
                             exchange_interval=6, sync_every=4)


# --------------------------------------------------------------------------
# Remainder-tail rounds (satellite: iters % exchange_interval != 0).
# --------------------------------------------------------------------------

def test_sync_variant_remainder_tail_vs_divisible():
    """iters no longer must divide exchange_interval: on one shard (where
    the exchange collective is semantically a no-op) the non-divisible
    schedule must produce the same trajectory as the divisible one, both
    equal to the plain single-chip run."""
    cfg = PSOConfig(dim=3, particle_cnt=64, fitness="sphere").resolved()
    mesh = _mesh()
    st = init_sharded_swarm(cfg, 1, mesh)
    div = make_distributed_run(cfg, mesh, iters=24, variant="queue",
                               exchange_interval=8)(st)
    ndiv = make_distributed_run(cfg, mesh, iters=24, variant="queue",
                                exchange_interval=7)(st)
    assert int(div.iteration) == int(ndiv.iteration) == 24
    np.testing.assert_allclose(np.asarray(div.pos), np.asarray(ndiv.pos),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(float(div.gbest_fit), float(ndiv.gbest_fit),
                               rtol=1e-4)


# --------------------------------------------------------------------------
# Eager multi-island oracle (kernels/ref.py).
# --------------------------------------------------------------------------

def test_oracle_one_island_reduces_to_run_async():
    cfg = PSOConfig(dim=4, particle_cnt=128, fitness="rastrigin").resolved()
    isl, _ = run_islands_ring_oracle(cfg, 7, 1, 24, 8, sync_every=4)
    ref = run_async(cfg, init_swarm(cfg, 7), 24, sync_every=4,
                    n_blocks=isl[0].lbest_fit.shape[0])
    for f in ref._fields:
        np.testing.assert_array_equal(np.asarray(getattr(isl[0], f)),
                                      np.asarray(getattr(ref, f)),
                                      err_msg=f)


def test_oracle_staleness_and_final_flush_invariants():
    """Four eager islands: (a) any island's round-r best is visible on the
    island d hops downstream by round r+d — so everywhere within n_shards
    rounds; (b) after the drain every island's gbest equals the max over
    ALL pbests (final-flush invariant)."""
    n_shards = 4
    cfg = PSOConfig(dim=3, particle_cnt=256, fitness="rastrigin").resolved()
    isl, hist = run_islands_ring_oracle(cfg, 0, n_shards, 24, 8,
                                        sync_every=4)
    all_pbest = np.concatenate([np.asarray(s.pbest_fit) for s in isl])
    for s in isl:
        assert float(s.gbest_fit) == float(all_pbest.max())
    for r in range(len(hist)):
        for i in range(n_shards):
            v = hist[r][i][0]
            for d in range(1, n_shards):
                if r + d < len(hist):
                    assert hist[r + d][(i + d) % n_shards][0] >= v, (
                        f"round {r} island {i} best lost after {d} hops")


# --------------------------------------------------------------------------
# The real >= 4-device mesh (subprocess: forced virtual CPU devices).
# --------------------------------------------------------------------------

_MESH_SCRIPT = r"""
import json
import jax, numpy as np
from repro.core import PSOConfig
from repro.core.distributed import init_sharded_swarm, make_distributed_run
from repro.kernels.ref import run_islands_ring_oracle

n_shards = 4
cfg = PSOConfig(dim=3, particle_cnt=256, fitness="rastrigin").resolved()
mesh = jax.make_mesh((n_shards,), ("data",))
st = init_sharded_swarm(cfg, 0, mesh)
runner = make_distributed_run(cfg, mesh, iters=24, variant="async",
                              exchange_interval=8, sync_every=4)
out = runner(st)
gf = float(out.gbest_fit)
pb = np.asarray(out.pbest_fit)
shard_vals = [float(np.asarray(s.data))
              for s in out.gbest_fit.addressable_shards]
isl, hist = run_islands_ring_oracle(cfg, 0, n_shards, 24, 8, sync_every=4)
per_island = [bool(np.allclose(pb[i*64:(i+1)*64],
                               np.asarray(isl[i].pbest_fit),
                               rtol=1e-3, atol=1e-3))
              for i in range(n_shards)]
print(json.dumps({
    "devices": len(jax.devices()),
    "gbest": gf,
    "max_pbest": float(pb.max()),
    "replicated": all(v == gf for v in shard_vals),
    "oracle_gbest": float(isl[0].gbest_fit),
    "per_island_pbest_close": per_island,
    "iteration": int(out.iteration),
}))
"""


def test_ring_on_four_device_mesh():
    """End-to-end on a 4-device CPU mesh (subprocess so the forced device
    count cannot leak into the in-process backend): final-flush invariant,
    gbest replication across shards, and agreement with the eager oracle."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", _MESH_SCRIPT], cwd=REPO,
                       env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    got = json.loads(r.stdout.strip().splitlines()[-1])
    assert got["devices"] == 4
    assert got["iteration"] == 24
    assert got["replicated"], got
    assert got["gbest"] == got["max_pbest"], got
    # shard_map vs eager oracle compile differently (1-ulp amplification
    # over chaotic iterations) — compare with tolerance
    assert abs(got["gbest"] - got["oracle_gbest"]) <= 1e-3 * max(
        1.0, abs(got["oracle_gbest"])), got
    assert all(got["per_island_pbest_close"]), got


def test_pso_run_cli_islands_async_four_devices():
    """The previously-forbidden CLI spelling runs end to end on a 4-device
    mesh: `pso_run --islands 4 --variant async`."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.pso_run", "--dim", "3",
         "--particles", "256", "--iters", "30", "--variant", "async",
         "--islands", "4", "--exchange", "10", "--sync-every", "5"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "gbest_fit=" in r.stdout


def test_pso_run_cli_islands_async_single_device():
    """...and on the plain 1-device box (no forced devices)."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.pso_run", "--dim", "2",
         "--particles", "128", "--iters", "20", "--variant", "async",
         "--islands", "1", "--exchange", "5"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "gbest_fit=" in r.stdout


def test_method_facade_islands():
    """Method(islands=...) routes solve() through the distributed runner."""
    import repro
    res = repro.solve("rastrigin", dim=3, particles=128, iters=16, seed=0,
                      method=repro.Method(variant="async", islands=1,
                                          exchange_interval=8,
                                          sync_every=4))
    ref = repro.solve("rastrigin", dim=3, particles=128, iters=16, seed=0,
                      method=repro.Method(variant="async", sync_every=4))
    assert res.gbest_fit == ref.gbest_fit      # 1-island ring == single chip
    assert res.method.islands == 1
    with pytest.raises(ValueError, match="solve_many"):
        repro.solve_many("cubic", seeds=[0, 1],
                         method=repro.Method(islands=2))
    with pytest.raises(ValueError, match="ring local loop"):
        repro.Method(variant="async", backend="kernel", islands=2)
    # sync variants still run the barrier path under the facade
    res_q = repro.solve("rastrigin", dim=3, particles=128, iters=16, seed=0,
                        method=repro.Method(variant="queue", islands=1,
                                            exchange_interval=4))
    assert np.isfinite(res_q.gbest_fit)

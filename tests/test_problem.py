"""Problem API: first-class objectives, the d-major adapter, the unified
solve facade, and the legacy string path's bit-compatibility with the seed.

Layers covered: core/problem.py (registry, bounds, sense), core/pso.py
(PSOConfig widening, per-dimension bounds), kernels/pso_step.py
(dmajor_adapter + const hoisting + hand-tuned fast paths), kernels/ref.py
(oracle parity for custom objectives), repro.api (solve/solve_many/Method/
Result), launch/serve.py (content-hashed compile keys), core/tuner.py and
core/distributed.py (Problems thread through), core/blocking.py (unified
block sizing).
"""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import agreement
import repro
from repro import Method, Problem
from repro.core import PSOConfig, get_problem, init_swarm, solve
from repro.core.blocking import pick_block_n
from repro.core.fitness import DEFAULT_BOUNDS, FITNESS_FNS, FITNESS_IDS
from repro.core.pso import _default_async_blocks
from repro.core.problem import register_problem, resolve_problem
from repro.kernels import ops, ref
from repro.kernels.pso_step import (KERNEL_FITNESS, _fitness_dmajor,
                                    dmajor_adapter, is_converted,
                                    kernel_fitness, pad_dim)


def _digest(state) -> str:
    h = hashlib.sha1()
    for a in (state.pos, state.vel, state.pbest_fit, state.gbest_pos,
              state.gbest_fit):
        h.update(np.ascontiguousarray(np.asarray(a)).tobytes())
    return h.hexdigest()[:16]


def _wbowl():
    w = jnp.asarray([1.0, 4.0, 0.25])
    c = jnp.asarray([1.0, -2.0, 0.5])

    def weighted_bowl(x):
        return jnp.sum(w * (x - c) ** 2, axis=-1)

    return Problem(name="weighted_bowl", fn=weighted_bowl,
                   lo=(-5.0, -10.0, -2.0), hi=(5.0, 10.0, 2.0), sense="min")


# --------------------------------------------------------------------------
# Registry + Problem semantics
# --------------------------------------------------------------------------

def test_builtins_registered():
    assert set(FITNESS_FNS) <= set(repro.list_problems())
    for name, fn in FITNESS_FNS.items():
        p = get_problem(name)
        assert p.fn is fn                      # the SAME function object
        assert p.sense == "max"
        assert (p.lo, p.hi) == DEFAULT_BOUNDS[name]
    # stable kernel-side ids (order = declaration order)
    assert FITNESS_IDS == {n: i for i, n in enumerate(
        ["cubic", "sphere", "rosenbrock", "griewank", "rastrigin", "ackley"])}


def test_register_and_resolve():
    p = register_problem("t_reg_prob", lambda x: -jnp.sum(x * x, axis=-1),
                         lo=-1.0, hi=1.0)
    assert get_problem("t_reg_prob") is p
    assert resolve_problem("t_reg_prob") is p
    assert resolve_problem(p) is p
    register_problem(p)                        # identical re-register: ok
    with pytest.raises(ValueError, match="different content"):
        register_problem("t_reg_prob", lambda x: jnp.sum(x, axis=-1))
    register_problem("t_reg_prob", lambda x: jnp.sum(x, axis=-1),
                     overwrite=True)
    assert get_problem("t_reg_prob") is not p


def test_problem_validation():
    fn = lambda x: jnp.sum(x, axis=-1)
    with pytest.raises(ValueError, match="sense"):
        Problem(name="x", fn=fn, sense="down")
    with pytest.raises(ValueError, match="lo <= hi"):
        Problem(name="x", fn=fn, lo=1.0, hi=-1.0)
    with pytest.raises(ValueError, match="lo <= hi"):
        Problem(name="x", fn=fn, lo=(0.0, 2.0), hi=(1.0, 1.0))
    # lo == hi is legal: the coordinate is frozen (tests/test_bounds.py)
    Problem(name="x", fn=fn, lo=(0.0, 0.5), hi=(1.0, 0.5))
    with pytest.raises(ValueError, match="lengths differ"):
        Problem(name="x", fn=fn, lo=(0.0, 0.0), hi=(1.0, 1.0, 1.0))
    # arrays normalize to tuples (hashable); scalar broadcasts against [D]
    p = Problem(name="x", fn=fn, lo=np.array([-1.0, -2.0]), hi=3)
    assert p.lo == (-1.0, -2.0) and p.hi == (3.0, 3.0)
    assert p.ndim == 2
    hash(p)                                    # jit-static requirement


def test_sense_canonicalization():
    fn = lambda x: jnp.sum(x * x, axis=-1)
    pmin = Problem(name="x", fn=fn, sense="min")
    pmax = Problem(name="x", fn=fn, sense="max")
    x = jnp.asarray([[1.0, 2.0]])
    assert float(pmin.max_fn(x)[0]) == -5.0    # canonical = negated
    assert float(pmax.max_fn(x)[0]) == 5.0
    assert pmax.max_fn is fn                   # max sense: untouched object
    assert pmin.max_fn is pmin.max_fn          # stable wrapper identity
    assert pmin.user_value(-3.0) == 3.0


def test_cache_key_is_content_based():
    f1 = lambda x: jnp.sum(x * x, axis=-1)
    f2 = lambda x: jnp.sum(x * x * x, axis=-1)
    a = Problem(name="same", fn=f1)
    b = Problem(name="same", fn=f2)            # same name, different code
    c = Problem(name="same", fn=f1)
    assert a.cache_key() != b.cache_key()
    assert a.cache_key() == c.cache_key()
    assert a.cache_key() != Problem(name="same", fn=f1, lo=-1.0,
                                    hi=1.0).cache_key()
    # closure values count as content
    def make(k):
        return Problem(name="same", fn=lambda x: k * jnp.sum(x, axis=-1))
    assert make(2.0).cache_key() != make(3.0).cache_key()


# --------------------------------------------------------------------------
# Legacy string path: bit-identical to the seed
# --------------------------------------------------------------------------

# SHA1 digests of (pos, vel, pbest_fit, gbest_pos, gbest_fit) captured from
# the SEED tree (commit 4b5c2fe, pre-Problem-API) on XLA:CPU/f32. The string
# path must keep resolving through the new registry to these exact bits.
SEED_DIGESTS = [
    ("cubic", 2, 64, 50, "queue_lock", "649cc0206e00b1bf"),
    ("cubic", 1, 128, 40, "queue", "53b5412a0a919c50"),
    ("rastrigin", 3, 64, 30, "reduction", "d3f5e2947555481c"),
    ("sphere", 5, 64, 25, "async", "0f2a4ff94b78904d"),
    ("griewank", 4, 64, 20, "queue", "3c02a38e175968c6"),
    ("ackley", 3, 64, 20, "queue_lock", "df71b03492f319b4"),
    ("rosenbrock", 2, 64, 20, "reduction", "7e614c844a9061ef"),
]


@pytest.mark.parametrize("name,dim,n,iters,variant,want", SEED_DIGESTS)
def test_legacy_string_path_bit_identical_to_seed(name, dim, n, iters,
                                                  variant, want):
    s = solve(PSOConfig(dim=dim, particle_cnt=n, fitness=name), seed=3,
              iters=iters, variant=variant)
    assert _digest(s) == want
    # and the Problem-object spelling of the same built-in matches exactly
    s2 = solve(PSOConfig(dim=dim, particle_cnt=n, fitness=get_problem(name)),
               seed=3, iters=iters, variant=variant)
    assert _digest(s2) == want


def test_legacy_kernel_path_bit_identical_to_seed():
    cfg = PSOConfig(dim=2, particle_cnt=128, fitness="cubic").resolved()
    s0 = init_swarm(cfg, 5)
    k = ops.run_queue_lock_fused(cfg, s0, iters=12, block_n=64)
    assert _digest(k) == "e738dfc1df826106"
    a = ops.run_queue_lock_fused_async(cfg, s0, iters=12, sync_every=4,
                                       block_n=64)
    assert _digest(a) == "919036ad04111333"


def test_resolved_bounds_match_seed_defaults():
    for name, (lo, hi) in DEFAULT_BOUNDS.items():
        cfg = PSOConfig(fitness=name).resolved()
        assert cfg.min_pos == lo and cfg.max_pos == hi
        assert cfg.max_v == 0.5 * (hi - lo)
        assert cfg.fitness_fn is FITNESS_FNS[name]


# --------------------------------------------------------------------------
# Unified block sizing (ROADMAP satellite)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1, 7, 33, 96, 100, 128, 384, 640, 1009, 1024,
                               1042, 131072])
def test_default_async_blocks_shares_pick_block_n(n):
    # the jnp fallback = lane-free pick: largest divisor <= target
    nb = _default_async_blocks(n)
    assert nb == n // pick_block_n(n, lane=1)
    assert n % nb == 0
    # seed semantics: the block SIZE is the largest divisor <= 512
    bn = n // nb
    assert all(n % d for d in range(bn + 1, min(n, 512) + 1))


def test_pick_block_n_lane_preference_still_wins():
    assert pick_block_n(640) == 128            # lane-aligned beats larger 320
    assert pick_block_n(640, lane=1) == 320    # lane-free: largest divisor


# --------------------------------------------------------------------------
# d-major adapter: parity with the hand-tuned forms
# --------------------------------------------------------------------------

@pytest.mark.parametrize("fitness", list(KERNEL_FITNESS))
@pytest.mark.parametrize("d,n", [(1, 128), (3, 64), (7, 96), (13, 128),
                                 (120, 64)])
def test_adapter_parity_with_hand_tuned(fitness, d, n):
    """dmajor_adapter(library fn) must agree with _fitness_dmajor on the
    same masked tile, across odd/prime dims and particle counts."""
    rng = np.random.default_rng(d * 1000 + n)
    pos = rng.uniform(-5, 5, size=(n, d)).astype(np.float32)
    packed = ops.pack_dmajor(jnp.asarray(pos), d)
    dmask = jnp.asarray((np.arange(pad_dim(d)) < d)[:, None]
                        & np.ones((1, n), bool))
    hand = np.asarray(_fitness_dmajor(fitness, packed, dmask, d))[0]
    lifted = dmajor_adapter(FITNESS_FNS[fitness])
    got = np.asarray(lifted(packed, dmask, d))[0]
    np.testing.assert_allclose(got, hand, rtol=2e-5, atol=2e-4)


def test_kernel_fitness_routing():
    # strings and built-in Problems take the hand-tuned fast path
    assert not is_converted("cubic")
    assert not is_converted(get_problem("cubic"))
    # custom Problems are adapter-lowered
    assert is_converted(_wbowl())
    # a user kernel_fn is used verbatim
    marker = lambda pos, dmask, d: -jnp.sum(pos, axis=0, keepdims=True)
    p = Problem(name="k", fn=lambda x: -jnp.sum(x, axis=-1), kernel_fn=marker)
    assert kernel_fitness(p) is marker
    assert is_converted(p)
    with pytest.raises(TypeError):
        kernel_fitness(123)


# --------------------------------------------------------------------------
# Custom objective end-to-end: jnp fallback + Pallas kernels vs oracle
# --------------------------------------------------------------------------

def _oracle_inputs(cfg, seed):
    s0 = init_swarm(cfg, seed)
    scal, pos, vel, pbp, pbf, gp, gf = ops.state_to_kernel(s0, cfg.dim)
    kw = ops._cfg_kwargs(cfg)
    kw["d_real"] = cfg.dim
    fitness = kw.pop("fitness")
    return s0, (pos, vel, pbp, pbf, gp, float(gf[0])), fitness, kw


def test_custom_fused_kernel_vs_oracle():
    prob = _wbowl()
    cfg = PSOConfig(dim=3, particle_cnt=64, fitness=prob).resolved()
    s0, (pos, vel, pbp, pbf, gp, gf), fitness, kw = _oracle_inputs(cfg, 1)
    out = ops.run_queue_lock_fused(cfg, s0, iters=8, block_n=32)
    o = ref.run_fused_oracle(int(s0.seed), 0, pos, vel, pbp, pbf, gp, gf,
                             8, 32, fitness=fitness, **kw)
    np.testing.assert_allclose(np.asarray(ops.pack_dmajor(out.pos, 3)),
                               np.asarray(o[0]), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out.pbest_fit),
                               np.asarray(o[3])[0], rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(float(out.gbest_fit), float(o[5]), rtol=1e-6)


@pytest.mark.parametrize("iters,sync_every,block_n", [(8, 4, 32), (10, 4, 32),
                                                      (7, 7, 64)])
def test_custom_async_kernel_vs_oracle(iters, sync_every, block_n):
    prob = _wbowl()
    cfg = PSOConfig(dim=3, particle_cnt=64, fitness=prob).resolved()
    s0, (pos, vel, pbp, pbf, gp, gf), fitness, kw = _oracle_inputs(cfg, 1)
    out = ops.run_queue_lock_fused_async(cfg, s0, iters=iters,
                                         sync_every=sync_every,
                                         block_n=block_n)
    o = ref.run_fused_async_oracle(int(s0.seed), 0, pos, vel, pbp, pbf, gp,
                                   gf, iters, block_n, sync_every,
                                   fitness=fitness, **kw)
    np.testing.assert_allclose(np.asarray(ops.pack_dmajor(out.pos, 3)),
                               np.asarray(o[0]), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(out.gbest_fit), float(o[5]), rtol=1e-6)


def test_custom_async_single_block_equals_fused_bitwise():
    """Kernel-to-kernel invariant (exact): with one block the async kernel
    IS the fused kernel, for custom objectives too."""
    prob = _wbowl()
    cfg = PSOConfig(dim=3, particle_cnt=64, fitness=prob).resolved()
    s0 = init_swarm(cfg, 1)
    f = ops.run_queue_lock_fused(cfg, s0, iters=8, block_n=64)
    for se in (1, 2, 4, 8):
        a = ops.run_queue_lock_fused_async(cfg, s0, iters=8, sync_every=se,
                                           block_n=64)
        assert np.array_equal(np.asarray(f.pos), np.asarray(a.pos))
        assert float(f.gbest_fit) == float(a.gbest_fit)


def test_custom_problem_solves_and_respects_bounds():
    prob = _wbowl()
    res = repro.solve(prob, particles=256, iters=300, seed=0, variant="queue")
    assert res.config.dim == 3                 # dim pinned by [D] bounds
    assert res.best_fit < 0.5                  # near the optimum f=0
    lo = np.array([-5.0, -10.0, -2.0])
    hi = np.array([5.0, 10.0, 2.0])
    pos = np.asarray(res.state.pos)
    assert np.all(pos >= lo - 1e-5) and np.all(pos <= hi + 1e-5)
    # per-dimension velocity clamp: |v_i| <= 0.5 * (hi_i - lo_i)
    vel = np.abs(np.asarray(res.state.vel))
    assert np.all(vel <= 0.5 * (hi - lo) * (1 + 1e-6))
    # user sense: reported value is the minimized objective
    assert res.best_fit == -res.gbest_fit


def test_custom_problem_jnp_vs_kernel_agree():
    prob = _wbowl()
    kw = dict(particles=64, iters=64, seed=2)
    rj = repro.solve(prob, variant="queue_lock", backend="jnp", **kw)
    rk = repro.solve(prob, method=Method(variant="queue_lock",
                                         backend="kernel"), **kw)
    ra = repro.solve(prob, method=Method(variant="async", backend="kernel",
                                         sync_every=8), **kw)
    # independent implementations on the same landscape: same neighborhood
    assert abs(rj.best_fit - rk.best_fit) < 0.5
    assert abs(rk.best_fit - ra.best_fit) < 0.5
    for r in (rj, rk, ra):
        assert np.isfinite(r.best_fit)


# --------------------------------------------------------------------------
# Facade
# --------------------------------------------------------------------------

def test_method_validation(monkeypatch):
    with pytest.raises(ValueError, match="unknown variant"):
        Method(variant="warp")
    with pytest.raises(ValueError, match="unknown backend"):
        Method(backend="gpu")
    with pytest.raises(ValueError, match="kernel"):
        Method(variant="queue", backend="kernel")
    sphere = get_problem("sphere")
    assert Method(variant="queue").resolve_backend(sphere) == "jnp"
    assert Method(variant="queue_lock",
                  backend="kernel").resolve_backend(sphere) == "kernel"
    # interpret mode is the kernels' own decision, from the platform alone
    from repro.kernels.pso_step import default_interpret
    assert default_interpret() is (jax.default_backend() != "tpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert default_interpret() is False
    assert Method(variant="async").resolve_backend(sphere) == "kernel"


def test_solve_rejects_method_plus_loose_kwargs():
    with pytest.raises(ValueError, match="not both"):
        repro.solve("cubic", particles=64, iters=5,
                    method=Method(variant="queue"), variant="queue_lock")


def test_facade_matches_core_solve():
    cfg = PSOConfig(dim=2, particle_cnt=64, fitness="cubic")
    want = solve(cfg, seed=7, iters=40, variant="queue_lock")
    got = repro.solve("cubic", dim=2, particles=64, iters=40, seed=7,
                      variant="queue_lock")
    assert _digest(got.state) == _digest(want)


def test_facade_solve_many_row_identity():
    """A batched row against its standalone solve: two programs, so under
    the contract (tests/agreement.py)."""
    rs = repro.solve_many("cubic", [0, 1, 2, 3], dim=2, particles=64,
                          iters=30, variant="queue")
    r1 = repro.solve("cubic", dim=2, particles=64, iters=30, seed=2,
                     variant="queue")
    agreement.assert_states_agree(rs[2].state, r1.state,
                                  agreement.box_span(r1.config))
    assert repro.best(rs).gbest_fit == max(r.gbest_fit for r in rs)


def test_facade_solve_many_kernel_backend():
    prob = _wbowl()
    rs = repro.solve_many(prob, [0, 1], particles=64, iters=16,
                          method=Method(variant="queue_lock",
                                        backend="kernel"))
    r1 = repro.solve(prob, particles=64, iters=16, seed=1,
                     method=Method(variant="queue_lock", backend="kernel"))
    # batched vs standalone kernel programs may round 1-2 ulp apart on
    # XLA:CPU for adapter-lowered objectives (same fusion-context class as
    # the S=4 caveat in core/multi_swarm.py); exact for built-ins is
    # asserted in tests/test_multi_swarm.py.
    np.testing.assert_allclose(np.asarray(rs[1].state.pos),
                               np.asarray(r1.state.pos),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(rs[1].gbest_fit, r1.gbest_fit, rtol=1e-5)


# --------------------------------------------------------------------------
# Serving: content-hashed compile keys
# --------------------------------------------------------------------------

def test_serve_distinct_custom_objectives_never_share_a_batch():
    from repro.launch.serve import SolveRequest

    f1 = lambda x: -jnp.sum(x * x, axis=-1)
    f2 = lambda x: -jnp.sum(x * x * x * x, axis=-1)
    a = SolveRequest(dim=2, particle_cnt=64,
                     fitness=Problem(name="mine", fn=f1))
    b = SolveRequest(dim=2, particle_cnt=64,
                     fitness=Problem(name="mine", fn=f2))
    assert a.batch_key != b.batch_key
    # a built-in by name and by Problem object DO share one
    c = SolveRequest(dim=2, particle_cnt=64, fitness="cubic")
    d = SolveRequest(dim=2, particle_cnt=64, fitness=get_problem("cubic"))
    assert c.batch_key == d.batch_key


def test_serve_solves_custom_problems():
    from repro.launch.serve import SolveRequest, SolveServer

    prob = _wbowl()
    srv = SolveServer(backend="jnp")
    reqs = [SolveRequest(dim=3, particle_cnt=64, fitness=prob, seed=i,
                         iters=50, variant="queue") for i in range(9)]
    out = srv.solve_all(reqs)
    assert len(out) == 9
    assert srv.stats.dispatches == 1           # one compile group
    for r in out:
        assert np.isfinite(r.gbest_fit)
        assert r.objective == -r.gbest_fit     # sense="min" reporting


# --------------------------------------------------------------------------
# Tuner + distributed + serial: Problems thread through
# --------------------------------------------------------------------------

def test_tuner_with_custom_problem():
    from repro.core.tuner import (PSO_COEFF_DIMS, PSOTuner,
                                  make_solve_many_fitness)

    cfg = PSOConfig(dim=3, particle_cnt=32, fitness=_wbowl())
    bf = make_solve_many_fitness(cfg, seeds=[0, 1], iters=15)
    tuner = PSOTuner(PSO_COEFF_DIMS, particles=3, seed=0)
    res = tuner.run(batch_fitness=bf, iters=2)
    assert np.isfinite(res.best_fitness)
    assert set(res.best_params) == {"w", "c1", "c2"}


def test_distributed_custom_problem():
    from repro.core.distributed import (init_sharded_swarm,
                                        make_distributed_run)

    mesh = jax.make_mesh((1,), ("data",))
    cfg = PSOConfig(dim=3, particle_cnt=64, fitness=_wbowl())
    state = init_sharded_swarm(cfg, 0, mesh)
    runner = make_distributed_run(cfg, mesh, iters=20, variant="queue",
                                  exchange_interval=5)
    out = runner(state)
    assert float(out.gbest_fit) >= float(state.gbest_fit)
    assert np.isfinite(float(out.gbest_fit))


def test_serial_baseline_custom_problem():
    from repro.core.serial import run_serial_fast

    cfg = PSOConfig(dim=3, particle_cnt=32, fitness=_wbowl())
    gf, gp = run_serial_fast(cfg.resolved(), seed=0, iters=30)
    assert np.isfinite(gf)
    assert gp.shape == (3,)
    assert np.all(gp >= np.array([-5.0, -10.0, -2.0]) - 1e-5)
    assert np.all(gp <= np.array([5.0, 10.0, 2.0]) + 1e-5)

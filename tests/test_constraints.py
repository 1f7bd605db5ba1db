"""Constrained-optimization subsystem (repro.core.constraints).

Layers covered: core/constraints.py (Constraint/ConstraintSet, violation,
projection, repair, CLI spec parser), core/problem.py (constraints field,
penalized max_fn, cache_key content), core/pso.py (projection hook, repair
init, run_with_history), core/serial.py (constrained mirror),
kernels/pso_step.py + kernels/ref.py (projection/penalty lowering, the new
constrained oracle), repro.api (Result.feasible/violation/history, Deb
best(), penalty ramp), launch/serve.py (constraint-aware batch keys +
feasibility reporting), core/tuner.py (constrained batch fitness), and the
pso_run CLI (--constraint presets).
"""
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import agreement
import repro
from repro import Constraint, ConstraintSet, Method, Problem
from repro.core import PSOConfig, init_swarm, run, run_async, solve
from repro.core.constraints import (constrain_problem, constraint_from_spec,
                                    constraint_set_from_cli, project_simplex,
                                    simplex_constraints)
from repro.core.problem import get_problem
from repro.core.pso import run_with_history
from repro.kernels import ops, ref
from repro.kernels.pso_step import is_converted, kernel_projection

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _ball_repair(tries=8):
    return Problem(
        name="ball_repair", fn=lambda x: -jnp.sum(x * x, -1),
        lo=-2.0, hi=2.0,
        constraints=ConstraintSet(
            constraints=(Constraint(fn=lambda x: jnp.sum(x * x, -1) - 4.0,
                                    name="ball"),),
            mode="repair", repair_tries=tries))


def _plane_ball(tries=64):
    """Repair-mode problem whose UNCONSTRAINED optimum is infeasible:
    maximize sum(x) in [-2, 2]^D subject to ||x||^2 <= 1.5^2. The box
    corner beats every feasible point, so a raw ``fit > pbest`` fold
    drives pbests out of the feasible set — the Deb-rule litmus."""
    return Problem(
        name="plane_ball", fn=lambda x: jnp.sum(x, -1), lo=-2.0, hi=2.0,
        constraints=ConstraintSet(
            constraints=(Constraint(fn=lambda x: jnp.sum(x * x, -1) - 2.25,
                                    name="ball"),),
            mode="repair", repair_tries=tries))


# --------------------------------------------------------------------------
# Constraint / ConstraintSet semantics
# --------------------------------------------------------------------------

def test_constraint_violation_forms():
    ineq = Constraint(fn=lambda x: jnp.sum(x, -1) - 1.0)
    x = jnp.asarray([[0.3, 0.3], [0.9, 0.9]])
    np.testing.assert_allclose(np.asarray(ineq.violation(x)),
                               [0.0, 0.8], atol=1e-6)
    eq = Constraint(fn=lambda x: jnp.sum(x, -1) - 1.0, kind="eq", tol=0.1)
    np.testing.assert_allclose(np.asarray(eq.violation(x)),
                               [0.3, 0.7], atol=1e-6)
    # aggregate sums contributions; empty set is identically feasible
    cs = ConstraintSet(constraints=(ineq, eq), mode="penalty")
    np.testing.assert_allclose(np.asarray(cs.violation(x)),
                               [0.3, 1.5], atol=1e-6)


def test_constraint_validation():
    fn = lambda x: jnp.sum(x, -1)
    with pytest.raises(ValueError, match="kind"):
        Constraint(fn=fn, kind="leq")
    with pytest.raises(ValueError, match="mode"):
        ConstraintSet(constraints=(Constraint(fn=fn),), mode="clip")
    with pytest.raises(ValueError, match="projection"):
        ConstraintSet(constraints=(Constraint(fn=fn),), mode="projection")
    with pytest.raises(ValueError, match="projection"):
        ConstraintSet(constraints=(Constraint(fn=fn),), mode="penalty",
                      projection=lambda x: x)
    with pytest.raises(ValueError, match="at least one"):
        ConstraintSet(constraints=(), mode="penalty")
    # projection mode with no declared constraints is fine (reporting-only)
    cs = ConstraintSet(mode="projection", projection=project_simplex)
    assert float(cs.violation(jnp.asarray([5.0, 5.0]))) == 0.0
    # hashable (jit-static requirement), like Problem
    hash(cs)
    hash(Problem(name="c", fn=fn, constraints=ConstraintSet(
        constraints=(Constraint(fn=fn),))))


def test_problem_constraint_validation():
    fn = lambda x: -jnp.sum(x * x, -1)
    with pytest.raises(TypeError, match="ConstraintSet"):
        Problem(name="x", fn=fn, constraints="simplex")
    with pytest.raises(ValueError, match="mutually exclusive"):
        Problem(name="x", fn=fn,
                kernel_fn=lambda p, m, d: -jnp.sum(p, 0, keepdims=True),
                constraints=ConstraintSet(
                    constraints=(Constraint(fn=fn),)))


def test_cache_key_covers_constraints():
    fn = lambda x: -jnp.sum(x * x, -1)
    g = lambda x: jnp.sum(x, -1) - 1.0
    base = Problem(name="p", fn=fn)
    pen = Problem(name="p", fn=fn, constraints=ConstraintSet(
        constraints=(Constraint(fn=g),), mode="penalty", weight=10.0))
    pen2 = Problem(name="p", fn=fn, constraints=ConstraintSet(
        constraints=(Constraint(fn=g),), mode="penalty", weight=20.0))
    rep = Problem(name="p", fn=fn, constraints=ConstraintSet(
        constraints=(Constraint(fn=g),), mode="repair"))
    keys = {base.cache_key(), pen.cache_key(), pen2.cache_key(),
            rep.cache_key()}
    assert len(keys) == 4                      # mode and weight are content
    # identical reconstruction shares the key (serving batches together)
    pen_again = Problem(name="p", fn=fn, constraints=ConstraintSet(
        constraints=(Constraint(fn=g),), mode="penalty", weight=10.0))
    assert pen_again.cache_key() == pen.cache_key()


def test_penalized_max_fn():
    p = get_problem("sphere_simplex_pen")
    x = jnp.asarray([0.25, 0.25, 0.25, 0.25])     # feasible: penalty-free
    assert float(p.max_fn(x)) == pytest.approx(-0.25, rel=1e-6)
    y = jnp.asarray([0.5, 0.5, 0.5, 0.5])         # sum=2: viol ~ 1 - tol
    w = p.constraints.weight
    assert float(p.max_fn(y)) == pytest.approx(-1.0 - w * (1.0 - 1e-5),
                                               rel=1e-5)
    assert p.max_fn is p.max_fn                   # stable wrapper identity
    # the unconstrained fast path is untouched (object identity)
    sphere = get_problem("sphere")
    assert sphere.max_fn is sphere.fn


def test_project_simplex_known_points():
    got = project_simplex(jnp.asarray([[0.25, 0.25, 0.5],   # already on it
                                       [1.0, 1.0, 1.0],     # uniform
                                       [10.0, 0.0, 0.0]]))  # vertex
    want = np.asarray([[0.25, 0.25, 0.5],
                       [1 / 3, 1 / 3, 1 / 3],
                       [1.0, 0.0, 0.0]])
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-6)
    # random points project onto the simplex (nonneg, sum 1)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.uniform(-3, 3, size=(64, 7)).astype(np.float32))
    px = np.asarray(project_simplex(x))
    assert px.min() >= 0.0
    np.testing.assert_allclose(px.sum(-1), 1.0, atol=1e-5)


# --------------------------------------------------------------------------
# jnp engines: projection/penalty/repair through init + every variant
# --------------------------------------------------------------------------

@pytest.mark.parametrize("variant", ["reduction", "queue", "queue_lock",
                                     "async"])
def test_projection_mode_stays_feasible_and_converges(variant):
    cfg = PSOConfig(dim=6, particle_cnt=128,
                    fitness=get_problem("sphere_simplex"), w=0.7)
    s = solve(cfg.resolved(), seed=0, iters=150, variant=variant)
    pos = np.asarray(s.pos)
    assert pos.min() >= 0.0                       # never leaves the simplex
    np.testing.assert_allclose(pos.sum(-1), 1.0, atol=1e-5)
    # optimum is 1/D (canonical max: -1/D)
    assert float(s.gbest_fit) == pytest.approx(-1.0 / 6.0, abs=1e-4)


def test_penalty_mode_converges_near_feasible():
    cfg = PSOConfig(dim=6, particle_cnt=256,
                    fitness=get_problem("sphere_simplex_pen"), w=0.7)
    s = solve(cfg.resolved(), seed=0, iters=200, variant="queue_lock")
    p = get_problem("sphere_simplex_pen")
    assert p.violation_at(s.gbest_pos) < 1e-2     # near-feasible
    assert float(p.user_value(s.gbest_fit)) < 0.5  # well below random (~1)


def test_repair_mode_feasible_init():
    p = _ball_repair()
    cfg = PSOConfig(dim=3, particle_cnt=256, fitness=p).resolved()
    s0 = init_swarm(cfg, 0)
    frac = float((np.asarray(p.violation_fn(s0.pos)) <= 0).mean())
    assert frac > 0.95                            # vs ~0.52 unrepaired
    cfg_u = PSOConfig(dim=3, particle_cnt=256, fitness="sphere",
                      min_pos=-2.0, max_pos=2.0).resolved()
    frac_u = float((np.asarray(p.violation_fn(init_swarm(cfg_u, 0).pos))
                    <= 0).mean())
    assert frac_u < 0.7
    # velocities and the RNG chain are untouched by the resampling
    assert np.array_equal(np.asarray(s0.vel), np.asarray(init_swarm(
        cfg_u, 0).vel))


def test_serial_mirror_matches_constrained_init_and_runs():
    from repro.core.serial import SerialSwarm, run_serial_fast
    for prob in (get_problem("sphere_simplex"),
                 get_problem("sphere_simplex_pen"), _ball_repair()):
        cfg = PSOConfig(dim=4, particle_cnt=64, fitness=prob).resolved()
        ser = SerialSwarm(cfg, seed=0)
        jnp_init = init_swarm(cfg, 0)
        assert np.array_equal(ser.pos, np.asarray(jnp_init.pos))
        gf, gp = run_serial_fast(cfg, 0, 20)
        assert np.isfinite(gf)
        if prob.projection_fn is not None:
            assert prob.violation_at(gp) <= 1e-5
    # string spelling of a registered constrained problem works too
    cfg = PSOConfig(dim=4, particle_cnt=32, fitness="sphere_simplex")
    gf, _ = run_serial_fast(cfg.resolved(), 0, 10)
    assert np.isfinite(gf)


# --------------------------------------------------------------------------
# The eager oracle: the jnp engine agrees under the contract
# (tests/agreement.py), dispatched per iteration and as one loop program
# --------------------------------------------------------------------------

@pytest.mark.parametrize("prob_name", ["sphere_simplex",
                                       "sphere_simplex_pen", "repair"])
def test_jnp_queue_lock_bit_exact_vs_constrained_oracle(prob_name):
    """The jnp engine, dispatched per iteration and as one loop program,
    agrees with the independent eager oracle on every field."""
    prob = _ball_repair() if prob_name == "repair" else get_problem(prob_name)
    cfg = PSOConfig(dim=5, particle_cnt=64, fitness=prob).resolved()
    span = agreement.box_span(cfg)
    o = ref.run_constrained_oracle(cfg, 3, 12, variant="queue_lock")
    s = init_swarm(cfg, 3)
    for _ in range(12):
        s = run(cfg, s, 1, "queue_lock")
    agreement.assert_states_agree(s, o, span, msg="per iteration")
    sf = solve(cfg, seed=3, iters=12, variant="queue_lock")
    agreement.assert_states_agree(sf, o, span, msg="loop program")


@pytest.mark.parametrize("prob_name,sync_every,n_blocks",
                         [("sphere_simplex", 4, 4),
                          ("sphere_simplex_pen", 4, 2),
                          ("sphere_simplex_pen", 3, 4),
                          ("repair", 4, 2)])
def test_jnp_async_bit_exact_vs_constrained_oracle(prob_name, sync_every,
                                                   n_blocks):
    prob = _ball_repair() if prob_name == "repair" else get_problem(prob_name)
    cfg = PSOConfig(dim=5, particle_cnt=64, fitness=prob).resolved()
    iters = 14
    o = ref.run_constrained_oracle(cfg, 3, iters, variant="async",
                                   sync_every=sync_every, n_blocks=n_blocks)
    s = init_swarm(cfg, 3)
    span = agreement.box_span(cfg)
    fields = agreement.STATE_FIELDS + ("lbest_pos", "lbest_fit")
    for _ in range(iters):      # per-iteration windows, phase auto-aligned
        s = run_async(cfg, s, 1, sync_every=sync_every, n_blocks=n_blocks)
    agreement.assert_states_agree(s, o, span, fields, msg="per iteration")
    sf = run_async(cfg, init_swarm(cfg, 3), iters, sync_every=sync_every,
                   n_blocks=n_blocks)
    agreement.assert_states_agree(sf, o, span, fields, msg="loop program")


def test_deb_improved_predicate():
    """The shared Deb mask: feasible beats infeasible regardless of fitness,
    two feasible compare on fitness, two infeasible on violation; strict
    comparisons keep the incumbent on ties."""
    from repro.core.constraints import deb_improved
    fit_n = jnp.asarray([5.0, 5.0, 1.0, 1.0, 5.0, 1.0])
    viol_n = jnp.asarray([0.0, 2.0, 0.0, 1.0, 3.0, 1.0])
    fit_o = jnp.asarray([1.0, 1.0, 5.0, 5.0, 1.0, 0.0])
    viol_o = jnp.asarray([1.0, 0.0, 0.0, 2.0, 2.0, 1.0])
    np.testing.assert_array_equal(
        np.asarray(deb_improved(fit_n, viol_n, fit_o, viol_o)),
        [True, False, False, True, False, False])
    # unconstrained degeneration: all-zero violations == raw fitness fold
    z = jnp.zeros_like(fit_n)
    np.testing.assert_array_equal(
        np.asarray(deb_improved(fit_n, z, fit_o, z)),
        np.asarray(fit_n > fit_o))


@pytest.mark.parametrize("variant", ["reduction", "queue", "queue_lock",
                                     "async"])
def test_deb_pbest_selection_keeps_feasible_pbests(variant):
    """Engine-level Deb rule (every jnp variant): on a repair-mode problem
    whose unconstrained optimum is infeasible, the raw fold would drive
    pbests out of the feasible set; with Deb selection no infeasible
    candidate ever displaces a feasible pbest, so the (feasible-at-init)
    pbest population stays feasible through the run."""
    p = _plane_ball()
    cfg = PSOConfig(dim=3, particle_cnt=64, fitness=p, w=0.7).resolved()
    vf = p.violation_fn
    s0 = init_swarm(cfg, 0)
    assert float(np.asarray(vf(s0.pbest_pos)).max()) <= 0.0   # feasible init
    s = solve(cfg, seed=0, iters=40, variant=variant)
    assert float(np.asarray(vf(s.pbest_pos)).max()) <= 0.0
    # ...and the rule actually bit: the final population holds infeasible
    # candidates whose raw fitness beats their (feasible) pbest — exactly
    # the swaps the old fold would have taken
    blocked = ((np.asarray(vf(s.pos)) > 0)
               & (np.asarray(s.fit) > np.asarray(s.pbest_fit)))
    assert blocked.any()


# --------------------------------------------------------------------------
# Pallas kernels: constrained problems through fused/async, vs the oracles
# --------------------------------------------------------------------------

def _oracle_inputs(cfg, seed):
    s0 = init_swarm(cfg, seed)
    scal, pos, vel, pbp, pbf, gp, gf = ops.state_to_kernel(s0, cfg.dim)
    kw = ops._cfg_kwargs(cfg)
    kw["d_real"] = cfg.dim
    fitness = kw.pop("fitness")
    return s0, (pos, vel, pbp, pbf, gp, float(gf[0])), fitness, kw


def test_constrained_problems_lower_by_conversion():
    assert is_converted(get_problem("sphere_simplex"))
    assert is_converted(get_problem("sphere_simplex_pen"))
    assert kernel_projection(get_problem("sphere_simplex")) is not None
    assert kernel_projection(get_problem("sphere_simplex_pen")) is None
    assert kernel_projection("sphere") is None
    # built-ins stay on the hand-tuned fast path
    assert not is_converted(get_problem("sphere"))


def test_registered_constrained_name_resolves_on_kernel_path():
    """A registered non-builtin STRING fitness must resolve through the
    registry on the kernel path (regression: it used to hit the
    hand-tuned ``_fitness_dmajor`` and raise NotImplementedError — and
    ``kernel_projection`` silently dropped the projection)."""
    assert kernel_projection("sphere_simplex") is not None
    assert is_converted("sphere_simplex_pen")
    cfg_s = PSOConfig(dim=4, particle_cnt=64,
                      fitness="sphere_simplex").resolved()
    cfg_p = PSOConfig(dim=4, particle_cnt=64,
                      fitness=get_problem("sphere_simplex")).resolved()
    a = ops.run_queue_lock_fused(cfg_s, init_swarm(cfg_s, 0), iters=6,
                                 block_n=32)
    b = ops.run_queue_lock_fused(cfg_p, init_swarm(cfg_p, 0), iters=6,
                                 block_n=32)
    assert np.array_equal(np.asarray(a.pos), np.asarray(b.pos))
    pos = np.asarray(a.pos)
    assert pos.min() >= 0.0                    # projection actually applied
    np.testing.assert_allclose(pos.sum(-1), 1.0, atol=1e-5)


@pytest.mark.parametrize("prob_name", ["sphere_simplex",
                                       "sphere_simplex_pen"])
def test_constrained_fused_kernel_single_block_bit_exact_vs_oracle(
        prob_name):
    prob = get_problem(prob_name)
    cfg = PSOConfig(dim=5, particle_cnt=64, fitness=prob).resolved()
    s0, (pos, vel, pbp, pbf, gp, gf), fitness, kw = _oracle_inputs(cfg, 1)
    out = ops.run_queue_lock_fused(cfg, s0, iters=8, block_n=64)
    o = ref.run_fused_oracle(int(s0.seed), 0, pos, vel, pbp, pbf, gp, gf,
                             8, 64, fitness=fitness, **kw)
    # the interpret program and the eager oracle: the contract
    span = agreement.box_span(cfg)
    agreement.assert_swarm_close(ops.pack_dmajor(out.pos, 5), o[0], span,
                                 "pos")
    agreement.assert_swarm_close(ops.pack_dmajor(out.vel, 5), o[1], span,
                                 "vel")
    agreement.assert_fit_close(out.pbest_fit, np.asarray(o[3])[0],
                               "pbest_fit")
    agreement.assert_fit_close(out.gbest_fit, o[5], "gbest_fit")


@pytest.mark.parametrize("prob_name", ["sphere_simplex",
                                       "sphere_simplex_pen"])
def test_constrained_fused_kernel_multi_block_vs_oracle(prob_name):
    """Multi-block: same validation class as adapter-lowered customs —
    exact gbest trajectory value, ulp-tight positions (XLA:CPU
    fusion-context rounding; see ROADMAP kernel-batch caveat)."""
    prob = get_problem(prob_name)
    cfg = PSOConfig(dim=5, particle_cnt=64, fitness=prob).resolved()
    s0, (pos, vel, pbp, pbf, gp, gf), fitness, kw = _oracle_inputs(cfg, 1)
    out = ops.run_queue_lock_fused(cfg, s0, iters=8, block_n=32)
    o = ref.run_fused_oracle(int(s0.seed), 0, pos, vel, pbp, pbf, gp, gf,
                             8, 32, fitness=fitness, **kw)
    np.testing.assert_allclose(np.asarray(ops.pack_dmajor(out.pos, 5)),
                               np.asarray(o[0]), rtol=1e-5, atol=1e-6)
    assert float(out.gbest_fit) == pytest.approx(float(o[5]), rel=1e-6)


@pytest.mark.parametrize("prob_name,iters,sync_every,block_n",
                         [("sphere_simplex", 8, 4, 32),
                          ("sphere_simplex", 10, 4, 32),
                          ("sphere_simplex_pen", 8, 4, 32),
                          ("sphere_simplex_pen", 7, 7, 64)])
def test_constrained_async_kernel_vs_oracle(prob_name, iters, sync_every,
                                            block_n):
    prob = get_problem(prob_name)
    cfg = PSOConfig(dim=5, particle_cnt=64, fitness=prob).resolved()
    s0, (pos, vel, pbp, pbf, gp, gf), fitness, kw = _oracle_inputs(cfg, 1)
    out = ops.run_queue_lock_fused_async(cfg, s0, iters=iters,
                                         sync_every=sync_every,
                                         block_n=block_n)
    o = ref.run_fused_async_oracle(int(s0.seed), 0, pos, vel, pbp, pbf,
                                   gp, gf, iters, block_n, sync_every,
                                   fitness=fitness, **kw)
    # the interpret program and the eager oracle: the contract
    agreement.assert_swarm_close(ops.pack_dmajor(out.pos, 5), o[0],
                                 agreement.box_span(cfg), "pos")
    agreement.assert_fit_close(out.gbest_fit, o[5], "gbest_fit")


def test_constrained_async_single_block_equals_fused_bitwise():
    """Kernel-to-kernel invariant (exact float equality): one block ⇒ the
    async kernel IS the fused kernel — for constrained problems too."""
    for prob_name in ("sphere_simplex", "sphere_simplex_pen"):
        prob = get_problem(prob_name)
        cfg = PSOConfig(dim=5, particle_cnt=64, fitness=prob).resolved()
        s0 = init_swarm(cfg, 1)
        f = ops.run_queue_lock_fused(cfg, s0, iters=8, block_n=64)
        for se in (1, 2, 4, 8):
            a = ops.run_queue_lock_fused_async(cfg, s0, iters=8,
                                               sync_every=se, block_n=64)
            assert np.array_equal(np.asarray(f.pos), np.asarray(a.pos))
            assert float(f.gbest_fit) == float(a.gbest_fit)


def test_constrained_kernel_projection_output_feasible():
    prob = get_problem("sphere_simplex")
    cfg = PSOConfig(dim=5, particle_cnt=64, fitness=prob).resolved()
    s0 = init_swarm(cfg, 0)
    out = ops.run_queue_lock_fused(cfg, s0, iters=12, block_n=32)
    pos = np.asarray(out.pos)
    assert pos.min() >= 0.0
    np.testing.assert_allclose(pos.sum(-1), 1.0, atol=1e-5)


def test_constrained_kernel_repair_deb_pbest():
    """Kernel-level Deb rule: repair-mode ``_plane_ball`` through the fused
    kernel agrees with the (Deb-ized) d-major oracle under the contract
    (tests/agreement.py), and the pbest
    population stays feasible — the raw fold would have let the infeasible
    box corner displace feasible pbests. Gbest feasibility is NOT asserted:
    the kernel publishes from the current fitness (documented seam)."""
    p = _plane_ball()
    cfg = PSOConfig(dim=3, particle_cnt=64, fitness=p, w=0.7).resolved()
    s0, (pos, vel, pbp, pbf, gp, gf), fitness, kw = _oracle_inputs(cfg, 0)
    out = ops.run_queue_lock_fused(cfg, s0, iters=8, block_n=64)
    o = ref.run_fused_oracle(int(s0.seed), 0, pos, vel, pbp, pbf, gp, gf,
                             8, 64, fitness=fitness, **kw)
    agreement.assert_swarm_close(ops.pack_dmajor(out.pos, 3), o[0],
                                 agreement.box_span(cfg), "pos")
    agreement.assert_fit_close(out.gbest_fit, o[5], "gbest_fit")
    assert float(np.asarray(p.violation_fn(out.pbest_pos)).max()) <= 0.0


def test_constrained_batched_kernel_row_matches_standalone():
    from repro.core.multi_swarm import init_batch, batch_row
    prob = get_problem("sphere_simplex_pen")
    cfg = PSOConfig(dim=4, particle_cnt=64, fitness=prob).resolved()
    batch = init_batch(cfg, np.asarray([0, 1, 2], np.int64))
    out = ops.run_queue_lock_fused_batch(cfg, batch, iters=6, block_n=32)
    lone = ops.run_queue_lock_fused(cfg, init_swarm(cfg, 1), iters=6,
                                    block_n=32)
    # adapter-lowered rows are ulp-tight vs standalone on XLA:CPU (same
    # class as test_facade_solve_many_kernel_backend)
    np.testing.assert_allclose(np.asarray(batch_row(out, 1).pos),
                               np.asarray(lone.pos), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(float(out.gbest_fit[1]),
                               float(lone.gbest_fit), rtol=1e-5)


# --------------------------------------------------------------------------
# Facade: feasibility reporting, Deb best(), history, ramp
# --------------------------------------------------------------------------

def test_result_feasibility_fields():
    res = repro.solve("sphere_simplex", dim=4, particles=128, iters=100,
                      seed=0, w=0.7, variant="queue_lock")
    assert res.feasible and res.violation == 0.0
    assert res.best_fit == pytest.approx(0.25, abs=1e-3)
    # unconstrained results are trivially feasible
    r2 = repro.solve("cubic", dim=1, particles=64, iters=20, seed=0)
    assert r2.feasible and r2.violation == 0.0 and r2.first_feasible_iter == 0


def test_deb_rule_best():
    feas_good = repro.solve("sphere_simplex", dim=4, particles=128,
                            iters=100, seed=0, w=0.7)
    feas_bad = repro.solve("sphere_simplex", dim=4, particles=8, iters=2,
                           seed=1, w=0.7)
    pen = repro.solve("sphere_simplex_pen", dim=4, particles=32, iters=3,
                      seed=2, w=0.7)
    assert feas_good.feasible and feas_bad.feasible
    # among feasible: fitness decides (regardless of infeasible entries)
    assert repro.best([feas_bad, pen, feas_good]) is feas_good
    if not pen.feasible:
        # all-infeasible: min violation decides
        pen2 = repro.solve("sphere_simplex_pen", dim=4, particles=256,
                           iters=150, seed=0, w=0.7)
        picked = repro.best([pen, pen2])
        assert picked.violation == min(pen.violation, pen2.violation)


def test_record_history_and_first_feasible():
    res = repro.solve("sphere_simplex", dim=4, particles=128, iters=60,
                      seed=0, w=0.7, variant="queue_lock",
                      record_history=True)
    h = res.history
    assert len(h) == 60
    assert np.array_equal(h.iteration, np.arange(1, 61))
    assert np.all(np.diff(h.gbest_fit) >= 0)          # gbest monotone
    assert float(h.gbest_fit[-1]) == res.gbest_fit
    assert res.first_feasible_iter == 1               # projected from init
    # async: one record per sync point + the tail
    ra = repro.solve("sphere_simplex_pen", dim=4, particles=64, iters=30,
                     seed=0, w=0.7, variant="async", sync_every=8,
                     record_history=True)
    assert list(ra.history.iteration) == [8, 16, 24, 30]
    assert ra.history.violation is not None
    # unconstrained history has no violation track
    ru = repro.solve("cubic", dim=1, particles=64, iters=10, seed=0,
                     variant="queue", record_history=True)
    assert ru.history.violation is None and len(ru.history) == 10


def test_record_history_identical_final_state():
    """History mode must not change the answer (async segmentation is the
    checkpoint-exact split; the scan records without re-steering)."""
    kw = dict(dim=4, particles=64, iters=40, seed=0, w=0.7)
    plain = repro.solve("sphere_simplex", variant="async", sync_every=8,
                        **kw)
    hist = repro.solve("sphere_simplex", variant="async", sync_every=8,
                       record_history=True, **kw)
    assert np.array_equal(np.asarray(plain.state.pos),
                          np.asarray(hist.state.pos))
    assert plain.gbest_fit == hist.gbest_fit


def test_record_history_validation():
    # the kernel backend records history now (chunked launches with a
    # gbest readback per sync point) — constructing the Method is legal
    Method(variant="queue_lock", backend="kernel", record_history=True)
    # islands stay genuinely unsupported: precise error
    with pytest.raises(ValueError, match="single-device"):
        Method(variant="queue", islands=1, record_history=True)
    # the batch engine surfaces per-row histories now
    rs = repro.solve_many("cubic", [0, 1], dim=1, particles=64, iters=5,
                          method=Method(record_history=True))
    assert all(r.history is not None and len(r.history) == 5 for r in rs)


def test_penalty_ramp_segments_and_improves_feasibility():
    cset = ConstraintSet(
        constraints=simplex_constraints(), mode="penalty",
        weight=1.0, ramp=4.0, ramp_every=50)
    ramped = Problem(name="simplex_ramp", fn=lambda x: jnp.sum(x * x, -1),
                     lo=0.0, hi=1.0, sense="min", constraints=cset)
    static = get_problem("sphere_simplex_pen")
    kw = dict(dim=6, particles=128, iters=200, seed=0, w=0.7,
              variant="queue_lock")
    r_ramp = repro.solve(ramped, record_history=True, **kw)
    r_stat = repro.solve(static, **kw)
    assert len(r_ramp.history) == 200            # segments concatenate
    assert r_ramp.violation <= r_stat.violation + 1e-6
    assert r_ramp.violation < 1e-3
    # ramp also rides solve_many (segmented batch engine)
    rs = repro.solve_many(ramped, [0, 1], dim=6, particles=64, iters=100,
                          w=0.7, variant="queue_lock")
    assert len(rs) == 2 and all(np.isfinite(r.best_fit) for r in rs)


def test_penalty_ramp_composes_with_islands():
    """The ramp now rides islands: one ``make_distributed_run`` per
    segment, carried fitness re-weighted at the boundaries. Ground truth
    is the manual per-segment composition — bit-identical."""
    import dataclasses
    import jax
    from repro.api import _reweight_state
    from repro.core.distributed import (init_sharded_swarm,
                                        make_distributed_run)
    cset = ConstraintSet(
        constraints=simplex_constraints(), mode="penalty",
        weight=1.0, ramp=4.0, ramp_every=50)
    ramped = Problem(name="simplex_ramp_i", fn=lambda x: jnp.sum(x * x, -1),
                     lo=0.0, hi=1.0, sense="min", constraints=cset)
    m = Method(variant="queue", islands=1)
    r = repro.solve(ramped, dim=6, particles=64, iters=100, w=0.7, method=m)
    assert np.isfinite(r.best_fit)
    mesh = jax.make_mesh((1,), ("data",))
    st = init_sharded_swarm(r.config, 0, mesh)
    for k, wgt in enumerate([1.0, 4.0]):
        cfg_k = dataclasses.replace(
            r.config, fitness=ramped.with_penalty_weight(wgt))
        if k:
            st = _reweight_state(cfg_k, st)
        st = make_distributed_run(cfg_k, mesh, iters=50, variant="queue",
                                  exchange_interval=m.exchange_interval)(st)
    assert float(st.gbest_fit) == float(r.state.gbest_fit)
    np.testing.assert_array_equal(np.asarray(st.pos), np.asarray(r.state.pos))
    np.testing.assert_array_equal(np.asarray(st.gbest_pos),
                                  np.asarray(r.state.gbest_pos))
    # the async ring re-seeds its block locals at each segment boundary
    # (the reweight drops them) — the composition must run end to end
    ra = repro.solve(ramped, dim=6, particles=64, iters=100, w=0.7,
                     method=Method(variant="async", islands=1))
    assert np.isfinite(ra.best_fit) and ra.state.lbest_fit is not None


def test_solve_many_feasibility_roundtrip():
    rs = repro.solve_many("sphere_simplex", [0, 1, 2], dim=4, particles=64,
                          iters=80, w=0.7, variant="queue")
    for r in rs:
        assert r.feasible and r.violation == 0.0
    lone = repro.solve("sphere_simplex", dim=4, particles=64, iters=80,
                       seed=1, w=0.7, variant="queue")
    assert rs[1].gbest_fit == pytest.approx(lone.gbest_fit, rel=1e-6)


# --------------------------------------------------------------------------
# Serving + tuner + CLI
# --------------------------------------------------------------------------

def test_serve_constraint_aware_batch_keys_and_results():
    from repro.launch.serve import SolveRequest, SolveServer

    p = get_problem("sphere_simplex_pen")
    a = SolveRequest(dim=4, particle_cnt=64, fitness=p)
    b = SolveRequest(dim=4, particle_cnt=64,
                     fitness=p.with_penalty_weight(99.0))
    assert a.batch_key != b.batch_key            # weight is content
    c = SolveRequest(dim=4, particle_cnt=64, fitness="sphere_simplex_pen")
    assert a.batch_key == c.batch_key            # name == object spelling
    srv = SolveServer(backend="jnp")
    out = srv.solve_all([SolveRequest(dim=4, particle_cnt=64, fitness=p,
                                      seed=i, iters=40, variant="queue")
                         for i in range(5)])
    assert srv.stats.dispatches == 1             # one compile group
    for r in out:
        assert isinstance(r.feasible, bool)
        assert r.violation >= 0.0
        assert r.objective == -r.gbest_fit       # sense="min" reporting


def test_tuner_with_constrained_problem():
    from repro.core.tuner import (PSO_COEFF_DIMS, PSOTuner,
                                  make_solve_many_fitness)

    cfg = PSOConfig(dim=4, particle_cnt=32,
                    fitness=get_problem("sphere_simplex"))
    bf = make_solve_many_fitness(cfg, seeds=[0, 1], iters=10)
    tuner = PSOTuner(PSO_COEFF_DIMS, particles=3, seed=0)
    res = tuner.run(batch_fitness=bf, iters=2)
    assert np.isfinite(res.best_fitness)
    assert res.best_fitness <= 0.0               # canonical max of -||x||^2


def test_cli_constraint_parsing_helpers():
    c = constraint_from_spec("norm(x)<=2.5")
    assert c.kind == "ineq"
    assert float(c.violation(jnp.asarray([3.0, 4.0]))) == pytest.approx(2.5)
    c2 = constraint_from_spec("min(x)>=0")
    assert float(c2.violation(jnp.asarray([-0.5, 1.0]))) == pytest.approx(0.5)
    c3 = constraint_from_spec("sum(x)==1")
    assert c3.kind == "eq"
    with pytest.raises(ValueError, match="cannot parse"):
        constraint_from_spec("x[0]<=1")
    with pytest.raises(ValueError, match="simplex"):
        constraint_set_from_cli(["sum(x)<=1"], mode="projection")
    cs = constraint_set_from_cli(["simplex"], mode="projection")
    assert cs.projection is project_simplex
    p = constrain_problem("sphere", cs)
    assert p.constrained and p.name == "sphere_constrained"


def test_pso_run_cli_constrained():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.pso_run", "--dim", "3",
         "--particles", "64", "--iters", "30", "--fitness", "sphere",
         "--constraint", "simplex", "--constraint-mode", "projection"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "feasible=True" in r.stdout
    assert "violation=" in r.stdout
    # registered constrained built-in by name
    r2 = subprocess.run(
        [sys.executable, "-m", "repro.launch.pso_run", "--dim", "3",
         "--particles", "64", "--iters", "20", "--fitness",
         "sphere_simplex_pen"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert r2.returncode == 0, r2.stderr[-2000:]
    assert "violation=" in r2.stdout


def test_distributed_constrained_problem():
    import jax
    from repro.core.distributed import (init_sharded_swarm,
                                        make_distributed_run)

    mesh = jax.make_mesh((1,), ("data",))
    cfg = PSOConfig(dim=4, particle_cnt=64,
                    fitness=get_problem("sphere_simplex"), w=0.7)
    state = init_sharded_swarm(cfg.resolved(), 0, mesh)
    runner = make_distributed_run(cfg.resolved(), mesh, iters=30,
                                  variant="queue", exchange_interval=5)
    out = runner(state)
    pos = np.asarray(out.pos)
    assert pos.min() >= 0.0                       # projection held on-shard
    np.testing.assert_allclose(pos.sum(-1), 1.0, atol=1e-5)


def test_history_run_with_history_matches_plain_run_async():
    """Core-level: async history segmentation is the checkpoint-exact
    split (bit-identical final state to the uninterrupted run)."""
    cfg = PSOConfig(dim=5, particle_cnt=64,
                    fitness=get_problem("sphere_simplex_pen")).resolved()
    s0 = init_swarm(cfg, 0)
    plain = run_async(cfg, s0, 22, sync_every=4)
    st, (its, fits, viols) = run_with_history(cfg, s0, 22, "async",
                                              sync_every=4)
    assert np.array_equal(np.asarray(plain.pos), np.asarray(st.pos))
    assert float(plain.gbest_fit) == float(st.gbest_fit)
    assert its == (4, 8, 12, 16, 20, 22)
    assert float(fits[-1]) == float(st.gbest_fit)


def test_projection_problems_resolve_to_jnp():
    """A projection-mode constraint set sorts inside the advance, which
    Mosaic cannot lower: such problems resolve to jnp whatever the
    platform, and an explicit kernel request fails with a clear error
    before any lowering."""
    prob = get_problem("sphere_simplex")
    assert Method(variant="async").resolve_backend(prob) == "jnp"
    assert Method(variant="async").resolve_backend(
        get_problem("sphere_simplex_pen")) in ("jnp", "kernel")
    with pytest.raises(ValueError, match="projection-mode"):
        Method(variant="queue_lock", backend="kernel").resolve_backend(prob)
    with pytest.raises(ValueError, match="projection-mode"):
        repro.solve(prob, dim=4, particles=64, iters=2,
                    method=Method(variant="async", telemetry=True))
    res = repro.solve(prob, dim=4, particles=64, iters=4, variant="async")
    assert res.feasible


def test_projection_problems_refused_by_kernel_front_ends(monkeypatch):
    """The flush server and the PSO CLI ask ``Method.resolve_backend``
    before a kernel dispatch, so a projection-mode problem on the kernel
    backend fails with the api's error, not inside lowering."""
    from repro.launch import pso_run
    from repro.launch.serve import SolveRequest, SolveServer
    prob = get_problem("sphere_simplex")
    [res] = SolveServer(backend="kernel").solve_all(
        [SolveRequest(dim=4, particle_cnt=64, fitness=prob, seed=0,
                      iters=2, variant="queue_lock")])
    assert not res.ok and "projection-mode" in str(res.error)
    monkeypatch.setattr(pso_run, "use_persistent_cache", lambda: None)
    monkeypatch.setattr(sys, "argv", [
        "pso_run", "--dim", "4", "--particles", "64", "--iters", "2",
        "--variant", "queue_lock", "--kernel", "--constraint", "simplex",
        "--constraint-mode", "projection"])
    with pytest.raises(SystemExit) as e:
        pso_run.main()
    assert e.value.code == 2

"""Update-rule layer: every (rule, variant) pair vs its ref.py eager
oracle, and the default rule against the same rule spelled out (one
program, bit for bit)."""
import hashlib

import jax.numpy as jnp
import numpy as np
import pytest

import agreement
import repro
from repro import Method
from repro.core import PSOConfig, init_swarm
from repro.core.pso import run, run_async
from repro.core.update_rules import (TOPOLOGIES, UPDATE_RULES, PSORule,
                                     UpdateRule, resolve_rule, rule_names)
from repro.kernels import ops, ref

RULES = tuple(sorted(UPDATE_RULES))


def _digest(state) -> str:
    h = hashlib.sha1()
    for a in (state.pos, state.vel, state.pbest_fit, state.gbest_pos,
              state.gbest_fit):
        h.update(np.ascontiguousarray(np.asarray(a)).tobytes())
    return h.hexdigest()[:16]


def _oracle_inputs(cfg, seed):
    s0 = init_swarm(cfg, seed)
    scal, pos, vel, pbp, pbf, gp, gf = ops.state_to_kernel(s0, cfg.dim)
    kw = ops._cfg_kwargs(cfg)          # carries rule=cfg.update_rule
    kw["d_real"] = cfg.dim
    fitness = kw.pop("fitness")
    return s0, (pos, vel, pbp, pbf, gp, float(gf[0])), fitness, kw


# --------------------------------------------------------------------------
# Registry semantics
# --------------------------------------------------------------------------

def test_registry_and_resolve():
    assert rule_names() == RULES
    assert {"pso", "sso", "lowcost"} <= set(RULES)
    for name in RULES:
        r = UPDATE_RULES[name]
        assert resolve_rule(name) is r
        assert resolve_rule(r) is r            # instances pass through
        # all shipped rules draw both streams: swapping the rule changes
        # no RNG bookkeeping anywhere in the stack
        assert r.rng_draws == 2
        assert r.kernel_eligible
    with pytest.raises(ValueError) as ei:
        resolve_rule("warp_speed")
    # the error enumerates every valid name
    assert all(n in str(ei.value) for n in RULES)


def test_rule_advance_semantics():
    """Hand-checkable elementwise semantics on a 1x4 tile."""
    r1 = jnp.asarray([[0.1, 0.5, 0.8, 0.95]])
    r2 = jnp.asarray([[0.25, 0.25, 0.75, 0.25]])
    pos = jnp.asarray([[1.0, 1.0, 1.0, 1.0]])
    vel = jnp.asarray([[0.5, 0.5, 0.5, 0.5]])
    pbp = jnp.asarray([[2.0, 2.0, 2.0, 2.0]])
    gp = jnp.asarray([[3.0, 3.0, 3.0, 3.0]])
    kw = dict(w=0.5, c1=1.0, c2=1.0, mv=10.0, lo=-10.0, hi=10.0)
    # sso: thresholds 0.4 / 0.7 / 0.9 -> gbest, pbest, keep, resample
    p, v = UPDATE_RULES["sso"].advance(r1, r2, pos, vel, pbp, gp, **kw)
    np.testing.assert_allclose(np.asarray(p)[0],
                               [3.0, 2.0, 1.0, -10.0 + 20.0 * 0.25])
    np.testing.assert_array_equal(np.asarray(v), np.asarray(vel))  # untouched
    # lowcost: Bernoulli(1/2) selection of the difference terms
    p, v = UPDATE_RULES["lowcost"].advance(r1, r2, pos, vel, pbp, gp, **kw)
    np.testing.assert_allclose(np.asarray(v)[0],
                               [0.5 + 1.0 + 2.0,   # both selected
                                0.5 + 0.0 + 2.0,   # r1 >= .5? no: r1=.5 -> off
                                0.5 + 0.0 + 0.0,   # both off
                                0.5 + 0.0 + 2.0])
    np.testing.assert_allclose(np.asarray(p), np.asarray(pos + v))
    # pso: the canonical chain
    p, v = UPDATE_RULES["pso"].advance(r1, r2, pos, vel, pbp, gp, **kw)
    want_v = 0.5 * 0.5 + np.asarray(r1)[0] * 1.0 + np.asarray(r2)[0] * 2.0
    np.testing.assert_allclose(np.asarray(v)[0], want_v, rtol=1e-6)


# --------------------------------------------------------------------------
# Kernels vs eager oracles, per rule
# --------------------------------------------------------------------------

@pytest.mark.parametrize("rule", RULES)
def test_queue_kernel_vs_oracle_per_rule(rule):
    cfg = PSOConfig(dim=3, particle_cnt=64, fitness="rastrigin",
                    update_rule=rule).resolved()
    s0, (pos, vel, pbp, pbf, gp, gf), fitness, kw = _oracle_inputs(cfg, 11)
    out = ops.queue_step(cfg, s0, block_n=32)
    o = ref.queue_step_oracle(int(s0.seed), 0, pos, vel, pbp, pbf, gp, gf,
                              32, fitness=fitness, **kw)
    np.testing.assert_allclose(np.asarray(ops.pack_dmajor(out.pos, 3)),
                               np.asarray(o[0]), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(out.gbest_fit), float(o[5]), rtol=1e-6)


@pytest.mark.parametrize("rule", RULES)
@pytest.mark.parametrize("block_n", [64, 32])
def test_fused_kernel_vs_oracle_per_rule(rule, block_n):
    """Single- and multi-block, ulp-tight (compiled-vs-eager FMA
    contraction is the repo's documented caveat; the bit-exact surface is
    kernel-vs-kernel, below)."""
    cfg = PSOConfig(dim=3, particle_cnt=64, fitness="sphere",
                    update_rule=rule).resolved()
    s0, (pos, vel, pbp, pbf, gp, gf), fitness, kw = _oracle_inputs(cfg, 1)
    out = ops.run_queue_lock_fused(cfg, s0, iters=8, block_n=block_n)
    o = ref.run_fused_oracle(int(s0.seed), 0, pos, vel, pbp, pbf, gp, gf,
                             8, block_n, fitness=fitness, **kw)
    got = np.asarray(ops.pack_dmajor(out.pos, 3))
    np.testing.assert_allclose(got, np.asarray(o[0]), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(out.gbest_fit), float(o[5]), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(out.pbest_fit),
                               np.asarray(o[3])[0], rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("rule", RULES)
@pytest.mark.parametrize("block_n", [64, 32])
def test_async_kernel_vs_oracle_per_rule(rule, block_n):
    cfg = PSOConfig(dim=3, particle_cnt=64, fitness="sphere",
                    update_rule=rule).resolved()
    s0, (pos, vel, pbp, pbf, gp, gf), fitness, kw = _oracle_inputs(cfg, 1)
    out = ops.run_queue_lock_fused_async(cfg, s0, iters=8, sync_every=4,
                                         block_n=block_n)
    o = ref.run_fused_async_oracle(int(s0.seed), 0, pos, vel, pbp, pbf, gp,
                                   gf, 8, block_n, 4,
                                   fitness=fitness, **kw)
    got = np.asarray(ops.pack_dmajor(out.pos, 3))
    np.testing.assert_allclose(got, np.asarray(o[0]), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(out.gbest_fit), float(o[5]), rtol=1e-5)


@pytest.mark.parametrize("rule", RULES)
def test_async_single_block_equals_fused_per_rule(rule):
    """With one block the async kernel IS the fused kernel — the scaffold
    invariant survives every rule, not just the default."""
    cfg = PSOConfig(dim=2, particle_cnt=64, fitness="cubic",
                    update_rule=rule).resolved()
    s0 = init_swarm(cfg, 5)
    f = ops.run_queue_lock_fused(cfg, s0, iters=8, block_n=64)
    a = ops.run_queue_lock_fused_async(cfg, s0, iters=8, sync_every=2,
                                       block_n=64)
    assert np.array_equal(np.asarray(f.pos), np.asarray(a.pos))
    assert float(f.gbest_fit) == float(a.gbest_fit)


# --------------------------------------------------------------------------
# jnp engine vs the constrained-run oracle, per rule
# --------------------------------------------------------------------------

@pytest.mark.parametrize("rule", RULES)
@pytest.mark.parametrize("variant", ["queue_lock", "async"])
def test_jnp_engine_vs_oracle_per_rule(rule, variant):
    """Per-iteration dispatch agrees with the eager oracle for every rule,
    under the contract (tests/agreement.py): two programs, an ulp or two
    apart."""
    cfg = PSOConfig(dim=5, particle_cnt=64, fitness="sphere",
                    update_rule=rule).resolved()
    iters = 10
    o = ref.run_constrained_oracle(cfg, 3, iters, variant=variant,
                                   sync_every=4, n_blocks=4)
    s = init_swarm(cfg, 3)
    for _ in range(iters):
        if variant == "async":
            s = run_async(cfg, s, 1, sync_every=4, n_blocks=4)
        else:
            s = run(cfg, s, 1, "queue_lock")
    agreement.assert_states_agree(
        s, o, agreement.box_span(cfg),
        fields=("pos", "vel", "pbest_pos", "pbest_fit", "gbest_fit"))


# --------------------------------------------------------------------------
# The default rule: the same program as the rule spelled out, bit for bit,
# and the eager oracle's trajectory under the contract (same params as
# tests/test_problem.py's kernel case)
# --------------------------------------------------------------------------

def test_scaffold_default_rule_digests_unchanged():
    cfg = PSOConfig(dim=2, particle_cnt=128, fitness="cubic").resolved()
    assert isinstance(resolve_rule(cfg.update_rule), PSORule)
    s0, (pos, vel, pbp, pbf, gp, gf), fitness, kw = _oracle_inputs(cfg, 5)
    span = agreement.box_span(cfg)
    k = ops.run_queue_lock_fused(cfg, s0, iters=12, block_n=64)
    o = ref.run_fused_oracle(int(s0.seed), 0, pos, vel, pbp, pbf, gp, gf,
                             12, 64, fitness=fitness, **kw)
    agreement.assert_swarm_close(ops.pack_dmajor(k.pos, 2), o[0], span,
                                 "fused pos")
    agreement.assert_fit_close(k.gbest_fit, o[5], "fused gbest_fit")
    a = ops.run_queue_lock_fused_async(cfg, s0, iters=12, sync_every=4,
                                       block_n=64)
    oa = ref.run_fused_async_oracle(int(s0.seed), 0, pos, vel, pbp, pbf,
                                    gp, gf, 12, 64, 4, fitness=fitness,
                                    **kw)
    agreement.assert_swarm_close(ops.pack_dmajor(a.pos, 2), oa[0], span,
                                 "async pos")
    agreement.assert_fit_close(a.gbest_fit, oa[5], "async gbest_fit")
    # spelling the default rule explicitly traces the same program
    cfg2 = PSOConfig(dim=2, particle_cnt=128, fitness="cubic",
                     update_rule="pso").resolved()
    k2 = ops.run_queue_lock_fused(cfg2, init_swarm(cfg2, 5), iters=12,
                                  block_n=64)
    assert _digest(k2) == _digest(k)
    a2 = ops.run_queue_lock_fused_async(cfg2, init_swarm(cfg2, 5), iters=12,
                                        sync_every=4, block_n=64)
    assert _digest(a2) == _digest(a)


# --------------------------------------------------------------------------
# Method facade + config plumbing
# --------------------------------------------------------------------------

def test_config_validates_rule_and_topology():
    with pytest.raises(ValueError, match="unknown update rule"):
        PSOConfig(dim=2, particle_cnt=64, fitness="cubic",
                  update_rule="warp_speed")
    with pytest.raises(ValueError, match="topology"):
        PSOConfig(dim=2, particle_cnt=64, fitness="cubic",
                  topology="hypercube")
    # resolved() preserves both fields
    cfg = PSOConfig(dim=2, particle_cnt=64, fitness="cubic",
                    update_rule="sso", topology="ring").resolved()
    assert cfg.update_rule == "sso" and cfg.topology == "ring"


def test_method_validates_rule_and_topology():
    with pytest.raises(ValueError) as ei:
        Method(rule="warp_speed")
    assert all(n in str(ei.value) for n in RULES)
    with pytest.raises(ValueError, match="async"):
        Method(variant="queue", topology="ring")
    for t in TOPOLOGIES:
        Method(variant="async", topology=t)     # all valid on async
    # a non-kernel-eligible custom rule is rejected on the kernel backend
    class HostRule(UpdateRule):
        pass
    host = HostRule("hostonly", kernel_eligible=False)
    UPDATE_RULES["hostonly"] = host
    try:
        Method(variant="queue_lock", backend="jnp", rule="hostonly")
        with pytest.raises(ValueError, match="kernel"):
            Method(variant="queue_lock", backend="kernel", rule="hostonly")
    finally:
        del UPDATE_RULES["hostonly"]


@pytest.mark.parametrize("rule", ["sso", "lowcost"])
@pytest.mark.parametrize("backend,variant", [("jnp", "queue_lock"),
                                             ("jnp", "async"),
                                             ("kernel", "queue_lock"),
                                             ("kernel", "async")])
def test_solve_end_to_end_per_rule(rule, backend, variant):
    """The non-default rules run end-to-end through the facade on both
    backends, improve on the init and respect the box."""
    res = repro.solve("sphere", dim=3, particles=128, iters=60, seed=0,
                      method=Method(variant=variant, backend=backend,
                                    rule=rule))
    s0 = init_swarm(res.config, 0)
    assert float(res.state.gbest_fit) >= float(s0.gbest_fit)
    pos = np.asarray(res.state.pos)
    assert np.all(pos >= res.config.min_pos - 1e-5)
    assert np.all(pos <= res.config.max_pos + 1e-5)
    assert not np.any(np.isnan(pos))

"""Per-dimension bounds: degenerate and mixed-sign boxes.

The ``[Dpad, 1]`` bound columns (kernels) and ``[D]`` bound arrays (jnp
engine) must handle the edges the Problem API now allows: ``lo == hi`` on
some dimensions (the coordinate is frozen: zero span at init, zero
velocity budget — ``max_v = 0.5 * (hi - lo) = 0`` — so the clip chain pins
it forever) and boxes that do not straddle zero (all-negative,
all-positive, mixed per dimension) through init, advance, the serial
mirror and the Pallas kernels.
"""
import jax.numpy as jnp
import numpy as np
import pytest

import agreement
import repro
from repro.core import PSOConfig, init_swarm, run, solve
from repro.core.problem import Problem
from repro.core.serial import run_serial_fast
from repro.kernels import ops

FROZEN_LO = (0.5, -2.0, 1.0)     # dims 0 and 2 frozen (lo == hi)
FROZEN_HI = (0.5, 2.0, 1.0)


def _frozen_cfg(n=64, fitness="sphere"):
    return PSOConfig(dim=3, particle_cnt=n, fitness=fitness,
                     min_pos=FROZEN_LO, max_pos=FROZEN_HI).resolved()


def test_frozen_dims_init():
    cfg = _frozen_cfg()
    s0 = init_swarm(cfg, 0)
    pos, vel = np.asarray(s0.pos), np.asarray(s0.vel)
    assert np.all(pos[:, 0] == 0.5) and np.all(pos[:, 2] == 1.0)
    assert np.all(vel[:, 0] == 0.0) and np.all(vel[:, 2] == 0.0)
    assert pos[:, 1].min() >= -2.0 and pos[:, 1].max() <= 2.0
    assert np.all(np.isfinite(np.asarray(s0.fit)))


@pytest.mark.parametrize("variant", ["reduction", "queue", "queue_lock",
                                     "async"])
def test_frozen_dims_stay_frozen_through_advance(variant):
    cfg = _frozen_cfg()
    s = solve(cfg, seed=1, iters=40, variant=variant)
    pos, vel = np.asarray(s.pos), np.asarray(s.vel)
    assert np.all(pos[:, 0] == 0.5) and np.all(pos[:, 2] == 1.0)
    assert np.all(vel[:, 0] == 0.0) and np.all(vel[:, 2] == 0.0)
    # the free dim still optimizes: sphere's best is at x_1 = 0, so the
    # optimum of the frozen problem is -(0.25 + 0 + 1)
    assert float(s.gbest_fit) == pytest.approx(-1.25, abs=1e-3)


def test_frozen_dims_through_kernels():
    cfg = _frozen_cfg()
    s0 = init_swarm(cfg, 0)
    for out in (ops.run_queue_lock_fused(cfg, s0, iters=10, block_n=32),
                ops.run_queue_lock_fused_async(cfg, s0, iters=10,
                                               sync_every=4, block_n=32)):
        pos, vel = np.asarray(out.pos), np.asarray(out.vel)
        assert np.all(pos[:, 0] == 0.5) and np.all(pos[:, 2] == 1.0)
        assert np.all(vel[:, 0] == 0.0) and np.all(vel[:, 2] == 0.0)
        assert float(out.gbest_fit) >= float(s0.gbest_fit)


def test_frozen_dims_kernel_matches_jnp_init_exactly():
    """The frozen columns are bound consts: the kernel and library inits
    must agree on them bit-for-bit (both compute lo + 0 * u)."""
    cfg = _frozen_cfg()
    s0 = init_swarm(cfg, 3)
    out = ops.queue_step(cfg, s0, block_n=32)
    pos = np.asarray(out.pos)
    assert np.all(pos[:, 0] == 0.5) and np.all(pos[:, 2] == 1.0)


def test_frozen_dims_serial_mirror():
    cfg = _frozen_cfg(n=32)
    gf, gp = run_serial_fast(cfg, 0, 20)
    assert gp[0] == 0.5 and gp[2] == 1.0
    assert np.isfinite(gf)


@pytest.mark.parametrize("lo,hi", [
    ((-5.0, -3.0), (-1.0, -0.5)),     # all-negative box
    ((2.0, 0.25), (6.0, 8.0)),        # all-positive box
    ((-4.0, 1.0), (-1.0, 3.0)),       # mixed-sign per dimension
])
def test_mixed_sign_bounds_respected(lo, hi):
    prob = Problem(name="box", fn=lambda x: -jnp.sum(x * x, -1),
                   lo=lo, hi=hi)
    cfg = PSOConfig(dim=2, particle_cnt=64, fitness=prob).resolved()
    lo_a, hi_a = np.asarray(lo), np.asarray(hi)
    for variant in ("queue", "async"):
        s = solve(cfg, seed=0, iters=30, variant=variant)
        pos = np.asarray(s.pos)
        assert np.all(pos >= lo_a - 1e-6) and np.all(pos <= hi_a + 1e-6)
        vel = np.abs(np.asarray(s.vel))
        assert np.all(vel <= 0.5 * (hi_a - lo_a) * (1 + 1e-6))
    k = ops.run_queue_lock_fused(cfg, init_swarm(cfg, 0), iters=10,
                                 block_n=32)
    pos = np.asarray(k.pos)
    assert np.all(pos >= lo_a - 1e-6) and np.all(pos <= hi_a + 1e-6)
    # the clamped optimum is the box corner closest to the origin
    want = -np.sum(np.where(lo_a > 0, lo_a, np.where(hi_a < 0, hi_a, 0.0))
                   ** 2)
    s = solve(cfg, seed=0, iters=200, variant="queue")
    assert float(s.gbest_fit) == pytest.approx(want, abs=1e-2)


def test_frozen_dims_batched_engine_row_identity():
    cfg = _frozen_cfg()
    rs = repro.solve_many(cfg.fitness, [0, 1], dim=3, particles=64,
                          iters=20, min_pos=FROZEN_LO, max_pos=FROZEN_HI,
                          variant="queue")
    lone = repro.solve(cfg.fitness, dim=3, particles=64, iters=20, seed=1,
                       min_pos=FROZEN_LO, max_pos=FROZEN_HI,
                       variant="queue")
    # a batched row and a standalone solve are two programs: the contract
    agreement.assert_swarm_close(rs[1].state.pos, lone.state.pos,
                                 agreement.box_span(lone.config))
    for r in rs:
        pos = np.asarray(r.state.pos)
        assert np.all(pos[:, 0] == 0.5) and np.all(pos[:, 2] == 1.0)


def test_fully_degenerate_scalar_box():
    """lo == hi on EVERY dim: the swarm is pinned at one point — legal,
    if useless (the engine must not NaN out on the zero span)."""
    prob = Problem(name="pin", fn=lambda x: -jnp.sum(x * x, -1),
                   lo=2.0, hi=2.0)
    cfg = PSOConfig(dim=2, particle_cnt=16, fitness=prob).resolved()
    s = solve(cfg, seed=0, iters=5, variant="queue")
    assert np.all(np.asarray(s.pos) == 2.0)
    assert float(s.gbest_fit) == -8.0

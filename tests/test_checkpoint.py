"""Checkpoint/restart: atomicity, resume, pruning, crash simulation."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import agreement
from repro import checkpoint as ckpt
from repro.core import PSOConfig, init_swarm, run


def _tree():
    return {"w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
            "nested": {"b": jnp.ones((5,), jnp.bfloat16)},
            "step_count": jnp.asarray(7, jnp.int32)}


def test_save_restore_roundtrip(tmp_path):
    d = str(tmp_path)
    tree = _tree()
    ckpt.save(d, 3, tree)
    assert ckpt.latest_step(d) == 3
    out = ckpt.restore(d, 3, tree)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(tree)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
        assert a.dtype == b.dtype


def test_latest_and_prune(tmp_path):
    d = str(tmp_path)
    tree = _tree()
    for s in (1, 2, 3, 4, 5):
        ckpt.save(d, s, tree)
    assert ckpt.latest_step(d) == 5
    ckpt.prune(d, keep=2)
    assert ckpt.latest_step(d) == 5
    assert ckpt.restore_latest(d, tree)[0] == 5
    with pytest.raises(FileNotFoundError):
        ckpt.restore(d, 1, tree)


def test_incomplete_checkpoint_ignored(tmp_path):
    """A dir without manifest (simulated crash mid-write) is not 'latest'."""
    d = str(tmp_path)
    ckpt.save(d, 1, _tree())
    os.makedirs(os.path.join(d, "step_00000009"))  # torn write, no manifest
    assert ckpt.latest_step(d) == 1


def test_shape_mismatch_rejected(tmp_path):
    d = str(tmp_path)
    ckpt.save(d, 1, _tree())
    bad = dict(_tree(), w=jnp.zeros((2, 2)))
    with pytest.raises(ValueError, match="mismatch"):
        ckpt.restore(d, 1, bad)


def test_pso_crash_restart_bit_exact(tmp_path):
    """Run 30 iters; 'crash'; resume from step-10 checkpoint and re-run —
    trajectory must be bit-exact vs uninterrupted (counter RNG contract)."""
    d = str(tmp_path)
    cfg = PSOConfig(dim=5, particle_cnt=64, fitness="rastrigin").resolved()
    s = init_swarm(cfg, 3)
    s10 = run(cfg, s, 10, "queue")
    ckpt.save(d, 10, s10)
    full = run(cfg, s10, 20, "queue")          # uninterrupted continuation
    # --- crash happens here; new process restores:
    step, restored = ckpt.restore_latest(d, jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), s10))
    assert step == 10
    from repro.core.pso import SwarmState
    restored = SwarmState(*restored) if not isinstance(
        restored, SwarmState) else restored
    resumed = run(cfg, restored, 20, "queue")
    np.testing.assert_array_equal(np.asarray(full.pos),
                                  np.asarray(resumed.pos))
    assert float(full.gbest_fit) == float(resumed.gbest_fit)


def test_async_checkpoint_resume_bit_exact_at_chunk_boundary(tmp_path):
    """Async resume must not restart the staleness window: the checkpoint
    carries the block-local bests (SwarmState.lbest_*), so resuming at a
    chunk boundary reproduces the uninterrupted run bit for bit —
    trajectory AND the relaxed-consistency bookkeeping."""
    from repro.core import run_async
    d = str(tmp_path)
    cfg = PSOConfig(dim=3, particle_cnt=128, fitness="rastrigin").resolved()
    s0 = init_swarm(cfg, 9)
    full = run_async(cfg, s0, 32, sync_every=4, n_blocks=4)
    s16 = run_async(cfg, s0, 16, sync_every=4, n_blocks=4)
    assert s16.lbest_fit is not None and s16.lbest_fit.shape == (4,)
    ckpt.save(d, 16, s16)
    # --- crash; new process restores (locals ride the checkpoint pytree):
    step, restored = ckpt.restore_latest(d, jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), s16))
    assert step == 16
    assert restored.lbest_fit is not None         # locals survived the disk
    resumed = run_async(cfg, restored, 16, sync_every=4, n_blocks=4)
    for f in full._fields:
        np.testing.assert_array_equal(np.asarray(getattr(full, f)),
                                      np.asarray(getattr(resumed, f)),
                                      err_msg=f)


def test_async_resume_mid_window_keeps_publication_schedule():
    """Resuming OFF the sync grid (e.g. --ckpt-every not a multiple of
    sync_every) stays bit-exact too: the carried locals plus the static
    ``phase`` (auto-derived from state.iteration) keep publish points on
    absolute iteration numbers, and the tail flush publishes without
    resetting the blocks."""
    from repro.core import run, run_async
    # particle_cnt=1024 → the default block picker yields 2 blocks, so the
    # run() path (no explicit n_blocks) exercises real multi-block locals
    cfg = PSOConfig(dim=2, particle_cnt=1024, fitness="cubic").resolved()
    s0 = init_swarm(cfg, 4)
    full = run_async(cfg, s0, 20, sync_every=8)
    # 20 = 6 + 14: both splits are off the sync_every=8 grid
    part = run_async(cfg, s0, 6, sync_every=8)
    assert float(part.gbest_fit) == float(np.max(np.asarray(part.pbest_fit)))
    resumed = run_async(cfg, part, 14, sync_every=8)
    for f in full._fields:
        np.testing.assert_array_equal(np.asarray(getattr(full, f)),
                                      np.asarray(getattr(resumed, f)),
                                      err_msg=f)
    # the run() dispatcher path (what the CLI chunked loop uses) resumes
    # identically
    resumed2 = run(cfg, part, 14, "async", sync_every=8)
    np.testing.assert_array_equal(np.asarray(full.pos),
                                  np.asarray(resumed2.pos))


def test_batched_async_resume_bit_exact_any_boundary():
    """Regression (PR 5 known bug): the batched engine vmaps run_async, so
    the per-swarm phase auto-derivation hit a tracer and silently restarted
    every swarm's publication window at 0 on resume. run_many now reads the
    phases off the concrete batch before jit entry, so a batched async solve
    split at ANY boundary — chunk-aligned or mid-window — is bit-exact vs
    the uninterrupted batched run AND per-row vs the single-swarm path."""
    from repro.core import batch_row, init_batch, run_async, run_many
    # particle_cnt=1024 -> the default block picker yields 2 blocks, so the
    # publication schedule is observable (single-block async degenerates)
    cfg = PSOConfig(dim=2, particle_cnt=1024, fitness="cubic").resolved()
    seeds = list(range(8))
    b0 = init_batch(cfg, seeds)
    for split in (8, 6):                  # chunk boundary AND mid-window
        full = run_many(cfg, b0, 20, "async", sync_every=8)
        part = run_many(cfg, b0, split, "async", sync_every=8)
        assert part.lbest_fit is not None and part.lbest_fit.shape == (8, 2)
        resumed = run_many(cfg, part, 20 - split, "async", sync_every=8)
        for f in full._fields:
            np.testing.assert_array_equal(np.asarray(getattr(full, f)),
                                          np.asarray(getattr(resumed, f)),
                                          err_msg=f"{f} (split={split})")
        # row identity against the standalone resume (engine contract)
        single = run_async(cfg, batch_row(part, 3), 20 - split, sync_every=8)
        np.testing.assert_array_equal(np.asarray(resumed.pos[3]),
                                      np.asarray(single.pos))


def test_batched_async_resume_mixed_phases():
    """Rows checkpointed at different iterations resume correctly: run_many
    splits the batch into per-phase dispatch groups, and each row still
    agrees with its own standalone run_async continuation under the
    contract (tests/agreement.py): a two-row group and a standalone run
    are two programs."""
    from repro.core import batch_row, run_async, run_many, stack_states
    from repro.core.pso import init_swarm
    cfg = PSOConfig(dim=2, particle_cnt=1024, fitness="cubic").resolved()
    states = []
    for sd, pre in zip(range(6), (3, 6, 11, 3, 6, 11)):
        # 11 % 8 == 3: same phase as pre=3 but a different iteration count,
        # so the grouping is genuinely by phase, not by iteration
        states.append(run_async(cfg, init_swarm(cfg, sd), pre, sync_every=8))
    batch = stack_states(states)
    out = run_many(cfg, batch, 9, "async", sync_every=8)
    for i in range(6):
        single = run_async(cfg, batch_row(batch, i), 9, sync_every=8)
        agreement.assert_states_agree(
            batch_row(out, i), single, agreement.box_span(cfg),
            ("pos", "pbest_fit", "gbest_fit", "lbest_fit"), msg=f"row {i}")


def test_step_runner_retry_and_resume(tmp_path):
    """StepRunner recovers from a transient failure via its checkpoint."""
    from repro.runtime import RunnerConfig, StepRunner
    calls = {"n": 0}

    def flaky_step(state, step):
        calls["n"] += 1
        if calls["n"] == 7:                       # one transient device loss
            raise RuntimeError("simulated device failure")
        return jax.tree.map(lambda x: x + 1, state)

    runner = StepRunner(RunnerConfig(str(tmp_path), ckpt_interval=2,
                                     backoff_s=0.0), flaky_step)
    out = runner.run({"x": jnp.zeros(())}, 0, 10)
    assert float(out["x"]) == 10.0                # all 10 steps applied
    assert ckpt.latest_step(str(tmp_path)) == 10

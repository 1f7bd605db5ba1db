"""The synchronous kernels' stream account (``repro.telemetry.streams``):
what one launch moves between HBM and VMEM, recorded by each build under
the kernel's name, against counts made by hand from the layout."""
import jax.numpy as jnp
import pytest

import repro
from repro.kernels import pso_step
from repro.telemetry import kernel_streams

BOX = dict(w=1.0, c1=2.0, c2=2.0, min_pos=-5.12, max_pos=5.12, max_v=5.12)
F32 = 4


def _block(dpad, bn):
    """Bytes of one block of pos, vel, pbest_pos [dpad, bn], pbest_fit
    [1, bn] in float32."""
    return (3 * dpad + 1) * bn * F32


@pytest.mark.parametrize("build,name,want", [
    # streamed: 8 blocks of [120, 128] in and out at each of 3 x 8 steps,
    # the gbest column [120, 1] in and out once
    (lambda: pso_step.fused_call(1024, 120, 3, 128, jnp.float32,
                                 fitness="rastrigin", **BOX),
     "cupso_fused_queue_lock",
     (24, 2 * (24 * _block(120, 128) + 120 * F32), True, (3, 8))),
    # resident: one block of [8, 128] in once and out once over 5 steps
    (lambda: pso_step.fused_call(128, 3, 5, 128, jnp.float32,
                                 fitness="sphere", telemetry=True, **BOX),
     "cupso_fused_queue_lock",
     (5, 2 * (_block(8, 128) + 8 * F32), False, (5, 1))),
    # 4 swarms of 2 streamed blocks, 2 iterations: 16 steps
    (lambda: pso_step.fused_batch_call(4, 256, 10, 2, 128, jnp.float32,
                                       fitness="sphere", **BOX),
     "cupso_fused_queue_lock_batch",
     (16, 2 * (16 * _block(16, 128) + 4 * 16 * F32), True, (4, 2, 2))),
    # 4 resident swarms of one block: each in once and out once
    (lambda: pso_step.hetero_fused_batch_call(
        4, 128, 10, 7, 128, jnp.float32, w=1.0, c1=2.0, c2=2.0,
        members=(("sphere", -5.12, 5.12, 5.12),
                 ("rastrigin", -5.12, 5.12, 5.12))),
     "cupso_hetero_fused_queue_lock_batch",
     (28, 2 * (4 * _block(16, 128) + 4 * 16 * F32), False, (4, 7, 1))),
], ids=["fused-streamed", "fused-resident", "batch-streamed",
        "hetero-resident"])
def test_account_matches_a_hand_count(build, name, want):
    build()
    a = kernel_streams()[name]
    assert (a.grid_steps, a.bytes, a.streamed, a.grid) == want
    assert a.itemsize == F32


def test_a_solve_records_the_kernel_it_launched():
    repro.solve("rastrigin", dim=120, particles=1024, iters=2, seed=1,
                method=repro.Method(variant="queue_lock", backend="kernel",
                                    block_n=256))
    a = kernel_streams()["cupso_fused_queue_lock"]
    assert a.grid == (2, 4) and (a.dpad, a.block_n) == (120, 256)
    assert a.bytes == 2 * (8 * _block(120, 256) + 120 * F32)

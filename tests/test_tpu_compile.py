"""Mosaic compiles of the Pallas kernels for a described TPU v5e.

Interpret mode accepts layouts the TPU compiler refuses (unaligned blocks,
lane-dynamic slices, casts Mosaic has no lowering for). These tests build
every kernel of the main path with ``interpret=False`` at the paper's widths
and compile it for one chip of a ``v5e:2x2`` topology that is described,
not attached, so they need no chip. Each asserts that the compiled program
holds the kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file. JAX's persistent compilation cache is off around the compiles,
because a program compiled for a described chip can be written to it but
not read back without one.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.blocking import pick_block_n
from repro.core.problem import Problem
from repro.kernels import pso_step

F32, I32 = jnp.float32, jnp.int32
COEFFS = dict(w=0.72, c1=1.49, c2=1.49)
BOX = dict(min_pos=-5.12, max_pos=5.12, max_v=1.0)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


def _compile(call, shapes, sharding):
    """Compile ``call`` for the described chip from ``(shape, dtype)``
    pairs; return the compiled program's text."""
    specs = [jax.ShapeDtypeStruct(shp, dt, sharding=sharding)
             for shp, dt in shapes]
    return jax.jit(call).lower(*specs).compile().as_text()


def _sync_shapes(d, n):
    dp = pso_step.pad_dim(d)
    return [((2,), I32), ((dp, n), F32), ((dp, n), F32), ((dp, n), F32),
            ((1, n), F32), ((dp, 1), F32), ((1,), F32)]


def _async_shapes(d, n, nb):
    dp = pso_step.pad_dim(d)
    return _sync_shapes(d, n) + [((nb, dp, 1), F32), ((nb,), F32)]


def _batch_shapes(s, d, n, prefix, nb=None):
    dp = pso_step.pad_dim(d)
    out = [((s,), I32)] * prefix + [
        ((s, dp, n), F32), ((s, dp, n), F32), ((s, dp, n), F32),
        ((s, 1, n), F32), ((s, dp, 1), F32), ((s,), F32)]
    if nb is not None:
        out += [((s * nb, dp, 1), F32), ((s * nb,), F32)]
    return out


# The paper's largest swarms: Table 4 (1-D cubic) and Table 5 (120-D).
@pytest.mark.parametrize("d,n,fitness,telemetry", [
    (1, 131072, "cubic", False),
    (120, 32768, "rastrigin", False),
    (120, 32768, "rastrigin", True),
    (10, 256, "rastrigin", True),      # one block: the resident-window path
])
def test_fused_compiles(one_chip, d, n, fitness, telemetry):
    call = pso_step.fused_call(n, d, 4, pick_block_n(n), F32,
                               fitness=fitness, telemetry=telemetry,
                               interpret=False, **COEFFS, **BOX)
    shapes = _sync_shapes(d, n) + ([((3,), I32)] if telemetry else [])
    assert "tpu_custom_call" in _compile(call, shapes, one_chip)


@pytest.mark.parametrize("d,n,fitness,topology", [
    (1, 131072, "cubic", "gbest"),
    (120, 32768, "rastrigin", "gbest"),
    (120, 32768, "rastrigin", "ring"),
])
def test_async_compiles(one_chip, d, n, fitness, topology):
    bn = pick_block_n(n)
    call = pso_step.fused_async_call(n, d, 16, bn, 8, F32, fitness=fitness,
                                     topology=topology, interpret=False,
                                     **COEFFS, **BOX)
    text = _compile(call, _async_shapes(d, n, n // bn), one_chip)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n", [2048, 64])
def test_batched_compiles(one_chip, n):
    # n=64: a swarm narrower than one lane tile still gets legal blocks
    s, d = 16, 10
    call = pso_step.fused_batch_call(s, n, d, 4, pick_block_n(n), F32,
                                     fitness="rastrigin", interpret=False,
                                     **COEFFS, **BOX)
    text = _compile(call, _batch_shapes(s, d, n, 2), one_chip)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("topology", ["gbest", "vonneumann"])
def test_async_batched_compiles(one_chip, topology):
    s, d, n = 16, 10, 2048
    bn = 512
    call = pso_step.fused_async_batch_call(
        s, n, d, 16, bn, 8, F32, fitness="rastrigin", topology=topology,
        interpret=False, **COEFFS, **BOX)
    text = _compile(call, _batch_shapes(s, d, n, 2, nb=n // bn), one_chip)
    assert "tpu_custom_call" in text


_MEMBERS = tuple((f, -5.12, 5.12, 1.0)
                 for f in ("rastrigin", "sphere", "ackley", "griewank"))


def test_hetero_batched_compiles(one_chip):
    s, d, n = 16, 10, 1024
    call = pso_step.hetero_fused_batch_call(
        s, n, d, 4, 512, F32, members=_MEMBERS, telemetry=True,
        interpret=False, **COEFFS)
    shapes = _batch_shapes(s, d, n, 3) + [((3 * s,), I32)]
    assert "tpu_custom_call" in _compile(call, shapes, one_chip)


def test_hetero_async_batched_compiles(one_chip):
    s, d, n, bn = 16, 10, 1024, 512
    call = pso_step.hetero_fused_async_batch_call(
        s, n, d, 16, bn, 8, F32, members=_MEMBERS, interpret=False,
        **COEFFS)
    text = _compile(call, _batch_shapes(s, d, n, 3, nb=n // bn), one_chip)
    assert "tpu_custom_call" in text


def test_adapter_objective_with_matrix_const_compiles(one_chip):
    """A user objective closing over a rotation matrix: the adapter lowers
    it and ``lower_statics`` passes the [D, D] matrix in as an operand."""
    d, n = 16, 8192
    rot = jnp.asarray(np.linalg.qr(
        np.random.default_rng(0).normal(size=(d, d)))[0], F32)

    def rotated_sphere(x):
        y = x @ rot
        return jnp.sum(y * y, axis=-1)

    prob = Problem("rotated_sphere", rotated_sphere, lo=-5.0, hi=5.0,
                   sense="min")
    call = pso_step.fused_call(n, d, 4, pick_block_n(n), F32, fitness=prob,
                               interpret=False, w=0.72, c1=1.49, c2=1.49,
                               min_pos=-5.0, max_pos=5.0, max_v=1.0)
    assert "tpu_custom_call" in _compile(call, _sync_shapes(d, n), one_chip)

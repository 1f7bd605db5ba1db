"""Counter-based stateless RNG shared by the jnp library code, the Pallas
kernel bodies, and the kernel reference oracles.

The paper uses cuRAND's ``curand_uniform_double`` (§5.4) because a stateful
hand-rolled RNG is not thread-safe on GPU. The TPU-native adaptation is a
*counter-based* generator: a 32-bit mixing hash of ``(seed, iteration,
stream, element index)``. It is stateless (no RNG state to carry, checkpoint
or shard), identical inside and outside Pallas (the body is plain jnp
integer ops that Mosaic and XLA both lower; the one conversion to float goes
through int32, because Mosaic has no uint32 -> float cast), and reproducible
across any device layout — resharding a swarm never changes its trajectory.

The mixer is two rounds of the murmur3/splitmix finalizer over a Weyl-summed
counter. It passes the birthday/equidistribution sanity checks in
``tests/test_rng.py``; it is not cryptographic and does not need to be.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

# numpy scalars (NOT jnp arrays): Pallas kernel bodies may not close over
# array constants, and numpy scalars fold into the kernel at trace time.
_U32 = np.uint32

# Weyl constants (odd, high-entropy) for combining counter components.
_W0 = _U32(0x9E3779B9)  # golden-ratio
_W1 = _U32(0x85EBCA6B)
_W2 = _U32(0xC2B2AE35)
_W3 = _U32(0x27D4EB2F)


def _mix(x: jnp.ndarray) -> jnp.ndarray:
    """murmur3 fmix32 finalizer (uint32 in, uint32 out)."""
    x = x ^ (x >> 16)
    x = x * _W1
    x = x ^ (x >> 13)
    x = x * _W2
    x = x ^ (x >> 16)
    return x


def hash_u32(seed, iteration, stream, index) -> jnp.ndarray:
    """uint32 hash of the 4-component counter. All args broadcastable uint32/int32."""
    seed = jnp.asarray(seed).astype(_U32)
    iteration = jnp.asarray(iteration).astype(_U32)
    stream = jnp.asarray(stream).astype(_U32)
    index = jnp.asarray(index).astype(_U32)
    h = seed * _W0 + iteration * _W1 + stream * _W2 + index * _W3
    h = _mix(h)
    # Second round decorrelates consecutive indices fully.
    h = _mix(h ^ (index * _W0 + iteration * _W2))
    return h


def uniform(seed, iteration, stream, index, dtype=jnp.float32) -> jnp.ndarray:
    """Uniform in [0, 1) with 24 bits of mantissa entropy."""
    bits = hash_u32(seed, iteration, stream, index)
    # bits >> 8 < 2**24 fits int32 exactly, so the int32 hop changes no bit
    # of the result; Mosaic refuses a direct uint32 -> float cast.
    # python-float scale: folds at trace time, keeps dtype via weak promotion
    return (bits >> 8).astype(jnp.int32).astype(dtype) * (1.0 / (1 << 24))


def uniform_grid(seed, iteration, stream, n, d, dtype=jnp.float32) -> jnp.ndarray:
    """Uniform [n, d] grid keyed by flat element index — the common PSO shape."""
    idx = jnp.arange(n * d, dtype=jnp.uint32).reshape(n, d)
    return uniform(seed, iteration, stream, idx, dtype=dtype)

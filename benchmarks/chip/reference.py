"""Plain reference of the PSO that the benchmark's cells run.

It imports nothing of ``repro``: the counter-based generator, the
initialisation, the paper's update rule (cuPSO §3, Eq. 1-2, w/c1/c2 from
§6.1), the six objectives and the queue-lock publication are written out
here again from their definitions. The comparison in ``correct.py`` runs
this reference for the seeds the window served and holds the program's
answers to it.

Semantics. Particles are split into ``nb`` blocks of ``n // nb``. In every
iteration the blocks advance one after another; block ``b`` steers toward
the swarm best as published by blocks ``0..b-1`` of the same iteration,
and publishes its own best lane (first lane on ties) when that beats the
swarm best. This is the fused queue-lock kernel's schedule (its grid runs
blocks in order inside an iteration). With one block it is also the
asynchronous variant's answer: the block-local best is then the swarm
best, refreshed every iteration and published every ``sync_every``
iterations, so the final answer is the same.

Everything is computed in the configuration's dtype (float32) with the
same association of the arithmetic as the equations are written, so that
a sound program and the reference take the same steps; ``dtype`` is also
how the lower-precision control is run (``bfloat16``).
"""
from __future__ import annotations

import math
from typing import Dict, NamedTuple, Tuple

import numpy as np

# murmur3 fmix32 / Weyl constants of the counter-based generator.
_W0 = np.uint32(0x9E3779B9)
_W1 = np.uint32(0x85EBCA6B)
_W2 = np.uint32(0xC2B2AE35)
_W3 = np.uint32(0x27D4EB2F)

STREAM_INIT_POS, STREAM_INIT_VEL, STREAM_R1, STREAM_R2 = 0, 1, 2, 3


def _mix(x):
    x = x ^ (x >> 16)
    x = x * _W1
    x = x ^ (x >> 13)
    x = x * _W2
    x = x ^ (x >> 16)
    return x


def uniform(seed, iteration, stream, index, dtype):
    """Uniform in [0, 1) from the 24 high bits of a 32-bit counter hash of
    ``(seed, iteration, stream, index)``."""
    import jax.numpy as jnp
    u32 = jnp.uint32
    seed = jnp.asarray(seed).astype(u32)
    iteration = jnp.asarray(iteration).astype(u32)
    stream = jnp.asarray(stream).astype(u32)
    index = jnp.asarray(index).astype(u32)
    h = seed * _W0 + iteration * _W1 + stream * _W2 + index * _W3
    h = _mix(h)
    h = _mix(h ^ (index * _W0 + iteration * _W2))
    return (h >> 8).astype(jnp.int32).astype(dtype) * (1.0 / (1 << 24))


# --- objectives, in the maximised form the engines track ----------------

def _obj_jnp(name: str, x):
    import jax.numpy as jnp
    d = x.shape[-1]
    if name == "cubic":
        return jnp.sum(x * x * x - 0.8 * (x * x) - 1000.0 * x + 8000.0,
                       axis=-1)
    if name == "sphere":
        return -jnp.sum(x * x, axis=-1)
    if name == "rosenbrock":
        a, b = x[..., :-1], x[..., 1:]
        return -jnp.sum(100.0 * (b - a * a) ** 2 + (1.0 - a) ** 2, axis=-1)
    if name == "griewank":
        idx = jnp.arange(1, d + 1, dtype=x.dtype)
        s = jnp.sum(x * x, axis=-1) / 4000.0
        p = jnp.prod(jnp.cos(x / jnp.sqrt(idx)), axis=-1)
        return -(s - p + 1.0)
    if name == "rastrigin":
        return -(10.0 * d + jnp.sum(x * x - 10.0 * jnp.cos(2.0 * jnp.pi * x),
                                    axis=-1))
    if name == "ackley":
        s1 = jnp.sqrt(jnp.sum(x * x, axis=-1) / d)
        s2 = jnp.sum(jnp.cos(2.0 * jnp.pi * x), axis=-1) / d
        return -(-20.0 * jnp.exp(-0.2 * s1) - jnp.exp(s2) + 20.0 + jnp.e)
    raise KeyError(f"no reference objective {name!r}")


def objective64(name: str, x) -> float:
    """The objective at one position, in float64 and in the maximised
    form: what a reported gbest value has to say about its position."""
    x = np.asarray(x, np.float64)
    d = x.shape[-1]
    if name == "cubic":
        return float(np.sum(x ** 3 - 0.8 * x ** 2 - 1000.0 * x + 8000.0))
    if name == "sphere":
        return float(-np.sum(x * x))
    if name == "rosenbrock":
        a, b = x[:-1], x[1:]
        return float(-np.sum(100.0 * (b - a * a) ** 2 + (1.0 - a) ** 2))
    if name == "griewank":
        i = np.arange(1, d + 1, dtype=np.float64)
        return float(-(np.sum(x * x) / 4000.0
                       - np.prod(np.cos(x / np.sqrt(i))) + 1.0))
    if name == "rastrigin":
        return float(-(10.0 * d + np.sum(x * x
                                         - 10.0 * np.cos(2 * math.pi * x))))
    if name == "ackley":
        s1 = math.sqrt(np.sum(x * x) / d)
        s2 = np.sum(np.cos(2 * math.pi * x)) / d
        return float(-(-20.0 * math.exp(-0.2 * s1) - math.exp(s2) + 20.0
                       + math.e))
    raise KeyError(f"no reference objective {name!r}")


class Answer(NamedTuple):
    """What a solve reports, plus the swarm it ends with."""
    gbest_fit: np.ndarray   # []
    gbest_pos: np.ndarray   # [D]
    pos: np.ndarray         # [N, D]
    vel: np.ndarray         # [N, D]
    pbest_fit: np.ndarray   # [N]


def _solve_fn(name: str, n: int, d: int, nb: int, iters: int, lo: float,
              hi: float, w: float, c1: float, c2: float, dtype):
    """A jitted ``seed -> Answer`` for one objective and shape."""
    import jax
    import jax.numpy as jnp
    bn = n // nb
    span = hi - lo
    mv = 0.5 * (hi - lo)
    lanes = jnp.arange(bn, dtype=jnp.uint32)[:, None] * d \
        + jnp.arange(d, dtype=jnp.uint32)[None, :]          # [bn, d]

    def fit_of(x):
        return _obj_jnp(name, x)

    def block(seed, it, carry, xs):
        gf, gp = carry
        b, pos, vel, pbp, pbf = xs
        idx = lanes + (b * bn * d).astype(jnp.uint32)
        r1 = uniform(seed, it, STREAM_R1, idx, dtype)
        r2 = uniform(seed, it, STREAM_R2, idx, dtype)
        vel = w * vel + c1 * r1 * (pbp - pos) + c2 * r2 * (gp[None, :] - pos)
        vel = jnp.clip(vel, -mv, mv)
        pos = jnp.clip(pos + vel, lo, hi)
        fit = fit_of(pos)
        imp = fit > pbf
        pbf = jnp.where(imp, fit, pbf)
        pbp = jnp.where(imp[:, None], pos, pbp)
        k = jnp.argmax(fit)
        take = fit[k] > gf
        gf = jnp.where(take, fit[k], gf)
        gp = jnp.where(take, pos[k], gp)
        return (gf, gp), (pos, vel, pbp, pbf)

    @jax.jit
    def solve(seed):
        seed = jnp.asarray(seed, jnp.uint32)
        idx = jnp.arange(n * d, dtype=jnp.uint32).reshape(n, d)
        pos = lo + span * uniform(seed, 0, STREAM_INIT_POS, idx, dtype)
        vel = -mv + 2.0 * mv * uniform(seed, 0, STREAM_INIT_VEL, idx, dtype)
        fit = fit_of(pos)
        k = jnp.argmax(fit)
        carry0 = (fit[k], pos[k])
        blocks = (pos.reshape(nb, bn, d), vel.reshape(nb, bn, d),
                  pos.reshape(nb, bn, d), fit.reshape(nb, bn))
        ids = jnp.arange(nb, dtype=jnp.uint32)

        def iteration(c, t):
            g, blk = c
            g, blk = jax.lax.scan(
                lambda gc, xs: block(seed, t + 1, gc, xs), g, (ids,) + blk)
            return (g, blk), None

        (g, blk), _ = jax.lax.scan(iteration, (carry0, blocks),
                                   jnp.arange(iters, dtype=jnp.int32))
        pos, vel, _, pbf = blk
        return Answer(g[0], g[1], pos.reshape(n, d), vel.reshape(n, d),
                      pbf.reshape(n))

    return solve


class Reference:
    """Reference solves for one configuration, compiled once per objective.

    ``functions`` maps each objective's name to its box ``(lo, hi)``;
    ``blocks`` is the number of particle blocks the program's schedule
    uses (see the module docstring)."""

    def __init__(self, *, dim: int, particles: int, iters: int, blocks: int,
                 functions: Dict[str, Tuple[float, float]], w: float,
                 c1: float, c2: float, dtype: str = "float32"):
        if particles % blocks:
            raise ValueError(f"{blocks} blocks do not divide {particles}")
        self._args = (particles, dim, blocks, iters)
        self._coeffs = (w, c1, c2)
        self._functions = dict(functions)
        self._dtype = dtype
        self._fns = {}
        self._answers = {}

    def solve(self, name: str, seed: int) -> Answer:
        """The reference's answer for one objective and seed (kept, so a
        second comparison of the same solve costs nothing)."""
        key = (name, int(seed))
        if key not in self._answers:
            self._answers[key] = self._solve(name, seed)
        return self._answers[key]

    def _solve(self, name: str, seed: int) -> Answer:
        import jax
        import jax.numpy as jnp
        fn = self._fns.get(name)
        if fn is None:
            lo, hi = self._functions[name]
            fn = self._fns[name] = _solve_fn(
                name, *self._args, float(lo), float(hi), *self._coeffs,
                jnp.dtype(self._dtype))
        out = jax.device_get(fn(int(seed)))
        return Answer(*(np.asarray(a, np.float64) for a in out))
